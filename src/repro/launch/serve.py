"""Serving launcher: batched generation through the `repro.api` front door.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --smoke \
        --prompt-len 32 --new-tokens 32 --batch 4 [--mode kv_offload]

Without ``--smoke`` the arch runs at its published widths and depth, with
random bfloat16 weights and a bfloat16 KV cache; ``--smoke`` runs its
reduced float32 config. ``--mode`` selects the `OffloadConfig` mode.
``--remote-bw GB/s`` swaps the default topology's remote tier for a
bandwidth-throttled modeled tier (the paper's Fig. 6 D2H sweep, one point
per invocation), and ``--recalibrate`` re-runs the generation after
feeding the measured per-tier-pair bandwidths back into planning.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.api import HyperOffloadSession, OffloadConfig
from repro.configs import REGISTRY
from repro.data.pipeline import SyntheticTokens
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model, build_model
from repro.offload.kvcache import worst_case_page_bytes
from repro.pool import TierTopology, sweep_topologies


def load_model(arch: str, *, smoke: bool, seed: int = 0):
    """(model, params, dtype name): ``arch`` at its published config, or
    its reduced one with ``smoke``. Params are drawn from ``seed``
    straight into the serving dtype — bfloat16 at full width, float32 for
    the smoke config — without a float32 copy of the full model."""
    cfg = REGISTRY[arch].reduced() if smoke else REGISTRY[arch]
    dtype = "float32" if smoke else "bfloat16"
    model = build_model(cfg)
    params = jax.jit(model.init, static_argnums=1)(
        jax.random.key(seed), jnp.dtype(dtype))
    return model, params, dtype


def offload_config(model: Model, *, mode: str, max_batch: int,
                   max_seq: int, cache_dtype: str, **extra) -> OffloadConfig:
    """The session config for serving ``model``. In ``kv_offload`` the
    device tier holds about half the running batch's rows, so cold pages
    spill to the host tier and are prefetched back — the traffic the
    mode exists for."""
    kwargs = dict(mode=mode, max_batch=max_batch, max_seq=max_seq,
                  cache_dtype=cache_dtype, **extra)
    if mode == "kv_offload":
        row = worst_case_page_bytes(
            model.cache_specs(1, max_seq, jnp.dtype(cache_dtype)))
        kwargs.update(device_capacity=max(1, max_batch // 2) * row,
                      host_capacity=2 * max_batch * row)
    return OffloadConfig(**kwargs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(REGISTRY), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--batch", type=int, default=4)
    # this launcher drives ServeEngine only — the paged/continuous modes
    # live in examples/serve_offload.py and benchmarks/serve_continuous.py
    ap.add_argument("--mode", choices=("resident", "kv_offload"),
                    default="resident")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--remote-bw", type=float, default=None, metavar="GBPS",
                    help="throttle the remote tier's read bandwidth to this "
                         "many GB/s (modeled tier; Fig.-6-style sweep point)")
    ap.add_argument("--recalibrate", action="store_true",
                    help="after the run, replan from measured per-tier-pair "
                         "bandwidths and generate once more")
    args = ap.parse_args(argv)
    mode = args.mode

    enable_compile_cache()
    model, params, dtype = load_model(args.arch, smoke=args.smoke,
                                      seed=args.seed)
    cfg = model.cfg
    data = SyntheticTokens(cfg.vocab_size, seq_len=args.prompt_len,
                           global_batch=args.batch, seed=args.seed)
    batch = data.batch(0, cfg)
    batch.pop("targets", None)

    topology = None
    if args.remote_bw is not None:
        topology, = sweep_topologies(
            TierTopology.default(), "remote",
            read_bws=[args.remote_bw * 1e9])
    config = OffloadConfig(mode=mode, max_batch=args.batch,
                           max_seq=args.prompt_len + args.new_tokens,
                           cache_dtype=dtype, topology=topology)
    with HyperOffloadSession(config) as session:
        engine = session.serve_engine(model, params)
        t0 = time.time()
        out = engine.generate(batch, args.new_tokens,
                              temperature=args.temperature, seed=args.seed)
        dt = time.time() - t0
        toks = args.batch * args.new_tokens
        print(f"arch={cfg.name} mode={mode} "
              f"tiers={'/'.join(session.pool.spill_order)} "
              f"generated {out.shape} in {dt:.2f}s ({toks/dt:.1f} tok/s)")
        print("first sequence:", out[0].tolist())
        s = session.stats()
        print(f"stats: {s['serve']} pool_puts={s['pool']['puts']} "
              f"pool_gets={s['pool']['gets']}")
        if args.recalibrate:
            spec = session.recalibrate()
            t0 = time.time()
            out = engine.generate(batch, args.new_tokens,
                                  temperature=args.temperature,
                                  seed=args.seed)
            dt2 = time.time() - t0
            print(f"recalibrated hw={spec.name} "
                  f"d2r={spec.pool_bw_d2r:.3g}B/s r2d={spec.pool_bw_r2d:.3g}B/s "
                  f"rerun {dt2:.2f}s ({toks/dt2:.1f} tok/s)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
