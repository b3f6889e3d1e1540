"""Serving launcher: one continuous-batching serve over a Poisson trace,
with the telemetry front door exposed as flags.

    PYTHONPATH=src python -m repro.launch.serve [--arch ARCH] [--smoke]
        [--mode resident|kv_offload]
        [--trace-out TRACE.json] [--stats-json STATS.json]

The model is ``--arch`` at its published widths and depth, with random
bfloat16 weights and a bfloat16 KV cache (a 3.8B model's float32 weights
alone fill a 16 GB chip). ``--smoke`` serves the arch's reduced float32
config instead, small enough for the CPU.

``--trace-out`` enables the session's telemetry block
(``OffloadConfig.telemetry``) and writes the Chrome trace-event /
Perfetto JSON file there on session close — open it at
https://ui.perfetto.dev. ``--stats-json`` writes the merged
``session.stats()`` snapshot (pool/transfer/sched counters, plus the
latency histograms and trace-ring state when tracing is on). With
neither flag telemetry stays disabled and no tracer is ever constructed.

SLO flags: ``--interactive-fraction F`` annotates the trace with mixed
interactive/batch priority classes and TTFT deadlines; ``--slo`` turns
on the SLO-aware scheduler (deadline-first admission, preemption, early
shedding — ``OffloadConfig.slo``); ``--overload X`` multiplies the
arrival rate by X to push the trace past capacity. With any of them the
launcher prints a per-class SLO attainment summary after the run.

``load_model`` and ``offload_config`` are the serving helpers the
benchmark (``bench/program.py``) and ``chip_smoke.py`` build on.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.api import HyperOffloadSession, OffloadConfig
from repro.api.config import MODES, TelemetryConfig
from repro.configs import REGISTRY
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import Model, build_model
from repro.offload.kvcache import worst_case_page_bytes


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
    # only the serve loop needs these: importing the helpers above stays
    # as light as it is for the benchmark
    from repro.sched import poisson_trace
    from repro.slo import SLOConfig, attainment_summary

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", choices=sorted(REGISTRY),
                    default="phi3-mini-3.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the arch's reduced float32 config")
    ap.add_argument("--mode", choices=MODES, default="kv_offload")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--rate", type=float, default=0.8,
                    help="Poisson arrivals per scheduler step")
    ap.add_argument("--max-batch", type=int, default=3)
    ap.add_argument("--max-seq", type=int, default=48)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable telemetry and write the Chrome trace here")
    ap.add_argument("--stats-json", default=None, metavar="PATH",
                    help="write the merged session.stats() snapshot here")
    ap.add_argument("--slo", action="store_true",
                    help="SLO-aware scheduling: deadline-first admission, "
                         "preemption, early shedding")
    ap.add_argument("--overload", type=float, default=None, metavar="X",
                    help="multiply --rate by X (drive the trace past "
                         "capacity)")
    ap.add_argument("--interactive-fraction", type=float, default=None,
                    metavar="F",
                    help="annotate the trace: F of requests interactive "
                         "(TTFT deadline), rest batch")
    args = ap.parse_args(argv)
    if args.slo and args.interactive_fraction is None:
        args.interactive_fraction = 0.35   # --slo alone still demos SLOs

    enable_compile_cache()
    model, params, dtype = load_model(args.arch, smoke=args.smoke,
                                      seed=args.seed)
    cfg = model.cfg

    extra = dict(prefill_budget=2)
    if args.trace_out is not None:
        extra["telemetry"] = TelemetryConfig(enable=True,
                                             trace_path=args.trace_out)
    if args.slo:
        extra["slo"] = SLOConfig(enable=True)

    session = HyperOffloadSession(offload_config(
        model, mode=args.mode, max_batch=args.max_batch,
        max_seq=args.max_seq, cache_dtype=dtype, **extra))
    sched = session.scheduler(model, params)
    rate = args.rate * (args.overload or 1.0)
    trace = poisson_trace(args.requests, rate=rate,
                          vocab_size=cfg.vocab_size, prompt_lens=(4, 16),
                          new_tokens=(2, 12), prompt_quantum=4,
                          interactive_fraction=args.interactive_fraction,
                          seed=args.seed)
    t0 = time.time()
    out = sched.run(trace)
    wall = time.time() - t0
    tokens = sum(len(v) for v in out.values())
    print(f"serve,{args.mode},requests:{len(out)},tokens:{tokens},"
          f"steps:{sched.stats.steps},wall_s:{wall:.2f}")

    if args.slo or args.interactive_fraction is not None:
        att = attainment_summary(sched.finished.values())
        st = sched.stats
        print(f"serve,slo,goodput_tokens:{att['met_tokens']},"
              f"goodput_tok/step:"
              f"{att['met_tokens'] / max(sched.now, 1e-9):.2f},"
              f"preemptions:{st.preemptions},resumes:{st.resumes},"
              f"shed:{st.shed}")
        for cls, c in sorted(att["classes"].items()):
            tta = c["ttft_attainment"]
            print(f"serve,slo_class,{cls},requests:{c['requests']},"
                  f"met_tokens:{c['met_tokens']}/{c['tokens']},"
                  f"shed:{c['shed']},ttft_attainment:"
                  f"{'n/a' if tta is None else format(tta, '.2f')}")

    if args.trace_out is not None:
        ov = session.overlap()
        hf = ov["hidden_fraction"]
        print(f"serve,overlap,transfers:{ov['transfers']},"
              f"hidden_s:{ov['hidden_s']:.4f},"
              f"exposed_s:{ov['exposed_s']:.4f},hidden_fraction:"
              f"{'n/a' if hf is None else format(hf, '.2f')}")
    if args.stats_json is not None:
        with open(args.stats_json, "w") as f:
            json.dump(session.stats(), f, indent=2, sort_keys=True,
                      default=str)
        print(f"serve,stats,{args.stats_json}")
    session.close()   # exports the trace to --trace-out (telemetry.trace_path)
    if args.trace_out is not None:
        print(f"serve,trace,{args.trace_out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
