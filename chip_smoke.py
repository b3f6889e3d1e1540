"""Chip smoke check: serve phi3-mini-3.8b at its published widths on one TPU.

    python chip_smoke.py

One process drives one chip. The check refuses to run (non-zero exit, no
result line) unless JAX's first device is a TPU. Two phases, each fatal
on any failure:

1. **serve** — phi3-mini-3.8b at full width and depth (d_model 3072,
   32 layers, 32 heads of 96, d_ff 8192, vocab 32064), random bfloat16
   weights from ``SEED`` and a bfloat16 KV cache, built and served by
   ``repro.launch.serve``'s helpers, as ``launch/serve.py`` does. One
   Poisson trace (6
   requests, prompts 128-512 tokens, 32 greedy new tokens each, batch 4,
   ``max_seq`` 768, chunked prefill) is served first in ``resident``
   mode, then in ``kv_offload`` mode with the device tier holding half
   the batch's rows. Every request must finish with its token count,
   ``kv_offload`` must emit exactly the ``resident`` tokens, and the host
   tier must be ``jax-host[pinned_host]`` with traffic both ways.
2. **kernels** — flash, ring-decode and paged-decode attention at phi3
   widths (Hq = Hkv = 32, D = 96, page 32, bfloat16) through
   ``repro.kernels.ops``. Each compiled program must hold a Mosaic kernel
   (``tpu_custom_call``: no interpret mode) and match ``kernels.ref``.

Lines before the last are bring-up observations, not benchmark numbers.
The last line is one JSON object naming the device.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path
from typing import Dict

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import HyperOffloadSession  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import load_model, offload_config  # noqa: E402
from repro.sched import poisson_trace  # noqa: E402
from repro.serving.engine import jit_prefill_chunk  # noqa: E402

ARCH = "phi3-mini-3.8b"
SEED = 0   # of the weights, the trace and the kernel inputs
PUBLISHED = dict(d_model=3072, n_layers=32, n_heads=32, n_kv_heads=32,
                 head_dim=96, d_ff=8192, vocab_size=32064)
# At max_seq 1024, kv_offload peaked at 15.7 and 16.1 GB in two runs of
# the same trace against a 16.9 GB limit. 768 still holds the longest
# request (512 + 32 tokens); measured at 768, kv_offload peaked at
# 14.3 GB, which leaves 2.6 GB (PERF.md, section 5).
MAX_BATCH, MAX_SEQ, CHUNK = 4, 768, 128
HOST_BACKEND = "jax-host[pinned_host]"
# Kernel and reference read the same bfloat16 inputs and accumulate in
# float32, then both round the output to bfloat16: where the two float32
# values straddle a rounding boundary they land one bfloat16 step apart,
# at most 2**-7 of the value. The absolute term allows for the MXU
# contracting float32 operands in a single bfloat16 pass (Mosaic's default
# precision): q·scale and the softmax weights then reach it rounded by up
# to 2**-9 each. Flash's early causal rows attend over a few keys, where
# that moves an output by up to about 2**-8 of max|v| (max|v| is near 4
# for these standard-normal draws); on a v5e it errs by 1.6e-2. The
# decode kernels average 256-700 keys, so the roundings cancel, and on a
# v5e they err by at most 2.0e-3; 2**-8 is twice that. Losing or gaining
# one key (ring ``pos`` or paged ``tail_len`` off by one) moves their
# outputs by 1.8e-2 to 5.5e-2 over seeds 0-2, past the decode limit by at
# least 1.4e-2 (PERF.md, Findings).
KERNEL_RTOL = 2.0 ** -7
KERNEL_ATOL = {"flash_attention": 2.0 ** -6, "decode_attention": 2.0 ** -8,
               "paged_decode_attention": 2.0 ** -8}


def check(ok: bool, what: str) -> None:
    """Fail the smoke check (an ``assert`` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def require_tpu() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX's first device is "
                 f"{dev.platform} ({dev.device_kind})")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    return dev


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own monitoring events."""

    def __init__(self) -> None:
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def serve_phase(dev: jax.Device, clock: CompileClock) -> None:
    t0, c0 = time.perf_counter(), clock.seconds
    model, params, dtype = load_model(ARCH, smoke=False, seed=SEED)
    jax.block_until_ready(params)
    cfg = model.cfg
    widths = {k: getattr(cfg, k) for k in PUBLISHED}
    check(widths == PUBLISHED, f"not the published widths: {widths}")
    # weights are bfloat16; norm scales stay float32 by the model's design
    wrong = [jax.tree_util.keystr(p) for p, x in
             jax.tree_util.tree_leaves_with_path(params)
             if "norm" not in jax.tree_util.keystr(p) and x.dtype != dtype]
    check(dtype == "bfloat16" and not wrong, f"not bfloat16: {wrong}")
    print(f"serve,init,arch={cfg.name},dtype={dtype},"
          f"params={sum(x.size for x in jax.tree.leaves(params))},"
          f"wall_s={time.perf_counter() - t0:.2f},"
          f"compile_s={clock.seconds - c0:.2f}")

    trace = poisson_trace(6, rate=0.5, vocab_size=cfg.vocab_size,
                          prompt_lens=(128, 512), new_tokens=(32, 32),
                          prompt_quantum=CHUNK, seed=SEED)
    # one mode at a time: each scheduler's stacked cache is freed when
    # serve_mode returns, before the next one is allocated
    outs = {mode: serve_mode(mode, model, params, dtype, trace, dev, clock)
            for mode in ("resident", "kv_offload")}
    differ = [r.req_id for r in trace if not np.array_equal(
        outs["resident"][r.req_id], outs["kv_offload"][r.req_id])]
    check(not differ, f"kv_offload tokens differ from resident: {differ}")
    print("serve,identical_tokens=True")


def serve_mode(mode: str, model, params, dtype: str, trace, dev: jax.Device,
               clock: CompileClock) -> Dict[int, np.ndarray]:
    """Serve ``trace`` in one session of ``mode``; every request must
    finish with its token count. Returns req_id -> tokens."""
    session = HyperOffloadSession(offload_config(
        model, mode=mode, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
        cache_dtype=dtype, chunk_size=CHUNK))
    sched = session.scheduler(model, params)
    t0, c0 = time.perf_counter(), clock.seconds
    out = sched.run(trace)
    wall = time.perf_counter() - t0
    stats = session.stats()
    session.close()
    print(f"serve,{mode},requests={len(out)},"
          f"tokens={sum(len(v) for v in out.values())},"
          f"steps={sched.stats.steps},wall_s={wall:.2f},"
          f"compile_s={clock.seconds - c0:.2f},"
          f"prefill_executables={jit_prefill_chunk(model)._cache_size()},"
          f"peak_bytes_in_use={dev.memory_stats()['peak_bytes_in_use']}")
    for r in trace:
        got = len(out.get(r.req_id, ()))
        check(got == r.max_new_tokens,
              f"{mode}: request {r.req_id} emitted {got} of "
              f"{r.max_new_tokens} tokens")
    if mode == "kv_offload":
        host = stats["pool"]["tier/host"]
        pairs = stats["pool"]["transfer"]["pairs"]
        puts = pairs.get("device->host", {}).get("transfers", 0)
        gets = pairs.get("host->device", {}).get("transfers", 0)
        print(f"serve,host_tier,backend={host['backend']},"
              f"peak_bytes={host['peak']},puts={puts},gets={gets}")
        check(host["backend"] == HOST_BACKEND,
              f"host tier is {host['backend']}, not {HOST_BACKEND}")
        check(puts > 0 and gets > 0, "no host-tier traffic")
    return out


def _check_kernel(name: str, args, want: jax.Array, scale: float,
                  **kw) -> None:
    """Run ``ops.<name>`` on ``args``; its compiled program must hold the
    Mosaic kernel and its output must match ``want``."""
    fn = getattr(ops, name)
    out = fn(*args, scale=scale, **kw)
    hlo = fn.lower(*args, scale=scale, **kw).compile().as_text()
    check("tpu_custom_call" in hlo, f"{name}: no Mosaic kernel in program")
    check(out.shape == want.shape, f"{name}: shape {out.shape}")
    out = np.asarray(out, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(out - want)
    atol = KERNEL_ATOL[name]
    excess = float(np.max(err - atol - KERNEL_RTOL * np.abs(want)))
    print(f"kernel,{name},shape={out.shape},max_abs_err={err.max():.3e},"
          f"rtol={KERNEL_RTOL},atol={atol},worst_excess={excess:.3e}")
    check(excess <= 0.0, f"{name}: kernel differs from kernels.ref")


def kernel_phase() -> None:
    h, d, page = 32, 96, 32
    scale = d ** -0.5
    ks = iter(jax.random.split(jax.random.key(SEED), 16))

    def normal(shape):
        return jax.random.normal(next(ks), shape, jnp.bfloat16)

    # prefill: (B, S, H, D) model layout, causal over 512 tokens
    q, k, v = (normal((1, 512, h, d)) for _ in range(3))
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(
            *(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
            scale=scale).transpose(0, 2, 1, 3)
    _check_kernel("flash_attention", (q, k, v), want, scale)

    # ring decode: layer 1 of a two-layer stacked cache, batch 4 over
    # max_seq-slot rings at lengths that end inside, on and past a block
    q = normal((4, h * d))
    k, v = normal((2, 4, MAX_SEQ, h * d)), normal((2, 4, MAX_SEQ, h * d))
    pos = jnp.asarray([700, 300, 255, 256], jnp.int32)

    def heads(x):
        return x[1].reshape(4, MAX_SEQ, h, d).transpose(0, 2, 1, 3)

    with jax.default_matmul_precision("highest"):
        want = ref.decode_attention_ref(
            q.reshape(4, h, d), heads(k), heads(v), pos,
            scale=scale).reshape(4, h * d)
    _check_kernel("decode_attention", (q, k, v, jnp.int32(1), pos), want,
                  scale, head_dim=d)

    # paged decode: 12 scrambled pages of a 16-slot buffer + a 19-token tail
    q = normal((4, h, d))
    kp, vp = normal((16, 4, h, page, d)), normal((16, 4, h, page, d))
    kt, vt = normal((4, h, page, d)), normal((4, h, page, d))
    table = jax.random.permutation(next(ks), 16)[:12].astype(jnp.int32)
    args = (q, kp, vp, table, kt, vt, jnp.int32(19))
    with jax.default_matmul_precision("highest"):
        want = ref.paged_decode_attention_ref(*args, scale=scale)
    _check_kernel("paged_decode_attention", args, want, scale)


def main() -> None:
    dev = require_tpu()
    print(f"compile_cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    serve_phase(dev, clock)
    kernel_phase()
    mem = dev.memory_stats()
    print(f"total,wall_s={time.perf_counter() - t0:.2f},"
          f"compile_s={clock.seconds:.2f},"
          f"peak_bytes_in_use={mem['peak_bytes_in_use']},"
          f"bytes_limit={mem['bytes_limit']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
