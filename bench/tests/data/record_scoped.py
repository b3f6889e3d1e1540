"""Record ``scoped_tpu.xplane.pb`` and ``scoped_tpu.json`` on one TPU.

    python3 bench/tests/data/record_scoped.py <out_dir>

A jitted step with two nested ``jax.named_scope`` (``bench_outer``, and
``bench_inner`` inside it) and one Pallas kernel (``bench_add_one``, in
``bench_inner``), run three times under the profiler, each run followed
by 20 ms of host sleep. The JSON holds the marker's ``perf_counter``
reading (``trace_reduce.MARKER``), the window, and the scopes and kernel
the step used; ``test_trace_reduce`` reads both files.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402

MARKER = "bench.clock"
OUTER, INNER, KERNEL = "bench_outer", "bench_inner", "bench_add_one"


def _add_one(x_ref, o_ref):
    o_ref[...] = x_ref[...] + 1.0


def add_one(x):
    return pl.pallas_call(
        _add_one, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name=KERNEL, interpret=jax.default_backend() == "cpu")(x)


@jax.jit
def step(x, w):
    with jax.named_scope(OUTER):
        y = x @ w
        with jax.named_scope(INNER):
            y = add_one(jnp.tanh(y) @ w)
        y = jnp.cumsum(y, axis=0)
    return (y * y).sum()


def main(out_dir: str) -> None:
    kx, kw = jax.random.split(jax.random.key(0))
    x = jax.random.normal(kx, (512, 1024), jnp.float32)
    w = jax.random.normal(kw, (1024, 1024), jnp.float32) / 32
    jax.block_until_ready(step(x, w))
    trace_dir = tempfile.mkdtemp()
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(MARKER):
        marker = time.perf_counter()
    t_open = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(step(x, w))
        time.sleep(0.02)
    t_close = time.perf_counter()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    shutil.copy(path, os.path.join(out_dir, "scoped_tpu.xplane.pb"))
    shutil.rmtree(trace_dir)
    with open(os.path.join(out_dir, "scoped_tpu.json"), "w") as f:
        json.dump({"marker": marker, "window": [t_open, t_close],
                   "scopes": [OUTER, INNER], "kernel": KERNEL}, f)


if __name__ == "__main__":
    main(sys.argv[1])
