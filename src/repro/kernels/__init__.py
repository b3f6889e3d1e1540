"""Pallas TPU kernels for the perf-critical compute layers.

- flash_attention — prefill attention (GQA, sliding window, logit softcap)
- decode_attention — decode over the stacked ring cache, reading only
  each row's live kv blocks (the scheduler's decode on a TPU)
- paged_attention — paged decode attention (consumes pool-prefetched KV
  pages tile-by-tile)
- ssd_scan       — Mamba2 SSD chunked scan with VMEM state carry

Each has a jit wrapper in ``ops`` and a pure-jnp oracle in ``ref``;
``tests/test_kernels.py`` sweeps shapes/dtypes/flags in interpret mode.
"""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
