"""Paged decode attention Pallas TPU kernel over the pool's pages
(``paged_decode_attention_pallas``); the stacked ring cache's decode
kernel is ``kernels/decode_attention.py``.

It is HyperOffload §5.2's serving hot path. The request's KV lives as
*non-contiguous* pages in a device page buffer plus a partial tail page;
instead of gathering + concatenating them per decode step (the
``offload.kvcache`` round trip this kernel replaces), the page table rides
in as a **scalar-prefetch operand** and the k/v BlockSpec index maps walk
it: grid step ``ik`` pulls page ``page_table[ik]`` straight from the
paged buffer, the final grid step covers the device tail, and one online
softmax merges all of it — no materialized contiguous copy at any point.
Tail validity (``arange(page) < tail_len``) is built in the kernel from
``tail_len``, a second scalar-prefetch operand, so an empty, partial, or
just-flushed tail needs no kernel recompile.
``kernels.ref.paged_decode_attention_ref`` is the lowering-free oracle
(and the CPU serving fallback — bit-identical to the legacy gather path).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


# ---------------------------------------------------------------------------
# paged decode: page-table-driven BlockSpecs over the pool page buffer
# ---------------------------------------------------------------------------


def _paged_decode_kernel(pt_ref, tl_ref, q_ref, k_ref, v_ref, kt_ref,
                         vt_ref, o_ref, m_scr, l_scr, acc_scr,
                         *, scale: float, logit_cap: Optional[float],
                         n_blocks: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (G, D)
    # the last grid step is the tail segment; every earlier step is the
    # page the index map prefetched via pt_ref (n_blocks is static, so
    # this select folds per grid position)
    is_tail = ik == n_blocks - 1
    k = jnp.where(is_tail, kt_ref[0, 0], k_ref[0, 0, 0])
    v = jnp.where(is_tail, vt_ref[0, 0], v_ref[0, 0, 0])
    k = k.astype(jnp.float32)                            # (page, D)
    v = v.astype(jnp.float32)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, k.shape[0]), 1)
    valid = jnp.logical_or(jnp.logical_not(is_tail), slot < tl_ref[0])

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (G, page)
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(ik == n_blocks - 1)
    def _finalize():
        denom = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0, ...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def paged_decode_attention_pallas(
    q: jax.Array,           # (B, Hq, D)
    k_pages: jax.Array,     # (P, B, Hkv, page, D) — page buffer slots
    v_pages: jax.Array,
    page_table: jax.Array,  # (n,) int32 — slots to attend, in order
    k_tail: jax.Array,      # (B, Hkv, page, D)
    v_tail: jax.Array,
    tail_len: jax.Array,    # scalar int32
    *,
    scale: float,
    logit_cap: Optional[float] = None,
    interpret: bool,
) -> jax.Array:
    """Fused paged decode: attend directly over the non-contiguous pages
    named by ``page_table`` plus the device tail, in one online-softmax
    pass (see module docstring). The page dimension is the kv block, so
    pool-transfer granularity and kernel tiling coincide. Pages are laid
    out head-major (``Hkv`` before ``page``) so that every block's last
    two dimensions are a whole ``(page, D)`` tile, which the TPU compiler
    requires whatever ``page`` and ``D`` are."""
    b, hq, d = q.shape
    hkv, page = k_tail.shape[1], k_tail.shape[2]
    g = hq // hkv
    if k_pages.shape[0] == 0:
        # the k/v operands need at least one indexable slot even when the
        # table is empty (tail-only attention); a zero page is never read
        # — no index map ever points at it
        k_pages = jnp.zeros((1,) + k_pages.shape[1:], k_pages.dtype)
        v_pages = jnp.zeros((1,) + v_pages.shape[1:], v_pages.dtype)
    n = int(page_table.shape[0])
    n_blocks = n + 1                                     # pages ++ tail
    # the tail grid step never reads the paged operands, but its index map
    # still runs — park it on slot 0 so the prefetch stays in range
    pt = jnp.concatenate([jnp.asarray(page_table, jnp.int32),
                          jnp.zeros((1,), jnp.int32)])
    tl = jnp.reshape(jnp.asarray(tail_len, jnp.int32), (1,))
    qg = q.reshape(b, hkv, g, d)

    kernel = functools.partial(_paged_decode_kernel, scale=scale,
                               logit_cap=logit_cap, n_blocks=n_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b * hkv, n_blocks),
        in_specs=[
            pl.BlockSpec((1, 1, g, d),
                         lambda bh, ik, pt, tl: (bh // hkv, bh % hkv, 0, 0)),
            pl.BlockSpec((1, 1, 1, page, d),
                         lambda bh, ik, pt, tl: (pt[ik], bh // hkv,
                                                 bh % hkv, 0, 0)),
            pl.BlockSpec((1, 1, 1, page, d),
                         lambda bh, ik, pt, tl: (pt[ik], bh // hkv,
                                                 bh % hkv, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bh, ik, pt, tl: (bh // hkv, bh % hkv, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bh, ik, pt, tl: (bh // hkv, bh % hkv, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d),
                               lambda bh, ik, pt, tl: (bh // hkv, bh % hkv,
                                                       0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=interpret,
    )(pt, tl, qg, k_pages, v_pages, k_tail, v_tail)
    return out.reshape(b, hq, d)
