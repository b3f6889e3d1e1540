"""Decode attention over the stacked ring cache that reads only live KV.

One query token per row attends over its layer of the cache stack
``(L, B, C, Hkv·D)`` (``models/attention.py``), where it lies: the kernel
takes the whole stack and the layer index ``r`` as a scalar-prefetch
operand, so the layer loop slices no layer out. A ring's live slots are
the prefix ``[0, min(pos + 1, C))`` (``attention._ring_valid_mask``'s
rule, wrapped rings included), so each row's length, the second
scalar-prefetch operand, says which kv blocks hold live tokens.

Grid ``(B, C / bk)``. The K/V index maps clamp a row's block index to its
last live block: past it the index repeats, so the pipeline issues no
DMA, and ``pl.when`` skips the compute. A row with no live token (an
empty batch slot: the scheduler gives it position -1) points at the
block the previous grid step held, so it fetches nothing. Lengths are
data: no length recompiles.

Inside a block the heads stay side by side on the lane axis, as the
cache stores them (a 96-wide head does not tile the 128 lanes): the
scores are one ``(Hq, Hkv·D) x (bk, Hkv·D)ᵀ`` matmul against the
block-diagonal query, the softmax is online per query head, and the
values one ``(Hq, bk) x (bk, Hkv·D)`` matmul; the last step keeps each
head's own kv lanes. Query heads sit member-major in the kernel (row
``j·Hkv + kv`` holds head ``kv·g + j``), so that group member ``j``'s
heads are one run of rows. Operands stay in the cache dtype; the sums
are float32.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38
#: the kernel's name in a profile (``pallas_call(name=...)``)
KERNEL_NAME = "decode_attn"
#: VMEM for the K and V blocks, each double-buffered
KV_VMEM_BYTES = 6 * 2**20


def block_k(c: int, x: int, itemsize: int) -> int:
    """Ring slots per kv block for a ring of ``c`` slots of ``x`` lanes:
    the largest divisor of ``c`` that is a whole number of the dtype's
    sublane tiles and whose K and V blocks, double-buffered, fit
    ``KV_VMEM_BYTES``; the whole ring where no divisor is."""
    tile = 8 * max(1, 4 // itemsize)
    cap = KV_VMEM_BYTES // (4 * x * itemsize)
    fits = [bk for bk in range(tile, min(c, cap) + 1, tile) if c % bk == 0]
    return fits[-1] if fits else c


def ring_lengths(pos, c: int):
    """Live slots of each row's ring once token ``pos`` is written:
    ``min(pos + 1, C)``, and 0 for position -1 (an empty slot). NumPy or
    JAX arrays."""
    return (pos + 1).clip(0, c)


def live_blocks(lens, bk: int):
    """kv blocks that hold a row's live slots. NumPy or JAX arrays."""
    return -(-lens // bk)


def slots_read(lens: np.ndarray, bk: int) -> int:
    """Ring slots the grid fetches for rows of ``lens``: each row's live
    blocks. The first grid step fetches a block whatever the lengths, so
    a batch with no live row still reads one."""
    return max(int(live_blocks(np.asarray(lens), bk).sum()), 1) * bk


def kv_schedule(lens: jax.Array, bk: int):
    """Per row, the scalar-prefetch operands of the K/V index maps: the
    row whose blocks it walks and the lowest and highest block. A live row
    walks its own blocks ``0 .. live_blocks - 1``. An empty row stays on
    the block the grid step before it held, the last of the nearest live
    row above it, or, above the first live row, that row's first block."""
    nb = live_blocks(lens, bk)
    live = nb > 0
    rows = jnp.arange(lens.shape[0], dtype=jnp.int32)
    above = jax.lax.cummax(jnp.where(live, rows, -1))
    held = jnp.where(above >= 0, above, jnp.argmax(live).astype(jnp.int32))
    held_blk = jnp.where(above >= 0, nb[held] - 1, 0)
    row = jnp.where(live, rows, held)
    lo = jnp.where(live, 0, held_blk)
    hi = jnp.where(live, nb - 1, held_blk)
    return (row.astype(jnp.int32), lo.astype(jnp.int32),
            hi.astype(jnp.int32))


def kv_block_index(b, ik, r_ref, len_ref, row_ref, lo_ref, hi_ref):
    """The K/V index map: grid step ``(b, ik)`` reads layer ``r``, row
    ``row[b]``, block ``ik`` clamped to ``[lo[b], hi[b]]``."""
    del len_ref
    return (r_ref[0], row_ref[b],
            jnp.minimum(jnp.maximum(ik, lo_ref[b]), hi_ref[b]), 0)


def head_mask(hq: int, hkv: int, hd: int, dtype) -> jax.Array:
    """(Hq, Hkv·D) 0/1: kernel row ``j·Hkv + kv`` owns kv head ``kv``'s
    lanes."""
    return (jnp.arange(hkv * hd)[None, :] // hd
            == jnp.arange(hq)[:, None] % hkv).astype(dtype)


def _kernel(r_ref, len_ref, row_ref, lo_ref, hi_ref,
            q_ref, k_ref, v_ref, mask_ref, o_ref,
            qbd_scr, m_scr, l_scr, acc_scr,
            *, scale: float, logit_cap: Optional[float], bk: int, g: int,
            hkv: int):
    b, ik = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        # block-diagonal query: row j·Hkv + kv is member j's flat query,
        # kept on kv head kv's lanes
        rows = jax.lax.broadcasted_iota(jnp.int32, qbd_scr.shape, 0)
        q = jnp.zeros(qbd_scr.shape, jnp.float32)
        for j in range(g):
            member = (rows >= j * hkv) & (rows < (j + 1) * hkv)
            q = jnp.where(member, q_ref[j:j + 1, :].astype(jnp.float32), q)
        qbd_scr[...] = (q * mask_ref[...].astype(jnp.float32)
                        ).astype(qbd_scr.dtype)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(ik * bk < n)
    def _block():
        k = k_ref[...]
        s = jax.lax.dot_general(qbd_scr[...], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                                       # (Hq, bk)
        if logit_cap is not None:
            s = logit_cap * jnp.tanh(s / logit_cap)
        slot = ik * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(slot < n, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[...]
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == pl.num_programs(1) - 1)
    def _finish():
        l = l_scr[...]
        own = acc_scr[...] * mask_ref[...].astype(jnp.float32) \
            / jnp.where(l == 0.0, 1.0, l)
        for j in range(g):
            o_ref[j:j + 1, :] = jnp.sum(own[j * hkv:(j + 1) * hkv], axis=0,
                                        keepdims=True)


def decode_attention_pallas(
    q: jax.Array,       # (B, Hq·D), heads flat
    k: jax.Array,       # (L, B, C, Hkv·D) — the whole layer stack
    v: jax.Array,
    layer: jax.Array,   # scalar int32: the layer to read
    lens: jax.Array,    # (B,) int32: live slots per row (``ring_lengths``)
    *,
    head_dim: int,
    scale: float,
    logit_cap: Optional[float] = None,
    interpret: bool,
) -> jax.Array:
    """One query per row against layer ``layer`` of the stacked ring
    cache, reading only the blocks that hold each row's first ``lens``
    slots. Returns (B, Hq·D) float32; a row with no live slot gives
    zeros."""
    _, b, c, x = k.shape
    hd = head_dim
    hkv, hq = x // hd, q.shape[-1] // hd
    g = hq // hkv
    bk = block_k(c, x, k.dtype.itemsize)
    # (B, Hq·D) -> (B, g, Hkv·D): member j of every kv head in the kv
    # heads' lane order (a no-op for g == 1)
    qg = q.astype(k.dtype).reshape(b, hkv, g, hd).transpose(0, 2, 1, 3)
    qg = qg.reshape(b, g, x)
    lens = lens.astype(jnp.int32)
    row, lo, hi = kv_schedule(lens, bk)
    r = jnp.reshape(layer, (1,)).astype(jnp.int32)

    kv_spec = pl.BlockSpec((None, None, bk, x), kv_block_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, c // bk),
        in_specs=[
            pl.BlockSpec((None, g, x),
                         lambda i, ik, r, n, row, lo, hi: (row[i], 0, 0)),
            kv_spec,
            kv_spec,
            pl.BlockSpec((hq, x), lambda i, ik, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, g, x), lambda i, ik, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, x), k.dtype),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, 1), jnp.float32),
            pltpu.VMEM((hq, x), jnp.float32),
        ],
    )
    kernel = functools.partial(_kernel, scale=scale, logit_cap=logit_cap,
                               bk=bk, g=g, hkv=hkv)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, g, x), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name=KERNEL_NAME,
        interpret=interpret,
    )(r, lens, row, lo, hi, qg, k, v, head_mask(hq, hkv, hd, k.dtype))
    # (B, g, Hkv·D) -> (B, Hq·D), head kv·g + j
    return out.reshape(b, g, hkv, hd).transpose(0, 2, 1, 3).reshape(b, hq * hd)
