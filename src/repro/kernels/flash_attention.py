"""Flash attention (prefill) Pallas TPU kernel.

Online-softmax attention tiled for VMEM: grid (batch·q_heads, q_blocks,
kv_blocks), with the kv dimension innermost so the running max / sum /
accumulator scratch carries across kv iterations (TPU grids iterate
sequentially, minor-to-major). Supports GQA (kv head = q head // group),
causal masking, sliding windows, and gemma2-style logit soft-capping.

Block shapes are MXU-aligned (multiples of 128 on the sequence dims); the
working set per grid step is q(bq×D) + k,v(bk×D) + acc(bq×D) — a few
hundred KiB in VMEM at the default 128/128 tiling.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _flash_kernel(q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr,
                  *, scale: float, causal: bool, window: Optional[int],
                  logit_cap: Optional[float], block_q: int, block_k: int,
                  seq_q: int, seq_k: int, n_kv_blocks: int):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)               # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
    if logit_cap is not None:
        s = logit_cap * jnp.tanh(s / logit_cap)

    qi = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    kj = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kj < seq_k
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                            # (bq, bk)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ik == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.where(l_scr[...] == 0.0, 1.0, l_scr[...])
        o_ref[0, 0, ...] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array,   # (B, Hq, S, D)
    k: jax.Array,   # (B, Hkv, T, D)
    v: jax.Array,
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool,
) -> jax.Array:
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = hq // hkv
    block_q = min(block_q, max(8, s))
    block_k = min(block_k, max(8, t))

    pad_q = (-s) % block_q
    pad_k = (-t) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sq, tk = s + pad_q, t + pad_k
    nq, nk = sq // block_q, tk // block_k

    grid = (b * hq, nq, nk)

    def q_index(bh, iq, ik):
        return (bh // hq, bh % hq, iq, 0)

    def kv_index(bh, iq, ik):
        return (bh // hq, (bh % hq) // g, ik, 0)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        logit_cap=logit_cap, block_q=block_q, block_k=block_k,
        seq_q=s, seq_k=t, n_kv_blocks=nk)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bh, iq, ik: q_index(bh, iq, ik)),
            pl.BlockSpec((1, 1, block_k, d), lambda bh, iq, ik: kv_index(bh, iq, ik)),
            pl.BlockSpec((1, 1, block_k, d), lambda bh, iq, ik: kv_index(bh, iq, ik)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bh, iq, ik: q_index(bh, iq, ik)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :s, :]
