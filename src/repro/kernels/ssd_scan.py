"""Mamba2 SSD (state-space duality) Pallas TPU kernel.

Chunked scan: grid (batch·heads, n_chunks) with the chunk dimension
innermost, carrying the (P×N) inter-chunk state in VMEM scratch across grid
steps — the TPU's sequential minor-to-major grid order makes the scratch a
legal scan carry. Per chunk:

  intra:  Y  = ((C·Bᵀ) ∘ exp(segsum(a)) ∘ tril) · X          (MXU matmuls)
  inter:  Y += exp(cumsum(a)) ∘ (C · stateᵀ)
  carry:  state ← state·exp(Σa) + Xᵀ·(B ∘ exp(Σa − cumsum(a)))

Inputs follow the SSD convention: X pre-scaled by dt, a = dt·A (negative).
The chunk length is the VMEM tile knob: work set ≈ L·(P+2N) + L² + P·N
floats.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref, state_scr,
                *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0].astype(jnp.float32)      # (L, P)
    a = a_ref[0].astype(jnp.float32)      # (L,)
    bm = b_ref[0].astype(jnp.float32)     # (L, N)
    cm = c_ref[0].astype(jnp.float32)     # (L, N)

    a_cs = jnp.cumsum(a)                  # (L,)
    # intra-chunk (diagonal block)
    seg = a_cs[:, None] - a_cs[None, :]   # (L, L)
    tril = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    l_mat = jnp.where(tril, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ()))) * l_mat
    y = jax.lax.dot(scores, x)            # (L, P)

    # inter-chunk contribution from the carried state (P, N)
    state = state_scr[...]
    y += jnp.exp(a_cs)[:, None] * jax.lax.dot_general(
        cm, state, (((1,), (1,)), ((), ())))          # (L, P)

    # state update
    a_last = a_cs[-1]
    decay = jnp.exp(a_last - a_cs)[:, None]           # (L, 1)
    state_scr[...] = state * jnp.exp(a_last) + jax.lax.dot_general(
        x, bm * decay, (((0,), (0,)), ((), ())))      # (P, N)

    y_ref[0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _final():
        state_out_ref[0] = state_scr[...].astype(state_out_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,      # (B, S, H, P) pre-scaled by dt
    a: jax.Array,      # (B, S, H) = dt * A
    b_mat: jax.Array,  # (B, S, H, N)
    c_mat: jax.Array,  # (B, S, H, N)
    chunk: int,
    *,
    interpret: bool,
):
    bsz, s, h, p = x.shape
    n = b_mat.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    # (B, S, H, ·) -> (B·H, S, ·)
    xt = x.transpose(0, 2, 1, 3).reshape(bsz * h, s, p)
    at = a.transpose(0, 2, 1).reshape(bsz * h, s)
    bt = b_mat.transpose(0, 2, 1, 3).reshape(bsz * h, s, n)
    ct = c_mat.transpose(0, 2, 1, 3).reshape(bsz * h, s, n)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, state = pl.pallas_call(
        kernel,
        grid=(bsz * h, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk), lambda bh, ci: (bh, ci)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, n), lambda bh, ci: (bh, ci, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, p, n), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz * h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bsz * h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xt, at, bt, ct)

    y = y.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)
    state = state.reshape(bsz, h, p, n)
    return y, state
