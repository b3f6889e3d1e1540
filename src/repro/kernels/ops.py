"""Jit'd public wrappers around the Pallas kernels.

The kernels compile for the TPU. XLA:CPU cannot run a Mosaic kernel, so
on the CPU backend (tests, dry runs) they execute in interpret mode;
``_interpret`` makes that choice, at trace time, for every wrapper here.
The model substrate calls these through ``repro.models.runtime`` dispatch.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (
    decode_attention_pallas, ring_lengths,
)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas


def _interpret() -> bool:
    """Interpret the kernels only where they cannot compile: on the CPU."""
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("scale", "window", "logit_cap", "causal"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, window: Optional[int] = None,
                    logit_cap: Optional[float] = None,
                    causal: bool = True) -> jax.Array:
    """Model-layout flash attention: q (B,S,Hq,D), k/v (B,T,Hkv,D) →
    (B,S,Hq,D)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention_pallas(qt, kt, vt, scale=scale, causal=causal,
                                 window=window, logit_cap=logit_cap,
                                 interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit,
                   static_argnames=("head_dim", "scale", "logit_cap"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     layer: jax.Array, pos: jax.Array, *, head_dim: int,
                     scale: float,
                     logit_cap: Optional[float] = None) -> jax.Array:
    """Decode attention over layer ``layer`` of a stacked ring cache:
    q (B, Hq·D), cache (L, B, C, Hkv·D), ``pos`` the index each row just
    wrote (scalar or (B,); -1 for an empty row) → (B, Hq·D) float32."""
    pos = jnp.broadcast_to(pos, (q.shape[0],)).astype(jnp.int32)
    return decode_attention_pallas(
        q, k, v, layer, ring_lengths(pos, k.shape[2]), head_dim=head_dim,
        scale=scale, logit_cap=logit_cap, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "logit_cap"))
def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_table: jax.Array,
                           k_tail: jax.Array, v_tail: jax.Array,
                           tail_len: jax.Array, *, scale: float,
                           logit_cap: Optional[float] = None) -> jax.Array:
    """Paged decode attention over pool pages + device tail: q (B,Hq,D),
    pages (P,B,Hkv,page,D), tail (B,Hkv,page,D), table (n,) → (B,Hq,D).
    Retraces only when the table *length* changes (one flush per
    page_size tokens) — the slot values ride in as data via scalar
    prefetch."""
    return paged_decode_attention_pallas(
        q, k_pages, v_pages, page_table, k_tail, v_tail, tail_len,
        scale=scale, logit_cap=logit_cap, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x: jax.Array, a: jax.Array, b_mat: jax.Array, c_mat: jax.Array,
             chunk: int) -> Tuple[jax.Array, jax.Array]:
    return ssd_scan_pallas(x, a, b_mat, c_mat, chunk, interpret=_interpret())
