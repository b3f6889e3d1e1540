"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernel tests sweep against
(tests/test_kernels.py: shapes × dtypes × flags, assert_allclose).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -2.3819763e38


def _softcap(x, cap):
    return x if cap is None else cap * jnp.tanh(x / cap)


def flash_attention_ref(
    q: jax.Array,   # (B, Hq, S, D)
    k: jax.Array,   # (B, Hkv, T, D)
    v: jax.Array,   # (B, Hkv, T, D)
    *,
    scale: float,
    causal: bool = True,
    window: Optional[int] = None,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    b, hq, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, s, d) * scale
    kf = k.astype(jnp.float32)
    sc = jnp.einsum("bkgsd,bktd->bkgst", qf, kf)
    sc = _softcap(sc, logit_cap)
    qi = jnp.arange(s)[:, None]
    kj = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kj <= qi
    if window is not None:
        mask &= kj > qi - window
    sc = jnp.where(mask[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgst,bktd->bkgsd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, s, d).astype(q.dtype)


def decode_attention_ref(
    q: jax.Array,      # (B, Hq, D) — one token per sequence
    k: jax.Array,      # (B, Hkv, C, D) ring cache
    v: jax.Array,      # (B, Hkv, C, D)
    pos: jax.Array,    # scalar or (B,) int32 — token index just written
    *,
    scale: float,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    """Attention of one query over a ring-buffer cache: slot j holds token
    t_j = pos - ((pos - j) mod C); valid iff t_j >= 0. A scalar ``pos``
    holds for every row."""
    b, hq, d = q.shape
    hkv, c = k.shape[1], k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d) * scale
    sc = jnp.einsum("bkgd,bkcd->bkgc", qf, k.astype(jnp.float32))
    sc = _softcap(sc, logit_cap)
    at = jnp.reshape(pos, (-1, 1, 1, 1))            # (1 or B, 1, 1, 1)
    tj = at - jnp.mod(at - jnp.arange(c), c)
    sc = jnp.where(tj >= 0, sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgc,bkcd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def paged_decode_attention_ref(
    q: jax.Array,           # (B, Hq, D) — one token per sequence
    k_pages: jax.Array,     # (P, B, Hkv, page, D) — page-resident slots
    v_pages: jax.Array,
    page_table: jax.Array,  # (n,) int — slots to attend over, in order
    k_tail: jax.Array,      # (B, Hkv, page, D) — device tail buffer
    v_tail: jax.Array,
    tail_len: jax.Array,    # scalar int — valid tokens in the tail
    *,
    scale: float,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    """Decode attention over non-contiguous pages + device tail.

    The lowering-free oracle for ``kernels.paged_attention``'s
    paged-decode kernel: gathers ``k_pages[page_table]`` and then runs
    ``merged_pages_attention``, the math ``offload.kvcache``'s gather
    path runs too — so the output is bit-for-bit the gather path's,
    which is what makes codec-"none" serving token-identical when the
    fused path replaces the per-step gather/concat round trip."""
    return merged_pages_attention(
        q, k_pages[page_table], v_pages[page_table], k_tail, v_tail,
        tail_len, scale=scale, logit_cap=logit_cap)


def merged_pages_attention(
    q: jax.Array,           # (B, Hq, D)
    k_sel: jax.Array,       # (n, B, Hkv, page, D) — selected pages, in order
    v_sel: jax.Array,
    k_tail: jax.Array,      # (B, Hkv, page, D)
    v_tail: jax.Array,
    tail_len: jax.Array,    # scalar int — valid tokens in the tail
    *,
    scale: float,
    logit_cap: Optional[float] = None,
) -> jax.Array:
    """Exact attention over [pages ++ tail]: scores per segment, tail
    masked at ``tail_len``, one concatenated softmax. Pages and tail are
    head-major, the layout ``PagedKVCache`` stores and the kernel tiles."""
    b, hq, d = q.shape
    n, _, hkv, page, _ = k_sel.shape
    g = hq // hkv
    k_flat = k_sel.transpose(1, 2, 0, 3, 4).reshape(b, hkv, n * page, d)
    v_flat = v_sel.transpose(1, 2, 0, 3, 4).reshape(b, hkv, n * page, d)
    qf = q.astype(jnp.float32).reshape(b, hkv, g, d) * scale
    s_pages = jnp.einsum("bkgd,bktd->bkgt", qf,
                         k_flat.astype(jnp.float32)).reshape(b, hq, n * page)
    s_tail = jnp.einsum("bkgd,bktd->bkgt", qf,
                        k_tail.astype(jnp.float32)).reshape(b, hq, page)
    s_pages = _softcap(s_pages, logit_cap)
    s_tail = _softcap(s_tail, logit_cap)
    t_mask = jnp.arange(page) < tail_len
    s_tail = jnp.where(t_mask[None, None, :], s_tail, NEG_INF)
    s = jnp.concatenate([s_pages, s_tail], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    v_all = jnp.concatenate([v_flat, v_tail], axis=2)   # (B, Hkv, T, D)
    pf = p.reshape(b, hkv, g, -1)
    out = jnp.einsum("bkgt,bktd->bkgd", pf, v_all.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def ssd_scan_ref(
    x: jax.Array,     # (B, S, H, P) pre-scaled by dt
    a: jax.Array,     # (B, S, H) = dt * A (negative)
    b_mat: jax.Array,  # (B, S, H, N)
    c_mat: jax.Array,  # (B, S, H, N)
    chunk: int,
) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD oracle — delegates to the model-substrate implementation
    (itself validated against the O(S) recurrence in tests/test_ssm.py)."""
    from repro.models.ssm import ssd_chunked
    return ssd_chunked(x, a, b_mat, c_mat, chunk)
