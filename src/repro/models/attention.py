"""Attention mixers: GQA (sliding window, logit softcap, RoPE/M-RoPE),
MLA (multi-head latent attention), cross-attention, and their decode paths.

KV caches for sliding-window layers are ring buffers of capacity
``min(window, max_seq)`` — token ``t`` lives in slot ``t % C`` — so a
windowed layer at 500k context holds only ``window`` tokens of KV.

A self-attention layer caches K and V as ``(B, C, Hkv·D)``: heads side by
side on one minor axis, which on a TPU is lane-aligned (a 96-wide head
minor would pad to 128, so the device would store a ``(…, Hkv, D)`` cache
transposed and every decode step would relayout it). Decode reads and
writes the cache in that layout and scores heads with a 0/1 head matrix
instead of reshaping it; chunked prefill reshapes its one cached row into
heads. On a TPU the decode read is a Pallas kernel that fetches only the
ring blocks holding each row's live tokens; elsewhere jnp scores every
slot and masks the dead ones.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.kernels.decode_attention import (
    decode_attention_pallas, ring_lengths,
)
from repro.models import runtime
from repro.models.common import (
    apply_rope, apply_rope_flat, dense_init, rmsnorm, softcap,
)
from repro.sharding.rules import constrain

NEG_INF = -2.3819763e38  # same constant XLA uses for -inf masking in f32


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def init_attn_params(cfg: ModelConfig, spec: LayerSpec, key, dtype) -> Dict:
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 8)
    p = {
        "wq": dense_init(ks[0], (d, hq * hd), dtype),
        "wk": dense_init(ks[1], (d, hkv * hd), dtype),
        "wv": dense_init(ks[2], (d, hkv * hd), dtype),
        "wo": dense_init(ks[3], (hq * hd, d), dtype),
    }
    if spec.cross_attn:
        p.update({
            "xwq": dense_init(ks[4], (d, hq * hd), dtype),
            "xwk": dense_init(ks[5], (d, hkv * hd), dtype),
            "xwv": dense_init(ks[6], (d, hkv * hd), dtype),
            "xwo": dense_init(ks[7], (hq * hd, d), dtype),
        })
    return p


def init_mla_params(cfg: ModelConfig, key, dtype) -> Dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wdq": dense_init(ks[0], (d, m.q_lora_rank), dtype),
        "q_norm": jnp.zeros((m.q_lora_rank,), jnp.float32),
        "wuq": dense_init(ks[1], (m.q_lora_rank, h * (dn + dr)), dtype),
        "wdkv": dense_init(ks[2], (d, m.kv_lora_rank), dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), jnp.float32),
        "wkr": dense_init(ks[3], (d, dr), dtype),
        "wukv": dense_init(ks[4], (m.kv_lora_rank, h * (dn + dv)), dtype),
        "wo": dense_init(ks[5], (h * dv, d), dtype),
    }


# ---------------------------------------------------------------------------
# Cache layout
# ---------------------------------------------------------------------------


def attn_cache_len(cfg: ModelConfig, spec: LayerSpec, max_seq: int,
                   swa_override: Optional[int] = None) -> int:
    window = spec.window
    if swa_override is not None and spec.mixer in ("attn",) and window is None:
        window = swa_override
    if window is None:
        return max_seq
    return min(window, max_seq)


def init_attn_cache(cfg: ModelConfig, spec: LayerSpec, batch: int, max_seq: int,
                    dtype, swa_override: Optional[int] = None,
                    enc_frames: Optional[int] = None) -> Dict:
    c = attn_cache_len(cfg, spec, max_seq, swa_override)
    if spec.mixer == "mla":
        m = cfg.mla
        cache = {
            "ckv": jnp.zeros((batch, c, m.kv_lora_rank), dtype),
            "krope": jnp.zeros((batch, c, m.qk_rope_head_dim), dtype),
        }
    else:
        kv = cfg.n_kv_heads * cfg.head_dim
        cache = {
            "k": jnp.zeros((batch, c, kv), dtype),
            "v": jnp.zeros((batch, c, kv), dtype),
        }
    if spec.cross_attn:
        assert enc_frames is not None
        cache["xk"] = jnp.zeros((batch, enc_frames, cfg.n_kv_heads, cfg.head_dim), dtype)
        cache["xv"] = jnp.zeros((batch, enc_frames, cfg.n_kv_heads, cfg.head_dim), dtype)
    return cache


# ---------------------------------------------------------------------------
# Score computation (GQA aware)
# ---------------------------------------------------------------------------


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,S,Hq,D), k: (B,T,Hkv,D) -> scores (B,S,Hq,T) in f32."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qf = q.astype(jnp.float32).reshape(b, s, hkv, g, d)
    kf = k.astype(jnp.float32)
    sc = jnp.einsum("bskgd,btkd->bskgt", qf, kf)
    return sc.reshape(b, s, hq, k.shape[1])


def _gqa_out(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (B,S,Hq,T), v: (B,T,Hkv,Dv) -> (B,S,Hq,Dv)."""
    b, s, hq, t = probs.shape
    hkv = v.shape[2]
    g = hq // hkv
    pf = probs.reshape(b, s, hkv, g, t)
    out = jnp.einsum("bskgt,btkd->bskgd", pf, v.astype(jnp.float32))
    return out.reshape(b, s, hq, v.shape[-1])


def _masked_softmax(scores: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    return jax.nn.softmax(scores, axis=-1)


def make_causal_mask(s: int, t: int, window: Optional[int],
                     offset: int = 0) -> jax.Array:
    """(1,S,1,T) mask: query i (global position offset+i) may see key j<=i
    within the window."""
    qi = jnp.arange(s)[:, None] + offset
    kj = jnp.arange(t)[None, :]
    m = kj <= qi
    if window is not None:
        m &= kj > qi - window
    return m[None, :, None, :]


# threshold above which the full-sequence XLA path switches to the
# scan-chunked formulation (transient scores bq×T instead of S×T)
CHUNKED_ATTN_THRESHOLD = 2048
CHUNK_Q = 512


def _chunked_causal_attention(q, k, v, scale, window, cap):
    """Query-chunked causal attention: lax.scan over q blocks keeps the
    score transient at (B, bq, Hq, T) — the pure-XLA analogue of the flash
    kernel, used for long sequences on the dry-run path."""
    b, s, hq, hd = q.shape
    bq = CHUNK_Q
    pad = (-s) % bq
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nb = (s + pad) // bq
    qc = q.reshape(b, nb, bq, hq, hd).transpose(1, 0, 2, 3, 4)  # (nb,B,bq,H,hd)

    def body(_, xs):
        qb, ib = xs
        offset = ib * bq
        sc = _gqa_scores(qb, k) * scale               # (B,bq,Hq,T)
        sc = softcap(sc, cap)
        qi = offset + jnp.arange(bq)[:, None]
        kj = jnp.arange(k.shape[1])[None, :]
        m = kj <= qi
        if window is not None:
            m &= kj > qi - window
        sc = jnp.where(m[None, :, None, :], sc, NEG_INF)
        probs = jax.nn.softmax(sc, axis=-1)
        return None, _gqa_out(probs, v)

    _, out = jax.lax.scan(body, None, (qc, jnp.arange(nb)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, nb * bq, hq, -1)
    return out[:, :s]


# ---------------------------------------------------------------------------
# Full-sequence attention (training / prefill / encoder)
# ---------------------------------------------------------------------------


def attention_full(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    swa_override: Optional[int] = None,
) -> jax.Array:
    """Self-attention over a full sequence. Returns (B,S,D)."""
    if spec.mixer == "mla":
        return _mla_full(cfg, p, x, positions)
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    if cfg.rope_mode in ("rope", "mrope"):
        sections = cfg.mrope_sections if cfg.rope_mode == "mrope" else None
        q = apply_rope(q, positions, cfg.rope_theta, sections)
        k = apply_rope(k, positions, cfg.rope_theta, sections)
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5

    window = spec.window
    if swa_override is not None and window is None:
        window = swa_override

    if runtime.attention_impl() == "pallas" and causal:
        from repro.kernels import ops as kops
        out = kops.flash_attention(
            q, k, v, scale=scale, window=window,
            logit_cap=cfg.attn_logit_softcap, causal=True)
    elif causal and s > CHUNKED_ATTN_THRESHOLD:
        out = _chunked_causal_attention(q, k, v, scale, window,
                                        cfg.attn_logit_softcap)
    else:
        scores = _gqa_scores(q, k) * scale
        scores = softcap(scores, cfg.attn_logit_softcap)
        mask = make_causal_mask(s, s, window) if causal else None
        probs = _masked_softmax(scores, mask)
        out = _gqa_out(probs, v)
    out = out.astype(x.dtype).reshape(b, s, hq * hd)
    return out @ p["wo"]


def cross_attention_full(cfg: ModelConfig, p: Dict, x: jax.Array,
                         enc_out: jax.Array) -> jax.Array:
    """Decoder cross-attention over encoder output (B,T,D)."""
    b, s, d = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["xwq"]).reshape(b, s, hq, hd)
    k = (enc_out @ p["xwk"]).reshape(b, enc_out.shape[1], hkv, hd)
    v = (enc_out @ p["xwv"]).reshape(b, enc_out.shape[1], hkv, hd)
    scores = _gqa_scores(q, k) * hd ** -0.5
    probs = _masked_softmax(scores, None)
    out = _gqa_out(probs, v).astype(x.dtype).reshape(b, s, hq * hd)
    return out @ p["xwo"]


def cross_attention_kv(cfg: ModelConfig, p: Dict, enc_out: jax.Array) -> Tuple[jax.Array, jax.Array]:
    b, t, _ = enc_out.shape
    hkv, hd = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["xwk"]).reshape(b, t, hkv, hd)
    v = (enc_out @ p["xwv"]).reshape(b, t, hkv, hd)
    return k, v


def _mla_full(cfg: ModelConfig, p: Dict, x: jax.Array,
              positions: jax.Array) -> jax.Array:
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    qlat = rmsnorm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = (qlat @ p["wuq"]).reshape(b, s, h, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    qr = apply_rope(qr, positions, cfg.rope_theta)
    ckv = rmsnorm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)  # (B,S,r)
    kr = apply_rope((x @ p["wkr"])[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]  # (B,S,dr)
    kv = (ckv @ p["wukv"]).reshape(b, s, h, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5

    def block(qn_b, qr_b, offset, bq):
        sc = jnp.einsum("bshd,bthd->bsht", qn_b.astype(jnp.float32),
                        kn.astype(jnp.float32))
        sc += jnp.einsum("bshd,btd->bsht", qr_b.astype(jnp.float32),
                         kr.astype(jnp.float32))
        sc *= scale
        qi = offset + jnp.arange(bq)[:, None]
        kj = jnp.arange(s)[None, :]
        sc = jnp.where((kj <= qi)[None, :, None, :], sc, NEG_INF)
        probs = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bsht,bthd->bshd", probs, v.astype(jnp.float32))

    if s > CHUNKED_ATTN_THRESHOLD:
        bq = CHUNK_Q
        pad = (-s) % bq
        qn_p = jnp.pad(qn, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else qn
        qr_p = jnp.pad(qr, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else qr
        nb = (s + pad) // bq
        qn_c = qn_p.reshape(b, nb, bq, h, dn).transpose(1, 0, 2, 3, 4)
        qr_c = qr_p.reshape(b, nb, bq, h, dr).transpose(1, 0, 2, 3, 4)

        def body(_, xs):
            qn_b, qr_b, ib = xs
            return None, block(qn_b, qr_b, ib * bq, bq)

        _, out = jax.lax.scan(body, None, (qn_c, qr_c, jnp.arange(nb)))
        out = out.transpose(1, 0, 2, 3, 4).reshape(b, nb * bq, h, dv)[:, :s]
    else:
        out = block(qn, qr, 0, s)
    out = out.astype(x.dtype).reshape(b, s, h * dv)
    return out @ p["wo"]


# ---------------------------------------------------------------------------
# Prefill (full attention + cache write)
# ---------------------------------------------------------------------------


def attention_prefill(cfg, spec, p, x, positions, cache, *,
                      swa_override=None, enc_out=None):
    """Full causal attention; also fills the layer KV cache.

    Tokens t ∈ [0, S) are written to ring slot t % C.
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    out = attention_full(cfg, spec, p, x, positions, causal=True,
                         swa_override=swa_override)
    new_cache = dict(cache)
    if spec.mixer == "mla":
        m = cfg.mla
        ckv = rmsnorm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)
        kr = apply_rope((x @ p["wkr"])[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
        new_cache["ckv"] = _ring_write_seq(cache["ckv"], ckv.astype(cache["ckv"].dtype))
        new_cache["krope"] = _ring_write_seq(cache["krope"], kr.astype(cache["krope"].dtype))
    else:
        hkv, hd = cfg.n_kv_heads, cfg.head_dim
        k = (x @ p["wk"]).reshape(b, s, hkv, hd)
        v = (x @ p["wv"]).reshape(b, s, hkv, hd)
        if cfg.rope_mode in ("rope", "mrope"):
            sections = cfg.mrope_sections if cfg.rope_mode == "mrope" else None
            k = apply_rope(k, positions, cfg.rope_theta, sections)
        new_cache["k"] = _ring_write_seq(
            cache["k"], k.reshape(b, s, hkv * hd).astype(cache["k"].dtype))
        new_cache["v"] = _ring_write_seq(
            cache["v"], v.reshape(b, s, hkv * hd).astype(cache["v"].dtype))
    if spec.cross_attn and enc_out is not None:
        xk, xv = cross_attention_kv(cfg, p, enc_out)
        new_cache["xk"] = xk.astype(cache["xk"].dtype)
        new_cache["xv"] = xv.astype(cache["xv"].dtype)
    return out, new_cache


def _ring_write_seq(buf: jax.Array, vals: jax.Array) -> jax.Array:
    """Write a full sequence (B,S,...) into a ring buffer (B,C,...):
    token t -> slot t % C. When S <= C this is a plain prefix write."""
    c = buf.shape[1]
    s = vals.shape[1]
    if s <= c:
        return jax.lax.dynamic_update_slice_in_dim(buf, vals, 0, axis=1)
    # keep the last C tokens, rotated so that token t sits at slot t % C
    tail = vals[:, s - c:]
    start = (s - c) % c
    rolled = jnp.roll(tail, shift=start, axis=1)
    return rolled


def _ring_write_at(buf: jax.Array, vals: jax.Array, offset: jax.Array,
                   valid_len: jax.Array) -> jax.Array:
    """Write a chunk (B,S,...) into a ring buffer (B,C,...) at an arbitrary
    start position: token ``offset + i`` -> slot ``(offset + i) % C``.

    Only the first ``valid_len`` tokens are real (the rest padding of a
    final partial chunk) — padded tokens are never written, so slots that
    still hold live earlier tokens of a windowed layer are not clobbered.
    When the valid region exceeds C only its last C tokens land (unique
    slots), matching ``_ring_write_seq``'s keep-the-tail semantics. Both
    ``offset`` and ``valid_len`` may be traced scalars: dropped writes are
    routed out of bounds (scatter ``mode="drop"``), so one compiled shape
    serves every (offset, valid_len)."""
    c = buf.shape[1]
    s = vals.shape[1]
    i = jnp.arange(s)
    keep = (i < valid_len) & (i >= valid_len - c)
    slots = jnp.where(keep, jnp.mod(offset + i, c), c)   # c = out of bounds
    return buf.at[:, slots].set(vals.astype(buf.dtype), mode="drop")


# ---------------------------------------------------------------------------
# Chunked prefill (chunk attends over [cache ++ chunk] at a position offset)
# ---------------------------------------------------------------------------


def attention_prefill_chunk(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict,
    x: jax.Array,            # (B, S_chunk, D) — chunk at global offset
    offset: jax.Array,       # scalar int32: global position of chunk token 0
    positions: jax.Array,    # (B, S_chunk) or (3, B, S_chunk) rope positions
    valid_len: jax.Array,    # scalar int32: real tokens in the chunk (rest pad)
    cache: Dict,
    *,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, Dict]:
    """One prefill chunk against an existing cache: queries attend over
    ``[cache ++ chunk]`` with per-query causal (and sliding-window) masks at
    the correct position offset, then the chunk's K/V ring-write into the
    cache at slots ``(offset + i) % C``.

    The prior-cache segment is read *before* the write, so a windowed layer
    whose chunk wraps the ring never loses in-window history mid-chunk.
    Padded tail tokens (``i >= valid_len``) produce garbage rows that the
    caller discards and are neither attended (causality excludes them for
    every valid query) nor written. Everything is shape-static except the
    traced ``offset``/``valid_len`` scalars — one compiled executable per
    chunk shape."""
    if spec.mixer == "mla":
        return _mla_prefill_chunk(cfg, p, x, offset, positions, valid_len,
                                  cache)
    b, s, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    c = cache["k"].shape[1]
    q = (x @ p["wq"]).reshape(b, s, hq, hd)
    k = (x @ p["wk"]).reshape(b, s, hkv, hd)
    v = (x @ p["wv"]).reshape(b, s, hkv, hd)
    if cfg.rope_mode in ("rope", "mrope"):
        sections = cfg.mrope_sections if cfg.rope_mode == "mrope" else None
        q = apply_rope(q, positions, cfg.rope_theta, sections)
        k = apply_rope(k, positions, cfg.rope_theta, sections)
    k_hist = cache["k"].reshape(b, c, hkv, hd)
    v_hist = cache["v"].reshape(b, c, hkv, hd)
    window = spec.window
    if swa_override is not None and window is None:
        window = swa_override
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5

    # two segments, merged softmax: (a) the prior cache — before the chunk,
    # ring slot j holds token h_j = (offset-1) - ((offset-1-j) mod C), valid
    # while h_j >= 0 (and in-window per query); (b) the chunk itself, plain
    # causal at a shared offset (so the mask is offset-independent).
    qi = offset + jnp.arange(s)                          # global query pos
    j = jnp.arange(c)
    hj = (offset - 1) - jnp.mod(offset - 1 - j, c)       # cached token ids
    m_hist = jnp.broadcast_to((hj >= 0) & (offset > 0), (s, c))
    ii = jnp.arange(s)
    m_chunk = (ii[None, :] <= ii[:, None]) & (ii[None, :] < valid_len)
    if window is not None:
        m_hist = m_hist & (hj[None, :] > qi[:, None] - window)
        m_chunk = m_chunk & (ii[None, :] > ii[:, None] - window)
    sc_hist = _gqa_scores(q, k_hist) * scale             # (B,S,Hq,C)
    sc_chunk = _gqa_scores(q, k) * scale                 # (B,S,Hq,S)
    scores = jnp.concatenate([sc_hist, sc_chunk], axis=-1)
    scores = softcap(scores, cfg.attn_logit_softcap)
    mask = jnp.concatenate([m_hist, m_chunk], axis=-1)   # (S, C+S)
    probs = _masked_softmax(scores, mask[None, :, None, :])
    v_all = jnp.concatenate([v_hist, v.astype(v_hist.dtype)], axis=1)
    out = _gqa_out(probs, v_all).astype(x.dtype).reshape(b, s, hq * hd)
    out = out @ p["wo"]

    new_cache = dict(cache)
    new_cache["k"] = _ring_write_at(cache["k"], k.reshape(b, s, hkv * hd),
                                    offset, valid_len)
    new_cache["v"] = _ring_write_at(cache["v"], v.reshape(b, s, hkv * hd),
                                    offset, valid_len)
    return out, new_cache


def _mla_prefill_chunk(cfg, p, x, offset, positions, valid_len, cache):
    """MLA chunk prefill: write the chunk's latent KV into the cache, then
    attend every chunk query over the whole updated cache (the decode path's
    expand-from-latent, generalized to S queries). Write-then-attend is
    exact here because MLA caches are full-length (no sliding window), so a
    chunk never overwrites history a query still needs."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    c = cache["ckv"].shape[1]
    qlat = rmsnorm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = (qlat @ p["wuq"]).reshape(b, s, h, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    qr = apply_rope(qr, positions, cfg.rope_theta)
    ckv_t = rmsnorm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)
    kr_t = apply_rope((x @ p["wkr"])[:, :, None, :], positions,
                      cfg.rope_theta)[:, :, 0]
    new_ckv = _ring_write_at(cache["ckv"], ckv_t, offset, valid_len)
    new_kr = _ring_write_at(cache["krope"], kr_t, offset, valid_len)
    kv = (new_ckv @ p["wukv"]).reshape(b, c, h, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5
    sc = jnp.einsum("bshd,bthd->bsht", qn.astype(jnp.float32),
                    kn.astype(jnp.float32))
    sc += jnp.einsum("bshd,btd->bsht", qr.astype(jnp.float32),
                     new_kr.astype(jnp.float32))
    sc *= scale
    # after the write, ring slot j holds token P - ((P - j) mod C) for the
    # last written position P; causal: visible iff 0 <= t_j <= query pos
    last = offset + valid_len - 1
    tj = last - jnp.mod(last - jnp.arange(c), c)
    qi = offset + jnp.arange(s)
    mask = (tj[None, :] >= 0) & (tj[None, :] <= qi[:, None])     # (S, C)
    sc = jnp.where(mask[None, :, None, :], sc, NEG_INF)
    probs = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bsht,bthd->bshd", probs, v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(b, s, h * dv)
    out = out @ p["wo"]
    new_cache = dict(cache)
    new_cache["ckv"], new_cache["krope"] = new_ckv, new_kr
    return out, new_cache


# ---------------------------------------------------------------------------
# Decode (single token vs cache)
# ---------------------------------------------------------------------------


def attention_decode(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict,
    x: jax.Array,           # (B, 1, D)
    pos: jax.Array,         # scalar int32 — or (B,) per-row write positions
    positions: jax.Array,   # (B, 1) or (3, B, 1) rope positions of this token
    cache: Dict,
    *,
    layer: jax.Array,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, Dict]:
    """One token against the cache. A self-attention layer's
    ``cache["k"]``/``["v"]`` are the whole layer stack ``(L, B, C,
    Hkv·D)``: the token is written into layer ``layer`` in place and that
    layer is read where it lies, so the decode scan never copies a layer
    out of the stack and back. A row at position -1 is an empty batch
    slot: its output means nothing, the kernel reads none of its cache,
    and its write lands in a row that the next join overwrites whole. MLA
    caches are one layer's."""
    if spec.mixer == "mla":
        return _mla_decode(cfg, p, x, pos, positions, cache)
    hd = cfg.head_dim
    q = x @ p["wq"]                               # (B, 1, Hq·D), heads flat
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.rope_mode in ("rope", "mrope"):
        sections = cfg.mrope_sections if cfg.rope_mode == "mrope" else None
        q = apply_rope_flat(q, positions, cfg.rope_theta, hd, sections)
        k = apply_rope_flat(k, positions, cfg.rope_theta, hd, sections)
    new_k = _ring_write_token(cache["k"], k, pos, layer)
    new_v = _ring_write_token(cache["v"], v, pos, layer)
    scale = cfg.query_scale if cfg.query_scale is not None else hd ** -0.5
    attend = dict(hd=hd, scale=scale, cap=cfg.attn_logit_softcap)
    rows = jnp.broadcast_to(pos, (x.shape[0],)).astype(jnp.int32)
    out = jax.lax.platform_dependent(
        q[:, 0], new_k, new_v, layer, rows,
        tpu=functools.partial(_attend_kernel, **attend, interpret=False),
        default=functools.partial(_attend_xla, **attend))
    out = out.astype(x.dtype)[:, None] @ p["wo"]          # (B,1,D)
    new_cache = dict(cache)
    new_cache["k"], new_cache["v"] = new_k, new_v
    return out, new_cache


def _attend_kernel(q, k, v, layer, pos, *, hd, scale, cap, interpret):
    """The decode read on a TPU: ``kernels/decode_attention.py``, which
    fetches only each row's live blocks of the stack. (B, Hq·D) f32."""
    return decode_attention_pallas(
        q, k, v, layer, ring_lengths(pos, k.shape[2]), head_dim=hd,
        scale=scale, logit_cap=cap, interpret=interpret)


def _attend_xla(q, k, v, layer, pos, *, hd, scale, cap):
    """The decode read elsewhere: layer ``layer`` of the stack, every ring
    slot scored and the dead ones masked. (B, Hq·D) f32."""
    k_l = jax.lax.dynamic_index_in_dim(k, layer, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(v, layer, 0, keepdims=False)
    scores = _flat_scores(q, k_l, hd) * scale              # (B,Hq,C)
    scores = softcap(scores, cap)
    scores = _apply_valid_mask(scores, _ring_valid_mask(pos, k_l.shape[1]))
    probs = jax.nn.softmax(scores, axis=-1)
    return _flat_out(probs, v_l, hd)


def _head_matrix(hkv: int, hd: int, dtype) -> jax.Array:
    """(Hkv·D, Hkv) 0/1: lane x of a flat K/V row belongs to head x // D."""
    return (jnp.arange(hkv * hd)[:, None] // hd
            == jnp.arange(hkv)[None, :]).astype(dtype)


def _flat_scores(q: jax.Array, k: jax.Array, hd: int) -> jax.Array:
    """q: (B, Hq·D), k: (B, C, Hkv·D) -> scores (B, Hq, C) in f32.

    The query is spread into a block-diagonal (B, Hkv·D, Hq): query head
    h = kv·g + j keeps its D lanes in kv head kv's rows and zeros
    elsewhere, so one matmul per row contracts over the cache's flat minor
    axis and yields every head's scores. Operands stay in the cache dtype;
    the sums are f32."""
    b, qd = q.shape
    x = k.shape[-1]
    hkv, hq = x // hd, qd // hd
    g = hq // hkv
    eye = _head_matrix(hkv, hd, k.dtype)
    # (B, Hq·D) -> (B, g, Hkv·D): group member j of every kv head, in the
    # kv heads' lane order (a no-op for g == 1)
    qg = q.astype(k.dtype).reshape(b, hkv, g, hd).transpose(0, 2, 1, 3)
    qg = qg.reshape(b, g, x)
    qbd = qg.transpose(0, 2, 1)[:, :, None, :] * eye[None, :, :, None]
    qbd = qbd.reshape(b, x, hq)                       # head index kv·g + j
    return jnp.einsum("bcx,bxh->bhc", k, qbd,
                      preferred_element_type=jnp.float32)


def _flat_out(probs: jax.Array, v: jax.Array, hd: int) -> jax.Array:
    """probs: (B, Hq, C), v: (B, C, Hkv·D) -> (B, Hq·D) in f32: every query
    head weighs all lanes, then keeps its own kv head's D of them."""
    b, hq, _ = probs.shape
    x = v.shape[-1]
    hkv = x // hd
    g = hq // hkv
    full = jnp.einsum("bhc,bcx->bhx", probs.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)  # (B, Hq, Hkv·D)
    eye = _head_matrix(hkv, hd, jnp.float32)
    own = jnp.einsum("bkjx,xk->bjx", full.reshape(b, hkv, g, x), eye)
    # (B, g, Hkv·D) -> (B, Hq·D), head kv·g + j (a no-op for g == 1)
    return own.reshape(b, g, hkv, hd).transpose(0, 2, 1, 3).reshape(b, hq * hd)


def cross_attention_decode(cfg: ModelConfig, p: Dict, x: jax.Array, cache: Dict) -> jax.Array:
    b, _, _ = x.shape
    hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["xwq"]).reshape(b, 1, hq, hd)
    scores = _gqa_scores(q, cache["xk"]) * hd ** -0.5
    probs = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(probs, cache["xv"]).astype(x.dtype).reshape(b, 1, hq * hd)
    return out @ p["xwo"]


def _ring_valid_mask(pos: jax.Array, c: int) -> jax.Array:
    """Which ring slots hold live tokens once token ``pos`` is written.

    Slot j holds token t_j = pos - ((pos - j) mod C); valid iff t_j >= 0.
    For a full (non-ring) cache this reduces to j <= pos. ``pos`` may be a
    scalar (uniform batch) → (C,), or per-row (B,) → (B, C).
    """
    j = jnp.arange(c)
    p = pos[..., None]              # () -> (1,), (B,) -> (B, 1)
    t = p - jnp.mod(p - j, c)
    return t >= 0


def _apply_valid_mask(scores: jax.Array, valid: jax.Array) -> jax.Array:
    """Mask decode scores (B,...,C) with a (C,) or per-row (B,C) mask."""
    if valid.ndim == 2:
        valid = valid.reshape(valid.shape[:1] + (1,) * (scores.ndim - 2)
                              + valid.shape[1:])
    return jnp.where(valid, scores, NEG_INF)


def _ring_write_token(buf: jax.Array, vals: jax.Array, pos: jax.Array,
                      layer: Optional[jax.Array] = None) -> jax.Array:
    """Write one token's entries (B,1,...) into the ring buffer (B,C,...),
    or, given ``layer``, in place into that layer of a stack (L,B,C,...).

    Scalar ``pos`` writes every row at the same slot (uniform batch); a
    (B,) ``pos`` writes row i at its own slot ``pos[i] % C`` — the
    continuous-batching case where requests sit at different positions.
    """
    if layer is None:
        return _ring_write_token(buf[None], vals, pos, 0)[0]
    b, c = buf.shape[1], buf.shape[2]
    vals = vals.astype(buf.dtype)
    if jnp.ndim(pos) == 0:
        start = (layer, 0, jnp.mod(pos, c)) + (0,) * (buf.ndim - 3)
        return jax.lax.dynamic_update_slice(buf, vals[None], start)
    return buf.at[layer, jnp.arange(b), jnp.mod(pos, c)].set(vals[:, 0])


def _mla_decode(cfg, p, x, pos, positions, cache):
    m = cfg.mla
    b = x.shape[0]
    h = cfg.n_heads
    dn, dr, dv = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    c = cache["ckv"].shape[1]
    qlat = rmsnorm(x @ p["wdq"], p["q_norm"], cfg.norm_eps)
    q = (qlat @ p["wuq"]).reshape(b, 1, h, dn + dr)
    qn, qr = q[..., :dn], q[..., dn:]
    qr = apply_rope(qr, positions, cfg.rope_theta)
    ckv_t = rmsnorm(x @ p["wdkv"], p["kv_norm"], cfg.norm_eps)
    kr_t = apply_rope((x @ p["wkr"])[:, :, None, :], positions, cfg.rope_theta)[:, :, 0]
    new_ckv = _ring_write_token(cache["ckv"], ckv_t, pos)
    new_kr = _ring_write_token(cache["krope"], kr_t, pos)
    kv = (new_ckv @ p["wukv"]).reshape(b, c, h, dn + dv)
    kn, v = kv[..., :dn], kv[..., dn:]
    scale = (dn + dr) ** -0.5
    sc = jnp.einsum("bshd,bthd->bsht", qn.astype(jnp.float32), kn.astype(jnp.float32))
    sc += jnp.einsum("bshd,btd->bsht", qr.astype(jnp.float32), new_kr.astype(jnp.float32))
    sc *= scale
    valid = _ring_valid_mask(pos, c)
    sc = _apply_valid_mask(sc, valid)
    probs = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bsht,bthd->bshd", probs, v.astype(jnp.float32))
    out = out.astype(x.dtype).reshape(b, 1, h * dv)
    out = out @ p["wo"]
    new_cache = dict(cache)
    new_cache["ckv"], new_cache["krope"] = new_ckv, new_kr
    return out, new_cache
