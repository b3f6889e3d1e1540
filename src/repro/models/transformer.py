"""Segment-scan transformer driver.

Parameters for each repeated layer pattern are stacked along a leading
``repeats`` dimension and the pattern is applied under ``jax.lax.scan`` —
one pattern body is traced/compiled regardless of depth, which keeps the
HLO small enough to compile 80-layer production configs with 512 host
devices on the dry-run machine. KV/SSM caches share the same stacked
layout: prefill scans them as xs/ys, decode carries them.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig, Segment
from repro.models import blocks
from repro.models.common import apply_norm, embed_init, norm_params, softcap
from repro.sharding.rules import constrain


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def _init_pattern_params(cfg: ModelConfig, pattern, key, dtype) -> Dict:
    ks = jax.random.split(key, len(pattern))
    return {f"p{i}": blocks.init_layer_params(cfg, spec, ks[i], dtype)
            for i, spec in enumerate(pattern)}


def init_params(cfg: ModelConfig, key, dtype=jnp.float32) -> Dict:
    n_seg = len(cfg.segments)
    keys = jax.random.split(key, n_seg + 4)
    params: Dict[str, Any] = {
        "embed": embed_init(keys[0], (cfg.padded_vocab, cfg.d_model), dtype),
        "final_norm": norm_params(cfg, keys[1]),
        "segments": [],
    }
    for si, seg in enumerate(cfg.segments):
        seg_keys = jax.random.split(keys[2 + si], seg.repeats)
        stacked = jax.vmap(
            lambda k: _init_pattern_params(cfg, seg.pattern, k, dtype)
        )(seg_keys)
        params["segments"].append(stacked)
    if not cfg.tie_embeddings:
        params["lm_head"] = embed_init(keys[-2], (cfg.d_model, cfg.padded_vocab), dtype)
    if cfg.encoder is not None:
        enc_spec = LayerSpec(mixer="attn", ffn="gelu")
        enc_keys = jax.random.split(keys[-1], cfg.encoder.n_layers)
        enc_layers = jax.vmap(
            lambda k: blocks.init_layer_params(cfg, enc_spec, k, dtype)
        )(enc_keys)
        params["encoder"] = {
            "layers": enc_layers,
            "final_norm": norm_params(cfg, keys[-1]),
        }
    return params


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=jnp.float32,
               swa_override: Optional[int] = None) -> Dict:
    """Stacked per-segment caches mirroring the parameter layout."""
    enc_frames = cfg.encoder.n_frames if cfg.encoder is not None else None
    cache: Dict[str, Any] = {"segments": []}
    for seg in cfg.segments:
        one = {
            f"p{i}": blocks.init_layer_cache(
                cfg, spec, batch, max_seq, dtype,
                swa_override=swa_override, enc_frames=enc_frames)
            for i, spec in enumerate(seg.pattern)
        }
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (seg.repeats,) + x.shape), one)
        cache["segments"].append(stacked)
    return cache


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------


def _sinusoid(positions: jax.Array, d: int) -> jax.Array:
    """Sinusoidal position encodings, shape positions.shape + (d,)."""
    half = d // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (jnp.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def embed_tokens(cfg: ModelConfig, params: Dict, tokens: jax.Array,
                 positions: jax.Array,
                 vision_embeds: Optional[jax.Array] = None,
                 vision_mask: Optional[jax.Array] = None) -> jax.Array:
    x = params["embed"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if vision_embeds is not None and vision_mask is not None:
        # scatter precomputed patch embeddings (frontend stub) over the
        # positions flagged by vision_mask; vision_embeds is (B, S, D) aligned
        x = jnp.where(vision_mask[..., None], vision_embeds.astype(x.dtype), x)
    if cfg.rope_mode == "learned":
        # implemented as sinusoidal (parameter-free — covers arbitrary decode
        # lengths; documented deviation from whisper's learned table)
        pos2d = positions if positions.ndim == 2 else positions[0]
        x = x + _sinusoid(pos2d, cfg.d_model).astype(x.dtype)
    x = constrain(x, ("batch", "seq_act", "embed_act"))
    return x


def final_logits(cfg: ModelConfig, params: Dict, x: jax.Array) -> jax.Array:
    x = apply_norm(cfg, params["final_norm"], x)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].T
    else:
        logits = x @ params["lm_head"]
    logits = softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
    if cfg.padded_vocab != cfg.vocab_size:
        # padded-vocab sharding: masked pad columns never win softmax/argmax
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits


# ---------------------------------------------------------------------------
# Encoder (whisper)
# ---------------------------------------------------------------------------


def encode(cfg: ModelConfig, params: Dict, enc_embeds: jax.Array) -> jax.Array:
    """enc_embeds: (B, frames, D) precomputed frontend-stub embeddings."""
    enc_spec = LayerSpec(mixer="attn", ffn="gelu")
    b, t, _ = enc_embeds.shape
    pos = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    x = enc_embeds + _sinusoid(pos, cfg.d_model).astype(enc_embeds.dtype)

    def body(h, layer_p):
        h, _ = blocks.apply_layer(cfg, enc_spec, layer_p, h, pos, causal=False)
        return h, None

    # rematerialize encoder internals in the backward pass — without this the
    # scan saves every layer's full (frames × frames) attention scores
    body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return apply_norm(cfg, params["encoder"]["final_norm"], x)


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------


def forward(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    *,
    positions: Optional[jax.Array] = None,
    enc_embeds: Optional[jax.Array] = None,
    vision_embeds: Optional[jax.Array] = None,
    vision_mask: Optional[jax.Array] = None,
    swa_override: Optional[int] = None,
    remat_policy=None,
) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits, aux_loss)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if cfg.rope_mode == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, b, s))
    enc_out = None
    if cfg.encoder is not None:
        assert enc_embeds is not None, "whisper needs encoder frontend embeddings"
        enc_out = encode(cfg, params, enc_embeds)
    x = embed_tokens(cfg, params, tokens, positions, vision_embeds, vision_mask)
    aux_total = jnp.zeros((), jnp.float32)

    for seg, seg_params in zip(cfg.segments, params["segments"]):
        def pattern_body(h, layer_params, seg=seg):
            aux_sum = jnp.zeros((), jnp.float32)
            for i, spec in enumerate(seg.pattern):
                h, aux = blocks.apply_layer(
                    cfg, spec, layer_params[f"p{i}"], h, positions,
                    enc_out=enc_out, swa_override=swa_override)
                aux_sum = aux_sum + aux
            return h, aux_sum

        if remat_policy is not None:
            pattern_body = jax.checkpoint(pattern_body, policy=remat_policy,
                                          static_argnums=())

        def scan_body(carry, layer_params):
            h, aux_acc = carry
            h, aux_sum = pattern_body(h, layer_params)
            return (h, aux_acc + aux_sum), None

        (x, aux_total), _ = jax.lax.scan(scan_body, (x, aux_total), seg_params)

    logits = final_logits(cfg, params, x)
    return logits, aux_total


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,
    cache: Dict,
    *,
    positions: Optional[jax.Array] = None,
    enc_embeds: Optional[jax.Array] = None,
    vision_embeds: Optional[jax.Array] = None,
    vision_mask: Optional[jax.Array] = None,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, Dict]:
    """Forward over the prompt; returns (last-token logits, filled cache)."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if cfg.rope_mode == "mrope":
            positions = jnp.broadcast_to(positions[None], (3, b, s))
    enc_out = None
    if cfg.encoder is not None:
        assert enc_embeds is not None
        enc_out = encode(cfg, params, enc_embeds)
    x = embed_tokens(cfg, params, tokens, positions, vision_embeds, vision_mask)

    new_cache: Dict[str, Any] = {"segments": []}
    for seg, seg_params, seg_cache in zip(
            cfg.segments, params["segments"], cache["segments"]):

        def scan_body(h, xs, seg=seg):
            layer_params, layer_cache = xs
            out_cache = {}
            for i, spec in enumerate(seg.pattern):
                h, _, c = blocks.apply_layer_prefill(
                    cfg, spec, layer_params[f"p{i}"], h, positions,
                    layer_cache[f"p{i}"], enc_out=enc_out,
                    swa_override=swa_override)
                out_cache[f"p{i}"] = c
            return h, out_cache

        x, seg_new_cache = jax.lax.scan(scan_body, x, (seg_params, seg_cache))
        new_cache["segments"].append(seg_new_cache)

    logits = final_logits(cfg, params, x[:, -1:, :])
    return logits, new_cache


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------


def prefill_chunk(
    cfg: ModelConfig,
    params: Dict,
    tokens: jax.Array,   # (B, S_chunk) int32 — chunk at global offset
    offset: jax.Array,   # scalar int32: global position of chunk token 0
    valid_len: jax.Array,  # scalar int32: real tokens (the rest is padding)
    cache: Dict,
    *,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, Dict]:
    """Cache-aware prefill of one prompt chunk (the serving scheduler's
    chunked-prefill entry point). Each chunk attends over
    ``[cache ++ chunk]`` at its global position offset, so prefilling a
    prompt ``chunk`` tokens at a time produces the same cache a whole-prompt
    ``prefill`` would. Returns (logits of the last *valid* chunk token
    (B,1,V), updated cache). Shapes are static except the traced
    ``offset``/``valid_len`` scalars — mixed prompt lengths share ONE
    compiled executable per chunk shape."""
    b, s = tokens.shape
    positions = offset + jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, b, s))
    x = embed_tokens(cfg, params, tokens, positions)

    new_cache: Dict[str, Any] = {"segments": []}
    for seg, seg_params, seg_cache in zip(
            cfg.segments, params["segments"], cache["segments"]):

        def scan_body(h, xs, seg=seg):
            layer_params, layer_cache = xs
            out_cache = {}
            for i, spec in enumerate(seg.pattern):
                h, _, c = blocks.apply_layer_prefill_chunk(
                    cfg, spec, layer_params[f"p{i}"], h, offset, positions,
                    valid_len, layer_cache[f"p{i}"],
                    swa_override=swa_override)
                out_cache[f"p{i}"] = c
            return h, out_cache

        x, seg_new_cache = jax.lax.scan(scan_body, x, (seg_params, seg_cache))
        new_cache["segments"].append(seg_new_cache)

    last = jax.lax.dynamic_slice_in_dim(x, valid_len - 1, 1, axis=1)
    logits = final_logits(cfg, params, last)
    return logits, new_cache


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def decode_step(
    cfg: ModelConfig,
    params: Dict,
    cache: Dict,
    token: jax.Array,   # (B, 1) int32
    pos: jax.Array,     # scalar int32 — or (B,) per-row indices being written
    *,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, Dict]:
    """One autoregressive step. Returns (logits (B,1,V), new cache).

    ``pos`` may be a scalar (uniform batch — every row writes the same
    index) or a (B,) vector (continuous batching — each row sits at its own
    sequence position; rows are independent, so per-row results equal the
    corresponding single-request decode).

    The stacked cache is the layer scan's CARRY, so the while-loop state
    aliases across iterations and is updated in place. A self-attention
    layer's K/V stacks go to the layer whole (``apply_layer_decode(...,
    layer=r)``): it writes its token into layer r and reads layer r where
    it lies, so no iteration copies a layer's cache out and back. Every
    other leaf (MLA latents, SSM state, cross-attention K/V) is sliced out
    for its layer and written back."""
    b = token.shape[0]
    if jnp.ndim(pos) == 0:
        positions = jnp.broadcast_to(pos[None, None], (b, 1)).astype(jnp.int32)
    else:
        positions = pos.astype(jnp.int32)[:, None]
    if cfg.rope_mode == "mrope":
        positions = jnp.broadcast_to(positions[None], (3, b, 1))
    x = embed_tokens(cfg, params, token, positions)

    new_cache: Dict[str, Any] = {"segments": []}
    for seg, seg_params, seg_cache in zip(
            cfg.segments, params["segments"], cache["segments"]):

        def carry_body(carry, xs, seg=seg):
            h, cache_st = carry
            layer_params, r = xs
            cache_st = dict(cache_st)
            for i, spec in enumerate(seg.pattern):
                st = cache_st[f"p{i}"]
                whole = ("k", "v") if spec.mixer == "attn" else ()
                layer_cache = {
                    n: v if n in whole
                    else jax.lax.dynamic_index_in_dim(v, r, 0, keepdims=False)
                    for n, v in st.items()}
                h, c = blocks.apply_layer_decode(
                    cfg, spec, layer_params[f"p{i}"], h, pos, positions,
                    layer_cache, layer=r, swa_override=swa_override)
                cache_st[f"p{i}"] = {
                    n: c[n] if n in whole
                    else jax.lax.dynamic_update_index_in_dim(
                        v, c[n].astype(v.dtype), r, 0)
                    for n, v in st.items()}
            return (h, cache_st), None

        (x, seg_cache), _ = jax.lax.scan(
            carry_body, (x, seg_cache), (seg_params, jnp.arange(seg.repeats)))
        new_cache["segments"].append(seg_cache)

    logits = final_logits(cfg, params, x)
    return logits, new_cache
