"""Layer (block) application: pre-norm residual structure over a mixer and an
FFN, with gemma2-style optional post-sublayer norms and whisper-style
cross-attention sublayers. One code path per execution mode (train-forward,
prefill, decode) so caches stay explicit."""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import apply_norm, norm_params
from repro.sharding.rules import constrain


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------


def init_layer_params(cfg: ModelConfig, spec: LayerSpec, key, dtype) -> Dict:
    k_mix, k_ffn, k_norm = jax.random.split(key, 3)
    p: Dict = {"pre_norm": norm_params(cfg, k_norm)}
    if spec.mixer == "mamba2":
        p["mixer"] = ssm_mod.init_mamba_params(cfg, k_mix, dtype)
    elif spec.mixer == "mla":
        p["mixer"] = attn.init_mla_params(cfg, k_mix, dtype)
    else:
        p["mixer"] = attn.init_attn_params(cfg, spec, k_mix, dtype)
    if spec.post_norms:
        p["post_norm"] = norm_params(cfg, k_norm)
    if spec.cross_attn:
        p["cross_norm"] = norm_params(cfg, k_norm)
    if spec.ffn != "none":
        p["ffn_norm"] = norm_params(cfg, k_norm)
        if spec.ffn == "moe":
            p["ffn"] = moe_mod.init_moe_params(cfg, k_ffn, dtype)
        elif spec.ffn == "gelu":
            p["ffn"] = mlp_mod.init_gelu_params(cfg, k_ffn, dtype)
        else:
            p["ffn"] = mlp_mod.init_swiglu_params(cfg, k_ffn, dtype)
        if spec.post_norms:
            p["post_ffn_norm"] = norm_params(cfg, k_norm)
    return p


def init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_seq: int, dtype,
                     swa_override: Optional[int] = None,
                     enc_frames: Optional[int] = None) -> Dict:
    if spec.mixer == "mamba2":
        return ssm_mod.init_mamba_cache(cfg, batch, dtype)
    return attn.init_attn_cache(cfg, spec, batch, max_seq, dtype,
                                swa_override=swa_override,
                                enc_frames=enc_frames)


# ---------------------------------------------------------------------------
# Forward (training — no cache)
# ---------------------------------------------------------------------------


def apply_layer(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    enc_out: Optional[jax.Array] = None,
    causal: bool = True,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg, p["pre_norm"], x)
    if spec.mixer == "mamba2":
        h = ssm_mod.mamba_forward(cfg, p["mixer"], h)
    else:
        h = attn.attention_full(cfg, spec, p["mixer"], h, positions,
                                causal=causal, swa_override=swa_override)
    if spec.post_norms:
        h = apply_norm(cfg, p["post_norm"], h)
    # seq-shard the sublayer output BEFORE the residual add: the row-parallel
    # wo matmul's all-reduce becomes a reduce-scatter (Megatron-SP), and the
    # saved "attn_out" tensor is 1/TP the size
    h = constrain(h, ("batch", "seq_act", "embed_act"))
    h = checkpoint_name(h, "attn_out")
    x = x + h
    if spec.cross_attn and enc_out is not None:
        h = apply_norm(cfg, p["cross_norm"], x)
        x = x + attn.cross_attention_full(cfg, p["mixer"], h, enc_out)
    if spec.ffn != "none":
        h = apply_norm(cfg, p["ffn_norm"], x)
        if spec.ffn == "moe":
            h, aux = moe_mod.moe_ffn(cfg, p["ffn"], h)
        elif spec.ffn == "gelu":
            h = mlp_mod.gelu_mlp(p["ffn"], h)
        else:
            h = mlp_mod.swiglu(p["ffn"], h)
        if spec.post_norms:
            h = apply_norm(cfg, p["post_ffn_norm"], h)
        h = constrain(h, ("batch", "seq_act", "embed_act"))
        h = checkpoint_name(h, "mlp_out")
        x = x + h
    x = constrain(x, ("batch", "seq_act", "embed_act"))
    x = checkpoint_name(x, "resid")
    return x, aux


# ---------------------------------------------------------------------------
# Prefill (forward + cache build)
# ---------------------------------------------------------------------------


def apply_layer_prefill(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict,
    x: jax.Array,
    positions: jax.Array,
    cache: Dict,
    *,
    enc_out: Optional[jax.Array] = None,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, Dict]:
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg, p["pre_norm"], x)
    if spec.mixer == "mamba2":
        h, new_cache = ssm_mod.mamba_prefill(cfg, p["mixer"], h, cache)
    else:
        h, new_cache = attn.attention_prefill(
            cfg, spec, p["mixer"], h, positions, cache,
            swa_override=swa_override, enc_out=enc_out)
    if spec.post_norms:
        h = apply_norm(cfg, p["post_norm"], h)
    x = x + h
    if spec.cross_attn and enc_out is not None:
        h = apply_norm(cfg, p["cross_norm"], x)
        x = x + attn.cross_attention_full(cfg, p["mixer"], h, enc_out)
    if spec.ffn != "none":
        h = apply_norm(cfg, p["ffn_norm"], x)
        if spec.ffn == "moe":
            h, aux = moe_mod.moe_ffn(cfg, p["ffn"], h)
        elif spec.ffn == "gelu":
            h = mlp_mod.gelu_mlp(p["ffn"], h)
        else:
            h = mlp_mod.swiglu(p["ffn"], h)
        if spec.post_norms:
            h = apply_norm(cfg, p["post_ffn_norm"], h)
        x = x + h
    x = constrain(x, ("batch", "seq_act", "embed_act"))
    return x, aux, new_cache


def apply_layer_prefill_chunk(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict,
    x: jax.Array,            # (B, S_chunk, D)
    offset: jax.Array,       # scalar: global position of chunk token 0
    positions: jax.Array,    # (B, S_chunk) or (3, B, S_chunk)
    valid_len: jax.Array,    # scalar: real tokens in the chunk
    cache: Dict,
    *,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array, Dict]:
    """Chunked cache-aware prefill step for one layer: the chunk attends
    over [cache ++ chunk] at its position offset and the cache advances by
    the chunk's (valid) K/V. Attention/MLA mixers only — recurrent (mamba2)
    and cross-attention layers have no per-position cache to resume from
    (``Model.supports_chunked_prefill`` gates this upstream)."""
    if spec.mixer == "mamba2" or spec.cross_attn:
        raise NotImplementedError(
            "chunked prefill supports attention/MLA self-attention layers "
            "only (gate on Model.supports_chunked_prefill)")
    aux = jnp.zeros((), jnp.float32)
    h = apply_norm(cfg, p["pre_norm"], x)
    h, new_cache = attn.attention_prefill_chunk(
        cfg, spec, p["mixer"], h, offset, positions, valid_len, cache,
        swa_override=swa_override)
    if spec.post_norms:
        h = apply_norm(cfg, p["post_norm"], h)
    x = x + h
    if spec.ffn != "none":
        h = apply_norm(cfg, p["ffn_norm"], x)
        if spec.ffn == "moe":
            h, aux = moe_mod.moe_ffn(cfg, p["ffn"], h)
        elif spec.ffn == "gelu":
            h = mlp_mod.gelu_mlp(p["ffn"], h)
        else:
            h = mlp_mod.swiglu(p["ffn"], h)
        if spec.post_norms:
            h = apply_norm(cfg, p["post_ffn_norm"], h)
        x = x + h
    x = constrain(x, ("batch", "seq_act", "embed_act"))
    return x, aux, new_cache


# ---------------------------------------------------------------------------
# Decode (single token)
# ---------------------------------------------------------------------------


def apply_layer_decode(
    cfg: ModelConfig,
    spec: LayerSpec,
    p: Dict,
    x: jax.Array,            # (B, 1, D)
    pos: jax.Array,          # scalar, or (B,) per-row
    positions: jax.Array,    # (B,1) or (3,B,1)
    cache: Dict,
    *,
    layer: jax.Array,
    swa_override: Optional[int] = None,
) -> Tuple[jax.Array, Dict]:
    """``layer``: the index into the K/V stacks of a self-attention layer
    (see ``attention.attention_decode``)."""
    h = apply_norm(cfg, p["pre_norm"], x)
    if spec.mixer == "mamba2":
        h, new_cache = ssm_mod.mamba_decode(cfg, p["mixer"], h, cache)
    else:
        h, new_cache = attn.attention_decode(
            cfg, spec, p["mixer"], h, pos, positions, cache, layer=layer,
            swa_override=swa_override)
    if spec.post_norms:
        h = apply_norm(cfg, p["post_norm"], h)
    x = x + h
    if spec.cross_attn:
        h = apply_norm(cfg, p["cross_norm"], x)
        x = x + attn.cross_attention_decode(cfg, p["mixer"], h, cache)
    if spec.ffn != "none":
        h = apply_norm(cfg, p["ffn_norm"], x)
        if spec.ffn == "moe":
            h, _ = moe_mod.moe_ffn(cfg, p["ffn"], h)
        elif spec.ffn == "gelu":
            h = mlp_mod.gelu_mlp(p["ffn"], h)
        else:
            h = mlp_mod.swiglu(p["ffn"], h)
        if spec.post_norms:
            h = apply_norm(cfg, p["post_ffn_norm"], h)
        x = x + h
    return x, new_cache
