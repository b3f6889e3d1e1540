"""Shared building blocks: norms, rotary embeddings (RoPE / M-RoPE), init."""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis: int = 0):
    """Truncated-normal fan-in init (MaxText-style)."""
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32).astype(dtype)


def embed_init(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype) * 0.02


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    # gemma-style (1 + scale) parameterisation keeps zero-init neutral
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dtype)


def layernorm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32)) + bias.astype(jnp.float32)).astype(dtype)


def norm_params(cfg: ModelConfig, key) -> dict:
    if cfg.norm == "layernorm":
        return {
            "scale": jnp.zeros((cfg.d_model,), jnp.float32),
            "bias": jnp.zeros((cfg.d_model,), jnp.float32),
        }
    return {"scale": jnp.zeros((cfg.d_model,), jnp.float32)}


def apply_norm(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies for rotary embedding, shape (head_dim // 2,)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def _rope_angles(
    positions: jax.Array,
    inv: jax.Array,
    mrope_sections: Optional[Tuple[int, int, int]],
) -> jax.Array:
    """Rotation angles (B, S, half) of ``positions`` (B, S), or of M-RoPE's
    three planes (3, B, S) [arXiv:2409.12191], where ``mrope_sections``
    partitions the half frequency channels among the planes."""
    half = inv.shape[0]
    if mrope_sections is not None:
        assert positions.ndim == 3 and positions.shape[0] == 3, positions.shape
        assert sum(mrope_sections) == half, (mrope_sections, half)
        # per-channel section id -> select the matching position plane
        sec_id = jnp.repeat(
            jnp.arange(3), jnp.array(mrope_sections), total_repeat_length=half
        )  # (half,)
        sec_onehot = jax.nn.one_hot(sec_id, 3, dtype=jnp.float32)  # (half, 3)
        pos = positions.astype(jnp.float32)  # (3, B, S)
        ang_all = pos[..., None] * inv[None, None, None, :]  # (3, B, S, half)
        return jnp.einsum("pbsh,hp->bsh", ang_all, sec_onehot)  # (B, S, half)
    pos = positions.astype(jnp.float32)  # (B, S)
    return pos[..., None] * inv[None, None, :]  # (B, S, half)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    mrope_sections: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """Rotate ``x`` of shape (..., S, H, D) by ``positions``.

    positions: (B, S) for standard RoPE, or (3, B, S) for M-RoPE
    [arXiv:2409.12191] where the three planes carry temporal/height/width
    coordinates and ``mrope_sections`` partitions the D//2 frequency channels.
    """
    d = x.shape[-1]
    half = d // 2
    ang = _rope_angles(positions, rope_freqs(d, theta), mrope_sections)
    sin = jnp.sin(ang)[..., None, :]  # (B, S, 1, half)
    cos = jnp.cos(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def apply_rope_flat(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    head_dim: int,
    mrope_sections: Optional[Tuple[int, int, int]] = None,
) -> jax.Array:
    """``apply_rope`` on heads laid side by side: ``x`` is (B, S, H·D) and
    each ``head_dim``-wide block is rotated as ``apply_rope`` rotates a
    head. ``x`` is never reshaped into heads, so the projection that made
    it is read in its stored layout, with no transposed copy of its
    weight."""
    half = head_dim // 2
    heads = x.shape[-1] // head_dim
    ang = _rope_angles(positions, rope_freqs(head_dim, theta), mrope_sections)
    cos = jnp.tile(jnp.cos(ang), 2 * heads)             # (B, S, H·D)
    sin = jnp.tile(jnp.sin(ang), 2 * heads)
    low = (jnp.arange(x.shape[-1]) % head_dim) < half   # x1 of each head
    # rotate-half inside each head: x1 -> -x2, x2 -> x1
    partner = jnp.where(low, jnp.roll(x, -half, axis=-1),
                        jnp.roll(x, half, axis=-1))
    out = x * cos + partner * jnp.where(low, -sin, sin)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)
