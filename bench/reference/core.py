"""Plain float32 decoder forward shared by the reference families.

It imports nothing of the program and takes nothing the program made: it
draws the weights again from the seed (``bench/weights.py``), one layer
at a time, and runs every sequence of a batch over its whole length in
float32 with ``HIGHEST`` matmul precision. Sequences are right-padded;
with causal attention a padding position never reaches a real one.

The configuration file states the decoder's scalars; a key it leaves out
takes the plain decoder's value. Their names and places are those of
IBM Granite's published forward pass (transformers ``GraniteMoe``,
``modeling_granitemoe.py``; keys of the checkpoints' ``config.json``):

- ``embedding_multiplier`` (1.0): token embeddings are multiplied by it;
- ``attention_multiplier`` (absent: ``head_dim ** -0.5``): attention
  scores are multiplied by it;
- ``residual_multiplier`` (1.0): each sublayer adds its output times it
  to the residual stream;
- ``logits_scaling`` (1.0): the output logits are divided by it.

``control=True`` also runs the control beside it: the same forward with
every matmul operand rounded to float8 e4m3 with a per-tensor scale, the
next precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def fp8(x: jax.Array) -> jax.Array:
    """Round to float8 e4m3 under one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq: str, a: jax.Array, b: jax.Array, low: bool) -> jax.Array:
    if low:
        a, b = fp8(a), fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rmsnorm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding, rotate-half convention; x is (n, S, H, D) at
    positions 0..S-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(cfg: Dict, w: Dict, h: jax.Array, low: bool) -> jax.Array:
    n, s, _ = h.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    q = mm("nsd,de->nse", h, w["wq"], low).reshape(n, s, hq, hd)
    k = mm("nsd,de->nse", h, w["wk"], low).reshape(n, s, hkv, hd)
    v = mm("nsd,de->nse", h, w["wv"], low).reshape(n, s, hkv, hd)
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    g = hq // hkv   # query head i reads key/value head i // g
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scale = cfg.get("attention_multiplier")
    if scale is None:
        scale = hd ** -0.5
    sc = mm("nqhd,nkhd->nhqk", q, k, low) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = mm("nhqk,nkhd->nqhd", p, v, low).reshape(n, s, hq * hd)
    return mm("nse,ed->nsd", o, w["wo"], low)


FFN = Callable[[Dict, Dict, jax.Array, jax.Array, int, bool], jax.Array]


def _layer(cfg: Dict, ffn: FFN, x: jax.Array, w: Dict,
           prompt_len: jax.Array, chunk: int, low: bool) -> jax.Array:
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    eps, r = cfg["rms_norm_eps"], cfg.get("residual_multiplier", 1.0)
    x = x + r * attention(cfg, w, rmsnorm(x, w["attn_norm"], eps), low)
    return x + r * ffn(cfg, w, rmsnorm(x, w["ffn_norm"], eps), prompt_len,
                       chunk, low)


def _logits(cfg: Dict, top: Dict, x: jax.Array, at: jax.Array,
            low: bool) -> jax.Array:
    top = jax.tree.map(lambda a: a.astype(jnp.float32), top)
    h = jnp.take_along_axis(x, at[..., None], axis=1)     # (n, m, d)
    h = rmsnorm(h, top["final_norm"], cfg["rms_norm_eps"])
    head = top["embed"].T if cfg["tie_word_embeddings"] else top["lm_head"]
    return mm("nmd,dv->nmv", h, head, low) / cfg.get("logits_scaling", 1.0)


def forward(cfg: Dict, ffn: FFN, seed: int, tokens: np.ndarray,
            prompt_len: np.ndarray, at: np.ndarray, *, chunk: int,
            control: bool = False) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Logits (n, m, V) at positions ``at`` (n, m) of each row of
    ``tokens`` (n, S), whose first ``prompt_len`` tokens were prefilled
    in chunks of ``chunk``; with ``control`` also the control's."""
    key = W.seed_key(seed)
    top = jax.jit(lambda k: W.top_weights(cfg, k))(key)
    layer_w = jax.jit(lambda k, l: W.layer_weights(cfg, k, l))
    step = jax.jit(
        lambda x, w, pl, low: _layer(cfg, ffn, x, w, pl, chunk, low),
        static_argnums=3)
    head = jax.jit(lambda t, x, a, low: _logits(cfg, t, x, a, low),
                   static_argnums=3)
    tokens, pl, at = jnp.asarray(tokens), jnp.asarray(prompt_len), \
        jnp.asarray(at)
    x = (top["embed"][tokens].astype(jnp.float32)
         * cfg.get("embedding_multiplier", 1.0))
    xs = [x, x] if control else [x]
    for layer in range(cfg["num_hidden_layers"]):
        w = layer_w(key, layer)
        xs = [step(xi, w, pl, low == 1) for low, xi in enumerate(xs)]
    out = [np.asarray(head(top, xi, at, low == 1))
           for low, xi in enumerate(xs)]
    return out[0], (out[1] if control else None)
