"""Random weights of a configuration, drawn from ``--seed``.

The benchmark makes the weights, not the program, so that the reference
can draw the very same values again, layer by layer, without taking
anything the program made. Leaves are named here in the benchmark's own
layout; ``bench/program.py`` maps them onto the program's parameter tree.

Every leaf has its own key: ``fold_in(fold_in(seed_key, 1 + layer),
leaf_index)`` for a layer's leaves and ``fold_in(seed_key, 0)`` split
for the embedding, final norm and output head. ``stacked_weights`` draws
every layer in one jitted call with ``lax.map``, and ``layer_weights``
draws one layer alone; both give the same values (``bench/tests``).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

BF16, F32 = jnp.bfloat16, jnp.float32

#: leaf name -> (shape, dtype, initialiser, fan-in axis)
Spec = Dict[str, Tuple[Tuple[int, ...], object, str, int]]


def seed_key(seed: int) -> jax.Array:
    """A key from a seed of up to 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def layer_spec(cfg: Dict) -> Spec:
    """The leaves of one decoder layer, in drawing order: a feed-forward
    of experts where the file has ``num_local_experts``, else a dense
    one."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f = cfg["intermediate_size"]
    spec: Spec = {
        "attn_norm": ((d,), F32, "norm", 0),
        "wq": ((d, hq * hd), BF16, "fan_in", 0),
        "wk": ((d, hkv * hd), BF16, "fan_in", 0),
        "wv": ((d, hkv * hd), BF16, "fan_in", 0),
        "wo": ((hq * hd, d), BF16, "fan_in", 0),
        "ffn_norm": ((d,), F32, "norm", 0),
    }
    if "num_local_experts" in cfg:
        e = cfg["num_local_experts"]
        spec.update({
            "router": ((d, e), F32, "fan_in", 0),
            "w_gate": ((e, d, f), BF16, "fan_in", 1),
            "w_up": ((e, d, f), BF16, "fan_in", 1),
            "w_down": ((e, f, d), BF16, "fan_in", 1),
        })
    else:
        spec.update({
            "w_gate": ((d, f), BF16, "fan_in", 0),
            "w_up": ((d, f), BF16, "fan_in", 0),
            "w_down": ((f, d), BF16, "fan_in", 0),
        })
    return spec


def top_spec(cfg: Dict) -> Spec:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    spec: Spec = {"embed": ((v, d), BF16, "embed", 0),
                  "final_norm": ((d,), F32, "norm", 0)}
    if not cfg["tie_word_embeddings"]:
        spec["lm_head"] = ((d, v), BF16, "embed", 0)
    return spec


def _draw(key, shape, dtype, init: str, fan_axis: int) -> jax.Array:
    if init == "fan_in":
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape, F32)
        x = x * shape[fan_axis] ** -0.5
    elif init == "embed":
        x = 0.02 * jax.random.normal(key, shape, F32)
    elif init == "norm":
        x = jnp.clip(1.0 + 0.1 * jax.random.normal(key, shape, F32), 0.5, 1.5)
    else:
        raise ValueError(init)
    return x.astype(dtype)


def _draw_spec(spec: Spec, key) -> Dict[str, jax.Array]:
    return {name: _draw(jax.random.fold_in(key, i), *leaf)
            for i, (name, leaf) in enumerate(spec.items())}


def layer_weights(cfg: Dict, key, layer) -> Dict[str, jax.Array]:
    """Layer ``layer``'s leaves (``layer`` may be traced)."""
    return _draw_spec(layer_spec(cfg), jax.random.fold_in(key, 1 + layer))


def top_weights(cfg: Dict, key) -> Dict[str, jax.Array]:
    return _draw_spec(top_spec(cfg), jax.random.fold_in(key, 0))


def stacked_weights(cfg: Dict, key) -> Dict[str, object]:
    """Every leaf: ``{"top": {...}, "layers": {name: (L, ...)}}``. Traced
    inside one ``jit``; ``lax.map`` draws one layer at a time, so the
    float32 draws of a single layer are the largest transient."""
    layers = jax.lax.map(lambda l: layer_weights(cfg, key, l),
                         jnp.arange(cfg["num_hidden_layers"]))
    return {"top": top_weights(cfg, key), "layers": layers}
