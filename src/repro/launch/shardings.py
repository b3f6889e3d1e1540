"""Logical sharding assignment for parameter / optimizer / cache pytrees.

Leaves are matched by their final key-path name and mapped to logical axis
tuples; ``sharding.rules.logical_spec`` resolves those against the active
mesh, dropping any axis that does not divide evenly (GQA kv=8 on a 16-way
model axis, 40 experts, batch=1, ...). Extra *leading* dimensions (the
stacked-layers axis from the segment scan) are padded with the "layers"
logical name (unsharded).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.sharding.rules import AxisRules, logical_spec

# final-path-key -> logical names for the *trailing* dims
PARAM_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    # embeddings / head
    "embed": ("vocab", "embed"),
    "lm_head": ("embed", "vocab"),
    # attention
    "wq": ("embed", "q_dim"),
    "wk": ("embed", "kv_dim"),
    "wv": ("embed", "kv_dim"),
    "wo": ("q_dim", "embed"),
    "xwq": ("embed", "q_dim"),
    "xwk": ("embed", "kv_dim"),
    "xwv": ("embed", "kv_dim"),
    "xwo": ("q_dim", "embed"),
    # MLA
    "wdq": ("embed", "lora"),
    "wuq": ("lora", "q_dim"),
    "wdkv": ("embed", "lora"),
    "wkr": ("embed", None),
    "wukv": ("lora", "q_dim"),
    "q_norm": (None,),
    "kv_norm": (None,),
    # dense mlp
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
    "w_in": ("embed", "mlp"),
    "w_out": ("mlp", "embed"),
    # router (E small — replicated)
    "router": ("embed", None),
    # mamba2
    "in_proj": ("embed", "ssm_inner"),
    "out_proj": ("ssm_inner", "embed"),
    "conv_w": (None, "ssm_inner"),
    "conv_b": ("ssm_inner",),
    "A_log": ("ssm_heads",),
    "D": ("ssm_heads",),
    "dt_bias": ("ssm_heads",),
    "gate_norm": ("ssm_inner",),
    # norms
    "scale": (None,),
    "bias": (None,),
}

# MoE expert tensors are 3-D (E, ·, ·): expert dim first
MOE_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "w_gate": ("experts", "embed", "expert_mlp"),
    "w_up": ("experts", "embed", "expert_mlp"),
    "w_down": ("experts", "expert_mlp", "embed"),
}

CACHE_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "k": ("cache_batch", "cache_seq", "cache_heads"),   # heads flat: Hkv·D
    "v": ("cache_batch", "cache_seq", "cache_heads"),
    "xk": ("cache_batch", "frames", "cache_heads", None),
    "xv": ("cache_batch", "frames", "cache_heads", None),
    "ckv": ("cache_batch", "cache_seq", None),
    "krope": ("cache_batch", "cache_seq", None),
    "conv": ("cache_batch", "ssm_inner", None),
    "ssm": ("cache_batch", "ssm_heads", None, None),
}

BATCH_LOGICAL: Dict[str, Tuple[Optional[str], ...]] = {
    "tokens": ("batch", None),
    "targets": ("batch", None),
    "token": ("batch", None),
    "enc_embeds": ("batch", None, None),
    "vision_embeds": ("batch", None, None),
    "vision_mask": ("batch", None),
    "positions": (None, "batch", None),
}


def _leaf_name(path) -> str:
    for p in reversed(path):
        k = getattr(p, "key", None)
        if isinstance(k, str):
            return k
    return ""


def _spec_for(path, leaf, table: Dict[str, Tuple[Optional[str], ...]],
              rules: AxisRules, mesh: Mesh) -> P:
    name = _leaf_name(path)
    keys = [str(getattr(p, "key", "")) for p in path]
    logical = None
    if name in MOE_LOGICAL and leaf.ndim - _lead(leaf, MOE_LOGICAL[name]) >= 0 \
            and "ffn" in keys and leaf.ndim >= 3:
        cand = MOE_LOGICAL[name]
        if leaf.ndim >= len(cand):
            logical = cand
    if logical is None:
        logical = table.get(name)
    if logical is None:
        return P()
    lead = leaf.ndim - len(logical)
    if lead < 0:
        return P()
    names = ("layers",) * lead + tuple(logical)
    return logical_spec(leaf.shape, names, rules, mesh)


def _lead(leaf, logical):
    return leaf.ndim - len(logical)


def param_shardings(params_spec: Any, mesh: Mesh, rules: AxisRules) -> Any:
    """NamedShardings for a params (or optimizer-moments) pytree spec."""
    def f(path, leaf):
        return NamedSharding(mesh, _spec_for(path, leaf, PARAM_LOGICAL, rules, mesh))
    return jax.tree_util.tree_map_with_path(f, params_spec)


def cache_shardings(cache_spec: Any, mesh: Mesh, rules: AxisRules) -> Any:
    def f(path, leaf):
        return NamedSharding(mesh, _spec_for(path, leaf, CACHE_LOGICAL, rules, mesh))
    return jax.tree_util.tree_map_with_path(f, cache_spec)


def batch_shardings(batch_spec: Any, mesh: Mesh, rules: AxisRules) -> Any:
    def f(path, leaf):
        return NamedSharding(mesh, _spec_for(path, leaf, BATCH_LOGICAL, rules, mesh))
    return jax.tree_util.tree_map_with_path(f, batch_spec)
