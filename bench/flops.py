"""Operations and bytes the served model needs, from a configuration
file's sizes. Kept with the benchmark so that the yardstick cannot move
with the program.

A token's forward pass at context length ``ctx`` (it attends ``ctx``
keys, itself included) costs two operations per weight it multiplies by,
plus ``4 * layers * heads * head_dim * ctx`` for the scores and the
weighted sum of values. A mixture-of-experts layer multiplies by the
router and by ``num_experts_per_tok`` experts: a layer has experts where
the file has ``num_local_experts``. The output head is counted
once per emitted token, since only those logits are needed.
"""

from __future__ import annotations

from typing import Dict


def _attn_weights(cfg: Dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 2 * d * hq * hd + 2 * d * hkv * hd


def _ffn_weights(cfg: Dict, active: bool) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    if "num_local_experts" in cfg:
        e = cfg["num_experts_per_tok"] if active else cfg["num_local_experts"]
        return d * cfg["num_local_experts"] + e * 3 * d * f
    return 3 * d * f


def token_flops(cfg: Dict, ctx: int) -> int:
    """One token through every decoder layer at context length ``ctx``."""
    layers = cfg["num_hidden_layers"]
    per_layer = 2 * (_attn_weights(cfg) + _ffn_weights(cfg, True))
    attend = 4 * cfg["num_attention_heads"] * cfg["head_dim"] * ctx
    return layers * (per_layer + attend)


def head_flops(cfg: Dict) -> int:
    """The output head for one token."""
    return 2 * cfg["hidden_size"] * cfg["vocab_size"]


def decode_weight_bytes(cfg: Dict) -> int:
    """The weights a decode step multiplies by, once: bf16 matrices and
    output head, float32 norms and router. An untied input embedding is
    only gathered by row, so its table is not counted."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    layers = cfg["num_hidden_layers"]
    mats = _attn_weights(cfg) + _ffn_weights(cfg, False)
    f32 = 2 * d
    if "num_local_experts" in cfg:
        mats -= d * cfg["num_local_experts"]
        f32 += d * cfg["num_local_experts"]
    return layers * (2 * mats + 4 * f32) + 2 * v * d + 4 * d


def kv_bytes_per_token(cfg: Dict) -> int:
    """bf16 keys and values of one token over every layer."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * cfg["head_dim"] * 2)
