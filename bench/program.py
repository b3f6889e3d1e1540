"""The system under test, as the benchmark drives it.

Everything the benchmark knows of the program's interface is here: how a
configuration file becomes the program's ``ModelConfig``, how the
benchmark's weights (``bench/weights.py``) become its parameter tree, and
how a workload's serving settings become a ``HyperOffloadSession`` and
its ``ContinuousScheduler``, built through ``repro.launch.serve``'s
``offload_config`` as the program's launcher does.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

import weights as W


#: The equations a configuration file states beyond its sizes: file key,
#: the ``ModelConfig`` field it sets, and the value at which the equation
#: is the plain decoder's. In the order ``model_config`` reads them.
SCALARS = (
    ("attention_multiplier", "query_scale", None),
    ("embedding_multiplier", "embedding_multiplier", 1.0),
    ("residual_multiplier", "residual_multiplier", 1.0),
    ("logits_scaling", "logits_scaling", 1.0),
)


def _put(kw: Dict, cls, key: str, field: str, value, identity) -> None:
    """Set ``field`` of ``cls`` to the file's ``value``; where ``cls`` has
    no such field, only the identity can be run."""
    if field in {f.name for f in dataclasses.fields(cls)}:
        kw[field] = value
    elif value != identity:
        raise ValueError(f"{key}: the file asks for {field} = {value!r}, "
                         f"and the program's {cls.__name__} has no such "
                         "field")


def model_config(cfg: Dict):
    """The program's ``ModelConfig`` for the configuration file ``cfg``:
    the registry entry's structure (layer kinds, norm and position
    encoding) with every number and equation of the file put in, so that
    the file holds the configuration as it is run. A layer has experts
    when the file has ``num_local_experts``.

    The file's equations, and the program fields they set; a key the
    file leaves out takes its identity value:

    ========================  ==================================  =========
    file key                  program field                       identity
    ========================  ==================================  =========
    attention_multiplier      ModelConfig.query_scale             None
    embedding_multiplier      ModelConfig.embedding_multiplier    1.0
    residual_multiplier       ModelConfig.residual_multiplier     1.0
    logits_scaling            ModelConfig.logits_scaling          1.0
    capacity_factor absent    MoEConfig.dropless = True           stated
    ========================  ==================================  =========

    Logits are divided by ``logits_scaling``. A value other than the
    identity whose field the program's dataclass lacks raises
    ``ValueError`` naming the file key; so does a registry entry whose
    norm, positions, softcaps, embedding scale or vocabulary padding the
    reference does not model."""
    from repro.configs import REGISTRY
    from repro.configs.base import ModelConfig, MoEConfig
    base = REGISTRY[cfg["registry"]]
    layers = cfg["num_hidden_layers"]
    pattern = base.segments[0].pattern
    if len(base.segments) != 1 or len(pattern) != 1:
        raise ValueError(f"{cfg['registry']}: expected one uniform segment")
    seg = dataclasses.replace(base.segments[0], repeats=layers)
    kw = dict(
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        segments=(seg,), norm_eps=cfg["rms_norm_eps"],
        rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"])
    for key, field, identity in SCALARS:
        _put(kw, ModelConfig, key, field, cfg.get(key, identity), identity)
    if "num_local_experts" in cfg:
        moe = dict(n_experts=cfg["num_local_experts"],
                   top_k=cfg["num_experts_per_tok"],
                   d_ff_expert=cfg["intermediate_size"])
        if "capacity_factor" in cfg:
            moe["capacity_factor"] = cfg["capacity_factor"]
        _put(moe, MoEConfig, "capacity_factor", "dropless",
             "capacity_factor" not in cfg, False)
        kw["moe"] = dataclasses.replace(base.moe, **moe)
    out = dataclasses.replace(base, **kw)
    if (out.norm, out.rope_mode, out.attn_logit_softcap,
            out.final_logit_softcap, out.scale_embeddings,
            out.vocab_pad_multiple) != ("rmsnorm", "rope", None, None,
                                        False, 1):
        raise ValueError(f"{cfg['registry']}: the reference does not "
                         "model this configuration's norm, positions, "
                         "softcaps, embedding scale or vocabulary padding")
    return out


def _to_program(cfg: Dict, w: Dict) -> Dict:
    """Map the benchmark's leaves onto the program's parameter tree. The
    program's RMSNorm multiplies by ``1 + scale``; ``w - 1`` is exact in
    float32 for weights in [0.5, 1.5]."""
    top, lay = w["top"], w["layers"]
    ffn = ({"router": lay["router"], "w_gate": lay["w_gate"],
            "w_up": lay["w_up"], "w_down": lay["w_down"]}
           if "num_local_experts" in cfg else
           {"w_gate": lay["w_gate"], "w_up": lay["w_up"],
            "w_down": lay["w_down"]})
    layer = {"pre_norm": {"scale": lay["attn_norm"] - 1.0},
             "mixer": {k: lay[k] for k in ("wq", "wk", "wv", "wo")},
             "ffn_norm": {"scale": lay["ffn_norm"] - 1.0},
             "ffn": ffn}
    params = {"embed": top["embed"],
              "final_norm": {"scale": top["final_norm"] - 1.0},
              "segments": [{"p0": layer}]}
    if "lm_head" in top:
        params["lm_head"] = top["lm_head"]
    return params


def build(cfg: Dict, seed: int) -> Tuple[Any, Dict]:
    """(model, params): the program's model for ``cfg`` and its weights,
    drawn on the device in one jitted call from ``seed``."""
    from repro.models.model import build_model
    model = build_model(model_config(cfg))
    params = jax.jit(lambda k: _to_program(cfg, W.stacked_weights(cfg, k)))(
        W.seed_key(seed))
    want = model.param_specs(jnp.bfloat16)
    got_shapes = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    want_shapes = jax.tree.map(lambda x: (x.shape, x.dtype), want)
    if got_shapes != want_shapes:
        raise ValueError("the benchmark's weights do not match the "
                         f"program's parameter tree: {got_shapes} vs "
                         f"{want_shapes}")
    return model, params


def tier_rows(serving: Dict) -> Tuple[int, int]:
    """Device and host tier rows that ``offload_config`` gives a batch."""
    b = serving["max_batch"]
    return max(1, b // 2), 2 * b


def session(model, serving: Dict, *, telemetry: bool, ring: int):
    """A session for the workload's ``serving`` settings."""
    from repro.api import HyperOffloadSession
    from repro.api.config import TelemetryConfig
    from repro.launch.serve import offload_config
    if serving["mode"] == "kv_offload" and tuple(
            serving["tier_rows"]) != tier_rows(serving):
        raise ValueError(f"tier_rows {serving['tier_rows']} are not what "
                         f"offload_config gives: {tier_rows(serving)}")
    extra = {"prefill_tokens": serving["prefill_tokens"]} \
        if serving.get("prefill_tokens") else {}
    return HyperOffloadSession(offload_config(
        model, mode=serving["mode"], max_batch=serving["max_batch"],
        max_seq=serving["max_seq"], cache_dtype="bfloat16",
        chunk_size=serving["chunk_size"],
        telemetry=TelemetryConfig(enable=telemetry, ring_capacity=ring),
        **extra))


def request(item, arrival: float):
    """The program's ``Request`` for a traffic item, greedy."""
    from repro.sched import Request
    return Request(req_id=item.index, tokens=item.prompt,
                   max_new_tokens=item.max_new_tokens, arrival=arrival)
