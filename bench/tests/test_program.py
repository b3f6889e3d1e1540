"""``program.model_config`` against the contract in its docstring: every
number and equation of the program's ``ModelConfig`` comes from the
configuration file, a layer has experts where the file has
``num_local_experts``, and an equation that the program cannot run is
refused by the file key that states it. What the program can run is read
from its dataclasses' fields, so these tests hold as fields are added."""

from __future__ import annotations

import dataclasses

import pytest

import conftest
import program

#: a value other than the identity for each key of ``program.SCALARS``:
#: Granite 3.0's published ones
STATED = {"attention_multiplier": 0.015625, "embedding_multiplier": 12.0,
          "residual_multiplier": 0.22, "logits_scaling": 6.0}
#: the sizes each held file puts into the program's configuration
HELD = {
    "phi3-mini-3.8b": dict(
        d_model=3072, n_heads=32, n_kv_heads=32, head_dim=96, d_ff=8192,
        vocab_size=32064, norm_eps=1e-5, rope_theta=10000.0,
        tie_embeddings=False),
    "granite-moe-3b-a800m": dict(
        d_model=1536, n_heads=24, n_kv_heads=8, head_dim=64, d_ff=512,
        vocab_size=49155, norm_eps=1e-6, rope_theta=10000.0,
        tie_embeddings=True),
}


def _fields(cls):
    return {f.name for f in dataclasses.fields(cls)}


def _expected(name: str):
    """What ``model_config`` gave each held file before the equations
    moved into the file: the registry entry with the file's sizes, the
    plain decoder's equations, and the capacity rule for experts."""
    from repro.configs import REGISTRY
    from repro.configs.base import ModelConfig, MoEConfig
    base = REGISTRY[name]
    kw = dict(HELD[name], segments=(dataclasses.replace(
        base.segments[0], repeats=32),))
    kw.update({field: identity for _, field, identity in program.SCALARS
               if field in _fields(ModelConfig)})
    if base.moe is not None:
        moe = dict(n_experts=40, top_k=8, d_ff_expert=512,
                   capacity_factor=1.25)
        if "dropless" in _fields(MoEConfig):
            moe["dropless"] = False
        kw["moe"] = dataclasses.replace(base.moe, **moe)
    return dataclasses.replace(base, **kw)


@pytest.mark.parametrize("name", sorted(HELD))
def test_held_files_give_the_same_model_config(name):
    cfg = conftest.load_config(name)
    assert program.model_config(cfg) == _expected(name)
    # the identity values stated explicitly change nothing
    cfg.update({key: identity for key, _, identity in program.SCALARS})
    assert program.model_config(cfg) == _expected(name)


@pytest.mark.parametrize("key", [key for key, _, _ in program.SCALARS])
def test_stated_scalar_is_run_or_refused_by_its_key(key):
    from repro.configs.base import ModelConfig
    field = {k: f for k, f, _ in program.SCALARS}[key]
    cfg = dict(conftest.load_config("phi3-mini-3.8b"), **{key: STATED[key]})
    if field in _fields(ModelConfig):
        assert getattr(program.model_config(cfg), field) == STATED[key]
    else:
        with pytest.raises(ValueError, match=key):
            program.model_config(cfg)


def test_experts_without_capacity_factor_route_dropless_or_are_refused():
    from repro.configs.base import MoEConfig
    cfg = conftest.load_config("granite-moe-3b-a800m")
    del cfg["capacity_factor"]
    if "dropless" in _fields(MoEConfig):
        assert program.model_config(cfg).moe.dropless
    else:
        with pytest.raises(ValueError, match="capacity_factor"):
            program.model_config(cfg)


def test_experts_are_found_by_the_files_keys_not_its_reference():
    cfg = dict(conftest.load_config("granite-moe-3b-a800m"),
               reference="any_new_module")
    assert program.model_config(cfg) == _expected("granite-moe-3b-a800m")


def test_granite_as_published_is_refused_by_the_first_key_not_run():
    """Today that is ``embedding_multiplier``: ``attention_multiplier``
    sets ``query_scale``, which the program has."""
    from repro.configs.base import ModelConfig, MoEConfig
    cfg = conftest.granite_published()
    missing = [key for key, field, _ in program.SCALARS
               if field not in _fields(ModelConfig)]
    missing += ["capacity_factor"] * ("dropless" not in _fields(MoEConfig))
    if missing:
        with pytest.raises(ValueError, match=missing[0]):
            program.model_config(cfg)
    else:
        out = program.model_config(cfg)
        assert out.moe.dropless and out.moe.n_experts == 40
        for key, field, _ in program.SCALARS:
            assert getattr(out, field) == cfg[key]
