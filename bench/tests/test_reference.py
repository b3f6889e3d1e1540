"""The float32 references against the program run in float32 at a tiny
size, through chunked prefill and cached decode: the same equations give
the same logits. The MoE case drops tokens: capacity factor 1.25 over a
128-token chunk leaves 80 slots per expert against a mean load of 64."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
import program
import weights as W
from reference import dense, moe

REFS = {"dense": dense, "moe": moe}


@pytest.mark.parametrize("name", sorted(conftest.TINY_CONFIG))
def test_reference_matches_program_in_float32(name):
    from repro.models.model import build_model
    cfg = json.loads((conftest.BENCH / "configs" / f"{name}.json").read_text())
    cfg.update(conftest.TINY_CONFIG[name])
    if cfg["reference"] == "moe":
        cfg.update(conftest.TINY_ROUTED)
    seed, prompt_len, chunk, steps = 2 ** 31 + 5, 256, 128, 6
    model = build_model(program.model_config(cfg))
    # drawn under jit, as ``program.build`` draws them
    drawn = jax.jit(lambda k: program._to_program(
        cfg, W.stacked_weights(cfg, k)))(W.seed_key(seed))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), drawn)
    prompt = np.random.default_rng(0).integers(
        0, cfg["vocab_size"], prompt_len).astype(np.int32)
    cache = model.init_cache(1, 384, jnp.float32)
    for s in range(0, prompt_len, chunk):
        logits, cache = model.prefill_chunk(
            params, {"tokens": jnp.asarray(prompt[None, s:s + chunk])},
            jnp.int32(s), jnp.int32(chunk), cache)
    got = [np.asarray(logits[0, -1])]
    toks = [int(got[-1].argmax())]
    for pos in range(prompt_len, prompt_len + steps):
        logits, cache = model.decode_step(
            params, cache, jnp.asarray([[toks[-1]]], jnp.int32),
            jnp.asarray([pos], jnp.int32))
        got.append(np.asarray(logits[0, 0]))
        toks.append(int(got[-1].argmax()))
    seq = np.concatenate([prompt, toks[:-1]])
    tokens = np.zeros((1, 384), np.int32)
    tokens[0, :len(seq)] = seq
    at = np.arange(prompt_len - 1, prompt_len + steps)[None].astype(np.int32)
    want, low = REFS[cfg["reference"]].forward(
        cfg, seed, tokens, np.array([prompt_len], np.int32), at,
        chunk=chunk, control=True)
    scale = np.abs(want).max()
    assert np.abs(np.stack(got) - want[0]).max() < 1e-5 * max(scale, 1.0)
    # the control is another computation, not the same one
    assert np.abs(low - want).max() > 1e-2 * scale


def test_capacity_drops_happen_at_tiny_size():
    cfg = json.loads((conftest.BENCH / "configs"
                      / "granite-moe-3b-a800m.json").read_text())
    cfg.update(conftest.TINY_CONFIG["granite-moe-3b-a800m"],
               **conftest.TINY_ROUTED)
    assert moe.capacity(cfg, 128) == 80
    # full size: 32 slots per expert in a 128-token chunk, 8 in decode
    full = json.loads((conftest.BENCH / "configs"
                       / "granite-moe-3b-a800m.json").read_text())
    assert moe.capacity(full, 128) == 32 and moe.capacity(full, 8) == 8
