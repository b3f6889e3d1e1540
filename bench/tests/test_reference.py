"""The float32 references against the program run in float32 at a tiny
size, through chunked prefill and cached decode: the same equations give
the same logits. The MoE case drops tokens: capacity factor 1.25 over a
128-token chunk leaves 80 slots per expert against a mean load of 64.
Granite's published scalars and dropless routing, which the program does
not run yet, are held against a float64 NumPy forward written out here."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
import program
import weights as W
from reference import moe

#: the identity value of each scalar a configuration file may state
IDENTITY = {key: identity for key, _, identity in program.SCALARS}


def _reference(cfg):
    return importlib.import_module(f"reference.{cfg['reference']}")


@pytest.mark.parametrize("name", sorted(conftest.TINY_CONFIG))
def test_reference_matches_program_in_float32(name):
    from repro.models.model import build_model
    cfg = conftest.load_config(name)
    cfg.update(conftest.TINY_CONFIG[name])
    if "num_local_experts" in cfg:
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
    want, low = _reference(cfg).forward(
        cfg, seed, tokens, np.array([prompt_len], np.int32), at,
        chunk=chunk, control=True)
    scale = np.abs(want).max()
    assert np.abs(np.stack(got) - want[0]).max() < 1e-5 * max(scale, 1.0)
    # the control is another computation, not the same one
    assert np.abs(low - want).max() > 1e-2 * scale


def _tiny_routed():
    cfg = conftest.load_config("granite-moe-3b-a800m")
    cfg.update(conftest.TINY_CONFIG["granite-moe-3b-a800m"],
               **conftest.TINY_ROUTED)
    return cfg


def test_capacity_drops_happen_at_tiny_size():
    assert moe.capacity(_tiny_routed(), 128) == 80
    # full size: 32 slots per expert in a 128-token chunk, 8 in decode
    full = conftest.load_config("granite-moe-3b-a800m")
    assert moe.capacity(full, 128) == 32 and moe.capacity(full, 8) == 8


def _logits(cfg, seed=2 ** 31 + 11, n=2, s=256, control=False):
    """The reference's logits at every position of ``n`` prompts of ``s``
    tokens, prefilled in chunks of 128."""
    tokens = np.random.default_rng(3).integers(
        0, cfg["vocab_size"], (n, s)).astype(np.int32)
    at = np.tile(np.arange(s, dtype=np.int32), (n, 1))
    return _reference(cfg).forward(cfg, seed, tokens,
                                   np.full(n, s, np.int32), at, chunk=128,
                                   control=control)


@pytest.mark.parametrize("name", sorted(conftest.TINY_CONFIG))
def test_identity_scalars_stated_give_the_same_logits(name):
    """A file that states the plain decoder's scalars runs what one that
    leaves them out runs, bit for bit, in the reference and its control."""
    cfg = dict(conftest.load_config(name), **conftest.TINY_CONFIG[name])
    assert not set(IDENTITY) & set(cfg)
    plain = _logits(cfg, control=True)
    stated = _logits(dict(cfg, **IDENTITY), control=True)
    for a, b in zip(plain, stated):
        np.testing.assert_array_equal(a, b)


def test_dropless_is_the_capacity_rule_without_overflow():
    """Dropless routing is what the capacity rule gives where capacity is
    the whole chunk, and differs where an expert's 80 slots overflow."""
    capped = _tiny_routed()
    dropless = {k: v for k, v in capped.items() if k != "capacity_factor"}
    roomy = dict(capped, capacity_factor=2.0)
    assert moe.capacity(roomy, 128) == 128
    free = _logits(dropless, n=1)[0]
    np.testing.assert_array_equal(_logits(roomy, n=1)[0], free)
    gap = np.abs(_logits(capped, n=1)[0] - free).max()
    assert gap > 1e-3 * np.abs(free).max()


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _granite_numpy(cfg, seed, tokens):
    """Granite's published forward pass (transformers ``GraniteMoe``) in
    float64 on the benchmark's weights: embeddings times
    ``embedding_multiplier``, scores times ``attention_multiplier``, each
    sublayer added at ``residual_multiplier``, experts gated by the
    softmax over the top-k router logits with no capacity, and logits
    divided by ``logits_scaling``."""
    key = W.seed_key(seed)
    top = {k: np.asarray(v.astype(jnp.float32), np.float64)
           for k, v in W.top_weights(cfg, key).items()}
    draw = jax.jit(lambda k, l: W.layer_weights(cfg, k, l))
    n, s = tokens.shape
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    k_top, eps, r = (cfg["num_experts_per_tok"], cfg["rms_norm_eps"],
                     cfg["residual_multiplier"])

    def norm(x, w):
        return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * w

    def rope(x):
        half = hd // 2
        inv = cfg["rope_theta"] ** -(np.arange(half) / half)
        ang = np.arange(s)[:, None] * inv
        cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
        x1, x2 = x[..., :half], x[..., half:]
        return np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    causal = np.tril(np.ones((s, s), bool))
    x = top["embed"][tokens] * cfg["embedding_multiplier"]
    for layer in range(cfg["num_hidden_layers"]):
        w = {k: np.asarray(v.astype(jnp.float32), np.float64)
             for k, v in draw(key, layer).items()}
        h = norm(x, w["attn_norm"])
        q = rope((h @ w["wq"]).reshape(n, s, hq, hd))
        kk = np.repeat(rope((h @ w["wk"]).reshape(n, s, hkv, hd)),
                       hq // hkv, axis=2)
        v = np.repeat((h @ w["wv"]).reshape(n, s, hkv, hd), hq // hkv,
                      axis=2)
        sc = np.einsum("nqhd,nkhd->nhqk", q, kk) * cfg["attention_multiplier"]
        sc = np.where(causal, sc, -np.inf)
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("nhqk,nkhd->nqhd", p, v).reshape(n, s, hq * hd)
        x = x + r * (o @ w["wo"])
        h = norm(x, w["ffn_norm"])
        logits = h @ w["router"]
        experts = np.argsort(-logits, -1)[..., :k_top]
        picked = np.take_along_axis(logits, experts, -1)
        gates = np.exp(picked - picked.max(-1, keepdims=True))
        gates /= gates.sum(-1, keepdims=True)
        out = np.zeros_like(x)
        for j in range(k_top):
            e = experts[..., j]
            g = np.einsum("nsd,nsdf->nsf", h, w["w_gate"][e])
            u = np.einsum("nsd,nsdf->nsf", h, w["w_up"][e])
            out += gates[..., j, None] * np.einsum(
                "nsf,nsfd->nsd", _silu(g) * u, w["w_down"][e])
        x = x + r * out
    h = norm(x, top["final_norm"])
    return h @ top["embed"].T / cfg["logits_scaling"]


def test_granite_as_published_matches_float64_numpy():
    cfg = dict(conftest.granite_published(), **conftest.TINY_GRANITE)
    assert "capacity_factor" not in cfg and cfg["num_local_experts"] == 40
    seed = 2 ** 31 + 23
    tokens = np.random.default_rng(4).integers(
        0, cfg["vocab_size"], (2, 96)).astype(np.int32)
    at = np.tile(np.arange(96, dtype=np.int32), (2, 1))
    got, _ = moe.forward(cfg, seed, tokens, np.full(2, 96, np.int32), at,
                         chunk=128)
    want = _granite_numpy(cfg, seed, tokens)
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
