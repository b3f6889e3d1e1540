"""Per-architecture smoke tests: REDUCED variant of each assigned arch —
one forward/train step on CPU, asserting output shapes and no NaNs, plus
prefill+decode consistency with the full forward, and per-row decode
against a whole-sequence prefill."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, REGISTRY
from repro.configs.base import Segment
from repro.data.pipeline import SyntheticTokens
from repro.models.model import build_model
from repro.training.step import TrainStepConfig, init_train_state, make_train_step


def make_batch(cfg, b=2, s=16, key=1):
    toks = jax.random.randint(jax.random.key(key), (b, s), 0, cfg.vocab_size)
    batch = {"tokens": toks, "targets": toks}
    if cfg.frontend == "audio":
        batch["enc_embeds"] = 0.1 * jax.random.normal(
            jax.random.key(2), (b, cfg.encoder.n_frames, cfg.d_model))
    if cfg.frontend == "vision":
        batch["vision_embeds"] = 0.1 * jax.random.normal(
            jax.random.key(2), (b, s, cfg.d_model))
        batch["vision_mask"] = jnp.zeros((b, s), bool).at[:, :4].set(True)
        batch["positions"] = jnp.broadcast_to(
            jnp.arange(s)[None, None, :], (3, b, s)).astype(jnp.int32)
    return batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_forward_shapes_no_nans(arch):
    cfg = REGISTRY[arch].reduced()
    # zamba2's irreducible hybrid pattern is 6 layers (5 mamba + 1 attn) + a
    # 1-layer epilogue segment — everything else reduces to ≤ 2 layers
    assert cfg.n_layers <= 7 and cfg.d_model <= 256
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    batch = make_batch(cfg)
    logits, aux = m.forward(params, batch)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_train_step(arch):
    cfg = REGISTRY[arch].reduced()
    m = build_model(cfg)
    ts = TrainStepConfig(warmup=1, total_steps=4, peak_lr=1e-3)
    params, opt = init_train_state(m, jax.random.key(0), ts=ts)
    step = make_train_step(m, ts)
    batch = make_batch(cfg)
    p0 = jax.tree.leaves(params)[0].copy()
    params, opt, metrics = step(params, opt, batch)
    assert not bool(jnp.isnan(metrics["loss"]))
    assert float(metrics["grad_norm"]) > 0
    # params actually changed
    p1 = jax.tree.leaves(params)[0]
    assert not bool(jnp.all(p0 == p1))


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_prefill_decode_matches_forward(arch):
    cfg = REGISTRY[arch].reduced()
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    b, s = 2, 12
    batch = make_batch(cfg, b, s)
    logits_full, _ = m.forward(params, batch)

    pre = {k: (v[:, :s - 1] if k in ("tokens", "vision_embeds", "vision_mask")
               else v) for k, v in batch.items() if k != "targets"}
    if "positions" in pre:
        pre["positions"] = batch["positions"][:, :, :s - 1]
    cache = m.init_cache(b, s)
    lg_pre, cache = m.prefill(params, pre, cache)
    assert jnp.max(jnp.abs(lg_pre[:, 0] - logits_full[:, s - 2])) < 1e-3

    lg_dec, cache = m.decode_step(params, cache,
                                  batch["tokens"][:, s - 1:s], jnp.int32(s - 1))
    assert jnp.max(jnp.abs(lg_dec[:, 0] - logits_full[:, s - 1])) < 1e-3


def _stacked_smoke(arch, n_kv_heads=None, window=None):
    """The arch's smoke config with every segment repeated twice, so the
    decode scan writes and reads a layer stack deeper than one layer;
    optionally fewer KV heads (GQA) or a short sliding window."""
    cfg = REGISTRY[arch].reduced()
    segments = tuple(
        Segment(pattern=tuple(
            dataclasses.replace(spec, window=window)
            if window is not None and spec.window is not None else spec
            for spec in seg.pattern), repeats=2)
        for seg in cfg.segments)
    return dataclasses.replace(
        cfg, segments=segments, n_kv_heads=n_kv_heads or cfg.n_kv_heads)


PER_ROW_CASES = {
    "mha": lambda: _stacked_smoke("phi3-mini-3.8b"),
    "gqa": lambda: _stacked_smoke("granite-moe-3b-a800m", n_kv_heads=2),
    # local layers keep a 5-slot ring, which every row wraps; logit softcap
    "window-softcap": lambda: _stacked_smoke("gemma2-9b", window=5),
    "ssm-hybrid": lambda: _stacked_smoke("zamba2-7b"),
}


@pytest.mark.parametrize("case", sorted(PER_ROW_CASES))
def test_per_row_decode_matches_prefill(case):
    """Two rows at different positions share one batched decode (per-row
    ``pos``, as the continuous scheduler calls it); each step's logits
    equal a whole-sequence prefill of that row up to the same position."""
    cfg = PER_ROW_CASES[case]()
    if case == "window-softcap":
        assert cfg.attn_logit_softcap is not None
    m = build_model(cfg)
    params = m.init(jax.random.key(0))
    max_seq, lens, steps = 16, (3, 9), 6
    toks = jax.random.randint(jax.random.key(3), (2, max(lens) + steps),
                              0, cfg.vocab_size)

    def prefill_row(r, n):
        return m.prefill(params, {"tokens": toks[r:r + 1, :n]},
                         m.init_cache(1, max_seq))

    cache = m.init_cache(2, max_seq)
    for r, n in enumerate(lens):
        _, row = prefill_row(r, n)
        cache = jax.tree.map(lambda big, one, r=r: big.at[:, r].set(one[:, 0]),
                             cache, row)
    pos = jnp.array(lens, jnp.int32)
    for _ in range(steps):
        tok = toks[jnp.arange(2), pos][:, None]
        lg, cache = m.decode_step(params, cache, tok, pos)
        for r in range(2):
            want, _ = prefill_row(r, int(pos[r]) + 1)
            assert jnp.max(jnp.abs(lg[r, 0] - want[0, 0])) < 1e-3, (r, pos)
        pos = pos + 1


def test_training_learns_synthetic_structure():
    """A real (small) model trained briefly on the synthetic Markov stream
    must beat the uniform-loss floor by a wide margin."""
    cfg = REGISTRY["phi3-mini-3.8b"].reduced()
    m = build_model(cfg)
    ts = TrainStepConfig(warmup=5, total_steps=60, peak_lr=2e-3)
    params, opt = init_train_state(m, jax.random.key(0), ts=ts)
    step = make_train_step(m, ts)
    data = SyntheticTokens(cfg.vocab_size, seq_len=32, global_batch=8, noise=0.05)
    first = last = None
    for i in range(60):
        params, opt, metrics = step(params, opt, data.batch(i))
        if i == 0:
            first = float(metrics["loss"])
        last = float(metrics["loss"])
    assert last < first - 1.0, (first, last)
