"""Attention unit tests (ring buffers, windows, softcap, M-RoPE) and MoE
dispatch properties."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stub

given, settings, st = hypothesis_or_stub()

from repro.configs import REGISTRY
from repro.configs.base import LayerSpec, MoEConfig, ModelConfig, Segment
from repro.models import attention as A
from repro.models import moe as M
from repro.models.common import apply_rope


# ---------------------------------------------------------------------------
# Ring buffer
# ---------------------------------------------------------------------------


def test_ring_valid_mask_prefix():
    m = A._ring_valid_mask(jnp.int32(3), 8)
    np.testing.assert_array_equal(np.asarray(m),
                                  [True] * 4 + [False] * 4)


def test_ring_valid_mask_wrapped():
    # pos=9, C=8: all slots live
    m = A._ring_valid_mask(jnp.int32(9), 8)
    assert bool(jnp.all(m))


@given(st.integers(0, 50), st.integers(2, 16))
@settings(max_examples=50, deadline=None)
def test_ring_mask_matches_bruteforce(pos, c):
    m = np.asarray(A._ring_valid_mask(jnp.int32(pos), c))
    expect = np.zeros(c, bool)
    for t in range(max(0, pos - c + 1), pos + 1):
        expect[t % c] = True
    np.testing.assert_array_equal(m, expect)


def test_ring_write_seq_wraps_correctly():
    buf = jnp.zeros((1, 4, 1, 1))
    vals = jnp.arange(10.0).reshape(1, 10, 1, 1)
    out = A._ring_write_seq(buf, vals)
    # token t at slot t % 4: tokens 6..9 survive
    got = np.asarray(out[0, :, 0, 0])
    np.testing.assert_array_equal(got, [8, 9, 6, 7])


def test_sliding_window_decode_equals_full_with_window_mask():
    """A windowed layer's ring cache must reproduce full attention restricted
    to the window."""
    cfg = REGISTRY["gemma2-9b"].reduced()
    spec_w = LayerSpec(mixer="attn", ffn="swiglu", window=6)
    p = A.init_attn_params(cfg, spec_w, jax.random.key(0), jnp.float32)
    b, s = 1, 16
    x = 0.3 * jax.random.normal(jax.random.key(1), (b, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    full = A.attention_full(cfg, spec_w, p, x, pos)  # masked full attention
    cache = A.init_attn_cache(cfg, spec_w, b, s, jnp.float32)
    assert cache["k"].shape[1] == 6  # ring capacity = window
    _, cache = A.attention_prefill(cfg, spec_w, p, x[:, : s - 1], pos[:, : s - 1], cache)
    stack = jax.tree.map(lambda v: v[None], cache)   # a one-layer stack
    out, _ = A.attention_decode(cfg, spec_w, p, x[:, s - 1 :], jnp.int32(s - 1),
                                pos[:, s - 1 :], stack, layer=0)
    np.testing.assert_allclose(np.asarray(out[:, 0]), np.asarray(full[:, -1]),
                               atol=2e-5)


def test_mrope_sections_differ_from_plain_rope():
    x = jax.random.normal(jax.random.key(0), (1, 4, 2, 16))
    pos2d = jnp.broadcast_to(jnp.arange(4)[None], (1, 4))
    pos3d = jnp.stack([pos2d, pos2d * 2, pos2d * 3])  # distinct planes
    plain = apply_rope(x, pos2d, 10000.0)
    mr = apply_rope(x, pos3d, 10000.0, mrope_sections=(2, 3, 3))
    assert not np.allclose(np.asarray(plain), np.asarray(mr))
    # equal planes reduce to plain rope
    mr_eq = apply_rope(x, jnp.stack([pos2d] * 3), 10000.0,
                       mrope_sections=(2, 3, 3))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(mr_eq), atol=1e-5)


# ---------------------------------------------------------------------------
# MoE dispatch
# ---------------------------------------------------------------------------


def moe_cfg(e=4, k=2, cf=2.0):
    return ModelConfig(
        name="t", family="moe", citation="x", d_model=32, n_heads=2,
        n_kv_heads=2, head_dim=16, d_ff=64, vocab_size=64,
        segments=(Segment(pattern=(LayerSpec(mixer="attn", ffn="moe"),), repeats=1),),
        moe=MoEConfig(n_experts=e, top_k=k, d_ff_expert=32, capacity_factor=cf),
    )


def test_moe_lossless_capacity_weight_sum():
    """With capacity ≥ N no tokens drop: output = weighted expert mix, and
    permutation of tokens permutes outputs (no cross-token leakage)."""
    cfg = moe_cfg(cf=4.0)
    p = M.init_moe_params(cfg, jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 8, 32))
    out, aux = M.moe_ffn(cfg, p, x)
    assert out.shape == x.shape and float(aux) > 0
    perm = jnp.array([3, 1, 0, 2, 7, 5, 6, 4])
    out_p, _ = M.moe_ffn(cfg, p, x[:, perm])
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out[:, perm]),
                               atol=1e-5)


def test_moe_capacity_drops_some_tokens():
    cfg = moe_cfg(cf=0.3)
    p = M.init_moe_params(cfg, jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32))
    out, _ = M.moe_ffn(cfg, p, x)
    # dropped tokens produce exactly zero output rows
    norms = jnp.linalg.norm(out.reshape(-1, 32), axis=-1)
    assert bool(jnp.any(norms == 0.0))
    assert bool(jnp.any(norms > 0.0))


def test_moe_grads_flow_to_all_param_groups():
    cfg = moe_cfg(cf=4.0)
    p = M.init_moe_params(cfg, jax.random.key(0), jnp.float32)
    x = jax.random.normal(jax.random.key(1), (1, 8, 32))

    def loss(p):
        out, aux = M.moe_ffn(cfg, p, x)
        return jnp.sum(out ** 2) + aux

    g = jax.grad(loss)(p)
    for name, leaf in g.items():
        assert float(jnp.max(jnp.abs(leaf))) > 0, f"zero grad for {name}"
