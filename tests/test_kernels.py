"""Pallas kernels vs pure-jnp oracles: shape/dtype/flag sweeps in
interpret mode (CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.kernels.ref import (
    decode_attention_ref,
    flash_attention_ref,
    paged_decode_attention_ref,
    ssd_scan_ref,
)

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,hq,hkv,s,d", [
    (2, 4, 2, 64, 32),
    (1, 4, 4, 96, 64),
    (2, 8, 1, 33, 16),     # ragged seq (padding path)
    (1, 2, 2, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(b, hq, hkv, s, d, dtype):
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d)).astype(dtype)
    out = flash_attention_pallas(q, k, v, scale=d ** -0.5, block_q=32, block_k=32,
                                 interpret=True)
    ref = flash_attention_ref(q, k, v, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window,cap,causal", [
    (None, None, True),
    (32, None, True),
    (None, 30.0, True),
    (16, 50.0, True),
    (None, None, False),
])
def test_flash_attention_flags(window, cap, causal):
    b, hq, hkv, s, d = 2, 4, 2, 80, 32
    ks = jax.random.split(jax.random.key(1), 3)
    q = jax.random.normal(ks[0], (b, hq, s, d))
    k = jax.random.normal(ks[1], (b, hkv, s, d))
    v = jax.random.normal(ks[2], (b, hkv, s, d))
    out = flash_attention_pallas(q, k, v, scale=0.2, causal=causal,
                                 window=window, logit_cap=cap,
                                 block_q=16, block_k=16, interpret=True)
    ref = flash_attention_ref(q, k, v, scale=0.2, causal=causal,
                              window=window, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,hq,hkv,c,d,pos", [
    (2, 4, 2, 64, 32, 5),
    (2, 4, 2, 64, 32, 63),
    (2, 4, 2, 64, 32, 200),   # wrapped ring
    (1, 8, 8, 100, 16, 99),
    (3, 6, 1, 48, 64, 20),
])
def test_decode_attention(b, hq, hkv, c, d, pos):
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, hkv, c, d))
    v = jax.random.normal(ks[2], (b, hkv, c, d))
    out = decode_attention_pallas(q, k, v, jnp.int32(pos), scale=d ** -0.5,
                                  block_k=32, interpret=True)
    ref = decode_attention_ref(q, k, v, jnp.int32(pos), scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------------------
# paged decode: page-table-driven kernel vs gather ref
# ---------------------------------------------------------------------------


def _paged_inputs(key, b, hq, hkv, d, page, n_pool):
    ks = jax.random.split(jax.random.key(key), 5)
    q = jax.random.normal(ks[0], (b, hq, d))
    kp = jax.random.normal(ks[1], (n_pool, b, hkv, page, d))
    vp = jax.random.normal(ks[2], (n_pool, b, hkv, page, d))
    kt = jax.random.normal(ks[3], (b, hkv, page, d))
    vt = jax.random.normal(ks[4], (b, hkv, page, d))
    return q, kp, vp, kt, vt


@pytest.mark.parametrize("hq,hkv", [(2, 2), (8, 2), (4, 1)])   # GQA groups
@pytest.mark.parametrize("cap", [None, 30.0])
def test_paged_decode_gqa_and_softcap(hq, hkv, cap):
    b, d, page = 2, 32, 8
    q, kp, vp, kt, vt = _paged_inputs(10, b, hq, hkv, d, page, 5)
    table = jnp.asarray([3, 0, 4], jnp.int32)     # scrambled, non-contiguous
    args = (q, kp, vp, table, kt, vt, jnp.int32(5))
    out = paged_decode_attention_pallas(*args, scale=d ** -0.5, logit_cap=cap,
                                        interpret=True)
    ref = paged_decode_attention_ref(*args, scale=d ** -0.5, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("table,tail_len", [
    ((0, 1, 2, 3, 4), 5),   # all pages, partial tail
    ((2, 4), 0),            # tail empty
    ((1, 3), 8),            # tail exactly full (just-flushed boundary)
    ((), 3),                # tail-only attention (no pages yet)
    ((), 1),                # single-token tail
])
def test_paged_decode_tail_boundaries(table, tail_len):
    """Ring-slot validity at the page boundary (ISSUE satellite): the
    fused kernel must reproduce the two-segment merged softmax when the
    tail is empty, partial, and exactly full."""
    b, hq, hkv, d, page = 2, 4, 2, 32, 8
    q, kp, vp, kt, vt = _paged_inputs(11, b, hq, hkv, d, page, 5)
    args = (q, kp, vp, jnp.asarray(table, jnp.int32), kt, vt,
            jnp.int32(tail_len))
    out = paged_decode_attention_pallas(*args, scale=d ** -0.5,
                                        interpret=True)
    ref = paged_decode_attention_ref(*args, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_decode_ref_is_bitwise_the_gather_path():
    """The lowering-free ref path IS the legacy gather/concat math — this
    identity is what makes codec-"none" fused serving token-identical."""
    from repro.offload.kvcache import _paged_attend
    b, hq, hkv, d, page = 2, 4, 2, 32, 8
    q, kp, vp, kt, vt = _paged_inputs(12, b, hq, hkv, d, page, 6)
    for table, tl in [((5, 1, 2), 4), ((0,), 0), ((), 7)]:
        t = jnp.asarray(table, jnp.int32)
        ref = paged_decode_attention_ref(q, kp, vp, t, kt, vt,
                                         jnp.int32(tl), scale=d ** -0.5)
        gather = _paged_attend(q, kp[t], vp[t], kt, vt, jnp.int32(tl),
                               scale=d ** -0.5)
        assert bool(jnp.all(ref == gather))


@pytest.mark.parametrize("pos", [63, 64, 65, 95, 96, 200])
def test_decode_attention_ring_wrap_mid_block(pos):
    """Ring wrap regression (ISSUE satellite): positions at, just past,
    and mid-way through block boundaries of the ring cache, where the
    validity mask wraps inside a kv block."""
    b, hq, hkv, c, d = 2, 4, 2, 64, 32
    ks = jax.random.split(jax.random.key(13), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, hkv, c, d))
    v = jax.random.normal(ks[2], (b, hkv, c, d))
    out = decode_attention_pallas(q, k, v, jnp.int32(pos), scale=d ** -0.5,
                                  block_k=32, interpret=True)
    ref = decode_attention_ref(q, k, v, jnp.int32(pos), scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_paged_decode_ops_wrapper_jits():
    b, hq, hkv, d, page = 1, 4, 2, 16, 8
    q, kp, vp, kt, vt = _paged_inputs(14, b, hq, hkv, d, page, 3)
    t = jnp.asarray([1, 2], jnp.int32)
    out = ops.paged_decode_attention(q, kp, vp, t, kt, vt, jnp.int32(2),
                                     scale=d ** -0.5)
    ref = paged_decode_attention_ref(q, kp, vp, t, kt, vt, jnp.int32(2),
                                     scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("b,s,h,p,n,chunk", [
    (2, 128, 4, 32, 16, 32),
    (1, 256, 2, 64, 32, 64),
    (2, 64, 8, 16, 8, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ssd_scan(b, s, h, p, n, chunk, dtype):
    ks = jax.random.split(jax.random.key(3), 4)
    x = jax.random.normal(ks[0], (b, s, h, p), dtype)
    a = -jnp.abs(jax.random.normal(ks[1], (b, s, h))) * 0.1
    bm = jax.random.normal(ks[2], (b, s, h, n)) * 0.3
    cm = jax.random.normal(ks[3], (b, s, h, n)) * 0.3
    y, st = ops.ssd_scan(x, a, bm, cm, chunk)
    yr, sr = ssd_scan_ref(x, a, bm, cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=3e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(sr), atol=3e-5, rtol=1e-4)


def test_model_level_pallas_equivalence():
    from repro.configs import REGISTRY
    from repro.models.model import build_model
    from repro.models.runtime import use_attention_impl

    for name in ("gemma2-9b", "mamba2-370m"):
        cfg = REGISTRY[name].reduced()
        m = build_model(cfg)
        params = m.init(jax.random.key(0))
        toks = jax.random.randint(jax.random.key(1), (2, 64), 0, cfg.vocab_size)
        l1, _ = m.forward(params, {"tokens": toks, "targets": toks})
        with use_attention_impl("pallas"):
            l2, _ = m.forward(params, {"tokens": toks, "targets": toks})
        assert float(jnp.max(jnp.abs(l1 - l2))) < 5e-5
