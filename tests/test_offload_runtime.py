"""JAX-native offload runtime: remat policies, optimizer-state offload,
paged KV cache, serving engine round trips — all must be numerically
equivalent to the resident baselines."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.data.pipeline import SyntheticTokens
from repro.models.model import build_model
from repro.offload.kvcache import PagedKVCache
from repro.offload.optstate import device_fetch_state, host_offload_state
from repro.pool import default_pool
from repro.pool.backend import is_host_resident
from repro.kernels.ref import decode_attention_ref
from repro.serving.engine import ServeEngine
from repro.training.step import TrainStepConfig, init_train_state, make_train_step


CFG = REGISTRY["phi3-mini-3.8b"].reduced()


def _train(remat, offload_opt, steps=8):
    m = build_model(CFG)
    ts = TrainStepConfig(remat=remat, offload_opt_state=offload_opt,
                         warmup=2, total_steps=steps, peak_lr=1e-3)
    params, opt = init_train_state(m, jax.random.key(0), ts=ts)
    step = make_train_step(m, ts)
    data = SyntheticTokens(CFG.vocab_size, seq_len=24, global_batch=4, noise=0.05)
    for i in range(steps):
        params, opt, metrics = step(params, opt, data.batch(i))
    return params, opt, float(metrics["loss"])


def test_offload_training_bitwise_matches_resident():
    p_res, _, l_res = _train("none", False)
    p_off, opt_off, l_off = _train("offload", True)
    assert l_res == pytest.approx(l_off, abs=1e-6)
    for a, b in zip(jax.tree.leaves(p_res), jax.tree.leaves(p_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # moments really live in host memory (probed kind; NumPy as last resort)
    assert all(is_host_resident(x) for x in jax.tree.leaves(opt_off.mu))


def test_full_remat_matches_no_remat():
    p1, _, l1 = _train("none", False)
    p2, _, l2 = _train("full", False)
    assert l1 == pytest.approx(l2, rel=1e-5)


def test_host_offload_round_trip_preserves_values():
    tree = {"a": jnp.arange(128.0).reshape(8, 16),
            "b": jnp.ones((4,), jnp.bfloat16)}
    parked = host_offload_state(tree)
    assert all(is_host_resident(x) for x in jax.tree.leaves(parked))
    back = device_fetch_state(parked)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_serving_offload_kv_equals_resident():
    m = build_model(CFG)
    params = m.init(jax.random.key(0))
    data = SyntheticTokens(CFG.vocab_size, seq_len=16, global_batch=4)
    prompt = {"tokens": data.batch(0)["tokens"]}
    res = ServeEngine(m, params, max_seq=32).generate(prompt, 8)
    pool = default_pool()
    off_engine = ServeEngine(m, params, max_seq=32, offload_kv=True,
                             pool=pool)
    off = off_engine.generate(prompt, 8)
    np.testing.assert_array_equal(np.asarray(res), np.asarray(off))
    assert off_engine.stats.cache_round_trips == 7
    # real traffic went through the pool manager
    pool = off_engine.pool_stats()
    assert pool["puts"] > 0 and pool["bytes_stored"] > 0
    assert pool["gets"] > 0 and pool["bytes_fetched"] > 0
    assert pool["transfer"]["issued"] > 0


# ---------------------------------------------------------------------------
# Paged KV cache
# ---------------------------------------------------------------------------


def test_paged_kvcache_all_pages_exact():
    """Selecting all pages must reproduce dense ring attention exactly."""
    b, hq, hkv, d, page = 2, 4, 2, 32, 8
    max_seq = 64
    cache = PagedKVCache.create(batch=b, max_seq=max_seq, page_size=page,
                                n_kv_heads=hkv, head_dim=d,
                                pool=default_pool())
    ks = jax.random.split(jax.random.key(0), 3)
    s0 = 29   # 3 full pages + tail of 5
    k_seq = jax.random.normal(ks[0], (b, s0, hkv, d))
    v_seq = jax.random.normal(ks[1], (b, s0, hkv, d))
    cache.prefill(k_seq, v_seq)
    assert cache.full_pages == 3 and cache.tail_len == 5

    q = jax.random.normal(ks[2], (b, hq, d))
    out = cache.attend(q, scale=d ** -0.5, top_k_pages=None)
    # dense oracle over a big ring buffer holding the same tokens
    kd = jnp.zeros((b, hkv, max_seq, d)).at[:, :, :s0].set(
        k_seq.transpose(0, 2, 1, 3))
    vd = jnp.zeros((b, hkv, max_seq, d)).at[:, :, :s0].set(
        v_seq.transpose(0, 2, 1, 3))
    ref = decode_attention_ref(q, kd, vd, jnp.int32(s0 - 1), scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    assert cache.flushes == 3


def test_paged_kvcache_prefill_takes_model_cache_layout():
    """A prompt in the model's cache layout (B, S, Hkv·D) lands in the
    same pages and tail as the same prompt split into heads."""
    b, hkv, d, page, s0 = 2, 2, 16, 8, 21
    ks = jax.random.split(jax.random.key(1), 2)
    k_seq = jax.random.normal(ks[0], (b, s0, hkv, d))
    v_seq = jax.random.normal(ks[1], (b, s0, hkv, d))
    caches = []
    for k, v in ((k_seq, v_seq), (k_seq.reshape(b, s0, hkv * d),
                                  v_seq.reshape(b, s0, hkv * d))):
        c = PagedKVCache.create(batch=b, max_seq=64, page_size=page,
                                n_kv_heads=hkv, head_dim=d,
                                pool=default_pool())
        c.prefill(k, v)
        caches.append(c)
    heads, flat = caches
    assert flat.full_pages == heads.full_pages == 2
    for a, z in ((heads.fetch_pages(range(2)), flat.fetch_pages(range(2))),
                 ((heads.k_tail, heads.v_tail), (flat.k_tail, flat.v_tail))):
        for x, y in zip(a, z):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_paged_kvcache_append_flush_and_sparse_selection():
    b, hq, hkv, d, page = 1, 2, 1, 16, 4
    cache = PagedKVCache.create(batch=b, max_seq=32, page_size=page,
                                n_kv_heads=hkv, head_dim=d,
                                pool=default_pool())
    ks = jax.random.split(jax.random.key(1), 64)
    for t in range(10):
        cache.append(jax.random.normal(ks[2 * t], (b, hkv, d)),
                     jax.random.normal(ks[2 * t + 1], (b, hkv, d)))
    assert cache.length == 10 and cache.full_pages == 2 and cache.tail_len == 2
    q = jax.random.normal(ks[-1], (b, hq, d))
    idx = cache.select_pages(q, top_k=1)
    assert len(idx) == 1 and 0 <= idx[0] < 2
    out = cache.attend(q, scale=d ** -0.5, top_k_pages=1)
    assert out.shape == (b, hq, d)
    assert not bool(jnp.isnan(out).any())
    assert cache.fetches >= 1
    # pool pages really live in the manager's host tier
    assert any(k is not None for k in cache.k_pool)
    assert all(cache.pool.tier_of(k) == "host" and cache.pool.is_host_resident(k)
               for k in cache.k_pool if k is not None)
    stats = cache.pool_stats()
    assert stats["bytes_stored"] > 0 and stats["bytes_fetched"] > 0


def _filled_cache(codec=None, device_pages=None, seed=0,
                  b=2, hq=4, hkv=2, d=32, page=8, s0=29):
    pool = default_pool(codec=codec, codec_below="host") if codec \
        else default_pool()
    cache = PagedKVCache.create(batch=b, max_seq=64, page_size=page,
                                n_kv_heads=hkv, head_dim=d, pool=pool,
                                device_pages=device_pages)
    ks = jax.random.split(jax.random.key(seed), 3)
    cache.prefill(jax.random.normal(ks[0], (b, s0, hkv, d)),
                  jax.random.normal(ks[1], (b, s0, hkv, d)))
    q = jax.random.normal(ks[2], (b, hq, d))
    return cache, q, d ** -0.5


def test_attend_fused_bitwise_matches_gather_and_caches_pages():
    """The fused decode path must be token-identical to the legacy
    gather/concat path (same decoded pages, same math), and the device
    page buffer must turn repeat visits into hits, not pool fetches."""
    cache, q, scale = _filled_cache()
    gather = cache.attend(q, scale=scale, top_k_pages=None)
    fused = cache.attend_fused(q, scale=scale)
    assert bool(jnp.all(fused == gather))
    assert cache.buffer_misses == 3 and cache.buffer_hits == 0
    fetches0 = cache.fetches
    again = cache.attend_fused(q, scale=scale)
    assert bool(jnp.all(again == gather))
    assert cache.buffer_hits == 3 and cache.fetches == fetches0
    # Pallas kernel variant: same pages, online-softmax numerics
    out = cache.attend_fused(q, scale=scale, use_kernel=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(gather),
                               atol=2e-5)


def test_attend_fused_restricted_budget_evicts_lru():
    """A device_pages budget below the page count still serves sparse
    selections (mixed pool/device residency) but refuses a selection
    wider than the buffer instead of silently truncating it."""
    cache, q, scale = _filled_cache(device_pages=2, seed=1)
    top2 = cache.attend_fused(q, scale=scale, top_k_pages=2)
    ref = cache.attend(q, scale=scale, top_k_pages=2)
    assert bool(jnp.all(top2 == ref))
    with pytest.raises(ValueError, match="smaller than one step's"):
        cache.attend_fused(q, scale=scale)   # 3 pages > 2 slots


def test_attend_fused_int8_codec_matches_gather_and_bounds_error():
    """Under an int8 pool codec both paths decode the same quantized
    pages — fused stays bitwise-identical to gather — and the result
    stays close to a full-precision run of the same tokens."""
    cache, q, scale = _filled_cache(codec="int8", seed=2)
    exact, q2, _ = _filled_cache(codec=None, seed=2)
    gather = cache.attend(q, scale=scale, top_k_pages=None)
    fused = cache.attend_fused(q, scale=scale)
    assert bool(jnp.all(fused == gather))
    oracle = exact.attend(q2, scale=scale, top_k_pages=None)
    assert float(jnp.max(jnp.abs(fused - oracle))) < 0.05
