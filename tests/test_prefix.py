"""Cross-request prefix cache (`repro.prefix`): radix index semantics,
ref-counted/pinned pages vs pool eviction, tier-floor invalidation through
the evict listener, and end-to-end scheduler integration — prefix-hit
serving must stay token-identical to cold serving in both resident and
kv_offload modes while skipping the shared prompt tokens' prefill."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import HyperOffloadSession, OffloadConfig
from repro.api.config import PrefixCacheConfig
from repro.configs import REGISTRY
from repro.models.model import build_model
from repro.offload.kvcache import worst_case_page_bytes
from repro.pool import (
    DEVICE_TIER, HOST_TIER, MemoryPoolManager, TierState, TransferEngine,
    default_pool,
)
from repro.pool import backend as B
from repro.prefix import PrefixCacheManager, RadixPrefixIndex
from repro.sched import (
    ContinuousScheduler, Request, SchedulerConfig, poisson_trace,
)
from repro.serving.engine import ServeEngine

CFG = REGISTRY["phi3-mini-3.8b"].reduced()
MAX_SEQ = 32


@pytest.fixture(scope="module")
def model_and_params():
    m = build_model(CFG)
    return m, m.init(jax.random.key(0))


def _toks(*ids):
    return np.asarray(ids, np.int32)


# ---------------------------------------------------------------------------
# radix index
# ---------------------------------------------------------------------------


def test_radix_match_insert_remove():
    idx = RadixPrefixIndex(page_size=2)
    assert idx.match(_toks(1, 2, 3, 4)) == []

    chain, created = idx.insert(_toks(1, 2, 3, 4), 2)
    assert len(chain) == 2 and created == chain and len(idx) == 2
    assert [n.depth for n in chain] == [1, 2]

    # longest-prefix semantics at page granularity
    assert len(idx.match(_toks(1, 2, 3, 4, 9, 9))) == 2
    assert len(idx.match(_toks(1, 2, 9, 9))) == 1       # diverges at page 2
    assert idx.match(_toks(9, 9, 3, 4)) == []           # diverges at page 1
    assert len(idx.match(_toks(1, 2, 3))) == 1          # partial page ignored
    assert len(idx.match(_toks(1, 2, 3, 4), max_pages=1)) == 1

    # re-insert is idempotent; extending shares the existing chain
    chain2, created2 = idx.insert(_toks(1, 2, 3, 4, 5, 6), 3)
    assert created2 == chain2[2:] and chain2[:2] == chain
    assert len(idx) == 3

    # removing an interior node prunes the whole subtree
    removed = idx.remove(chain[1])
    assert {n.node_id for n in removed} == {n.node_id for n in chain2[1:]}
    assert len(idx) == 1 and len(idx.match(_toks(1, 2, 3, 4))) == 1

    with pytest.raises(ValueError):
        idx.insert(_toks(1, 2, 3), 2)    # no 2 full pages in 3 tokens


def test_radix_evictable_is_coldest_unrefd_leaves():
    idx = RadixPrefixIndex(page_size=1)
    a, _ = idx.insert(_toks(1, 2), 2)         # chain 1 -> 2
    b, _ = idx.insert(_toks(1, 7), 2)         # shares the root page
    idx.match(_toks(1, 2))                    # chain a is now hotter
    ev = idx.evictable()
    # only leaves qualify (the shared interior page would orphan both)
    assert [n.node_id for n in ev] == [b[1].node_id, a[1].node_id]
    b[1].refs = 1
    assert [n.node_id for n in idx.evictable()] == [a[1].node_id]


# ---------------------------------------------------------------------------
# manager: refs pin pages against eviction; pin_tier floor invalidates
# ---------------------------------------------------------------------------


def _page(kb: int, fill: float = 1.0) -> jax.Array:
    return jnp.full((kb * 256,), fill, jnp.float32)   # kb KiB


def _donate(mgr, tokens, n_pages, kb=256):
    return mgr.donate(np.asarray(tokens, np.int32), n_pages,
                      lambda p: {"L0.0": _page(kb, float(p))})


def test_donate_lookup_release_roundtrip():
    pool = default_pool()
    mgr = PrefixCacheManager(pool, page_size=2)
    assert _donate(mgr, [1, 2, 3, 4], 2, kb=1) == 2
    assert mgr.stats.donated_pages == 2 and len(mgr) == 2
    # re-donating the same prefix extracts nothing new
    assert _donate(mgr, [1, 2, 3, 4], 2, kb=1) == 0

    hit = mgr.lookup(_toks(1, 2, 3, 4, 9))
    assert hit is not None and hit.n_pages == 2 and hit.tokens == 4
    assert mgr.live_refs == 2
    np.testing.assert_array_equal(
        np.asarray(pool.get(hit.page_keys()[1]["L0.0"])),
        np.asarray(_page(1, 1.0)))
    # the match cap leaves at least one token to prefill
    short = mgr.lookup(_toks(1, 2, 3, 4), max_tokens=3)
    assert short is not None and short.n_pages == 1

    mgr.release(hit)
    mgr.release(hit)          # idempotent
    mgr.release(short)
    assert mgr.live_refs == 0 and mgr.stats.releases == 2
    assert mgr.lookup(_toks(5, 5, 5, 5)) is None
    assert mgr.stats.misses == 1

    mgr.close()
    mgr.close()               # idempotent
    assert len(pool.entries) == 0
    pool.close()


def test_eviction_skips_refd_pages_and_invalidates_once_on_final_release():
    """The satellite's pinning contract: a page with live refs is never a
    pool victim (two readers: releasing ONE keeps it pinned); after the
    FINAL release it becomes evictable, and the spill below the pin_tier
    floor fires the invalidation exactly once."""
    # device fits exactly one page; pin_tier="device" makes any spill an
    # invalidation
    pool = default_pool(device_capacity=256 * 1024)
    mgr = PrefixCacheManager(pool, page_size=2, pin_tier=DEVICE_TIER)
    assert _donate(mgr, [1, 2], 1) == 1
    key = next(iter(mgr.index.nodes.values())).entries["L0.0"]

    h1 = mgr.lookup(_toks(1, 2, 9))
    h2 = mgr.lookup(_toks(1, 2, 8))
    assert h1 is not None and h2 is not None and mgr.live_refs == 2

    # device pressure while ref'd: the pinned page is skipped — the
    # overflowing put fails rather than spilling it
    from repro.pool import PoolCapacityError
    with pytest.raises(PoolCapacityError):
        pool.put("pressure", _page(256), DEVICE_TIER, priority=99.0)
    assert pool.tier_of(key) == DEVICE_TIER

    mgr.release(h1)           # one of two readers: still pinned
    with pytest.raises(PoolCapacityError):
        pool.put("pressure", _page(256), DEVICE_TIER, priority=99.0)
    assert mgr.stats.invalidations == 0

    mgr.release(h2)           # FINAL release: unpinned, evictable
    pool.put("pressure", _page(256), DEVICE_TIER, priority=99.0)
    assert mgr.stats.invalidations == 1      # exactly once
    assert len(mgr) == 0
    assert mgr.lookup(_toks(1, 2, 9)) is None   # also flushes the drop
    assert key not in pool
    mgr.close()
    pool.close()


def test_pin_tier_floor_invalidates_whole_chain():
    """Default floor (host): host→remote spill of ONE page invalidates it
    AND every deeper page of its chain; device→host does not."""
    # device fits one page, host fits two: both donated pages can age down
    # to host (the floor) and remain valid
    pool = default_pool(device_capacity=256 * 1024, host_capacity=512 * 1024)
    mgr = PrefixCacheManager(pool, page_size=2, pin_tier=HOST_TIER)
    assert _donate(mgr, [1, 2, 3, 4], 2) == 2
    k1 = mgr.index.match(_toks(1, 2))[0].entries["L0.0"]

    # device→host spills — cold but still valid
    pool.put("p1", _page(256), DEVICE_TIER, priority=99.0)
    assert mgr.stats.invalidations == 0 and len(mgr) == 2
    assert pool.tier_of(k1) in (DEVICE_TIER, HOST_TIER)

    # host pressure pushes a page host→remote — below the floor: the owning
    # node and its descendant leave the index and the pool together
    pool.put("p2", _page(256), HOST_TIER, priority=99.0)
    assert mgr.stats.invalidations == 2
    assert len(mgr) == 0
    assert mgr.lookup(_toks(1, 2, 3, 4, 9)) is None   # flushes the drops
    assert k1 not in pool
    # the cascade left the tier accounting exact: only p1 + p2 remain
    assert pool.occupancy(DEVICE_TIER)[0] == 256 * 1024
    assert pool.occupancy(HOST_TIER)[0] == 256 * 1024
    assert pool.occupancy("remote")[0] == 0
    mgr.close()
    pool.close()


def test_max_pages_budget_evicts_coldest_leaf_first():
    pool = default_pool()
    mgr = PrefixCacheManager(pool, page_size=1, max_pages=2)
    assert _donate(mgr, [1], 1, kb=1) == 1
    assert _donate(mgr, [2], 1, kb=1) == 1
    mgr.release(mgr.lookup(_toks(1)))        # refresh: [2] is now coldest
    assert _donate(mgr, [3], 1, kb=1) == 1   # evicts [2]
    assert mgr.stats.evictions == 1 and len(mgr) == 2
    assert mgr.lookup(_toks(2)) is None

    # a budget full of ref'd pages rejects the donation instead
    ha = mgr.lookup(_toks(1))
    hb = mgr.lookup(_toks(3))
    assert _donate(mgr, [4], 1, kb=1) == 0
    assert mgr.stats.rejected_donations == 1 and len(mgr) == 2
    mgr.release(ha)
    mgr.release(hb)
    mgr.close()
    pool.close()


def test_manager_validation():
    pool = default_pool()
    with pytest.raises(ValueError, match="max_pages"):
        PrefixCacheManager(pool, page_size=2, max_pages=0)
    with pytest.raises(ValueError, match="min_match_pages"):
        PrefixCacheManager(pool, page_size=2, min_match_pages=0)
    with pytest.raises(ValueError, match="pin_tier"):
        PrefixCacheManager(pool, page_size=2, pin_tier="nvram")
    mgr = PrefixCacheManager(pool, page_size=2, min_match_pages=2)
    _donate(mgr, [1, 2], 1, kb=1)
    assert mgr.lookup(_toks(1, 2, 9)) is None    # 1 page < min_match_pages
    mgr.close()
    pool.close()


# ---------------------------------------------------------------------------
# scheduler integration: token identity + prefill savings
# ---------------------------------------------------------------------------


def _family_trace(n, prefix_len=12, seed=1):
    """Requests sharing one prompt prefix, arriving far enough apart that
    each retires (donates) before the next arrives."""
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, CFG.vocab_size, size=prefix_len, dtype=np.int32)
    reqs = []
    for i in range(n):
        sfx = rng.integers(0, CFG.vocab_size, size=int(rng.integers(3, 8)),
                           dtype=np.int32)
        reqs.append(Request(tokens=np.concatenate([pre, sfx]),
                            max_new_tokens=4, arrival=12.0 * i, seed=i))
    return reqs


def _reference(model, params, reqs):
    eng = ServeEngine(model, params, max_seq=MAX_SEQ)
    out = {r.req_id: np.asarray(
        eng.generate({"tokens": jnp.asarray(r.tokens[None, :])},
                     r.max_new_tokens, seed=r.seed))[0] for r in reqs}
    eng.close()
    return out


# NB: these scheduler tests use chunk_size=6 — test_sched's compile-count
# test asserts a jit-cache DELTA for its own chunk_size=8, and the chunk
# entry point is cached per model config, shared across test modules.


def test_prefix_hits_are_token_identical_resident(model_and_params):
    model, params = model_and_params
    reqs = _family_trace(3)
    pool = default_pool()
    mgr = PrefixCacheManager(pool, page_size=4)
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, chunk_size=6),
        pool=pool, prefix_cache=mgr)
    out = sched.run(reqs)
    assert sched.stats.prefix_hits == 2          # every request after the 1st
    assert sched.stats.prefix_hit_tokens == 2 * 12
    snap = mgr.snapshot()
    assert snap["hits"] == 2 and snap["donations"] >= 1
    assert snap["refs"] == 0                     # all released at retire
    # the cached tokens were never prefilled again
    total = sum(r.prompt_len for r in reqs)
    assert sched.stats.prefill_tokens == total - 2 * 12
    ref = _reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    sched.close()
    mgr.close()
    pool.close()


def test_prefix_hits_are_token_identical_kv_offload(model_and_params):
    """kv_offload under device pressure: prefix pages ride the pool tiers
    (and the PlanPrefetcher on fetch), shared pages survive the mid-prefill
    park/restore cycle, and outputs stay token-identical."""
    model, params = model_and_params
    reqs = _family_trace(3)
    row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ, jnp.float32))
    pool = default_pool(device_capacity=int(1.5 * row), host_capacity=6 * row,
                        transfer=TransferEngine(depth=64))
    mgr = PrefixCacheManager(pool, page_size=4)
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, kv_offload=True,
                        chunk_size=6),
        pool=pool, prefix_cache=mgr)
    out = sched.run(reqs)
    assert sched.stats.prefix_hits == 2
    assert sched.stats.pages_parked > 0          # park/restore really ran
    assert pool.snapshot()["evictions"] > 0      # tiering pressure was real
    ref = _reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    sched.close()
    mgr.close()
    pool.close()


def test_prefix_requires_chunked_prefill(model_and_params):
    model, params = model_and_params
    pool = default_pool()
    mgr = PrefixCacheManager(pool, page_size=4)
    with pytest.raises(ValueError, match="chunk"):
        ContinuousScheduler(model, params,
                            SchedulerConfig(max_batch=2, max_seq=MAX_SEQ),
                            pool=pool, prefix_cache=mgr)
    # kv_offload mode must share the scheduler's pool
    other = default_pool()
    with pytest.raises(ValueError, match="pool"):
        ContinuousScheduler(
            model, params,
            SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, kv_offload=True,
                            chunk_size=6),
            pool=other, prefix_cache=mgr)
    mgr.close()
    pool.close()
    other.close()


# ---------------------------------------------------------------------------
# front door: config block, session wiring, stats surface
# ---------------------------------------------------------------------------


def test_prefix_config_validation_and_roundtrip():
    cfg = OffloadConfig(
        chunk_size=6,
        prefix_cache=PrefixCacheConfig(enable=True, page_size=4,
                                       max_pages=64, min_match_pages=2,
                                       pin_tier="device"))
    assert OffloadConfig.from_dict(cfg.to_dict()) == cfg
    # the block survives a JSON round trip too
    import json
    assert OffloadConfig.from_dict(json.loads(json.dumps(cfg.to_dict()))) \
        == cfg

    with pytest.raises(ValueError, match="chunk_size"):
        OffloadConfig(prefix_cache=PrefixCacheConfig(enable=True))
    # both modes serve through the scheduler; a retired mode is refused
    for mode in ("paged", "continuous"):
        with pytest.raises(ValueError, match=f"'{mode}' not in"):
            OffloadConfig(mode=mode, chunk_size=6,
                          prefix_cache=PrefixCacheConfig(enable=True))
    # tier names are declarative (the topology's), so pin_tier validates
    # at the OffloadConfig level against the effective chain — but only
    # when the cache is actually enabled
    with pytest.raises(ValueError, match="pin_tier"):
        OffloadConfig(chunk_size=8,
                      prefix_cache=PrefixCacheConfig(enable=True,
                                                     pin_tier="nvram"))
    OffloadConfig(prefix_cache=PrefixCacheConfig(pin_tier="nvram"))
    with pytest.raises(ValueError, match="page_size"):
        PrefixCacheConfig(page_size=0)


def test_session_builds_and_surfaces_prefix_cache(model_and_params):
    model, params = model_and_params
    cfg = OffloadConfig(max_batch=2, max_seq=MAX_SEQ,
                        chunk_size=6,
                        prefix_cache=PrefixCacheConfig(enable=True,
                                                       page_size=4))
    reqs = _family_trace(3)
    with HyperOffloadSession(cfg) as session:
        assert session.prefix_cache is not None
        sched = session.scheduler(model, params)
        out = sched.run(reqs)
        stats = session.stats()
        assert stats["prefix"]["hits"] == 2
        assert stats["prefix"]["donated_pages"] >= 1
        assert stats["sched"]["prefix_hits"] == 2
        assert stats["sched"]["prefix_hit_tokens"] == 24
    ref = _reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])

    # disabled (default) sessions surface no prefix block
    with HyperOffloadSession(OffloadConfig()) as session:
        assert session.prefix_cache is None
        assert session.stats()["prefix"] is None


# ---------------------------------------------------------------------------
# shared-prefix traces
# ---------------------------------------------------------------------------


def test_poisson_trace_shared_prefix_mode():
    tr = poisson_trace(12, rate=1.0, vocab_size=97, prompt_lens=(4, 8),
                       prompt_quantum=4, n_prefix_families=2, prefix_len=16,
                       seed=5)
    heads = {t.tokens[:16].tobytes() for t in tr}
    assert len(heads) == 2                       # exactly the two families
    for t in tr:
        assert t.prompt_len in (16 + 4, 16 + 8)  # prefix + on-grid suffix

    # disabled mode leaves seeded traces byte-identical to the old RNG path
    a = poisson_trace(6, rate=1.0, vocab_size=97, seed=3)
    b = poisson_trace(6, rate=1.0, vocab_size=97, n_prefix_families=None,
                      prefix_len=0, seed=3)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.tokens, y.tokens)
        assert (x.arrival, x.max_new_tokens) == (y.arrival, y.max_new_tokens)

    with pytest.raises(ValueError, match="n_prefix_families"):
        poisson_trace(2, rate=1.0, vocab_size=97, n_prefix_families=0,
                      prefix_len=4)
    with pytest.raises(ValueError, match="prefix_len"):
        poisson_trace(2, rate=1.0, vocab_size=97, n_prefix_families=2)
