"""Runtime memory-pool subsystem: capacity accounting, eviction order,
transfer-engine overlap semantics, backend fallback, and executed-residency
agreement with the compiler's memory simulator."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stub, small_graph
from repro.core import memsim
from repro.core.costmodel import TPU_V5E
from repro.core.jax_exec import run_baseline
from repro.core.planner import HyperOffloadPlanner
from repro.pool import (
    MemoryPoolManager, OffloadPlanExecutor, PoolCapacityError, TierSpec,
    TierState, TierTopology, TransferEngine, default_pool, sweep_topologies,
)
from repro.pool import backend as B

given, settings, st = hypothesis_or_stub()


def _arr(kb: int, fill: float = 1.0) -> jax.Array:
    return jnp.full((kb * 256,), fill, jnp.float32)   # kb KiB


# ---------------------------------------------------------------------------
# backend probing + fallback
# ---------------------------------------------------------------------------


def test_backend_probe_and_host_roundtrip():
    caps = B.capabilities()
    # the probed host kind must actually be addressable (or None → NumPy)
    if caps.host_kind is not None:
        assert caps.host_kind in caps.memory_kinds
    be = B.make_host_backend()
    x = jnp.arange(512.0)
    h = be.put(x)
    assert be.holds(h)
    np.testing.assert_array_equal(np.asarray(be.get(h)), np.asarray(x))


def test_numpy_backend_is_always_available():
    be = B.NumpyHostBackend()
    x = jnp.arange(64.0).reshape(8, 8)
    h = be.put(x)
    assert isinstance(h, np.ndarray) and be.holds(h)
    y = be.get(h)
    assert isinstance(y, jax.Array)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_host_tier_without_host_kind(monkeypatch, platform):
    """Only a CPU build that addresses no host memory kind may fall back
    to NumPy host buffers; an accelerator raises instead of hiding the
    host tier off its host memory."""
    caps = B.Capabilities(platform=platform, memory_kinds=("device",),
                          default_kind="device", host_kind=None)
    monkeypatch.setattr(B, "capabilities", lambda device=None: caps)
    if platform == "cpu":
        assert isinstance(B.make_host_backend(), B.NumpyHostBackend)
    else:
        with pytest.raises(RuntimeError, match="no host memory kind"):
            B.make_host_backend()


def test_to_host_to_device_helpers():
    x = jnp.ones((4, 4), jnp.bfloat16)
    parked = B.to_host(x)
    assert B.is_host_resident(parked)
    back = B.to_device(parked)
    np.testing.assert_array_equal(np.asarray(back, np.float32),
                                  np.asarray(x, np.float32))


# ---------------------------------------------------------------------------
# manager: capacity accounting + eviction
# ---------------------------------------------------------------------------


def test_capacity_accounting_and_drop():
    p = default_pool(host_capacity=1 << 20)
    p.put("a", _arr(64))
    p.put("b", _arr(128))
    used, cap = p.occupancy("host")
    assert used == (64 + 128) * 1024 and cap == 1 << 20
    assert p.snapshot()["bytes_stored"] == used
    p.drop("a")
    assert p.occupancy("host")[0] == 128 * 1024
    assert "a" not in p and "b" in p
    with pytest.raises(KeyError):
        p.get("a")


def test_eviction_spills_lru_lowest_priority_first():
    # host holds exactly 2 × 256 KiB pages; third put must spill one
    p = default_pool(host_capacity=2 * 256 * 1024)
    p.put("old", _arr(256, 1.0))
    p.put("new", _arr(256, 2.0))
    p.get("old")                       # "old" is now more recently used
    p.put("third", _arr(256, 3.0))
    # LRU victim is "new"; it spilled down to the remote tier, not vanished
    assert p.tier_of("new") == "remote" and p.tier_of("old") == "host"
    assert p.tier_of("third") == "host"
    np.testing.assert_array_equal(np.asarray(p.get("new")),
                                  np.asarray(_arr(256, 2.0)))
    assert p.snapshot()["evictions"] == 1

    # planner-priority hints beat recency: low-priority entries go first
    p2 = default_pool(host_capacity=2 * 256 * 1024)
    p2.put("cheap", _arr(256), priority=0.0)
    p2.put("precious", _arr(256), priority=10.0)
    p2.get("cheap")                    # recency would protect "cheap"...
    p2.put("x", _arr(256))
    assert p2.tier_of("cheap") == "remote"      # ...but priority wins
    assert p2.tier_of("precious") == "host"


def test_set_priority_reranks_eviction():
    """`set_priority` re-ranks an existing entry for eviction in place —
    no data movement, no recency bump — and ignores unknown keys."""
    p = default_pool(host_capacity=2 * 256 * 1024)
    p.put("a", _arr(256, 1.0), priority=5.0)
    p.put("b", _arr(256, 2.0), priority=0.0)
    # demote "a" below "b": priority alone must now pick "a" as victim
    p.set_priority("a", -1.0)
    p.set_priority("ghost", 9.0)                 # unknown key: silent no-op
    assert "ghost" not in p
    p.put("c", _arr(256, 3.0))
    assert p.tier_of("a") == "remote"            # demoted entry spilled...
    assert p.tier_of("b") == "host"              # ...not the LRU-older "b"
    # re-ranking never touched the payload
    np.testing.assert_array_equal(np.asarray(p.get("a")),
                                  np.asarray(_arr(256, 1.0)))
    # promote back above "b": next pressure evicts "b" instead
    p.set_priority("a", 10.0)
    p.set_priority("b", -5.0)
    p.put("d", _arr(256, 4.0))
    assert p.tier_of("b") == "remote"
    assert p.tier_of("c") == "host" or p.tier_of("d") == "host"


def test_pinned_entries_never_evict_and_last_tier_overflows():
    host = TierState("host", B.make_host_backend(), capacity=256 * 1024)
    p = MemoryPoolManager([host])      # single tier: nowhere to spill
    p.put("pinned", _arr(256), tier="host", pinned=True)
    with pytest.raises(PoolCapacityError):
        p.put("overflow", _arr(256), tier="host")
    assert p.tier_of("pinned") == "host"


def test_reserve_release_and_headroom():
    """Admission-control ledger: reservations count against the named
    tiers' combined capacity; release returns the headroom."""
    p = default_pool(device_capacity=1 << 20, host_capacity=1 << 20)
    tiers = ("device", "host")
    assert p.headroom(tiers) == 2 << 20
    assert p.reserve("r1", 1 << 20, tiers)
    assert p.reserve("r2", 512 << 10, tiers)
    assert not p.reserve("r3", 1 << 20, tiers)      # would over-commit
    assert p.reserved_bytes(tiers) == (1 << 20) + (512 << 10)
    p.put("a", _arr(256), tier="host")              # occupancy counts too
    assert p.headroom(tiers) == (512 << 10) - 256 * 1024
    p.release("r1")
    assert p.reserve("r3", 1 << 20, tiers)
    p.release("r2")
    p.release("r3")
    p.release("r3")                                  # no-op re-release
    assert p.reserved_bytes() == 0
    assert p.snapshot()["reserved"] == 0
    # an unbounded tier in the set always admits
    assert p.reserve("big", 1 << 40, ("device", "host", "remote"))


def test_evict_listener_fires_on_spill():
    p = default_pool(host_capacity=256 * 1024)
    seen = []
    p.add_evict_listener(lambda entry, dst: seen.append((entry.key, dst)))
    p.put("cold", _arr(256))
    p.put("hot", _arr(256))                          # spills "cold" → remote
    assert seen == [("cold", "remote")]
    assert p.tier_of("cold") == "remote"


def test_shared_pool_across_caches_does_not_collide():
    """The documented shared-pool-across-layers setup: page keys are
    namespaced per cache instance."""
    from repro.offload.kvcache import PagedKVCache

    pool = default_pool()
    b, hkv, d, page = 1, 1, 8, 4
    c1 = PagedKVCache.create(batch=b, max_seq=8, page_size=page,
                             n_kv_heads=hkv, head_dim=d, pool=pool)
    c2 = PagedKVCache.create(batch=b, max_seq=8, page_size=page,
                             n_kv_heads=hkv, head_dim=d, pool=pool)
    ones = jnp.ones((b, page, hkv, d))
    c1.prefill(ones, ones)
    c2.prefill(ones * 7.0, ones * 7.0)
    k1, _ = c1.fetch_pages([0])
    k2, _ = c2.fetch_pages([0])
    page0 = np.ones((1, b, hkv, page, d))     # pages are head-major
    np.testing.assert_array_equal(np.asarray(k1), page0)
    np.testing.assert_array_equal(np.asarray(k2), page0 * 7.0)


# ---------------------------------------------------------------------------
# declarative tier topology
# ---------------------------------------------------------------------------


def test_topology_default_reproduces_three_tier_pool():
    """`TierTopology.default()` is the historical pool, exactly: names,
    admission set, store tier, and backend classes per slot."""
    p = default_pool()
    assert p.spill_order == ["device", "host", "remote"]
    assert p.top_tier == "device"
    assert p.default_store_tier == "host"
    assert p.admission_tiers == ("device", "host")
    assert isinstance(p.tiers["device"].backend, B.DeviceBackend)
    assert isinstance(p.tiers["remote"].backend, B.ModeledTierBackend)
    assert not p.tiers["remote"].backend.throttled
    # legacy capacity kwargs land on the matching TierSpec slots
    p2 = default_pool(device_capacity=1 << 20, host_capacity=1 << 21,
                      remote_capacity=1 << 22)
    assert [p2.tiers[n].capacity for n in p2.spill_order] == [
        1 << 20, 1 << 21, 1 << 22]
    with pytest.raises(ValueError, match="capacities"):
        default_pool(host_capacity=1 << 20,
                     topology=TierTopology.default())


def test_topology_validation_and_roundtrip():
    with pytest.raises(ValueError, match="kind"):
        TierSpec("x", kind="tape")
    with pytest.raises(ValueError, match="only"):
        TierSpec("x", kind="host", read_bw=1e9)      # throttle on real tier
    with pytest.raises(ValueError, match="first"):
        TierTopology(tiers=(TierSpec("h", kind="host"),
                            TierSpec("d", kind="device")))
    with pytest.raises(ValueError, match="duplicate"):
        TierTopology(tiers=(TierSpec("a"), TierSpec("a")))
    with pytest.raises(ValueError, match="admit"):
        TierTopology(tiers=(TierSpec("a", admit=False),))
    topo = TierTopology.default(host_capacity=1 << 20)
    assert TierTopology.from_dict(topo.to_dict()) == topo
    with pytest.raises(ValueError, match="unknown"):
        TierTopology.from_dict({"tiers": [{"name": "a", "kindd": "host"}]})
    # sweeps rebuild only the named modeled tier
    sw = sweep_topologies(topo, "remote", read_bws=[1e9, 2e9])
    assert [s.spec("remote").read_bw for s in sw] == [1e9, 2e9]
    assert all(s.spec("host") == topo.spec("host") for s in sw)
    with pytest.raises(ValueError, match="modeled"):
        sweep_topologies(topo, "host", read_bws=[1e9])


def test_modeled_tier_enforces_bandwidth():
    """A modeled tier's sleep-throttle holds measured per-transfer read
    bandwidth within 20% of its spec (ISSUE acceptance: the paper's
    Fig. 6 D2H sweep needs trustworthy grid points). MiB-scale arrays
    keep the per-transfer latency term negligible."""
    bw = 200e6                                       # 200 MB/s
    topo = TierTopology(tiers=(
        TierSpec("device", kind="device"),
        TierSpec("pooled", kind="modeled", read_bw=bw, write_bw=bw),
    ))
    p = default_pool(topology=topo)
    x = jnp.ones((1 << 20,), jnp.float32)            # 4 MiB
    for i in range(3):
        p.put(f"k{i}", x, tier="pooled")
        p.get(f"k{i}")
    pairs = p.snapshot()["transfer"]["pairs"]
    for pair in ("pooled->device", "device->pooled"):
        meas = pairs[pair]
        assert meas["transfers"] == 3
        measured_bw = meas["bytes"] / meas["busy_s"]
        assert measured_bw == pytest.approx(bw, rel=0.20), pair
    p.close()


def test_n_tier_chain_spills_step_by_step():
    """A deeper-than-three chain spills strictly one hop at a time and
    get() works from any depth."""
    unit = 64 * 1024
    topo = TierTopology(tiers=(
        TierSpec("l0", kind="numpy", capacity=unit),
        TierSpec("l1", kind="numpy", capacity=unit),
        TierSpec("l2", kind="numpy", capacity=unit),
        TierSpec("l3", kind="numpy"),
    ))
    p = default_pool(topology=topo)
    hops = []
    p.add_evict_listener(lambda e, dst: hops.append((e.key, dst)))
    for i in range(4):
        p.put(f"k{i}", _arr(64, float(i)), tier="l0")
    # k0 rippled down the whole chain, one hop per incoming page
    assert [p.tier_of(f"k{i}") for i in range(4)] == ["l3", "l2", "l1", "l0"]
    names = list(topo.names)
    for key, dst in hops:
        assert names.index(dst) >= 1                 # only ever downward
    np.testing.assert_array_equal(np.asarray(p.get("k0")),
                                  np.asarray(_arr(64, 0.0)))
    p.close()


@given(st.integers(2, 5), st.integers(0, 10_000))
@settings(max_examples=12, deadline=None)
def test_property_spill_conserves_bytes_and_chain_order(n_tiers, seed):
    """For arbitrary N-tier topologies and workloads: spill-down moves
    entries strictly one hop down the chain, bounded tiers never exceed
    capacity, and bytes are conserved across the hierarchy."""
    rng = np.random.default_rng(seed)
    unit = 16 * 1024
    # bounded tiers hold >= the largest page, so a spill chain always
    # terminates at the unbounded bottom tier
    tiers = tuple(
        TierSpec(f"t{i}", kind="numpy",
                 capacity=int(rng.integers(2, 5)) * unit)
        for i in range(n_tiers - 1)
    ) + (TierSpec(f"t{n_tiers - 1}", kind="numpy"),)
    topo = TierTopology(tiers=tiers)
    p = default_pool(topology=topo)
    names = list(topo.names)
    tier_at = {}                                     # key -> expected index
    hops = []

    def on_evict(entry, dst):
        # checked live: one hop down from where the entry last was
        hops.append((entry.key, dst))
        assert names.index(dst) == tier_at[entry.key] + 1, (entry.key, dst)
        tier_at[entry.key] = names.index(dst)

    p.add_evict_listener(on_evict)
    live = {}
    for i in range(int(rng.integers(4, 12))):
        key = f"k{i % 6}"                            # re-puts included
        kb = int(rng.integers(1, 3)) * 16            # 16 or 32 KiB
        p.put(key, _arr(kb, float(i)), tier=names[0],
              priority=float(rng.integers(0, 3)))
        live[key] = (kb, float(i))
        tier_at[key] = 0
        if rng.integers(0, 2) and live:
            probe = str(rng.choice(sorted(live)))
            p.get(probe)                             # recency traffic
            tier_at[probe] = names.index(p.tier_of(probe))
        if rng.integers(0, 2) and live:
            p.set_priority(str(rng.choice(sorted(live))),
                           float(rng.integers(-2, 5)))
    # bounded tiers respect capacity; bytes are conserved
    for spec in topo:
        if spec.capacity is not None:
            used, cap = p.occupancy(spec.name)
            assert used <= cap, spec.name
    total = sum(p.occupancy(n)[0] for n in names)
    assert total == sum(kb * 1024 for kb, _ in live.values())
    assert sum(p.snapshot()[f"tier/{n}"]["entries"]
               for n in names) == len(live)
    # the chain actually exercised spilling for multi-page workloads
    assert all(names.index(p.tier_of(k)) == tier_at[k] for k in live)
    # payload integrity from wherever each entry landed
    for key, (kb, fill) in live.items():
        np.testing.assert_array_equal(np.asarray(p.get(key)),
                                      np.asarray(_arr(kb, fill)))
    p.close()


# ---------------------------------------------------------------------------
# KV page codecs: round-trip bounds + on-wire byte accounting
# ---------------------------------------------------------------------------


def _rand_page(seed=0, shape=(2, 8, 2, 16)):
    return jnp.asarray(np.random.default_rng(seed)
                       .standard_normal(shape).astype(np.float32))


@pytest.mark.parametrize("name", ["int8", "fp8"])
def test_codec_roundtrip_within_hard_bound(name):
    from repro.pool import make_codec, roundtrip_bound
    c = make_codec(name)
    x = _rand_page(1)
    payload, scale = c.encode(x)
    y = c.decode(payload, scale, str(x.dtype))
    assert y.shape == x.shape and y.dtype == x.dtype
    err = float(jnp.max(jnp.abs(y - x)))
    bound = roundtrip_bound(c, float(jnp.max(jnp.abs(x))))
    assert err <= bound, (name, err, bound)
    # 4-byte payloads become 1-byte payloads (+4B scale)
    assert c.encoded_nbytes(x.shape, x.dtype) == x.size + 4
    assert c.ratio(4) == 0.25


def test_codec_none_and_unknown():
    from repro.pool import make_codec
    assert make_codec(None) is None and make_codec("none") is None
    with pytest.raises(ValueError, match="unknown"):
        make_codec("zstd")


def test_codec_pool_records_wire_bytes_not_decoded():
    """The byte-accounting bugfix: tier occupancy, bytes_stored/fetched,
    and the per tier-pair calibration table must all see *encoded* bytes —
    decoded nbytes would inflate measured bandwidth 4× under int8."""
    p = default_pool(topology=TierTopology.default(),
                     codec="int8", codec_below="host")
    x = _rand_page(2)                                 # 4 KiB decoded
    wire = x.size + 4
    e = p.put("pg", x, tier="host")
    assert e.nbytes == wire
    assert x.nbytes >= 2 * wire          # int8 at least halves the wire
    snap = p.snapshot()
    assert snap["tier/host"]["used"] == wire
    assert snap["bytes_stored"] == wire
    assert snap["transfer"]["pairs"]["device->host"]["bytes"] == wire
    y = p.get("pg")
    assert y.dtype == x.dtype and y.shape == x.shape
    assert float(jnp.max(jnp.abs(y - x))) < 0.05
    snap = p.snapshot()
    assert snap["bytes_fetched"] == wire
    assert snap["transfer"]["pairs"]["host->device"]["bytes"] == wire
    # the measured-bandwidth path consumes these pairs directly
    from repro.core.calibration import measurements_from_pairs
    ms = measurements_from_pairs(snap["transfer"]["pairs"])
    assert ms[("host", "device")].nbytes == wire
    p.close()


def test_codec_spill_encodes_at_boundary_and_moves_payload_below():
    """Device→host spill quantizes (wire bytes shrink 4×); host→remote
    moves the payload as-is — no re-encode, so quantization error does
    NOT compound across the lower hop."""
    x = _rand_page(3, (16, 16))                       # 1024 B decoded
    wire = 16 * 16 + 4
    p = default_pool(
        topology=TierTopology.default(device_capacity=1500,
                                      host_capacity=300),
        codec="int8", codec_below="host")
    p.put("p0", x, tier="device")
    assert p.entries["p0"].nbytes == x.nbytes         # device: decoded
    p.put("p1", x, tier="device")                     # spills p0 → host
    assert p.tier_of("p0") == "host"
    assert p.entries["p0"].nbytes == wire
    one_hop = np.asarray(p.get("p0"))                 # single quantization
    p.put("p2", x, tier="device")                     # p1→host, p0→remote
    assert p.tier_of("p0") == "remote"
    assert p.entries["p0"].nbytes == wire
    pairs = p.snapshot()["transfer"]["pairs"]
    assert pairs["host->remote"]["bytes"] == wire     # on-wire, encoded
    # byte-identical to the one-hop decode: the payload moved untouched
    np.testing.assert_array_equal(np.asarray(p.get("p0")), one_hop)
    p.close()


def test_codec_raises_admission_capacity():
    """The admission bugfix: reservations stay in decoded bytes, but a
    codec tier counts at decoded-equivalent capacity — 4× the raw byte
    budget for fp32 pages in int8 — so quantization admits more, not
    fewer, requests."""
    p = default_pool(
        topology=TierTopology.default(device_capacity=0, host_capacity=1100,
                                      remote_capacity=0),
        codec="int8", codec_below="host")
    # raw-byte ledger (itemsize=None): 4000 decoded B can't fit in 1100
    assert not p.reserve("raw", 4000, ("host",))
    # decoded-equivalent ledger: 1100 B of int8 holds ~4384 fp32 bytes
    assert p.reserve("scaled", 4000, ("host",), itemsize=4)
    assert p.headroom(("host",), itemsize=4) == 4 * 1100 - 4000
    p.release("scaled")
    # occupancy is scaled per tier too: a parked page charges wire bytes
    x = _rand_page(4, (16, 16))                       # 1024 B decoded
    p.put("pg", x, tier="host")                       # 260 B at rest
    assert p.headroom(("host",), itemsize=4) == 4 * (1100 - 260)
    p.close()


def test_codec_boundary_validation():
    with pytest.raises(ValueError, match="accelerator"):
        default_pool(codec="int8", codec_below="device")
    with pytest.raises(ValueError, match="not in topology"):
        default_pool(codec="int8", codec_below="nvme")
    # codec None/none → no wrapping at all
    p = default_pool(codec="none")
    assert not isinstance(p.tiers["host"].backend, B.CodecBackend)
    p.close()


def test_codec_encoded_pages_survive_n_tier_chain():
    """Every tier below the boundary is wrapped, so a page spilling to
    the bottom of a deep chain stays decodable (an encoded payload can
    never land in a plain tier)."""
    unit = 300
    topo = TierTopology(tiers=(
        TierSpec("l0", kind="numpy", capacity=unit, admit=True),
        TierSpec("l1", kind="numpy", capacity=unit),
        TierSpec("l2", kind="numpy"),
    ))
    p = default_pool(topology=topo, codec="fp8", codec_below="l0")
    x = _rand_page(5, (16, 16))
    for i in range(3):
        p.put(f"k{i}", x, tier="l0")                  # 260 B each encoded
    assert p.tier_of("k0") == "l2"
    y = p.get("k0")
    assert float(jnp.max(jnp.abs(y - x))) < 0.5       # fp8, single hop
    p.close()


# ---------------------------------------------------------------------------
# transfer engine: overlap semantics
# ---------------------------------------------------------------------------


def test_transfer_issued_before_wait_and_overlaps():
    eng = TransferEngine(depth=2, workers=2)

    def slow(v):
        time.sleep(0.15)
        return v

    h1 = eng.submit(lambda: slow(1), key="t1")
    h2 = eng.submit(lambda: slow(2), key="t2")
    # both issued (seq assigned) before anything was waited on
    assert h1.seq < h2.seq
    assert eng.stats.issued == 2
    assert eng.stats.waits_overlapped + eng.stats.waits_blocked == 0
    assert h1.wait() == 1 and h2.wait() == 2
    assert eng.stats.max_in_flight == 2          # genuinely concurrent
    assert eng.stats.completed == 2
    eng.close()


def test_transfer_depth_bounds_in_flight():
    eng = TransferEngine(depth=1, workers=1)
    h1 = eng.submit(lambda: 1)
    h2 = eng.submit(lambda: 2)   # forces retirement of h1 first
    assert h1.done               # double-buffer back-pressure retired it
    assert h2.wait() == 2
    eng.close()


def test_pool_prefetch_returns_wait_handle():
    p = default_pool()
    x = jnp.arange(2048.0)
    p.put("page", x)
    h = p.prefetch("page")
    np.testing.assert_array_equal(np.asarray(h.wait()), np.asarray(x))
    snap = p.snapshot()
    assert snap["transfer"]["issued"] == 1
    assert snap["bytes_fetched"] == x.nbytes


# ---------------------------------------------------------------------------
# plan executor: executed residency == memsim prediction
# ---------------------------------------------------------------------------


def test_executor_residency_matches_memsim_on_planned_graph():
    g = small_graph()
    plan = HyperOffloadPlanner(TPU_V5E).plan(g)
    predicted = memsim.simulate(plan.graph, plan.order)

    pool = default_pool()
    env, trace = OffloadPlanExecutor(plan, pool).run()
    assert trace.usage == predicted.usage          # node-for-node agreement
    assert trace.peak_bytes == predicted.peak_bytes
    assert trace.prefetches > 0                    # the plan really moved data
    snap = pool.snapshot()
    assert snap["bytes_fetched"] > 0 and snap["bytes_stored"] > 0
    assert snap["transfer"]["issued"] == trace.prefetches


def test_executor_values_match_resident_baseline():
    g = small_graph()
    plan = HyperOffloadPlanner(TPU_V5E).plan(g)

    def fn(*args, _n=1):
        s = sum(jnp.sum(a.astype(jnp.float32)) for a in args)
        return tuple(jnp.full((8,), s) + i for i in range(_n))

    fns = {n: (lambda *a, _n=len(node.outputs): fn(*a, _n=_n))
           for n, node in plan.graph.nodes.items() if node.kind == "compute"}
    key = jax.random.key(7)
    inputs = {"x": jax.random.normal(key, (16,))}
    for i in range(4):
        inputs[f"w{i}"] = jnp.full((8,), float(i + 1))

    env, trace = OffloadPlanExecutor(plan, default_pool(), fns).run(inputs)
    ref = run_baseline(g, fns, inputs)
    np.testing.assert_allclose(np.asarray(env["y"]), np.asarray(ref["y"]),
                               rtol=1e-6)
    assert trace.stores >= 1 and trace.detaches >= 1


def test_executor_rejects_invalid_order():
    g = small_graph()
    plan = HyperOffloadPlanner(TPU_V5E).plan(g)
    bad = list(reversed(plan.order))
    with pytest.raises(ValueError):
        OffloadPlanExecutor(plan, default_pool()).run(order=bad)
