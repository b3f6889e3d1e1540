"""The `repro.api` front door: `OffloadConfig` serialization and surface
pinning, `HyperOffloadSession` single-pool wiring, the config-derived
transfer-depth policy, and the deprecation shims that keep the old
per-subsystem constructors working for one release."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.api
from repro.api import HyperOffloadSession, OffloadConfig
from repro.api.__main__ import main as api_main
from repro.api.config import CalibrationConfig, PrefixCacheConfig
from repro.configs import REGISTRY
from repro.core.calibration import (
    CalibratedHardwareSpec, measurements_from_pairs,
)
from repro.core.costmodel import HardwareSpec
from repro.core.insertion import PAGED_INSERTION, InsertionOptions
from repro.core.schedule import ScheduleOptions
from repro.models.model import build_model
from repro.offload.kvcache import PagedKVCache
from repro.pool import TierSpec, TierTopology, auto_depth
from repro.sched import ContinuousScheduler, Request, SchedulerConfig
from repro.serving.engine import ServeEngine

CFG = REGISTRY["phi3-mini-3.8b"].reduced()
MAX_SEQ = 32


@pytest.fixture(scope="module")
def model_and_params():
    m = build_model(CFG)
    return m, m.init(jax.random.key(0))


# ---------------------------------------------------------------------------
# public surface + config serialization
# ---------------------------------------------------------------------------


def test_public_api_surface_is_pinned():
    assert repro.api.__all__ == [
        "KVCodecConfig",
        "OffloadConfig",
        "HyperOffloadSession",
        "HW_SPECS",
        "MODES",
    ]


def test_config_round_trips_through_json():
    cfg = OffloadConfig(
        mode="kv_offload",
        hw="ascend_910c_like",
        device_capacity=1 << 20,
        host_capacity=1 << 22,
        transfer_depth=16,
        max_seq=64, max_batch=2, prefill_budget=2,
        chunk_size=16, prefill_tokens=32,
        cache_dtype="bfloat16",
        insertion=InsertionOptions(min_bytes=4096,
                                   force_prefixes=("kv_",)),
        schedule=ScheduleOptions(max_candidates=8),
        remat="offload", offload_opt_state=True)
    wire = json.loads(json.dumps(cfg.to_dict()))
    assert OffloadConfig.from_dict(wire) == cfg


def test_config_round_trips_custom_hardware():
    hw = HardwareSpec(name="lab_box", flops=1e12, hbm_bw=1e11,
                      hbm_bytes=8e9, pool_bw_d2r=1e10, pool_bw_r2d=1e10,
                      link_bw=1e10)
    cfg = OffloadConfig(hw=hw)
    wire = json.loads(json.dumps(cfg.to_dict()))
    back = OffloadConfig.from_dict(wire)
    assert back.hardware == hw
    # a registered spec serializes compactly, by name
    assert OffloadConfig(hw="tpu_v5e").to_dict()["hw"] == "tpu_v5e"


def test_config_validates_fields():
    with pytest.raises(ValueError, match="mode"):
        OffloadConfig(mode="turbo")
    with pytest.raises(ValueError, match="remat"):
        OffloadConfig(remat="sometimes")
    with pytest.raises(ValueError, match="hardware"):
        OffloadConfig(hw="abacus")
    with pytest.raises(ValueError, match="transfer_depth"):
        OffloadConfig(transfer_depth=0)
    with pytest.raises(ValueError, match="chunk_size"):
        OffloadConfig(chunk_size=0)
    with pytest.raises(ValueError, match="chunk_size"):
        OffloadConfig(chunk_size=256, max_seq=128)
    with pytest.raises(ValueError, match="requires chunk_size"):
        OffloadConfig(prefill_tokens=16)
    with pytest.raises(ValueError, match="unknown OffloadConfig fields"):
        OffloadConfig.from_dict({"modee": "resident"})
    # a typo inside a nested options dict must not silently default
    with pytest.raises(ValueError, match="unknown InsertionOptions fields"):
        OffloadConfig.from_dict({"insertion": {"min_byte": 4096}})


def test_mode_resolves_planner_and_depth_defaults():
    # kv_offload plans every pool-resident KV tensor (the old hard-coded
    # min_bytes=1 at the PlanPrefetcher call site); resident keeps the
    # cost-model threshold
    assert OffloadConfig(mode="kv_offload").insertion_options() \
        == PAGED_INSERTION
    assert OffloadConfig().insertion_options().min_bytes == 1 << 20
    custom = InsertionOptions(min_bytes=7)
    assert OffloadConfig(mode="kv_offload",
                         insertion=custom).insertion_options() is custom
    # depth policy: auto derives from the consumer's shape, int pins
    auto = OffloadConfig()
    assert auto.depth_for(layers=16) == auto_depth(layers=16) == 64
    assert auto.depth_for(pages=40) == 80
    assert auto.depth_for() == 8                       # floor
    assert OffloadConfig(transfer_depth=3).depth_for(pages=1000) == 3


def test_kv_offload_override_keeps_mandatory_prefetch_planning(
        model_and_params):
    """session.scheduler(kv_offload=True) on a resident-mode session must
    still plan the mandatory prefetch of every pool-resident KV tensor —
    the resident cost-model thresholds would filter smoke-scale KV leaves
    out of the plan and the prefetcher would never issue a fetch."""
    model, params = model_and_params
    session = HyperOffloadSession(OffloadConfig(max_seq=32, max_batch=2))
    sched = session.scheduler(model, params, kv_offload=True)
    assert sched.cfg.insert_opts == PAGED_INSERTION
    assert sched.prefetcher is not None
    assert len(sched.prefetcher.planned_layers) > 0
    session.close()


def test_print_config_cli(capsys):
    assert api_main(["--print-config"]) == 0
    dumped = json.loads(capsys.readouterr().out)
    assert dumped["mode"] == "resident"
    assert dumped["transfer_depth"] == "auto"
    # the dump is the default config, exactly (drift detector for CI)
    resolved = dumped.pop("insertion_resolved")
    topo = dumped.pop("topology_resolved")
    assert OffloadConfig.from_dict(dumped) == OffloadConfig()
    assert resolved["min_bytes"] == OffloadConfig().insertion_options().min_bytes
    assert [t["name"] for t in topo["tiers"]] == ["device", "host", "remote"]


def test_config_topology_roundtrip_and_validation():
    topo = TierTopology(tiers=(
        TierSpec("device", kind="device", capacity=1 << 20),
        TierSpec("host", kind="host", capacity=1 << 22),
        TierSpec("cxl", kind="modeled", read_bw=5e9, write_bw=4e9,
                 read_latency_s=1e-4, admit=False),
    ))
    cfg = OffloadConfig(mode="kv_offload", topology=topo,
                        calibration=CalibrationConfig(min_transfers=4,
                                                      max_inflight=32))
    wire = json.loads(json.dumps(cfg.to_dict()))
    back = OffloadConfig.from_dict(wire)
    assert back == cfg and back.tier_topology == topo
    # no explicit topology: the default chain built from capacity fields
    d = OffloadConfig(host_capacity=1 << 20)
    assert d.tier_topology.names == ("device", "host", "remote")
    assert d.tier_topology.spec("host").capacity == 1 << 20
    with pytest.raises(ValueError, match="TierTopology"):
        OffloadConfig(topology={"tiers": []})        # dict, not the type
    with pytest.raises(ValueError, match="capacities"):
        OffloadConfig(topology=topo, host_capacity=1 << 20)
    with pytest.raises(ValueError, match="pin_tier"):
        OffloadConfig(chunk_size=8, topology=topo,
                      prefix_cache=PrefixCacheConfig(enable=True,
                                                     pin_tier="remote"))
    # a disabled prefix cache never vetoes a custom chain (its default
    # pin names the legacy "host" tier)
    OffloadConfig(topology=TierTopology(tiers=(TierSpec("ram",
                                                        kind="numpy"),)))
    with pytest.raises(ValueError, match="min_transfers"):
        CalibrationConfig(min_transfers=0)
    with pytest.raises(ValueError, match="max_inflight"):
        CalibrationConfig(max_inflight=0)


# ---------------------------------------------------------------------------
# session wiring
# ---------------------------------------------------------------------------


def test_session_shares_one_pool_and_merges_stats(model_and_params):
    model, params = model_and_params
    cfg = OffloadConfig(mode="kv_offload", max_seq=MAX_SEQ, max_batch=2)
    with HyperOffloadSession(cfg) as session:
        engine = session.serve_engine(model, params)
        sched = session.scheduler(model, params)
        cache = session.paged_kv(batch=1, n_kv_heads=CFG.n_kv_heads,
                                 head_dim=CFG.head_dim)
        # exactly one pool / transfer engine behind every subsystem
        assert engine.pool is session.pool
        assert sched.pool is session.pool
        assert cache.pool is session.pool
        assert session.transfer is session.pool.transfer

        out = engine.generate(
            {"tokens": jnp.ones((1, 4), jnp.int32)}, 3)
        assert out.shape == (1, 3)
        sched.run([Request(tokens=np.ones((4,), np.int32),
                           max_new_tokens=4, seed=0)])

        s = session.stats()
        assert s["mode"] == "kv_offload"
        assert s["serve"]["engines"] == 1
        assert s["serve"]["decoded_tokens"] == 2      # 3 tokens, 2 decode steps
        assert s["serve"]["cache_round_trips"] == 2
        assert s["sched"]["schedulers"] == 1
        assert s["sched"]["retires"] == 1
        assert s["sched"]["prefetch"]["fetches_issued"] > 0
        assert s["paged"]["caches"] == 1
        assert s["pool"]["puts"] > 0 and "transfer" in s["pool"]
        assert s["plans_cached"] == 1
    # close() is idempotent and reaches the owned pool
    session.close()


def test_session_plan_cache_is_shared(model_and_params):
    model, params = model_and_params
    cfg = OffloadConfig(mode="kv_offload", max_seq=MAX_SEQ, max_batch=2)
    with HyperOffloadSession(cfg) as session:
        s1 = session.scheduler(model, params)
        s2 = session.scheduler(model, params)
        assert s1.prefetcher.plan is s2.prefetcher.plan   # one plan, reused
        assert session.stats()["plans_cached"] == 1


def test_session_auto_depth_grows_pinned_does_not(model_and_params):
    model, params = model_and_params
    with HyperOffloadSession(OffloadConfig(mode="kv_offload",
                                           max_seq=MAX_SEQ)) as session:
        base = session.transfer.depth
        session.paged_kv(batch=1, n_kv_heads=CFG.n_kv_heads,
                         head_dim=CFG.head_dim, max_seq=256, page_size=4)
        assert session.transfer.depth == max(base, 2 * (256 // 4))
    with HyperOffloadSession(OffloadConfig(mode="kv_offload",
                                           max_seq=MAX_SEQ,
                                           transfer_depth=5)) as session:
        session.paged_kv(batch=1, n_kv_heads=CFG.n_kv_heads,
                         head_dim=CFG.head_dim, max_seq=256, page_size=4)
        assert session.transfer.depth == 5                # pinned
    # the pin applies to an injected pool too
    from repro.pool import default_pool
    ext = default_pool(transfer_depth=5)
    session = HyperOffloadSession(
        OffloadConfig(mode="kv_offload", max_seq=MAX_SEQ, transfer_depth=5),
        pool=ext)
    session.paged_kv(batch=1, n_kv_heads=CFG.n_kv_heads,
                     head_dim=CFG.head_dim, max_seq=256, page_size=4)
    assert ext.transfer.depth == 5
    session.close()
    ext.close()


def test_session_scheduler_overrides(model_and_params):
    model, params = model_and_params
    cfg = OffloadConfig(max_seq=MAX_SEQ, max_batch=4)
    with HyperOffloadSession(cfg) as session:
        sched = session.scheduler(model, params, max_batch=2,
                                  prefill_budget=2)
        assert sched.cfg.max_batch == 2
        assert sched.cfg.prefill_budget == 2
        assert sched.cfg.kv_offload is False
        with pytest.raises(TypeError, match="not both"):
            session.scheduler(model, params, SchedulerConfig(), max_batch=2)
        with pytest.raises(TypeError, match="not both"):
            session.train_step(model, session.train_config(), total_steps=5)
        with pytest.raises(TypeError, match="not both"):
            session.init_train_state(model, jax.random.key(0),
                                     ts=session.train_config(), total_steps=5)


def test_default_topology_is_behaviorally_identical(model_and_params):
    """ISSUE acceptance: an explicit `TierTopology.default()` serves
    token-identically to the legacy (topology=None) config in both
    resident and kv_offload modes, with the same stats() surface."""
    model, params = model_and_params
    batch = {"tokens": jnp.ones((2, 4), jnp.int32)}
    for mode in ("resident", "kv_offload"):
        outs, shapes = [], []
        for topo in (None, TierTopology.default()):
            cfg = OffloadConfig(mode=mode, max_batch=2, max_seq=MAX_SEQ,
                                topology=topo)
            with HyperOffloadSession(cfg) as s:
                out = s.serve_engine(model, params).generate(batch, 6)
                outs.append(np.asarray(out))
                st = s.stats()
                shapes.append((sorted(st), sorted(st["pool"])))
        np.testing.assert_array_equal(outs[0], outs[1])
        assert shapes[0] == shapes[1]


def test_recalibrate_replans_from_measured_bandwidth(model_and_params):
    """ISSUE acceptance: recalibrate() yields a spec whose transfer
    numbers are the byte-weighted measured per-tier-pair bandwidths (not
    the static HardwareSpec's), and swaps it into the planner and every
    live scheduler."""
    model, params = model_and_params
    cfg = OffloadConfig(mode="kv_offload", max_batch=2, max_seq=MAX_SEQ)
    with HyperOffloadSession(cfg) as s:
        sched = s.scheduler(model, params)
        sched.run([Request(tokens=np.ones((6,), np.int32),
                           max_new_tokens=4, seed=0)])
        # the serve engine's cache round trips produce the host->device
        # read traffic calibration feeds on
        s.serve_engine(model, params).generate(
            {"tokens": jnp.ones((2, 4), jnp.int32)}, 4)
        static = s.hw
        pairs = s.transfer.stats.snapshot()["pairs"]
        ms = measurements_from_pairs(pairs)
        spec = s.recalibrate()
        assert isinstance(spec, CalibratedHardwareSpec)
        assert spec.name == f"{static.name}+measured"
        # the scalar the cost model consumes is the measured byte-weighted
        # read bandwidth into the device tier, exactly
        reads = [m for (src, dst), m in ms.items()
                 if dst == "device" and src != "device"
                 and m.transfers >= 2 and m.nbytes >= 1024]
        assert reads, "serving must have produced eligible read traffic"
        expect = (sum(m.nbytes for m in reads)
                  / sum(m.busy_s for m in reads))
        assert spec.pool_bw_r2d == pytest.approx(expect)
        assert spec.pool_bw_r2d != static.pool_bw_r2d
        # the per-pair table carries each measured link
        for m in reads:
            assert spec.bandwidth_between(m.src, m.dst) == pytest.approx(
                m.bandwidth)
        # planner and scheduler both run on the measured spec now
        assert s.planner.hw is spec
        assert sched.cfg.hw is spec
        assert sched.prefetcher is not None
        # calibrating again never stacks name suffixes
        assert s.recalibrate().name == f"{static.name}+measured"


# ---------------------------------------------------------------------------
# implicit-private-pool construction is gone (was a one-release shim)
# ---------------------------------------------------------------------------


def test_offload_construction_requires_explicit_pool(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="HyperOffloadSession"):
        ServeEngine(model, params, max_seq=MAX_SEQ, offload_kv=True)
    with pytest.raises(ValueError, match="HyperOffloadSession"):
        ContinuousScheduler(
            model, params,
            SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, kv_offload=True))
    with pytest.raises(ValueError, match="HyperOffloadSession"):
        PagedKVCache.create(batch=1, max_seq=64, page_size=16,
                            n_kv_heads=2, head_dim=8)


def test_session_construction_does_not_warn(model_and_params):
    """The front-door path raises no deprecation noise anywhere."""
    import warnings
    model, params = model_and_params
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        with HyperOffloadSession(OffloadConfig(mode="kv_offload",
                                               max_seq=MAX_SEQ)) as session:
            session.serve_engine(model, params)
            session.scheduler(model, params)
            session.paged_kv(batch=1, n_kv_heads=CFG.n_kv_heads,
                             head_dim=CFG.head_dim)
