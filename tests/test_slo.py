"""SLO-aware scheduling: priority classes, deadline-driven preemption,
goodput-maximizing admission.

Policy units (no model): spec/config validation, candidate ordering,
outcome scoring, the preemption victim policy, and the bounded prefill
boost. Integration (reduced model): preempted-then-restored sequences are
token-identical to unpreempted runs (mid-decode and mid-prefill-chunk, in
resident and kv_offload mode), admission never over-commits pool capacity
with SLOs on, higher priority classes never starve lower ones to
incompleteness at 3x overload, and deadline-infeasible requests are shed
before admission rather than admitted and missed.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import HyperOffloadSession, OffloadConfig
from repro.configs import REGISTRY
from repro.models.model import build_model
from repro.offload.kvcache import worst_case_page_bytes
from repro.pool import DEVICE_TIER, HOST_TIER, TransferEngine, default_pool
from repro.sched import (
    DONE, PREFILL, SHED, ContinuousScheduler, Request, RequestState,
    SchedulerConfig, poisson_trace,
)
from repro.serving.engine import ServeEngine
from repro.slo import (
    DEFAULT_SLO, PRIORITY_CLASSES, GoodputController, PreemptionEngine,
    SLOConfig, SLOSpec, attainment_summary, candidate_key,
)

CFG = REGISTRY["phi3-mini-3.8b"].reduced()
MAX_SEQ = 32


@pytest.fixture(scope="module")
def model_and_params():
    m = build_model(CFG)
    return m, m.init(jax.random.key(0))


def _sequential_reference(model, params, requests):
    eng = ServeEngine(model, params, max_seq=MAX_SEQ)
    out = {}
    for r in requests:
        got = eng.generate({"tokens": jnp.asarray(r.tokens[None, :])},
                           r.max_new_tokens, seed=r.seed)
        out[r.req_id] = np.asarray(got)[0]
    eng.close()
    return out


def _state(slo=None, arrival=0.0, prompt=4, max_new=4, seed=0):
    return RequestState(request=Request(
        tokens=np.ones((prompt,), np.int32), max_new_tokens=max_new,
        arrival=arrival, seed=seed, slo=slo))


# ---------------------------------------------------------------------------
# policy units
# ---------------------------------------------------------------------------


def test_slospec_validation_and_rank():
    assert PRIORITY_CLASSES["interactive"] > PRIORITY_CLASSES["standard"] \
        > PRIORITY_CLASSES["batch"]
    assert SLOSpec("interactive", ttft_deadline=8.0).rank == 2
    assert DEFAULT_SLO.priority_class == "standard"
    assert DEFAULT_SLO.ttft_deadline is None
    with pytest.raises(ValueError, match="priority_class"):
        SLOSpec("urgent")
    with pytest.raises(ValueError, match="ttft_deadline"):
        SLOSpec("batch", ttft_deadline=0.0)
    with pytest.raises(ValueError, match="tpot_deadline"):
        SLOSpec("batch", tpot_deadline=-1.0)


def test_sloconfig_validation():
    with pytest.raises(ValueError, match="max_prefill_boost"):
        SLOConfig(max_prefill_boost=0.5)
    with pytest.raises(ValueError, match="max_preempt_per_step"):
        SLOConfig(max_preempt_per_step=-1)
    assert not SLOConfig().enable           # FIFO by default


def test_candidate_key_orders_class_deadline_fifo():
    batch = _state(SLOSpec("batch"), arrival=0.0)
    late_deadline = _state(SLOSpec("interactive", ttft_deadline=20.0),
                           arrival=1.0)
    tight_deadline = _state(SLOSpec("interactive", ttft_deadline=5.0),
                            arrival=2.0)
    unannotated = _state(None, arrival=0.5)     # standard, no deadlines
    order = sorted([batch, late_deadline, tight_deadline, unannotated],
                   key=candidate_key)
    assert order == [tight_deadline, late_deadline, unannotated, batch]
    # within a class with no deadlines, FIFO by (arrival, req_id)
    a, b = _state(arrival=3.0), _state(arrival=1.0)
    assert min([a, b], key=candidate_key) is b


def test_attainment_scores_and_shed_counts_as_miss():
    met = _state(SLOSpec("interactive", ttft_deadline=4.0), arrival=0.0,
                 max_new=3)
    met.status, met.out = DONE, [1, 2, 3]
    met.t_first_token, met.t_done = 3.0, 5.0
    missed = _state(SLOSpec("interactive", ttft_deadline=2.0), arrival=0.0,
                    max_new=2)
    missed.status, missed.out = DONE, [1, 2]
    missed.t_first_token, missed.t_done = 6.0, 7.0
    shed = _state(SLOSpec("interactive", ttft_deadline=2.0), arrival=0.0)
    shed.status, shed.t_done = SHED, 4.0
    free = _state(SLOSpec("batch"), max_new=2)       # no deadlines
    free.status, free.out = DONE, [1, 2]
    free.t_first_token, free.t_done = 50.0, 51.0

    att = attainment_summary([met, missed, shed, free])
    assert att["requests"] == 4 and att["shed"] == 1
    assert att["tokens"] == 7
    # goodput = met interactive (3) + deadline-free batch (2)
    assert att["met_tokens"] == 5
    ic = att["classes"]["interactive"]
    # shedding must not launder attainment: 1 met of 3 deadline-carriers
    assert ic["ttft_n"] == 3 and ic["ttft_met"] == 1
    assert ic["ttft_attainment"] == pytest.approx(1 / 3)
    bc = att["classes"]["batch"]
    assert bc["met_tokens"] == 2 and bc["ttft_attainment"] is None


def test_pick_victim_policy():
    eng = PreemptionEngine(SLOConfig(enable=True))
    eng.begin_step()
    remaining = lambda s: s.request.max_new_tokens - len(s.out)
    batch_long = _state(SLOSpec("batch"), max_new=10, seed=1)
    batch_short = _state(SLOSpec("batch"), max_new=5, seed=2)
    running = [batch_short, batch_long]
    urgent = _state(SLOSpec("interactive", ttft_deadline=2.0), arrival=4.0)

    # no TTFT deadline → pure-throughput work never preempts
    calm = _state(SLOSpec("interactive"), arrival=4.0)
    assert eng.pick_victim(calm, running, 4.0, est_prefill_steps=1.0,
                           remaining_steps=remaining) is None
    # slack covers the earliest natural retirement → patience suffices
    patient = _state(SLOSpec("interactive", ttft_deadline=20.0), arrival=4.0)
    assert eng.pick_victim(patient, running, 4.0, est_prefill_steps=1.0,
                           remaining_steps=remaining) is None
    # same class is never preempted (FIFO fairness within a class)
    peer = _state(SLOSpec("interactive", ttft_deadline=2.0), arrival=4.0)
    inter_running = [_state(SLOSpec("interactive", ttft_deadline=2.0),
                            max_new=10)]
    assert eng.pick_victim(peer, inter_running, 4.0, est_prefill_steps=1.0,
                           remaining_steps=remaining) is None
    # eligible: lowest class with the MOST remaining work is parked
    assert eng.pick_victim(urgent, running, 4.0, est_prefill_steps=1.0,
                           remaining_steps=remaining) is batch_long
    # per-step quota (default 1) now spent
    assert eng.pick_victim(urgent, running, 4.0, est_prefill_steps=1.0,
                           remaining_steps=remaining) is None
    eng.begin_step()   # next step: quota restored
    assert eng.pick_victim(urgent, running, 4.0, est_prefill_steps=1.0,
                           remaining_steps=remaining) is batch_long


def test_preemption_disabled_never_picks():
    eng = PreemptionEngine(SLOConfig(enable=True, preemption=False))
    eng.begin_step()
    urgent = _state(SLOSpec("interactive", ttft_deadline=1.0), arrival=0.0)
    running = [_state(SLOSpec("batch"), max_new=10)]
    assert eng.pick_victim(urgent, running, 5.0, est_prefill_steps=1.0,
                           remaining_steps=lambda s: 10) is None


def test_boost_budget_bounded():
    ctl = GoodputController(SLOConfig(enable=True, max_prefill_boost=3.0))
    # no deadline pressure → base budget, no boost counted
    calm = _state(SLOSpec("batch"), prompt=24)
    assert ctl.boost_budget(4, [calm], 0.0) == 4
    assert ctl.boosted_steps == 0
    # 24 tokens in 2 steps of slack needs 12/step — boosted
    pressed = _state(SLOSpec("interactive", ttft_deadline=2.0), prompt=24)
    assert ctl.boost_budget(4, [pressed], 0.0) == 12
    assert ctl.boosted_steps == 1
    # hopeless pressure is capped at ceil(base * max_prefill_boost)
    hopeless = _state(SLOSpec("interactive", ttft_deadline=1.0), prompt=28)
    hopeless.request.arrival = -30.0        # slack floor (max(slack,1)) hit
    assert ctl.boost_budget(4, [hopeless], 0.0) == 12   # == 4 * 3.0


def test_goodput_rate_floors_at_base_budget():
    ctl = GoodputController(SLOConfig(enable=True))
    assert ctl.rate(4) == 4.0               # no measurements yet
    ctl.note_step(16)
    assert ctl.rate(4) == 16.0              # EWMA seeds at first sample
    ctl.note_step(0)                        # idle steps don't decay it
    assert ctl.rate(4) == 16.0
    ctl.note_step(2)
    assert ctl.rate(4) >= 4.0               # never below the base budget


def test_infeasible_requires_deadline_and_flag():
    ctl = GoodputController(SLOConfig(enable=True))
    doomed = _state(SLOSpec("interactive", ttft_deadline=1.0), arrival=0.0)
    assert ctl.infeasible(doomed, 5.0, est_prefill_steps=1.0)
    assert not ctl.infeasible(doomed, 0.0, est_prefill_steps=1.0)
    assert not ctl.infeasible(_state(SLOSpec("batch")), 5.0,
                              est_prefill_steps=1.0)
    off = GoodputController(SLOConfig(enable=True, shed_infeasible=False))
    assert not off.infeasible(doomed, 5.0, est_prefill_steps=1.0)


# ---------------------------------------------------------------------------
# preempt/restore token identity
# ---------------------------------------------------------------------------


def _preempt_run(model, params, reqs, *, kv_offload=False, **cfg_kw):
    """Run on a 1-slot batch so the interactive arrival MUST preempt, then
    check every output against the unpreempted sequential reference."""
    pool = None
    if kv_offload:
        row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ,
                                                      jnp.float32))
        pool = default_pool(device_capacity=int(1.5 * row),
                            host_capacity=4 * row,
                            transfer=TransferEngine(depth=64))
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, kv_offload=kv_offload,
                        slo=SLOConfig(enable=True), **cfg_kw),
        pool=pool)
    out = sched.run(reqs)
    ref = _sequential_reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    assert sched.stats.preemptions >= 1 and sched.stats.resumes >= 1
    assert sched.stats.shed == 0
    victim = sched.finished[reqs[0].req_id]
    assert victim.status == DONE and victim.preemptions >= 1
    sched.close()
    if pool is not None:
        pool.close()
    return sched


@pytest.mark.parametrize("kv_offload", [False, True])
def test_preempt_mid_decode_token_identity(model_and_params, kv_offload):
    """A batch sequence parked mid-DECODE for an interactive arrival and
    later restored emits the exact token stream of an unpreempted run."""
    model, params = model_and_params
    rng = np.random.default_rng(10)
    reqs = [
        Request(tokens=rng.integers(0, CFG.vocab_size, 5, dtype=np.int32),
                max_new_tokens=10, arrival=0.0, seed=0,
                slo=SLOSpec("batch")),
        Request(tokens=rng.integers(0, CFG.vocab_size, 4, dtype=np.int32),
                max_new_tokens=3, arrival=3.0, seed=1,
                slo=SLOSpec("interactive", ttft_deadline=2.0)),
    ]
    sched = _preempt_run(model, params, reqs, kv_offload=kv_offload)
    ia = sched.finished[reqs[1].req_id]
    assert ia.t_first_token - reqs[1].arrival <= 2.0   # deadline held


@pytest.mark.parametrize("kv_offload", [False, True])
def test_preempt_mid_prefill_chunk_token_identity(model_and_params,
                                                  kv_offload):
    """A long prompt parked mid-prefill-CHUNK (partial row on chunk_cache /
    in the pool) resumes its chunk walk and stays token-identical."""
    model, params = model_and_params
    rng = np.random.default_rng(11)
    reqs = [
        Request(tokens=rng.integers(0, CFG.vocab_size, 24, dtype=np.int32),
                max_new_tokens=4, arrival=0.0, seed=0,
                slo=SLOSpec("batch")),
        Request(tokens=rng.integers(0, CFG.vocab_size, 4, dtype=np.int32),
                max_new_tokens=3, arrival=2.0, seed=1,
                slo=SLOSpec("interactive", ttft_deadline=6.0)),
    ]
    pool = None
    if kv_offload:
        row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ,
                                                      jnp.float32))
        pool = default_pool(device_capacity=int(1.5 * row),
                            host_capacity=4 * row,
                            transfer=TransferEngine(depth=64))
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, chunk_size=4,
                        kv_offload=kv_offload, slo=SLOConfig(enable=True)),
        pool=pool)
    for r in reqs:
        sched.submit(r)
    # drive manually so the preemption moment is observable: the victim
    # must still be mid-prefill (no first token yet) when it is parked
    guard = 0
    while sched.stats.preemptions == 0:
        sched.step()
        guard += 1
        assert guard < 20, "expected a preemption within a few steps"
    victim = next(s for s in sched.preempted if s.req_id == reqs[0].req_id)
    assert victim.t_first_token is None          # parked mid-prefill…
    assert 0 < victim.prefill_pos < reqs[0].prompt_len   # …mid-chunk-walk
    out = sched.run()
    ref = _sequential_reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    assert sched.stats.resumes >= 1
    assert sched.finished[reqs[0].req_id].status == DONE
    sched.close()
    if pool is not None:
        pool.close()


# ---------------------------------------------------------------------------
# admission properties under SLO
# ---------------------------------------------------------------------------


def test_admission_never_overcommits_with_slo(model_and_params):
    """The over-commit invariant from test_sched holds verbatim with the
    SLO path on: preempted sequences keep their reservations, so device+
    host reserved bytes never exceed capacity and nothing spills remote."""
    model, params = model_and_params
    row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ, jnp.float32))
    for seed in range(3):
        reqs = poisson_trace(6, rate=5.0, vocab_size=CFG.vocab_size,
                             prompt_lens=(4, 8), new_tokens=(3, 8),
                             prompt_quantum=4, interactive_fraction=0.5,
                             seed=seed)
        pool = default_pool(device_capacity=row, host_capacity=row,
                            transfer=TransferEngine(depth=64))
        cap = 2 * row
        sched = ContinuousScheduler(
            model, params,
            SchedulerConfig(max_batch=3, max_seq=MAX_SEQ, kv_offload=True,
                            slo=SLOConfig(enable=True)),
            pool=pool)
        for r in reqs:
            sched.submit(r)
        guard = 0
        while len(sched.queue) or sched.active or sched.preempted:
            if not sched.active and not sched.preempted \
                    and sched.queue.head_ready(sched.now) is None:
                sched.now = sched.queue.next_arrival()
            sched.step()
            assert sched.pool.reserved_bytes((DEVICE_TIER, HOST_TIER)) <= cap
            snap = sched.pool.snapshot()
            assert snap["tier/remote"]["entries"] == 0, \
                "pages forced remote — SLO admission over-committed"
            guard += 1
            assert guard < 500
        # every request reached a terminal state (DONE or SHED) and every
        # reservation was released
        assert len(sched.finished) == len(reqs)
        assert sched.pool.reserved_bytes() == 0
        sched.close()
        pool.close()


def test_no_starvation_at_3x_overload(model_and_params):
    """Strict-priority admission at 3x overload must not starve the batch
    class: every batch request still runs to completion with its full
    decode budget (batch carries no deadline, so it can never be shed)."""
    model, params = model_and_params
    # ~3x the 2-slot service capacity for this mix
    reqs = poisson_trace(14, rate=1.2, vocab_size=CFG.vocab_size,
                         prompt_lens=(4, 8), new_tokens=(4, 8),
                         prompt_quantum=4, interactive_fraction=0.5,
                         seed=7)
    assert any((r.slo or DEFAULT_SLO).priority_class == "batch"
               for r in reqs)
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, chunk_size=4,
                        slo=SLOConfig(enable=True)))
    out = sched.run(reqs)
    assert len(sched.finished) == len(reqs)
    for r in reqs:
        st = sched.finished[r.req_id]
        if (r.slo or DEFAULT_SLO).priority_class == "batch":
            assert st.status == DONE
            assert len(out[r.req_id]) == r.max_new_tokens
    sched.close()


def test_slo_beats_fifo_goodput_and_ttft_at_3x_overload(model_and_params):
    """On one annotated trace at 3x the scheduler's service capacity, the
    SLO-aware policy beats FIFO on goodput (deadline-met tokens per
    virtual step) and on interactive TTFT attainment. Both run on the
    virtual clock, so the comparison is deterministic."""
    model, params = model_and_params
    max_batch, max_seq, chunk = 4, 64, 8
    prompt_lens, new_tokens = (4, 16), (8, 24)
    # service capacity: slots over the mean steps a request holds one
    # (prefill chunks plus decode steps)
    mean_steps = sum(prompt_lens) / 2 / chunk + sum(new_tokens) / 2
    trace = poisson_trace(
        16, rate=3 * max_batch / mean_steps, vocab_size=CFG.vocab_size,
        prompt_lens=prompt_lens, new_tokens=new_tokens, prompt_quantum=4,
        interactive_fraction=0.35,
        interactive_slo=SLOSpec("interactive", ttft_deadline=10.0),
        batch_slo=SLOSpec("batch"), seed=11)
    results = []
    for slo in (SLOConfig(), SLOConfig(enable=True)):        # FIFO, SLO
        with HyperOffloadSession(OffloadConfig(
                max_batch=max_batch, max_seq=max_seq, prefill_budget=2,
                chunk_size=chunk, slo=slo)) as session:
            sched = session.scheduler(model, params)
            sched.run(list(trace))
            att = attainment_summary(sched.finished.values())
            results.append((att["met_tokens"] / sched.now,
                            att["classes"]["interactive"]["ttft_attainment"]))
    (fifo_goodput, fifo_ttft), (slo_goodput, slo_ttft) = results
    assert slo_goodput > fifo_goodput
    assert slo_ttft > fifo_ttft


def test_infeasible_request_shed_before_admission(model_and_params):
    """A TTFT deadline no admission could meet — 24 prompt tokens at 4
    per step (boost disabled) cannot land a first token inside 3 steps —
    is shed at the queue: no slot, no prefill tokens, no output, and the
    attainment summary books it as a deadline miss, not a
    disappearance."""
    model, params = model_and_params
    doomed = Request(tokens=np.ones((24,), np.int32), max_new_tokens=4,
                     arrival=0.0, seed=0,
                     slo=SLOSpec("interactive", ttft_deadline=3.0))
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, chunk_size=4,
                        slo=SLOConfig(enable=True, max_prefill_boost=1.0)))
    out = sched.run([doomed])
    st = sched.finished[doomed.req_id]
    assert st.status == SHED and st.t_done is not None
    assert out[doomed.req_id].size == 0
    assert sched.stats.shed == 1 and sched.stats.prefill_tokens == 0
    att = attainment_summary([st])
    assert att["shed"] == 1 and att["met_tokens"] == 0
    assert att["classes"]["interactive"]["ttft_attainment"] == 0.0
    sched.close()


def test_shed_disabled_admits_and_misses(model_and_params):
    """With shed_infeasible=False the same doomed request is admitted,
    served in full, and booked as a miss — tokens flow, goodput doesn't."""
    model, params = model_and_params
    doomed = Request(tokens=np.ones((24,), np.int32), max_new_tokens=4,
                     arrival=0.0, seed=0,
                     slo=SLOSpec("interactive", ttft_deadline=3.0))
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, chunk_size=4,
                        slo=SLOConfig(enable=True, shed_infeasible=False,
                                      max_prefill_boost=1.0)))
    out = sched.run([doomed])
    assert sched.stats.shed == 0
    assert len(out[doomed.req_id]) == 4
    snap = sched.slo_snapshot()
    assert snap["missed_requests"] == 1 and snap["goodput_tokens"] == 0
    sched.close()


# ---------------------------------------------------------------------------
# config + session wiring
# ---------------------------------------------------------------------------


def test_offload_config_slo_round_trip_and_mode_gate():
    cfg = OffloadConfig(slo=SLOConfig(enable=True, max_prefill_boost=2.0,
                                      max_preempt_per_step=2))
    back = OffloadConfig.from_dict(cfg.to_dict())
    assert back.slo == cfg.slo and back.slo.max_preempt_per_step == 2
    assert OffloadConfig().slo == SLOConfig()      # default: disabled
    # both modes serve through the scheduler; a retired mode is refused
    for mode in ("resident", "kv_offload"):
        assert OffloadConfig(mode=mode, slo=SLOConfig(enable=True)).slo.enable
    for mode in ("paged", "continuous"):
        with pytest.raises(ValueError, match=f"'{mode}' not in"):
            OffloadConfig(mode=mode, slo=SLOConfig(enable=True))


def test_session_slo_stats_exposed(model_and_params):
    """The front door: session-built schedulers run the SLO policy and
    ``session.stats()['sched']`` carries the preemption/shed/goodput
    counters the launchers and benchmark report."""
    model, params = model_and_params
    rng = np.random.default_rng(12)
    reqs = [
        Request(tokens=rng.integers(0, CFG.vocab_size, 5, dtype=np.int32),
                max_new_tokens=10, arrival=0.0, seed=0,
                slo=SLOSpec("batch")),
        Request(tokens=rng.integers(0, CFG.vocab_size, 4, dtype=np.int32),
                max_new_tokens=3, arrival=3.0, seed=1,
                slo=SLOSpec("interactive", ttft_deadline=2.0)),
    ]
    session = HyperOffloadSession(OffloadConfig(
        max_batch=1, max_seq=MAX_SEQ,
        slo=SLOConfig(enable=True)))
    sched = session.scheduler(model, params)
    sched.run(reqs)
    s = session.stats()["sched"]
    assert s["preemptions"] == 1 and s["resumes"] == 1 and s["shed"] == 0
    assert s["slo"]["goodput_tokens"] == 13      # both requests met
    assert s["slo"]["met_requests"] == 2
    assert s["slo"]["missed_requests"] == 0
    session.close()


def test_slo_disabled_keeps_fifo_counters_zero(model_and_params):
    """Without slo.enable the scheduler is byte-for-byte the FIFO path:
    no goodput controller, zero preemption/shed counters, no slo block in
    the session snapshot."""
    model, params = model_and_params
    session = HyperOffloadSession(OffloadConfig(
        max_batch=1, max_seq=MAX_SEQ))
    sched = session.scheduler(model, params)
    sched.run([Request(tokens=np.ones((4,), np.int32), max_new_tokens=2,
                       slo=SLOSpec("interactive", ttft_deadline=1.0))])
    assert sched.slo_snapshot() is None
    s = session.stats()["sched"]
    assert s["preemptions"] == 0 and s["shed"] == 0
    assert "slo" not in s
    session.close()
