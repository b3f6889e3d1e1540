"""The `repro.obs` telemetry subsystem: tracer ring semantics, Chrome
trace export + schema checker, metrics registry / Prometheus exposition,
the overlap analyzer's hidden-vs-exposed decomposition and its exact
agreement with `TransferStats`, and the front-door wiring (telemetry on:
one shared tracer, contiguous wall-clock request spans, step sub-spans
nested in their phases, latency histograms in seconds, the phases in a
`jax.profiler` capture; telemetry off: zero events, no annotations,
`session.stats()` unchanged in shape, identical tokens)."""

import glob
import json
import time

import jax
import numpy as np
import pytest

from repro.api import HyperOffloadSession, OffloadConfig
from repro.api.config import TelemetryConfig
from repro.api.session import _weighted_plan_lead
from repro.configs import REGISTRY
from repro.models.model import build_model
from repro.obs import (
    NULL_TRACER, MetricsRegistry, OverlapAnalyzer, TraceEvent, Tracer,
)
from repro.obs.check import validate_events, validate_file
from repro.pool.transfer import TransferEngine
from repro.sched import Request

CFG = REGISTRY["phi3-mini-3.8b"].reduced()
#: the scheduler's step span and its phases: the names the serving
#: benchmark's readers and ``session._overlap_window_s`` match
PHASES = ("admit_prefill", "collect", "decode", "park_issue")
RESERVED = ("step",) + PHASES
SUB_SPANS = ("dispatch", "device_wait")


@pytest.fixture(scope="module")
def model_and_params():
    m = build_model(CFG)
    return m, m.init(jax.random.key(0))


def _trace(requests=3, **telemetry):
    # chunk_size=6 (not 8): test_sched's compile-count test asserts a
    # jit-cache DELTA for chunk_size=8, and the chunk entry point is
    # cached per model config, shared across test modules.
    return OffloadConfig(
        mode="kv_offload", max_batch=2, max_seq=32, chunk_size=6,
        telemetry=TelemetryConfig(enable=True, **telemetry))


def _run_requests(session, model_and_params, n=3):
    """Run n requests; outputs keyed by submission index (req_ids come
    from a global counter, so they differ run to run)."""
    model, params = model_and_params
    sched = session.scheduler(model, params)
    reqs = [Request(tokens=np.arange(4 + 2 * i) % CFG.vocab_size,
                    max_new_tokens=3, seed=i) for i in range(n)]
    out = sched.run(reqs)
    return {i: out[r.req_id] for i, r in enumerate(reqs)}, sched


# ---------------------------------------------------------------------------
# tracer ring
# ---------------------------------------------------------------------------


def test_ring_eviction_keeps_newest():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant("t", f"e{i}")
    evs = tr.events()
    assert [e.name for e in evs] == ["e6", "e7", "e8", "e9"]
    assert tr.dropped == 6
    assert tr.snapshot() == {"events": 4, "dropped": 6, "capacity": 4}


def test_span_end_ge_start():
    tr = Tracer()
    with tr.span("t", "work", tag=1):
        time.sleep(0.001)
    (ev,) = tr.events()
    assert ev.ph == "X" and ev.end >= ev.ts and ev.dur >= 0.001
    assert ev.args == {"tag": 1}
    # a negative duration fed directly is clamped, never exported
    tr.complete("t", "clamped", tr.now(), -1.0)
    assert tr.events()[-1].dur == 0.0


def test_exported_trace_is_valid_chrome_json(tmp_path):
    tr = Tracer()
    with tr.span("sched", "step", step=0):
        tr.instant("request", "QUEUED", {"req": 1})
    path = str(tmp_path / "trace.json")
    tr.export(path)
    with open(path) as f:
        obj = json.load(f)
    assert validate_events(obj) == []
    phases = {e["ph"] for e in obj["traceEvents"]}
    assert "M" in phases and "X" in phases and "i" in phases
    # timestamps are rebased to the tracer epoch in microseconds
    data_events = [e for e in obj["traceEvents"] if e["ph"] != "M"]
    assert all(e["ts"] >= 0 for e in data_events)


def test_null_tracer_emits_nothing():
    nt = NULL_TRACER
    assert nt.enabled is False
    nt.instant("t", "x")
    nt.complete("t", "x", 0.0, 1.0)
    with nt.span("t", "x", a=1), nt.step_span("t", "step", 3):
        pass
    assert nt.events() == [] and len(nt) == 0


# ---------------------------------------------------------------------------
# schema checker rejects corrupt traces
# ---------------------------------------------------------------------------


def test_checker_rejects_corrupt_traces():
    assert validate_events([1, 2]) != []
    assert validate_events({"nope": []}) != []
    bad_ph = {"traceEvents": [
        {"name": "x", "ph": "Q", "ts": 0, "pid": 1, "tid": 0}]}
    assert any("ph" in e for e in validate_events(bad_ph))
    neg_dur = {"traceEvents": [
        {"name": "x", "ph": "X", "ts": 5.0, "dur": -2.0, "pid": 1,
         "tid": 0}]}
    assert any("end < start" in e for e in validate_events(neg_dur))
    empty = {"traceEvents": [
        {"name": "x", "ph": "i", "ts": 0, "pid": 1, "tid": 0, "s": "t"}]}
    assert any("no complete spans" in e for e in validate_events(empty))
    anonymous = {"traceEvents": [
        {"name": "request.queue", "cat": "request", "ph": "X", "ts": 0,
         "dur": 1.0, "pid": 1, "tid": 0}]}
    assert any("args.req" in e for e in validate_events(anonymous))


def test_checker_wait_ordering():
    def span(name, ts, dur, args):
        return {"name": name, "cat": "transfer", "ph": "X", "ts": ts,
                "dur": dur, "pid": 1, "tid": 0, "args": args}
    # a wait must never resolve before its transfer completes
    obj = {"traceEvents": [
        span("transfer", 1000.0, 500.0, {"seq": 1}),
        span("transfer.wait", 100.0, 50.0, {"seq": 1, "hit": False}),
    ]}
    errs = validate_events(obj)
    assert any("before its transfer completed" in e for e in errs)
    # an overlapped (hit) wait must start after the transfer completed
    obj = {"traceEvents": [
        span("transfer", 1000.0, 500.0, {"seq": 1}),
        span("transfer.wait", 1200.0, 400.0, {"seq": 1, "hit": True}),
    ]}
    errs = validate_events(obj)
    assert any("before the transfer completed" in e for e in errs)
    # a BLOCKED wait starting before the transfer span is legal: the span
    # covers execution only, so queue time puts wait-start ahead of it
    obj = {"traceEvents": [
        span("transfer", 1000.0, 500.0, {"seq": 1}),
        span("transfer.wait", 100.0, 1400.0, {"seq": 1, "hit": False}),
    ]}
    assert validate_events(obj) == []


def test_checker_validate_file_unreadable(tmp_path):
    p = tmp_path / "junk.json"
    p.write_text("{not json")
    assert any("not readable" in e for e in validate_file(str(p)))


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_instruments_and_prometheus():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", help="requests")
    c.inc()
    c.inc(2)
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("reqs_total") is c      # idempotent getter
    reg.gauge("depth").set(7)
    h = reg.histogram("lat", (1, 2, 4))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["count"] == 4 and snap["buckets"] == {1: 1, 2: 2, 4: 3}
    assert snap["sum"] == pytest.approx(105.0)
    with pytest.raises(ValueError):
        reg.histogram("lat", (1, 2, 8))        # bucket mismatch
    reg.register_collector("pool", lambda: {"puts": 3, "tier": {"used": 9},
                                            "name": "host", "ok": True})
    text = reg.render_prometheus()
    assert "# TYPE reqs_total counter" in text
    assert "reqs_total 3" in text
    assert 'lat_bucket{le="4"} 3' in text
    assert 'lat_bucket{le="+Inf"} 4' in text
    assert "pool_puts 3" in text and "pool_tier_used 9" in text
    # strings and bools never become samples
    assert "pool_name" not in text and "pool_ok" not in text
    assert reg.collect() == {"pool": {"puts": 3, "tier": {"used": 9},
                                      "name": "host", "ok": True}}


def test_histogram_requires_ascending_buckets():
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("bad", (4, 2, 1))
    with pytest.raises(ValueError):
        MetricsRegistry().histogram("bad", ())


# ---------------------------------------------------------------------------
# plan-lead aggregation (the stats() weighting fix)
# ---------------------------------------------------------------------------


def test_weighted_plan_lead():
    # a 1-step scheduler must not pull a 99-step scheduler's figure toward
    # itself the way the old unweighted mean of means did
    assert _weighted_plan_lead([(99, 2.0), (1, 10.0)]) == \
        pytest.approx((99 * 2.0 + 10.0) / 100)
    assert _weighted_plan_lead([(0, 3.0), (0, 5.0)]) == pytest.approx(4.0)
    assert _weighted_plan_lead([(5, 1.5)]) == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# overlap analyzer on synthetic traces
# ---------------------------------------------------------------------------


def _transfer_events():
    """Two transfers: seq 1 waited-blocked (0.2s exposed of 1.0 inflight),
    seq 2 never waited (fully hidden, 0.5s), plus one sched step span
    containing the wait."""
    return [
        TraceEvent("sched", "step", "X", 0.0, 2.0, args={"step": 0}),
        TraceEvent("transfer", "transfer", "X", 0.0, 1.0,
                   args={"seq": 1, "src": "host", "dst": "device"}),
        TraceEvent("transfer", "transfer.wait", "X", 0.8, 0.2,
                   args={"seq": 1, "hit": False}),
        TraceEvent("transfer", "transfer", "X", 0.5, 0.5,
                   args={"seq": 2, "src": "remote", "dst": "device"}),
    ]


def test_overlap_decomposition():
    rep = OverlapAnalyzer(_transfer_events()).report()
    assert rep["transfers"] == 2
    assert rep["waits_blocked"] == 1 and rep["waits_overlapped"] == 0
    assert rep["exposed_s"] == pytest.approx(0.2)
    assert rep["hidden_s"] == pytest.approx(0.8 + 0.5)
    assert rep["hidden_fraction"] == pytest.approx(1.3 / 1.5)
    assert rep["inflight_s"] == pytest.approx(1.5)
    assert rep["by_tier"]["host->device"]["exposed_s"] == pytest.approx(0.2)
    assert rep["by_tier"]["remote->device"]["hidden_fraction"] == 1.0
    # both transfers land in step 0 (wait time / issue time attribution)
    (step0,) = rep["by_step"]
    assert step0["step"] == 0 and step0["transfers"] == 2


def test_overlap_validate_against_stats():
    an = OverlapAnalyzer(_transfer_events())
    good = {"waits_overlapped": 0, "waits_blocked": 1, "blocked_s": 0.2}
    assert an.validate(good) == []
    bad = {"waits_overlapped": 3, "waits_blocked": 1, "blocked_s": 0.9}
    errs = an.validate(bad)
    assert any("waits_overlapped" in e for e in errs)
    assert any("blocked_s" in e for e in errs)


def test_overlap_orphan_waits():
    evs = [TraceEvent("transfer", "transfer.wait", "X", 0.8, 0.2,
                      args={"seq": 99, "hit": False})]
    an = OverlapAnalyzer(evs)
    assert an.orphan_waits == 1
    # with ring drops only the total wait count can be checked
    assert an.validate({"waits_overlapped": 1, "waits_blocked": 0,
                        "blocked_s": 0.0}) == []
    errs = an.validate({"waits_overlapped": 5, "waits_blocked": 2,
                        "blocked_s": 0.0})
    assert any("total waits" in e for e in errs)


def test_overlap_hidden_fraction_none_without_time():
    assert OverlapAnalyzer([]).report()["hidden_fraction"] is None


# ---------------------------------------------------------------------------
# per-handle ordering through a real TransferEngine
# ---------------------------------------------------------------------------


def test_transfer_engine_handle_ordering():
    tr = Tracer()
    eng = TransferEngine(depth=4, tracer=tr)
    try:
        h_slow = eng.submit(lambda: time.sleep(0.01) or "a", key="slow",
                            src="host", dst="device")
        h_fast = eng.submit(lambda: "b", key="fast")
        time.sleep(0.05)          # let 'fast' complete before its wait
        assert h_fast.wait() == "b" and h_slow.wait() == "a"
        h_fast.wait()             # idempotent: no second wait span
    finally:
        eng.close()
    evs = tr.events()
    transfers = {e.args["seq"]: e for e in evs if e.name == "transfer"}
    waits = {e.args["seq"]: e for e in evs if e.name == "transfer.wait"}
    assert len(transfers) == 2 and len(waits) == 2
    assert waits[h_fast.seq].args["hit"] is True
    eps = 1e-4
    for seq, w in waits.items():
        t = transfers[seq]
        assert t.end >= t.ts                      # issue <= complete
        assert w.end + eps >= t.end               # wait resolves after done
        assert w.ts + eps >= t.ts                 # wait starts after issue
    # the trace's exposed time IS blocked_s — same measurement, recorded
    # once — so the agreement is exact, not approximate
    errs = OverlapAnalyzer(evs).validate(eng.stats.snapshot(), tol_s=1e-9)
    assert errs == []


# ---------------------------------------------------------------------------
# front-door wiring (session-level, tiny model)
# ---------------------------------------------------------------------------


def test_session_telemetry_end_to_end(model_and_params, tmp_path):
    path = str(tmp_path / "trace.json")
    with HyperOffloadSession(_trace(trace_path=path)) as s:
        out, sched = _run_requests(s, model_and_params)
        st = s.stats()
        # the overlap decomposition agrees with the engine's own counters
        errs = OverlapAnalyzer.from_tracer(s.tracer).validate(
            s.pool.snapshot()["transfer"])
        assert errs == []
        rep = s.overlap()
        assert rep["transfers"] > 0 and rep["hidden_fraction"] is not None
        # request spans: one queue, prefill and decode span per request
        req_spans = [e for e in s.tracer.events() if e.cat == "request"]
        names = [e.name for e in req_spans]
        for name in ("request.queue", "request.prefill", "request.decode"):
            assert names.count(name) == len(out)
        # step phases + pool traffic + per-request histograms all present
        cats = {(e.cat, e.name) for e in s.tracer.events()}
        assert ("sched", "step") in cats and ("pool", "put") in cats
        hists = st["telemetry"]["histograms"]["histograms"]
        for name in ("req_ttft_seconds", "req_queue_wait_seconds",
                     "req_tpot_seconds"):
            assert hists[name]["count"] == len(out)
        # TTFT in seconds is the request's queue and prefill spans
        assert hists["req_ttft_seconds"]["sum"] == pytest.approx(sum(
            e.dur for e in req_spans if e.name != "request.decode"))
        assert "req_ttft_seconds_bucket" in s.stats_text()
    # close() exported to telemetry.trace_path; the file passes the checker
    assert validate_file(path) == []


def _request_spans(events):
    """Each request's ``request.*`` spans in time order."""
    out = {}
    for e in events:
        if e.cat == "request" and e.ph == "X":
            out.setdefault(e.args["req"], []).append(e)
    return {r: sorted(v, key=lambda e: e.ts) for r, v in out.items()}


def _preempting_requests():
    """A batch request running alone in one slot, then an interactive
    arrival whose TTFT deadline preempts it (SLO mode, ``max_batch=1``)."""
    from repro.slo import SLOSpec
    vocab = CFG.vocab_size
    rng = np.random.default_rng(9)
    return [
        Request(tokens=rng.integers(0, vocab, 5, dtype=np.int32),
                max_new_tokens=10, arrival=0.0, seed=0,
                slo=SLOSpec("batch")),
        Request(tokens=rng.integers(0, vocab, 4, dtype=np.int32),
                max_new_tokens=3, arrival=3.0, seed=1,
                slo=SLOSpec("interactive", ttft_deadline=2.0)),
    ]


def _slo_config():
    from repro.slo import SLOConfig
    return OffloadConfig(mode="continuous", max_batch=1, max_seq=32,
                         slo=SLOConfig(enable=True),
                         telemetry=TelemetryConfig(enable=True))


@pytest.mark.parametrize("preempting", [False, True])
def test_request_spans_are_contiguous(model_and_params, preempting):
    """Per request the spans tile submit → retire with no gap: queue,
    prefill, decode, and around a preemption a queue span of its own;
    each ``request.prefill`` ends inside a scheduler ``step`` span."""
    model, params = model_and_params
    with HyperOffloadSession(_slo_config() if preempting
                             else _trace()) as s:
        sched = s.scheduler(model, params)
        reqs = (_preempting_requests() if preempting else
                [Request(tokens=np.arange(4 + 2 * i) % CFG.vocab_size,
                         max_new_tokens=3, seed=i) for i in range(3)])
        sched.run(reqs)
        events = s.tracer.events()
    steps = [e for e in events if e.cat == "sched" and e.name == "step"]
    spans = _request_spans(events)
    assert set(spans) == {r.req_id for r in reqs}
    for rid, seq in spans.items():
        st = sched.finished[rid]
        names = [e.name for e in seq]
        assert names.count("request.queue") == 1 + st.preemptions
        assert names[0] == "request.queue" and names[-1] == "request.decode"
        assert seq[0].ts == st.wall_submit
        assert seq[-1].end == pytest.approx(st.wall_done, abs=1e-9)
        for a, b in zip(seq, seq[1:]):
            assert b.ts == pytest.approx(a.end, abs=1e-9)
        for e in seq:
            if e.name == "request.prefill":
                assert any(p.ts <= e.end <= p.end for p in steps)
    if preempting:
        assert sum(st.preemptions for st in sched.finished.values()) == 1


def test_step_sub_spans_nest_in_phases(model_and_params):
    """Every ``dispatch`` and ``device_wait`` span lies inside one of the
    step's phase spans, and the reserved phase names belong to the phase
    spans alone: at most one of each per step."""
    with HyperOffloadSession(_trace()) as s:
        _run_requests(s, model_and_params)
        events = [e for e in s.tracer.events() if e.ph == "X"]
    phases = [e for e in events if e.name in PHASES]
    subs = [e for e in events if e.name in SUB_SPANS]
    assert {e.name for e in subs} == set(SUB_SPANS)
    for e in subs:
        assert e.cat == "sched"
        assert any(p.tid == e.tid and p.ts <= e.ts and e.end <= p.end
                   for p in phases), e
    assert {e.cat for e in events if e.name in RESERVED} == {"sched"}
    steps = [e for e in events if e.name == "step"]
    placed = 0
    for st in steps:
        inside = [p.name for p in phases
                  if st.ts <= p.ts and p.end <= st.end]
        assert all(inside.count(n) <= 1 for n in PHASES), inside
        placed += len(inside)
    assert placed == len(phases)
    assert [p.name for p in phases].count("decode") == len(steps)


@pytest.mark.parametrize("enable", [True, False])
def test_profiler_capture_holds_scheduler_phases(model_and_params, tmp_path,
                                                 enable):
    """Under ``jax.profiler.trace`` the host plane carries the scheduler's
    spans as annotations, on the profiler's clock; telemetry off adds
    none."""
    from jax.profiler import ProfileData
    cfg = OffloadConfig(mode="continuous", max_batch=2, max_seq=32,
                        chunk_size=6,
                        telemetry=TelemetryConfig(enable=enable))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with HyperOffloadSession(cfg) as s:
        with jax.profiler.trace(str(tmp_path), profiler_options=opts):
            _run_requests(s, model_and_params)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    wanted = {"step", "admit_prefill", "decode"} | set(SUB_SPANS)
    if enable:
        assert wanted <= names
    else:
        assert not wanted & names


def test_session_disabled_shape_and_tokens(model_and_params):
    outs = {}
    for enable in (False, True):
        cfg = OffloadConfig(mode="kv_offload", max_batch=2, max_seq=32,
                            chunk_size=6,
                            telemetry=TelemetryConfig(enable=enable))
        with HyperOffloadSession(cfg) as s:
            out, _ = _run_requests(s, model_and_params)
            outs[enable] = {k: list(v) for k, v in out.items()}
            st = s.stats()
            if enable:
                assert "telemetry" in st
            else:
                assert "telemetry" not in st
                assert s.tracer is NULL_TRACER and s.tracer.events() == []
                assert set(st) == {"mode", "pool", "serve", "sched",
                                   "paged", "prefix", "plans_cached"}
                with pytest.raises(RuntimeError):
                    s.export_trace("/tmp/never.json")
                assert s.overlap() is None
    # telemetry is observation only: emitted tokens are identical
    assert outs[False] == outs[True]


def test_session_slo_counters_in_stats_and_prometheus(model_and_params):
    """SLO counters flow end to end: scheduler → session collector →
    ``stats()['sched']`` → the Prometheus text dump, all agreeing — and
    the preempt/resume lifecycle lands in the trace ring as instants."""
    model, params = model_and_params
    with HyperOffloadSession(_slo_config()) as s:
        sched = s.scheduler(model, params)
        sched.run(_preempting_requests())
        st = s.stats()["sched"]
        assert st["preemptions"] == 1 and st["resumes"] == 1
        assert st["shed"] == 0
        assert st["slo"]["goodput_tokens"] == 13
        text = s.stats_text()
        # the flattened collector samples mirror the snapshot numerically
        for line in ("sched_preemptions 1", "sched_resumes 1",
                     "sched_shed 0", "sched_slo_goodput_tokens 13",
                     "sched_slo_met_requests 2"):
            assert line in text, f"{line!r} missing from Prometheus dump"
        # the deadline-relative slack histogram saw the interactive request
        assert "req_ttft_slack_steps_bucket" in text
        # preempt/restore are first-class trace events
        names = [e.name for e in s.tracer.events() if e.cat == "request"]
        assert names.count("PREEMPTED") == 1 and names.count("RESUMED") == 1


def test_telemetry_config_round_trip():
    cfg = OffloadConfig(telemetry=TelemetryConfig(
        enable=True, ring_capacity=128, trace_path="/tmp/t.json"))
    again = OffloadConfig.from_dict(
        json.loads(json.dumps(cfg.to_dict(), default=str)))
    assert again.telemetry == cfg.telemetry
    with pytest.raises(ValueError):
        TelemetryConfig(ring_capacity=0)
