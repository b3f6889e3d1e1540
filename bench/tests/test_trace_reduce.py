"""The trace reducer: busy time as a union of op intervals, idle share,
gap attribution to the scheduler's phases, program names, device time by
named scope, and the reading of the profiler's file."""

from __future__ import annotations

import json
import random
import time
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def op(start, end, name="%fusion", plane="/device:TPU:0", module=False):
    return tr.Op(plane, name, start, end, module)


def test_union_merges_overlaps():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]


def test_busy_is_the_union_clipped_to_the_window():
    ops = [op(0.0, 1.0), op(0.5, 1.5), op(3.0, 4.0), op(9.0, 12.0),
           op(0.0, 4.0, "jit_decode_step(77)", module=True)]
    r = tr.reduce(tr.Clock(0.0), ops, (0.0, 10.0), [])
    assert r.busy_s == pytest.approx(1.5 + 1.0 + 1.0)
    assert r.window_s == 10.0
    assert r.idle_share == pytest.approx(1 - 3.5 / 10)
    assert r.device_ops == [("jit_decode_step", 4.0)]


def test_gaps_are_named_by_the_phase_covering_most_of_them():
    ops = [op(0.0, 1.0), op(2.0, 3.0), op(6.0, 7.0)]
    spans = [("decode", (0.9, 1.2)), ("collect", (1.2, 2.0)),
             ("park_issue", (3.0, 5.5)), ("step", (0.0, 7.0))]
    r = tr.reduce(tr.Clock(0.0), ops, (0.0, 8.0), spans)
    # gaps: 1-2 (collect), 3-6 (park_issue), 7-8 (no phase)
    assert r.idle_gaps == [("park_issue", 3.0), ("collect", 1.0),
                           ("between_steps", 1.0)]


def test_clock_offset_moves_the_window():
    ops = [op(100.0, 101.0)]
    r = tr.reduce(tr.Clock(100.0), ops, (0.0, 2.0), [])
    assert r.busy_s == pytest.approx(1.0)


def test_no_device_op_reads_nothing():
    assert tr.reduce(tr.Clock(0.0), [op(5.0, 6.0)], (0.0, 1.0), []) is None


def _recorded(name):
    """(clock, ops, window, spans) of the profile ``data/<name>.*``
    recorded on a v5e. The device's clock reads about 1.5 ms ahead of the
    marker's mapping, so the window is widened by 5 ms before it opens."""
    meta = json.loads((DATA / f"{name}.json").read_text())
    clock, ops = tr.read_trace(str(DATA / f"{name}.xplane.pb"),
                               meta["marker"])
    spans = [(n, (a, b)) for n, a, b in meta.get("spans", [])]
    lo, hi = meta["window"]
    return clock, ops, (lo - 0.005, hi), spans


def test_recorded_tpu_trace():
    """``data/tiny_tpu.*``: three programs, each followed by 50 ms of host
    sleep inside a ``park_issue`` span."""
    clock, ops, window, spans = _recorded("tiny_tpu")
    r = tr.reduce(clock, ops, window, spans)
    modules = [o for o in ops if o.module]
    assert len(modules) == 3
    busy = sum(e - s for s, e in tr.union(
        (o.start, o.end) for o in ops if not o.module))
    assert r.busy_s == pytest.approx(busy)
    assert 1e-4 < r.busy_s < 1e-3
    assert r.device_ops[0][0] == "jit__lambda"
    assert r.idle_share > 0.99
    assert [n for n, _ in r.idle_gaps[:3]] == ["park_issue"] * 3
    assert all(0.045 < s < 0.06 for _, s in r.idle_gaps[:3])


# -- naming only the gaps reported ---------------------------------------


def _all_gaps_named(clock, ops, window, spans, top=10):
    """The idle gaps as the reduction once found them: every gap of the
    first device named, then the ``top`` longest kept, ties in time
    order."""
    lo, hi = clock.to_trace(window[0]), clock.to_trace(window[1])
    inside = [o for o in ops if not o.module and o.end > lo and o.start < hi]
    first = min(o.plane for o in inside)
    busy = tr.union((max(o.start, lo), min(o.end, hi))
                    for o in inside if o.plane == first)
    traced = [(n, (clock.to_trace(a), clock.to_trace(b)))
              for n, (a, b) in spans if n in tr.PHASES]
    return sorted(((tr.name_gap(g, traced), g[1] - g[0])
                   for g in tr.gaps(busy, lo, hi)),
                  key=lambda kv: -kv[1])[:top]


def _random_reading(seed):
    """Ops on two devices and phase spans on a coarse grid, so that many
    gaps are equally long and spans overlap gaps partly, fully, or not."""
    rng = random.Random(seed)
    ops, t = [], 1.0
    for _ in range(rng.randint(1, 60)):
        t += rng.choice([0, 1, 1, 2, 3])
        d = rng.choice([0.5, 1, 2])
        plane = rng.choice(["/device:TPU:0", "/device:TPU:1"])
        ops.append(op(t, t + d, plane=plane))
        t += d
    names = tr.PHASES + ("step", "dispatch")
    spans = []
    for _ in range(rng.randint(0, 40)):
        a = rng.choice(range(0, int(t) + 2)) + rng.choice([0, 0.25, 0.5])
        spans.append((rng.choice(names), (a, a + rng.choice([0.5, 1, 3]))))
    window = (rng.choice([-1.0, 0.0, 0.5]), t + rng.choice([0.0, 2.0]))
    return tr.Clock(rng.choice([0.0, 0.25])), ops, window, spans


@pytest.mark.parametrize("top", [1, 3, 10])
@pytest.mark.parametrize("seed", range(60))
def test_naming_the_reported_gaps_equals_naming_all(seed, top):
    clock, ops, window, spans = _random_reading(seed)
    r = tr.reduce(clock, ops, window, spans, top=top)
    assert r.idle_gaps == _all_gaps_named(clock, ops, window, spans, top)


def test_equal_gaps_keep_their_time_order():
    ops = [op(1.0, 2.0), op(3.0, 4.0), op(5.0, 6.0)]
    spans = [("collect", (0.0, 1.0)), ("decode", (2.0, 3.0)),
             ("park_issue", (4.0, 5.0))]
    r = tr.reduce(tr.Clock(0.0), ops, (0.0, 7.0), spans, top=3)
    assert r.idle_gaps == [("collect", 1.0), ("decode", 1.0),
                           ("park_issue", 1.0)]


@pytest.mark.parametrize("name", ["tiny_tpu", "scoped_tpu"])
def test_recorded_gaps_equal_naming_all(name):
    clock, ops, window, spans = _recorded(name)
    r = tr.reduce(clock, ops, window, spans)
    assert r.idle_gaps == _all_gaps_named(clock, ops, window, spans)


def test_200k_gaps_reduce_in_under_2_s():
    """A traced window of about 2,000 steps has that many idle gaps
    between ops; naming each against every phase span grew as their
    product."""
    ops = [op(2.0 * i, 2.0 * i + 1.0) for i in range(200_000)]
    spans = [(tr.PHASES[i % 4], (200.0 * i, 200.0 * i + 150.0))
             for i in range(2_000)]
    t = time.perf_counter()
    r = tr.reduce(tr.Clock(0.0), ops, (0.0, 400_000.0), spans)
    assert time.perf_counter() - t < 2.0
    assert len(r.idle_gaps) == 10
    assert r.busy_s == pytest.approx(200_000.0)


# -- device time by named scope -------------------------------------------


def test_scope_path_drops_the_op():
    assert tr.scope_path("jit(step)/outer/inner/dot_general:") == \
        ("jit(step)", "outer", "inner")
    assert tr.scope_path("jit(step)/k/pallas_call") == ("jit(step)", "k")
    assert tr.scope_path("reduce_window_sum:") == ()


def test_scope_seconds_union_overlaps_and_average_devices():
    a, b = ("jit(f)", "outer"), ("jit(f)", "outer", "inner")
    ops = [tr.Op("/device:TPU:0", "%x", 0.0, 2.0, False, a),
           tr.Op("/device:TPU:0", "%y", 1.0, 3.0, False, b),   # overlaps x
           tr.Op("/device:TPU:0", "%z", 8.0, 12.0, False, b),  # clipped
           tr.Op("/device:TPU:1", "%y", 0.0, 1.0, False, b),
           tr.Op("/device:TPU:0", "jit_f(1)", 0.0, 3.0, True)]
    r = tr.reduce(tr.Clock(0.0), ops, (0.0, 10.0), [])
    assert r.scope_s["outer"] == pytest.approx((3.0 + 2.0 + 1.0) / 2)
    assert r.scope_s["inner"] == pytest.approx((2.0 + 2.0 + 1.0) / 2)
    assert r.scope_s["jit(f)"] == r.scope_s["outer"]
    assert set(r.scope_s) == {"jit(f)", "outer", "inner"}


def test_recorded_scopes():
    """``data/scoped_tpu.*`` (``data/record_scoped.py``): a step with a
    matmul in ``bench_outer`` and, inside ``bench_inner``, a second matmul
    and the Pallas kernel ``bench_add_one``, run three times."""
    meta = json.loads((DATA / "scoped_tpu.json").read_text())
    clock, ops, window, spans = _recorded("scoped_tpu")
    r = tr.reduce(clock, ops, window, spans)
    outer, inner = meta["scopes"]
    kernel = meta["kernel"]
    for scope in (outer, inner, kernel):
        assert r.scope_s[scope] > 0, scope
    # an op counts toward every scope on its path, once
    assert r.scope_s[outer] > r.scope_s[inner] > r.scope_s[kernel]
    kernel_ops = [o for o in ops if kernel in o.scopes]
    assert len(kernel_ops) == 3
    assert all({outer, inner} <= set(o.scopes) for o in kernel_ops)
    lo, hi = clock.to_trace(window[0]), clock.to_trace(window[1])
    for scope in (outer, inner, kernel):
        want = sum(e - s for s, e in tr.union(
            (max(o.start, lo), min(o.end, hi)) for o in ops
            if scope in o.scopes and o.end > lo and o.start < hi))
        assert r.scope_s[scope] == pytest.approx(want)
    assert r.scope_s[outer] <= r.busy_s


@pytest.mark.parametrize("name", ["tiny_tpu", "scoped_tpu"])
def test_read_trace_gives_what_profile_data_gives(name):
    """The file read with ``bench/xplane.py`` gives the ops, times and
    marker that ``jax.profiler.ProfileData`` gives."""
    from jax.profiler import ProfileData
    meta = json.loads((DATA / f"{name}.json").read_text())
    path = str(DATA / f"{name}.xplane.pb")
    clock, ops = tr.read_trace(path, meta["marker"])
    want, marker = [], None
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if plane.name.startswith("/host:") and marker is None \
                        and ev.name == tr.MARKER:
                    marker = ev.start_ns * 1e-9
                if plane.name.startswith("/device:TPU") and line.name in (
                        tr.OPS_LINE, tr.MODULES_LINE):
                    s = ev.start_ns * 1e-9
                    want.append((plane.name, ev.name, s,
                                 s + ev.duration_ns * 1e-9,
                                 line.name == tr.MODULES_LINE))
    assert [(o.plane, o.name, o.start, o.end, o.module) for o in ops] == want
    assert clock.offset == marker - meta["marker"]
