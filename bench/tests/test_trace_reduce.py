"""The trace reducer: busy time as a union of op intervals, idle share,
gap attribution to the scheduler's phases, and program names."""

from __future__ import annotations

import pytest

import trace_reduce as tr


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


def test_recorded_tpu_trace():
    """A profile recorded on a v5e (``data/tiny_tpu.*``): three programs,
    each followed by 50 ms of host sleep inside a ``park_issue`` span. The
    device's clock reads about 1.5 ms ahead of the marker's mapping, so the
    window is widened by 5 ms before it opens."""
    import json
    from pathlib import Path
    data = Path(__file__).resolve().parent / "data"
    meta = json.loads((data / "tiny_tpu.json").read_text())
    clock, ops = tr.read_trace(str(data / "tiny_tpu.xplane.pb"),
                               meta["marker"])
    spans = [(n, (a, b)) for n, a, b in meta["spans"]]
    lo, hi = meta["window"]
    r = tr.reduce(clock, ops, (lo - 0.005, hi), spans)
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
