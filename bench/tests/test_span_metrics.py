"""The scheduler's span metrics (``queue_wait_p90_ms``, ``prefill_p90_ms``,
``device_wait_ms_per_step``, ``host_self_ms_per_step``) on a synthetic
reading, and in a traced run of the chat cell at a tiny size."""

from __future__ import annotations

import types

import numpy as np
import pytest

import run

SPAN_METRICS = ("queue_wait_p90_ms", "prefill_p90_ms",
                "device_wait_ms_per_step", "host_self_ms_per_step")


def _read(name, ctx):
    return run.reader(run.ROOT, name)(ctx)


def _ctx(spans, n_steps):
    """A reading whose window holds ``spans`` (name, (start, end)) in
    seconds and ``n_steps`` steps. As the harness gives them, every span
    here ends inside the window."""
    return types.SimpleNamespace(spans=spans, steps=[None] * n_steps)


def _at(name, durations_s, t0=100.0):
    return [(name, (t0 + i, t0 + i + d)) for i, d in enumerate(durations_s)]


@pytest.mark.parametrize("metric,span", [("queue_wait_p90_ms",
                                          "request.queue"),
                                         ("prefill_p90_ms",
                                          "request.prefill")])
def test_request_p90_is_the_90th_percentile_of_its_spans(metric, span):
    durations = [0.010 * k for k in range(1, 11)]   # 10 .. 100 ms
    other = "request.prefill" if span == "request.queue" else "request.queue"
    ctx = _ctx(_at(span, durations) + _at(other, [5.0] * 4)
               + _at("step", [9.0]), 3)
    # linear interpolation between the 9th and 10th of ten: 91 ms
    assert _read(metric, ctx) == pytest.approx(91.0)
    assert _read(metric, ctx) == pytest.approx(
        np.percentile([1e3 * d for d in durations], 90))


def test_step_metrics_split_the_step_into_host_and_device_wait():
    steps = _at("step", [0.050, 0.060])
    waits = [("device_wait", (100.010, 100.050)),
             ("device_wait", (101.001, 101.003)),
             ("device_wait", (101.020, 101.058))]
    ctx = _ctx(steps + waits + _at("dispatch", [0.5]), 2)
    assert _read("device_wait_ms_per_step", ctx) == pytest.approx(40.0)
    assert _read("host_self_ms_per_step", ctx) == pytest.approx(15.0)


def test_spans_ending_outside_the_window_are_not_read():
    """The harness hands readers only the spans that end inside the
    window (``Window.inside`` of the span's end), so a span that ends
    before the open or after the close moves no metric."""
    import endtoend
    window = endtoend.Window(10.0, 20.0, [], {})
    events = [("request.queue", (11.0, 11.2)),
              ("request.queue", (19.0, 25.0)),     # ends after the close
              ("request.queue", (5.0, 9.0)),       # ends before the open
              ("step", (12.0, 12.05)), ("device_wait", (12.0, 12.04)),
              ("step", (19.99, 20.5)), ("device_wait", (19.99, 20.4))]
    spans = [(n, iv) for n, iv in events if window.inside(iv[1])]
    ctx = _ctx(spans, 1)
    assert _read("queue_wait_p90_ms", ctx) == pytest.approx(200.0)
    assert _read("device_wait_ms_per_step", ctx) == pytest.approx(40.0)
    assert _read("host_self_ms_per_step", ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_nothing_to_read_gives_none(metric):
    """A program without these spans (the parent of the change that adds
    them) reads as nothing, not as 0."""
    assert _read(metric, _ctx([], 5)) is None
    # the parent's spans: step phases, no request or device_wait spans
    assert _read(metric, _ctx(_at("step", [0.05]) + _at("decode", [0.04]),
                              1)) is None


def test_traced_chat_run_reports_span_metrics(tiny_root, on_cpu):
    res = on_cpu.run(on_cpu.parse(
        ["--workload", "phi3-resident-chat", "--seed", str(2 ** 31 + 77),
         "--seconds", "2", "--trace", "1"]), root=tiny_root)
    assert res["correct"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(SPAN_METRICS) <= set(got)
    assert all(got[m] > 0 for m in SPAN_METRICS)
