"""The ring decode kernel's metrics (``decode_kv_read_share``,
``decode_attn_roofline``) on a synthetic reading, and the share in a
traced run of the chat cell at a tiny size."""

from __future__ import annotations

import types

import pytest

import flops
import run
import trace_reduce
from conftest import load_config

PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def _read(name, ctx):
    return run.reader(run.ROOT, name)(ctx)


def _ctx(counters=None, scope_s=None, decode_ctx=()):
    trace = None if scope_s is None else trace_reduce.Reduced(
        1.0, 10.0, [], [], {}, scope_s)
    steps = [types.SimpleNamespace(decode_ctx=list(c)) for c in decode_ctx]
    return types.SimpleNamespace(
        counters=counters or {}, trace=trace, steps=steps,
        cfg=load_config("phi3-mini-3.8b"), peaks=PEAKS)


def test_read_share_is_fetched_over_whole_ring_slots():
    ctx = _ctx({"sched.decode_kv_slots_read": 3 * 256 * 32,
                "sched.decode_kv_slots_ring": 8 * 768 * 32})
    assert _read("decode_kv_read_share", ctx) == pytest.approx(12.5)


def test_roofline_is_live_kv_bytes_over_kernel_time_at_bandwidth():
    """phi3 is bound by the bytes: 393,216 B of keys and values a token
    over every layer, here 1,000 keys read in 1 ms."""
    ctx = _ctx(scope_s={"decode_attn": 1e-3, "other": 5.0},
               decode_ctx=[(300, 200), (), (500,)])
    kv = flops.kv_bytes_per_token(ctx.cfg)
    assert kv == 393_216
    assert _read("decode_attn_roofline", ctx) == pytest.approx(
        100 * 1000 * kv / 819e9 / 1e-3)


def test_kernel_name_is_the_programs():
    from repro.kernels.decode_attention import KERNEL_NAME

    import metrics.decode_attn_roofline as m
    assert m.KERNEL == KERNEL_NAME


@pytest.mark.parametrize("ctx", [
    _ctx(),                                                  # the parent's
    _ctx({"sched.decode_kv_slots_read": 0.0,
          "sched.decode_kv_slots_ring": 0.0}),               # no decode
    _ctx({"sched.steps": 4.0}, scope_s={"jit(f)": 1.0},
         decode_ctx=[(3,)]),                                 # no kernel
    _ctx(scope_s={"decode_attn": 1e-3}),                     # no decode keys
])
@pytest.mark.parametrize("metric", ["decode_kv_read_share",
                                    "decode_attn_roofline"])
def test_nothing_to_read_gives_none(metric, ctx):
    assert _read(metric, ctx) is None


def test_traced_chat_run_reports_read_share(tiny_root, on_cpu):
    """The scheduler's counters reach the reader through
    ``session.stats()``. The CPU's profile has no TPU plane, so the
    kernel's roofline finds nothing to read."""
    res = on_cpu.run(on_cpu.parse(
        ["--workload", "phi3-resident-chat", "--seed", str(2 ** 31 + 91),
         "--seconds", "2", "--trace", "1"]), root=tiny_root)
    assert res["correct"]
    share = res["metrics"]["decode_kv_read_share"]["value"]
    assert 0 < share <= 100
    assert "decode_attn_roofline" not in res["metrics"]
