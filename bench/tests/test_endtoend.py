"""End-to-end arithmetic on a scripted clock: the window closes at a step
boundary, rates are over the whole window, tails over every request, and
a stall inside the window moves every metric."""

from __future__ import annotations

from typing import List

import endtoend
import run
from traffic.generate import Item


class Clock:
    def __init__(self) -> None:
        self.t = 0.0

    def now(self) -> float:
        return self.t

    def sleep(self, dt: float) -> None:
        self.t += dt


class FakeDriver:
    """Every step takes ``step_s`` (``stall_s`` more at step
    ``stall_at``) and emits one token for each submitted request that
    still wants one, the first of them one step after submission."""

    def __init__(self, clock: Clock, step_s: float, tokens: int,
                 stall_at: int = -1, stall_s: float = 0.0) -> None:
        self.clock, self.step_s, self.tokens = clock, step_s, tokens
        self.stall_at, self.stall_s = stall_at, stall_s
        self.left = {}
        self.stamps: List = []
        self.n = 0

    def submit(self, item: Item) -> None:
        self.left[item.index] = self.tokens

    def busy(self) -> bool:
        return any(self.left.values())

    def step(self) -> float:
        self.clock.t += self.step_s + (self.stall_s if self.n == self.stall_at
                                       else 0.0)
        self.n += 1
        for rid, k in self.left.items():
            if k:
                self.left[rid] = k - 1
                self.stamps.append((rid, self.clock.t))
        return self.clock.t


def window(step_s=0.1, tokens=5, gap=0.25, n=12, seconds=2.0, **stall):
    clock = Clock()
    drv = FakeDriver(clock, step_s, tokens, **stall)
    items = [Item(i, i * gap, None, tokens) for i in range(n)]
    t_close, due = run.open_loop(drv, items, 0.0, seconds, clock.now,
                                 clock.sleep)
    return endtoend.Window(0.0, t_close, drv.stamps, due)


def test_window_closes_at_the_end_of_the_first_step_past_the_limit():
    w = window(step_s=0.3, seconds=2.0)
    assert w.t_close == max(t for _, t in w.stamps)
    assert 2.0 <= w.t_close < 2.0 + 0.3 + 1e-9


def test_idle_window_closes_at_its_length():
    w = window(n=1, tokens=1, seconds=2.0)
    assert w.t_close == 2.0
    assert endtoend.output_tok_s(w) == 1 / 2.0


def test_rate_is_over_all_the_window():
    w = window(step_s=0.1, tokens=5, gap=0.25, seconds=2.0)
    inside = [t for _, t in w.stamps if 0.0 < t <= w.t_close]
    assert endtoend.output_tok_s(w) == len(inside) / w.t_close


def test_tails_are_over_every_request_due():
    w = window(step_s=0.1, tokens=3, gap=0.25, n=12, seconds=2.0)
    ttft = endtoend.ttft_ms(w)
    assert len(ttft) == len(w.due) == 8   # items due at 0 .. 1.75 s
    assert endtoend.p90(ttft) is not None
    assert all(v > 0 for v in ttft)


def test_a_stall_inside_the_window_moves_every_metric():
    calm = window(step_s=0.1, tokens=4, gap=0.2, n=10, seconds=2.0)
    stall = window(step_s=0.1, tokens=4, gap=0.2, n=10, seconds=2.0,
                   stall_at=4, stall_s=0.6)
    assert endtoend.output_tok_s(stall) < endtoend.output_tok_s(calm)
    assert (endtoend.p90(endtoend.ttft_ms(stall))
            > endtoend.p90(endtoend.ttft_ms(calm)))
    assert (endtoend.p90(endtoend.tpot_ms(stall))
            > endtoend.p90(endtoend.tpot_ms(calm)))
