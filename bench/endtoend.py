"""End-to-end metrics from the harness's own host clock.

A token's time is the ``perf_counter`` reading when the scheduler step
that emitted it returned; that step synchronises on the device through
its argmax. The window opens after set-up and closes at the end of the
first step that finishes after ``--seconds`` (or at ``--seconds`` if the
system is idle then), so every rate is over all the work and all the
time of the window.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    #: (request id, time) of every token emitted, inside the window or not
    stamps: List[Tuple[int, float]]
    #: absolute due time of every request due inside the window
    due: Dict[int, float]

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def inside(self, t: float) -> bool:
        return self.t_open < t <= self.t_close

    def tokens_inside(self) -> int:
        return sum(1 for _, t in self.stamps if self.inside(t))


def p90(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 90)) if values else None


def p50(values: List[float]) -> Optional[float]:
    return float(np.percentile(values, 50)) if values else None


def output_tok_s(w: Window) -> float:
    return w.tokens_inside() / w.seconds


def first_token_times(w: Window) -> Dict[int, float]:
    first: Dict[int, float] = {}
    for rid, t in w.stamps:
        first.setdefault(rid, t)
    return first


def ttft_ms(w: Window) -> List[float]:
    """Per request due in the window, from its due time to its first
    token. A request still without one counts to the last stamp."""
    first = first_token_times(w)
    end = max((t for _, t in w.stamps), default=w.t_close)
    return [1e3 * (first.get(rid, end) - due) for rid, due in w.due.items()]


def tpot_ms(w: Window) -> List[float]:
    """Per request with two or more tokens in the window: the span from
    its first to its last token in the window over the gaps between."""
    times: Dict[int, List[float]] = {}
    for rid, t in w.stamps:
        if w.inside(t):
            times.setdefault(rid, []).append(t)
    return [1e3 * (ts[-1] - ts[0]) / (len(ts) - 1)
            for ts in times.values() if len(ts) > 1]

