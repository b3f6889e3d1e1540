"""Reduce a JAX profiler trace of the measured window to device busy time,
idle share, the costliest device operations and the longest idle gaps.

The window and the program's ``obs`` spans are on the host's
``time.perf_counter`` clock; the trace has its own. The harness opens a
``jax.profiler.TraceAnnotation`` named ``MARKER`` and reads
``perf_counter`` inside it, so the marker's start in the trace and that
reading are the same instant; ``Clock`` converts between the two.

Busy time on a device is the union of the intervals of its operations
(the ``XLA Ops`` line of each ``/device:`` plane), clipped to the window.
Device time is also summed per compiled program of the ``XLA Modules``
line, named without its hash (``jit_decode_step``, ``jit_scatter``):
the per-layer metrics of the model step divide by the time of the
model's own programs. Each idle gap is named by the ``obs`` phase that
covers most of it, or ``between_steps`` where none does.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

MARKER = "bench.clock"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the scheduler's step phases an idle gap is attributed to
PHASES = ("admit_prefill", "collect", "decode", "park_issue")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Clock:
    """trace seconds = perf_counter seconds + ``offset``"""
    offset: float

    def to_trace(self, t: float) -> float:
        return t + self.offset


@dataclasses.dataclass(frozen=True)
class Op:
    plane: str
    name: str
    start: float      # trace seconds
    end: float
    module: bool      # a whole compiled program, not one of its ops


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def module_name(name: str) -> str:
    """``jit_scatter(1234)`` -> ``jit_scatter``"""
    return name.split("(", 1)[0]


def read_trace(path: str, marker_perf_s: float) -> Tuple[Clock, List[Op]]:
    """(clock, device ops and programs) of the trace at ``path``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    marker = None
    ops: List[Op] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER and marker is None:
                        marker = ev.start_ns * 1e-9
        elif plane.name.startswith("/device:TPU"):
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append(Op(plane.name, ev.name, s,
                                  s + ev.duration_ns * 1e-9,
                                  line.name == MODULES_LINE))
    if marker is None:
        raise ValueError(f"marker {MARKER!r} not found in {path}")
    return Clock(marker - marker_perf_s), ops


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def name_gap(gap: Interval, spans: Sequence[Tuple[str, Interval]]) -> str:
    """The phase that covers most of ``gap``; ``between_steps`` if none."""
    cover: Dict[str, float] = defaultdict(float)
    for name, iv in spans:
        cover[name] += _overlap(gap, iv)
    best = max(cover.items(), key=lambda kv: kv[1], default=("", 0.0))
    return best[0] if best[1] > 0 else "between_steps"


@dataclasses.dataclass
class Reduced:
    busy_s: float             # mean over the devices that ran anything
    window_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]
    #: device seconds of each compiled program, mean over those devices
    module_s: Dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def reduce(clock: Clock, ops: Sequence[Op], window: Interval,
           spans: Sequence[Tuple[str, Interval]],
           top: int = 10) -> Optional[Reduced]:
    """Reduce ``ops`` to the window (perf_counter seconds). ``spans`` are
    (phase, perf_counter interval). None where no device ran an op."""
    lo, hi = clock.to_trace(window[0]), clock.to_trace(window[1])
    by_plane: Dict[str, List[Interval]] = defaultdict(list)
    time_by_op: Dict[str, float] = defaultdict(float)
    for op in ops:
        iv = clip([(op.start, op.end)], lo, hi)
        if not iv:
            continue
        if op.module:
            time_by_op[module_name(op.name)] += iv[0][1] - iv[0][0]
        else:
            by_plane[op.plane].append(iv[0])
    if not by_plane:
        return None
    merged = {p: union(ivs) for p, ivs in by_plane.items()}
    busy = sum(sum(e - s for s, e in m) for m in merged.values()) \
        / len(merged)
    traced_spans = [(n, (clock.to_trace(a), clock.to_trace(b)))
                    for n, (a, b) in spans if n in PHASES]
    first = min(merged)  # the first device's gaps stand for the step's
    idle = sorted(((name_gap(g, traced_spans), g[1] - g[0])
                   for g in gaps(merged[first], lo, hi)),
                  key=lambda kv: -kv[1])[:top]
    module_s = {k: v / len(merged) for k, v in time_by_op.items()}
    ops_top = sorted(module_s.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(busy, hi - lo, ops_top, idle, module_s)
