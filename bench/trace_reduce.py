"""Reduce a JAX profiler trace of the measured window to device busy time,
idle share, the costliest device operations, the longest idle gaps, and
device time by compiled program and by ``jax.named_scope``.

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
model's own programs.

Device time by scope: on a TPU the profile carries an op's
``jax.named_scope`` path in the ``tf_op`` stat of the op's event
metadata (``jit(step)/outer/inner/dot_general:``); the device plane has
no name-scope line, and ``jax.profiler.ProfileData`` does not expose
metadata stats, so the file is read with ``bench/xplane.py``. Every name
on the path but the last (the operation itself) is a scope of the op,
the jitted function's ``jit(...)`` among them; a Pallas kernel's path
ends in ``<kernel name>/pallas_call``, so the kernel's ``name`` is a
scope of its op (``tests/data/scoped_tpu.*``). An op counts toward every
scope on its path; a scope's seconds are the union of its ops' intervals
on each device, clipped to the window, so overlapping ops count once. A
fusion takes the path of its root op.

The longest idle gaps are named by the ``obs`` phase that covers most of
each, or ``between_steps`` where none does; only the gaps reported are
named.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import xplane

MARKER = "bench.clock"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the event metadata stat that holds an op's name-scope path
SCOPE_STAT = "tf_op"
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
    #: the named scopes the op ran under, outermost first
    scopes: Tuple[str, ...] = ()


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def module_name(name: str) -> str:
    """``jit_scatter(1234)`` -> ``jit_scatter``"""
    return name.split("(", 1)[0]


def scope_path(tf_op: str) -> Tuple[str, ...]:
    """``jit(step)/outer/inner/dot_general:`` -> ``("jit(step)", "outer",
    "inner")``: the scopes of an op, without the op itself."""
    return tuple(tf_op.rsplit(":", 1)[0].split("/")[:-1])


def read_trace(path: str, marker_perf_s: float) -> Tuple[Clock, List[Op]]:
    """(clock, device ops and programs) of the trace at ``path``. Times
    are whole nanoseconds, as ``jax.profiler.ProfileData`` gives them."""
    marker = None
    ops: List[Op] = []
    for plane in xplane.read(path).planes:
        if plane.name.startswith("/host:") and marker is None:
            ids = {e.key for e in plane.event_metadata
                   if e.value.name == MARKER}
            for line in plane.lines:
                for ev in line.events:
                    if ev.metadata_id in ids:
                        marker = (line.timestamp_ns + ev.offset_ps // 1000) \
                            * 1e-9
                        break
                if marker is not None:
                    break
        elif plane.name.startswith("/device:TPU"):
            names = {e.key: e.value.name for e in plane.event_metadata}
            scopes = {k: scope_path(v) for k, v in
                      xplane.stat_strings(plane, SCOPE_STAT).items()}
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                module = line.name == MODULES_LINE
                for ev in line.events:
                    s = (line.timestamp_ns + ev.offset_ps // 1000) * 1e-9
                    mid = ev.metadata_id
                    ops.append(Op(plane.name, names[mid], s,
                                  s + (ev.duration_ps // 1000) * 1e-9,
                                  module, () if module
                                  else scopes.get(mid, ())))
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
    #: device seconds of the ops under each named scope, mean over those
    #: devices
    scope_s: Dict[str, float]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def _covered(by_plane: Dict[str, List[Interval]]) -> float:
    """Seconds covered by the intervals, summed over the planes."""
    return sum(sum(e - s for s, e in union(ivs)) for ivs in by_plane.values())


def reduce(clock: Clock, ops: Sequence[Op], window: Interval,
           spans: Sequence[Tuple[str, Interval]],
           top: int = 10) -> Optional[Reduced]:
    """Reduce ``ops`` to the window (perf_counter seconds). ``spans`` are
    (phase, perf_counter interval). None where no device ran an op."""
    lo, hi = clock.to_trace(window[0]), clock.to_trace(window[1])
    by_plane: Dict[str, List[Interval]] = defaultdict(list)
    by_scope: Dict[str, Dict[str, List[Interval]]] = defaultdict(
        lambda: defaultdict(list))
    time_by_op: Dict[str, float] = defaultdict(float)
    for op in ops:
        if op.end <= lo or op.start >= hi:
            continue
        iv = (max(op.start, lo), min(op.end, hi))
        if op.module:
            time_by_op[module_name(op.name)] += iv[1] - iv[0]
            continue
        by_plane[op.plane].append(iv)
        for scope in op.scopes:
            by_scope[scope][op.plane].append(iv)
    if not by_plane:
        return None
    merged = {p: union(ivs) for p, ivs in by_plane.items()}
    n = len(merged)
    busy = sum(sum(e - s for s, e in m) for m in merged.values()) / n
    traced_spans = [(name, (clock.to_trace(a), clock.to_trace(b)))
                    for name, (a, b) in spans if name in PHASES]
    first = min(merged)  # the first device's gaps stand for the step's
    # longest first, ties in time order; only those reported are named
    longest = sorted(gaps(merged[first], lo, hi),
                     key=lambda g: -(g[1] - g[0]))[:top]
    idle = [(name_gap(g, traced_spans), g[1] - g[0]) for g in longest]
    module_s = {k: v / n for k, v in time_by_op.items()}
    ops_top = sorted(module_s.items(), key=lambda kv: -kv[1])[:top]
    scope_s = {k: _covered(v) / n for k, v in by_scope.items()}
    return Reduced(busy, hi - lo, ops_top, idle, module_s, scope_s)
