"""Serving benchmark of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name: the cell in ``BENCHMARK.json``, its workload
file ``bench/workloads/<cell>.json`` (serving settings and the
correctness limit), its traffic mix ``bench/traffic/<traffic>.json``
(read by ``bench/traffic/generate.py``), its configuration file, the
plain reference the file names in ``bench/reference/<reference>.py``, and
one reader per metric in ``bench/metrics/<metric>.py``. The program is
driven through its normal serving path:
``HyperOffloadSession(offload_config(...)).scheduler``, stepped by
``ContinuousScheduler.step()``.

A run: refuse anything but a TPU with the chips the cell asks for; draw
the weights on the device from the seed; warm up every shape the cell's
traffic uses (for a backlog, fill the batch until every row decodes);
open the window and submit each request as its due time passes, stamping
every token with the time its step returned; close the window; with
``--trace 1`` the profiler traces the window and the per-layer metrics
are read instead of the end-to-end ones.

A metric's reader takes a ``Reading`` (below): the window and its steps,
the program's spans, its counters over the window (every numeric leaf of
``session.stats()`` by dotted path, as ``sched.steps``: read them with
``metrics/_counters.delta``), and in a traced run the trace reduction,
with device seconds by compiled program (``metrics/_model_step``) and by
``jax.named_scope`` or Pallas kernel name (``metrics/_scope.device_s``).
A configuration's kernel reader needs only its own operation and byte
counts beside these.

After the window the program is freed and a sample of the served
requests is compared with the float32 reference (``bench/reference``):
the widest gap by which a served token's logit lies below the
reference's best must stay under the workload's limit.

The last line of standard output is the result as one JSON object; the
numbers compared, each beside its limit, are the last lines of standard
error and the result's last key.
"""

from __future__ import annotations

import os
import time

T_START = time.perf_counter()
# libtpu writes its logs under /tmp unless told otherwise
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from collections import Counter  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Dict, List, Optional, Tuple  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))
sys.path.insert(1, str(ROOT / "src"))

import numpy as np  # noqa: E402

import endtoend  # noqa: E402
import peaks as peaks_mod  # noqa: E402
import trace_reduce  # noqa: E402
from traffic.generate import Item, generate  # noqa: E402

#: where a traced run's profile goes (inside the checkout, gitignored)
TRACE_DIR = ROOT / ".bench_trace"
#: enough events for every transfer of a traced window: the tracer must
#: drop none
RING = 1 << 22
#: served requests compared with the reference, and the served tokens the
#: sample aims to hold at least
SAMPLE_MAX, SAMPLE_TOKENS = 8, 320
#: sequences the reference runs at once
REF_GROUP = 4
#: after the window, how long first tokens of requests due in it are
#: waited for
DRAIN_S = 60.0


# ---------------------------------------------------------------------------
# the cell, found by name
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    workload: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    workload = json.loads(
        (root / "bench" / "workloads" / f"{name}.json").read_text())
    if workload["config"] != w["config"]:
        raise SystemExit(f"{name}: workload file names config "
                         f"{workload['config']!r}, BENCHMARK.json "
                         f"{w['config']!r}")
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, int(w["chips"]), config, workload, traffic,
                [m for m in bench["end_to_end"] if _reports(m, name)],
                [m for m in bench["per_layer"] if _reports(m, name)])


def _module(root: Path, package: str, name: str):
    """``bench/<package>/<name>.py`` under ``root``, loaded by its path."""
    path = root / "bench" / package / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"{package}.{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reader(root: Path, name: str):
    """The ``read`` function of ``bench/metrics/<name>.py`` under ``root``."""
    return _module(root, "metrics", name).read


def reference(root: Path, cfg: Dict):
    """The plain reference that the configuration file names: the module
    ``bench/reference/<cfg["reference"]>.py`` under ``root``."""
    return _module(root, "reference", cfg["reference"])


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------


def require_tpu(chips: int):
    """The first device, or exit non-zero (no result line) unless JAX
    sees TPUs, at least ``chips`` of them."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"bench: needs {chips} TPU chip(s); JAX sees "
                 f"{len(devs)} {devs[0].platform} ({devs[0].device_kind})")
    return devs[0]


class CompileClock:
    """Seconds XLA spent compiling, and how many programs were compiled or
    loaded from the persistent cache, from JAX's own monitoring events."""

    def __init__(self) -> None:
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.count += 1


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StepWork:
    """What one scheduler step computed: context lengths of the prompt
    tokens it prefilled and of the rows it decoded, and tokens emitted."""
    t_end: float
    prefill_ctx: List[int]
    decode_ctx: List[int]
    emitted: int


@dataclasses.dataclass
class Served:
    """What the timed path produced, kept after the program is freed."""
    window: endtoend.Window
    steps: List[StepWork]           # steps that ended inside the window
    spans: List[Tuple[str, Tuple[float, float]]]
    counters: Dict[str, float]
    stepped: int                    # steps taken between their reads
    outputs: Dict[int, Tuple[np.ndarray, List[int]]]   # prompt, tokens
    longest: int
    compiles_in_window: int
    setup_s: float
    peak_bytes: int
    trace: Optional[trace_reduce.Reduced]
    attempted: int


class Driver:
    """Steps the scheduler on the host clock and records every token."""

    def __init__(self, sched) -> None:
        self.sched = sched
        self.states: Dict[int, Any] = {}
        self.stamps: List[Tuple[int, float]] = []
        self.steps: List[StepWork] = []

    def submit(self, item: Item) -> None:
        import program
        self.states[item.index] = self.sched.submit(
            program.request(item, arrival=self.sched.now))

    def busy(self) -> bool:
        s = self.sched
        return bool(len(s.queue) or s.active or s.preempted)

    def step(self) -> float:
        before = {st.req_id: (st.status, st.prefill_pos)
                  for st in self.sched.active}
        emitted = self.sched.step()
        t = time.perf_counter()
        counts = Counter(rid for rid, _ in emitted)
        touched = {st.req_id: st for st in self.sched.active}
        touched.update({rid: self.states[rid] for rid in counts})
        prefill: List[int] = []
        decode: List[int] = []
        for rid, st in touched.items():
            status, pos = before.get(rid, ("QUEUED", 0))
            was_decoding = status == "DECODE"
            if not was_decoding:
                # the prompt token at position p attends p + 1 keys
                prefill += range(pos + 1, st.prefill_pos + 1)
            if counts[rid] - (not was_decoding) > 0:
                decode.append(st.pos)   # its decode attended pos keys
        self.stamps += [(rid, t) for rid, _ in emitted]
        self.steps.append(StepWork(t, prefill, decode, len(emitted)))
        return t


def _warm_items(items: List[Item], n: int, new_tokens: int) -> List[Item]:
    """``n`` warm-up requests (ids past the stream's) with the stream's
    first prompts: enough decode steps that every slot is busy at once."""
    base = len(items)
    return [Item(base + i, 0.0, items[i].prompt, new_tokens)
            for i in range(n)]


def _split_prompt_item(items: List[Item], serving: Dict) -> Item:
    """A warm-up request whose prompt outlasts one step's prefill budget,
    so that its row is parked between chunk steps and restored: a path
    the batch fill takes or not depending on the seed."""
    budget = serving.get("prefill_tokens") or serving["chunk_size"]
    n = budget + serving["chunk_size"]
    return Item(len(items) + serving["max_batch"], 0.0,
                np.resize(items[0].prompt, n), 1)


def open_loop(drv, pending: List[Item], t_open: float, seconds: float,
              now=time.perf_counter, sleep=time.sleep
              ) -> Tuple[float, Dict[int, float]]:
    """Drive the window: submit each pending item once its due time has
    passed, step while there is work, and sleep while there is none.
    Returns the close (the end of the first step that finishes at or
    after ``seconds``, or ``t_open + seconds`` if idle then) and the due
    time of every request submitted."""
    t_end = t_open + seconds
    due: Dict[int, float] = {}
    pending = list(pending)
    while True:
        t = now()
        while pending and t_open + pending[0].due_s <= t:
            it = pending.pop(0)
            due[it.index] = t_open + it.due_s
            drv.submit(it)
        if drv.busy():
            t_step = drv.step()
            if t_step >= t_end:
                return t_step, due
            continue
        nxt = t_open + pending[0].due_s if pending else t_end
        if nxt >= t_end:
            sleep(max(0.0, t_end - t))
            return t_end, due
        sleep(max(0.0, nxt - t))


def serve(cell: Cell, seed: int, seconds: float, trace: bool, dev,
          clock: CompileClock) -> Served:
    import jax

    import program
    cfg, wl = cell.config, cell.workload
    serving, traffic = wl["serving"], cell.traffic
    items = generate(traffic, seed, cfg["vocab_size"])
    for it in items:
        if len(it.prompt) + it.max_new_tokens > serving["max_seq"]:
            raise ValueError(f"request {it.index} exceeds max_seq")
    model, params = program.build(cfg, seed)
    session = program.session(model, serving, telemetry=trace, ring=RING)
    sched = session.scheduler(model, params)
    drv = Driver(sched)
    backlog = traffic["arrivals"] == "backlog"
    batch = serving["max_batch"]

    # -- warm-up: every shape the window uses ---------------------------
    if backlog:
        drv.submit(_split_prompt_item(items, serving))
        while drv.busy():
            drv.step()
        for it in items:
            drv.submit(it)
        while sum(st.status == "DECODE" for st in sched.active) < batch:
            drv.step()
        drv.step()   # every slot's pages go round the pool once more
        pending: List[Item] = []
    else:
        for it in _warm_items(items, batch, batch + 2):
            drv.submit(it)
        while drv.busy():
            drv.step()
        pending = list(items)
    jax.block_until_ready(sched.cache)

    stats0 = counters(session.stats())
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # host annotations (the marker) without Python function events
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        with jax.profiler.TraceAnnotation(trace_reduce.MARKER):
            t_marker = time.perf_counter()
    n_warm_steps = len(drv.steps)
    compiles0 = clock.count

    # -- the window -----------------------------------------------------
    t_open = time.perf_counter()
    setup_s = t_open - T_START
    t_close, due = open_loop(drv, pending, t_open, seconds)
    compiles = clock.count - compiles0
    stats1 = counters(session.stats())
    window_steps = drv.steps[n_warm_steps:]
    if trace:
        jax.profiler.stop_trace()

    # requests due in the window are waited for until their first token
    deadline = time.perf_counter() + DRAIN_S
    first = endtoend.first_token_times(
        endtoend.Window(t_open, t_close, drv.stamps, due))
    while (any(r not in first for r in due) and drv.busy()
           and time.perf_counter() < deadline):
        drv.step()
        first = endtoend.first_token_times(
            endtoend.Window(t_open, t_close, drv.stamps, due))

    window = endtoend.Window(t_open, t_close, drv.stamps, due)
    spans = []
    reduced = None
    if trace:
        if session.tracer.dropped:
            raise RuntimeError(f"obs tracer dropped "
                               f"{session.tracer.dropped} events")
        spans = [(e.name, (e.ts, e.end)) for e in session.tracer.events()
                 if e.ph == "X" and window.inside(e.end)]
        tclock, ops = trace_reduce.read_trace(
            trace_reduce.latest_xplane(str(TRACE_DIR)), t_marker)
        reduced = trace_reduce.reduce(tclock, ops, (t_open, t_close), spans)
    # the CPU backend keeps no memory statistics: 0 there
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices()[:cell.chips])
    outputs = {rid: (np.asarray(st.request.tokens), list(st.out))
               for rid, st in drv.states.items()
               if st.out and rid < len(items)}
    in_window = {rid for rid, t in drv.stamps if window.inside(t)}
    served = Served(
        window=window, steps=[s for s in window_steps
                              if window.inside(s.t_end)],
        spans=spans,
        counters={k: v - stats0.get(k, 0.0) for k, v in stats1.items()},
        stepped=len(window_steps),
        outputs=outputs,
        longest=max(outputs, key=lambda r: len(outputs[r][1])),
        compiles_in_window=compiles, setup_s=setup_s, peak_bytes=peak,
        trace=reduced, attempted=len(in_window | set(due)))
    session.close()
    del sched, session, params, drv
    gc.collect()
    return served


def counters(stats: Dict[str, Any], prefix: str = "") -> Dict[str, float]:
    """Every numeric leaf of ``stats`` (``session.stats()``) under its
    dotted path: ``sched.steps``, ``pool.transfer.pairs.host->device.bytes``,
    ``telemetry.histograms.histograms.req_ttft_seconds.count``."""
    out: Dict[str, float] = {}
    for key, value in stats.items():
        name = f"{prefix}{key}"
        if isinstance(value, dict):
            out.update(counters(value, f"{name}."))
        elif isinstance(value, (int, float)) and not isinstance(value, bool):
            out[name] = float(value)
    return out


def transfer_bytes(deltas: Dict[str, float]) -> Dict[str, int]:
    """Bytes the pool moved over the window, by tier pair
    (``device->host``), from the counters' changes."""
    head, tail = "pool.transfer.pairs.", ".bytes"
    return {k[len(head):-len(tail)]: int(v) for k, v in deltas.items()
            if k.startswith(head) and k.endswith(tail)}


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sample(outputs: Dict[int, Tuple[np.ndarray, List[int]]], longest: int,
           seed: int) -> List[int]:
    """Served requests to compare: the longest, then others in an order
    drawn from the seed, until ``SAMPLE_TOKENS`` served tokens or
    ``SAMPLE_MAX`` requests."""
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    rest = [r for r in sorted(outputs) if r != longest]
    picked = [longest]
    for i in rng.permutation(len(rest)):
        if (len(picked) >= SAMPLE_MAX or sum(
                len(outputs[r][1]) for r in picked) >= SAMPLE_TOKENS):
            break
        picked.append(rest[i])
    return picked


def logit_gaps(cfg: Dict, seed: int, outputs, picked: List[int], *,
               chunk: int, control: bool = False,
               root: Path = ROOT) -> Dict[str, float]:
    """Run the reference that ``cfg`` names (under ``root``) once over each
    picked prompt with its served tokens. At each served position the gap is how far the served token's
    logit lies below the reference's best: ``max_logit_gap`` is the widest
    and ``mean_logit_gap`` the mean over every served token compared.
    With ``control``, also ``control_max_logit_gap`` and
    ``control_mean_logit_gap``: the same for the token the control puts
    first at each of those positions."""
    ref = reference(root, cfg)
    served_gaps, control_gaps = [], []
    for g in range(0, len(picked), REF_GROUP):
        group = picked[g:g + REF_GROUP]
        seqs = [np.concatenate([outputs[r][0], outputs[r][1][:-1]])
                for r in group]
        s_pad = -(-max(map(len, seqs)) // 128) * 128
        m = max(len(outputs[r][1]) for r in group)
        tokens = np.zeros((len(group), s_pad), np.int32)
        at = np.zeros((len(group), m), np.int32)
        served = np.zeros((len(group), m), np.int32)
        valid = np.zeros((len(group), m), bool)
        plen = np.zeros((len(group),), np.int32)
        for i, r in enumerate(group):
            prompt, out = outputs[r]
            tokens[i, :len(seqs[i])] = seqs[i]
            plen[i] = len(prompt)
            n = len(out)
            at[i, :n] = np.arange(len(prompt) - 1, len(prompt) - 1 + n)
            served[i, :n] = out
            valid[i, :n] = True
        want, low = ref.forward(cfg, seed, tokens, plen, at, chunk=chunk,
                                control=control)
        best = want.max(-1)

        def gap_of(picks):
            return (best - np.take_along_axis(
                want, picks[..., None], -1)[..., 0])[valid]
        served_gaps.append(gap_of(served))
        if control:
            control_gaps.append(gap_of(low.argmax(-1)))
    out = _gap_stats("", np.concatenate(served_gaps))
    if control:
        out.update(_gap_stats("control_", np.concatenate(control_gaps)))
    return out


def judge(limits: Dict[str, float], gaps: Dict[str, float],
          prefix: str = "") -> Dict[str, Dict[str, float]]:
    """Each number the workload file holds its cell to (``limits``),
    read as ``prefix + name`` from ``gaps``, beside its limit."""
    return {name: {"value": gaps[prefix + name], "limit": limit}
            for name, limit in limits.items()}


def passes(checks: Dict[str, Dict[str, float]]) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def _gap_stats(prefix: str, gaps: np.ndarray) -> Dict[str, float]:
    return {f"{prefix}max_logit_gap": float(gaps.max()),
            f"{prefix}mean_logit_gap": float(gaps.mean())}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Reading:
    """What a metric's reader (``bench/metrics/<metric>.py``) reads:

    - ``window``: its open and close on the host clock, every token's
      time and every request's due time (``endtoend.Window``);
    - ``steps``: each scheduler step that ended in the window, with the
      context lengths it prefilled and decoded and the tokens it emitted;
    - ``spans``: the program's ``obs`` spans (name, host interval) that
      ended in the window; traced runs only;
    - ``counters``: the change over the window of every numeric leaf of
      ``session.stats()`` by its dotted path (``sched.steps``,
      ``sched.decoded_tokens``, ``pool.transfer.pairs.host->device.bytes``;
      with ``--trace 1`` also the histograms, as
      ``telemetry.histograms.histograms.req_ttft_seconds.count`` and
      ``.sum``), read before the window opens and after it closes
      (``metrics/_counters.py``); ``transfer_bytes``: the pool's bytes
      moved by tier pair, from the same counters;
    - ``cfg``: the configuration file; ``peaks``: the chip's peaks
      (``peaks.json``);
    - ``trace``: with ``--trace 1`` on a TPU, the trace reduction
      (``trace_reduce.Reduced``): busy and idle time, device seconds by
      compiled program (``module_s``, ``metrics/_model_step.py``) and by
      ``jax.named_scope`` or Pallas kernel name (``scope_s``,
      ``metrics/_scope.py``); None otherwise;
    - ``peak_bytes``: the fullest chip's peak memory; ``setup_s``.
    """
    window: endtoend.Window
    steps: List[StepWork]
    spans: List[Tuple[str, Tuple[float, float]]]
    counters: Dict[str, float]
    transfer_bytes: Dict[str, int]
    cfg: Dict
    peaks: Dict[str, float]
    trace: Optional[trace_reduce.Reduced]
    peak_bytes: int
    setup_s: float


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def start(cell: Cell):
    """Set-up shared by every entry that serves a cell: refuse anything
    but the TPUs the cell asks for, look up the chip's peaks, keep every
    compiled program in the checkout's persistent cache, and start the
    compile clock. Returns (device, peaks, clock)."""
    dev = require_tpu(cell.chips)
    peaks = peaks_mod.lookup(dev.device_kind)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    # every program of the cell, however quick to compile, is kept: later
    # runs then find all of them in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return dev, peaks, CompileClock()


def run(args: argparse.Namespace, root: Path = ROOT) -> Dict[str, Any]:
    """One run of a cell; returns the result object."""
    cell = load_cell(root, args.workload)
    dev, peaks, clock = start(cell)
    import jax
    trace = bool(args.trace)
    served = serve(cell, args.seed, args.seconds, trace, dev, clock)

    ctx = Reading(served.window, served.steps, served.spans,
                  served.counters, transfer_bytes(served.counters),
                  cell.config, peaks, served.trace, served.peak_bytes,
                  served.setup_s)
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = reader(root, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    step_s = np.diff([served.window.t_open] + [s.t_end for s in served.steps])
    if len(step_s):
        print(f"step seconds: min {step_s.min():.4f}, median "
              f"{np.median(step_s):.4f}, max {step_s.max():.4f}",
              file=sys.stderr)
    print(f"window: {served.window.seconds:.3f} s, "
          f"{served.window.tokens_inside()} tokens, "
          f"{len(served.steps)} steps, {len(served.window.due)} requests "
          f"due, setup {served.setup_s:.3f} s "
          f"(compile {clock.seconds:.3f} s), compiles in window "
          f"{served.compiles_in_window}", file=sys.stderr)
    print(f"counters: sched.steps {served.counters.get('sched.steps')} "
          f"(steps taken {served.stepped}), sched.decoded_tokens "
          f"{served.counters.get('sched.decoded_tokens')}", file=sys.stderr)
    ttft = endtoend.ttft_ms(served.window)
    if ttft:
        q = np.percentile(ttft, [50, 75, 90, 95, 100])
        print("ttft ms: p50 {:.1f}, p75 {:.1f}, p90 {:.1f}, p95 {:.1f}, "
              "max {:.1f} over {} requests".format(*q, len(ttft)),
              file=sys.stderr)

    t_ref = time.perf_counter()
    picked = sample(served.outputs, served.longest, args.seed)
    gaps = logit_gaps(cell.config, args.seed, served.outputs, picked,
                      chunk=cell.workload["serving"]["chunk_size"],
                      root=root)
    print(f"reference: {sum(len(served.outputs[r][1]) for r in picked)} "
          f"served tokens of {len(picked)} requests compared in "
          f"{time.perf_counter() - t_ref:.1f} s", file=sys.stderr)
    checks = judge(cell.workload["limits"], gaps)
    correct = passes(checks)
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": served.attempted,
        "failed": 0,
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": served.peak_bytes},
    }
    if trace and served.trace is not None:
        result["device"]["busy_s"] = served.trace.busy_s
        result["device"]["window_s"] = served.trace.window_s
        result["breakdown"] = {"device_ops": served.trace.device_ops,
                               "idle_gaps": served.trace.idle_gaps}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    result = run(parse(argv))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
