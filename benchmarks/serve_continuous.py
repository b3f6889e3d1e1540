"""Continuous batching vs. static batching on mixed-length Poisson traffic.

Two schedulers over the same arrival trace and the same model:

- **static**  — the seed's serving pattern: requests are grouped into
  arrival-order batches of ``max_batch``; a batch prefills together
  (prompts end-padded to the batch max) and decodes until its *longest*
  member finishes — short requests burn padded decode steps and late
  requests wait for the whole previous batch.
- **continuous** — ``sched.ContinuousScheduler``: sequences join and
  retire every decode step, so a retired slot is refilled immediately and
  nobody decodes padding.

Reported: wall-clock generated tokens/s, virtual-step throughput, and
p50/p99 request latency in scheduler steps (finish − arrival on the
deterministic virtual clock; 1 step = one batched decode). A second,
small ``kv_offload`` run reports the plan-driven prefetcher's stats —
fetches issued ahead of consumption (plan lead ≥ 1, overlapped waits)
instead of the old store-then-immediately-wait round trip.

A third section drives a mixed short/long-prompt trace through the
scheduler step by step, whole-prompt vs **chunked prefill**
(``--chunk-size``): per-step prefill tokens and wall latency show the
long-prompt stall bounded by the chunk budget, and the jit cache sizes
show chunked prefill compiling exactly ONE executable where whole-prompt
prefill compiles one per distinct prompt length.

A fourth section replays a **shared-prefix** trace (``poisson_trace``'s
prefix-family mode: ~2/3 of every prompt is one of two shared prefixes)
with the cross-request prefix cache off vs on: after warming one request
per family, every later request's shared pages come from the cache, so
prefill tokens drop by the shared fraction while the emitted tokens stay
identical. Reported: prefill tokens saved, hit rate, tokens/s both ways.

    PYTHONPATH=src python benchmarks/serve_continuous.py [--smoke] [--out F]
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import HyperOffloadSession, OffloadConfig
from repro.api.config import PrefixCacheConfig, TelemetryConfig
from repro.configs import REGISTRY
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import build_model
from repro.obs import OverlapAnalyzer
from repro.offload.kvcache import worst_case_page_bytes
from repro.pool import TierSpec, TierTopology
from repro.sched import Request, poisson_trace
from repro.serving.engine import jit_prefill_chunk
from repro.slo import SLOConfig, SLOSpec, attainment_summary


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs), q)) if xs else 0.0


# ---------------------------------------------------------------------------
# static-batching baseline
# ---------------------------------------------------------------------------


def run_static(session, model, params, trace: List[Request],
               max_batch: int) -> Dict[str, float]:
    engine = session.serve_engine(model, params, offload_kv=False)
    clock = 0.0
    latencies: List[float] = []
    tokens = 0
    t0 = time.perf_counter()
    for i in range(0, len(trace), max_batch):
        batch = trace[i:i + max_batch]
        start = max(clock, max(r.arrival for r in batch))
        s_max = max(r.prompt_len for r in batch)
        # a partial final batch is padded with copies of its last request
        # (uncounted) so the engine only ever sees full-batch shapes
        padded = np.zeros((max_batch, s_max), np.int32)
        for j in range(max_batch):
            r = batch[min(j, len(batch) - 1)]
            padded[j, :r.prompt_len] = r.tokens
        steps = max(r.max_new_tokens for r in batch)
        engine.generate({"tokens": jnp.asarray(padded)}, steps)
        clock = start + steps        # everyone waits for the longest member
        tokens += sum(r.max_new_tokens for r in batch)
        latencies += [clock - r.arrival for r in batch]
    wall = time.perf_counter() - t0
    engine.close()
    return {
        "tokens": tokens, "wall_s": wall, "virtual_steps": clock,
        "tokens_per_s": tokens / wall,
        "tokens_per_step": tokens / max(clock, 1e-9),
        "p50_latency_steps": _pct(latencies, 50),
        "p99_latency_steps": _pct(latencies, 99),
    }


# ---------------------------------------------------------------------------
# continuous scheduler
# ---------------------------------------------------------------------------


def run_continuous(session, model, params, trace: List[Request], *,
                   kv_offload: bool = False) -> Dict[str, float]:
    sched = session.scheduler(model, params, kv_offload=kv_offload)
    t0 = time.perf_counter()
    out = sched.run(trace)
    wall = time.perf_counter() - t0
    tokens = sum(len(v) for v in out.values())
    lats = [st.t_done - st.request.arrival for st in sched.finished.values()]
    res = {
        "tokens": tokens, "wall_s": wall, "virtual_steps": sched.now,
        "tokens_per_s": tokens / wall,
        "tokens_per_step": tokens / max(sched.now, 1e-9),
        "p50_latency_steps": _pct(lats, 50),
        "p99_latency_steps": _pct(lats, 99),
        "joins": sched.stats.joins, "retires": sched.stats.retires,
        "admission_blocked": sched.admission.blocked,
    }
    if kv_offload:
        snap = sched.pool_stats()
        res["prefetch"] = sched.prefetch_stats()
        res["transfer"] = snap["transfer"]
        res["pool_evictions"] = snap["evictions"]
        res["pages_parked"] = sched.stats.pages_parked
        res["cold_spills"] = sched.stats.cold_spills
        if session.config.telemetry.enable:
            # the overlap proof: decompose the trace into hidden vs
            # exposed transfer time, cross-checked against the engine's
            # own wait counters — a disagreement is a bug, not noise
            analyzer = OverlapAnalyzer.from_tracer(session.tracer)
            errs = analyzer.validate(snap["transfer"])
            assert not errs, f"overlap/TransferStats disagree: {errs}"
            res["overlap"] = analyzer.report()
    sched.close()
    return res


# ---------------------------------------------------------------------------
# chunked prefill vs whole-prompt on long-prompt traffic
# ---------------------------------------------------------------------------


def run_continuous_stepwise(session, model, params, trace: List[Request], *,
                            chunk_size=None,
                            prefill_tokens=None) -> Dict[str, float]:
    """Drive the scheduler step by step, recording per-step wall latency
    and per-step prefill tokens — the stall metric: whole-prompt prefill
    spends an entire prompt in one step, chunked prefill never exceeds its
    token budget."""
    overrides = {}
    if chunk_size is not None:
        overrides = dict(chunk_size=chunk_size, prefill_tokens=prefill_tokens)
    sched = session.scheduler(model, params, **overrides)
    for r in trace:
        sched.submit(r)
    # run()'s no-progress guard: a scheduler stall must fail CI with a
    # diagnostic, not hang it
    max_steps = sched.default_max_steps()
    step_wall_ms: List[float] = []
    step_prefill: List[int] = []
    t0 = time.perf_counter()
    while len(sched.queue) or sched.active:
        if not sched.active and sched.queue.head_ready(sched.now) is None:
            sched.now = max(sched.now, sched.queue.next_arrival())
        before = sched.stats.prefill_tokens
        s0 = time.perf_counter()
        sched.step()
        step_wall_ms.append((time.perf_counter() - s0) * 1e3)
        step_prefill.append(sched.stats.prefill_tokens - before)
        if len(step_wall_ms) > max_steps:
            raise RuntimeError(
                f"scheduler made no progress ({len(step_wall_ms)} steps, "
                f"{len(sched.queue)} queued)")
    wall = time.perf_counter() - t0
    tokens = sum(len(st.out) for st in sched.finished.values())
    lats = [st.t_done - st.request.arrival for st in sched.finished.values()]
    res = {
        "tokens": tokens, "wall_s": wall,
        "virtual_steps": sched.now,
        "tokens_per_s": tokens / wall,
        "p50_latency_steps": _pct(lats, 50),
        "p99_latency_steps": _pct(lats, 99),
        "max_step_prefill_tokens": max(step_prefill),
        "p99_step_prefill_tokens": _pct([float(x) for x in step_prefill], 99),
        "p99_step_wall_ms": _pct(step_wall_ms, 99),
        "prefill_chunks": sched.stats.prefill_chunks,
    }
    sched.close()
    return res


def _jit_cache_size(fn):
    """Compiled-executable count of a jitted entry point, via jax's
    private ``_cache_size`` — None when a jax version doesn't expose it
    (callers must treat None as 'unknown', not assert on it)."""
    return fn._cache_size() if hasattr(fn, "_cache_size") else None


def run_long_prompt_comparison(session, model, params, trace: List[Request],
                               chunk_size: int,
                               prefill_tokens) -> Dict[str, Dict[str, float]]:
    budget = prefill_tokens or chunk_size
    # warm every prefill shape the trace needs OUTSIDE the timed runs, so
    # the step-latency comparison measures scheduling stalls rather than
    # XLA compiles — and count executables over this warm phase: one per
    # distinct prompt length for whole-prompt prefill, exactly ONE for the
    # chunk path regardless of length mix
    lengths = sorted({r.prompt_len for r in trace})
    c0 = _jit_cache_size(jit_prefill_chunk(model))
    for i, s in enumerate(lengths):
        warm = [Request(tokens=np.ones((s,), np.int32), max_new_tokens=2,
                        seed=2000 + i)]
        run_continuous_stepwise(session, model, params, warm)
        run_continuous_stepwise(session, model, params, warm,
                                chunk_size=chunk_size,
                                prefill_tokens=prefill_tokens)
    c1 = _jit_cache_size(jit_prefill_chunk(model))
    chunk_exec = None if c0 is None else c1 - c0

    whole = run_continuous_stepwise(session, model, params, trace)
    # the whole-prompt path needs one (1, length) executable per distinct
    # prompt length in the trace — a jit-cache delta would under-count
    # lengths other sections of this benchmark already compiled
    whole["prefill_executables"] = len(lengths)
    chunked = run_continuous_stepwise(session, model, params, trace,
                                      chunk_size=chunk_size,
                                      prefill_tokens=prefill_tokens)
    chunked["prefill_executables"] = chunk_exec
    # the acceptance invariants: bounded per-step prefill, one executable
    assert chunked["max_step_prefill_tokens"] <= budget + chunk_size - 1, \
        "chunked prefill exceeded its per-step token budget"
    assert chunk_exec is None or chunk_exec == 1, \
        "mixed prompt lengths must share ONE compiled chunk executable"
    return {"whole_prompt": whole, "chunked": chunked,
            "chunk_size": chunk_size, "prefill_token_budget": budget}


# ---------------------------------------------------------------------------
# cross-request prefix cache on shared-prefix traffic
# ---------------------------------------------------------------------------


def run_prefix_cache_comparison(model, params, *, requests: int, rate: float,
                                vocab_size: int, max_batch: int, max_seq: int,
                                chunk_size: int, seed: int) -> Dict[str, object]:
    """The same shared-prefix trace with the prefix cache off vs on. One
    warm request per family donates the shared pages first, so the
    measured run isolates steady-state hit behavior; asserts the emitted
    tokens are identical both ways and that prefill tokens drop by at
    least half (the trace shares ~2/3 of every prompt).

    Note the wall-clock comparison is pessimistic at smoke scale: on the
    tiny reduced model a page fetch costs more than recomputing the page,
    so the saved-prefill-tokens count (which scales with model FLOPs) is
    the signal, not smoke tokens/s."""
    page, prefix_len = 4, 16
    trace = poisson_trace(
        requests, rate=rate, vocab_size=vocab_size, prompt_lens=(4, 8),
        new_tokens=(2, 8), prompt_quantum=4, n_prefix_families=2,
        prefix_len=prefix_len, seed=seed)
    fams: Dict[bytes, np.ndarray] = {}
    for r in trace:
        head = np.asarray(r.tokens[:prefix_len])
        fams.setdefault(head.tobytes(), head)
    warm = [Request(tokens=np.concatenate([p, np.full((4,), 1, np.int32)]),
                    max_new_tokens=2, seed=5000 + i)
            for i, p in enumerate(fams.values())]

    results: Dict[str, Dict[str, float]] = {}
    outs: Dict[str, Dict[int, np.ndarray]] = {}
    for label, enable in (("off", False), ("on", True)):
        session = HyperOffloadSession(OffloadConfig(
            mode="continuous", max_batch=max_batch, max_seq=max_seq,
            prefill_budget=2, chunk_size=chunk_size,
            prefix_cache=PrefixCacheConfig(enable=enable, page_size=page)))
        sched = session.scheduler(model, params)
        sched.run(list(warm))              # donate the family prefixes
        base = sched.stats.prefill_tokens
        t0 = time.perf_counter()
        out = sched.run(list(trace))
        wall = time.perf_counter() - t0
        tokens = sum(len(out[r.req_id]) for r in trace)
        results[label] = {
            "prefill_tokens": sched.stats.prefill_tokens - base,
            "tokens": tokens, "wall_s": wall, "tokens_per_s": tokens / wall,
            "prefix_hits": sched.stats.prefix_hits,
            "prefix_hit_tokens": sched.stats.prefix_hit_tokens,
        }
        if enable:
            results[label]["cache"] = session.stats()["prefix"]
        outs[label] = {r.req_id: np.asarray(out[r.req_id]) for r in trace}
        session.close()

    # the acceptance invariants: a hit changes WHAT gets prefilled, never
    # what gets emitted; and shared prefixes stop being re-prefilled
    for r in trace:
        np.testing.assert_array_equal(outs["off"][r.req_id],
                                      outs["on"][r.req_id])
    saved = results["off"]["prefill_tokens"] - results["on"]["prefill_tokens"]
    reduction = saved / max(results["off"]["prefill_tokens"], 1)
    assert reduction >= 0.5, \
        f"prefix cache saved only {reduction:.0%} of prefill tokens"
    return {
        "off": results["off"], "on": results["on"],
        "page_size": page, "prefix_len": prefix_len,
        "prefill_tokens_saved": saved,
        "prefill_reduction": reduction,
        "hit_rate": results["on"]["prefix_hits"] / len(trace),
    }


# ---------------------------------------------------------------------------
# closed-loop calibration: static vs measured planning on a modeled tier
# ---------------------------------------------------------------------------


def run_calibration_comparison(model, params, *, requests: int, rate: float,
                               vocab_size: int, max_batch: int, max_seq: int,
                               seed: int) -> Dict[str, object]:
    """The same kv_offload trace twice over a latency-dominated modeled
    tier: once planned from the static `HardwareSpec`, once after
    ``session.recalibrate()`` folded the first arm's measured per-pair
    transfer telemetry back into planning.

    The topology squeezes the device tier so cold parked pages spill into
    a ``modeled`` tier whose reads cost milliseconds of enforced latency.
    The static arm runs with the engine's default 2 transfer workers —
    per-stream latency serializes a step's fetches and the collect phase
    eats blocked waits. Recalibration measures the per-transfer time and
    the real overlap window, sizes the required in-flight parallelism
    (``core.calibration.required_inflight``) and grows the engine, and
    re-plans on measured bandwidth — so the calibrated arm's fetches run
    concurrently and the same waits come back overlapped. Reported per
    arm: tokens/s, plan lead, hidden_fraction (per-arm trace slice);
    ``scripts/ci.sh`` hard-asserts calibrated >= static on
    hidden_fraction."""
    row = worst_case_page_bytes(model.cache_specs(1, max_seq, jnp.float32))
    topo = TierTopology(tiers=(
        TierSpec("device", kind="device", capacity=1 * row),
        TierSpec("pooled", kind="modeled", read_latency_s=6e-3),
    ))
    session = HyperOffloadSession(OffloadConfig(
        mode="kv_offload", max_batch=max_batch, max_seq=max_seq,
        prefill_budget=2, topology=topo,
        telemetry=TelemetryConfig(enable=True)))
    # pressure matters more than trace length here: enough concurrent
    # rows (arrival rate ≥ 2/step, decodes long enough to overlap) that
    # the one-row device tier spills parked pages every step — the
    # measured in-flight need must genuinely exceed the default 2 workers
    # for the loop to have anything to correct
    n = max(8, requests)
    mk = lambda: poisson_trace(
        n, rate=max(2.0, rate), vocab_size=vocab_size, prompt_lens=(4, 8),
        new_tokens=(6, 12), prompt_quantum=4, seed=seed)
    out: Dict[str, object] = {
        "tier_read_latency_s": topo.spec("pooled").read_latency_s,
        "device_capacity_rows": max(1, max_batch // 4),
    }
    for arm in ("static", "calibrated"):
        if arm == "calibrated":
            spec = session.recalibrate()     # measured replan + worker sizing
            out["hw_calibrated"] = spec.name
            out["measured_r2d_bw"] = spec.pool_bw_r2d
        sched = session.scheduler(model, params)
        n0 = len(session.tracer.events())
        t0 = time.perf_counter()
        res = sched.run(mk())
        wall = time.perf_counter() - t0
        tokens = sum(len(v) for v in res.values())
        ov = OverlapAnalyzer(session.tracer.events()[n0:]).report()
        out[arm] = {
            "tokens": tokens, "wall_s": wall,
            "tokens_per_s": tokens / wall,
            "plan_lead": sched.prefetch_stats()["mean_plan_lead"],
            "transfers": ov["transfers"],
            "hidden_s": ov["hidden_s"], "exposed_s": ov["exposed_s"],
            "hidden_fraction": ov["hidden_fraction"],
            "workers": session.transfer.workers,
        }
        sched.close()
    session.close()
    for arm in ("static", "calibrated"):
        assert out[arm]["hidden_fraction"] is not None, \
            f"calibration {arm} arm traced no transfer time"
    return out


# ---------------------------------------------------------------------------
# SLO-aware scheduling vs FIFO under overload
# ---------------------------------------------------------------------------


def _run_slo_mode(model, params, trace: List[Request], *, max_batch: int,
                  max_seq: int, chunk_size: int,
                  slo: SLOConfig) -> Dict[str, object]:
    """One run of an SLO-annotated trace; FIFO when ``slo`` is disabled.
    Attainment is scored post-hoc from the annotations either way, so the
    two modes are judged by the same yardstick."""
    session = HyperOffloadSession(OffloadConfig(
        mode="continuous", max_batch=max_batch, max_seq=max_seq,
        prefill_budget=2, chunk_size=chunk_size, slo=slo))
    sched = session.scheduler(model, params)
    t0 = time.perf_counter()
    sched.run(list(trace))
    wall = time.perf_counter() - t0
    att = attainment_summary(sched.finished.values())
    steps = max(sched.now, 1e-9)
    res = {
        "tokens": att["tokens"], "wall_s": wall, "virtual_steps": sched.now,
        "goodput_tokens": att["met_tokens"],
        "goodput_tokens_per_step": att["met_tokens"] / steps,
        "tokens_per_step": att["tokens"] / steps,
        "attainment": att,
        "preemptions": sched.stats.preemptions,
        "resumes": sched.stats.resumes,
        "shed": sched.stats.shed,
    }
    session.close()
    return res


def run_overload_comparison(model, params, *, requests: int, vocab_size: int,
                            max_batch: int, max_seq: int, chunk_size: int,
                            seed: int) -> Dict[str, object]:
    """Mixed interactive/batch traffic at 2-5x the scheduler's service
    capacity, FIFO vs SLO-aware admission+preemption over the SAME
    annotated trace. Under overload FIFO's arrival order lets long batch
    work block interactive TTFT deadlines; the SLO policy admits
    deadline-first, preempts batch decodes for deadline-pressed
    interactive arrivals, and sheds infeasible work early — so its
    goodput (deadline-met tokens per virtual step) and interactive TTFT
    attainment must both beat FIFO's (hard-asserted in CI at 3x).

    All metrics are on the deterministic virtual clock — CI-safe."""
    n = max(16, requests)
    # long decodes: slots stay held for tens of steps, so an interactive
    # arrival under overload has to preempt, not just wait for a retire
    prompt_lens, new_toks = (4, 16), (8, 24)
    # service capacity ≈ max_batch slots / mean steps a request holds one
    # (mean prefill chunks + mean decode steps); overload = factor × that
    mean_steps = ((prompt_lens[0] + prompt_lens[1]) / 2) / chunk_size \
        + (new_toks[0] + new_toks[1]) / 2
    capacity_rate = max_batch / mean_steps
    interactive = SLOSpec("interactive", ttft_deadline=10.0)
    batch = SLOSpec("batch")
    out: Dict[str, object] = {
        "requests": n, "capacity_rate": capacity_rate,
        "interactive_fraction": 0.35,
        "ttft_deadline_steps": interactive.ttft_deadline,
    }
    for factor in (2, 3, 5):
        trace = poisson_trace(
            n, rate=factor * capacity_rate, vocab_size=vocab_size,
            prompt_lens=prompt_lens, new_tokens=new_toks, prompt_quantum=4,
            interactive_fraction=0.35, interactive_slo=interactive,
            batch_slo=batch, seed=seed + factor)
        fifo = _run_slo_mode(model, params, trace, max_batch=max_batch,
                             max_seq=max_seq, chunk_size=chunk_size,
                             slo=SLOConfig())
        slo = _run_slo_mode(model, params, trace, max_batch=max_batch,
                            max_seq=max_seq, chunk_size=chunk_size,
                            slo=SLOConfig(enable=True))
        out[f"{factor}x"] = {"fifo": fifo, "slo": slo}
    return out


# ---------------------------------------------------------------------------
# paged decode: fused kernel vs legacy gather, KV codec wire traffic
# ---------------------------------------------------------------------------


def run_decode_kernel(*, steps: int, seed: int) -> Dict[str, object]:
    """The decode hot loop in isolation: one long prefilled context, then
    ``steps`` attention-only decode steps per arm.

    - **gather** — the seed path: every step fetches each pool page and
      ``jnp.concatenate``\\ s before attending.
    - **fused**  — ``attend_fused``: pages install once into the device
      page buffer, the page table indexes them in place (exact-math jnp
      ref; the Pallas kernel's error is reported alongside).

    A second pair of runs restricts the device buffer (``device_pages``)
    so every step's top-k selection pulls pool traffic, measuring on-wire
    bytes per fetch with the codec off vs int8."""
    from repro.offload.kvcache import PagedKVCache
    from repro.pool import default_pool

    b, hq, hkv, d, page, npages, tail = 2, 8, 2, 64, 16, 24, 8
    s0 = npages * page + tail
    ks = jax.random.split(jax.random.key(seed), 2 + steps)
    k_seq = jax.random.normal(ks[0], (b, s0, hkv, d))
    v_seq = jax.random.normal(ks[1], (b, s0, hkv, d))
    qs = [jax.random.normal(ks[2 + t], (b, hq, d)) for t in range(steps)]
    scale = d ** -0.5

    def build(codec=None, device_pages=None):
        pool = default_pool(codec=codec, codec_below="host")
        cache = PagedKVCache.create(batch=b, max_seq=s0 + page,
                                    page_size=page, n_kv_heads=hkv,
                                    head_dim=d, pool=pool,
                                    device_pages=device_pages)
        cache.prefill(k_seq, v_seq)
        return cache

    def timed(cache, attend):
        attend(qs[0])                       # warm the jit outside the clock
        t0 = time.perf_counter()
        outs = [attend(q) for q in qs]
        jax.block_until_ready(outs[-1])
        return outs, time.perf_counter() - t0

    cache = build()
    outs_g, wall_g = timed(
        cache, lambda q: cache.attend(q, scale=scale, top_k_pages=None))
    gather_fetches = cache.fetches
    cache.pool.close()

    cache = build()
    outs_f, wall_f = timed(
        cache, lambda q: cache.attend_fused(q, scale=scale))
    kernel_out = cache.attend_fused(qs[0], scale=scale, use_kernel=True)
    kernel_err = float(jnp.max(jnp.abs(kernel_out - outs_f[0])))
    buffer_hits, buffer_misses = cache.buffer_hits, cache.buffer_misses
    cache.pool.close()

    # token identity: bitwise-equal attention outputs feed bitwise-equal
    # logits, so greedy decoding emits the same tokens
    match = all(bool(jnp.all(f == g)) for f, g in zip(outs_f, outs_g))

    def traffic(codec):
        cache = build(codec=codec, device_pages=4)
        for q in qs:
            cache.attend_fused(q, scale=scale, top_k_pages=4)
        stats = cache.pool_stats()
        per_fetch = stats["bytes_fetched"] / max(cache.fetches, 1)
        cache.pool.close()
        return per_fetch

    bpf_none, bpf_int8 = traffic(None), traffic("int8")

    # quantization error of the full-context int8 page pool vs exact
    cache = build(codec="int8")
    int8_err = float(jnp.max(jnp.abs(
        cache.attend_fused(qs[0], scale=scale) - outs_g[0])))
    cache.pool.close()

    tokens = steps * b
    return {
        "batch": b, "steps": steps, "context": s0, "pages": npages,
        "gather": {"tokens_per_s": tokens / wall_g, "wall_s": wall_g,
                   "pool_fetches": gather_fetches},
        "fused": {"tokens_per_s": tokens / wall_f, "wall_s": wall_f,
                  "buffer_hits": buffer_hits,
                  "buffer_misses": buffer_misses},
        "decode_speedup": wall_g / wall_f,
        "tokens_match_gather": match,
        "kernel_max_abs_err": kernel_err,
        "codec": {"bytes_per_fetch_none": bpf_none,
                  "bytes_per_fetch_int8": bpf_int8,
                  "byte_reduction": bpf_none / bpf_int8,
                  "int8_max_abs_err": int8_err},
    }


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="Poisson arrivals per scheduler step")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--chunk-size", type=int, default=8,
                    help="chunked-prefill chunk for the long-prompt section")
    ap.add_argument("--prefill-tokens", type=int, default=None,
                    help="per-step prefill token budget (default: one chunk)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace for CI; implies --out BENCH_serving.json")
    ap.add_argument("--out", default=None, help="write JSON summary here")
    args = ap.parse_args()
    if args.smoke:
        args.requests = min(args.requests, 10)
        args.out = args.out or "BENCH_serving.json"
    enable_compile_cache()

    cfg = REGISTRY[args.arch].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    quantum = 4
    lo, hi = 4, min(24, args.max_seq // 2)
    mk = lambda seed: poisson_trace(
        args.requests, rate=args.rate, vocab_size=cfg.vocab_size,
        prompt_lens=(lo, hi), new_tokens=(2, min(16, args.max_seq // 3)),
        prompt_quantum=quantum, seed=seed)

    # one resident session serves the static + continuous baselines
    resident = HyperOffloadSession(OffloadConfig(
        mode="continuous", max_batch=args.max_batch, max_seq=args.max_seq,
        prefill_budget=2))

    # warm every prefill bucket + both decode shapes outside the timed
    # region (jitted entry points are shared across engine/scheduler
    # instances, so these compiles serve the measured runs)
    warm = [Request(tokens=np.ones((s,), np.int32), max_new_tokens=2,
                    seed=1000 + s)
            for s in range(lo, hi + 1, quantum)]
    for r in warm:   # one batch per bucket → every (max_batch, s) prefill
        run_static(resident, model, params, [r], args.max_batch)
    run_continuous(resident, model, params, warm)

    trace = mk(args.seed)
    static = run_static(resident, model, params, trace, args.max_batch)
    cont = run_continuous(resident, model, params, trace)

    # plan-driven prefetch demo: device tier sized to ~half the running
    # batch, so cold sequences' pages spill to host and get fetched back
    # along the planner's refined order
    off_trace = mk(args.seed + 2)[:max(4, args.requests // 2)]
    row = worst_case_page_bytes(model.cache_specs(1, args.max_seq, jnp.float32))
    off_session = HyperOffloadSession(OffloadConfig(
        mode="kv_offload", max_batch=args.max_batch, max_seq=args.max_seq,
        prefill_budget=2,
        device_capacity=max(1, args.max_batch // 2) * row,
        host_capacity=2 * args.max_batch * row,
        telemetry=TelemetryConfig(enable=True)))
    offload = run_continuous(off_session, model, params, off_trace,
                             kv_offload=True)

    # chunked prefill vs whole-prompt on a mixed short/long-prompt trace:
    # long prompts (up to ~3/4 of max_seq) stall every running decode for
    # a whole step under whole-prompt prefill; chunked prefill bounds the
    # per-step prefill work by the token budget and compiles exactly one
    # executable across every prompt length
    new_hi = max(2, min(12, args.max_seq // 4))
    # long prompts up to ~3/4 of max_seq, never inverted for small
    # --max-seq and always leaving room for the decode budget
    long_hi = min(max(args.max_seq // 2, args.max_seq - 16),
                  args.max_seq - new_hi)
    long_lo = min(args.max_seq // 2, long_hi)
    # the quantum grid must intersect both ranges (poisson_trace rejects a
    # range with no on-grid length): shrink the quantum for small max_seq
    # and align the long range's lower bound down onto the grid
    q_long = max(1, min(8, min(hi, long_lo)))
    long_lo = max(q_long, (long_lo // q_long) * q_long)
    long_trace = poisson_trace(
        args.requests, rate=args.rate, vocab_size=cfg.vocab_size,
        prompt_lens=(lo, min(hi, long_lo)), new_tokens=(2, new_hi),
        prompt_quantum=q_long, long_prompt_lens=(long_lo, long_hi),
        long_fraction=0.3, seed=args.seed + 4)
    long_prompts = run_long_prompt_comparison(
        resident, model, params, long_trace, args.chunk_size,
        args.prefill_tokens)

    # cross-request prefix cache on shared-prefix traffic (off vs on)
    prefix_cache = run_prefix_cache_comparison(
        model, params, requests=args.requests, rate=args.rate,
        vocab_size=cfg.vocab_size, max_batch=args.max_batch,
        max_seq=args.max_seq, chunk_size=args.chunk_size,
        seed=args.seed + 6)

    # closed-loop calibration: static vs measured planning over a
    # latency-dominated modeled tier (same trace both arms)
    calibration = run_calibration_comparison(
        model, params, requests=max(4, args.requests // 2), rate=args.rate,
        vocab_size=cfg.vocab_size, max_batch=args.max_batch,
        max_seq=args.max_seq, seed=args.seed + 10)

    # fused paged-decode kernel vs gather/concat + KV codec wire bytes
    decode_kernel = run_decode_kernel(steps=8 if args.smoke else 32,
                                      seed=args.seed + 12)

    # SLO-aware scheduling vs FIFO at 2-5x overload
    overload = run_overload_comparison(
        model, params, requests=args.requests, vocab_size=cfg.vocab_size,
        max_batch=args.max_batch, max_seq=args.max_seq,
        chunk_size=args.chunk_size, seed=args.seed + 8)

    speedup = cont["tokens_per_s"] / static["tokens_per_s"]
    summary = {
        "arch": cfg.name, "requests": args.requests, "rate": args.rate,
        "max_batch": args.max_batch, "max_seq": args.max_seq,
        "static": static, "continuous": cont, "kv_offload": offload,
        "long_prompts": long_prompts, "prefix_cache": prefix_cache,
        "calibration": calibration, "overload": overload,
        "decode_kernel": decode_kernel,
        # the merged front-door snapshot: pool/transfer counters next to
        # the throughput numbers (tracked in BENCH_serving.json)
        "session": off_session.stats(),
        "throughput_speedup": speedup,
        "step_throughput_speedup":
            cont["tokens_per_step"] / static["tokens_per_step"],
    }
    off_session.close()
    resident.close()
    for mode, r in (("static", static), ("continuous", cont),
                    ("kv_offload", offload)):
        print(f"serve_continuous,{mode},tok/s:{r['tokens_per_s']:.1f},"
              f"tok/step:{r['tokens_per_step']:.2f},"
              f"p50:{r['p50_latency_steps']:.1f},"
              f"p99:{r['p99_latency_steps']:.1f}")
    pf, tr = offload["prefetch"], offload["transfer"]
    print(f"serve_continuous,prefetch,plan_lead:{pf['mean_plan_lead']:.1f},"
          f"issued:{pf['fetches_issued']},"
          f"overlapped:{tr['waits_overlapped']},blocked:{tr['waits_blocked']},"
          f"evictions:{offload['pool_evictions']}")
    ov = offload["overlap"]
    hf = ov["hidden_fraction"]
    print(f"serve_continuous,overlap,transfers:{ov['transfers']},"
          f"hidden_s:{ov['hidden_s']:.4f},exposed_s:{ov['exposed_s']:.4f},"
          f"hidden_fraction:"
          f"{'n/a' if hf is None else format(hf, '.2f')}")
    print(f"serve_continuous,speedup,wall:{speedup:.2f},"
          f"steps:{summary['step_throughput_speedup']:.2f}")
    wl, ck = long_prompts["whole_prompt"], long_prompts["chunked"]
    print(f"serve_continuous,long_whole,prefill_stall_max:"
          f"{wl['max_step_prefill_tokens']},p99_step_ms:"
          f"{wl['p99_step_wall_ms']:.1f},executables:"
          f"{wl['prefill_executables']}")
    print(f"serve_continuous,long_chunked,chunk:{args.chunk_size},"
          f"prefill_step_max:{ck['max_step_prefill_tokens']},p99_step_ms:"
          f"{ck['p99_step_wall_ms']:.1f},executables:"
          f"{ck['prefill_executables']}")
    px = prefix_cache
    print(f"serve_continuous,prefix_cache,saved:{px['prefill_tokens_saved']},"
          f"reduction:{px['prefill_reduction']:.0%},"
          f"hit_rate:{px['hit_rate']:.2f},"
          f"tok/s_on:{px['on']['tokens_per_s']:.1f},"
          f"tok/s_off:{px['off']['tokens_per_s']:.1f}")
    for arm in ("static", "calibrated"):
        c = calibration[arm]
        hf = c["hidden_fraction"]
        print(f"serve_continuous,calibration_{arm},"
              f"tok/s:{c['tokens_per_s']:.1f},"
              f"plan_lead:{c['plan_lead']:.1f},"
              f"workers:{c['workers']},"
              f"hidden_fraction:"
              f"{'n/a' if hf is None else format(hf, '.2f')}")
    dk = decode_kernel
    print(f"serve_continuous,decode_kernel,"
          f"gather_tok/s:{dk['gather']['tokens_per_s']:.1f},"
          f"fused_tok/s:{dk['fused']['tokens_per_s']:.1f},"
          f"speedup:{dk['decode_speedup']:.2f},"
          f"match:{dk['tokens_match_gather']},"
          f"kernel_err:{dk['kernel_max_abs_err']:.1e},"
          f"byte_reduction:{dk['codec']['byte_reduction']:.2f}")
    for factor in ("2x", "3x", "5x"):
        fo, so = overload[factor]["fifo"], overload[factor]["slo"]
        f_tta = fo["attainment"]["classes"]["interactive"]["ttft_attainment"]
        s_tta = so["attainment"]["classes"]["interactive"]["ttft_attainment"]
        print(f"serve_continuous,overload_{factor},"
              f"goodput_fifo:{fo['goodput_tokens_per_step']:.2f},"
              f"goodput_slo:{so['goodput_tokens_per_step']:.2f},"
              f"ttft_att_fifo:{f_tta:.2f},ttft_att_slo:{s_tta:.2f},"
              f"preemptions:{so['preemptions']},shed:{so['shed']}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2, sort_keys=True)
        print(f"serve_continuous,written,{args.out}")


if __name__ == "__main__":
    main()
