"""Serving with a paged, pool-resident KV cache and sparse block selection
(the paper's §5.2 / DeepSeek+NSA case study, on a real small model).

    PYTHONPATH=src python examples/serve_offload.py [--continuous]

A GQA attention layer decodes against a PagedKVCache whose full pages live
in pinned-host (remote pool) memory. Each step selects the top-k most
relevant pages (mean-key summaries), prefetches only those, and attends
over [selected pages ++ device tail]. Selecting all pages is numerically
identical to dense attention; the sparse setting trades a bounded error
for fetching a fraction of the cache — the paper's NSA trade-off.

``--continuous`` instead demos the request-level continuous-batching
scheduler (``repro.sched``): mixed-length Poisson arrivals served on a
small slot pool with plan-driven KV prefetch and host-tier eviction of
cold sequences' pages. Adding ``--slo`` annotates the trace with mixed
interactive/batch priority classes, overloads the arrival rate, and
turns on SLO-aware scheduling (deadline-first admission, preemption,
early shedding) — the demo ends with a per-class attainment summary.

``--trace-out PATH`` turns the session's telemetry on for either demo:
the overlap summary (hidden vs exposed transfer time, straight from the
trace) prints at the end and the Chrome trace-event JSON lands at PATH
(open it at https://ui.perfetto.dev).
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.api import HyperOffloadSession, OffloadConfig
from repro.api.config import TelemetryConfig
from repro.kernels.ref import decode_attention_ref


def _telemetry(trace_out):
    return TelemetryConfig(enable=trace_out is not None,
                           trace_path=trace_out)


def _print_overlap(session, trace_out):
    """Overlap summary from the trace ring (tracing on only)."""
    ov = session.overlap()
    if ov is None:
        return
    hf = ov["hidden_fraction"]
    print(f"overlap: {ov['transfers']} transfers, "
          f"{ov['hidden_s'] * 1e3:.1f} ms hidden / "
          f"{ov['exposed_s'] * 1e3:.1f} ms exposed "
          f"(hidden fraction "
          f"{'n/a' if hf is None else format(hf, '.0%')}); "
          f"trace → {trace_out}")


def main(trace_out=None):
    b, hq, hkv, d = 2, 8, 4, 64
    page, ctx = 32, 512
    scale = d ** -0.5
    ks = jax.random.split(jax.random.key(0), 4)

    n_pages = -(-(ctx + 64) // page)
    page_nbytes = b * page * hkv * d * 4
    # host tier sized to exactly hold every K and V page (overflow would
    # spill to the remote tier) — tier topology is config, not a call site
    session = HyperOffloadSession(OffloadConfig(
        max_seq=ctx + 64, page_size=page,
        host_capacity=2 * n_pages * page_nbytes,
        telemetry=_telemetry(trace_out)))
    cache = session.paged_kv(batch=b, n_kv_heads=hkv, head_dim=d)
    k_ctx = jax.random.normal(ks[0], (b, ctx, hkv, d))
    v_ctx = jax.random.normal(ks[1], (b, ctx, hkv, d))
    cache.prefill(k_ctx, v_ctx)
    print(f"prefilled {ctx} tokens → {cache.full_pages} pool pages "
          f"(host-resident) + {cache.tail_len} tail tokens")

    q = jax.random.normal(ks[2], (b, hq, d))

    # dense oracle
    kd = k_ctx.transpose(0, 2, 1, 3)
    ref = decode_attention_ref(q, kd, v_ctx.transpose(0, 2, 1, 3),
                               jnp.int32(ctx - 1), scale=scale)

    t0 = time.time()
    out_all = cache.attend(q, scale=scale, top_k_pages=None)
    t_all = time.time() - t0
    err_all = float(jnp.max(jnp.abs(out_all - ref)))

    for k in (8, 4, 2):
        cache.fetches = 0
        t0 = time.time()
        out_k = cache.attend(q, scale=scale, top_k_pages=k)
        dt = time.time() - t0
        err = float(jnp.max(jnp.abs(out_k - ref)))
        print(f"top-{k:2d} pages: fetched {cache.fetches}/{cache.full_pages} "
              f"pages, err vs dense {err:.3e}, {dt * 1e3:.1f} ms")
    print(f"all pages: err {err_all:.3e} (exact), {t_all * 1e3:.1f} ms")

    # fused paged decode: pages install once into the device page buffer,
    # the page table indexes them in place — no per-step gather/concat
    out_f = cache.attend_fused(q, scale=scale)     # warm: installs pages
    t0 = time.time()
    out_f = cache.attend_fused(q, scale=scale)
    jax.block_until_ready(out_f)
    t_fused = time.time() - t0
    print(f"fused decode: bitwise match {bool(jnp.all(out_f == out_all))}, "
          f"{t_fused * 1e3:.1f} ms ({cache.buffer_hits} buffer hits / "
          f"{cache.buffer_misses} installs)")

    # decode loop with async prefetch: select on the post-append state (so a
    # page flushed this step is a candidate), issue all page fetches at once
    # through the transfer engine, and wait only inside attend — the fetches
    # overlap each other and the selection/summary work
    flushes0 = cache.flushes
    for t in range(64):
        cache.append(jax.random.normal(jax.random.fold_in(ks[3], t), (b, hkv, d)),
                     jax.random.normal(jax.random.fold_in(ks[3], 1000 + t), (b, hkv, d)))
        inflight = cache.prefetch_pages(cache.select_pages(q, top_k=4))
        _ = cache.attend(q, scale=scale, prefetched=inflight)
    print(f"decoded 64 tokens; {cache.flushes - flushes0} pages flushed to "
          f"the pool during decode; cache length {cache.length}")

    # pool-manager traffic/occupancy: what the runtime actually moved
    s = cache.pool_stats()
    host, xfer = s["tier/host"], s["transfer"]
    print(f"pool stats: {s['puts']} puts / {s['gets']} gets, "
          f"{s['bytes_stored'] / 1e6:.2f} MB stored, "
          f"{s['bytes_fetched'] / 1e6:.2f} MB fetched, "
          f"host tier {host['used'] / 1e6:.2f}/{(host['capacity'] or 0) / 1e6:.2f} MB "
          f"({host['entries']} pages, backend {host['backend']})")
    print(f"transfer engine: {xfer['issued']} async fetches issued, "
          f"{xfer['waits_overlapped']} fully overlapped, "
          f"{xfer['waits_blocked']} blocked ({xfer['blocked_s'] * 1e3:.1f} ms exposed)")
    _print_overlap(session, trace_out)
    session.close()


def main_continuous(trace_out=None, slo=False):
    """Continuous-batching scheduler demo: mixed traffic, pool-parked KV."""
    from repro.configs import REGISTRY
    from repro.models.model import build_model
    from repro.offload.kvcache import worst_case_page_bytes
    from repro.sched import poisson_trace
    from repro.slo import SLOConfig, attainment_summary

    cfg = REGISTRY["phi3-mini-3.8b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    max_batch, max_seq = 3, 48
    row = worst_case_page_bytes(model.cache_specs(1, max_seq, jnp.float32))
    # device tier ≈ 1.5 cache rows: cold sequences' pages spill to host
    session = HyperOffloadSession(OffloadConfig(
        mode="kv_offload", max_batch=max_batch, max_seq=max_seq,
        prefill_budget=2,
        device_capacity=int(1.5 * row),
        host_capacity=2 * max_batch * row,
        telemetry=_telemetry(trace_out),
        slo=SLOConfig(enable=slo)))
    sched = session.scheduler(model, params)
    # --slo: overload the arrival rate and mix interactive (TTFT-deadline)
    # with batch (throughput-only) requests, so the deadline-first policy
    # has something to prioritize
    rate = 2.4 if slo else 0.8
    trace = poisson_trace(10, rate=rate, vocab_size=cfg.vocab_size,
                          prompt_lens=(4, 16), new_tokens=(2, 12),
                          prompt_quantum=4,
                          interactive_fraction=0.4 if slo else None,
                          seed=0)
    t0 = time.time()
    out = sched.run(trace)
    dt = time.time() - t0
    tokens = sum(len(v) for v in out.values())
    st = sched.stats
    print(f"continuous scheduler: {len(out)} requests, {tokens} tokens in "
          f"{st.steps} steps ({dt:.2f}s wall) — {st.joins} joins / "
          f"{st.retires} retires, {sched.admission.blocked} admission blocks")
    print(f"pages: {st.pages_parked} parked, {st.cold_spills} cold spills "
          f"to lower tiers")
    pf = sched.prefetch_stats()
    xfer = sched.pool_stats()["transfer"]
    print(f"plan-driven prefetch: {pf['fetches_issued']} fetches over "
          f"{pf['layers_planned']} planned layers, mean plan lead "
          f"{pf['mean_plan_lead']:.1f} slots; {xfer['waits_overlapped']} "
          f"waits overlapped / {xfer['waits_blocked']} blocked")
    lat = sorted(s.t_done - s.request.arrival for s in sched.finished.values())
    print(f"latency (steps): p50 {lat[len(lat) // 2]:.1f}, max {lat[-1]:.1f}")
    if slo:
        att = attainment_summary(sched.finished.values())
        print(f"slo: {att['met_tokens']}/{att['tokens']} tokens within "
              f"deadline ({st.preemptions} preemptions, {st.resumes} "
              f"resumes, {st.shed} shed)")
        for cls, c in sorted(att["classes"].items()):
            tta = c["ttft_attainment"]
            print(f"  {cls}: {c['met_tokens']}/{c['tokens']} tokens met "
                  f"({c['requests']} requests, {c['shed']} shed), "
                  f"ttft attainment "
                  f"{'n/a' if tta is None else format(tta, '.0%')}")
    _print_overlap(session, trace_out)
    session.close()   # closes the scheduler and the session-owned pool


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--continuous", action="store_true",
                    help="run the continuous-batching scheduler demo")
    ap.add_argument("--slo", action="store_true",
                    help="with --continuous: overloaded mixed-class trace "
                         "under SLO-aware scheduling + attainment summary")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="enable telemetry; write the Chrome trace here")
    args = ap.parse_args()
    if args.continuous:
        main_continuous(args.trace_out, slo=args.slo)
    elif args.slo:
        ap.error("--slo requires --continuous")
    else:
        main(args.trace_out)
