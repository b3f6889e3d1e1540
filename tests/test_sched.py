"""Continuous-batching scheduler: token identity vs. sequential serving
(including mid-stream joins/retirements and host-tier eviction), admission
control never over-committing pool capacity, and plan-driven prefetch
issuing ahead of consumption."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import hypothesis_or_stub

given, settings, st = hypothesis_or_stub()

from repro.configs import REGISTRY
from repro.models.model import build_model
from repro.offload.kvcache import worst_case_page_bytes
from repro.pool import DEVICE_TIER, HOST_TIER, TransferEngine, default_pool
from repro.sched import (
    ArrivalQueue, ContinuousScheduler, Request, SchedulerConfig,
    poisson_trace,
)
from repro.serving.engine import ServeEngine, jit_prefill_chunk

CFG = REGISTRY["phi3-mini-3.8b"].reduced()
MAX_SEQ = 32


@pytest.fixture(scope="module")
def model_and_params():
    m = build_model(CFG)
    return m, m.init(jax.random.key(0))


def _mixed_trace():
    """Staggered arrivals + mixed lengths on a 2-slot batch: forces
    mid-stream joins, retirements, and continuous slot reuse."""
    rng = np.random.default_rng(0)
    shapes = [(5, 6, 0.0), (9, 3, 0.0), (3, 8, 2.0), (7, 1, 4.0), (4, 5, 4.0)]
    return [Request(tokens=rng.integers(0, CFG.vocab_size, size=s,
                                        dtype=np.int32),
                    max_new_tokens=n, arrival=a, seed=i)
            for i, (s, n, a) in enumerate(shapes)]


def _sequential_reference(model, params, requests, **kw):
    eng = ServeEngine(model, params, max_seq=MAX_SEQ)
    out = {}
    for r in requests:
        got = eng.generate({"tokens": jnp.asarray(r.tokens[None, :])},
                           r.max_new_tokens, seed=r.seed, **kw)
        out[r.req_id] = np.asarray(got)[0]
    eng.close()
    return out


def test_continuous_matches_sequential_greedy(model_and_params):
    model, params = model_and_params
    reqs = _mixed_trace()
    sched = ContinuousScheduler(model, params,
                                SchedulerConfig(max_batch=2, max_seq=MAX_SEQ))
    out = sched.run(reqs)
    assert sched.stats.joins == len(reqs) and sched.stats.retires == len(reqs)
    # the 2-slot batch over 5 staggered requests must have reused slots
    assert sched.stats.steps < sum(r.max_new_tokens for r in reqs)
    ref = _sequential_reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    sched.close()
    sched.close()   # idempotent


def test_decode_through_ring_kernel_counts_live_blocks(model_and_params,
                                                      monkeypatch):
    """Empty slots decode at position -1. With the decode read through the
    ring decode kernel (interpreted, as it runs on a TPU) the greedy tokens
    equal the sequential jnp reference, and the scheduler's counters
    advance by what the kernel's grid fetches at each step's positions
    (``kernels.decode_attention.slots_read``) and by the whole rings."""
    from repro.kernels import decode_attention as rd
    from repro.models import attention as A
    model, params = model_and_params
    reqs = _mixed_trace()
    ref = _sequential_reference(model, params, reqs)
    monkeypatch.setattr(A, "_attend_xla",
                        functools.partial(A._attend_kernel, interpret=True))
    # a model of its own, so that no decode traced on the jnp read is reused
    kernel_model = build_model(dataclasses.replace(
        CFG, name=CFG.name + "-ring-kernel"))
    sched = ContinuousScheduler(kernel_model, params,
                                SchedulerConfig(max_batch=2, max_seq=MAX_SEQ))
    positions = []
    decode = sched._decode

    def spy(params, cache, tok, pos):
        positions.append(np.asarray(pos))
        return decode(params, cache, tok, pos)

    sched._decode = spy
    out = sched.run(reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    assert any((p == -1).any() for p in positions)
    bk = rd.block_k(MAX_SEQ, CFG.n_kv_heads * CFG.head_dim, 4)
    want = CFG.n_layers * sum(
        rd.slots_read(rd.ring_lengths(p, MAX_SEQ), bk) for p in positions)
    assert sched.stats.decode_kv_slots_read == want
    assert sched.stats.decode_kv_slots_ring == (
        CFG.n_layers * len(positions) * 2 * MAX_SEQ)
    assert 0 < want < sched.stats.decode_kv_slots_ring
    sched.close()


def test_offload_matches_sequential_and_evicts_to_host(model_and_params):
    """kv_offload mode under device-tier pressure: cold sequences' pages
    spill to the host tier via the priority+LRU manager, fetches run
    through the plan, and outputs stay token-identical."""
    model, params = model_and_params
    reqs = _mixed_trace()
    row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ, jnp.float32))
    pool = default_pool(device_capacity=int(1.5 * row),
                        host_capacity=4 * row,
                        transfer=TransferEngine(depth=64))
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, kv_offload=True),
        pool=pool)
    out = sched.run(reqs)
    ref = _sequential_reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    snap = sched.pool_stats()
    assert snap["evictions"] > 0 and sched.stats.cold_spills > 0
    assert snap["tier/remote"]["entries"] == 0       # admission held
    sched.close()
    pool.close()


@pytest.mark.parametrize("codec", ["int8", "fp8"])
def test_offload_with_kv_codec_stays_token_identical(model_and_params, codec):
    """Quantized KV pages through the full continuous-scheduler
    park/restore path: the same device-pressure trace as above, but
    spilled pages round-trip through the codec host tier. On this trace
    the quantization noise flips no greedy tokens (pinned empirically —
    the hard requirement is the bounded codec round-trip, exercised end
    to end), and the on-wire spill traffic shrinks ~4× for fp32 pages."""
    model, params = model_and_params
    row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ, jnp.float32))

    def _run(name):
        pool = default_pool(device_capacity=int(1.5 * row),
                            host_capacity=4 * row,
                            transfer=TransferEngine(depth=64),
                            codec=name, codec_below="host")
        sched = ContinuousScheduler(
            model, params,
            SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, kv_offload=True),
            pool=pool)
        reqs = _mixed_trace()
        raw = sched.run(reqs)
        out = {r.seed: raw[r.req_id] for r in reqs}
        snap = sched.pool_stats()
        sched.close()
        pool.close()
        return out, snap

    exact, snap0 = _run(None)
    quant, snap1 = _run(codec)
    assert snap1["evictions"] > 0                 # pressure actually spilled
    for seed in exact:
        np.testing.assert_array_equal(quant[seed], exact[seed])
    spill0 = snap0["transfer"]["pairs"]["device->host"]["bytes"]
    spill1 = snap1["transfer"]["pairs"]["device->host"]["bytes"]
    assert spill1 * 2 <= spill0                   # >= 2x wire-byte reduction


def test_prefetcher_issues_ahead_of_consumption(model_and_params):
    """The plan schedules every layer's fetch before its consumer, and at
    runtime most waits find the transfer already complete — the
    store-then-immediately-wait round trip is gone from the decode loop."""
    model, params = model_and_params
    pool = default_pool()
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, kv_offload=True),
        pool=pool)
    sched.run(_mixed_trace())
    pf = sched.prefetch_stats()
    assert pf["fetches_issued"] > 0
    assert pf["mean_plan_lead"] >= 1.0          # issued ahead in the plan
    tr = sched.pool_stats()["transfer"]
    assert tr["issued"] == pf["fetches_issued"]
    assert tr["waits_overlapped"] > 0           # overlapped at runtime too
    sched.close()
    pool.close()


def test_temperature_sampling_matches_batch1_engine(model_and_params):
    """For temperature>0 the scheduler reproduces a batch-1 engine run's
    key stream (first token from the raw seed key, one split per step)."""
    model, params = model_and_params
    rng = np.random.default_rng(3)
    reqs = [Request(tokens=rng.integers(0, CFG.vocab_size, size=s,
                                        dtype=np.int32),
                    max_new_tokens=4, temperature=0.8, top_k=8, seed=i)
            for i, s in enumerate((5, 8))]
    sched = ContinuousScheduler(model, params,
                                SchedulerConfig(max_batch=2, max_seq=MAX_SEQ))
    out = sched.run(reqs)
    ref = _sequential_reference(model, params, reqs,
                                temperature=0.8, top_k=8)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    sched.close()


# ---------------------------------------------------------------------------
# chunked cache-aware prefill
# ---------------------------------------------------------------------------


def _long_trace():
    """Short and long prompts interleaved on a 2-slot batch: long prompts
    span several chunks, so PREFILL persists across steps while other
    requests join, decode, and retire around it."""
    rng = np.random.default_rng(1)
    shapes = [(5, 6, 0.0), (20, 3, 0.0), (9, 4, 2.0), (23, 2, 4.0),
              (4, 5, 4.0)]
    return [Request(tokens=rng.integers(0, CFG.vocab_size, size=s,
                                        dtype=np.int32),
                    max_new_tokens=n, arrival=a, seed=i)
            for i, (s, n, a) in enumerate(shapes)]


def _chunked_identity(model, params, chunk, **cfg_kw):
    reqs = _long_trace()
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, chunk_size=chunk,
                        **cfg_kw))
    out = sched.run(reqs)
    ref = _sequential_reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    return sched


def test_chunked_prefill_matches_whole_prompt(model_and_params):
    """chunk_size=4: every prompt spans multiple chunks; outputs must be
    token-identical to sequential whole-prompt serving across joins and
    retires."""
    model, params = model_and_params
    sched = _chunked_identity(model, params, 4)
    # long prompts really advanced chunk-by-chunk across steps
    assert sched.stats.prefill_chunks > sched.stats.joins
    assert sched.stats.prefill_tokens == sum(
        st.request.prompt_len for st in sched.finished.values())
    sched.close()


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [16, MAX_SEQ])
def test_chunked_prefill_matches_whole_prompt_coarse(model_and_params, chunk):
    """Coarser chunks (including chunk_size == max_seq, the whole-prompt-
    in-one-chunk degenerate case) stay token-identical."""
    model, params = model_and_params
    _chunked_identity(model, params, chunk).close()


@pytest.mark.slow
def test_chunked_prefill_kv_offload_identity(model_and_params):
    """Chunked prefill under kv_offload with a tight device tier: partial
    chunk rows park/restore through the pool between steps, cold pages
    spill to host, and outputs stay token-identical."""
    model, params = model_and_params
    reqs = _long_trace()
    row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ, jnp.float32))
    pool = default_pool(device_capacity=int(1.5 * row),
                        host_capacity=4 * row,
                        transfer=TransferEngine(depth=64))
    # prefill_tokens=8 > chunk_size exercises multi-chunk advancement per
    # step (row held resident across chunks, parked once per step)
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, chunk_size=4,
                        prefill_tokens=8, kv_offload=True),
        pool=pool)
    out = sched.run(reqs)
    ref = _sequential_reference(model, params, reqs)
    for r in reqs:
        np.testing.assert_array_equal(out[r.req_id], ref[r.req_id])
    # mid-prefill rows really were parked page-by-page (pages_parked counts
    # both prefill parks and decode parks; chunks > joins ⇒ prefill parked)
    assert sched.stats.prefill_chunks > sched.stats.joins
    snap = sched.pool_stats()
    assert snap["evictions"] > 0
    assert snap["tier/remote"]["entries"] == 0       # admission held
    sched.close()
    pool.close()


def test_chunked_prefill_compiles_once(model_and_params):
    """Mixed prompt lengths through one chunk shape compile exactly ONE
    prefill executable — the structural fix for whole-prompt prefill's
    per-length compile churn. (chunk_size=8 is used by no other test, so
    the jit cache delta is exactly this test's compiles.)"""
    model, params = model_and_params
    fn = jit_prefill_chunk(model)
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jax jit cache-size introspection unavailable")
    before = fn._cache_size()
    rng = np.random.default_rng(2)
    reqs = [Request(tokens=rng.integers(0, CFG.vocab_size, size=s,
                                        dtype=np.int32),
                    max_new_tokens=2, seed=i)
            for i, s in enumerate((5, 9, 14, 23, 26))]   # 5 distinct lengths
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, chunk_size=8))
    sched.run(reqs)
    assert fn._cache_size() - before == 1
    sched.close()


def test_chunked_prefill_token_budget_bounds_step(model_and_params):
    """prefill_tokens is a per-step token budget: with the default (one
    chunk) no step advances prefill by more than chunk_size tokens, even
    when a long prompt is waiting — the whole-prompt stall is gone."""
    model, params = model_and_params
    reqs = _long_trace()
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, chunk_size=4))
    for r in reqs:
        sched.submit(r)
    max_step_prefill = 0
    while len(sched.queue) or sched.active:
        if not sched.active and sched.queue.head_ready(sched.now) is None:
            sched.now = max(sched.now, sched.queue.next_arrival())
        before = sched.stats.prefill_tokens
        sched.step()
        max_step_prefill = max(max_step_prefill,
                               sched.stats.prefill_tokens - before)
    assert 0 < max_step_prefill <= 4
    # a doubled budget admits two chunks per step
    sched2 = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, chunk_size=4,
                        prefill_tokens=8))
    sched2.run(_long_trace())
    assert sched2.stats.steps < sched.stats.steps
    sched.close()
    sched2.close()


def test_chunked_long_prompts_do_not_trip_progress_guard(model_and_params):
    """Many long prompts at one chunk per step exceed the old
    decode-budget-only max_steps bound; the chunk-aware bound (ceil(prompt
    / chunk) extra steps per request) must let them complete."""
    model, params = model_and_params
    toks = np.ones((28,), np.int32)
    reqs = [Request(tokens=toks, max_new_tokens=1, seed=i) for i in range(8)]
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, chunk_size=4))
    out = sched.run(reqs)                     # default max_steps — no raise
    assert len(out) == len(reqs)
    # 8 requests x ceil(28/4)=7 chunk steps alone exceed the old bound of
    # 16 + 2*sum(max_new+1) = 48
    assert sched.stats.steps > 48
    sched.close()


def test_chunked_prefill_rejects_unsupported_models(model_and_params):
    model, params = model_and_params
    ssm_cfg = REGISTRY["mamba2-370m"].reduced()
    ssm = build_model(ssm_cfg)
    ssm_params = ssm.init(jax.random.key(0))
    with pytest.raises(ValueError, match="chunked prefill"):
        ContinuousScheduler(
            ssm, ssm_params,
            SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, chunk_size=4))
    with pytest.raises(ValueError, match="chunk_size"):
        ContinuousScheduler(
            model, params,
            SchedulerConfig(max_batch=1, max_seq=MAX_SEQ,
                            chunk_size=MAX_SEQ + 1))
    with pytest.raises(ValueError, match="requires chunk_size"):
        ContinuousScheduler(
            model, params,
            SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, prefill_tokens=8))


# ---------------------------------------------------------------------------
# arrival queue + trace generator
# ---------------------------------------------------------------------------


def test_arrival_queue_insort_scales_and_orders():
    """Regression for the O(n^2 log n) full re-sort per push: several
    thousand submits in adversarial (reverse-arrival) order stay cheap and
    come out ordered by (arrival, req_id) via the public accessor."""
    import time as _time
    q = ArrivalQueue()
    toks = np.ones((2,), np.int32)
    rng = np.random.default_rng(0)
    arrivals = np.concatenate([np.linspace(100.0, 0.0, 2000),
                               rng.uniform(0.0, 100.0, 2000)])
    t0 = _time.perf_counter()
    for a in arrivals:
        q.push(Request(tokens=toks, max_new_tokens=1, arrival=float(a)))
    elapsed = _time.perf_counter() - t0
    pend = q.pending()
    assert len(pend) == len(q) == 4000
    keys = [(s.request.arrival, s.req_id) for s in pend]
    assert keys == sorted(keys)
    assert elapsed < 5.0          # generous; the old path was ~quadratic


def test_poisson_trace_quantum_grid():
    """Prompt lengths land ON the quantum grid even when the range bounds
    are off-grid (the old round-down emitted the off-grid lower bound)."""
    tr = poisson_trace(64, rate=1.0, vocab_size=128, prompt_lens=(6, 21),
                       prompt_quantum=4, seed=0)
    lens = sorted({r.prompt_len for r in tr})
    assert all(l % 4 == 0 for l in lens)
    # ceil grid of lo=6, clamped at hi=21's grid floor — a caller sizing
    # hi against max_seq must never receive a longer prompt than asked
    assert lens[0] >= 8 and lens[-1] <= 20


def test_poisson_trace_rejects_oversized_quantum():
    """No on-grid length exists past a range's upper bound — emitting a
    longer-than-asked prompt would overflow callers' max_seq sizing."""
    with pytest.raises(ValueError, match="prompt_quantum"):
        poisson_trace(4, rate=1.0, vocab_size=128, prompt_lens=(2, 6),
                      prompt_quantum=8, seed=0)
    with pytest.raises(ValueError, match="long_prompt_lens"):
        poisson_trace(4, rate=1.0, vocab_size=128, prompt_lens=(8, 16),
                      long_prompt_lens=(2, 6), long_fraction=0.5,
                      prompt_quantum=8, seed=0)


def test_poisson_trace_interactive_annotations():
    """Mixed interactive/batch mode: every request carries a spec, the
    interactive share is ~the asked fraction, and custom specs pass
    through untouched."""
    from repro.slo import SLOSpec
    tr = poisson_trace(64, rate=1.0, vocab_size=128,
                       interactive_fraction=0.35, seed=0)
    classes = [r.slo.priority_class for r in tr]
    assert set(classes) == {"interactive", "batch"}
    assert 0.15 < classes.count("interactive") / len(tr) < 0.55
    for r in tr:
        if r.slo.priority_class == "interactive":
            assert r.slo.ttft_deadline == 8.0       # default tight TTFT
        else:
            assert r.slo.ttft_deadline is None      # batch: throughput only
    custom = poisson_trace(
        16, rate=1.0, vocab_size=128, interactive_fraction=0.5,
        interactive_slo=SLOSpec("interactive", ttft_deadline=3.0),
        batch_slo=SLOSpec("standard", tpot_deadline=2.0), seed=0)
    for r in custom:
        assert r.slo.ttft_deadline == 3.0 \
            or r.slo.tpot_deadline == 2.0
    with pytest.raises(ValueError, match="interactive_fraction"):
        poisson_trace(4, rate=1.0, vocab_size=128,
                      interactive_fraction=1.5, seed=0)


def test_poisson_trace_annotations_off_is_byte_identical():
    """With interactive_fraction=None the RNG call sequence is unchanged:
    tokens/lengths/arrivals match an annotated trace of the same seed
    draw for draw (the class draw comes after all existing draws)."""
    a = poisson_trace(8, rate=1.0, vocab_size=128, seed=3)
    b = poisson_trace(8, rate=1.0, vocab_size=128, seed=3,
                      interactive_fraction=0.9)
    assert all(r.slo is None for r in a)
    for x, y in zip(a, b):
        assert x.arrival == y.arrival
        assert x.max_new_tokens == y.max_new_tokens
        np.testing.assert_array_equal(x.tokens, y.tokens)


def test_poisson_trace_long_tail():
    long = poisson_trace(64, rate=1.0, vocab_size=128, prompt_lens=(4, 8),
                         long_prompt_lens=(40, 48), long_fraction=0.5,
                         prompt_quantum=4, seed=0)
    lens = [r.prompt_len for r in long]
    assert any(l >= 40 for l in lens) and any(l <= 8 for l in lens)
    assert all(l % 4 == 0 for l in lens)
    # RNG call sequence is unchanged while the tail is disabled
    a = poisson_trace(8, rate=1.0, vocab_size=128, seed=3)
    b = poisson_trace(8, rate=1.0, vocab_size=128, seed=3, long_fraction=0.9)
    for x, y in zip(a, b):
        assert x.arrival == y.arrival
        np.testing.assert_array_equal(x.tokens, y.tokens)


# ---------------------------------------------------------------------------
# admission control
# ---------------------------------------------------------------------------


def _run_checking_invariants(model, params, reqs, slots, device_rows,
                             host_rows):
    row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ, jnp.float32))
    pool = default_pool(device_capacity=device_rows * row,
                        host_capacity=host_rows * row,
                        transfer=TransferEngine(depth=64))
    cap = device_rows * row + host_rows * row
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=slots, max_seq=MAX_SEQ, kv_offload=True),
        pool=pool)
    for r in reqs:
        sched.submit(r)
    guard = 0
    max_active = 0
    while len(sched.queue) or sched.active:
        if not sched.active and sched.queue.head_ready(sched.now) is None:
            sched.now = sched.queue.next_arrival()
        sched.step()
        max_active = max(max_active, len(sched.active))
        # over-commit invariants, checked EVERY step:
        assert sched.pool.reserved_bytes((DEVICE_TIER, HOST_TIER)) <= cap
        snap = sched.pool.snapshot()
        assert snap["tier/remote"]["entries"] == 0, \
            "pages forced into the remote tier — admission over-committed"
        guard += 1
        assert guard < 500
    assert len(sched.finished) == len(reqs)
    assert max_active <= device_rows + host_rows   # ≤ capacity in rows
    assert sched.pool.reserved_bytes() == 0      # all released at retirement
    sched.close()
    pool.close()
    return sched


def test_admission_never_overcommits_deterministic(model_and_params):
    model, params = model_and_params
    blocked = 0
    for seed in range(3):
        # rate 5.0 clusters arrivals and decode budgets of 3-8 steps keep
        # capacity held, so a 3rd request contends while two (the whole
        # device+host capacity) are running
        reqs = poisson_trace(6, rate=5.0, vocab_size=CFG.vocab_size,
                             prompt_lens=(4, 8), new_tokens=(3, 8),
                             prompt_quantum=4, seed=seed)
        sched = _run_checking_invariants(model, params, reqs,
                                         slots=3, device_rows=1, host_rows=1)
        blocked += sched.admission.blocked
    assert blocked > 0    # 3 slots but capacity for 2 → admission gated


@given(st.integers(0, 10_000), st.integers(2, 4), st.integers(1, 2),
       st.integers(1, 2))
@settings(max_examples=8, deadline=None)
def test_admission_never_overcommits_property(seed, n_reqs, device_rows,
                                              host_rows):
    m = build_model(CFG)
    params = m.init(jax.random.key(0))
    reqs = poisson_trace(n_reqs, rate=2.0, vocab_size=CFG.vocab_size,
                         prompt_lens=(4, 8), new_tokens=(1, 3),
                         prompt_quantum=4, seed=seed)
    _run_checking_invariants(m, params, reqs, slots=3,
                             device_rows=device_rows, host_rows=host_rows)


def test_covered_reservations_allow_full_concurrency(model_and_params):
    """A running request's parked pages are charged via its reservation
    (``covers``), not double-counted as occupancy: capacity for exactly two
    worst-case rows really admits two concurrent requests."""
    model, params = model_and_params
    row = worst_case_page_bytes(model.cache_specs(1, MAX_SEQ, jnp.float32))
    pool = default_pool(device_capacity=row, host_capacity=row,
                        transfer=TransferEngine(depth=64))
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=2, max_seq=MAX_SEQ, prefill_budget=2,
                        kv_offload=True),
        pool=pool)
    reqs = [Request(tokens=np.ones((4,), np.int32), max_new_tokens=6, seed=i)
            for i in range(2)]
    for r in reqs:
        sched.submit(r)
    for _ in range(3):
        sched.step()
    assert len(sched.active) == 2       # both admitted, despite parked pages
    sched.run()
    assert sched.pool.snapshot()["tier/remote"]["entries"] == 0
    sched.close()
    pool.close()


def test_arrival_queue_orders_by_arrival_not_submission(model_and_params):
    """A future-dated request submitted first must not shadow an
    already-arrived later submission."""
    model, params = model_and_params
    late = Request(tokens=np.ones((4,), np.int32), max_new_tokens=2,
                   arrival=50.0, seed=0)
    early = Request(tokens=np.ones((4,), np.int32), max_new_tokens=2,
                    arrival=0.0, seed=1)
    sched = ContinuousScheduler(model, params,
                                SchedulerConfig(max_batch=1, max_seq=MAX_SEQ))
    sched.submit(late)
    sched.submit(early)
    sched.run()
    assert sched.finished[early.req_id].t_done < 50.0   # served before late
    sched.close()


def test_oversized_request_raises(model_and_params):
    model, params = model_and_params
    sched = ContinuousScheduler(model, params,
                                SchedulerConfig(max_batch=1, max_seq=MAX_SEQ))
    with pytest.raises(ValueError, match="exceeds max_seq"):
        sched.submit(Request(tokens=np.ones((MAX_SEQ,), np.int32),
                             max_new_tokens=4))
    sched.close()


def test_unadmittable_request_raises(model_and_params):
    """A request whose worst-case pages exceed device+host capacity must
    fail loudly, not deadlock the queue."""
    model, params = model_and_params
    pool = default_pool(device_capacity=64, host_capacity=64)
    sched = ContinuousScheduler(
        model, params,
        SchedulerConfig(max_batch=1, max_seq=MAX_SEQ, kv_offload=True),
        pool=pool)
    sched.submit(Request(tokens=np.ones((4,), np.int32), max_new_tokens=2))
    with pytest.raises(RuntimeError, match="never be admitted"):
        sched.step()
    sched.close()
    pool.close()


# ---------------------------------------------------------------------------
# engine round-trip key churn fix
# ---------------------------------------------------------------------------


def test_engine_round_trip_uses_stable_keys(model_and_params):
    model, params = model_and_params
    pool = default_pool()
    eng = ServeEngine(model, params, max_seq=MAX_SEQ, offload_kv=True,
                      pool=pool)
    toks = jnp.ones((1, 4), jnp.int32)
    eng.generate({"tokens": toks}, 5)
    snap = eng.pool_stats()
    n_leaves = len(jax.tree.leaves(model.init_cache(1, MAX_SEQ)))
    # stable keys: 4 round trips re-put the same leaf entries; the only
    # drops are the end-of-generate release (≤ one per leaf, not per step)
    assert eng.stats.cache_round_trips == 4
    assert snap["puts"] == 4 * n_leaves
    assert snap["drops"] <= n_leaves
    assert snap["tier/host"]["entries"] == 0     # released after generate
    eng.close()
    eng.close()   # idempotent
    pool.close()
