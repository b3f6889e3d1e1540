"""Request-level continuous-batching scheduler with plan-driven KV prefetch.

The step loop joins and retires sequences **every decode step** (continuous
batching): a fixed pool of ``max_batch`` cache slots holds the running
requests; each step the scheduler

1. retires the handles of the previous step's plan-driven page fetches
   (``kv_offload`` mode) and reassembles the stacked decode cache;
2. admits queued requests — at most ``prefill_budget`` per step, so prompt
   prefill interleaves with decode instead of stalling it — if a slot is
   free AND the pool's device+host tiers can hold the request's worst-case
   pages (``AdmissionController``); admitted prompts are prefilled
   (batch-1) and scattered into their slot, and their first token sampled
   from the prefill logits exactly as ``ServeEngine.generate`` does;

   with **chunked prefill** (``chunk_size`` set) prompts instead advance
   ``chunk_size`` tokens per scheduler step through one fixed-shape
   ``jit_prefill_chunk`` executable (final partial chunks padded and
   masked): the PREFILL state persists across steps, the per-step budget
   is ``prefill_tokens`` *tokens* (default: one chunk) rather than a
   whole-prompt count, and the first token is sampled only when the last
   chunk lands — a long prompt can no longer stall every running decode
   for its full prefill, and mixed-length traffic compiles exactly one
   prefill executable instead of one per distinct prompt length. Between
   chunk steps the partial batch-1 row cache stays on the request state
   (resident) or is parked page-by-page through the pool (``kv_offload``),
   under the same ``L{i}.{j}`` labels the decode loop parks under;
3. decodes all running requests in ONE batched ``decode_step`` with
   per-row positions (rows are independent, so each row's tokens equal the
   per-request run), samples per request from its own seed-derived key
   stream, and retires requests that hit their budget — freeing slots for
   step 2 of the next iteration;
4. in ``kv_offload`` mode, parks every running request's pages back into
   the pool (stable per-page keys, priority = remaining decode budget — the
   pool's priority+LRU manager spills *cold* sequences' pages, those
   closest to retirement, to the host tier under device-tier pressure) and
   immediately issues the next step's fetches along the planner's refined
   order (``PlanPrefetcher``) — ahead of their consumers, with the next
   step's admission and prefill work between issue and wait, replacing the
   reactive store-then-immediately-wait round trip.

Time is a virtual clock (1.0 per step) so arrival traces, admission and
the SLO policy are deterministic. Each request also carries wall-clock
stamps (submit, first slot, first token, retire) behind the per-request
latency histograms, in seconds. With a tracer, each of its state changes
closes a ``request.queue``, ``request.prefill`` or ``request.decode``
span, and inside the step's phase spans ``dispatch`` wraps each jitted
call the step enqueues and ``device_wait`` each read that blocks the host
on the device: a step's host work is its ``step`` span less its
``device_wait`` spans.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.costmodel import HardwareSpec, TPU_V5E
from repro.core.insertion import InsertionOptions
from repro.kernels.decode_attention import block_k, ring_lengths, slots_read
from repro.models.model import Model
from repro.obs.metrics import SECONDS_BUCKETS, MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.offload.kvcache import KVPageTable, worst_case_page_bytes
from repro.pool import MemoryPoolManager, auto_depth, default_pool
from repro.pool.manager import PoolEntry
from repro.prefix import PrefixCacheManager
from repro.sched.prefetch import InFlightFetches, PlanPrefetcher
from repro.sched.queue import AdmissionController, ArrivalQueue
from repro.sched.requests import (
    DECODE, DONE, PREEMPTED, PREFILL, SHED, Request, RequestState,
)
from repro.serving.engine import jit_decode, jit_prefill, jit_prefill_chunk
from repro.serving.sampling import sample_token
from repro.slo.admission import GoodputController
from repro.slo.policy import SLOConfig, candidate_key
from repro.slo.preempt import PreemptionEngine

#: pool priority of a preempted request's parked pages: below every live
#: sequence's pages (priority >= 1, their remaining work) but above the
#: prefix cache's 0.0 — device pressure spills preempted rows first.
_PREEMPTED_PAGE_PRIO = 0.25

_SCHED_IDS = itertools.count()


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    max_batch: int = 4            # cache slots (concurrent requests)
    max_seq: int = 128            # per-slot cache capacity
    prefill_budget: int = 1       # prompts prefilled (joined) per step
    # chunked prefill: when chunk_size is set, prompts advance chunk_size
    # tokens per scheduler step (one fixed compiled shape; final partial
    # chunks padded+masked) and prefill_tokens is the per-step *token*
    # budget across requests (None → one chunk per step). prefill_budget
    # is ignored in chunked mode; None chunk_size keeps the legacy
    # whole-prompt path.
    chunk_size: Optional[int] = None
    prefill_tokens: Optional[int] = None
    kv_offload: bool = False      # pages live in the pool between steps
    cache_dtype: Any = jnp.float32
    hw: HardwareSpec = TPU_V5E    # cost model driving the prefetch plan
    # planner knobs for the prefetch plan; None → the paged default
    # (PAGED_INSERTION). A session-built scheduler gets these from its
    # OffloadConfig instead of the old call-site hard-coding.
    insert_opts: Optional[InsertionOptions] = None
    refine: bool = True
    # SLO-aware scheduling (repro.slo): None (or enable=False) keeps pure
    # FIFO + capacity admission; enabled, ready requests are admitted
    # best-first (priority class, then earliest TTFT deadline), certainly-
    # infeasible requests are shed, and deadline-pressed arrivals may
    # preempt (park) a running lower-priority sequence.
    slo: Optional[SLOConfig] = None


@dataclasses.dataclass
class SchedStats:
    steps: int = 0
    joins: int = 0
    retires: int = 0
    prefill_tokens: int = 0
    prefill_chunks: int = 0       # jit_prefill_chunk calls (chunked mode)
    decoded_tokens: int = 0
    pages_parked: int = 0
    cold_spills: int = 0          # our pages spilled down-tier by the manager
    prefix_hits: int = 0          # admissions that matched the prefix cache
    prefix_hit_tokens: int = 0    # prompt tokens served from cached prefixes
    preemptions: int = 0          # running sequences parked for a deadline
    resumes: int = 0              # preempted sequences restored to a slot
    shed: int = 0                 # requests dropped as deadline-infeasible
    # ring slots the decode read fetches (each row's live kv blocks) and
    # the slots of every row's whole ring, summed over self-attention
    # layers and decode steps (``kernels/decode_attention.py``)
    decode_kv_slots_read: int = 0
    decode_kv_slots_ring: int = 0


class ContinuousScheduler:
    def __init__(self, model: Model, params: Any,
                 cfg: SchedulerConfig = SchedulerConfig(), *,
                 pool: Optional[MemoryPoolManager] = None,
                 plan_cache: Optional[Dict[Any, Any]] = None,
                 prefix_cache: Optional[PrefixCacheManager] = None,
                 tracer=None,
                 metrics: Optional[MetricsRegistry] = None) -> None:
        self.model = model
        self.params = params
        self.cfg = cfg
        self._ns = f"sched{next(_SCHED_IDS)}"
        self.stats = SchedStats()
        self.finished: Dict[int, RequestState] = {}
        self._tracer = tracer if tracer is not None else NULL_TRACER
        # per-request latency histograms (wall-clock seconds), shared
        # across a session's schedulers via the one registry
        self._metrics = metrics
        if metrics is not None:
            self._h_ttft = metrics.histogram(
                "req_ttft_seconds", SECONDS_BUCKETS,
                "request submit to first token, seconds")
            self._h_queue_wait = metrics.histogram(
                "req_queue_wait_seconds", SECONDS_BUCKETS,
                "request submit to its first slot, seconds")
            self._h_tpot = metrics.histogram(
                "req_tpot_seconds", SECONDS_BUCKETS,
                "mean time per output token after the first, seconds")

        if cfg.chunk_size is not None:
            if not 1 <= cfg.chunk_size <= cfg.max_seq:
                raise ValueError(
                    f"chunk_size {cfg.chunk_size} must be in [1, max_seq="
                    f"{cfg.max_seq}]")
            if not model.supports_chunked_prefill():
                raise ValueError(
                    f"model {model.cfg.name!r} has recurrent or cross-"
                    "attention layers; chunked prefill supports attention/"
                    "MLA self-attention models only (leave chunk_size "
                    "unset for whole-prompt prefill)")
            self._chunk_prefill = jit_prefill_chunk(model)
        if cfg.prefill_tokens is not None:
            if cfg.chunk_size is None:
                raise ValueError("prefill_tokens (a per-step token budget) "
                                 "requires chunk_size")
            if cfg.prefill_tokens < 1:
                raise ValueError("prefill_tokens must be >= 1")
        self._prefill = jit_prefill(model)
        self._decode = jit_decode(model)
        self.cache = model.init_cache(cfg.max_batch, cfg.max_seq,
                                      cfg.cache_dtype)
        # (layers, ring slots, kv block) of each self-attention K stack
        # (L, B, C, Hkv·D): what the decode read's grid walks
        self._kv_rings = [
            (k.shape[0], k.shape[2],
             block_k(k.shape[2], k.shape[3], k.dtype.itemsize))
            for seg in self.cache["segments"] for st in seg.values()
            for name, k in st.items() if name == "k"]
        self.slots: List[Optional[RequestState]] = [None] * cfg.max_batch
        # flat layer index -> (segment, repeat, pattern position); matches
        # cfg.layer_specs() and the decode-graph layer numbering
        self._flat: List[Tuple[int, int, int]] = [
            (si, ri, pi)
            for si, seg in enumerate(model.cfg.segments)
            for ri in range(seg.repeats)
            for pi in range(len(seg.pattern))
        ]
        self._owns_pool = pool is None
        # one full step's page fetches (every leaf of every slot) must
        # issue before anything waits — the auto depth policy's `pages`
        pages = cfg.max_batch * sum(
            len(jax.tree.leaves(self.cache["segments"][si][f"p{pi}"]))
            for si, _, pi in self._flat)
        if pool is None:
            if cfg.kv_offload:
                raise ValueError(
                    "ContinuousScheduler(kv_offload=True) requires a pool; "
                    "construct schedulers through repro.api."
                    "HyperOffloadSession.scheduler (mode='kv_offload')")
            pool = default_pool(transfer_depth=auto_depth(pages=pages))
        elif cfg.kv_offload:
            # shared (session) pool: grow the engine to cover this consumer
            pool.transfer.ensure_depth(auto_depth(pages=pages))
        self.pool = pool
        self._plan_cache = plan_cache
        self.queue = ArrivalQueue()
        # worst-case reservation is in decoded bytes; itemsize lets the
        # ledger count codec-wrapped tiers at decoded-equivalent capacity
        self.admission = AdmissionController(
            self.pool, itemsize=jnp.dtype(cfg.cache_dtype).itemsize)
        self._row_bytes = worst_case_page_bytes(
            model.cache_specs(1, cfg.max_seq, cfg.cache_dtype))
        # SLO-aware scheduling (repro.slo): policy objects + the parked
        # (preempted) states, which are in neither the queue nor a slot
        # but still hold their capacity reservation
        self.slo: Optional[SLOConfig] = \
            cfg.slo if (cfg.slo is not None and cfg.slo.enable) else None
        self.preempted: List[RequestState] = []
        self.goodput: Optional[GoodputController] = None
        self.preemptor: Optional[PreemptionEngine] = None
        if self.slo is not None:
            self.goodput = GoodputController(self.slo, metrics=metrics)
            self.preemptor = PreemptionEngine(self.slo)
        self.prefetcher: Optional[PlanPrefetcher] = None
        self._inflight: Optional[InFlightFetches] = None
        self._fetch_map: Dict[str, Tuple[int, int, int, int, int]] = {}
        if cfg.kv_offload:
            self.prefetcher = PlanPrefetcher(
                model.cfg, cfg.max_batch, cfg.max_seq, pool=self.pool,
                hw=cfg.hw, refine=cfg.refine, insert_opts=cfg.insert_opts,
                plan_cache=plan_cache, tracer=self._tracer)
            self.pool.add_evict_listener(self._on_evict)
        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            if cfg.chunk_size is None:
                raise ValueError(
                    "prefix_cache requires chunked prefill (chunk_size): a "
                    "hit resumes prefill at the match offset, which only "
                    "the chunked path supports")
            if cfg.kv_offload and prefix_cache.pool is not self.pool:
                raise ValueError(
                    "prefix_cache must share the scheduler's pool in "
                    "kv_offload mode (prefix-page fetches ride the same "
                    "PlanPrefetcher plan)")
            # prefix reuse slices/restores KV by absolute position, which
            # is only exact while no cache leaf's ring buffer has wrapped:
            # requests longer than the shortest leaf (a sliding-window
            # layer's window) bypass the cache entirely
            self._prefix_seq_limit = min(
                int(leaf.shape[2]) for leaf in jax.tree.leaves(self.cache))
        self.now = 0.0
        self._closed = False

    # ------------------------------------------------------------------
    def submit(self, request: Request) -> RequestState:
        if request.total_len > self.cfg.max_seq:
            raise ValueError(
                f"request {request.req_id}: prompt+decode "
                f"{request.total_len} exceeds max_seq {self.cfg.max_seq}")
        state = self.queue.push(request)
        state.wall_submit = state.wall_phase = self._tracer.now()
        return state

    @property
    def active(self) -> List[RequestState]:
        return [s for s in self.slots if s is not None]

    def close(self) -> None:
        """Idempotent shutdown: drop remaining pages, unhook from a shared
        pool, close an owned pool."""
        if self._closed:
            return
        self._closed = True
        if self.cfg.kv_offload:
            self.pool.remove_evict_listener(self._on_evict)
        for st in (list(self.slots) + list(self.preempted)
                   + list(self.finished.values())):
            if st is not None and st.pages is not None:
                st.pages.drop()
            if st is not None:
                if st.prefix_hit is not None and self.prefix_cache is not None:
                    self.prefix_cache.release(st.prefix_hit)
                self.admission.release(st)
        if self._owns_pool:
            self.pool.close()

    def pool_stats(self) -> Dict[str, Any]:
        return self.pool.snapshot()

    def prefetch_stats(self) -> Optional[Dict[str, float]]:
        return None if self.prefetcher is None else \
            self.prefetcher.stats.snapshot()

    def prefix_stats(self) -> Optional[Dict[str, float]]:
        return None if self.prefix_cache is None else \
            self.prefix_cache.snapshot()

    def _end_phase(self, state: RequestState, phase: str,
                   **args: Any) -> float:
        """End the request's current wall-clock phase with a
        ``request.<phase>`` span (``queue``, ``prefill`` or ``decode``)
        and start the next at the same instant, which is returned."""
        t = self._tracer.now()
        if self._tracer.enabled:
            self._tracer.complete("request", f"request.{phase}",
                                  state.wall_phase, t - state.wall_phase,
                                  dict(args, req=state.req_id))
        state.wall_phase = t
        return t

    # -- step phases ---------------------------------------------------
    def _on_evict(self, entry: PoolEntry, dst: str) -> None:
        if entry.key.startswith(self._ns + "/"):
            self.stats.cold_spills += 1

    def _subtree(self, si: int, pi: int):
        return self.cache["segments"][si][f"p{pi}"]

    def _collect_inflight(self) -> None:
        """Wait (in the plan's consumption order) on the fetches issued at
        the end of the previous step and scatter the pages back into the
        stacked cache."""
        fetched = self._inflight.wait_all()
        self._inflight = None
        updates: Dict[Tuple[int, int], List[Tuple[int, int, int, jax.Array]]] = {}
        for key, arr in fetched.items():
            dest = self._fetch_map.get(key)
            if dest is None:
                # the owner was preempted after these fetches were issued:
                # its slot may already hold another request, so the value
                # is dropped (the page itself stays pool-resident from the
                # last park — restore re-fetches it)
                continue
            si, pi, j, ri, slot = dest
            updates.setdefault((si, pi), []).append((j, ri, slot, arr))
        self._fetch_map = {}
        for (si, pi), ups in updates.items():
            leaves, treedef = jax.tree.flatten(self._subtree(si, pi))
            for j, ri, slot, arr in ups:
                leaves[j] = leaves[j].at[ri, slot].set(arr)
            self.cache["segments"][si][f"p{pi}"] = jax.tree.unflatten(
                treedef, leaves)

    def _reserve_capacity(self, state: RequestState) -> bool:
        """Worst-case capacity reservation shared by every admission path
        (the request's page-key prefix ``covers`` its future parked pages
        — "-" guards req3 vs req30). False = capacity pressure."""
        covers = f"{self._ns}/req{state.req_id}-"
        if self.admission.try_admit(state, self._row_bytes, covers):
            return True
        if (not self.active and not self.preempted
                and not self.admission.can_ever_admit(self._row_bytes)):
            raise RuntimeError(
                f"request {state.req_id} can never be admitted: "
                f"worst-case pages ({self._row_bytes} B) exceed the "
                "pool's device+host capacity")
        return False   # retirements will free it

    def _try_admit_head(self) -> Optional[Tuple[RequestState, int]]:
        """Admission guard shared by both prefill paths: pop the arrival
        queue's best candidate into a free slot (SLO mode: possibly freed
        by preemption) if the pool can hold its worst-case pages. Returns
        (state, slot) or None (no slot / not arrived / capacity
        pressure)."""
        if self.slo is not None:
            return self._try_admit_slo()
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return None
        state = self.queue.head_ready(self.now)
        if state is None:
            return None
        if not self._reserve_capacity(state):
            return None
        self.queue.pop()
        return state, free[0]

    def _try_admit_slo(self) -> Optional[Tuple[RequestState, int]]:
        """SLO admission: the best ready candidate (priority class, then
        earliest TTFT deadline — ``slo.candidate_key``) takes a free slot,
        or — when none is free and its deadline can't survive waiting for
        a natural retirement — a slot freed by preempting a running
        lower-priority sequence. Capacity is reserved *before* the
        preemption is performed, so a reservation failure never parks a
        victim for nothing."""
        ready = self.queue.ready(self.now)
        if not ready:
            return None
        state = min(ready, key=candidate_key)
        free = [i for i, s in enumerate(self.slots) if s is None]
        if free:
            if not self._reserve_capacity(state):
                return None
            self.queue.remove(state)
            return state, free[0]
        running = self.active
        if self.cfg.kv_offload:
            # a sequence that reached DECODE *this step* (prefill just
            # finished) has its freshest row only in the stacked cache —
            # its pool pages aren't parked until this step's epilogue —
            # so it is not preemptible yet
            running = [s for s in running
                       if not (s.status == DECODE
                               and s.last_step == self.stats.steps)]
        victim = self.preemptor.pick_victim(
            state, running, self.now,
            est_prefill_steps=self._est_prefill_steps(state),
            remaining_steps=self._remaining_steps)
        if victim is None:
            return None
        if not self._reserve_capacity(state):
            return None
        slot = victim.slot
        self._preempt(victim)
        self.queue.remove(state)
        return state, slot

    # -- SLO mechanics -------------------------------------------------
    def _est_prefill_steps(self, state: RequestState) -> float:
        """Optimistic steps from admission to first token for a queued
        candidate: its remaining prompt plus the prompt backlog already
        mid-prefill, at the measured per-step prefill rate. Whole-prompt
        mode prefills in the admission step itself."""
        if self.cfg.chunk_size is None:
            return 1.0
        base = self.cfg.prefill_tokens or self.cfg.chunk_size
        rate = self.goodput.rate(base)
        backlog = sum(max(s.request.prompt_len - s.prefill_pos, 0)
                      for s in self.slots
                      if s is not None and s.status == PREFILL)
        remaining = max(state.request.prompt_len - state.prefill_pos, 0)
        return max(1.0, np.ceil((backlog + remaining) / rate))

    def _remaining_steps(self, s: RequestState) -> int:
        """Steps until a running state retires and frees its slot (decode
        budget plus, mid-prefill, its outstanding chunks)."""
        n = s.request.max_new_tokens - len(s.out)
        if s.status == PREFILL and self.cfg.chunk_size is not None:
            base = self.cfg.prefill_tokens or self.cfg.chunk_size
            rem = max(s.request.prompt_len - s.prefill_pos, 0)
            n += -(-rem // base)
        return n

    def _slo_shed_sweep(self) -> None:
        """Drop every ready request whose TTFT deadline is certainly
        unmeetable — *before* admission, so no prefill is spent on
        admitted-then-missed work."""
        for state in self.queue.ready(self.now):
            if self.goodput.infeasible(
                    state, self.now, self._est_prefill_steps(state)):
                self._shed(state)

    def _shed(self, state: RequestState) -> None:
        """Terminal drop from the queue: never admitted, so there is no
        slot, reservation, or page to release."""
        self.queue.remove(state)
        self._end_phase(state, "queue")
        state.status = SHED
        state.t_done = self.now
        self.finished[state.req_id] = state
        self.stats.shed += 1
        self.goodput.note_retired(state)
        if self._tracer.enabled:
            self._tracer.instant("request", "SHED",
                                 {"req": state.req_id,
                                  "arrival": state.request.arrival})

    def _preempt(self, victim: RequestState) -> None:
        """Park a running sequence and free its slot. A DECODE victim's
        rows are either already pool-resident from the last ``_park_and_
        issue`` (kv_offload — just demote their priority and orphan any
        in-flight fetches targeting the reassigned slot) or sliced out of
        the stacked cache onto ``chunk_cache`` (resident). A mid-PREFILL
        victim's partial row is already on ``chunk_cache``/in the pool
        (``_park_chunk_row`` ran when the chunk budget moved on). The
        capacity reservation is kept — the pages still occupy pool space,
        so admission stays exactly as conservative as before."""
        slot = victim.slot
        self._end_phase(victim,
                        "decode" if victim.status == DECODE else "prefill")
        if victim.status == DECODE and not self.cfg.kv_offload:
            victim.chunk_cache = jax.tree.map(
                lambda big: big[:, slot:slot + 1], self.cache)
        if self.cfg.kv_offload and victim.pages is not None:
            for key in victim.pages.keys.values():
                self._fetch_map.pop(key, None)
                self.pool.set_priority(key, _PREEMPTED_PAGE_PRIO)
        victim.status = PREEMPTED
        victim.preemptions += 1
        victim.slot = None
        self.slots[slot] = None
        self.preempted.append(victim)
        self.stats.preemptions += 1
        if self._tracer.enabled:
            self._tracer.instant("request", "PREEMPTED",
                                 {"req": victim.req_id, "slot": slot})

    def _resume_preempted(self, *, final: bool) -> None:
        """Restore preempted sequences into free slots, best first. In the
        pre-pass (``final=False``) a preempted sequence only takes a slot
        if it outranks every ready queued candidate — otherwise admission
        gets first claim on the slot this step; the post-pass
        (``final=True``) hands any slots admission left free back to
        preempted work (its capacity is already reserved)."""
        while self.preempted:
            free = [i for i, s in enumerate(self.slots) if s is None]
            if not free:
                return
            best = min(self.preempted, key=candidate_key)
            if not final:
                ready = self.queue.ready(self.now)
                if ready and min(candidate_key(s) for s in ready) \
                        < candidate_key(best):
                    return
            # by identity: dataclass equality would compare token arrays
            self.preempted = [s for s in self.preempted if s is not best]
            self._resume(best, free[0])

    def _resume(self, state: RequestState, slot: int) -> None:
        """Inverse of ``_preempt``: a DECODE sequence's row rides the same
        restore path parked mid-prefill chunks use (chunk_cache or plan-
        driven pool fetches) and is scattered back into the slot; a mid-
        PREFILL sequence just re-enters the chunked loop, which restores
        its row on its next advance."""
        was_decode = state.t_first_token is not None
        self._end_phase(state, "queue")
        self.slots[slot] = state
        state.slot = slot
        state.status = DECODE if was_decode else PREFILL
        self.stats.resumes += 1
        if was_decode:
            row = self._restore_chunk_row(state)
            self.cache = jax.tree.map(
                lambda big, r: big.at[:, slot].set(r[:, 0]),
                self.cache, row)
        if self._tracer.enabled:
            self._tracer.instant("request", "RESUMED",
                                 {"req": state.req_id, "slot": slot})

    def slo_snapshot(self) -> Optional[Dict[str, int]]:
        return None if self.goodput is None else self.goodput.snapshot()

    def _admit_and_prefill(self) -> List[Tuple[int, int]]:
        if self.slo is not None:
            # SLO pre-pass: reset the preemption quota, shed certainly-
            # infeasible arrivals before any admission work, and restore
            # preempted sequences that outrank everything still queued
            pt0 = self.stats.prefill_tokens
            self.preemptor.begin_step()
            self._slo_shed_sweep()
            self._resume_preempted(final=False)
        if self.cfg.chunk_size is not None:
            emitted = self._admit_and_prefill_chunked()
        else:
            emitted = []
            for _ in range(self.cfg.prefill_budget):
                admitted = self._try_admit_head()
                if admitted is None:
                    break
                emitted.append(self._join(*admitted))
        if self.slo is not None:
            # slots admission left free (no ready candidates / capacity)
            # go back to preempted sequences, and the step's landed
            # prefill tokens feed the measured-rate estimate
            self._resume_preempted(final=True)
            self.goodput.note_step(self.stats.prefill_tokens - pt0)
        return emitted

    def _admit_and_prefill_chunked(self) -> List[Tuple[int, int]]:
        """Chunked admission/prefill: spend up to ``prefill_tokens`` chunk
        tokens this step — first advancing requests already mid-PREFILL
        (oldest join first, so prompts finish in admission order), then
        admitting new ones while budget remains. Each ``jit_prefill_chunk``
        call charges a full ``chunk_size`` against the budget (a padded
        final chunk costs the same compute as a full one); the first chunk
        of a step always runs even if the budget is smaller than one chunk,
        so the loop can't stall."""
        emitted: List[Tuple[int, int]] = []
        budget = self.cfg.prefill_tokens or self.cfg.chunk_size
        mid = [s for s in self.slots
               if s is not None and s.status == PREFILL]
        if self.goodput is not None:
            # deadline pressure on mid-prefill requests may raise the
            # step's token budget (capped at max_prefill_boost)
            budget = self.goodput.boost_budget(budget, mid, self.now)
        spent = 0
        for s in sorted(mid, key=lambda s: (s.joined_step, s.req_id)):
            out, spent = self._advance_chunks(s, spent, budget)
            emitted += out
        # SLO mode: mid-prefill work exhausting the budget must not hide
        # the admission (and preemption) check from a deadline-pressed
        # arrival — it still gets one seat attempt; its own chunks then
        # start next step
        tries = 0
        while spent < budget or (self.slo is not None and tries == 0):
            tries += 1
            admitted = self._try_admit_head()
            if admitted is None:
                break
            state, slot = admitted
            self._join_chunked(state, slot)
            out, spent = self._advance_chunks(state, spent, budget)
            emitted += out
        return emitted

    def _advance_chunks(self, state: RequestState, spent: int,
                        budget: int) -> Tuple[List[Tuple[int, int]], int]:
        """Advance one request as far as the step's token budget allows,
        holding its row cache resident across consecutive chunks — the row
        parks (once) only when the budget moves on with the prompt still
        unfinished, not once per chunk."""
        emitted: List[Tuple[int, int]] = []
        row = None
        while state.status == PREFILL and spent < budget:
            if row is None:
                row = self._restore_chunk_row(state)
            out, row = self._prefill_chunk_step(state, row)
            emitted += out
            spent += self.cfg.chunk_size
        if row is not None:
            self._park_chunk_row(state, row)
        return emitted, spent

    def _join_chunked(self, state: RequestState, slot: int) -> None:
        """Take the slot and the capacity reservation; prefill advances in
        ``_prefill_chunk_step`` calls from here on. With a prefix cache, a
        hit pre-loads the shared pages and moves ``prefill_pos`` past
        them — only the uncached suffix is ever prefilled."""
        self._take_slot(state, slot)
        state.prefill_pos = 0
        state.chunk_cache = self.model.init_cache(1, self.cfg.max_seq,
                                                  self.cfg.cache_dtype)
        if self.prefix_cache is not None:
            self._apply_prefix_hit(state)

    def _apply_prefix_hit(self, state: RequestState) -> None:
        """Admission-side prefix hit: match the prompt, *copy* every shared
        page into the request's own row cache (the copy is what makes the
        sharing copy-on-write — the cached entries are never written
        again), and resume prefill at the match offset. The match is capped
        at ``prompt_len - 1`` so at least one real token remains to prefill
        (the first sampled token needs its logits). Read refs on the
        matched pages are held until retirement."""
        req = state.request
        if req.total_len > self._prefix_seq_limit:
            return   # a ring-buffer leaf would wrap — positions unreliable
        hit = self.prefix_cache.lookup(req.tokens,
                                       max_tokens=req.prompt_len - 1)
        if hit is None:
            return
        state.prefix_hit = hit
        pages = hit.page_keys()
        values = self._fetch_prefix_pages(pages)
        ps = self.prefix_cache.page_size
        row = state.chunk_cache
        for i, (si, ri, pi) in enumerate(self._flat):
            leaves, treedef = jax.tree.flatten(row["segments"][si][f"p{pi}"])
            for j in range(len(leaves)):
                for p, entries in enumerate(pages):
                    arr = values[entries[f"L{i}.{j}"]]
                    leaves[j] = leaves[j].at[
                        ri, 0, p * ps:(p + 1) * ps].set(arr)
            row["segments"][si][f"p{pi}"] = jax.tree.unflatten(treedef, leaves)
        state.prefill_pos = hit.tokens
        self.stats.prefix_hits += 1
        self.stats.prefix_hit_tokens += hit.tokens

    def _fetch_prefix_pages(self, pages: List[Dict[str, str]]) -> Dict[str, Any]:
        """Materialize the matched pages' arrays. Host/remote-resident hits
        ride the ``PlanPrefetcher`` plan (kv_offload mode): every page's
        fetch issues in the refined order before any is waited on. Pages
        the plan doesn't cover — and all pages in resident mode — fall back
        to a sync pool get. Arrays are decommitted (NumPy) so the scatter
        into the row cache keeps the one-executable jit signature."""
        keys_by_layer: Dict[int, List[str]] = {}
        all_keys: List[str] = []
        for entries in pages:
            for label, key in entries.items():
                layer = int(label[1:label.index(".")])
                keys_by_layer.setdefault(layer, []).append(key)
                all_keys.append(key)
        fetched: Dict[str, Any] = {}
        if self.prefetcher is not None:
            fetched = self.prefetcher.issue(keys_by_layer).wait_all()
        pool = self.prefix_cache.pool
        return {k: np.asarray(fetched[k] if k in fetched else pool.get(k))
                for k in all_keys}

    def _prefill_chunk_step(
            self, state: RequestState,
            row: Any) -> Tuple[List[Tuple[int, int]], Optional[Any]]:
        """Advance one request by one chunk against its row cache. Returns
        (emitted, row): the advanced row while the prompt is unfinished
        (the caller keeps it resident or parks it), or None once the final
        chunk lands — then the row is scattered into the batch slot and the
        first token sampled from the last valid token's logits, exactly as
        whole-prompt ``_join`` does, so token identity is preserved."""
        req = state.request
        chunk = self.cfg.chunk_size
        start = state.prefill_pos
        end = min(start + chunk, req.prompt_len)
        valid = end - start
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = req.tokens[start:end]
        with self._tracer.span("sched", "dispatch"):
            logits, row = self._chunk_prefill(
                self.params, {"tokens": jnp.asarray(toks)},
                jnp.int32(start), jnp.int32(valid), row)
        state.prefill_pos = end
        state.last_step = self.stats.steps
        self.stats.prefill_tokens += valid
        self.stats.prefill_chunks += 1
        if end < req.prompt_len:
            return [], row
        # last chunk landed — shared completion with the whole-prompt path
        state.chunk_cache = None
        return [self._finish_prefill(state, logits, row)], None

    def _park_chunk_row(self, state: RequestState, row: Any) -> None:
        """Between chunk steps the partial row cache stays on the state
        (resident) or is parked page-by-page through the pool (kv_offload)
        — same ``L{i}.{j}`` labels the decode loop parks under, so once
        decoding starts the entries are replaced in place. Priority =
        remaining work (all decode steps plus unprefilled prompt tokens):
        mid-prefill rows are the hottest pages in the pool."""
        if not self.cfg.kv_offload:
            state.chunk_cache = row
            return
        prio = float(state.request.max_new_tokens
                     + state.request.prompt_len - state.prefill_pos)
        with self._tracer.span("sched", "park_row", req=state.req_id):
            for i, (si, ri, pi) in enumerate(self._flat):
                leaves = jax.tree.leaves(row["segments"][si][f"p{pi}"])
                for j, leaf in enumerate(leaves):
                    state.pages.park(f"L{i}.{j}", leaf[ri, 0],
                                     self.pool.top_tier, priority=prio)
                    self.stats.pages_parked += 1
        state.chunk_cache = None

    def _restore_chunk_row(self, state: RequestState) -> Any:
        """Inverse of ``_park_chunk_row``: the resident row is handed back
        directly (and detached — jit donates it); a parked row rides the
        ``PlanPrefetcher`` plan — every page's fetch issues in the refined
        order before any is waited on, the same async path decode pages
        take, instead of the old page-by-page sync round trip."""
        if state.chunk_cache is not None:
            row, state.chunk_cache = state.chunk_cache, None
            return row
        with self._tracer.span("sched", "restore_row", req=state.req_id):
            return self._restore_parked_row(state)

    def _restore_parked_row(self, state: RequestState) -> Any:
        row = self.model.init_cache(1, self.cfg.max_seq, self.cfg.cache_dtype)
        keys_by_layer: Dict[int, List[str]] = {}
        for i, (si, ri, pi) in enumerate(self._flat):
            n = len(jax.tree.leaves(row["segments"][si][f"p{pi}"]))
            keys_by_layer.setdefault(i, []).extend(
                state.pages.key_of(f"L{i}.{j}") for j in range(n))
        fetched: Dict[str, Any] = {}
        if self.prefetcher is not None:
            fetched = self.prefetcher.issue(keys_by_layer).wait_all()
        for i, (si, ri, pi) in enumerate(self._flat):
            leaves, treedef = jax.tree.flatten(row["segments"][si][f"p{pi}"])
            for j in range(len(leaves)):
                # layers outside the plan fall back to a sync fetch; either
                # way pages come back committed to their tier's device, so
                # strip the commitment (NumPy) so restored rows share the
                # (uncommitted) jit signature of fresh/resident rows — one
                # compiled chunk executable per chunk shape, not one per
                # residency path
                val = fetched.get(state.pages.key_of(f"L{i}.{j}"))
                if val is None:
                    val = state.pages.fetch(f"L{i}.{j}")
                leaves[j] = leaves[j].at[ri, 0].set(np.asarray(val))
            row["segments"][si][f"p{pi}"] = jax.tree.unflatten(treedef, leaves)
        return row

    def _take_slot(self, state: RequestState, slot: int) -> None:
        """Join bookkeeping shared by both prefill paths: occupy the batch
        slot and (kv_offload) create the request's page table."""
        state.status = PREFILL
        state.slot = slot
        self.slots[slot] = state
        state.joined_step = self.stats.steps
        state.wall_joined = self._end_phase(
            state, "queue", prompt_len=state.request.prompt_len)
        if self.cfg.kv_offload:   # resident mode never parks a page
            state.pages = KVPageTable(
                self.pool, f"{self._ns}/req{state.req_id}")
        self.stats.joins += 1

    def _finish_prefill(self, state: RequestState, logits: jax.Array,
                        row: Any) -> Tuple[int, int]:
        """Prompt fully prefilled (whole prompt, or the final chunk):
        scatter the batch-1 row into the slot and sample the first token
        from the last prompt token's logits, exactly as
        ``ServeEngine.generate`` does — ONE shared implementation, so the
        whole-prompt and chunked paths cannot drift apart on the token-
        identity-critical sampling and state transition."""
        req = state.request
        tr = self._tracer
        with tr.span("sched", "dispatch"):
            self.cache = jax.tree.map(
                lambda big, r: big.at[:, state.slot].set(r[:, 0]),
                self.cache, row)
        key = state.sample_key() if req.temperature > 0.0 else None
        with tr.span("sched", "device_wait"):
            tok = int(sample_token(logits[:, 0], key,
                                   temperature=req.temperature,
                                   top_k=req.top_k)[0])
        state.wall_first_token = self._end_phase(
            state, "prefill", prompt_len=req.prompt_len)
        state.out.append(tok)
        state.last_tok = tok
        state.pos = req.prompt_len    # next decode writes here
        state.t_first_token = self.now
        state.status = DECODE
        state.last_step = self.stats.steps
        if state.done:                # max_new_tokens == 1
            self._retire(state)
        return (req.req_id, tok)

    def _join(self, state: RequestState, slot: int) -> Tuple[int, int]:
        req = state.request
        self._take_slot(state, slot)
        row = self.model.init_cache(1, self.cfg.max_seq, self.cfg.cache_dtype)
        with self._tracer.span("sched", "dispatch"):
            logits, row = self._prefill(
                self.params, {"tokens": jnp.asarray(req.tokens[None, :])},
                row)
        self.stats.prefill_tokens += req.prompt_len
        return self._finish_prefill(state, logits, row)

    def _decode_active(self) -> List[Tuple[int, int]]:
        live = [s for s in self.slots if s is not None and s.status == DECODE]
        if not live:
            return []
        b = self.cfg.max_batch
        tok = np.zeros((b, 1), np.int32)
        pos = np.full((b,), -1, np.int32)     # -1: an empty slot
        for s in live:
            tok[s.slot, 0] = s.last_tok
            pos[s.slot] = s.pos
        for layers, c, bk in self._kv_rings:
            self.stats.decode_kv_slots_read += layers * slots_read(
                ring_lengths(pos, c), bk)
            self.stats.decode_kv_slots_ring += layers * b * c
        tr = self._tracer
        with tr.span("sched", "dispatch"):
            logits, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(tok), jnp.asarray(pos))
        emitted: List[Tuple[int, int]] = []
        greedy = None   # one batched argmax serves every temperature-0 row
        for s in live:
            req = s.request
            if req.temperature <= 0.0:
                if greedy is None:
                    with tr.span("sched", "device_wait"):
                        greedy = np.asarray(
                            jnp.argmax(logits[:, 0], axis=-1))
                t = int(greedy[s.slot])
            else:
                with tr.span("sched", "device_wait"):
                    t = int(sample_token(logits[s.slot:s.slot + 1, 0],
                                         s.sample_key(),
                                         temperature=req.temperature,
                                         top_k=req.top_k)[0])
            s.out.append(t)
            s.last_tok = t
            s.pos += 1
            s.last_step = self.stats.steps
            self.stats.decoded_tokens += 1
            emitted.append((req.req_id, t))
            if s.done:
                self._retire(s)
        return emitted

    def _retire(self, state: RequestState) -> None:
        state.status = DONE
        state.t_done = self.now
        state.wall_done = self._end_phase(state, "decode",
                                          tokens=len(state.out))
        if self._metrics is not None:
            self._h_ttft.observe(state.wall_first_token - state.wall_submit)
            self._h_queue_wait.observe(state.wall_joined - state.wall_submit)
            self._h_tpot.observe((state.wall_done - state.wall_first_token)
                                 / max(len(state.out) - 1, 1))
        if self.prefix_cache is not None:
            self._donate_prefix(state)
            if state.prefix_hit is not None:
                self.prefix_cache.release(state.prefix_hit)
        if state.pages is not None:
            state.pages.drop()
        self.admission.release(state)
        self.slots[state.slot] = None
        state.slot = None
        self.finished[state.req_id] = state
        self.stats.retires += 1
        if self.goodput is not None:
            self.goodput.note_retired(state)

    def _donate_prefix(self, state: RequestState) -> None:
        """Retirement-side donation: the retired prompt's full prefix pages
        enter the cache instead of being freed. The stacked decode cache
        still holds this slot's rows (retire runs right after the decode or
        final-chunk scatter), so pages are sliced straight out of it —
        decode only ever writes at positions >= prompt_len, so prompt-range
        slices are exactly the prefill-time KV. ``extract`` is lazy: the
        manager calls it only for pages not already cached."""
        req = state.request
        if req.total_len > self._prefix_seq_limit:
            return
        n_pages = req.prompt_len // self.prefix_cache.page_size
        if n_pages < 1:
            return
        slot, ps = state.slot, self.prefix_cache.page_size

        def extract(p: int) -> Dict[str, jax.Array]:
            a, b = p * ps, (p + 1) * ps
            page: Dict[str, jax.Array] = {}
            for i, (si, ri, pi) in enumerate(self._flat):
                leaves = jax.tree.leaves(self._subtree(si, pi))
                for j, leaf in enumerate(leaves):
                    page[f"L{i}.{j}"] = leaf[ri, slot, a:b]
            return page

        self.prefix_cache.donate(req.tokens, n_pages, extract)

    def _park_and_issue(self) -> None:
        """kv_offload epilogue: park every running request's pages (stable
        keys), then issue the next step's fetches along the plan.

        Page priority = the request's remaining decode budget: every
        device-resident page saves one host fetch per remaining step, so
        the manager's priority+LRU eviction spills the *coldest* sequences
        — those with the least future work, closest to retirement — first
        under device-tier pressure."""
        live = [s for s in self.slots if s is not None and s.status == DECODE]
        keys_by_layer: Dict[int, List[str]] = {}
        self._fetch_map = {}
        for s in live:
            prio = float(s.request.max_new_tokens - len(s.out))
            for i, (si, ri, pi) in enumerate(self._flat):
                leaves = jax.tree.leaves(self._subtree(si, pi))
                for j, leaf in enumerate(leaves):
                    key = s.pages.park(f"L{i}.{j}", leaf[ri, s.slot],
                                       self.pool.top_tier, priority=prio)
                    keys_by_layer.setdefault(i, []).append(key)
                    self._fetch_map[key] = (si, pi, j, ri, s.slot)
                    self.stats.pages_parked += 1
        if keys_by_layer:
            self._inflight = self.prefetcher.issue(keys_by_layer)

    # ------------------------------------------------------------------
    def replan(self, hw) -> None:
        """Swap in a prefetch plan computed under ``hw`` — the session's
        calibration loop calls this after measuring real per-tier transfer
        rates, so the refined issue order and plan leads reflect measured
        bandwidth rather than the static spec the scheduler was built
        with. No-op in resident mode (nothing is planned). Safe at a step
        boundary: parked pages keep their keys; only the *order* future
        fetches issue in (and the plan cached under the new spec's name)
        changes. Counters carry over so per-step rates stay meaningful."""
        self.cfg = dataclasses.replace(self.cfg, hw=hw)
        if self.prefetcher is None:
            return
        old_stats = self.prefetcher.stats
        self.prefetcher = PlanPrefetcher(
            self.model.cfg, self.cfg.max_batch, self.cfg.max_seq,
            pool=self.pool, hw=hw, refine=self.cfg.refine,
            insert_opts=self.cfg.insert_opts, plan_cache=self._plan_cache,
            tracer=self._tracer)
        self.prefetcher.stats.steps = old_stats.steps
        self.prefetcher.stats.fetches_issued = old_stats.fetches_issued

    def step(self) -> List[Tuple[int, int]]:
        """One scheduler step. Returns the (req_id, token) pairs emitted.

        Admission + prefill run *before* the in-flight fetches are waited
        on: that host/prefill work sits between the previous step's issue
        and this step's wait, so the transfers it overlaps are real. A
        newly admitted slot was free when the fetches were issued, so the
        joiner's freshly scattered rows are never clobbered by collect."""
        tr = self._tracer
        with tr.step_span("sched", "step", self.stats.steps):
            with tr.span("sched", "admit_prefill"):
                emitted = self._admit_and_prefill()
            if self._inflight is not None:
                # waits on the previous step's plan-driven fetches happen
                # here — the overlap analyzer charges their exposure to
                # this step's span
                with tr.span("sched", "collect"):
                    self._collect_inflight()
            with tr.span("sched", "decode"):
                emitted += self._decode_active()
            if self.cfg.kv_offload:
                with tr.span("sched", "park_issue"):
                    self._park_and_issue()
        self.stats.steps += 1
        self.now += 1.0
        return emitted

    def default_max_steps(self) -> int:
        """No-progress bound over everything queued + running: per request
        its decode budget, plus every prefill chunk still outstanding
        (chunked mode can spend whole steps advancing one prompt
        ``chunk_size`` tokens at a time). Shared by ``run`` and external
        drivers (the serving benchmark) so the formula cannot drift."""
        def _steps_for(s: RequestState) -> int:
            n = s.request.max_new_tokens + 1
            if self.cfg.chunk_size is not None:
                rem = max(s.request.prompt_len - s.prefill_pos, 0)
                n += -(-rem // self.cfg.chunk_size)   # ceil
            return n
        return 16 + 2 * sum(
            _steps_for(s) for s in (list(self.queue.pending()) + self.active
                                    + list(self.preempted)))

    def run(self, requests: Sequence[Request] = (), *,
            max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Drive the loop until every submitted request completes. Returns
        req_id -> generated token ids."""
        for r in requests:
            self.submit(r)
        if max_steps is None:
            max_steps = self.default_max_steps()
        steps = 0
        while len(self.queue) or self.active or self.preempted:
            if (not self.active and not self.preempted
                    and self.queue.head_ready(self.now) is None):
                self.now = max(self.now, self.queue.next_arrival())  # idle skip
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("scheduler made no progress "
                                   f"({steps} steps, {len(self.queue)} queued)")
        return {rid: st.tokens_array() for rid, st in self.finished.items()}
