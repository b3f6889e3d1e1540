"""Request lifecycle for the continuous-batching scheduler.

A ``Request`` is what a client submits: prompt tokens, a decode budget,
sampling parameters, and (optionally) an ``SLOSpec`` — priority class and
TTFT/TPOT deadlines the SLO-aware scheduler acts on. ``RequestState`` is
the scheduler's view of it moving through QUEUED → PREFILL → DECODE →
DONE:

- QUEUED   — waiting in the arrival queue (not yet admitted: no slot, no
             capacity reservation);
- PREFILL  — admitted: prompt being prefilled into its batch slot. With
             chunked prefill (``SchedulerConfig.chunk_size``) this state
             persists across scheduler steps — ``prefill_pos`` tracks how
             many prompt tokens have landed, and the partial batch-1 row
             cache lives on ``chunk_cache`` between steps (resident mode)
             or parked page-by-page in the memory pool (kv_offload mode);
- DECODE   — joined the running batch; one token per scheduler step;
- DONE     — produced ``max_new_tokens``; slot freed, reservation released,
             pages dropped.

On the wall clock the spine is three phases, each a ``request.*`` span
when the scheduler traces: ``request.queue`` (submit → slot taken),
``request.prefill`` (→ first token sampled) and ``request.decode`` (→
retired); a preempted request's time off its slot is a ``request.queue``
span of its own.

Two SLO-mode-only states branch off that spine:

- PREEMPTED — was PREFILL or DECODE; its slot was handed to a deadline-
              pressed higher-priority arrival. The KV rows live on
              ``chunk_cache`` (resident) or stay parked in the pool
              (kv_offload); the capacity reservation is *kept* (the pages
              really occupy pool space), so restoring never re-admits.
              Resumes to its prior state when a slot frees — token stream
              byte-identical to an unpreempted run;
- SHED      — dropped from the queue before admission because its TTFT
              deadline was already unmeetable (goodput: no prefill spent
              on certainly-missed work). Terminal, like DONE, but with no
              output.

Each admitted request owns a ``KVPageTable`` (offload.kvcache): its slice
of the stacked decode cache, page-granular, living in the memory pool when
the scheduler runs with ``kv_offload=True``. Sampling reproduces
``ServeEngine.generate`` per request exactly: the same seed-derived key
stream, first token from the prefill logits, one split per decode step —
so at ``temperature=0`` (and for any temperature, against a batch-1
engine run with the same seed) continuous batching is token-identical to
serving each request alone.
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, List, Optional

import jax
import numpy as np

from repro.offload.kvcache import KVPageTable
from repro.slo.policy import SLOSpec

QUEUED = "QUEUED"
PREFILL = "PREFILL"
DECODE = "DECODE"
DONE = "DONE"
PREEMPTED = "PREEMPTED"
SHED = "SHED"

_REQUEST_IDS = itertools.count()


@dataclasses.dataclass
class Request:
    """One client request: prompt ids (1-D), decode budget, sampling."""

    tokens: np.ndarray                 # (S,) int32 prompt ids
    max_new_tokens: int
    arrival: float = 0.0               # scheduler-clock arrival time
    temperature: float = 0.0
    top_k: Optional[int] = None
    seed: int = 0
    slo: Optional[SLOSpec] = None      # None → standard class, no deadlines
    req_id: int = dataclasses.field(default_factory=lambda: next(_REQUEST_IDS))

    def __post_init__(self) -> None:
        self.tokens = np.asarray(self.tokens, np.int32).reshape(-1)
        if self.tokens.size == 0:
            raise ValueError("empty prompt")
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def total_len(self) -> int:
        """Worst-case sequence length (prompt + all generated tokens)."""
        return self.prompt_len + self.max_new_tokens


@dataclasses.dataclass
class RequestState:
    """Scheduler-side mutable state of one request."""

    request: Request
    status: str = QUEUED
    slot: Optional[int] = None         # batch row while admitted
    pos: int = 0                       # next cache write index for decode
    prefill_pos: int = 0               # prompt tokens prefilled so far (chunked)
    chunk_cache: Optional[Any] = None  # partial row cache between chunk steps
    last_tok: int = -1                 # token fed to the next decode step
    out: List[int] = dataclasses.field(default_factory=list)
    key: Optional[jax.Array] = None    # per-request sampling key stream
    pages: Optional[KVPageTable] = None
    prefix_hit: Optional[Any] = None   # PrefixHit while admitted (refs held)
    reserve_key: str = ""              # pool reservation handle
    preemptions: int = 0               # times parked mid-flight (SLO mode)
    last_step: int = -1                # last scheduler step that decoded us
    joined_step: int = -1
    # virtual clock (scheduler steps): what the SLO policy reads
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None
    # wall clock (``Tracer.now``, seconds): submit, first slot taken,
    # first token sampled, retired; ``wall_phase`` is when the current
    # ``request.*`` span (queue, prefill or decode) began
    wall_submit: Optional[float] = None
    wall_joined: Optional[float] = None
    wall_first_token: Optional[float] = None
    wall_done: Optional[float] = None
    wall_phase: Optional[float] = None

    @property
    def req_id(self) -> int:
        return self.request.req_id

    @property
    def done(self) -> bool:
        return len(self.out) >= self.request.max_new_tokens

    def sample_key(self) -> jax.Array:
        """Next sampling key, mirroring ``ServeEngine.generate``: the raw
        seed key samples the first (prefill) token; every decode step
        splits once and samples with the subkey."""
        if self.key is None:
            self.key = jax.random.key(self.request.seed)
            return self.key
        self.key, sub = jax.random.split(self.key)
        return sub

    def tokens_array(self) -> np.ndarray:
        return np.asarray(self.out, np.int32)
