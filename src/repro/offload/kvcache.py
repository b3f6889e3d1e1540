"""Paged KV cache backed by the runtime memory pool (§5.2).

Layout per layer: each full page is its own entry in the
``MemoryPoolManager`` (host tier — pages are non-contiguous by
construction, exactly like a paged allocator); the device keeps (a) a small
*tail* buffer accumulating the current partial page and (b) per-page key
*summaries* (mean key per page) used for sparse block selection. Pages and
tail are head-major, ``(B, Hkv, page, D)``: the layout the paged-decode
kernel tiles, so no path transposes a page after it is written. This is the
paper's DeepSeek+NSA inference setting, where only the top-k relevant KV
blocks are reloaded per decode step instead of the whole cache.

Decode attention runs in two segments — selected pool pages + device tail —
merged in a single softmax, so selecting *all* pages reproduces dense
attention against the oracle (tests/test_offload_runtime.py).

The page fetch is the Prefetch cache operator (sync via ``pool.get`` or
async via ``prefetch_pages``/``TransferEngine``, which is how the serving
engine overlaps next-layer fetches with the current layer's compute); the
page flush on tail overflow is the Store. Capacity accounting and
host-kind probing live in the pool (``pool.backend``), so the cache never
sees which host memory kind holds its pages.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ref import (
    merged_pages_attention, paged_decode_attention_ref,
)
from repro.pool import MemoryPoolManager, TransferHandle, auto_depth

#: jitted exact-math fused attend (the lowering-free serving path);
#: retraces only when the page-table *length* changes — once per flushed
#: page — never per step
_fused_attend_ref = functools.partial(
    jax.jit, static_argnames=("scale", "logit_cap"))(
        paged_decode_attention_ref)
#: the gather path's attend over already-fetched pages: the same math
_paged_attend = functools.partial(
    jax.jit, static_argnames=("scale", "logit_cap"))(merged_pages_attention)

# per-instance pool-key namespace, so caches sharing one pool (e.g. one pool
# across a model's layers) never collide on page keys
_CACHE_IDS = itertools.count()


class KVPageTable:
    """One request's KV pages in the pool — the serving scheduler's
    per-request page table (``sched.requests``).

    Each page is one (layer, leaf) row of the request's slice of the
    stacked decode cache, stored under a request-stable key: re-parking a
    page replaces the entry in place (no key churn), and the pool's
    priority+LRU manager decides *where* it lives — pages are parked hot
    (device tier, priority = recency), and under capacity pressure cold
    sequences' pages spill to the host tier, then to remote, without the
    table noticing. Capacity admission for the table happens up front via
    ``MemoryPoolManager.reserve`` (see ``sched.queue``), sized by
    ``worst_case_page_bytes`` — pages the request has not produced yet are
    charged at their full worst case.
    """

    def __init__(self, pool: MemoryPoolManager, name: str) -> None:
        self.pool = pool
        self.key_ns = f"{name}-{next(_CACHE_IDS)}"
        self.keys: dict = {}       # page label -> pool key
        self.parks: int = 0

    def __len__(self) -> int:
        return len(self.keys)

    def key_of(self, label: str) -> str:
        return self.keys.setdefault(label, f"{self.key_ns}/{label}")

    def park(self, label: str, value: jax.Array, tier: str, *,
             priority: float = 0.0) -> str:
        key = self.key_of(label)
        self.pool.put(key, value, tier, priority=priority)
        self.parks += 1
        return key

    def prefetch(self, label: str) -> TransferHandle:
        return self.pool.prefetch(self.keys[label])

    def fetch(self, label: str) -> jax.Array:
        return self.pool.get(self.keys[label])

    def tiers(self) -> dict:
        """label -> tier currently holding the page (spill visibility)."""
        return {lb: self.pool.tier_of(k) for lb, k in self.keys.items()
                if k in self.pool}

    def drop(self) -> None:
        """Retire the request: drop every page still in the pool."""
        for k in self.keys.values():
            if k in self.pool:
                self.pool.drop(k)
        self.keys.clear()


def worst_case_page_bytes(cache_specs) -> int:
    """Worst-case pool footprint of one request's pages: the full
    per-request cache row at max_seq (``Model.cache_specs(1, max_seq)``),
    summed over every leaf. Used by admission control before any page
    exists."""
    total = 0
    for leaf in jax.tree.leaves(cache_specs):
        n = 1
        for d in leaf.shape:
            n *= d
        total += n * jnp.dtype(leaf.dtype).itemsize
    return int(total)


@jax.jit
def _page_summary(k_page: jax.Array) -> jax.Array:
    """(B, Hkv, page, D) -> (B, Hkv, D) mean key."""
    return jnp.mean(k_page, axis=2)


@dataclasses.dataclass
class PrefetchedPages:
    """In-flight page fetches; ``wait()`` yields what ``fetch_pages``
    would have returned synchronously, plus the page indices."""

    idx: np.ndarray
    k_handles: List[TransferHandle]
    v_handles: List[TransferHandle]
    _shape: Tuple[int, ...]
    _dtype: jnp.dtype

    def wait(self) -> Tuple[jax.Array, jax.Array, np.ndarray]:
        if not self.k_handles:
            empty = jnp.zeros((0,) + self._shape, self._dtype)
            return empty, empty, self.idx
        ks = jnp.stack([h.wait() for h in self.k_handles])
        vs = jnp.stack([h.wait() for h in self.v_handles])
        return ks, vs, self.idx


@dataclasses.dataclass
class PagedKVCache:
    """One attention layer's paged cache. ``n_layers`` instances make a model."""

    page_size: int
    n_pages: int               # pool capacity in pages
    batch: int
    n_kv_heads: int
    head_dim: int
    dtype: jnp.dtype

    pool: MemoryPoolManager    # tiered page store (host tier by default)
    k_pool: List[Optional[str]]   # per page: pool key of the K page, or None
    v_pool: List[Optional[str]]
    k_summary: jax.Array       # (n_pages, B, Hkv, D) — device
    k_tail: jax.Array          # (B, Hkv, page, D) — device (partial page)
    v_tail: jax.Array
    length: int = 0            # tokens appended so far
    fetches: int = 0           # pool→device page transfers (stats)
    flushes: int = 0           # device→pool page stores
    key_ns: str = ""           # pool-key namespace (unique per instance)

    # -- fused-decode device page buffer (attend_fused) ----------------
    # LRU slot cache of decoded pages on device: the fused path attends
    # over it in place via a page table, so steady-state decode does ZERO
    # pool round trips (the gather path does ~2·n_pages per step)
    device_pages: Optional[int] = None   # slot budget; None → all pages
    use_kernel: bool = False             # Pallas kernel vs exact jnp ref
    buffer_hits: int = 0
    buffer_misses: int = 0
    _kbuf: Optional[jax.Array] = None    # (n_slots, B, Hkv, page, D)
    _vbuf: Optional[jax.Array] = None
    _slot_of: Dict[int, int] = dataclasses.field(default_factory=dict)
    _slot_page: List[Optional[int]] = dataclasses.field(default_factory=list)
    _slot_use: List[int] = dataclasses.field(default_factory=list)
    _use_clock: int = 0

    # ------------------------------------------------------------------
    @classmethod
    def create(cls, *, batch: int, max_seq: int, page_size: int,
               n_kv_heads: int, head_dim: int, dtype=jnp.float32,
               pool: Optional[MemoryPoolManager] = None,
               device_pages: Optional[int] = None,
               use_kernel: bool = False) -> "PagedKVCache":
        n_pages = -(-max_seq // page_size)
        if pool is None:
            raise ValueError(
                "PagedKVCache.create() requires a pool; construct caches "
                "through repro.api.HyperOffloadSession.paged_kv")
        if device_pages is not None and device_pages < 1:
            raise ValueError("device_pages must be >= 1 (or None = all)")
        pool.transfer.ensure_depth(auto_depth(pages=n_pages))
        return cls(
            page_size=page_size, n_pages=n_pages, batch=batch,
            n_kv_heads=n_kv_heads, head_dim=head_dim, dtype=dtype,
            pool=pool,
            k_pool=[None] * n_pages, v_pool=[None] * n_pages,
            k_summary=jnp.zeros((n_pages, batch, n_kv_heads, head_dim), dtype),
            k_tail=jnp.zeros((batch, n_kv_heads, page_size, head_dim), dtype),
            v_tail=jnp.zeros((batch, n_kv_heads, page_size, head_dim), dtype),
            key_ns=f"kvcache{next(_CACHE_IDS)}",
            device_pages=device_pages, use_kernel=use_kernel,
        )

    @property
    def full_pages(self) -> int:
        return self.length // self.page_size

    @property
    def tail_len(self) -> int:
        return self.length % self.page_size

    def pool_stats(self) -> dict:
        return self.pool.snapshot()

    def close(self) -> None:
        """The (always caller-provided, possibly shared) pool is its
        owner's to close; nothing per-cache needs shutting down."""

    # ------------------------------------------------------------------
    def _store_page(self, page_idx: int, k_page: jax.Array,
                    v_page: jax.Array) -> None:
        # recent pages rank higher for sparse selection → keep them closest
        kk = f"{self.key_ns}/k{page_idx}"
        vk = f"{self.key_ns}/v{page_idx}"
        self.pool.put(kk, k_page, priority=float(page_idx))
        self.pool.put(vk, v_page, priority=float(page_idx))
        self.k_pool[page_idx] = kk
        self.v_pool[page_idx] = vk
        self.flushes += 1
        if self._kbuf is not None:
            # install at flush: the newest page is the hottest, and taking
            # it from the tail (not a pool fetch-back) keeps the buffer
            # exact even when a codec quantizes the pool copy
            self._install_page(page_idx, k_page, v_page)

    def _flush_tail(self) -> None:
        """Store: commit the full tail page to the pool + update summary."""
        page_idx = self.length // self.page_size - 1
        self._store_page(page_idx, self.k_tail, self.v_tail)
        self.k_summary = self.k_summary.at[page_idx].set(
            _page_summary(self.k_tail))

    def append(self, k_t: jax.Array, v_t: jax.Array) -> None:
        """Append one token's K/V: (B, Hkv, D)."""
        i = self.tail_len
        self.k_tail = self.k_tail.at[:, :, i].set(k_t.astype(self.dtype))
        self.v_tail = self.v_tail.at[:, :, i].set(v_t.astype(self.dtype))
        self.length += 1
        if self.length % self.page_size == 0:
            self._flush_tail()

    def prefill(self, k_seq: jax.Array, v_seq: jax.Array) -> None:
        """Bulk-append a prompt: (B, S, Hkv·D), the model's cache layout,
        or (B, S, Hkv, D)."""
        b, s = k_seq.shape[:2]
        heads = (b, s, self.n_kv_heads, self.head_dim)
        k_seq = k_seq.reshape(heads).transpose(0, 2, 1, 3).astype(self.dtype)
        v_seq = v_seq.reshape(heads).transpose(0, 2, 1, 3).astype(self.dtype)
        n_full = s // self.page_size
        for pi in range(n_full):
            sl = slice(pi * self.page_size, (pi + 1) * self.page_size)
            kp, vp = k_seq[:, :, sl], v_seq[:, :, sl]
            self._store_page(pi, kp, vp)
            self.k_summary = self.k_summary.at[pi].set(_page_summary(kp))
        rem = s - n_full * self.page_size
        if rem:
            self.k_tail = self.k_tail.at[:, :, :rem].set(
                k_seq[:, :, n_full * self.page_size:])
            self.v_tail = self.v_tail.at[:, :, :rem].set(
                v_seq[:, :, n_full * self.page_size:])
        self.length = s

    # ------------------------------------------------------------------
    def select_pages(self, q: jax.Array, top_k: Optional[int]) -> np.ndarray:
        """Sparse block selection: rank full pages by mean-key relevance to
        the query (B, Hq, D) → sorted page indices (host ints)."""
        n = self.full_pages
        if n == 0:
            return np.zeros((0,), np.int64)
        if top_k is None or top_k >= n:
            return np.arange(n)
        summ = self.k_summary[:n]                     # (n, B, Hkv, D)
        qm = jnp.mean(q.astype(jnp.float32), axis=(0, 1))   # (D,)
        scores = jnp.einsum("nbhd,d->n", summ.astype(jnp.float32), qm)
        idx = np.asarray(jax.lax.top_k(scores, top_k)[1])
        return np.sort(idx)

    def _page_shape(self) -> Tuple[int, ...]:
        return (self.batch, self.n_kv_heads, self.page_size, self.head_dim)

    def fetch_pages(self, idx: Sequence[int]) -> Tuple[jax.Array, jax.Array]:
        """Prefetch (sync): copy the selected pool pages to device memory.
        Returns (n_sel, B, Hkv, page, D) device arrays."""
        if len(idx) == 0:
            shape = (0,) + self._page_shape()
            return jnp.zeros(shape, self.dtype), jnp.zeros(shape, self.dtype)
        ks = [self.pool.get(self.k_pool[int(i)]) for i in idx]
        vs = [self.pool.get(self.v_pool[int(i)]) for i in idx]
        self.fetches += len(idx)
        return jnp.stack(ks), jnp.stack(vs)

    def prefetch_pages(self, idx: Sequence[int]) -> PrefetchedPages:
        """Prefetch (async): issue page fetches through the pool's transfer
        engine; the caller overlaps compute and calls ``.wait()`` at use."""
        idx = np.asarray(idx, np.int64)
        kh = [self.pool.prefetch(self.k_pool[int(i)]) for i in idx]
        vh = [self.pool.prefetch(self.v_pool[int(i)]) for i in idx]
        self.fetches += len(idx)
        return PrefetchedPages(idx=idx, k_handles=kh, v_handles=vh,
                               _shape=self._page_shape(), _dtype=self.dtype)

    # ------------------------------------------------------------------
    def attend(self, q: jax.Array, *, scale: float,
               top_k_pages: Optional[int] = None,
               prefetched=None) -> jax.Array:
        """Decode attention of q (B, Hq, D) over selected pages + tail.
        ``prefetched`` — a ``PrefetchedPages`` or an already-waited
        (k, v, idx) tuple — lets the engine overlap next-step fetches."""
        if prefetched is not None:
            if isinstance(prefetched, PrefetchedPages):
                kp, vp, idx = prefetched.wait()
            else:
                kp, vp, idx = prefetched
        else:
            idx = self.select_pages(q, top_k_pages)
            kp, vp = self.fetch_pages(idx)
        return _paged_attend(q, kp, vp, self.k_tail, self.v_tail,
                             jnp.int32(self.tail_len), scale=scale)

    # -- fused decode over the device page buffer ----------------------
    @property
    def n_slots(self) -> int:
        return self.device_pages if self.device_pages is not None \
            else self.n_pages

    def _ensure_buffer(self) -> None:
        if self._kbuf is None:
            shape = (self.n_slots,) + self._page_shape()
            self._kbuf = jnp.zeros(shape, self.dtype)
            self._vbuf = jnp.zeros(shape, self.dtype)
            self._slot_page = [None] * self.n_slots
            self._slot_use = [0] * self.n_slots

    def _touch(self, slot: int) -> None:
        self._use_clock += 1
        self._slot_use[slot] = self._use_clock

    def _alloc_slot(self, keep: frozenset) -> int:
        """A free slot, else the LRU slot whose page is not needed this
        step; its old page stays safe in the pool (the buffer is a cache,
        never the only copy of a flushed page)."""
        victims = [s for s in range(self.n_slots)
                   if self._slot_page[s] is None
                   or self._slot_page[s] not in keep]
        if not victims:
            raise ValueError(
                f"device_pages={self.n_slots} is smaller than one step's "
                "page selection; raise the budget or lower top_k_pages")
        slot = min(victims, key=lambda s: (self._slot_page[s] is not None,
                                           self._slot_use[s]))
        old = self._slot_page[slot]
        if old is not None:
            del self._slot_of[old]
        return slot

    def _install_page(self, page_idx: int, k_page: jax.Array,
                      v_page: jax.Array, keep: frozenset = frozenset()) -> None:
        slot = self._slot_of.get(page_idx)
        if slot is None:
            slot = self._alloc_slot(keep)
            self._slot_of[page_idx] = slot
            self._slot_page[slot] = page_idx
        self._kbuf = self._kbuf.at[slot].set(k_page.astype(self.dtype))
        self._vbuf = self._vbuf.at[slot].set(v_page.astype(self.dtype))
        self._touch(slot)

    def _ensure_resident(self, idx: Sequence[int]) -> np.ndarray:
        """Map the selected page indices onto buffer slots, fetching
        misses from the pool (decoded). Returns the slot table the fused
        kernel/ref walks."""
        self._ensure_buffer()
        need = frozenset(int(i) for i in idx)
        slots = []
        for i in idx:
            i = int(i)
            slot = self._slot_of.get(i)
            if slot is None:
                self.buffer_misses += 1
                self.fetches += 1
                self._install_page(i, self.pool.get(self.k_pool[i]),
                                   self.pool.get(self.v_pool[i]), keep=need)
                slot = self._slot_of[i]
            else:
                self.buffer_hits += 1
                self._touch(slot)
            slots.append(slot)
        return np.asarray(slots, np.int64)

    def attend_fused(self, q: jax.Array, *, scale: float,
                     top_k_pages: Optional[int] = None,
                     use_kernel: Optional[bool] = None) -> jax.Array:
        """Fused decode attention of q (B, Hq, D) over selected pages +
        tail — same selection and same merged-softmax semantics as
        ``attend``, but over the device page buffer via a page table:
        no per-step gather/concat pool round trip. Steady state (all
        selected pages resident) touches the pool zero times per step.

        ``use_kernel=False`` (instance default) runs the jitted exact-math
        reference — bit-identical to ``attend`` for resident pages, which
        is what makes codec-"none" serving token-identical; ``True`` runs
        the Pallas online-softmax kernel (parity-tested to 2e-5 in f32,
        interpret mode on CPU)."""
        idx = self.select_pages(q, top_k_pages)
        slots = self._ensure_resident(idx)
        args = (q, self._kbuf, self._vbuf, jnp.asarray(slots, jnp.int32),
                self.k_tail, self.v_tail, jnp.int32(self.tail_len))
        if use_kernel is None:
            use_kernel = self.use_kernel
        if use_kernel:
            from repro.kernels.ops import paged_decode_attention
            return paged_decode_attention(*args, scale=scale)
        return _fused_attend_ref(*args, scale=scale)

