"""Capacity-tracked memory-pool manager over the tiered backends.

``MemoryPoolManager`` owns an ordered spill chain of tiers, described
declaratively by a ``TierTopology`` (``default_pool`` builds the
historical device → host → remote chain when none is given). Each ``put``
is charged against the tier's byte capacity; when a tier is full, victims
are chosen by (planner priority, then LRU) among unpinned entries and
**spilled** to the next tier down the chain — the paper's hierarchy: HBM
overflows to the local host pool, the host pool overflows to the remote
pooled-DRAM tier — and an N-tier topology spills the same way, link by
link. Only when the last tier is full does a put fail with
``PoolCapacityError``.

Priorities are the planner's hint channel: the executor can mark a tensor
it will prefetch soon with a high priority so reactive churn never evicts
it — the graph-driven/reactive distinction at the heart of the paper.

All traffic is counted (puts/gets/evictions, bytes in/out, per-tier
occupancy and high-water mark); serving and the session surface these via
``stats.snapshot()``. Synchronous movement (puts, spills, blocking gets)
additionally lands in the transfer engine's per tier-pair table, so the
calibration loop sees every byte the hierarchy moves, not just the async
prefetches.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from repro.obs.trace import NULL_TRACER
from repro.pool import backend as B
from repro.pool import codec as codec_mod
from repro.pool.topology import TierTopology
from repro.pool.transfer import TransferEngine, TransferHandle


class PoolCapacityError(RuntimeError):
    """Every tier is full (after spilling) — the put cannot be honored."""


@dataclass
class PoolEntry:
    key: str
    tier: str
    handle: Any
    nbytes: int
    priority: float = 0.0      # higher → evicted later (planner hint)
    pinned: bool = False
    last_use: int = 0          # LRU clock


@dataclass
class TierState:
    name: str
    backend: B.MemoryBackend
    capacity: Optional[int] = None     # bytes; None → unbounded
    used: int = 0
    peak: int = 0

    def room_for(self, nbytes: int) -> bool:
        return self.capacity is None or self.used + nbytes <= self.capacity


@dataclass
class PoolStats:
    puts: int = 0
    gets: int = 0
    evictions: int = 0
    drops: int = 0
    bytes_stored: int = 0
    bytes_fetched: int = 0
    bytes_evicted: int = 0

    def snapshot(self) -> Dict[str, float]:
        return dict(self.__dict__)


class MemoryPoolManager:
    def __init__(self, tiers: Sequence[TierState],
                 transfer: Optional[TransferEngine] = None,
                 tracer=None, topology: Optional[TierTopology] = None) -> None:
        if not tiers:
            raise ValueError("need at least one tier")
        self.tiers: Dict[str, TierState] = {t.name: t for t in tiers}
        self.spill_order: List[str] = [t.name for t in tiers]
        self.topology = topology
        if topology is not None and list(topology.names) != self.spill_order:
            raise ValueError(
                f"topology names {topology.names} do not match tier states "
                f"{self.spill_order}")
        self.entries: Dict[str, PoolEntry] = {}
        self.transfer = transfer or TransferEngine()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        if tracer is not None:
            self.transfer.set_tracer(tracer)
        self.stats = PoolStats()
        self._clock = 0
        self._lock = threading.RLock()
        # admission ledger: key -> (nbytes, tiers reserved against, covered
        # key prefix whose entries the reservation pays for)
        self._reservations: Dict[str, Tuple[int, Tuple[str, ...], Optional[str]]] = {}
        self._evict_listeners: List[Callable[[PoolEntry, str], None]] = []

    def set_tracer(self, tracer) -> None:
        """Attach/replace the tracer on the pool AND its transfer engine
        (the session wires its telemetry into an injected pool here)."""
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.transfer.set_tracer(tracer)

    # -- topology-derived roles ----------------------------------------
    @property
    def top_tier(self) -> str:
        """The chain's fastest tier — where compute-resident pages park."""
        return self.spill_order[0]

    @property
    def default_store_tier(self) -> str:
        """Where ``put`` lands when the caller names no tier: the
        topology's declared store tier, else the historical ``host``
        default when such a tier exists, else the first off-accelerator
        tier of the chain."""
        if self.topology is not None:
            return self.topology.default_store_tier
        if B.HOST_TIER in self.tiers:
            return B.HOST_TIER
        for name in self.spill_order:
            if not isinstance(self._tier(name).backend, B.DeviceBackend):
                return name
        return self.spill_order[-1]

    @property
    def admission_tiers(self) -> Tuple[str, ...]:
        """Tiers admission control may count a request's worst-case pages
        against (``sched.queue.AdmissionController``) — declared per-spec
        in the topology; for topology-less pools, the historical
        device+host pair (every tier above the last as a fallback)."""
        if self.topology is not None:
            return self.topology.admission_tiers
        legacy = tuple(n for n in self.spill_order
                       if n in (B.DEVICE_TIER, B.HOST_TIER))
        if legacy:
            return legacy
        return tuple(self.spill_order[:-1]) or (self.spill_order[0],)

    # -- storing -------------------------------------------------------
    def put(self, key: str, value, tier: Optional[str] = None, *,
            priority: float = 0.0, pinned: bool = False) -> PoolEntry:
        """Store ``value`` into ``tier`` (default: the pool's
        ``default_store_tier``), evicting (spilling down-hierarchy)
        as needed. Re-putting an existing key replaces it; if the new value
        doesn't fit, the old entry survives untouched."""
        if tier is None:
            tier = self.default_store_tier
        t0 = self.tracer.now() if self.tracer.enabled else 0.0
        with self._lock:
            st = self._tier(tier)
            # on-wire size: what this put moves and occupies at rest. For
            # a codec-wrapped tier this is the *encoded* size — every
            # byte counter downstream (tier occupancy, bytes_stored, the
            # per tier-pair calibration table) must see wire bytes, not
            # the decoded nbytes, or measured bandwidth inflates by the
            # compression ratio.
            nbytes = int(st.backend.wire_nbytes(value))
            old = self.entries.pop(key, None)
            if old is not None:
                self._tier(old.tier).used -= old.nbytes
            try:
                self._make_room(st, nbytes)
            except PoolCapacityError:
                if old is not None:   # restore — a failed put loses nothing
                    self.entries[key] = old
                    self._tier(old.tier).used += old.nbytes
                raise
            t_x = time.perf_counter()
            handle = st.backend.put(value)
            if not isinstance(st.backend, B.DeviceBackend):
                # value arrives device-side; a store into any lower tier is
                # measured d2r traffic the calibration table should see
                self.transfer.record_pair(B.DEVICE_TIER, tier, nbytes,
                                          time.perf_counter() - t_x)
            self._clock += 1
            entry = PoolEntry(key=key, tier=tier, handle=handle,
                              nbytes=nbytes, priority=priority,
                              pinned=pinned, last_use=self._clock)
            self.entries[key] = entry
            st.used += nbytes
            st.peak = max(st.peak, st.used)
            self.stats.puts += 1
            self.stats.bytes_stored += nbytes
            if self.tracer.enabled:
                self.tracer.complete("pool", "put", t0, self.tracer.now() - t0,
                                     {"key": key, "tier": tier,
                                      "nbytes": nbytes})
            return entry

    # -- fetching ------------------------------------------------------
    def get(self, key: str):
        """Materialize the entry on device (synchronous)."""
        t0 = self.tracer.now() if self.tracer.enabled else 0.0
        with self._lock:
            entry = self.entries[key]
            self._clock += 1
            entry.last_use = self._clock
            self.stats.gets += 1
            self.stats.bytes_fetched += entry.nbytes
            backend, handle = self._tier(entry.tier).backend, entry.handle
        t_x = time.perf_counter()
        value = backend.get(handle)
        if not isinstance(backend, B.DeviceBackend):
            self.transfer.record_pair(entry.tier, B.DEVICE_TIER, entry.nbytes,
                                      time.perf_counter() - t_x)
        if self.tracer.enabled:
            self.tracer.complete("pool", "fetch", t0, self.tracer.now() - t0,
                                 {"key": key, "tier": entry.tier,
                                  "nbytes": entry.nbytes})
        return value

    def prefetch(self, key: str) -> TransferHandle:
        """Issue an async device fetch through the transfer engine; the
        returned handle's ``wait()`` yields the device array. The source
        tier rides along as trace metadata (per-tier-pair overlap)."""
        with self._lock:
            entry = self.entries[key]   # fail fast on unknown keys
            backend, handle = self._tier(entry.tier).backend, entry.handle
            src = entry.tier

        def fetch():
            with self._lock:
                self._clock += 1
                entry.last_use = self._clock
                self.stats.gets += 1
                self.stats.bytes_fetched += entry.nbytes
            return backend.get(handle)

        return self.transfer.submit(fetch, key=key, src=src,
                                    dst=B.DEVICE_TIER, nbytes=entry.nbytes)

    # -- bookkeeping ---------------------------------------------------
    def close(self) -> None:
        """Drain and shut down the transfer engine's worker threads."""
        self.transfer.close()

    def drop(self, key: str) -> None:
        with self._lock:
            self._forget(key)
            self.stats.drops += 1

    def pin(self, key: str, pinned: bool = True) -> None:
        with self._lock:
            self.entries[key].pinned = pinned

    def set_priority(self, key: str, priority: float) -> None:
        """Re-rank an entry for eviction without touching its data — the
        scheduler demotes a preempted request's parked pages this way so
        device-tier pressure spills them ahead of live sequences' pages
        (no-op for keys not in the pool)."""
        with self._lock:
            entry = self.entries.get(key)
            if entry is not None:
                entry.priority = priority

    # -- admission control (capacity reservation) ----------------------
    def reserve(self, key: str, nbytes: int,
                tiers: Optional[Sequence[str]] = None,
                covers: Optional[str] = None,
                itemsize: Optional[int] = None) -> bool:
        """Reserve ``nbytes`` of worst-case capacity against the combined
        byte budget of ``tiers`` (default: every tier). This is the serving
        scheduler's admission-control ledger: a request is admitted only if
        its worst-case KV pages fit alongside current occupancy plus every
        standing reservation. Reservations are bookkeeping only — they never
        block ``put`` (puts spill down-tier by design) — but a put made
        under a reservation is guaranteed a home in the reserved tiers.

        ``covers`` names a key prefix whose entries this reservation pays
        for: their occupancy is excluded from the capacity check (they are
        bounded by — and already charged as — the reservation), so a
        running request's parked pages aren't double-counted against new
        admissions.

        ``nbytes`` is always the *decoded* (full-precision) worst case;
        ``itemsize`` tells the ledger the decoded element size so
        codec-wrapped tiers are counted at their decoded-equivalent
        capacity (a tier storing int8 payloads of fp32 pages effectively
        holds 4× the decoded bytes its raw capacity suggests). Without it
        the check is raw-byte (historical) and under-admits when a codec
        is active.

        Returns False (and records nothing) if it doesn't fit; re-reserving
        an existing key replaces it. A tier with unbounded capacity makes
        the reservation always succeed."""
        with self._lock:
            tiers = tuple(tiers) if tiers is not None else tuple(self.spill_order)
            old = self._reservations.pop(key, None)
            cap, used, unbounded = self._capacity_used(tiers, itemsize)
            if not unbounded:
                held = sum(n for n, ts, _ in self._reservations.values()
                           if set(ts) & set(tiers))
                if used + held + int(nbytes) > cap:
                    if old is not None:
                        self._reservations[key] = old
                    return False
            self._reservations[key] = (int(nbytes), tiers, covers)
            return True

    def release(self, key: str) -> None:
        """Drop a reservation (no-op if absent)."""
        with self._lock:
            self._reservations.pop(key, None)

    def reserved_bytes(self, tiers: Optional[Sequence[str]] = None) -> int:
        with self._lock:
            if tiers is None:
                return sum(n for n, _, _ in self._reservations.values())
            want = set(tiers)
            return sum(n for n, ts, _ in self._reservations.values()
                       if set(ts) & want)

    def headroom(self, tiers: Sequence[str],
                 itemsize: Optional[int] = None) -> Optional[int]:
        """Free *decoded-equivalent* bytes across ``tiers`` after occupancy
        (reservation-covered entries excluded) and standing reservations
        (None = unbounded). ``itemsize`` as in :meth:`reserve`."""
        with self._lock:
            cap, used, unbounded = self._capacity_used(tiers, itemsize)
            if unbounded:
                return None
            return cap - used - self.reserved_bytes(tiers)

    def tier_scale(self, name: str, itemsize: Optional[int]) -> float:
        """On-wire bytes per decoded byte for entries at rest in ``name``
        (< 1 on a codec-wrapped tier). ``None`` itemsize → 1.0, the
        historical raw-byte accounting."""
        if itemsize is None:
            return 1.0
        b = self._tier(name).backend
        if isinstance(b, B.CodecBackend):
            return b.codec.ratio(int(itemsize))
        return 1.0

    def _capacity_used(self, tiers: Sequence[str],
                       itemsize: Optional[int] = None) -> Tuple[int, int, bool]:
        """(capacity, occupancy-net-of-covered-entries, any-unbounded)
        across ``tiers``, in decoded-equivalent bytes when ``itemsize``
        is given (each codec tier's capacity and occupancy are divided by
        its wire/decoded ratio before summing — per tier, because the
        ratio differs tier to tier). Covered entries (key under a
        reservation's ``covers`` prefix) are bounded by their reservation,
        which the caller charges separately."""
        cap = used = 0.0
        unbounded = False
        names = set(tiers)
        prefixes = tuple(c for _, ts, c in self._reservations.values()
                         if c is not None and set(ts) & names)
        for t in tiers:
            st = self._tier(t)
            if st.capacity is None:
                unbounded = True
                continue
            scale = self.tier_scale(t, itemsize)
            tier_used = st.used
            if prefixes:
                tier_used -= sum(e.nbytes for e in self.entries.values()
                                 if e.tier == t and e.key.startswith(prefixes))
            cap += st.capacity / scale
            used += tier_used / scale
        # floor capacity / ceil occupancy: rounding never over-admits
        return int(math.floor(cap)), int(math.ceil(used)), unbounded

    # -- eviction notification -----------------------------------------
    def add_evict_listener(self, cb: Callable[[PoolEntry, str], None]) -> None:
        """Register ``cb(entry, dst_tier)``, called after an entry spills
        down-hierarchy. Called under the pool lock — keep it cheap and
        don't block (pool methods are safe to call: the lock is reentrant)."""
        with self._lock:
            self._evict_listeners.append(cb)

    def remove_evict_listener(self, cb: Callable[[PoolEntry, str], None]) -> None:
        """Unregister a listener (no-op if absent) — callers sharing a
        long-lived pool must remove themselves on shutdown."""
        with self._lock:
            if cb in self._evict_listeners:
                self._evict_listeners.remove(cb)

    def __contains__(self, key: str) -> bool:
        return key in self.entries

    def tier_of(self, key: str) -> str:
        return self.entries[key].tier

    def is_host_resident(self, key: str) -> bool:
        """The entry lives off-device AND its handle checks out where its
        tier claims (device-tier entries are never 'host resident')."""
        entry = self.entries[key]
        st = self._tier(entry.tier)
        return (not isinstance(st.backend, B.DeviceBackend)
                and st.backend.holds(entry.handle))

    def occupancy(self, tier: str) -> Tuple[int, Optional[int]]:
        st = self._tier(tier)
        return st.used, st.capacity

    def snapshot(self) -> Dict[str, Any]:
        """Stats + per-tier occupancy, merged into ``session.stats()``."""
        with self._lock:
            out: Dict[str, Any] = self.stats.snapshot()
            out["transfer"] = self.transfer.stats.snapshot()
            out["reserved"] = self.reserved_bytes()
            for name, st in self.tiers.items():
                out[f"tier/{name}"] = {
                    "backend": st.backend.name, "used": st.used,
                    "peak": st.peak, "capacity": st.capacity,
                    "entries": sum(1 for e in self.entries.values()
                                   if e.tier == name),
                }
            return out

    # -- internals -----------------------------------------------------
    def _tier(self, name: str) -> TierState:
        try:
            return self.tiers[name]
        except KeyError:
            raise KeyError(f"unknown tier {name!r}; have {list(self.tiers)}")

    def _forget(self, key: str) -> None:
        entry = self.entries.pop(key)
        self._tier(entry.tier).used -= entry.nbytes

    def _next_tier(self, name: str) -> Optional[str]:
        i = self.spill_order.index(name)
        return self.spill_order[i + 1] if i + 1 < len(self.spill_order) else None

    def _make_room(self, st: TierState, nbytes: int) -> None:
        while not st.room_for(nbytes):
            victim = self._pick_victim(st.name)
            if victim is None:
                raise PoolCapacityError(
                    f"tier {st.name!r}: need {nbytes} bytes, "
                    f"{st.used}/{st.capacity} used, nothing evictable")
            self._evict(victim)

    def _pick_victim(self, tier: str) -> Optional[PoolEntry]:
        candidates = [e for e in self.entries.values()
                      if e.tier == tier and not e.pinned]
        if not candidates:
            return None
        # lowest planner priority first; LRU breaks ties
        return min(candidates, key=lambda e: (e.priority, e.last_use))

    def _evict(self, entry: PoolEntry) -> None:
        """Spill one entry to the next tier down (or fail at the bottom)."""
        dst = self._next_tier(entry.tier)
        if dst is None:
            raise PoolCapacityError(
                f"cannot evict {entry.key!r}: {entry.tier!r} is the last tier")
        src_st, dst_st = self._tier(entry.tier), self._tier(dst)
        # the entry's at-rest size may change across the boundary: a spill
        # into a codec tier quantizes (fewer wire bytes), a spill between
        # two codec tiers moves the payload as-is. What actually crosses
        # the link is the destination's wire size.
        new_nbytes = int(dst_st.backend.wire_nbytes(entry.handle))
        self._make_room(dst_st, new_nbytes)
        t_x = time.perf_counter()
        entry.handle = dst_st.backend.put(entry.handle)
        self.transfer.record_pair(src_st.name, dst, new_nbytes,
                                  time.perf_counter() - t_x)
        src_st.used -= entry.nbytes
        dst_st.used += new_nbytes
        dst_st.peak = max(dst_st.peak, dst_st.used)
        entry.tier = dst
        entry.nbytes = new_nbytes
        self.stats.evictions += 1
        self.stats.bytes_evicted += new_nbytes
        if self.tracer.enabled:
            self.tracer.instant("pool", "spill",
                                {"key": entry.key, "src": src_st.name,
                                 "dst": dst, "nbytes": new_nbytes})
        for cb in self._evict_listeners:
            cb(entry, dst)


# ---------------------------------------------------------------------------


def default_pool(host_capacity: Optional[int] = None,
                 remote_capacity: Optional[int] = None,
                 device_capacity: Optional[int] = None,
                 device=None,
                 transfer: Optional[TransferEngine] = None, *,
                 topology: Optional[TierTopology] = None,
                 transfer_depth: Optional[int] = None,
                 transfer_workers: int = 2,
                 codec: Optional[str] = None,
                 codec_below: Optional[str] = None,
                 tracer=None) -> MemoryPoolManager:
    """Build a pool from a declarative ``TierTopology`` — by default the
    standard three-tier chain: device HBM → host → modeled remote
    (unthrottled, i.e. the historical simulated-remote behavior).

    Capacities may be passed either through the legacy per-tier kwargs (the
    default chain only) or inside an explicit ``topology``'s specs — never
    both.

    ``codec`` names a KV page codec (``"int8"``/``"fp8"``; ``None``/
    ``"none"`` disables). Every tier from ``codec_below`` (default: the
    topology's default store tier) down to the bottom of the chain gets its
    backend wrapped in a :class:`~repro.pool.backend.CodecBackend`, so
    pages quantize once on first arrival below the boundary and spills
    deeper down move the compact payload as-is. Spills only ever descend,
    so an encoded page can never land in an unwrapped tier. The boundary
    must not be an accelerator tier — the compute path needs full-precision
    pages on device.

    ``transfer_depth``/``transfer_workers`` build the engine here so callers
    outside the pool subsystem never construct a ``TransferEngine`` — depth
    comes from ``transfer.auto_depth`` (or ``OffloadConfig``)."""
    if topology is None:
        topology = TierTopology.default(device_capacity=device_capacity,
                                        host_capacity=host_capacity,
                                        remote_capacity=remote_capacity)
    elif any(c is not None for c in (host_capacity, remote_capacity,
                                     device_capacity)):
        raise ValueError(
            "pass capacities inside the topology's TierSpecs, not alongside "
            "an explicit topology")
    if transfer is None and transfer_depth is not None:
        transfer = TransferEngine(depth=transfer_depth, workers=transfer_workers)
    codec_obj = codec_mod.make_codec(codec)
    boundary = codec_below if codec_below is not None \
        else topology.default_store_tier
    if codec_obj is not None and boundary not in topology.names:
        raise ValueError(
            f"kv_codec boundary tier {boundary!r} not in topology "
            f"{list(topology.names)}")
    tiers = []
    below = False
    for s in topology.tiers:
        b = B.backend_for(s, device)
        if codec_obj is not None:
            if s.name == boundary:
                below = True
            if below:
                if isinstance(b, B.DeviceBackend):
                    raise ValueError(
                        f"kv_codec boundary {boundary!r} would wrap "
                        f"accelerator tier {s.name!r}; pick an "
                        "off-accelerator tier")
                b = B.CodecBackend(b, codec_obj)
        tiers.append(TierState(s.name, b, s.capacity))
    return MemoryPoolManager(tiers, transfer=transfer, tracer=tracer,
                             topology=topology)
