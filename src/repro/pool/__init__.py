"""Runtime memory-pool subsystem (§5 remote memory backend).

- ``topology`` — declarative ``TierTopology``: the spill chain as an
  ordered list of ``TierSpec``s (backend kind, capacity, admission role,
  modeled latency/bandwidth) instead of hard-coded tier strings;
- ``backend``  — tiered memory backends (device HBM / host memory-kind
  shardings / sleep-throttled modeled disaggregated tier) behind one
  interface, with per-device capability probing;
- ``codec``    — int8/fp8 KV page codecs (per-page absmax scales); tiers
  below a configurable boundary store and move encoded payloads, so
  host/remote transfers carry 2–4× fewer bytes;
- ``manager``  — capacity-tracked ``MemoryPoolManager`` with
  priority+LRU eviction that spills down the declared tier chain;
- ``transfer`` — async double-buffered ``TransferEngine`` with explicit
  wait handles (prefetches genuinely overlap compute);
- ``executor`` — ``OffloadPlanExecutor`` runs a planned graph's refined
  order against the real pool and proves the executed residency trace
  matches ``core.memsim``'s prediction.
"""

from repro.pool.backend import (
    DEVICE_TIER, HOST_TIER, REMOTE_TIER,
    CodecBackend, ModeledTierBackend, backend_for, capabilities,
    device_sharding, host_memory_kind, host_sharding, is_host_resident,
    make_backend, make_host_backend, to_device, to_host,
)
from repro.pool.codec import (
    CODECS, EncodedPage, Fp8Codec, Int8Codec, KVCodec, make_codec,
    roundtrip_bound,
)
from repro.pool.topology import TierSpec, TierTopology, sweep_topologies
from repro.pool.manager import (
    MemoryPoolManager, PoolCapacityError, PoolEntry, TierState, default_pool,
)
from repro.pool.transfer import (
    TransferEngine, TransferHandle, TransferStats, auto_depth,
)
from repro.pool.executor import ExecutionTrace, OffloadPlanExecutor

__all__ = [
    "DEVICE_TIER", "HOST_TIER", "REMOTE_TIER",
    "CODECS", "CodecBackend", "EncodedPage", "Fp8Codec", "Int8Codec",
    "KVCodec", "make_codec", "roundtrip_bound",
    "ModeledTierBackend", "backend_for",
    "capabilities", "device_sharding", "host_memory_kind", "host_sharding",
    "is_host_resident", "make_backend", "make_host_backend",
    "to_device", "to_host",
    "TierSpec", "TierTopology", "sweep_topologies",
    "MemoryPoolManager", "PoolCapacityError", "PoolEntry", "TierState",
    "default_pool",
    "TransferEngine", "TransferHandle", "TransferStats", "auto_depth",
    "ExecutionTrace", "OffloadPlanExecutor",
]
