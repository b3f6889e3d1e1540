"""Tiered memory backends behind one interface (§5 remote memory backend).

Backends are selected per-tier by a declarative ``TierSpec.kind``
(``pool.topology``); the default chain mirrors the paper's hierarchy:

- **device** — accelerator HBM (JAX default memory);
- **host**   — ``pinned_host`` memory-kind shardings, else
  ``unpinned_host``. An accelerator that addresses neither cannot keep
  the tier in its host memory, and building the tier there raises; only a
  CPU build that addresses no host kind falls back to plain NumPy
  buffers;
- **modeled** — the disaggregated pooled-DRAM stand-in (CloudMatrix /
  CXL-hybrid tier): NumPy storage behind a sleep-throttle that *enforces*
  the spec's per-direction bandwidth and latency, so the runtime feels —
  and the telemetry measures — a configurable transfer character instead
  of whatever the host happens to do. Unthrottled it degenerates to the
  old plain-NumPy remote tier.

Capability probing happens once per device and is cached; every offload
call site (kv pages, optimizer moments, plan execution) routes through the
probe instead of hard-coding ``pinned_host`` — that hard-coding is exactly
why the seed's offload runtime failed on CPU backends.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from dataclasses import dataclass
from typing import Any, Optional, Tuple

import jax
import numpy as np

from repro.pool import codec as codec_mod

DEVICE_TIER = "device"
HOST_TIER = "host"
REMOTE_TIER = "remote"

# preference order for the host tier's memory kind
_HOST_KIND_PREFERENCE = ("pinned_host", "unpinned_host")


@dataclass(frozen=True)
class Capabilities:
    """What one device can address, probed once."""

    platform: str
    memory_kinds: Tuple[str, ...]      # addressable kinds
    default_kind: Optional[str]        # the device's default memory kind
    host_kind: Optional[str]           # best host kind, None → NumPy fallback

    @property
    def supports_host_sharding(self) -> bool:
        return self.host_kind is not None


def _probe(device) -> Capabilities:
    kinds = tuple(m.kind for m in device.addressable_memories())
    host = next((k for k in _HOST_KIND_PREFERENCE if k in kinds), None)
    return Capabilities(platform=device.platform, memory_kinds=kinds,
                        default_kind=device.default_memory().kind,
                        host_kind=host)


@functools.lru_cache(maxsize=None)
def _capabilities_cached(device) -> Capabilities:
    return _probe(device)


def capabilities(device=None) -> Capabilities:
    return _capabilities_cached(device if device is not None else jax.devices()[0])


def host_memory_kind(device=None) -> Optional[str]:
    """Best host memory kind for this device, or None (→ NumPy fallback)."""
    return capabilities(device).host_kind


def device_sharding(device=None) -> jax.sharding.SingleDeviceSharding:
    d = device if device is not None else jax.devices()[0]
    return jax.sharding.SingleDeviceSharding(d)


def host_sharding(device=None) -> Optional[jax.sharding.SingleDeviceSharding]:
    d = device if device is not None else jax.devices()[0]
    kind = host_memory_kind(d)
    if kind is None:
        return None
    return jax.sharding.SingleDeviceSharding(d, memory_kind=kind)


# ---------------------------------------------------------------------------
# single-array transfer helpers (used by optstate / jax_exec)
# ---------------------------------------------------------------------------


def to_host(x, device=None):
    """Store one array in host memory: memory-kind sharding if supported,
    else a NumPy buffer (forces the device→host copy either way)."""
    s = host_sharding(device)
    if s is None:
        return np.asarray(x)
    return jax.device_put(x, s)


def to_device(x, device=None) -> jax.Array:
    """Prefetch one array (jax host-kind array or NumPy buffer) to device."""
    return jax.device_put(x, device_sharding(device))


def is_host_resident(x, device=None) -> bool:
    """True if ``x`` lives in the host tier (however this platform spells
    it). On probe-less builds only NumPy buffers count — a jax array's
    memory kind can't be trusted to mean "host" there."""
    if isinstance(x, np.ndarray):
        return True
    want = host_memory_kind(device)
    if want is None:
        return False
    return getattr(getattr(x, "sharding", None), "memory_kind", None) == want


# ---------------------------------------------------------------------------
# backend objects (the pool manager's tier storage)
# ---------------------------------------------------------------------------


class MemoryBackend:
    """One storage tier: ``put`` stores a device array into the tier and
    returns an opaque handle; ``get`` materializes a handle on device."""

    name: str = "abstract"

    def put(self, value) -> Any:
        raise NotImplementedError

    def get(self, handle) -> jax.Array:
        raise NotImplementedError

    def nbytes(self, handle) -> int:
        return int(handle.nbytes)

    def wire_nbytes(self, value) -> int:
        """Bytes a ``put(value)`` will move over the wire and occupy at
        rest in this tier — what capacity accounting and the transfer
        telemetry must charge. Identity for plain tiers; a codec-wrapped
        tier reports the *encoded* size."""
        return int(value.nbytes)

    def holds(self, handle) -> bool:
        """Residency check: does the handle live where this tier claims?"""
        raise NotImplementedError


class DeviceBackend(MemoryBackend):
    """Accelerator HBM — JAX default memory."""

    name = "device"

    def __init__(self, device=None) -> None:
        self.device = device if device is not None else jax.devices()[0]
        self._sharding = device_sharding(self.device)

    def put(self, value) -> jax.Array:
        return jax.device_put(value, self._sharding)

    def get(self, handle) -> jax.Array:
        return handle

    def holds(self, handle) -> bool:
        return isinstance(handle, jax.Array)


class JaxHostBackend(MemoryBackend):
    """Host memory via memory-kind shardings (pinned_host / unpinned_host)."""

    def __init__(self, device=None, kind: Optional[str] = None) -> None:
        self.device = device if device is not None else jax.devices()[0]
        self.kind = kind if kind is not None else host_memory_kind(self.device)
        if self.kind is None:
            raise ValueError(
                f"device {self.device} addresses no host memory kind; "
                "use NumpyHostBackend")
        self.name = f"jax-host[{self.kind}]"
        self._host = jax.sharding.SingleDeviceSharding(
            self.device, memory_kind=self.kind)
        self._dev = device_sharding(self.device)

    def put(self, value) -> jax.Array:
        return jax.device_put(value, self._host)

    def get(self, handle) -> jax.Array:
        return jax.device_put(handle, self._dev)

    def holds(self, handle) -> bool:
        return getattr(getattr(handle, "sharding", None),
                       "memory_kind", None) == self.kind


class NumpyHostBackend(MemoryBackend):
    """Plain NumPy host buffers — the simulated remote pool, and the
    last-resort host tier on platforms with no memory-kind support.
    ``np.asarray`` blocks until the device→host copy lands, so a handle is
    always a fully materialized host buffer."""

    name = "numpy-host"

    def __init__(self, device=None) -> None:
        self.device = device if device is not None else jax.devices()[0]
        self._dev = device_sharding(self.device)

    def put(self, value) -> np.ndarray:
        return np.asarray(value)

    def get(self, handle) -> jax.Array:
        return jax.device_put(handle, self._dev)

    def holds(self, handle) -> bool:
        return isinstance(handle, np.ndarray)


class ModeledTierBackend(MemoryBackend):
    """The modeled disaggregated tier: NumPy storage behind a throttle
    that enforces a configured transfer character. Each ``put`` sleeps out
    the remainder of ``write_latency_s + nbytes/write_bw`` past the time
    the real copy took (``get`` likewise with the read-direction numbers,
    after blocking until the device copy lands — enforced timing must
    cover the actual data movement, not an async dispatch). A ``None``
    bandwidth with zero latency disables the throttle for that direction,
    so an unthrottled modeled tier behaves exactly like the historical
    plain-NumPy remote tier.

    Throttling is per-transfer and independent across engine worker
    threads — concurrent transfers genuinely overlap, which is what makes
    the tier sweepable like a real link: aggregate throughput scales with
    in-flight parallelism up to the bandwidth-delay product, the dynamic
    the calibration loop (``core.calibration``) sizes prefetch workers
    against."""

    def __init__(self, device=None, *, read_bw: Optional[float] = None,
                 write_bw: Optional[float] = None,
                 read_latency_s: float = 0.0,
                 write_latency_s: float = 0.0,
                 name: str = "modeled") -> None:
        self.device = device if device is not None else jax.devices()[0]
        self._dev = device_sharding(self.device)
        self.read_bw = read_bw
        self.write_bw = write_bw
        self.read_latency_s = float(read_latency_s)
        self.write_latency_s = float(write_latency_s)
        self.name = name

    @property
    def throttled(self) -> bool:
        return (self.read_bw is not None or self.write_bw is not None
                or self.read_latency_s > 0 or self.write_latency_s > 0)

    @staticmethod
    def _throttle(t0: float, nbytes: int, bw: Optional[float],
                  latency_s: float) -> None:
        if bw is None and latency_s <= 0:
            return
        want = latency_s + (nbytes / bw if bw is not None else 0.0)
        remaining = want - (time.perf_counter() - t0)
        if remaining > 0:
            time.sleep(remaining)

    def put(self, value) -> np.ndarray:
        t0 = time.perf_counter()
        handle = np.asarray(value)   # blocks until the device→host copy lands
        self._throttle(t0, int(handle.nbytes), self.write_bw,
                       self.write_latency_s)
        return handle

    def get(self, handle) -> jax.Array:
        t0 = time.perf_counter()
        value = jax.device_put(handle, self._dev)
        if self.read_bw is not None or self.read_latency_s > 0:
            value.block_until_ready()
            self._throttle(t0, int(handle.nbytes), self.read_bw,
                           self.read_latency_s)
        return value

    def holds(self, handle) -> bool:
        return isinstance(handle, np.ndarray)


class CodecBackend(MemoryBackend):
    """A storage tier behind a KV page codec (``pool.codec``): encodes on
    ``put`` below the configured tier boundary, decodes on ``get``.

    The handle is an ``EncodedPage`` whose payload is stored through the
    wrapped backend, so the inner tier's character (memory-kind sharding,
    NumPy buffer, modeled sleep-throttle) applies to the *encoded* bytes —
    a throttled tier genuinely completes int8 pages ~4× faster, exactly
    the effect the codec exists to buy. Spilling an ``EncodedPage`` from
    one codec tier to another with the same codec moves the payload
    untouched: no decode/re-encode round trip, no compounding of
    quantization error. ``wire_nbytes``/``nbytes`` report the encoded
    size, which is what the pool's capacity accounting, the per tier-pair
    transfer table, and therefore ``core.calibration`` all see."""

    def __init__(self, inner: MemoryBackend, codec) -> None:
        self.inner = inner
        self.codec = codec
        self.name = f"{codec.name}[{inner.name}]"

    def put(self, value) -> "codec_mod.EncodedPage":
        if isinstance(value, codec_mod.EncodedPage):
            if value.codec != self.codec.name:
                raise ValueError(
                    f"cannot move a {value.codec!r}-encoded page into a "
                    f"{self.codec.name!r} tier without decoding first")
            # spill between codec tiers: move the encoded payload only
            return dataclasses.replace(
                value, payload=self.inner.put(value.payload))
        payload, scale = self.codec.encode(value)
        handle = self.inner.put(payload)
        return codec_mod.EncodedPage(
            codec=self.codec.name, payload=handle, scale=scale,
            dtype=str(value.dtype), shape=tuple(value.shape),
            nbytes=self.codec.encoded_nbytes(value.shape, value.dtype))

    def get(self, handle) -> jax.Array:
        payload = self.inner.get(handle.payload)
        return self.codec.decode(payload, handle.scale, handle.dtype)

    def nbytes(self, handle) -> int:
        return int(handle.nbytes)

    def wire_nbytes(self, value) -> int:
        if isinstance(value, codec_mod.EncodedPage):
            return int(value.nbytes)
        return self.codec.encoded_nbytes(value.shape, value.dtype)

    def holds(self, handle) -> bool:
        return (isinstance(handle, codec_mod.EncodedPage)
                and self.inner.holds(handle.payload))


def make_host_backend(device=None) -> MemoryBackend:
    """The best host-tier backend this platform supports: a memory-kind
    backend, or NumPy buffers on a CPU build that addresses no host kind.
    An accelerator without a host kind raises instead of hiding the host
    tier off its host memory."""
    caps = capabilities(device)
    if caps.host_kind is not None:
        return JaxHostBackend(device)
    if caps.platform != "cpu":
        raise RuntimeError(
            f"{caps.platform} device addresses no host memory kind "
            f"(has {caps.memory_kinds}); the host tier cannot live in its "
            "host memory")
    return NumpyHostBackend(device)


def make_backend(tier: str, device=None) -> MemoryBackend:
    if tier == DEVICE_TIER:
        return DeviceBackend(device)
    if tier == HOST_TIER:
        return make_host_backend(device)
    if tier == REMOTE_TIER:
        return NumpyHostBackend(device)
    raise ValueError(f"unknown tier {tier!r}")


def backend_for(spec, device=None) -> MemoryBackend:
    """Storage backend for one ``TierSpec`` (duck-typed on its fields —
    the spec type lives in ``pool.topology``; the dependency points this
    way so the topology module stays pure data)."""
    if spec.kind == "device":
        return DeviceBackend(device)
    if spec.kind == "host":
        return make_host_backend(device)
    if spec.kind == "numpy":
        return NumpyHostBackend(device)
    if spec.kind == "modeled":
        return ModeledTierBackend(
            device, read_bw=spec.read_bw, write_bw=spec.write_bw,
            read_latency_s=spec.read_latency_s,
            write_latency_s=spec.write_latency_s,
            name=f"modeled[{spec.name}]")
    raise ValueError(f"unknown tier kind {spec.kind!r}")
