"""Quantized KV page codecs — a pool-layer concern (ITME's tiered-memory
compression argument, PAPERS.md).

A codec turns a full-precision KV page into a compact on-wire payload plus
a per-page scale, so every transfer below the configured tier boundary
(device→host puts, host→remote spills, and the fetches back) moves 2–4×
fewer bytes. Encoding happens exactly once per put — ``pool.backend``
wraps the storage backends of the tiers below the boundary in a
``CodecBackend`` that encodes on ``put`` and decodes on ``get``; a spill
between two encoded tiers moves the *payload* untouched (no
decode/re-encode round trip, and no extra quantization error).

Codecs:

- ``none`` — identity (no wrapping happens; pages move full precision);
- ``int8`` — symmetric per-page absmax quantization: ``scale =
  absmax/127``, payload ``round(x/scale)`` clipped to [-127, 127]. The
  worst-case round-trip error is ``scale/2`` per element — the hard
  numeric bound the test gate asserts;
- ``fp8``  — ``float8_e4m3fn`` payload with a per-page scale mapping the
  page's absmax onto the format's max normal (448), so the full dynamic
  range is spent on the page's actual values. Relative error is bounded
  by the format's epsilon (2^-3) plus the scale rounding.

Scales are kept as host floats riding inside the handle (4 bytes per page
against a multi-KB payload — charged in the on-wire byte accounting, but
negligible); payloads are stored through the wrapped tier's own backend,
so a modeled tier's sleep-throttle and the transfer telemetry both see
the *encoded* byte counts.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

CODECS = ("none", "int8", "fp8")

#: float8_e4m3fn max normal — the target of the per-page scale
_FP8_MAX = 448.0


@dataclasses.dataclass
class EncodedPage:
    """One encoded page: the codec's opaque handle.

    ``payload`` is whatever the wrapped tier's backend returned for the
    quantized bytes (jax host array, NumPy buffer, …); ``nbytes`` is the
    on-wire size (payload + scale) that every pool/transfer counter and
    the modeled-tier throttle charge."""

    codec: str
    payload: Any
    scale: float
    dtype: str            # decoded dtype name
    shape: Tuple[int, ...]
    nbytes: int


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def _enc_int8(x, out_dtype=jnp.int8):
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127.0, 127.0)
    return q.astype(out_dtype), scale


@functools.partial(jax.jit, static_argnames=("dtype",))
def _dec_int8(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


@jax.jit
def _enc_fp8(x):
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)))
    scale = jnp.where(absmax > 0, absmax / _FP8_MAX, 1.0)
    return (x.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn), scale


@functools.partial(jax.jit, static_argnames=("dtype",))
def _dec_fp8(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


class KVCodec:
    """One quantization scheme: device array ↔ (1-byte payload, scale)."""

    name: str = "abstract"
    payload_itemsize: int = 1

    def encode(self, value) -> Tuple[jax.Array, float]:
        raise NotImplementedError

    def decode(self, payload, scale: float, dtype: str) -> jax.Array:
        raise NotImplementedError

    def ratio(self, itemsize: int) -> float:
        """On-wire bytes per decoded byte (< 1 compresses). The per-page
        scale is excluded — 4 bytes against a whole page — so capacity
        conversions stay simple; the exact per-page figure lives in
        ``encoded_nbytes``."""
        return self.payload_itemsize / float(itemsize)

    def encoded_nbytes(self, shape, dtype) -> int:
        """Exact on-wire size of one encoded page (payload + scale)."""
        n = 1
        for d in shape:
            n *= int(d)
        return n * self.payload_itemsize + 4

    def __repr__(self) -> str:
        return f"KVCodec({self.name})"


class Int8Codec(KVCodec):
    name = "int8"

    def encode(self, value):
        q, scale = _enc_int8(jnp.asarray(value))
        return q, float(scale)

    def decode(self, payload, scale, dtype):
        return _dec_int8(jnp.asarray(payload), jnp.float32(scale),
                         jnp.dtype(dtype))


class Fp8Codec(KVCodec):
    name = "fp8"

    def encode(self, value):
        q, scale = _enc_fp8(jnp.asarray(value))
        return q, float(scale)

    def decode(self, payload, scale, dtype):
        return _dec_fp8(jnp.asarray(payload), jnp.float32(scale),
                        jnp.dtype(dtype))


def make_codec(name: Optional[str]) -> Optional[KVCodec]:
    """Codec instance by name; ``None``/``"none"`` → no codec (identity
    pages, no backend wrapping)."""
    if name is None or name == "none":
        return None
    if name == "int8":
        return Int8Codec()
    if name == "fp8":
        return Fp8Codec()
    raise ValueError(f"unknown KV codec {name!r}; have {CODECS}")


def roundtrip_bound(codec: KVCodec, absmax: float) -> float:
    """Hard per-element round-trip error bound for a page with the given
    absmax — what the codec test gate asserts against.

    int8: half a quantization step (``scale/2`` = absmax/254).
    fp8 (e4m3): relative error ≤ 2^-4 of the element after scaling, so
    ``absmax * 2^-4`` bounds any element (coarse but hard)."""
    if codec.name == "int8":
        return absmax / 254.0 + 1e-7
    if codec.name == "fp8":
        return absmax / 16.0 + 1e-7
    raise ValueError(f"no round-trip bound for codec {codec.name!r}")
