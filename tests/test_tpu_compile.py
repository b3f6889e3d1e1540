"""Compile-only checks for one TPU v5e chip that is described, not attached.

The TPU compiler is installed even where no chip is. These tests compile
the serving path's attention kernels at phi3-mini-3.8b widths and its
full-width bfloat16 decode step for a described v5e, and read what the
compiler reports: a Mosaic kernel in each kernel's program (so it is not
interpreted), a decode step that fits the chip's 16 GB of HBM, and a
decode step whose layer loop reads the cache and the weights where they
lie, with no per-layer copy of either.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import REGISTRY
from repro.kernels.decode_attention import KERNEL_NAME, decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import paged_decode_attention_pallas
from repro.models.model import build_model

V5E_HBM_BYTES = 16 * 10**9
H, D, PAGE, BATCH, MAX_SEQ = 32, 96, 32, 4, 1024   # phi3-mini-3.8b serving
CHAT_BATCH, CHAT_SEQ = 8, 768        # the resident chat benchmark's shape


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compilation
    cache off: an entry written for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, chip):
    """(kernel, argument specs) at phi3 widths: prefill over 512 tokens,
    ring decode over the chat cell's stacked cache (and at Granite 3.0's
    24 query over 8 kv heads of 64), paged decode over 12 of 16 pages."""
    scale = D ** -0.5
    if name == "flash":
        qkv = [_spec((1, H, 512, D), chip)] * 3
        return (lambda q, k, v: flash_attention_pallas(
            q, k, v, scale=scale, interpret=False)), qkv
    if name in ("decode", "decode_gqa"):
        hq, hkv, hd = (H, H, D) if name == "decode" else (24, 8, 64)
        kv = _spec((32, CHAT_BATCH, CHAT_SEQ, hkv * hd), chip)
        return (lambda q, k, v, r, n: decode_attention_pallas(
            q, k, v, r, n, head_dim=hd, scale=hd ** -0.5,
            interpret=False)), [
            _spec((CHAT_BATCH, hq * hd), chip), kv, kv,
            _spec((), chip, jnp.int32), _spec((CHAT_BATCH,), chip, jnp.int32)]
    pages = _spec((16, BATCH, H, PAGE, D), chip)
    tail = _spec((BATCH, H, PAGE, D), chip)
    return (lambda q, kp, vp, t, kt, vt, n: paged_decode_attention_pallas(
        q, kp, vp, t, kt, vt, n, scale=scale, interpret=False)), [
        _spec((BATCH, H, D), chip), pages, pages,
        _spec((12,), chip, jnp.int32), tail, tail,
        _spec((), chip, jnp.int32)]


@pytest.mark.parametrize("name", ["flash", "decode", "decode_gqa",
                                  "paged"])
def test_attention_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_fits_one_v5e(one_chip):
    """phi3-mini-3.8b's bfloat16 decode step at published widths and full
    depth, batch 4 over a 1024-token cache, as the scheduler calls it."""
    model = build_model(REGISTRY["phi3-mini-3.8b"])
    on_chip = lambda s: _spec(s.shape, one_chip, s.dtype)
    params = jax.tree.map(on_chip, model.param_specs(jnp.bfloat16))
    cache = jax.tree.map(on_chip,
                         model.cache_specs(BATCH, MAX_SEQ, jnp.bfloat16))
    token = _spec((BATCH, 1), one_chip, jnp.int32)
    pos = _spec((BATCH,), one_chip, jnp.int32)
    compiled = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, token, pos).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert mem.argument_size_in_bytes > 7 * 10**9   # 3.8B bf16 params
    assert used < V5E_HBM_BYTES, used


# --- reading the optimized HLO text --------------------------------------

_DTYPE_BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1}
_INSTR = re.compile(r"(?:ROOT )?%([\w.\-]+) = (\S+) ([\w\-]+)\((.*)")


def _computations(hlo):
    """{computation name: [instruction lines]}; the entry also as ""."""
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(2), [])
            if head.group(1):
                comps[""] = cur
        elif line == "}":
            cur = None
        elif cur is not None and line.strip():
            cur.append(line.strip())
    return comps


def _ops(comps, name):
    """(opcode, instruction name, logical output bytes) per instruction
    of a computation; a fusion's opcode is its fused root's, so a fused
    slice or relayout reads as ``dynamic-slice`` or ``copy``."""
    for line in comps[name]:
        m = _INSTR.match(line)
        if not m:
            continue
        iname, shape, op, rest = m.groups()
        called = re.search(r"calls=%([\w.\-]+)", rest)
        if op == "fusion" and called:
            root = [x for x in comps[called.group(1)] if x.startswith("ROOT")]
            op = _INSTR.match(root[0]).group(3)
        dims = re.match(r"(\w+)\[([\d,]*)\]", shape)
        nbytes = 0
        if dims and dims.group(1) in _DTYPE_BYTES:
            nbytes = _DTYPE_BYTES[dims.group(1)] * math.prod(
                int(d) for d in dims.group(2).split(",") if d)
        yield op, iname, nbytes


def test_decode_layer_loop_reads_cache_and_weights_in_place(one_chip):
    """phi3-mini-3.8b's bfloat16 decode step at the chat benchmark's shape
    (batch 8, 768-slot cache, per-row positions, cache donated). In the
    layer scan's loop body nothing as large as a projection weight (3072²
    bf16, 18.9 MB) or a layer's K or V (37.7 MB) is sliced out or relaid
    out, and the entry copies no cache-sized buffer: each step reads the
    cache and the weights once, in their stored layout. The layer's
    attention read is one call of the ring decode kernel, which takes the
    whole stack, and nothing else reads the cache's slots."""
    model = build_model(REGISTRY["phi3-mini-3.8b"])
    on_chip = lambda s: _spec(s.shape, one_chip, s.dtype)
    params = jax.tree.map(on_chip, model.param_specs(jnp.bfloat16))
    cache = jax.tree.map(on_chip, model.cache_specs(
        CHAT_BATCH, CHAT_SEQ, jnp.bfloat16))
    token = _spec((CHAT_BATCH, 1), one_chip, jnp.int32)
    pos = _spec((CHAT_BATCH,), one_chip, jnp.int32)
    hlo = jax.jit(model.decode_step, donate_argnums=(1,)).lower(
        params, cache, token, pos).compile().as_text()
    comps = _computations(hlo)
    bodies = re.findall(r"body=%([\w.\-]+)", "\n".join(comps[""]))
    assert len(bodies) == 1, bodies          # one segment, one layer scan
    weight = 3072 * 3072 * 2
    moved = [(op, name, n) for op, name, n in _ops(comps, bodies[0])
             if op in ("copy", "transpose", "dynamic-slice") and n >= weight]
    assert not moved, moved
    calls = [line for line in comps[bodies[0]] if " custom-call(" in line]
    assert len(calls) == 1 and f"%{KERNEL_NAME}" in calls[0], calls
    # the jnp read's two whole-ring einsums are gone
    assert not [line for line in hlo.splitlines()
                if "bcx,bxh->bhc" in line or "bhc,bcx->bhx" in line]
    leaf = CHAT_BATCH * CHAT_SEQ * 3072 * 2 * 32
    entry_copies = [(op, name, n) for op, name, n in _ops(comps, "")
                    if op in ("copy", "transpose") and n >= leaf]
    assert not entry_copies, entry_copies
