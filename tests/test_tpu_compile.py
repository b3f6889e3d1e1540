"""Compile-only checks for one TPU v5e chip that is described, not attached.

The TPU compiler is installed even where no chip is. These tests compile
the serving path's attention kernels at phi3-mini-3.8b widths and its
full-width bfloat16 decode step for a described v5e, and read what the
compiler reports: a Mosaic kernel in each kernel's program (so it is not
interpreted) and a decode step that fits the chip's 16 GB of HBM.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import REGISTRY
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.paged_attention import (
    decode_attention_pallas,
    paged_decode_attention_pallas,
)
from repro.models.model import build_model

V5E_HBM_BYTES = 16 * 10**9
H, D, PAGE, BATCH, MAX_SEQ = 32, 96, 32, 4, 1024   # phi3-mini-3.8b serving


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
    ring decode over a full cache, paged decode over 12 of 16 pages."""
    scale = D ** -0.5
    if name == "flash":
        qkv = [_spec((1, H, 512, D), chip)] * 3
        return (lambda q, k, v: flash_attention_pallas(
            q, k, v, scale=scale, interpret=False)), qkv
    if name == "decode":
        kv = _spec((BATCH, H, MAX_SEQ, D), chip)
        return (lambda q, k, v, pos: decode_attention_pallas(
            q, k, v, pos, scale=scale, interpret=False)), [
            _spec((BATCH, H, D), chip), kv, kv,
            _spec((), chip, jnp.int32)]
    pages = _spec((16, BATCH, H, PAGE, D), chip)
    tail = _spec((BATCH, H, PAGE, D), chip)
    return (lambda q, kp, vp, t, kt, vt, n: paged_decode_attention_pallas(
        q, kp, vp, t, kt, vt, n, scale=scale, interpret=False)), [
        _spec((BATCH, H, D), chip), pages, pages,
        _spec((12,), chip, jnp.int32), tail, tail,
        _spec((), chip, jnp.int32)]


@pytest.mark.parametrize("name", ["flash", "decode", "paged"])
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
