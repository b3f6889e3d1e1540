"""Drawing every layer at once gives the values of drawing each alone:
the reference's layer-by-layer draw sees the program's weights. The
bf16 matrices agree bit for bit; a float32 leaf (norm weights, router)
may differ in its last bit where XLA fuses ``1 + 0.1 * x`` differently
in the two programs."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import conftest
import weights as W

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


@pytest.mark.parametrize("name", sorted(conftest.TINY_CONFIG))
def test_stacked_equals_layer_by_layer(name):
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    cfg.update(conftest.TINY_CONFIG[name])
    key = W.seed_key(2 ** 31 + 99)
    stacked = jax.jit(lambda k: W.stacked_weights(cfg, k))(key)
    one = jax.jit(lambda k, l: W.layer_weights(cfg, k, l))
    for layer in range(cfg["num_hidden_layers"]):
        for leaf, value in one(key, layer).items():
            got = np.asarray(stacked["layers"][leaf][layer], np.float32)
            want = np.asarray(value, np.float32)
            if value.dtype == jnp.bfloat16:
                np.testing.assert_array_equal(got, want)
            else:
                np.testing.assert_allclose(got, want, rtol=2e-7, atol=0)
