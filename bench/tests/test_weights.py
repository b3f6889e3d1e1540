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


BF, F = "bfloat16", "float32"
#: every leaf of a layer and of the top at full size, as drawn since the
#: benchmark's first weights: (shape, dtype, initialiser, fan-in axis)
LEAVES = {
    "phi3-mini-3.8b": (
        {"attn_norm": ((3072,), F, "norm", 0),
         "wq": ((3072, 3072), BF, "fan_in", 0),
         "wk": ((3072, 3072), BF, "fan_in", 0),
         "wv": ((3072, 3072), BF, "fan_in", 0),
         "wo": ((3072, 3072), BF, "fan_in", 0),
         "ffn_norm": ((3072,), F, "norm", 0),
         "w_gate": ((3072, 8192), BF, "fan_in", 0),
         "w_up": ((3072, 8192), BF, "fan_in", 0),
         "w_down": ((8192, 3072), BF, "fan_in", 0)},
        {"embed": ((32064, 3072), BF, "embed", 0),
         "final_norm": ((3072,), F, "norm", 0),
         "lm_head": ((3072, 32064), BF, "embed", 0)}),
    "granite-moe-3b-a800m": (
        {"attn_norm": ((1536,), F, "norm", 0),
         "wq": ((1536, 1536), BF, "fan_in", 0),
         "wk": ((1536, 512), BF, "fan_in", 0),
         "wv": ((1536, 512), BF, "fan_in", 0),
         "wo": ((1536, 1536), BF, "fan_in", 0),
         "ffn_norm": ((1536,), F, "norm", 0),
         "router": ((1536, 40), F, "fan_in", 0),
         "w_gate": ((40, 1536, 512), BF, "fan_in", 1),
         "w_up": ((40, 1536, 512), BF, "fan_in", 1),
         "w_down": ((40, 512, 1536), BF, "fan_in", 1)},
        {"embed": ((49155, 1536), BF, "embed", 0),
         "final_norm": ((1536,), F, "norm", 0)}),
}


def _named(spec):
    return {name: (shape, jnp.dtype(dtype).name, init, axis)
            for name, (shape, dtype, init, axis) in spec.items()}


@pytest.mark.parametrize("name,cfg", [
    (name, conftest.load_config(name)) for name in sorted(LEAVES)] + [
    # Granite as published draws the held granite file's leaves: its
    # scalars and routing change no weight
    ("granite-moe-3b-a800m", conftest.granite_published())])
def test_leaves_at_full_size(name, cfg):
    layer, top = LEAVES[name]
    assert _named(W.layer_spec(cfg)) == layer
    assert _named(W.top_spec(cfg)) == top
    # the stacked draw, by shape alone
    shapes = jax.eval_shape(lambda k: W.stacked_weights(cfg, k),
                            W.seed_key(1))
    assert {k: v.shape for k, v in shapes["layers"].items()} == {
        k: (32,) + v[0] for k, v in layer.items()}
