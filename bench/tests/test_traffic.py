"""The generator: the same seed gives the same stream; every seed offers
the same sizes and arrivals, in another order unless the mix fixes it."""

import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from traffic.generate import generate

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
MIXES = sorted(p.stem for p in TRAFFIC.glob("*.json"))
BIG_SEED = 2 ** 31 + 12345


def _load(name):
    return json.loads((TRAFFIC / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_stream(mix):
    a = generate(_load(mix), BIG_SEED, 1000)
    b = generate(_load(mix), BIG_SEED, 1000)
    assert [(i.due_s, i.max_new_tokens) for i in a] == \
        [(i.due_s, i.max_new_tokens) for i in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_load(mix):
    t = _load(mix)
    a, b = generate(t, 1, 1000), generate(t, BIG_SEED, 1000)
    block = t["block"]
    for k in range(0, len(a), block):
        for field in (lambda i: len(i.prompt), lambda i: i.max_new_tokens):
            assert Counter(map(field, a[k:k + block])) == \
                Counter(map(field, b[k:k + block]))
    assert a[-1].due_s == pytest.approx(b[-1].due_s)
    same_order = [len(i.prompt) for i in a] == [len(i.prompt) for i in b]
    assert same_order == (t.get("order") == "fixed")


@pytest.mark.parametrize("order", ["seed", "fixed"])
def test_order_fixed_keeps_arrivals_and_sizes_across_seeds(order):
    t = dict(_load(MIXES[0]), order=order)
    a, b = generate(t, 1, 1000), generate(t, BIG_SEED, 1000)
    shape = [[(i.due_s, len(i.prompt), i.max_new_tokens) for i in s]
             for s in (a, b)]
    assert (shape[0] == shape[1]) == (order == "fixed")
    # the seed still draws the token ids
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_unknown_order_is_refused():
    with pytest.raises(ValueError, match="order"):
        generate(dict(_load(MIXES[0]), order="random"), 1, 1000)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_stay_in_range(mix):
    t = _load(mix)
    for it in generate(t, 7, 1000):
        for dist, n in ((t["prompt"], len(it.prompt)),
                        (t["output"], it.max_new_tokens)):
            lo = min(dist["values"]) if "values" in dist else dist["lo"]
            hi = max(dist["values"]) if "values" in dist else dist["hi"]
            assert lo <= n <= hi
            assert n % dist.get("grid", 1) == 0
