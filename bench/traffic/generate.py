"""One traffic generator for every cell, driven by the workload file's
``traffic`` object.

Every seed offers the same load: lengths and inter-arrival gaps are fixed
quantiles of their distributions, laid out in blocks of ``block``
requests, and the seed only permutes each block and draws the token ids.
So any prefix of the stream holds the same mix of sizes whatever the seed,
and two seeds differ in order and content, not in the work they ask for.
With ``"order": "fixed"`` the order, too, is the same for every seed, and
the seed draws only the token ids.

Traffic object keys:

- ``arrivals``: ``"backlog"`` (every request due at time 0, so a slot is
  refilled as soon as a row retires) or ``"poisson"`` (open loop, gaps of
  an exponential distribution with ``rate_per_s``).
- ``count``: requests in the stream.
- ``block``: requests per stratification block.
- ``order``: ``"seed"`` (the default: the seed permutes each block) or
  ``"fixed"`` (one permutation of each block, the same for every seed).
- ``prompt`` / ``output``: a length distribution:
  ``{"dist": "choice", "values": [...]}`` (equally likely),
  ``{"dist": "uniform", "lo", "hi"}`` or ``{"dist": "lognormal",
  "median", "sigma", "lo", "hi"}``; the last two optionally with
  ``"grid"``: lengths are rounded to the nearest multiple of it, then
  clipped to [lo, hi].
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    """One request of the stream: due ``due_s`` seconds after the window
    opens, with its prompt token ids and output length."""
    index: int
    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def _quantile(dist: Dict, u: float) -> float:
    kind = dist["dist"]
    if kind == "uniform":
        return dist["lo"] + u * (dist["hi"] - dist["lo"])
    if kind == "lognormal":
        z = NormalDist().inv_cdf(u)
        return dist["median"] * math.exp(dist["sigma"] * z)
    raise ValueError(f"unknown length distribution {kind!r}")


def _length(dist: Dict, u: float) -> int:
    if dist["dist"] == "choice":
        return int(dist["values"][int(u * len(dist["values"]))])
    grid = dist.get("grid", 1)
    n = int(round(_quantile(dist, u) / grid)) * grid
    return min(max(n, dist["lo"]), dist["hi"])


def block_quantiles(block: int) -> List[float]:
    """The ``block`` mid-point quantiles every block of the stream uses."""
    return [(i + 0.5) / block for i in range(block)]


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def generate(traffic: Dict, seed: int, vocab_size: int) -> List[Item]:
    """The cell's request stream for ``seed`` (see module doc)."""
    count, block = int(traffic["count"]), int(traffic["block"])
    if count % block:
        raise ValueError(f"count {count} is not a multiple of block {block}")
    order = traffic.get("order", "seed")
    if order not in ("seed", "fixed"):
        raise ValueError(f"unknown order {order!r}")
    qs = block_quantiles(block)
    rng = _rng(seed if order == "seed" else 0, 0)
    prompts, outputs, gaps = [], [], []
    for _ in range(count // block):
        # three independent permutations: prompt and output lengths are
        # not paired the same way in every block
        prompts += [_length(traffic["prompt"], qs[i])
                    for i in rng.permutation(block)]
        outputs += [_length(traffic["output"], qs[i])
                    for i in rng.permutation(block)]
        gaps += [-math.log(1.0 - qs[i]) for i in rng.permutation(block)]
    if traffic["arrivals"] == "backlog":
        due = [0.0] * count
    elif traffic["arrivals"] == "poisson":
        rate = float(traffic["rate_per_s"])
        # every seed's last request is due at the same time: the gaps
        # are the same set in another order
        due = list(np.cumsum(np.asarray(gaps) / rate))
    else:
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    tok_rng = _rng(seed, 1)
    return [Item(i, float(due[i]),
                 tok_rng.integers(0, vocab_size, prompts[i], dtype=np.int32),
                 outputs[i])
            for i in range(count)]
