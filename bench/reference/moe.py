"""Reference for the sparse-expert family (granite-3.0 MoE): the dense
family's attention, with a token-choice mixture of experts in place of
the feed-forward. Each token takes the experts of its top
``num_experts_per_tok`` router logits, weighted by the softmax over those
logits (the published ``GraniteMoeTopKGating``), computed here as the
softmax over all experts renormalised over the top ones, which is the
same; each expert is a SwiGLU of width ``intermediate_size``.

Routing is dropless, as published, unless the configuration file states
``capacity_factor``: then it follows the program's capacity rule. The
program prefills a prompt in chunks of ``chunk`` tokens, and in each
chunk an expert keeps at most ``capacity`` of the tokens routed to it,
earliest first; a dropped token gets nothing from that expert and its
other weights are not renormalised. Capacity is ``max(8, ceil(chunk *
top_k / experts * capacity_factor) rounded up to 8)``. Decode positions
are never dropped, since a decode batch of B rows gives each expert at
most B tokens and the served batches give every expert at least that
many slots."""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from reference import core

def capacity(cfg: Dict, tokens: int) -> int:
    cap = math.ceil(tokens * cfg["num_experts_per_tok"]
                    / cfg["num_local_experts"] * cfg["capacity_factor"])
    return max(8, -(-cap // 8) * 8)


def _kept(cfg: Dict, routed: jax.Array, prompt_len: jax.Array,
          chunk: int) -> jax.Array:
    """Where each token keeps its place at the experts it is routed to
    under the capacity rule (n, s, e); decode positions keep theirs."""
    n, s, e = routed.shape
    # rank of each token among the earlier tokens of its chunk routed to
    # the same expert
    pad = -s % chunk
    r = jnp.pad(routed, ((0, 0), (0, pad), (0, 0)))
    r = r.reshape(n, -1, chunk, e)
    rank = (jnp.cumsum(r, 2) - r).reshape(n, -1, e)[:, :s]
    in_prompt = jnp.arange(s)[None, :] < prompt_len[:, None]
    return (rank < capacity(cfg, chunk)) | ~in_prompt[..., None]


def ffn(cfg: Dict, w: Dict, h: jax.Array, prompt_len: jax.Array,
        chunk: int, low: bool) -> jax.Array:
    e, k = cfg["num_local_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(core.mm("nsd,de->nse", h, w["router"], low), -1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    pick = jax.nn.one_hot(top_e, e, dtype=jnp.float32)        # (n,s,k,e)
    gate = jnp.sum(pick * top_w[..., None], 2)                # (n,s,e)
    if "capacity_factor" in cfg:
        gate = gate * _kept(cfg, jnp.sum(pick, 2), prompt_len, chunk)
    g = core.mm("nsd,edf->nsef", h, w["w_gate"], low)
    u = core.mm("nsd,edf->nsef", h, w["w_up"], low)
    # the gate is linear in each expert's output, so it can scale the
    # hidden activations before the down projection sums over experts
    act = jax.nn.silu(g) * u * gate[..., None]
    return core.mm("nsef,efd->nsd", act, w["w_down"], low)


def forward(cfg: Dict, seed: int, tokens, prompt_len, at, *, chunk: int,
            control: bool = False):
    return core.forward(cfg, ffn, seed, tokens, prompt_len, at,
                        chunk=chunk, control=control)
