"""Top-k token-choice Mixture-of-Experts with sort-based capacity dispatch.

GShard-style one-hot dispatch materializes an (N, E, C) tensor — infeasible
at our batch sizes. We instead sort (token, expert) assignments by expert id
and scatter into a dense (E, C, d) buffer, run batched expert matmuls, and
scatter back. Tokens beyond an expert's capacity are dropped (their combine
weight contribution is zero), matching capacity-factor MoE semantics
[arXiv:2401.04088, Switch Transformers].

Load-balancing auxiliary loss: E * sum_e(fraction_e * router_prob_e).
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.common import dense_init
from repro.sharding.rules import constrain


def init_moe_params(cfg: ModelConfig, key, dtype) -> Dict:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (d, e), jnp.float32),
        "w_gate": dense_init(k2, (e, d, f), dtype, in_axis=1),
        "w_up": dense_init(k3, (e, d, f), dtype, in_axis=1),
        "w_down": dense_init(k4, (e, f, d), dtype, in_axis=1),
    }


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    m = cfg.moe
    cap = int(math.ceil(n_tokens * m.top_k / m.n_experts * m.capacity_factor))
    # pad to an MXU-friendly multiple
    return max(8, -(-cap // 8) * 8)


def moe_ffn(cfg: ModelConfig, p: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (out, aux_loss).

    Under an active device mesh the dispatch runs inside ``shard_map`` over
    the data axes — sorting and capacity are *per data shard* (a global
    argsort would force GSPMD to replicate the full token buffer), and the
    tensor-parallel expert matmuls psum their partial products over the
    model axes. Without a mesh (unit tests) it runs as plain XLA."""
    from repro.sharding import rules as R
    mesh = R.current_mesh()
    rules = R.current_rules()
    if mesh is not None and rules is not None:
        return _moe_ffn_sharded(cfg, p, x, mesh, rules)
    return _moe_ffn_local(cfg, p, x)


def _moe_ffn_sharded(cfg: ModelConfig, p: Dict, x: jax.Array, mesh, rules):
    from jax.sharding import PartitionSpec as P

    dp = rules.get("batch") or ()
    dp = (dp,) if isinstance(dp, str) else tuple(dp)
    tp = rules.get("expert_mlp") or ()
    tp = (tp,) if isinstance(tp, str) else tuple(tp)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = tuple(a for a in dp if axis_sizes.get(a, 1) > 1 and x.shape[0] % axis_sizes[a] == 0)
    f = cfg.moe.d_ff_expert
    tp = tuple(a for a in tp if axis_sizes.get(a, 1) > 1 and f % axis_sizes[a] == 0)

    def local(xb, router, wg, wu, wd):
        pl = {"router": router, "w_gate": wg, "w_up": wu, "w_down": wd}
        out, aux = _moe_ffn_local(cfg, pl, xb, psum_axes=tp, manual=True)
        if dp:
            aux = jax.lax.pmean(aux, dp)
        return out, aux

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(dp or None), P(), P(None, None, tp or None),
                  P(None, None, tp or None), P(None, tp or None, None)),
        out_specs=(P(dp or None), P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])


def _moe_ffn_local(cfg: ModelConfig, p: Dict, x: jax.Array,
                   psum_axes: Tuple[str, ...] = (),
                   manual: bool = False) -> Tuple[jax.Array, jax.Array]:
    m = cfg.moe
    b, s, d = x.shape
    n = b * s
    e, k = m.n_experts, m.top_k
    cap = expert_capacity(n, cfg)
    xf = x.reshape(n, d)

    logits = (xf.astype(jnp.float32) @ p["router"])          # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)                    # (N, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)    # renormalize

    # auxiliary load-balance loss
    frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1), axis=0)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0)) * m.router_aux_weight

    # ---- sort-based dispatch ------------------------------------------------
    flat_e = top_e.reshape(n * k)                             # (NK,)
    flat_w = top_w.reshape(n * k)
    flat_tok = jnp.repeat(jnp.arange(n), k)                   # token id per assignment
    order = jnp.argsort(flat_e, stable=True)                  # group by expert
    se, sw, st = flat_e[order], flat_w[order], flat_tok[order]
    # rank within expert group = index - first index of that expert
    idx = jnp.arange(n * k)
    # first occurrence index per expert via cumulative counts
    counts = jnp.bincount(se, length=e)                       # (E,)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = idx - starts[se]
    keep = rank < cap
    dest = jnp.where(keep, se * cap + rank, e * cap)          # sentinel row e*cap

    buf = jnp.zeros((e * cap + 1, d), x.dtype)
    buf = buf.at[dest].set(xf[st], mode="drop")
    ein = buf[: e * cap].reshape(e, cap, d)
    if not manual:  # sharding constraints are illegal under manual axes
        ein = constrain(ein, (None, None, "embed_act"))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein, p["w_gate"]))
    h = h * jnp.einsum("ecd,edf->ecf", ein, p["w_up"])
    if not manual:
        h = constrain(h, ("experts", None, "expert_mlp"))
    eout = jnp.einsum("ecf,efd->ecd", h, p["w_down"])         # (E, C, D)

    eflat = jnp.concatenate(
        [eout.reshape(e * cap, d), jnp.zeros((1, d), eout.dtype)], axis=0)
    gathered = eflat[dest] * sw[:, None].astype(eout.dtype)   # (NK, D)
    out = jnp.zeros((n, d), x.dtype).at[st].add(gathered.astype(x.dtype))
    if psum_axes:
        # tensor-parallel experts: each shard computed f/|tp| of the hidden
        # dim, so the combined output is a partial sum — reduce it (combine
        # is linear, so psum after the scatter touches n·d, not E·C·d)
        out = jax.lax.psum(out, psum_axes)
    return out.reshape(b, s, d), aux
