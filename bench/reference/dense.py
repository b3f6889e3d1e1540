"""Reference for the dense family (phi3-mini): pre-norm decoder layers of
causal GQA attention with rotary positions and a SwiGLU feed-forward,
final RMSNorm, untied or tied output head (arXiv:2404.14219). The
program's separate q/k/v and gate/up matrices are the published fused
ones split; the arithmetic is the same."""

from __future__ import annotations

from typing import Dict

import jax

from reference import core


def ffn(cfg: Dict, w: Dict, h: jax.Array, prompt_len: jax.Array,
        chunk: int, low: bool) -> jax.Array:
    g = core.mm("nsd,df->nsf", h, w["w_gate"], low)
    u = core.mm("nsd,df->nsf", h, w["w_up"], low)
    return core.mm("nsf,fd->nsd", jax.nn.silu(g) * u, w["w_down"], low)


def forward(cfg: Dict, seed: int, tokens, prompt_len, at, *, chunk: int,
            control: bool = False):
    return core.forward(cfg, ffn, seed, tokens, prompt_len, at,
                        chunk=chunk, control=control)


