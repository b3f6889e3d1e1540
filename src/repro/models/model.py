"""Public model facade: build once from a ModelConfig, then call
``loss`` / ``forward`` / ``prefill`` / ``decode_step`` / ``input_specs``.

``input_specs`` returns ShapeDtypeStruct stand-ins for every model input of
a workload shape (the dry-run pattern: weak-type-correct, shardable, no
device allocation). Modality frontends are stubs: audio supplies
``enc_embeds`` (precomputed conv/mel frames), vision supplies aligned
``vision_embeds`` + ``vision_mask`` and M-RoPE ``positions``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.models import transformer as tfm


def _xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean cross-entropy in f32. logits (B,S,V), targets (B,S) int32."""
    logz = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logz, targets[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    swa_override: Optional[int] = None

    # -- init ---------------------------------------------------------------
    def init(self, key, dtype=jnp.float32) -> Dict:
        return tfm.init_params(self.cfg, key, dtype)

    def init_cache(self, batch: int, max_seq: int, dtype=jnp.float32) -> Dict:
        return tfm.init_cache(self.cfg, batch, max_seq, dtype,
                              swa_override=self.swa_override)

    # -- training -----------------------------------------------------------
    def forward(self, params: Dict, batch: Dict, remat_policy=None) -> Tuple[jax.Array, jax.Array]:
        return tfm.forward(
            self.cfg, params, batch["tokens"],
            positions=batch.get("positions"),
            enc_embeds=batch.get("enc_embeds"),
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"),
            swa_override=self.swa_override,
            remat_policy=remat_policy,
        )

    def loss(self, params: Dict, batch: Dict, remat_policy=None) -> jax.Array:
        logits, aux = self.forward(params, batch, remat_policy=remat_policy)
        return _xent(logits, batch["targets"]) + aux

    # -- inference ----------------------------------------------------------
    def prefill(self, params: Dict, batch: Dict, cache: Dict) -> Tuple[jax.Array, Dict]:
        return tfm.prefill(
            self.cfg, params, batch["tokens"], cache,
            positions=batch.get("positions"),
            enc_embeds=batch.get("enc_embeds"),
            vision_embeds=batch.get("vision_embeds"),
            vision_mask=batch.get("vision_mask"),
            swa_override=self.swa_override,
        )

    def prefill_chunk(self, params: Dict, batch: Dict, offset: jax.Array,
                      valid_len: jax.Array, cache: Dict) -> Tuple[jax.Array, Dict]:
        """Cache-aware prefill of one prompt chunk at a global position
        offset (see ``transformer.prefill_chunk``). Only the first
        ``valid_len`` tokens of the chunk are real; logits are the last
        valid token's. Requires ``supports_chunked_prefill``."""
        return tfm.prefill_chunk(
            self.cfg, params, batch["tokens"], offset, valid_len, cache,
            swa_override=self.swa_override)

    def supports_chunked_prefill(self) -> bool:
        """Chunked prefill resumes from a per-position KV cache; recurrent
        (mamba2) mixers, cross-attention layers, and encoder frontends have
        state the chunk path cannot yet carry."""
        return self.cfg.encoder is None and all(
            spec.mixer in ("attn", "mla") and not spec.cross_attn
            for seg in self.cfg.segments for spec in seg.pattern)

    def decode_step(self, params: Dict, cache: Dict, token: jax.Array,
                    pos: jax.Array) -> Tuple[jax.Array, Dict]:
        return tfm.decode_step(self.cfg, params, cache, token, pos,
                               swa_override=self.swa_override)

    # -- dry-run specs --------------------------------------------------------
    def param_specs(self, dtype=jnp.bfloat16) -> Any:
        return jax.eval_shape(lambda k: self.init(k, dtype),
                              jax.random.key(0))

    def cache_specs(self, batch: int, max_seq: int, dtype=jnp.bfloat16) -> Any:
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_seq, dtype))

    def input_specs(self, shape: InputShape, dtype=jnp.bfloat16) -> Dict:
        """ShapeDtypeStruct stand-ins for the workload batch."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        sds = jax.ShapeDtypeStruct
        if shape.kind == "train":
            batch = {
                "tokens": sds((b, s), jnp.int32),
                "targets": sds((b, s), jnp.int32),
            }
            self._add_frontend_specs(batch, b, s, dtype)
            return batch
        if shape.kind == "prefill":
            batch = {"tokens": sds((b, s), jnp.int32)}
            self._add_frontend_specs(batch, b, s, dtype)
            return batch
        if shape.kind == "decode":
            return {
                "token": sds((b, 1), jnp.int32),
                "pos": sds((), jnp.int32),
            }
        raise ValueError(shape.kind)

    def _add_frontend_specs(self, batch: Dict, b: int, s: int, dtype) -> None:
        cfg = self.cfg
        sds = jax.ShapeDtypeStruct
        if cfg.frontend == "audio":
            batch["enc_embeds"] = sds((b, cfg.encoder.n_frames, cfg.d_model), dtype)
        elif cfg.frontend == "vision":
            batch["vision_embeds"] = sds((b, s, cfg.d_model), dtype)
            batch["vision_mask"] = sds((b, s), jnp.bool_)
            batch["positions"] = sds((3, b, s), jnp.int32)


def build_model(cfg: ModelConfig, shape: Optional[InputShape] = None) -> Model:
    """Build a Model; enables the documented sliding-window variant when the
    workload is long_500k and the arch is full-attention (DESIGN.md §5)."""
    swa = None
    if shape is not None and shape.name == "long_500k" and cfg.long_context == "swa-variant":
        swa = cfg.swa_variant_window
    return Model(cfg=cfg, swa_override=swa)


# re-export for repro.models.__init__
init_params = tfm.init_params
init_cache = tfm.init_cache
