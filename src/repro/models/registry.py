"""Model facade: uniform init / loss / prefill / decode API per architecture,
plus `input_specs` (ShapeDtypeStruct stand-ins) for the multi-pod dry-run.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig

from . import transformer as T

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    # ----------------------------------------------------------- params
    def init(self, rng) -> dict:
        return T.init_lm(rng, self.cfg)

    def init_shapes(self) -> dict:
        """Abstract params (no allocation) — for the dry-run."""
        return jax.eval_shape(lambda r: T.init_lm(r, self.cfg), jax.random.PRNGKey(0))

    def param_count(self) -> int:
        return sum(
            int(jnp.prod(jnp.array(l.shape))) if l.shape else 1
            for l in jax.tree.leaves(self.init_shapes())
        )

    # ------------------------------------------------------------ train
    def forward_logits(self, params: dict, batch: dict[str, Array]) -> T.ForwardOut:
        """Family-dispatched forward: logits for train/prefill batches."""
        cfg = self.cfg
        tokens = batch["tokens"]
        if cfg.family == "audio":
            enc_out = T.encode(params, cfg, batch["frames"])
            cache = {**T.init_cache(cfg, tokens.shape[0], tokens.shape[1]), "enc_out": enc_out}
            out = T.forward(params, cfg, tokens, cache=cache)
        else:
            prefix = batch.get("patches")
            out = T.forward(params, cfg, tokens, prefix_embeds=prefix)
        logits = out.logits
        if cfg.family == "vlm" and "patches" in batch:
            logits = logits[:, batch["patches"].shape[1]:]
        return out._replace(logits=logits)

    def loss(self, params: dict, batch: dict[str, Array]) -> tuple[Array, dict]:
        labels = batch["labels"]
        out = self.forward_logits(params, batch)
        logits = out.logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        loss = nll.mean()
        total = loss + 0.01 * out.aux_loss + 0.001 * out.z_loss
        return total, {"nll": loss, "aux": out.aux_loss, "z": out.z_loss}

    # ------------------------------------------------------------ serve
    def init_cache(self, batch: int, max_seq: int) -> dict:
        return T.init_cache(self.cfg, batch, max_seq)

    def prefill(self, params: dict, tokens: Array, cache: dict,
                extra: Optional[dict] = None) -> tuple[Array, dict]:
        cfg = self.cfg
        if cfg.family == "audio":
            cache = dict(cache)
            cache["enc_out"] = T.encode(params, cfg, extra["frames"])
        prefix = extra.get("patches") if (extra and cfg.family == "vlm") else None
        out = T.forward(params, cfg, tokens, cache=cache, prefix_embeds=prefix)
        return out.logits[:, -1], out.cache

    def decode_step(self, params: dict, token: Array, cache: dict) -> tuple[Array, dict]:
        """token [B] -> (logits [B, V], cache)."""
        out = T.forward(params, self.cfg, token[:, None], cache=cache)
        return out.logits[:, 0], out.cache

    # ---------------------------------------------------------- dry-run
    def input_specs(self, shape: ShapeConfig, dp_shards: int = 1) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this cell.

        train  : {tokens, labels [B,S]} (+frontend stubs)
        prefill: {tokens [B,S]} (+frontend stubs)
        decode : {token [B], cache(seq_len)} — one new token against a full cache
        """
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        sds = jax.ShapeDtypeStruct

        def frontend(d):
            if cfg.frontend == "audio_frames":
                d["frames"] = sds((B, cfg.encoder_seq, cfg.d_model), jnp.float32)
            elif cfg.frontend == "vision_patches":
                d["patches"] = sds((B, cfg.frontend_tokens, cfg.d_model), jnp.float32)
            return d

        if shape.kind == "train":
            return frontend({"tokens": sds((B, S), i32), "labels": sds((B, S), i32)})
        if shape.kind == "prefill":
            return frontend({"tokens": sds((B, S), i32)})
        # decode: one token with a cache of length S
        cache = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
        return {"token": sds((B,), i32), "cache": cache}


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
