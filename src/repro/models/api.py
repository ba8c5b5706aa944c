"""Unified model API: one `Model` facade per architecture family.

Everything downstream (runtime steps, dry-run, examples, payload tasks)
talks to this interface only:

    m = build_model(cfg)
    m.specs()                         -> ParamSpec pytree
    m.loss(params, batch)             -> scalar (train objective)
    m.loss_and_stats(params, batch)   -> (scalar, dict of step statistics:
                                         an expert model's ``expert_tokens``)
    m.forward(params, batch)          -> (logits, aux)
    m.cache_specs(batch, s_max)       -> ParamSpec pytree (decode state)
    m.decode_step(params, cache, tokens, pos) -> (logits [B, V], cache)
    m.input_specs(shape)              -> ShapeDtypeStruct batch stand-ins
    m.serving_params(params)          -> params for forward / decode_step,
                                         weights pre-cast to COMPUTE_DTYPE
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from . import rwkv6 as rwkv_model
from . import ssm as ssm_model
from . import transformer as tf_model
from . import whisper as whisper_model
from .config import ModelConfig
from .layers import COMPUTE_DTYPE


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    _specs: Callable[[ModelConfig], Any]
    _loss: Callable
    _forward: Callable
    _cache_specs: Callable | None
    _decode: Callable | None
    _serving: Callable | None = None
    _loss_stats: Callable | None = None

    def specs(self):
        return self._specs(self.cfg)

    def loss(self, params, batch):
        return self._loss(params, batch, self.cfg)

    def loss_and_stats(self, params, batch):
        """(loss, stats); a family that keeps none gives ``{}``."""
        if self._loss_stats is None:
            return self.loss(params, batch), {}
        return self._loss_stats(params, batch, self.cfg)

    def forward(self, params, batch, **kw):
        return self._forward(params, batch, self.cfg, **kw)

    def cache_specs(self, batch: int, s_max: int):
        if self._cache_specs is None:
            raise ValueError(f"{self.cfg.name} has no decode path")
        return self._cache_specs(self.cfg, batch, s_max)

    def decode_step(self, params, cache, tokens, pos):
        return self._decode(params, cache, tokens, pos, self.cfg)

    def serving_params(self, params):
        """``params`` with the leaves that ``forward`` and ``decode_step``
        cast to COMPUTE_DTYPE at every use cast once, for a caller that
        runs many steps on one version of the weights; same outputs.  A
        family that declares no such leaves gets ``params`` back."""
        if self._serving is None:
            return params
        return self._serving(params, self.cfg)

    # -- batch stand-ins -----------------------------------------------------
    def input_specs(self, *, batch: int, seq: int, mode: str = "train"):
        """ShapeDtypeStruct stand-ins for one step's data inputs.

        mode: train | prefill | decode.  Decode returns (tokens [B,1],
        pos [B]); the cache is supplied separately via cache_specs.
        """
        cfg = self.cfg
        i32 = jnp.int32
        if mode == "decode":
            return {"tokens": jax.ShapeDtypeStruct((batch, 1), i32),
                    "pos": jax.ShapeDtypeStruct((batch,), i32)}
        out: dict[str, Any] = {}
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct(
                (batch, cfg.encoder_seq, cfg.d_model), COMPUTE_DTYPE)
            out["tokens"] = jax.ShapeDtypeStruct((batch, seq), i32)
        elif cfg.family == "vlm":
            out["tokens"] = jax.ShapeDtypeStruct((batch, seq), i32)
            out["positions"] = jax.ShapeDtypeStruct((3, batch, seq), i32)
        else:
            out["tokens"] = jax.ShapeDtypeStruct((batch, seq), i32)
        if mode == "train":
            out["labels"] = jax.ShapeDtypeStruct((batch, seq), i32)
        return out

    def make_batch(self, key, *, batch: int, seq: int, mode: str = "train"):
        """Concrete synthetic batch matching input_specs (smoke tests)."""
        cfg = self.cfg
        ks = jax.random.split(key, 4)
        if mode == "decode":
            return {
                "tokens": jax.random.randint(ks[0], (batch, 1), 0,
                                             cfg.vocab_size),
                "pos": jnp.zeros((batch,), jnp.int32),
            }
        out: dict[str, Any] = {}
        if cfg.family == "encdec":
            out["frames"] = jax.random.normal(
                ks[2], (batch, cfg.encoder_seq, cfg.d_model), jnp.float32
            ).astype(COMPUTE_DTYPE)
        if cfg.family == "vlm":
            pos = jnp.arange(seq, dtype=jnp.int32)[None, :].repeat(batch, 0)
            out["positions"] = pos[None].repeat(3, 0)
        out["tokens"] = jax.random.randint(ks[0], (batch, seq), 0,
                                           cfg.vocab_size)
        if mode == "train":
            out["labels"] = jax.random.randint(ks[1], (batch, seq), 0,
                                               cfg.vocab_size)
        return out


def build_model(cfg: ModelConfig) -> Model:
    if cfg.family in ("dense", "moe", "vlm"):
        return Model(cfg, tf_model.transformer_specs, tf_model.loss_fn,
                     tf_model.forward, tf_model.init_cache_specs,
                     tf_model.decode_step, tf_model.serving_params,
                     tf_model.loss_and_stats)
    if cfg.family == "ssm" and cfg.rwkv:
        return Model(cfg, rwkv_model.rwkv6_specs, rwkv_model.loss_fn,
                     rwkv_model.forward, rwkv_model.init_cache_specs,
                     rwkv_model.decode_step)
    if cfg.family in ("ssm", "hybrid"):
        return Model(cfg, ssm_model.zamba2_specs, ssm_model.loss_fn,
                     ssm_model.forward, ssm_model.init_cache_specs,
                     ssm_model.decode_step)
    if cfg.family == "encdec":
        return Model(cfg, whisper_model.whisper_specs, whisper_model.loss_fn,
                     whisper_model.forward, whisper_model.init_cache_specs,
                     whisper_model.decode_step)
    raise ValueError(f"unknown family {cfg.family!r}")
