"""Unified model API: one entry point per (family x phase), used by the
launcher, the dry-run, the smoke tests, and the benchmarks.

    model = ModelAPI(cfg)
    model.param_struct()                  -> Leaf pytree (init/dry-run/sharding)
    model.loss_fn(params, batch)          -> (loss, aux)        [train]
    model.prefill(params, tokens, ...)    -> (logits, cache)    [serving]
    model.decode_step(params, cache, tok, pos) -> (logits, cache)
    model.cache_struct(batch, max_seq)    -> Leaf pytree of the decode state
    model.input_specs(shape)              -> ShapeDtypeStruct batch for `shape`
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import params as params_lib
from repro.models import rwkv, transformer, zamba

PyTree = Any


@dataclasses.dataclass(frozen=True)
class ModelAPI:
    cfg: ModelConfig

    # ------------------------------------------------------------- params
    def param_struct(self) -> PyTree:
        if self.cfg.family == "transformer":
            return transformer.param_struct(self.cfg)
        if self.cfg.family == "rwkv":
            return rwkv.param_struct(self.cfg)
        if self.cfg.family == "zamba":
            return zamba.param_struct(self.cfg)
        raise ValueError(f"unknown family {self.cfg.family}")

    def init_params(self, rng: jax.Array) -> PyTree:
        return params_lib.init_params(self.param_struct(), rng)

    def param_count(self) -> int:
        return params_lib.count_params(self.param_struct())

    # -------------------------------------------------------------- train
    def loss_fn(self, params, batch, remat: bool = False):
        mod = {"transformer": transformer, "rwkv": rwkv, "zamba": zamba}[self.cfg.family]
        return mod.loss_fn(params, batch, self.cfg, remat=remat)

    def forward(self, params, tokens, **kw):
        mod = {"transformer": transformer, "rwkv": rwkv, "zamba": zamba}[self.cfg.family]
        return mod.forward(params, tokens, self.cfg, **kw)

    # -------------------------------------------------------------- serve
    def prefill(self, params, tokens, prefix_embeds=None):
        mod = {"transformer": transformer, "rwkv": rwkv, "zamba": zamba}[self.cfg.family]
        return mod.prefill(params, tokens, self.cfg, prefix_embeds=prefix_embeds)

    def decode_step(self, params, cache, tokens, pos):
        mod = {"transformer": transformer, "rwkv": rwkv, "zamba": zamba}[self.cfg.family]
        return mod.decode_step(params, cache, tokens, pos, self.cfg)

    def cache_struct(self, batch: int, max_seq: int) -> PyTree:
        if self.cfg.family == "transformer":
            return transformer.cache_struct(self.cfg, batch, max_seq)
        if self.cfg.family == "rwkv":
            return rwkv.state_struct(self.cfg, batch)
        if self.cfg.family == "zamba":
            return zamba.state_struct(self.cfg, batch, max_seq)
        raise ValueError(self.cfg.family)

    # -------------------------------------------------------------- specs
    def input_specs(self, shape: ShapeConfig) -> dict:
        """ShapeDtypeStruct stand-ins for every model input of this shape.

        train:   {"tokens", "labels"[, "prefix_embeds"]}
        prefill: {"tokens"[, "prefix_embeds"]}
        decode:  {"tokens" (B,), "pos" scalar, "cache": <struct>}
        """
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
                     "labels": jax.ShapeDtypeStruct((b, s), i32)}
            if self.cfg.stub_prefix:
                specs["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (b, self.cfg.stub_prefix, self.cfg.prefix_width),
                    jnp.dtype(self.cfg.dtype))
            return specs
        if shape.kind == "prefill":
            specs = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
            if self.cfg.stub_prefix:
                specs["prefix_embeds"] = jax.ShapeDtypeStruct(
                    (b, self.cfg.stub_prefix, self.cfg.prefix_width),
                    jnp.dtype(self.cfg.dtype))
            return specs
        # decode: one new token against a cache of size seq_len
        cache = params_lib.shape_structs(self.cache_struct(b, s))
        return {"tokens": jax.ShapeDtypeStruct((b,), i32),
                "pos": jax.ShapeDtypeStruct((), i32),
                "cache": cache}
