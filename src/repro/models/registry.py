"""Uniform model interface over the zoo.

``build(cfg)`` returns a :class:`ModelBundle` exposing init / loss_fn /
prefill / decode_step / init_cache / batch_specs regardless of family.

Shape conventions for the assigned input-shape grid:
  train_4k      tokens (B, S). VLM: S_text = S - n_prefix (patch embeds fill
                the rest). Enc-dec: S_enc = S_dec = S // 2.
  prefill_32k   decoder prefill of length S (enc-dec: encoder ctx = 4096).
  decode_*      one token against a KV cache (or SSM state) of length S.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs import (FAMILY_AUDIO, FAMILY_SSM, FAMILY_VLM, InputShape,
                           ModelConfig)
from repro.models import encdec, transformer

ENC_CTX_SERVE = 4096  # encoder context frames for enc-dec serve shapes


@dataclasses.dataclass
class ModelBundle:
    cfg: ModelConfig
    init: Callable
    loss_fn: Callable          # (params, batch, **kw) -> scalar
    prefill: Callable          # (params, batch, max_len, **kw) -> (logits, cache)
    decode_step: Callable      # (params, cache, token, **kw) -> (logits, cache)
    init_cache: Callable       # (batch, max_len, dtype) -> cache tree
    batch_specs: Callable      # (InputShape) -> dict of ShapeDtypeStruct


def build(cfg: ModelConfig) -> ModelBundle:
    if cfg.is_encoder_decoder:
        return _build_encdec(cfg)
    return _build_decoder(cfg)


def _build_decoder(cfg: ModelConfig) -> ModelBundle:
    def loss_fn(params, batch, *, dtype=jnp.bfloat16, remat=True,
                moe_ctx=None):
        return transformer.loss_fn(params, cfg, batch, dtype=dtype,
                                   remat=remat, moe_ctx=moe_ctx)

    def prefill_fn(params, batch, max_len=None, *, dtype=jnp.bfloat16):
        return transformer.prefill(params, cfg, batch["tokens"],
                                   prefix_embeds=batch.get("prefix_embeds"),
                                   max_len=max_len, dtype=dtype,
                                   last_index=batch.get("last_index"))

    def decode_fn(params, cache, token, *, dtype=jnp.bfloat16):
        return transformer.decode_step(params, cfg, cache, token,
                                       dtype=dtype)

    def init_cache(batch, max_len, dtype=jnp.bfloat16,
                   per_slot_pos=False, kv_dtype=None):
        return transformer.init_cache(cfg, batch, max_len, dtype,
                                      per_slot_pos=per_slot_pos,
                                      kv_dtype=kv_dtype)

    def batch_specs(shape: InputShape):
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "decode":
            return {"token": jax.ShapeDtypeStruct((b, 1), i32)}
        specs = {}
        s_text = s
        if cfg.family == FAMILY_VLM:
            s_text = s - cfg.n_prefix_embeds
            specs["prefix_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.n_prefix_embeds, cfg.d_model), jnp.bfloat16)
        specs["tokens"] = jax.ShapeDtypeStruct((b, s_text), i32)
        return specs

    return ModelBundle(cfg, lambda key: transformer.init(key, cfg), loss_fn,
                       prefill_fn, decode_fn, init_cache, batch_specs)


def _build_encdec(cfg: ModelConfig) -> ModelBundle:
    def loss_fn(params, batch, *, dtype=jnp.bfloat16, remat=True,
                moe_ctx=None):
        return encdec.loss_fn(params, cfg, batch, dtype=dtype, remat=remat)

    def prefill_fn(params, batch, max_len=None, *, dtype=jnp.bfloat16):
        return encdec.prefill(params, cfg, batch["tokens"],
                              batch["enc_embeds"], max_len=max_len,
                              dtype=dtype)

    def decode_fn(params, cache, token, *, dtype=jnp.bfloat16):
        return encdec.decode_step(params, cfg, cache, token, dtype=dtype)

    def init_cache(batch, max_len, dtype=jnp.bfloat16,
                   enc_len=ENC_CTX_SERVE):
        return encdec.init_cache(cfg, batch, max_len, enc_len, dtype)

    def batch_specs(shape: InputShape):
        b, s = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "decode":
            return {"token": jax.ShapeDtypeStruct((b, 1), i32)}
        if shape.kind == "train":
            s_enc = s_dec = s // 2
        else:  # prefill
            s_enc, s_dec = ENC_CTX_SERVE, s
        return {
            "tokens": jax.ShapeDtypeStruct((b, s_dec), i32),
            "enc_embeds": jax.ShapeDtypeStruct((b, s_enc, cfg.d_model),
                                               jnp.bfloat16),
        }

    return ModelBundle(cfg, lambda key: encdec.init(key, cfg), loss_fn,
                       prefill_fn, decode_fn, init_cache, batch_specs)
