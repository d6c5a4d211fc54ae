"""Decoder-only transformer LM covering the dense / moe / mla / hybrid / ssm /
vlm families via config switches. Layers are stacked and scanned
(jax.lax.scan) so compile time is independent of depth.

Public surface (used by registry / launch / engine):
  init(key, cfg)                          -> Param tree
  forward(params, cfg, tokens, ...)       -> logits (train/prefill path)
  loss_fn(params, cfg, batch, ...)        -> scalar loss
  init_cache(cfg, batch, max_len, dtype)  -> decode cache pytree (Param tree)
  prefill(params, cfg, tokens, cache)     -> (logits_last, cache)
  decode_step(params, cfg, cache, token)  -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import (ATTN_GQA, ATTN_MLA, ATTN_NONE, FAMILY_HYBRID,
                           FAMILY_SSM, ModelConfig)
from repro.models import attention as attn
from repro.models import common as cm
from repro.models import ffn as ffn_mod
from repro.models import ssm as ssm_mod


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 6)
    p = {}
    if cfg.attn_type == ATTN_GQA:
        p["attn_norm"] = cm.rmsnorm_init(cfg.d_model)
        p["attn"] = attn.gqa_init(ks[0], cfg)
    elif cfg.attn_type == ATTN_MLA:
        p["attn_norm"] = cm.rmsnorm_init(cfg.d_model)
        p["attn"] = attn.mla_init(ks[0], cfg)
    if cfg.ssm is not None:
        if cfg.family == FAMILY_HYBRID:
            p["ssm"] = ssm_mod.mamba2_init(ks[1], cfg)
            p["attn_out_norm"] = cm.rmsnorm_init(cfg.d_model)
            p["ssm_out_norm"] = cm.rmsnorm_init(cfg.d_model)
        else:
            p["ssm_norm"] = cm.rmsnorm_init(cfg.d_model)
            p["ssm"] = ssm_mod.mamba2_init(ks[1], cfg)
    if cfg.d_ff > 0:
        p["ffn_norm"] = cm.rmsnorm_init(cfg.d_model)
        if cfg.moe is not None:
            p["ffn"] = ffn_mod.moe_init(ks[2], cfg)
        else:
            p["ffn"] = ffn_mod.swiglu_init(ks[2], cfg.d_model, cfg.d_ff)
    return p


def init(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p = {
        "embed": cm.embedding(ks[0], cfg.vocab_size, cfg.d_model),
        "layers": cm.stack_layers(lambda k: block_init(k, cfg), ks[1],
                                  cfg.n_layers),
        "final_norm": cm.rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["unembed"] = cm.dense(ks[2], cfg.d_model, cfg.vocab_size,
                                ("embed", "vocab"))
    if cfg.n_prefix_embeds:
        # projection for precomputed modality embeddings (frontend stub)
        p["prefix_proj"] = cm.dense(ks[3], cfg.d_model, cfg.d_model,
                                    ("embed", "embed2"))
    return p


def layer_windows(cfg: ModelConfig):
    """Per-layer sliding window (0 = full attention)."""
    if cfg.sliding_window <= 0:
        return None
    w = jnp.full((cfg.n_layers,), cfg.sliding_window, jnp.int32)
    if cfg.full_attn_layers:
        idx = jnp.array(cfg.full_attn_layers)
        w = w.at[idx].set(0)
    return w


# ---------------------------------------------------------------------------
# Forward (train / prefill path)
# ---------------------------------------------------------------------------

def _block_forward(lp, x, cfg, window, positions, moe_ctx):
    """One layer. x: (B,S,d). window: python int 0 or traced int32 scalar.

    The constrain() on each mixer output pins the tensor-parallel
    all-reduce to the NARROW dtype: without it XLA fuses the bf16
    round-trip into downstream f32 consumers (residual + rmsnorm) and
    all-reduces the f32 carrier — 2x the ICI bytes (§Perf iteration 3)."""
    from repro.distributed import sharding as shd
    x = shd.constrain(x, ("batch", "seq", "embed_act"))
    if "attn" in lp:
        h = cm.rmsnorm(lp["attn_norm"], x, cfg.rms_eps)
        if cfg.attn_type == ATTN_MLA:
            a = attn.mla_forward(lp["attn"], h, cfg, positions=positions)
        else:
            a = attn.gqa_forward(lp["attn"], h, cfg, positions=positions,
                                 window=window)
        if cfg.family == FAMILY_HYBRID:
            s = ssm_mod.mamba2_forward(lp["ssm"], h, cfg)
            mix = 0.5 * (cm.rmsnorm(lp["attn_out_norm"], a, cfg.rms_eps)
                         + cm.rmsnorm(lp["ssm_out_norm"], s, cfg.rms_eps))
            x = x + mix
        else:
            x = x + a
    elif "ssm" in lp:
        h = cm.rmsnorm(lp["ssm_norm"], x, cfg.rms_eps)
        x = x + ssm_mod.mamba2_forward(lp["ssm"], h, cfg)
    if "ffn" in lp:
        h = cm.rmsnorm(lp["ffn_norm"], x, cfg.rms_eps)
        if cfg.moe is not None:
            if moe_ctx and moe_ctx.get("impl") == "shardmap":
                f = ffn_mod.moe_forward_shardmap(
                    lp["ffn"], h, cfg, moe_ctx["mesh"],
                    dp_axes=moe_ctx["dp_axes"])
            else:
                f = ffn_mod.moe_forward_gather(lp["ffn"], h, cfg)
        else:
            f = ffn_mod.swiglu(lp["ffn"], h)
        x = x + f
    return x


def embed_inputs(params, cfg, tokens, prefix_embeds=None, dtype=jnp.bfloat16):
    emb = params["embed"]["embedding"].value
    x = jnp.take(emb, tokens, axis=0).astype(dtype)
    if cfg.n_prefix_embeds and prefix_embeds is not None:
        pfx = cm.apply_dense(params["prefix_proj"],
                             prefix_embeds.astype(dtype))
        x = jnp.concatenate([pfx, x], axis=1)
    return x


def forward(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            dtype=jnp.bfloat16, remat=False, moe_ctx=None,
            inputs_embeds=None):
    """tokens: (B, S_text) int32. Returns logits (B, S_total, vocab) f32."""
    if inputs_embeds is not None:
        x = inputs_embeds.astype(dtype)
    else:
        x = embed_inputs(params, cfg, tokens, prefix_embeds, dtype)
    seq = x.shape[1]
    positions = jnp.arange(seq)[None, :]
    windows = layer_windows(cfg)

    def body(x, layer_in):
        lp, win = layer_in
        y = _block_forward(lp, x, cfg, win if win is not None else 0,
                           positions, moe_ctx)
        return y, None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)

    win_xs = windows if windows is not None else None
    x, _ = jax.lax.scan(body, x, (params["layers"], win_xs))
    x = cm.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    return unembed(params, cfg, x)


def unembed(params, cfg, x):
    from repro.distributed import sharding as shd
    if cfg.tie_embeddings:
        emb = params["embed"]["embedding"].value
        logits = jnp.einsum("bsd,vd->bsv", x, emb.astype(x.dtype)).astype(
            jnp.float32)
    else:
        logits = cm.apply_dense(params["unembed"], x).astype(jnp.float32)
    return shd.constrain(logits, ("batch", "seq", "vocab"))


def loss_fn(params, cfg: ModelConfig, batch, *, dtype=jnp.bfloat16,
            remat=True, moe_ctx=None):
    """batch: {"tokens": (B,S)} (+ "prefix_embeds" | "enc_embeds")."""
    tokens = batch["tokens"]
    logits = forward(params, cfg, tokens,
                     prefix_embeds=batch.get("prefix_embeds"),
                     dtype=dtype, remat=remat, moe_ctx=moe_ctx)
    npfx = logits.shape[1] - tokens.shape[1]
    if npfx:
        logits = logits[:, npfx:]
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.zeros_like(tokens[:, :1])], axis=1)
    mask = jnp.concatenate(
        [jnp.ones_like(tokens[:, 1:], jnp.float32),
         jnp.zeros_like(tokens[:, :1], jnp.float32)], axis=1)
    return cm.softmax_cross_entropy(logits, labels, mask)


# ---------------------------------------------------------------------------
# Decode cache
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, per_slot_pos: bool = False,
               kv_dtype=None):
    """Returns a Param tree so the sharding rules apply to cache leaves too.

    per_slot_pos=True allocates a (batch,) position vector — each slot
    decodes at its own depth (continuous batching, repro.engine).
    kv_dtype=jnp.int8 stores a quantized GQA cache + per-(pos, head)
    scales (§Perf pair C: decode streams half the bytes)."""
    L = cfg.n_layers
    c = {}
    if cfg.attn_type == ATTN_GQA:
        kv = (L, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        axes = ("layer", "batch", "kv_seq", "kv_heads", "head_dim")
        if kv_dtype == jnp.int8:
            c["k"] = cm.Param(jnp.zeros(kv, jnp.int8), axes)
            c["v"] = cm.Param(jnp.zeros(kv, jnp.int8), axes)
            sc = (L, batch, max_len, cfg.n_kv_heads)
            sc_axes = ("layer", "batch", "kv_seq", "kv_heads")
            c["k_scale"] = cm.Param(jnp.zeros(sc, jnp.bfloat16), sc_axes)
            c["v_scale"] = cm.Param(jnp.zeros(sc, jnp.bfloat16), sc_axes)
        else:
            c["k"] = cm.Param(jnp.zeros(kv, dtype), axes)
            c["v"] = cm.Param(jnp.zeros(kv, dtype), axes)
    elif cfg.attn_type == ATTN_MLA:
        m = cfg.mla
        c["ckv"] = cm.Param(
            jnp.zeros((L, batch, max_len, m.kv_lora_rank), dtype),
            ("layer", "batch", "kv_seq", "kv_lora"))
        c["krope"] = cm.Param(
            jnp.zeros((L, batch, max_len, m.qk_rope_head_dim), dtype),
            ("layer", "batch", "kv_seq", "head_dim"))
    if cfg.ssm is not None:
        d_inner, nh, conv_ch = ssm_mod.dims(cfg)
        c["ssm_state"] = cm.Param(
            jnp.zeros((L, batch, nh, cfg.ssm.d_state, cfg.ssm.head_dim),
                      jnp.float32),
            ("layer", "batch", "ssm_heads", "ssm_state", "head_dim"))
        c["conv_buf"] = cm.Param(
            jnp.zeros((L, batch, cfg.ssm.conv_width - 1, conv_ch), dtype),
            ("layer", "batch", "conv", "ssm_conv_ch"))
    if per_slot_pos:
        c["pos"] = cm.Param(jnp.zeros((batch,), jnp.int32), ("batch",))
    else:
        c["pos"] = cm.Param(jnp.zeros((), jnp.int32), ())
    return c


def _block_decode(lp, cache_l, x, pos, cfg, window):
    upd = {}
    if "attn" in lp:
        h = cm.rmsnorm(lp["attn_norm"], x, cfg.rms_eps)
        if cfg.attn_type == ATTN_MLA:
            a, ckv, krope = attn.mla_decode(
                lp["attn"], h, cache_l["ckv"], cache_l["krope"], pos, cfg)
            upd["ckv"], upd["krope"] = ckv, krope
        elif "k_scale" in cache_l:        # int8-quantized cache
            a, ck, cv, ks, vs = attn.gqa_decode_q8(
                lp["attn"], h, cache_l["k"], cache_l["v"],
                cache_l["k_scale"], cache_l["v_scale"], pos, cfg,
                window=window)
            upd["k"], upd["v"] = ck, cv
            upd["k_scale"], upd["v_scale"] = ks, vs
        else:
            a, ck, cv = attn.gqa_decode(
                lp["attn"], h, cache_l["k"], cache_l["v"], pos, cfg,
                window=window)
            upd["k"], upd["v"] = ck, cv
        if cfg.family == FAMILY_HYBRID:
            s, st, buf = ssm_mod.mamba2_decode(
                lp["ssm"], h, cache_l["ssm_state"], cache_l["conv_buf"], cfg)
            upd["ssm_state"], upd["conv_buf"] = st, buf
            mix = 0.5 * (cm.rmsnorm(lp["attn_out_norm"], a, cfg.rms_eps)
                         + cm.rmsnorm(lp["ssm_out_norm"], s, cfg.rms_eps))
            x = x + mix
        else:
            x = x + a
    elif "ssm" in lp:
        h = cm.rmsnorm(lp["ssm_norm"], x, cfg.rms_eps)
        s, st, buf = ssm_mod.mamba2_decode(
            lp["ssm"], h, cache_l["ssm_state"], cache_l["conv_buf"], cfg)
        upd["ssm_state"], upd["conv_buf"] = st, buf
        x = x + s
    if "ffn" in lp:
        h = cm.rmsnorm(lp["ffn_norm"], x, cfg.rms_eps)
        if cfg.moe is not None:
            x = x + ffn_mod.moe_forward_gather(lp["ffn"], h, cfg)
        else:
            x = x + ffn_mod.swiglu(lp["ffn"], h)
    return x, upd


def decode_step(params, cfg: ModelConfig, cache, token, *,
                dtype=jnp.bfloat16):
    """token: (B, 1) int32. Returns (logits (B,1,V) f32, new cache)."""
    pos = cache["pos"].value
    emb = params["embed"]["embedding"].value
    x = jnp.take(emb, token, axis=0).astype(dtype)
    windows = layer_windows(cfg)

    cache_vals = {k: v.value for k, v in cache.items() if k != "pos"}

    def body(x, layer_in):
        lp, cl, win = layer_in
        y, upd = _block_decode(lp, cl, x, pos, cfg,
                               win if win is not None else 0)
        # keep unmodified cache entries as-is so the scan carry matches
        out = {k: upd.get(k, cl[k]) for k in cl}
        return y, out

    x, new_cache_vals = jax.lax.scan(
        body, x, (params["layers"], cache_vals, windows))
    x = cm.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = unembed(params, cfg, x)
    new_cache = {k: cm.Param(v, cache[k].axes)
                 for k, v in new_cache_vals.items()}
    new_cache["pos"] = cm.Param(pos + 1, cache["pos"].axes)
    return logits, new_cache


def prefill(params, cfg: ModelConfig, tokens, *, prefix_embeds=None,
            max_len: Optional[int] = None, dtype=jnp.bfloat16,
            last_index=None):
    """Run the full-sequence forward while building the decode cache.

    Returns (last-position logits, cache). Implemented as a scan over layers
    mirroring `forward` but capturing K/V (or SSM state) per layer.
    ``last_index`` (B,) int32 picks, per row, the position whose logits are
    returned — the last real token of a right-padded prompt; None = the
    final position.
    """
    x = embed_inputs(params, cfg, tokens, prefix_embeds, dtype)
    b, seq = x.shape[0], x.shape[1]
    max_len = max_len or seq
    positions = jnp.arange(seq)[None, :]
    windows = layer_windows(cfg)

    def body(x, layer_in):
        lp, win = layer_in
        win = win if win is not None else 0
        caches = {}
        if "attn" in lp:
            h = cm.rmsnorm(lp["attn_norm"], x, cfg.rms_eps)
            if cfg.attn_type == ATTN_MLA:
                m = cfg.mla
                c_kv = cm.apply_dense(lp["attn"]["kv_down"], h)
                k_rope = cm.apply_dense(lp["attn"]["k_rope"], h)[:, :, 0]
                k_rope = cm.apply_rope(k_rope[:, :, None], positions,
                                       cfg.rope_theta)[:, :, 0]
                caches["ckv"] = _pad_to(c_kv, max_len, 1).astype(dtype)
                caches["krope"] = _pad_to(k_rope, max_len, 1).astype(dtype)
                a = attn.mla_forward(lp["attn"], h, cfg, positions=positions)
            else:
                q, k, v = attn.gqa_project_qkv(lp["attn"], h, positions,
                                               cfg.rope_theta)
                caches["k"] = _pad_to(k, max_len, 1).astype(dtype)
                caches["v"] = _pad_to(v, max_len, 1).astype(dtype)
                o = attn.chunked_attention(q, k, v, causal=True, window=win)
                a = cm.apply_dense(lp["attn"]["o"], o, in_dims=2)
            if cfg.family == FAMILY_HYBRID:
                s, (st, buf) = ssm_mod.mamba2_forward(lp["ssm"], h, cfg,
                                                      return_state=True)
                caches["ssm_state"], caches["conv_buf"] = st, buf.astype(dtype)
                mix = 0.5 * (cm.rmsnorm(lp["attn_out_norm"], a, cfg.rms_eps)
                             + cm.rmsnorm(lp["ssm_out_norm"], s, cfg.rms_eps))
                x = x + mix
            else:
                x = x + a
        elif "ssm" in lp:
            h = cm.rmsnorm(lp["ssm_norm"], x, cfg.rms_eps)
            s, (st, buf) = ssm_mod.mamba2_forward(lp["ssm"], h, cfg,
                                                  return_state=True)
            caches["ssm_state"], caches["conv_buf"] = st, buf.astype(dtype)
            x = x + s
        if "ffn" in lp:
            h = cm.rmsnorm(lp["ffn_norm"], x, cfg.rms_eps)
            if cfg.moe is not None:
                x = x + ffn_mod.moe_forward_gather(lp["ffn"], h, cfg)
            else:
                x = x + ffn_mod.swiglu(lp["ffn"], h)
        return x, caches

    x, cache_stk = jax.lax.scan(body, x, (params["layers"], windows))
    x = cm.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    if last_index is None:
        x_last = x[:, -1:]
    else:
        x_last = jnp.take_along_axis(
            x, jnp.asarray(last_index, jnp.int32)[:, None, None], axis=1)
    logits_last = unembed(params, cfg, x_last)

    axes_map = {
        "k": ("layer", "batch", "kv_seq", "kv_heads", "head_dim"),
        "v": ("layer", "batch", "kv_seq", "kv_heads", "head_dim"),
        "ckv": ("layer", "batch", "kv_seq", "kv_lora"),
        "krope": ("layer", "batch", "kv_seq", "head_dim"),
        "ssm_state": ("layer", "batch", "ssm_heads", "ssm_state", "head_dim"),
        "conv_buf": ("layer", "batch", "conv", "ssm_conv_ch"),
    }
    cache = {k: cm.Param(v, axes_map[k]) for k, v in cache_stk.items()}
    total = seq + (cfg.n_prefix_embeds if prefix_embeds is not None else 0)
    cache["pos"] = cm.Param(jnp.asarray(min(total, max_len), jnp.int32), ())
    return logits_last, cache


def _pad_to(x, n, axis):
    if x.shape[axis] == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, n - x.shape[axis])
    return jnp.pad(x, pad)
