"""The Qwen2 / Qwen1.5 decoder block, as the benchmark knows it.

Found by a configuration file's ``model_type`` (``qwen2``): its sizes
(:func:`dims`), the names and shapes of its weights (:func:`layout`),
the program's configuration for it (:func:`program_config`), its plain
reference forward and the fp8 control (:func:`layer`, :func:`hidden`,
:func:`logits_at`), and the operations its work needs
(:func:`prefill_flops`, :func:`decode_flops`).

The block, written from the model card and nothing of the program:
RMSNorm, grouped-query attention with biases on q, k and v, rotate-half
RoPE, a SwiGLU feed-forward, a final RMSNorm and the (tied or untied)
output head. The weight layout is named by the paths under which the
program's parameter tree keeps them. The reference draws its weights
from the seed one layer at a time, so it fits on the chip beside nothing
else, and runs in float32 at the highest matmul precision; with
``fp8=True`` every matmul operand is rounded to float8 e4m3
(``reference._mm``).
"""
from __future__ import annotations

import dataclasses
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as wts
from chipbench.reference import _mm
from chipbench.weights import Leaf


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes one forward pass needs, read from a configuration file."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    rms_eps: float
    tied: bool


def dims(cfg: dict) -> Dims:
    heads = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return Dims(layers=int(cfg["num_hidden_layers"]), d_model=d,
                heads=heads, kv_heads=int(cfg["num_key_value_heads"]),
                head_dim=int(cfg.get("head_dim", d // heads)),
                d_ff=int(cfg["intermediate_size"]),
                vocab=int(cfg["vocab_size"]),
                rope_theta=float(cfg["rope_theta"]),
                rms_eps=float(cfg["rms_norm_eps"]),
                tied=bool(cfg["tie_word_embeddings"]))


def layout(dims: Dims) -> List[Leaf]:
    d, h, kv, hd, f = (dims.d_model, dims.heads, dims.kv_heads,
                       dims.head_dim, dims.d_ff)
    leaves = [
        Leaf("embed/embedding", (dims.vocab, d), False, "embed", d),
        Leaf("final_norm/scale", (d,), False, "norm"),
        Leaf("layers/attn_norm/scale", (d,), True, "norm"),
        Leaf("layers/attn/q/w", (d, h, hd), True, "dense", d),
        Leaf("layers/attn/q/b", (h, hd), True, "bias"),
        Leaf("layers/attn/k/w", (d, kv, hd), True, "dense", d),
        Leaf("layers/attn/k/b", (kv, hd), True, "bias"),
        Leaf("layers/attn/v/w", (d, kv, hd), True, "dense", d),
        Leaf("layers/attn/v/b", (kv, hd), True, "bias"),
        Leaf("layers/attn/o/w", (h, hd, d), True, "dense", h * hd),
        Leaf("layers/ffn_norm/scale", (d,), True, "norm"),
        Leaf("layers/ffn/gate/w", (d, f), True, "dense", d),
        Leaf("layers/ffn/up/w", (d, f), True, "dense", d),
        Leaf("layers/ffn/down/w", (f, d), True, "dense", f),
    ]
    if not dims.tied:
        leaves.append(Leaf("unembed/w", (d, dims.vocab), False, "dense", d))
    return leaves


def program_config(cfg: dict):
    """The program's model configuration, as the configuration file
    states it (every size from the file, none from the program's zoo)."""
    from repro.configs import FAMILY_DENSE, ModelConfig  # system under test
    d = dims(cfg)
    return ModelConfig(
        name=cfg["name"], family=FAMILY_DENSE, n_layers=d.layers,
        d_model=d.d_model, n_heads=d.heads, n_kv_heads=d.kv_heads,
        head_dim=d.head_dim, d_ff=d.d_ff, vocab_size=d.vocab,
        qkv_bias=True, rope_theta=d.rope_theta, rms_eps=d.rms_eps,
        tie_embeddings=d.tied)


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x (B, S, H, D); rotate-half convention of the published models."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, :, None].astype(jnp.float32) * inv          # (B, S, D/2)
    cos, sin = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(p, x, dims: Dims, fp8: bool):
    b, s, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    h = _rms(x, p["attn_norm/scale"], dims.rms_eps)
    q = _mm("bsd,dhk->bshk", h, p["attn/q/w"], 2, 0, fp8) + p["attn/q/b"]
    k = _mm("bsd,dhk->bshk", h, p["attn/k/w"], 2, 0, fp8) + p["attn/k/b"]
    v = _mm("bsd,dhk->bshk", h, p["attn/v/w"], 2, 0, fp8) + p["attn/v/b"]
    q = _rope(q, pos, dims.rope_theta)
    k = _rope(k, pos, dims.rope_theta)
    group = dims.heads // dims.kv_heads
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    att = _mm("bshk,bthk->bhst", q, k, 3, 3, fp8) / np.sqrt(dims.head_dim)
    causal = jnp.tril(jnp.ones((s, s), bool))
    att = jnp.where(causal, att, -jnp.inf)
    att = jax.nn.softmax(att, axis=-1)
    o = _mm("bhst,bthk->bshk", att, v, 3, 1, fp8)
    x = x + _mm("bshk,hkd->bsd", o, p["attn/o/w"], (2, 3), (0, 1), fp8)
    h = _rms(x, p["ffn_norm/scale"], dims.rms_eps)
    g = _mm("bsd,df->bsf", h, p["ffn/gate/w"], 2, 0, fp8)
    u = _mm("bsd,df->bsf", h, p["ffn/up/w"], 2, 0, fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, p["ffn/down/w"],
                   2, 0, fp8)


_layer_jit = jax.jit(layer, static_argnames=("dims", "fp8"))


@jax.jit
def _embed(table, tokens):
    return jnp.take(table, tokens, axis=0)


def _head(x, at, norm, head, eps, fp8, tied):
    """Logits at positions ``at`` (B, P) of the final hidden ``x``."""
    x = jnp.take_along_axis(x, at[:, :, None], axis=1)
    x = _rms(x, norm, eps)
    if tied:
        return _mm("bpd,vd->bpv", x, head, 2, 1, fp8)
    return _mm("bpd,dv->bpv", x, head, 2, 0, fp8)


_head_jit = jax.jit(_head, static_argnames=("eps", "fp8", "tied"))


def hidden(dims: Dims, seed: int, tokens: np.ndarray, fp8: bool):
    """Final hidden states (B, S, d) of the padded token block."""
    leaves = layout(dims)
    x = _embed(wts.make_global(leaves, seed, "embed/embedding"),
               jnp.asarray(tokens))
    for i in range(dims.layers):
        x = _layer_jit(wts.make_layer(leaves, seed, i), x, dims, fp8)
    return x


def logits_at(dims: Dims, seed: int, x, at: np.ndarray, fp8: bool):
    leaves = layout(dims)
    name = "embed/embedding" if dims.tied else "unembed/w"
    return _head_jit(x, jnp.asarray(at), wts.make_global(
        leaves, seed, "final_norm/scale"), wts.make_global(
        leaves, seed, name), dims.rms_eps, fp8, dims.tied)


# ---------------------------------------------------------------------------
# operations, counted for what the algorithm needs (chipbench/flops.py)
# ---------------------------------------------------------------------------

def matmul_params(dims: Dims) -> int:
    """Weights one token multiplies through in the blocks (no head)."""
    d, h, kv, hd, f = (dims.d_model, dims.heads, dims.kv_heads,
                       dims.head_dim, dims.d_ff)
    return dims.layers * (d * (h + 2 * kv) * hd + h * hd * d + 3 * d * f)


def head_flops(dims: Dims) -> int:
    return 2 * dims.d_model * dims.vocab


def attn_flops(dims: Dims, keys: int) -> int:
    """Scores and weighted sum of one query over ``keys`` positions, all
    layers."""
    return 4 * dims.layers * dims.heads * dims.head_dim * keys


def prefill_flops(dims: Dims, prompt_len: int) -> int:
    """A prompt of ``prompt_len`` tokens under the causal mask, and the
    head at its last position."""
    keys = prompt_len * (prompt_len + 1) // 2
    return (2 * matmul_params(dims) * prompt_len + attn_flops(dims, keys)
            + head_flops(dims))


def decode_flops(dims: Dims, live: int, ctx_sum: int) -> int:
    """One tick: ``live`` slots, each attending over its own context;
    ``ctx_sum`` is the sum of those contexts."""
    return (live * (2 * matmul_params(dims) + head_flops(dims))
            + attn_flops(dims, ctx_sum))
