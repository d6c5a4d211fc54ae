"""Plain reference forward of the benchmark's dense decoders, and its
lower-precision control.

The published Qwen2 / Qwen1.5 block, written from the model card and
nothing of the program: RMSNorm, grouped-query attention with biases on
q, k and v, rotate-half RoPE, a SwiGLU feed-forward, a final RMSNorm and
the (tied or untied) output head. Weights come from ``weights`` by the
seed, one layer at a time, so the reference fits on the chip beside
nothing else. It runs in float32 at the highest matmul precision.

With ``control=True`` (``fp8`` below) it is the control: every matmul
operand is rounded to float8 e4m3 with a scale per slice of the
contraction (weights per output channel, activations per token), as an
fp8 serving path computes, and multiplied in float32.

A served token is judged by its *gap*: the reference's best logit at
that position minus the reference's logit for the token. A greedy token
that agrees with the reference has gap 0; one picked from the wrong
position or after a wrong step lies about the logits' spread below it.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import weights as wts

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fq(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(eq, x, w, x_axis, w_axis, fp8):
    if fp8:
        x, w = _fq(x, x_axis), _fq(w, w_axis)
    return jnp.einsum(eq, x, w, precision=jax.lax.Precision.HIGHEST)


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


def _layer(p, x, dims: wts.Dims, fp8: bool):
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


_layer_jit = jax.jit(_layer, static_argnames=("dims", "fp8"))


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


@jax.jit
def _gaps(ref_logits, tokens):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


def hidden(dims: wts.Dims, seed: int, tokens: np.ndarray, fp8: bool):
    """Final hidden states (B, S, d) of the padded token block."""
    x = _embed(wts.make_global(dims, seed, "embed/embedding"),
               jnp.asarray(tokens))
    for layer in range(dims.layers):
        x = _layer_jit(wts.make_layer(dims, seed, layer), x, dims, fp8)
    return x


def logits_at(dims: wts.Dims, seed: int, x, at: np.ndarray, fp8: bool):
    name = "embed/embedding" if dims.tied else "unembed/w"
    return _head_jit(x, jnp.asarray(at), wts.make_global(
        dims, seed, "final_norm/scale"), wts.make_global(dims, seed, name),
        dims.rms_eps, fp8, dims.tied)


def pack(requests: Sequence[Tuple[List[int], List[int]]], length: int,
         n_out: int):
    """(B, length) tokens of prompt + served tokens (the last served token
    is never fed back), zero-padded on the right, and for each request the
    ``n_out`` positions whose logits chose a served token, and those
    tokens. Positions and targets are padded by repeating the first (so
    padding adds no new gap)."""
    seqs = [list(p) + list(o[:-1]) for p, o in requests]
    s = length
    tokens = np.zeros((len(seqs), s), np.int32)
    at = np.zeros((len(seqs), n_out), np.int32)
    tgt = np.zeros((len(seqs), n_out), np.int32)
    for i, ((p, o), seq) in enumerate(zip(requests, seqs)):
        tokens[i, :len(seq)] = seq
        pos = [len(p) - 1 + j for j in range(len(o))]
        at[i] = pos + [pos[0]] * (n_out - len(o))
        tgt[i] = list(o) + [o[0]] * (n_out - len(o))
    return tokens, at, tgt


def served_gaps(dims: wts.Dims, seed: int, requests, *, length: int,
                n_out: int, control=False, batch: int = 8) -> np.ndarray:
    """Gap of every served token (``control=False``), or of the token the
    fp8 control puts first at the same positions (``control=True``), in
    blocks of ``batch`` requests of ``length`` tokens (one compiled shape
    for every block). Returns a flat array, one entry per served token."""
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(requests), batch):
            block = list(requests[i:i + batch])
            fill = block + [block[0]] * (batch - len(block))
            tokens, at, tgt = pack(fill, length, n_out)
            ref = logits_at(dims, seed, hidden(dims, seed, tokens, False),
                            at, False)
            if control:
                low = logits_at(dims, seed, hidden(dims, seed, tokens, True),
                                at, True)
                tgt = np.asarray(jnp.argmax(low, axis=-1), np.int32)
                del low
            gaps = np.asarray(_gaps(ref, jnp.asarray(tgt)))
            for j, (_, o) in enumerate(block):
                out.extend(gaps[j, :len(o)].tolist())
    return np.asarray(out, np.float64)
