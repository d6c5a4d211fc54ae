"""Plain reference of the benchmark's served models, and its
lower-precision control: what every architecture's reference shares.

Each architecture (``chipbench/archs/<model_type>.py``) writes its own
forward from its model card and nothing of the program, in float32 at
the highest matmul precision, with its matmuls through :func:`_mm`. With
``fp8=True`` that forward is the control: every matmul operand is
rounded to float8 e4m3 with a scale per slice of the contraction
(weights per output channel, activations per token), as an fp8 serving
path computes, and multiplied in float32.

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


@jax.jit
def _gaps(ref_logits, tokens):
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return best - got


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


def served_gaps(arch, dims, seed: int, requests, *, length: int,
                n_out: int, control=False, batch: int = 8) -> np.ndarray:
    """Gap of every served token (``control=False``), or of the token the
    fp8 control puts first at the same positions (``control=True``), in
    blocks of ``batch`` requests of ``length`` tokens (one compiled shape
    for every block), by the forward of the architecture module ``arch``
    at its ``dims``. Returns a flat array, one entry per served token."""
    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(requests), batch):
            block = list(requests[i:i + batch])
            fill = block + [block[0]] * (batch - len(block))
            tokens, at, tgt = pack(fill, length, n_out)
            ref = arch.logits_at(dims, seed,
                                 arch.hidden(dims, seed, tokens, False),
                                 at, False)
            if control:
                low = arch.logits_at(dims, seed,
                                     arch.hidden(dims, seed, tokens, True),
                                     at, True)
                tgt = np.asarray(jnp.argmax(low, axis=-1), np.int32)
                del low
            gaps = np.asarray(_gaps(ref, jnp.asarray(tgt)))
            for j, (_, o) in enumerate(block):
                out.extend(gaps[j, :len(o)].tolist())
    return np.asarray(out, np.float64)
