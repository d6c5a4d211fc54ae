"""Semantic-similarity kernels — the paper-specific compute hot spot.

Every improvement-score evaluation (Eq. 2) and every LLM-as-a-judge rating
compares batches of operator outputs by embedding cosine (§4.2 uses
Sentence-BERT). The embeddings are L2-normalized, so the comparison is a
plain GEMM — but it runs per optimizer iteration over every sampled record
pair, so it gets the kernel treatment:

  cosine_matrix   (M, D) x (N, D) -> (M, N): tiled MXU GEMM, full-D panels
                  in VMEM (embedding D is small: 256).
  rowwise_cosine  aligned pairs (M, D), (M, D) -> (M,): one fused
                  multiply-reduce sweep (used by semantic_equal_batch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM = 128
BN = 128


def _pad_rows(x, block: int):
    """Pad axis 0 up to a multiple of ``block`` (zero rows are inert for
    both kernels: a zero embedding row dots to 0). Returns (padded, m0)."""
    m0 = x.shape[0]
    pad = (-m0) % block
    if pad == 0:
        return x, m0
    return jnp.pad(x, ((0, pad), (0, 0))), m0


def _matrix_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())))


def cosine_matrix(a, b, *, bm: int = BM, bn: int = BN,
                  interpret: bool = False):
    """a: (M, D), b: (N, D), rows L2-normalized. Returns (M, N) fp32.

    Arbitrary M/N: inputs are padded up to block multiples and the result
    is sliced back, so callers (morsels, embedding cascades) never need
    divisibility — M=1 and M=BM+1 both work."""
    if a.shape[0] == 0 or b.shape[0] == 0:
        return jnp.zeros((a.shape[0], b.shape[0]), jnp.float32)
    a, m0 = _pad_rows(a, min(bm, a.shape[0]))
    b, n0 = _pad_rows(b, min(bn, b.shape[0]))
    m, d = a.shape
    n, _ = b.shape
    bm = min(bm, m)
    bn = min(bn, n)
    out = pl.pallas_call(
        _matrix_kernel,
        grid=(m // bm, n // bn),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(a, b)
    return out if (m0 == m and n0 == n) else out[:m0, :n0]


def _rowwise_kernel(a_ref, b_ref, o_ref):
    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.sum(a * b, axis=-1, keepdims=True)


def rowwise_cosine(a, b, *, bm: int = BM, interpret: bool = False):
    """Aligned-pair cosine: (M, D), (M, D) -> (M,) fp32. Arbitrary M:
    rows pad up to a block multiple and the result slices back."""
    if a.shape[0] == 0:
        return jnp.zeros((0,), jnp.float32)
    a, m0 = _pad_rows(a, min(bm, a.shape[0]))
    b, _ = _pad_rows(b, min(bm, b.shape[0]))
    m, d = a.shape
    bm = min(bm, m)
    out = pl.pallas_call(
        _rowwise_kernel,
        grid=(m // bm,),
        in_specs=[
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
            pl.BlockSpec((bm, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(a, b)
    return out[:m0, 0]
