"""Flash attention (prefill/train) — Pallas TPU kernel.

Online-softmax tiling adapted to the TPU memory hierarchy: Q/K/V blocks are
staged HBM->VMEM by BlockSpec; the running (max, denominator, accumulator)
live in VMEM scratch across the *sequential* innermost KV grid dimension, so
the S x S score matrix never exists in HBM and every matmul hits the MXU
with 128-aligned operands. GQA is handled in the K/V index_map (query head
h reads KV head h // group) — no K/V replication in memory.

Grid: (batch, q_heads, Sq/bq, Sk/bk), dimension_semantics
("parallel", "parallel", "parallel", "arbitrary"). Causal blocks that are
fully masked are skipped with pl.when (upper-triangle block skip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BQ = 128
DEFAULT_BK = 128


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, sk_valid: int, causal: bool, window: int,
            q_offset: int, scale: float):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq + q_offset          # absolute position of q block
    k_start = ki * bk

    def compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < sk_valid          # excludes block-padding keys
        if causal:
            mask &= q_pos >= k_pos
        if window > 0:
            mask &= q_pos - k_pos < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                             # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                    # (bq, 1)
        l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, d)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new
        l_scr[...] = l_new

    if causal:
        # skip blocks strictly above the diagonal (no valid positions)
        pl.when(q_start + bq - 1 >= k_start)(compute)
    else:
        compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0, ...] = (acc_scr[...] /
                            jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, sk_valid: int = 0,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK, scale=None,
                    interpret: bool = False):
    """q: (B, Hq, Sq, D); k/v: (B, Hkv, Sk, D). Sq % bq == Sk % bk == 0
    (ops.py pads; sk_valid = unpadded key count, 0 = all valid).
    Returns (B, Hq, Sq, D) in q.dtype."""
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    scale = float(scale if scale is not None else d ** -0.5)
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)

    grid = (b, hq, sq // bq, sk // bk)
    kern = functools.partial(
        _kernel, bq=bq, bk=bk, sk_valid=int(sk_valid) or sk, causal=causal,
        window=int(window), q_offset=int(q_offset), scale=scale)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bi, h, qi, ki: (bi, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, h, qi, ki, g=g: (bi, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, h, qi, ki, g=g: (bi, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, h, qi, ki: (bi, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
