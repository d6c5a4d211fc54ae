"""Decode attention (one token vs long KV cache) — Pallas TPU kernel.

Flash-decoding adapted to the TPU's sequential grid: decode is memory-bound
(the whole KV cache streams HBM->VMEM once; arithmetic intensity ~1 FLOP/B),
so the kernel's job is to keep that stream dense and never materialize
logits in HBM. The KV sequence is split into blocks ("split-K"); partial
(max, sum, acc) merge across the sequential last grid dimension in VMEM
scratch — the TPU analogue of the GPU version's cross-SM reduction tree.

Grid: (batch, q_heads, S/bk). The q row for a head is tiny (1 x D); it is
re-read per block from VMEM, which is free compared to the KV stream.
Variable cache lengths are masked from a scalar-prefetch cache_len vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BK = 512


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bk: int, scale: float):
    bi = pl.program_id(0)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    cache_len = len_ref[bi]
    k_start = ki * bk

    @pl.when(k_start < cache_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (1, d)
        k = k_ref[0, 0].astype(jnp.float32)                # (bk, d)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (1, bk)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where(k_pos < cache_len, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                             # (1, bk)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)                # (bk, d)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0, 0, ...] = (acc_scr[...] /
                            jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *,
                     bk: int = DEFAULT_BK, interpret: bool = False):
    """q: (B, Hq, 1, D); caches: (B, Hkv, S, D); cache_len: (B,) int32.
    Returns (B, Hq, 1, D) in q.dtype."""
    b, hq, one, d = q.shape
    assert one == 1
    _, hkv, s, _ = k_cache.shape
    g = hq // hkv
    scale = float(d ** -0.5)
    bk = min(bk, s)
    assert s % bk == 0, (s, bk)
    cache_len = jnp.asarray(cache_len, jnp.int32).reshape(b)

    grid = (b, hq, s // bk)
    kern = functools.partial(_kernel, bk=bk, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, d), lambda bi, h, ki, *_: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, h, ki, *_, g=g: (bi, h // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda bi, h, ki, *_, g=g: (bi, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d),
                               lambda bi, h, ki, *_: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(cache_len, q, k_cache, v_cache)
