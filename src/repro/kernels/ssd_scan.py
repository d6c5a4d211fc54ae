"""Mamba2 SSD chunked scan — Pallas TPU kernel.

The SSD dual form splits the sequence into chunks: inside a chunk the
output is a (masked, decay-weighted) L x L matmul — MXU work; across chunks
a small (N x P) state carries the recurrence. On TPU the natural mapping is
a *sequential* chunk grid dimension with the state living in VMEM scratch
between grid steps (the GPU version's inter-block shared-memory handoff has
no TPU analogue; the sequential-grid carry is the idiomatic replacement —
see DESIGN.md §Hardware-adaptation).

Grid: (batch, heads, S/L) with dimension_semantics ("parallel", "parallel",
"arbitrary"). B/C group projections are mapped per-head in the index_map
(head h reads group h // (H/G)) — the GQA-analogue of the SSD duality.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 256


def _kernel(dx_ref, dA_ref, b_ref, c_ref, init_ref, y_ref, fin_ref,
            state_scr, *, chunk: int):
    ci = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = init_ref[0, 0].astype(jnp.float32)

    dx = dx_ref[0, :, 0].astype(jnp.float32)      # (L, P)
    dA = dA_ref[0, :, 0].astype(jnp.float32)      # (L,)
    B = b_ref[0, :, 0].astype(jnp.float32)        # (L, N)
    C = c_ref[0, :, 0].astype(jnp.float32)        # (L, N)
    state = state_scr[...]                        # (N, P)

    cs = jnp.cumsum(dA)                           # (L,) inclusive log-decay
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))  # (L, L)
    delta = cs[:, None] - cs[None, :]
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # mask before exp (upper-triangle deltas overflow; see models/ssm.py)
    m = scores * jnp.exp(jnp.where(li >= si, delta, -1e30))
    y_diag = jax.lax.dot_general(m, dx, (((1,), (0,)), ((), ())))  # (L, P)

    # incoming-state contribution, decayed from chunk start to each step
    y_off = jax.lax.dot_general(C * jnp.exp(cs)[:, None], state,
                                (((1,), (0,)), ((), ())))          # (L, P)
    y_ref[0, :, 0] = (y_diag + y_off).astype(y_ref.dtype)

    # state update: decay to chunk end
    dec_end = jnp.exp(cs[-1] - cs)                # (L,)
    state_new = jax.lax.dot_general(B * dec_end[:, None], dx,
                                    (((0,), (0,)), ((), ())))      # (N, P)
    state_scr[...] = state * jnp.exp(cs[-1]) + state_new

    @pl.when(ci == nc - 1)
    def _finalize():
        fin_ref[0, 0] = state_scr[...]


def ssd_scan(dx, dA, B, C, initial_state=None, *,
             chunk: int = DEFAULT_CHUNK, interpret: bool = False):
    """Chunked SSD scan.

    dx: (B, S, H, P); dA: (B, S, H); B/C: (B, S, G, N). S % chunk == 0
    (ops.py pads). Returns (y (B,S,H,P) in dx.dtype, final_state
    (B,H,N,P) fp32).
    """
    b, s, h, p = dx.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    if initial_state is None:
        initial_state = jnp.zeros((b, h, n, p), jnp.float32)
    dA3 = dA[..., None]                            # (B,S,H,1) — 2D-tileable

    grid = (b, h, s // chunk)
    kern = functools.partial(_kernel, chunk=chunk)
    y, fin = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1, 1),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci, rep=rep: (bi, ci, hi // rep, 0)),
            pl.BlockSpec((1, chunk, 1, n),
                         lambda bi, hi, ci, rep=rep: (bi, ci, hi // rep, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, 1, p),
                         lambda bi, hi, ci: (bi, ci, hi, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, s, h, p), dx.dtype),
            jax.ShapeDtypeStruct((b, h, n, p), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dx, dA3, B, C, initial_state)
    return y, fin
