"""jit'd public wrappers around the Pallas kernels.

Handles layout adaptation (models use (B, S, H, D); kernels want
(B, H, S, D)), padding to block multiples, and backend dispatch: on TPU the
kernels compile natively; on CPU they run in interpret mode so tests
validate the exact kernel bodies against the ref.py oracles. Any other
backend raises.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import decode_attention as dec_mod
from repro.kernels import flash_attention as fa_mod
from repro.kernels import similarity as sim_mod
from repro.kernels import ssd_scan as ssd_mod

NEG_INF = -1e30


def interpret_mode() -> bool:
    """Interpret mode on the CPU only; any other non-TPU backend raises
    rather than silently running the kernels in the interpreter."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                       f"{backend!r}; use a TPU or JAX_PLATFORMS=cpu")


def _pad_axis(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


# ---------------------------------------------------------------------------
# Flash attention (train/prefill)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 0, bk: int = 0):
    """Model layout: q (B, Sq, Hq, D); k/v (B, Sk, Hkv, D).
    Returns (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    bq = bq or min(fa_mod.DEFAULT_BQ, max(8, sq))
    bk = bk or min(fa_mod.DEFAULT_BK, max(8, sk))
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)
    qt, sq0 = _pad_axis(qt, 2, bq)
    kt, sk0 = _pad_axis(kt, 2, bk)
    vt, _ = _pad_axis(vt, 2, bk)
    out = fa_mod.flash_attention(
        qt, kt, vt, causal=causal, window=window,
        q_offset=(sk0 - sq0) if causal else 0, sk_valid=sk0, bq=bq, bk=bk,
        interpret=interpret_mode())
    out = out[:, :, :sq0]
    return jnp.moveaxis(out, 1, 2)


# ---------------------------------------------------------------------------
# Decode attention (serve_step)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("bk",))
def decode_attention(q, k_cache, v_cache, cache_len, *, bk: int = 0):
    """Model layout: q (B, 1, Hq, D); caches (B, S, Hkv, D);
    cache_len scalar or (B,). Returns (B, 1, Hq, D)."""
    b, one, hq, d = q.shape
    s = k_cache.shape[1]
    bk = bk or min(dec_mod.DEFAULT_BK, max(8, s))
    qt = jnp.moveaxis(q, 2, 1)                      # (B, Hq, 1, D)
    kt = jnp.moveaxis(k_cache, 2, 1)
    vt = jnp.moveaxis(v_cache, 2, 1)
    kt, s0 = _pad_axis(kt, 2, bk)
    vt, _ = _pad_axis(vt, 2, bk)
    cl = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    out = dec_mod.decode_attention(qt, kt, vt, cl, bk=bk,
                                   interpret=interpret_mode())
    return jnp.moveaxis(out, 1, 2)


# ---------------------------------------------------------------------------
# SSD scan (Mamba2 / Hymba)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(dx, dA, B, C, initial_state=None, *, chunk: int = 0):
    """dx (B,S,H,P); dA (B,S,H); B/C (B,S,G,N). Returns (y, final_state)."""
    b, s, h, p = dx.shape
    chunk = chunk or min(ssd_mod.DEFAULT_CHUNK, s)
    while s % chunk:
        chunk //= 2
    return ssd_mod.ssd_scan(dx, dA, B, C, initial_state, chunk=chunk,
                            interpret=interpret_mode())


# ---------------------------------------------------------------------------
# Similarity (improvement score / judge)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("interpret",))
def cosine_matrix_jit(a, b, *, interpret: bool):
    """The compiled program behind :func:`cosine_matrix`."""
    a, m0 = _pad_axis(a, 0, sim_mod.BM)
    b, n0 = _pad_axis(b, 0, sim_mod.BN)
    out = sim_mod.cosine_matrix(a, b, interpret=interpret)
    return out[:m0, :n0]


def cosine_matrix(a, b):
    """(M, D) x (N, D) -> (M, N) fp32 cosine (rows pre-normalized)."""
    return np.asarray(cosine_matrix_jit(jnp.asarray(a), jnp.asarray(b),
                                        interpret=interpret_mode()))


@functools.partial(jax.jit, static_argnames=("interpret",))
def rowwise_cosine_jit(a, b, *, interpret: bool):
    """The compiled program behind :func:`rowwise_cosine`."""
    a, m0 = _pad_axis(a, 0, sim_mod.BM)
    b, _ = _pad_axis(b, 0, sim_mod.BM)
    out = sim_mod.rowwise_cosine(a, b, interpret=interpret)
    return out[:m0]


def rowwise_cosine(a, b):
    """Aligned pairs (M, D), (M, D) -> (M,) fp32 cosine."""
    return np.asarray(rowwise_cosine_jit(jnp.asarray(a), jnp.asarray(b),
                                         interpret=interpret_mode()))
