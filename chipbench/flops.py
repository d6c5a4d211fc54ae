"""Operations and bytes that the benchmark's work needs, from its shapes.

Counted for what the algorithm needs, not for what the program happens to
compute: real prompt tokens and the tokens live slots decode (no padding,
no idle slots), the output head once per chosen token, and the cascade
kernel's real rows. A multiply-add is two operations.
"""
from __future__ import annotations

from chipbench.weights import Dims


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


def rowwise_cosine_cost(rows: int, dim: int = 256):
    """(operations, bytes) of the aligned-pair cosine over ``rows`` f32
    rows of ``dim``: one multiply-add per element; both operands read and
    one f32 score written per row."""
    return 2 * rows * dim, 2 * rows * dim * 4 + rows * 4
