"""Operations and bytes that the benchmark's work needs, from its shapes.

Counted for what the algorithm needs, not for what the program happens to
compute: real prompt tokens and the tokens live slots decode (no padding,
no idle slots), the output head once per chosen token, and the cascade
kernel's real rows. A multiply-add is two operations. A model's own
counts are its architecture's (``prefill_flops`` and ``decode_flops`` of
``chipbench/archs/<model_type>.py``); the cascade kernel's are here.
"""
from __future__ import annotations


def rowwise_cosine_cost(rows: int, dim: int = 256):
    """(operations, bytes) of the aligned-pair cosine over ``rows`` f32
    rows of ``dim``: one multiply-add per element; both operands read and
    one f32 score written per row."""
    return 2 * rows * dim, 2 * rows * dim * 4 + rows * 4
