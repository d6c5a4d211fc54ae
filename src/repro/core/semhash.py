"""Deterministic text embedder for semantic-equality checks.

Plays the role Sentence-BERT plays in the paper (§4.2): map operator outputs
to vectors; two outputs are "semantically equal" when their cosine
similarity clears a threshold. Here the embedder is a character n-gram
feature hasher — deterministic, dependency-free, and order-insensitive
enough that reformatted-but-equal outputs ("250 USD" vs "USD 250.0") land
close while corrupted outputs land far.

The batched cosine(similarity-matrix) compute is the paper-specific hot
spot (every improvement-score evaluation and every judge call runs it over
sample batches); ``repro.kernels.similarity`` provides the Pallas TPU
kernel; this module's ``cosine_matrix`` is the pure-jnp path used on CPU
and as the kernel's oracle.
"""
from __future__ import annotations

import hashlib
import re
from typing import List, Sequence

import numpy as np

DIM = 256
_NGRAMS = (2, 3)


def _normalize_text(x) -> str:
    if isinstance(x, bool):
        return "true" if x else "false"
    if isinstance(x, float) and x == int(x):
        x = int(x)
    s = str(x).lower().strip()
    s = re.sub(r"[^\w\s\.]", " ", s)
    s = re.sub(r"\s+", " ", s)
    return s


def _h(token: str) -> int:
    return int.from_bytes(
        hashlib.blake2b(token.encode(), digest_size=4).digest(), "little")


def embed_one(x, dim: int = DIM) -> np.ndarray:
    """Hash word unigrams + char n-grams into a signed feature vector."""
    s = _normalize_text(x)
    v = np.zeros((dim,), np.float32)
    words = s.split()
    feats: List[str] = ["w:" + w for w in words]
    padded = "^" + s.replace(" ", "_") + "$"
    for n in _NGRAMS:
        feats.extend(padded[i:i + n] for i in range(len(padded) - n + 1))
    for f in feats:
        h = _h(f)
        v[h % dim] += 1.0 if (h >> 31) & 1 else -1.0
    n = np.linalg.norm(v)
    return v / n if n > 0 else v


def embed(xs: Sequence, dim: int = DIM) -> np.ndarray:
    return np.stack([embed_one(x, dim) for x in xs]) if len(xs) else \
        np.zeros((0, dim), np.float32)


def cosine_matrix(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Rows are already L2-normalized -> plain GEMM."""
    return a @ b.T


def pairwise_similarity(xs: Sequence, ys: Sequence) -> np.ndarray:
    """cos(x_i, y_i) for aligned pairs (the improvement-score compare)."""
    if len(xs) != len(ys):
        raise ValueError("pairwise_similarity needs aligned sequences")
    if not len(xs):
        return np.zeros((0,), np.float32)
    a, b = embed(xs), embed(ys)
    return np.sum(a * b, axis=1)


SEM_EQ_THRESHOLD = 0.80


def semantic_equal(x, y, threshold: float = SEM_EQ_THRESHOLD) -> bool:
    """Single-pair semantic equality (binary outputs compare directly)."""
    if isinstance(x, bool) or isinstance(y, bool):
        return bool(x) == bool(y)
    if isinstance(x, (int, float)) and isinstance(y, (int, float)):
        scale = max(abs(float(x)), abs(float(y)), 1e-9)
        return abs(float(x) - float(y)) / scale < 0.02
    if x is None or y is None:
        return x is y
    return float(np.dot(embed_one(x), embed_one(y))) >= threshold


def semantic_equal_batch(xs: Sequence, ys: Sequence,
                         threshold: float = SEM_EQ_THRESHOLD,
                         use_kernel: bool = True) -> np.ndarray:
    """Vectorized aligned-pair equality. Dispatches the cosine compute to
    the Pallas kernel (``kernels.ops`` picks interpret mode on the CPU)."""
    if len(xs) != len(ys):
        raise ValueError("aligned sequences required")
    if not len(xs):
        return np.zeros((0,), bool)
    fast = [i for i in range(len(xs))
            if isinstance(xs[i], (bool, int, float))
            or isinstance(ys[i], (bool, int, float))
            or xs[i] is None or ys[i] is None]
    out = np.zeros((len(xs),), bool)
    text_idx = [i for i in range(len(xs)) if i not in set(fast)]
    for i in fast:
        out[i] = semantic_equal(xs[i], ys[i], threshold)
    if text_idx:
        a = embed([xs[i] for i in text_idx])
        b = embed([ys[i] for i in text_idx])
        if use_kernel:
            from repro.kernels import ops as kops
            sims = np.asarray(kops.rowwise_cosine(a, b))
        else:
            sims = np.sum(a * b, axis=1)
        for j, i in enumerate(text_idx):
            out[i] = sims[j] >= threshold
    return out
