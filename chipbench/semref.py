"""Plain references for the query path: the tier-0 embedding and a
row-by-row evaluation of a query.

The embedding is the published recipe of the cascade's encoder, written
out again here: word unigrams and character 2- and 3-grams of the
lower-cased text, each hashed with BLAKE2b (4 bytes, little-endian) into
one of 256 signed buckets, then L2-normalised. A score is the dot
product of a row's and the instruction's embeddings, in float64.

A query is evaluated operator by operator over the whole table: a cascaded
filter keeps a row whose score is at or above the upper band, drops one at
or below the lower band, and asks the dataset's answer key otherwise;
every other operator asks the answer key. The answer key (the dataset's
oracle) is data, like the table: it is what the engine tier answers in
echo mode.
"""
from __future__ import annotations

import hashlib
import re
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

DIM = 256


def _text(x) -> str:
    if isinstance(x, bool):
        x = "true" if x else "false"
    elif isinstance(x, float) and x == int(x):
        x = int(x)
    s = str(x).lower().strip()
    s = re.sub(r"[^\w\s\.]", " ", s)
    return re.sub(r"\s+", " ", s)


def embed(x) -> np.ndarray:
    s = _text(x)
    feats = ["w:" + w for w in s.split()]
    padded = "^" + s.replace(" ", "_") + "$"
    for n in (2, 3):
        feats += [padded[i:i + n] for i in range(len(padded) - n + 1)]
    v = np.zeros(DIM, np.float64)
    for f in feats:
        h = int.from_bytes(hashlib.blake2b(f.encode(), digest_size=4)
                           .digest(), "little")
        v[h % DIM] += 1.0 if (h >> 31) & 1 else -1.0
    n = np.linalg.norm(v)
    return v / n if n > 0 else v


class Evaluator:
    """Row-by-row reference evaluation over one table, with embeddings
    and the answer key's answers kept per distinct value."""

    def __init__(self, table, oracle, bands: Optional[Tuple[float, float]]):
        self.table = table
        self.oracle = oracle
        self.bands = bands
        self.cols = {c: table.resolve(c) for c in table.column_names}
        self._emb: dict = {}
        self._ans: dict = {}

    def _embed(self, x) -> np.ndarray:
        k = value_key(x)
        if k not in self._emb:
            self._emb[k] = embed(x)
        return self._emb[k]

    def scores(self, instruction: str, values: Sequence[Any]) -> np.ndarray:
        a = embed(instruction)
        return np.array([self._embed(v) @ a for v in values], np.float64)

    def _answer(self, op, v):
        k = (op.kind, op.instruction, value_key(v))
        if k not in self._ans:
            self._ans[k] = self.oracle.answer(op, v)
        return self._ans[k]

    def walk(self, ops: List[dict], plan_ops):
        """Yield (spec, operator, input values) for each operator in turn,
        over the rows that reach it; returns the answer: ('rows', row ids)
        or ('scalar', value)."""
        rows = list(range(self.table.n_rows))
        cols = dict(self.cols)
        for spec, op in zip(ops, plan_ops):
            vals = [cols[spec["input"]][r] for r in rows]
            yield spec, op, vals
            kind = spec["kind"]
            if kind == "reduce":
                return "scalar", self.oracle.answer_reduce(op, vals)
            if kind == "map":
                full = [None] * self.table.n_rows
                for r, v in zip(rows, vals):
                    full[r] = self._answer(op, v)
                cols[spec["output"]] = full
                continue
            bands = self.bands
            s = self.scores(spec["instruction"], vals) if bands else None
            keep = []
            for i, (r, v) in enumerate(zip(rows, vals)):
                if bands and s[i] >= bands[1]:
                    keep.append(r)
                elif bands and s[i] <= bands[0]:
                    continue
                elif self._answer(op, v):
                    keep.append(r)
            rows = keep
        return "rows", rows

    def evaluate(self, ops: List[dict], plan_ops):
        """The reference answer of a query (see :meth:`walk`)."""
        it = self.walk(ops, plan_ops)
        while True:
            try:
                next(it)
            except StopIteration as done:
                return done.value


def value_key(v) -> str:
    """A value's identity as the output cache keys it."""
    return v if isinstance(v, str) else repr(v)


def scores(instruction: str, values: Sequence[Any]) -> np.ndarray:
    a = embed(instruction)
    return np.array([embed(v) @ a for v in values], np.float64)
