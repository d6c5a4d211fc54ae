"""One generator for every traffic mix (``chipbench/mixes/<name>.json``).

A mix file holds only data: the dataset, the loop (``open``: arrivals at a
fixed rate; ``closed``: clients that each wait for their answer), the
tenants and their skew, the cascade bands, and the queries, each a list
of operators (kind, instruction, input and output column).

The same seed gives the same traffic. Every seed gives the same amount of
work in another order: the set of queries, of inter-arrival gaps and of
tenants is fixed by the mix and the window's length, and the seed only
shuffles them.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Query:
    name: str
    ops: Tuple[Tuple[Tuple[str, str], ...], ...]   # each op: sorted items

    def op_dicts(self) -> List[Dict[str, str]]:
        return [dict(op) for op in self.ops]


@dataclasses.dataclass(frozen=True)
class Arrival:
    due_s: float
    query: Query
    tenant: str


def queries(mix: dict) -> List[Query]:
    """The mix's queries, in the file's order."""
    return [Query(t["name"], tuple(tuple(sorted(op.items()))
                                   for op in t["ops"]))
            for t in mix["queries"]]


def query_set(mix: dict, n: int) -> List[Query]:
    """``n`` queries, the mix's taken in turn: fixed by the mix and n."""
    qs = queries(mix)
    return [qs[i % len(qs)] for i in range(n)]


def tenant_counts(count: int, zipf_s: float, n: int) -> List[int]:
    """How many of ``n`` queries each of ``count`` tenants sends, in
    proportion to 1 / rank ** zipf_s, rounded so the counts add to n."""
    w = np.array([1.0 / (r + 1) ** zipf_s for r in range(count)])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def exp_gaps(n: int, seconds: float) -> np.ndarray:
    """``n`` gaps at the quantiles of an exponential distribution (a
    Poisson process's inter-arrival times), scaled to add to ``seconds``."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g * (seconds / g.sum())


def arrivals(mix: dict, seconds: float, seed: int) -> List[Arrival]:
    """Open loop: ``rate_qps * seconds`` queries due in [0, seconds)."""
    n = max(1, int(round(float(mix["rate_qps"]) * seconds)))
    rng = np.random.default_rng(seed)
    gaps = rng.permutation(exp_gaps(n, seconds))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    queries = query_set(mix, n)
    order = rng.permutation(n)
    ten = mix["tenants"]
    tenants = [f"t{k}" for k, c in enumerate(
        tenant_counts(int(ten["count"]), float(ten["zipf_s"]), n))
        for _ in range(c)]
    tenants = [tenants[i] for i in rng.permutation(n)]
    return [Arrival(float(due[i]), queries[order[i]], tenants[i])
            for i in range(n)]


def client_sequences(mix: dict, seed: int, length: int) -> List[List[Query]]:
    """Closed loop: each client's first ``length`` queries, every client
    running all the mix's queries in its own seeded order, repeated."""
    rng = np.random.default_rng(seed)
    base = queries(mix)
    out = []
    for _ in range(int(mix["clients"])):
        seq: List[Query] = []
        while len(seq) < length:
            seq.extend(base[i] for i in rng.permutation(len(base)))
        out.append(seq[:length])
    return out


def build_plan(query: Query, table):
    """The query's logical plan over ``table``, through the program's
    public dataframe API."""
    from repro.core.dataframe import SemanticDataFrame
    df = SemanticDataFrame(table)
    for op in query.op_dicts():
        kind = op["kind"]
        if kind == "filter":
            df = df.semantic_filter(op["instruction"], op["input"])
        elif kind == "map":
            df = df.semantic_map(op["instruction"], op["input"], op["output"])
        elif kind == "reduce":
            df = df.semantic_reduce(op["instruction"], op["input"])
        else:
            raise ValueError(f"unknown operator kind {kind!r}")
    return df.plan()


def lateness(due: Sequence[float], sent: Sequence[float]) -> dict:
    """How late the generator sent each query against its schedule."""
    late = np.maximum(0.0, np.asarray(sent) - np.asarray(due))
    if not len(late):
        return {"n": 0, "mean_ms": 0.0, "p99_ms": 0.0, "max_ms": 0.0}
    return {"n": int(len(late)), "mean_ms": float(late.mean() * 1e3),
            "p99_ms": float(np.percentile(late, 99) * 1e3),
            "max_ms": float(late.max() * 1e3)}

