"""The traffic generator: one seed, one schedule; every seed, one amount
of work; and how late the generator ran."""
import collections
import json

import pytest

from benchcase import REPO


def mix(name):
    return json.loads((REPO / "chipbench" / "mixes" / f"{name}.json")
                      .read_text())


def test_same_seed_same_arrivals():
    from chipbench import traffic
    m = mix("movie-interactive")
    a = traffic.arrivals(m, 30.0, 2**40 + 3)
    b = traffic.arrivals(m, 30.0, 2**40 + 3)
    assert a == b
    assert a != traffic.arrivals(m, 30.0, 2**40 + 4)


def test_every_seed_sends_the_same_work_in_another_order():
    from chipbench import traffic
    m = mix("movie-interactive")
    runs = [traffic.arrivals(m, 30.0, s) for s in (1, 99, 2**35)]
    n = round(m["rate_qps"] * 30.0)
    for arr in runs:
        assert len(arr) == n
        assert arr[0].due_s == 0.0 and max(a.due_s for a in arr) < 30.0
    key = lambda arr: sorted(collections.Counter(  # noqa: E731
        (a.query, a.tenant) for a in arr).items(), key=repr)
    queries = [collections.Counter(a.query for a in arr) for arr in runs]
    tenants = [collections.Counter(a.tenant for a in arr) for arr in runs]
    gaps = []
    for arr in runs:
        due = sorted(a.due_s for a in arr)
        gaps.append(sorted(round(b - a, 9) for a, b in zip(due, due[1:])))
    assert queries[0] == queries[1] == queries[2]
    assert tenants[0] == tenants[1] == tenants[2]
    assert key(runs[0]) != key(runs[1])          # paired differently
    # the same gaps, save the one the last arrival leaves to the close
    assert len(set(gaps[0]) & set(gaps[1])) >= len(gaps[0]) - 1


def test_zipf_tenants_and_exponential_gaps():
    from chipbench import traffic
    counts = traffic.tenant_counts(8, 1.1, 100)
    assert sum(counts) == 100 and counts == sorted(counts, reverse=True)
    assert counts[0] > 3 * counts[-1]
    g = traffic.exp_gaps(1000, 50.0)
    assert g.sum() == pytest.approx(50.0)
    assert abs(g.mean() - 0.05) < 1e-9 and g.max() > 5 * g.mean()


def test_closed_loop_clients_run_every_query():
    from chipbench import traffic
    m = mix("game-batch")
    seqs = traffic.client_sequences(m, 7, length=24)
    assert len(seqs) == 4
    for s in seqs:
        assert {q.name for q in s[:12]} == {f"q{i}" for i in range(1, 13)}
        assert len(set(s[:12])) == 12
    assert seqs == traffic.client_sequences(m, 7, length=24)
    assert seqs != traffic.client_sequences(m, 8, length=24)


def test_every_query_builds_a_plan_the_answer_key_knows():
    from chipbench import traffic
    from repro.data import load_dataset
    for name in ("movie-interactive", "game-batch"):
        m = mix(name)
        table, oracle = load_dataset(m["dataset"], max_rows=50)
        for q in traffic.queries(m):
            plan = traffic.build_plan(q, table)
            assert len(plan.ops) == len(q.op_dicts())
            for spec, op in zip(q.op_dicts(), plan.ops):
                if spec["kind"] == "filter" and spec["input"] in \
                        table.column_names:
                    oracle.answer(op, table.resolve(spec["input"])[0])


def test_lateness_report():
    from chipbench import traffic
    rep = traffic.lateness([0.0, 1.0, 2.0], [0.001, 1.0, 2.5])
    assert rep["n"] == 3
    assert rep["max_ms"] == pytest.approx(500.0)
    assert rep["mean_ms"] == pytest.approx(167.0)
