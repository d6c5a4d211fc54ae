"""Streaming semantic serve suite: continuous query admission onto one
shared dispatcher (launch.query_server.QueryServer) — admission-order
invariance of per-query results and meter totals vs solo runs, failure
isolation per handle, server-lifetime meter accounting, cross-tenant
serving quotas, per-query round-robin shard cursors, and the long-lived
shutdown paths (ExecutionContext.close, OutputCache.close, linger-ticker
stop)."""
import threading
import time

import pytest

from repro.core import backends as bk
from repro.core import executor as ex
from repro.core import plan as P
from repro.core import runtime as rt
from repro.distributed.morsel_shards import ShardedDispatcher
from repro.launch.query_server import QueryServer
from repro.testing import (KindOracle, SleepBackend, result_fingerprint,
                           tagged_plan, tagged_table)

SERVE_SHARDS = (1, 2)

# shared with benchmarks/bench_serve.py (one definition in repro.testing):
# per-query plans carry distinct instructions, so queries sharing the
# server cache never overlap on cache keys — their billing is then
# independent of co-tenants, which is what solo-identity asserts
_table = tagged_table
_plan = tagged_plan
_result_key = result_fingerprint


def _meter_key(meter):
    return {t: (u.calls, round(u.tok_in, 6), round(u.tok_out, 6),
                round(u.usd, 9), round(u.latency_s, 6))
            for t, u in sorted(meter.by_tier.items())}


def _ctx(shards: int = 1, delay_s: float = 0.004, **kw):
    backend = SleepBackend(KindOracle(), delay_s=delay_s)
    defaults = dict(backends={"m*": backend}, default_tier="m*",
                    concurrency=4, morsel_size=8, driver="threads",
                    shards=shards)
    defaults.update(kw)
    return rt.ExecutionContext(**defaults), backend


def _solo(plan, table, **kw):
    ctx, _ = _ctx(**kw)
    with ctx:
        meter = ctx.meter
        res = ex.execute(plan, table, ctx,
                         dispatcher=ctx.dispatcher())
    return res, meter


# ---------------------------------------------------------------------------
# Admission-order invariance: the serving isolation contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shards", SERVE_SHARDS)
def test_serve_concurrent_queries_match_solo_runs(shards):
    """Two queries admitted concurrently (threads driver) produce
    results AND per-query meter totals byte-identical to each query run
    solo — sharing the server's dispatcher/cache changes when calls run,
    never what they answer or bill."""
    specs = [("qa", False), ("qb", True)]
    want = {tag: (_result_key(r), _meter_key(m))
            for tag, tail in specs
            for r, m in [_solo(_plan(tag, tail), _table(tag),
                               shards=shards)]}
    ctx, _ = _ctx(shards=shards)
    with QueryServer(ctx) as server:
        handles = {tag: server.submit(_plan(tag, tail), _table(tag),
                                      name=tag)
                   for tag, tail in specs}
        got = {tag: (_result_key(h.result(timeout=30)),
                     _meter_key(h.meter))
               for tag, h in handles.items()}
    assert got == want


@pytest.mark.parametrize("shards", SERVE_SHARDS)
def test_serve_admission_order_is_invariant(shards):
    """Submitting [A, B] vs [B, A] yields identical per-query results
    and meter totals — nothing a query answers or bills depends on its
    admission position."""
    specs = [("qa", False), ("qb", True), ("qc", False)]
    runs = []
    for order in (specs, specs[::-1]):
        ctx, _ = _ctx(shards=shards)
        with QueryServer(ctx) as server:
            handles = [(tag, server.submit(_plan(tag, tail), _table(tag)))
                       for tag, tail in order]
            runs.append({tag: (_result_key(h.result(timeout=30)),
                               _meter_key(h.meter))
                         for tag, h in handles})
    assert runs[0] == runs[1]


def test_serve_per_query_logs_are_deterministic():
    """Each handle's finalized call log (entries + logical keys) is
    byte-identical across two server runs: per-query staging merges sort
    by the query-scoped logical key, not thread arrival order."""
    specs = [("qa", False), ("qb", True)]
    runs = []
    for _ in range(2):
        ctx, _ = _ctx(shards=2)
        with QueryServer(ctx) as server:
            handles = [(tag, server.submit(_plan(tag, tail), _table(tag)))
                       for tag, tail in specs]
            for _, h in handles:
                h.result(timeout=30)
            runs.append({tag: (list(h.meter.call_log),
                               list(h.meter.call_keys))
                         for tag, h in handles})
    assert runs[0] == runs[1]
    for log, keys in runs[0].values():
        assert log and all(k is not None for k in keys)


def test_serve_batched_coalesced_queries_match_solo():
    """Coalesced batch formation stays query-scoped on a shared server:
    with batch_size > 1 each query still pays ceil(survivors/batch)
    calls, and its outputs match the solo run."""
    specs = [("qa", False), ("qb", False)]
    want = {tag: (_result_key(r), _meter_key(m))
            for tag, tail in specs
            for r, m in [_solo(_plan(tag, tail), _table(tag),
                               batch_size=8)]}
    ctx, backend = _ctx(batch_size=8)
    with QueryServer(ctx) as server:
        handles = {tag: server.submit(_plan(tag, tail), _table(tag))
                   for tag, tail in specs}
        got = {tag: (_result_key(h.result(timeout=30)),
                     _meter_key(h.meter))
               for tag, h in handles.items()}
    assert got == want
    # 32 rows / batch 8 = 4 calls per op per query; nothing cross-filled
    assert all(h.meter.total.calls == 8 for h in handles.values())


def test_serve_simulated_driver_queries_match_solo():
    """The server also runs the simulated driver (inline execution, one
    shared lock-protected event scheduler): per-query results and meter
    totals still match solo runs."""
    specs = [("qa", True), ("qb", False)]
    want = {tag: (_result_key(r), _meter_key(m))
            for tag, tail in specs
            for r, m in [_solo(_plan(tag, tail), _table(tag),
                               driver="simulated", delay_s=0.0)]}
    ctx, _ = _ctx(driver="simulated", delay_s=0.0)
    with QueryServer(ctx) as server:
        handles = {tag: server.submit(_plan(tag, tail), _table(tag))
                   for tag, tail in specs}
        got = {tag: (_result_key(h.result(timeout=30)),
                     _meter_key(h.meter))
               for tag, h in handles.items()}
    assert got == want


# ---------------------------------------------------------------------------
# Failure isolation
# ---------------------------------------------------------------------------

class _BoomOracle(KindOracle):
    def answer(self, op, value):
        if "BOOM" in str(value):
            raise RuntimeError("backend down for this tenant")
        return super().answer(op, value)


@pytest.mark.parametrize("shards", SERVE_SHARDS)
def test_serve_failure_poisons_only_its_own_handle(shards):
    """One query's backend failure fails that query's handle; the other
    in-flight query completes correctly, and the server keeps admitting
    new queries afterwards."""
    backend = SleepBackend(_BoomOracle(), delay_s=0.002)
    ctx = rt.ExecutionContext(backends={"m*": backend}, default_tier="m*",
                              concurrency=4, morsel_size=8,
                              driver="threads", shards=shards)
    with QueryServer(ctx) as server:
        good = server.submit(_plan("ok"), _table("ok"))
        bad = server.submit(_plan("bad"), _table("BOOM"))
        with pytest.raises(RuntimeError, match="backend down"):
            bad.result(timeout=30)
        assert bad.failed()
        res = good.result(timeout=30)
        assert not good.failed()
        assert res.table.columns["a"] == [f"A:ok-{i}" for i in range(32)]
        # the server survives a tenant failure: admit another query
        after = server.submit(_plan("after"), _table("after"))
        assert after.result(timeout=30).table.n_rows == 32
        stats = server.stats()
    assert stats == {**stats, "admitted": 3, "completed": 2, "failed": 1}


def test_serve_failed_query_bills_all_straggler_calls():
    """Per-query cleanup waits for the failed query's sibling morsels
    and sibling fanout chunks: every backend call the query made lands
    in its handle meter (and therefore the lifetime bill) — none escape
    into staging that would only surface at dispatcher close — and the
    sharded round-robin cursor retains no entry for the dead query."""
    from repro.core.table import Table
    backend = SleepBackend(_BoomOracle(), delay_s=0.01)
    ctx = rt.ExecutionContext(backends={"m*": backend}, default_tier="m*",
                              concurrency=4, morsel_size=8,
                              driver="threads", shards=2)
    # morsel 0 is poison; morsels 1..3 are clean and still in flight
    # when morsel 0's failure surfaces
    table = Table({"v": [f"BOOM{i}" if i < 8 else f"x{i}"
                         for i in range(32)]}, name="mixed")
    with QueryServer(ctx) as server:
        h = server.submit(_plan("mixed"), table)
        with pytest.raises(RuntimeError, match="backend down"):
            h.result(timeout=30)
        # a failing call raises before it meters, so the billed calls
        # are exactly the backend's completed ones — equality proves no
        # straggler billed after the per-query staging was finalized
        assert h.meter.total.calls == backend.calls_made > 0
        assert ctx.meter.total.calls == h.meter.total.calls
        assert server._disp._query_base == {}     # released, not regrown


# ---------------------------------------------------------------------------
# Server-lifetime accounting + shared capacity
# ---------------------------------------------------------------------------

def test_serve_server_meter_accumulates_lifetime_totals():
    """The server context's meter absorbs every finished query's meter:
    lifetime totals equal the sum of per-query totals (failed queries
    included for whatever they billed)."""
    ctx, _ = _ctx()
    with QueryServer(ctx) as server:
        handles = [server.submit(_plan(t), _table(t))
                   for t in ("qa", "qb", "qc")]
        for h in handles:
            h.result(timeout=30)
        total = ctx.meter.total
        assert total.calls == sum(h.meter.total.calls for h in handles)
        assert total.usd == pytest.approx(
            sum(h.meter.total.usd for h in handles))
        assert len(ctx.meter.call_log) \
            == sum(len(h.meter.call_log) for h in handles)


def test_serve_per_tier_quota_caps_across_tenants():
    """per_tier_concurrency is a serving quota ACROSS queries: two
    in-flight queries' calls against one tier never exceed the cap."""
    from tests.test_shard import _PeakBackend
    backend = _PeakBackend(KindOracle(), delay_s=0.01)
    ctx = rt.ExecutionContext(backends={"m*": backend}, default_tier="m*",
                              concurrency=16, morsel_size=4,
                              per_tier_concurrency={"m*": 3},
                              driver="threads")
    with QueryServer(ctx) as server:
        handles = [server.submit(_plan(t), _table(t))
                   for t in ("qa", "qb")]
        for h in handles:
            h.result(timeout=30)
    assert backend.peak <= 3


def test_serve_concurrent_admission_overlaps_queries():
    """Two admitted queries interleave on the shared pools: the
    concurrent makespan beats back-to-back execution of the same two
    queries on an identical fresh server. The queries deliberately
    under-fill capacity solo (8-row morsels + a reduce barrier on a
    16-wide pool) — co-tenants fill the idle slots, which is the whole
    point of serving-level continuous batching."""
    def run(concurrent: bool) -> float:
        best = float("inf")
        for _ in range(3):
            ctx, _ = _ctx(delay_s=0.04, concurrency=16)
            with QueryServer(ctx) as server:
                t0 = time.perf_counter()
                if concurrent:
                    hs = [server.submit(_plan(t, reduce_tail=True),
                                        _table(t, 8))
                          for t in ("qa", "qb")]
                    for h in hs:
                        h.result(timeout=30)
                else:
                    for t in ("qa", "qb"):
                        server.submit(_plan(t, reduce_tail=True),
                                      _table(t, 8)).result(timeout=30)
                best = min(best, time.perf_counter() - t0)
        return best

    sequential, concurrent = run(False), run(True)
    assert concurrent < sequential * 0.85


# ---------------------------------------------------------------------------
# Per-query shard cursors
# ---------------------------------------------------------------------------

def test_serve_round_robin_cursor_is_per_query():
    """Each admitted query gets its own rotated shard cursor (so
    co-tenant queries spread over shards instead of piling on shard 0),
    and release_query drops the offset."""
    disp = ShardedDispatcher(shards=2, driver="threads", concurrency=2)
    try:
        # keyless callers (solo executions) keep plain round-robin
        assert [disp.shard_of(i) for i in range(4)] == [0, 1, 0, 1]
        assert [disp.shard_of(i, query=7) for i in range(4)] == [0, 1, 0, 1]
        assert [disp.shard_of(i, query=8) for i in range(4)] == [1, 0, 1, 0]
        disp.release_query(7)
        disp.release_query(7)                       # idempotent
        assert disp.shard_of(0, query=9) == 0       # freed base reused
    finally:
        disp.close()


# ---------------------------------------------------------------------------
# Long-lived shutdown paths
# ---------------------------------------------------------------------------

def test_serve_context_close_is_idempotent_and_terminal():
    ctx, _ = _ctx()
    disp = ctx.dispatcher()
    assert ctx.dispatcher() is disp          # cached, not rebuilt per call
    ctx.close()
    ctx.close()                              # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        ctx.dispatcher()
    # the dispatcher's pools are really shut down
    with pytest.raises(RuntimeError):
        disp.defer(disp.done(None), lambda v, r: (v, r))


def test_serve_context_manager_closes_and_forks_stay_independent():
    ctx, _ = _ctx()
    fork = ctx.fork(meter=bk.UsageMeter())
    with ctx:
        assert ctx.dispatcher() is not None
    with pytest.raises(RuntimeError):
        ctx.dispatcher()
    fdisp = fork.dispatcher()                # fork unaffected by close()
    fork.close()
    with pytest.raises(RuntimeError):
        fork.dispatcher()
    del fdisp


def test_serve_output_cache_close_unblocks_waiters():
    """A drained server must not leave threads blocked on cache keys
    whose owner will never publish: close() releases every reservation
    (idempotently) and waiters recompute solo."""
    cache = rt.OutputCache()
    key = ("k",)
    token = object()
    assert cache.claim([key], token)[0][0] == "own"
    state, event = cache.claim([key], object())[0]
    assert state == "wait"
    got = {}

    def wait():
        got["v"] = cache.wait_value(key, event)

    t = threading.Thread(target=wait)
    t.start()
    time.sleep(0.05)
    assert t.is_alive()                      # genuinely blocked
    cache.close()
    t.join(timeout=5)
    assert not t.is_alive()
    assert got["v"] == (False, None)         # unblocked, recomputes solo
    cache.close()                            # idempotent
    assert cache.closed


def test_serve_linger_ticker_stop_joins_daemon():
    """_LingerTicker.stop() is a deterministic shutdown: the daemon
    exits, and a later register starts a fresh one."""
    disp = rt.ThreadPoolDispatcher(concurrency=2)
    coal = rt.BatchCoalescer(disp, bk.UsageMeter(), batch_size=8,
                             linger_s=0.02)
    backend = SleepBackend(KindOracle(), delay_s=0.0)
    op = P.Operator(P.MAP, "annotate", "v", "a")
    try:
        group = coal.open(op, backend, "m*", expected=2)
        fut = group.submit(0, ["x"], 0.0)
        fut.result(timeout=5)                # linger flush fired
        assert rt._LINGER_TICKER.n_threads() == 1
        rt._LINGER_TICKER.stop()
        assert rt._LINGER_TICKER.n_threads() == 0
        rt._LINGER_TICKER.stop()             # idempotent
        # a fresh registration restarts the daemon
        coal2 = rt.BatchCoalescer(disp, bk.UsageMeter(), batch_size=8,
                                  linger_s=0.02)
        g2 = coal2.open(op, backend, "m*", expected=2)
        f2 = g2.submit(0, ["y"], 0.0)
        f2.result(timeout=5)
        assert rt._LINGER_TICKER.n_threads() == 1
        coal2.close()
    finally:
        coal.close()
        disp.close()


# ---------------------------------------------------------------------------
# Serve launcher surface
# ---------------------------------------------------------------------------

def test_serve_parser_and_stagger_offsets():
    from repro.launch import serve
    ap = serve.build_parser()
    args = ap.parse_args([])
    assert args.serve == 0 and args.stagger == 0.0
    args = ap.parse_args(["--semantic", "movie", "--serve", "4",
                          "--stagger", "0.2"])
    assert args.serve == 4 and args.stagger == pytest.approx(0.2)
    offs = serve.stagger_offsets(4, 0.2, seed=1)
    assert offs[0] == 0.0 and offs == sorted(offs) and len(offs) == 4
    assert serve.stagger_offsets(4, 0.2, seed=1) == offs   # deterministic
    assert serve.stagger_offsets(3, 0.0) == [0.0, 0.0, 0.0]


@pytest.mark.parametrize("argv,full", [
    (["--semantic", "movie"], False),
    (["--semantic", "movie", "--reduced"], False),
    (["--semantic", "movie", "--no-reduced"], True),
])
def test_serve_semantic_honours_no_reduced(argv, full):
    """The semantic modes serve the m1 tier's model, at the published
    widths under --no-reduced (config chosen without building it)."""
    from repro.configs import get_config
    from repro.core.cost_model import DEFAULT_TIERS
    from repro.launch import serve
    cfg = serve.semantic_config(serve.build_parser().parse_args(argv))
    published = get_config(DEFAULT_TIERS["m1"].arch)
    assert published.name == "qwen2-0.5b"
    assert (cfg == published) is full
    if full:
        assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == \
            (24, 896, 151936)
    else:
        assert cfg.d_model < published.d_model


def test_compile_cache_placed_from_env_or_checkout(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's own setting and left
    alone; otherwise the cache goes to one fixed path in the checkout."""
    import jax
    from repro.launch import compile_cache as cc
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "cache-from-env")
    assert cc.enable_compile_cache() == "cache-from-env"
    assert updates == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert cc.enable_compile_cache() == str(cc.REPO_CACHE_DIR)
    assert updates == [("jax_compilation_cache_dir", str(cc.REPO_CACHE_DIR))]
    assert cc.REPO_CACHE_DIR.name == ".jax_cache"
    assert (cc.REPO_CACHE_DIR.parent / "pyproject.toml").is_file()


def test_serve_submit_after_close_is_rejected():
    ctx, _ = _ctx()
    server = QueryServer(ctx)
    h = server.submit(_plan("qa"), _table("qa"))
    server.close()
    assert h.done()
    with pytest.raises(RuntimeError, match="closed"):
        server.submit(_plan("qb"), _table("qb"))
