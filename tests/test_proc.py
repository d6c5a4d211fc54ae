"""Process shard workers (`distributed.process_workers`): the `procs`
driver's invariance, death-ladder, and serialization contracts.

Naming: every test here matches `-k proc` (the CI proc-smoke job).
"""
import os
import pickle
import signal
import threading
import time

import pytest

from repro import testing as tg
from repro.core import backends as bk
from repro.core import executor as ex
from repro.core import plan as plan_ir
from repro.core import runtime as rt
from repro.distributed.morsel_shards import ShardedDispatcher, _compose
from repro.distributed.process_workers import (NO_DEVICE_PLATFORM,
                                               ProcessShardClient,
                                               ProcessShardDispatcher,
                                               shippable_backends)

pytestmark = pytest.mark.proc

MORSEL = 8


def _totals(meter):
    return {t: (u.calls, round(u.tok_in, 6), round(u.tok_out, 6),
                round(u.usd, 9), round(u.latency_s, 6))
            for t, u in sorted(meter.by_tier.items())}


def _log_key(meter):
    return sorted(zip(meter.call_keys,
                      [t for t, _ in meter.call_log],
                      [round(l, 9) for _, l in meter.call_log]))


def _run_inproc(plan, table, backend, driver, **kw):
    meter = bk.UsageMeter()
    res = ex.execute(plan, table, {"m*": backend}, default_tier="m*",
                     batch_size=1, morsel_size=MORSEL, meter=meter,
                     driver=driver, **kw)
    return res, meter


def _run_procs(plan, table, backend, n, cache=None, **disp_kw):
    meter = bk.UsageMeter()
    disp = ShardedDispatcher(shards=n, driver="procs", concurrency=4,
                             backends={"m*": backend}, **disp_kw)
    try:
        res = ex.execute(plan, table, {"m*": backend}, default_tier="m*",
                         batch_size=1, morsel_size=MORSEL, meter=meter,
                         cache=cache, dispatcher=disp)
        live = disp.live_shards()
        stats = [d.client.stats.copy() for d in disp._inner]
    finally:
        disp.close()
    return res, meter, live, stats


# -- invariance ------------------------------------------------------------

def test_proc_shard_count_invariance_results_and_meters():
    """procs in {1, 2, 4}: results and per-tier totals byte-identical to
    both in-process drivers; merged logical-key call logs byte-identical
    to the threads driver (same chunked key shapes)."""
    table, plan = tg.tagged_table("pi", 32), tg.tagged_plan("pi")

    def mk():
        return tg.SleepBackend(tg.KindOracle(), delay_s=0.01, sleep_s=0.0)

    res_sim, m_sim = _run_inproc(plan, table, mk(), "simulated")
    res_thr, m_thr = _run_inproc(plan, table, mk(), "threads")
    ref_fp = tg.result_fingerprint(res_sim)
    assert tg.result_fingerprint(res_thr) == ref_fp
    assert _totals(m_thr) == _totals(m_sim)
    for n in (1, 2, 4):
        res, m, live, _ = _run_procs(plan, table, mk(), n)
        assert tg.result_fingerprint(res) == ref_fp
        assert live == list(range(n))
        assert _totals(m) == _totals(m_sim)
        assert _log_key(m) == _log_key(m_thr)


def test_proc_udf_steps_run_in_worker_processes():
    """A compiled-UDF operator executes over the wire (client udf stats
    move) and produces the in-process results/meters byte-for-byte."""
    table = tg.tagged_table("pu", 32)
    plan = plan_ir.LogicalPlan((
        plan_ir.Operator(plan_ir.FILTER, "keep-pu", "v"),
        plan_ir.Operator(plan_ir.MAP, "annotate-pu", "v", "a"),
        plan_ir.Operator(plan_ir.MAP, "shout", "a", "b",
                         udf="lambda x: str(x).upper()"),
    ))

    def fp(res):
        return (tuple(res.table.columns[ex.ROWID]),
                tuple(map(str, res.table.columns["b"])))

    res_thr, m_thr = _run_inproc(
        plan, table,
        tg.SleepBackend(tg.KindOracle(), delay_s=0.01, sleep_s=0.0),
        "threads")
    res, m, _, stats = _run_procs(
        plan, table,
        tg.SleepBackend(tg.KindOracle(), delay_s=0.01, sleep_s=0.0), 2)
    assert fp(res) == fp(res_thr)
    assert _totals(m) == _totals(m_thr)
    assert _log_key(m) == _log_key(m_thr)
    assert sum(s["udf"] for s in stats) >= 4      # one per UDF morsel
    assert sum(s["llm"] for s in stats) > 0


# -- death ladder ----------------------------------------------------------

class SuicideBackend(tg.SleepBackend):
    """SIGKILLs its own *worker* process the first time it sees the
    trigger value (one-shot via a flag file, so the survivor's retry of
    the same logical call proceeds; never fires in the coordinator)."""

    def __init__(self, oracle, flag_path, parent_pid, trigger, **kw):
        super().__init__(oracle, **kw)
        self.flag_path = flag_path
        self.parent_pid = parent_pid
        self.trigger = trigger

    def run_values(self, op, values, meter=None, batch_size=1):
        if (os.getpid() != self.parent_pid
                and any(str(v) == self.trigger for v in values)
                and not os.path.exists(self.flag_path)):
            open(self.flag_path, "w").close()
            os.kill(os.getpid(), signal.SIGKILL)
        return super().run_values(op, values, meter=meter,
                                  batch_size=batch_size)


def test_proc_worker_sigkill_requeues_and_bills_exactly_once(tmp_path):
    """A SIGKILLed worker surfaces as the PR 8 contract: its shard goes
    dead, pending morsels requeue onto the survivor, and with the shared
    single-flight cache the merged totals and logical-key log are
    byte-identical to a healthy run — in-flight calls that died unbilled
    bill once on retry, completed chunks resolve as cache hits."""
    table, plan = tg.tagged_table("pk", 32), tg.tagged_plan("pk")
    healthy = tg.SleepBackend(tg.KindOracle(), delay_s=0.01, sleep_s=0.0)
    res_h, m_h, live_h, _ = _run_procs(plan, table, healthy, 2,
                                       cache=rt.OutputCache())
    assert live_h == [0, 1]

    sb = SuicideBackend(tg.KindOracle(), str(tmp_path / "boom"),
                        os.getpid(), "pk-17", delay_s=0.01, sleep_s=0.0)
    res_k, m_k, live_k, _ = _run_procs(plan, table, sb, 2,
                                       cache=rt.OutputCache())
    assert len(live_k) == 1                       # one worker died
    assert tg.result_fingerprint(res_k) == tg.result_fingerprint(res_h)
    assert _totals(m_k) == _totals(m_h)           # exactly-once billing
    assert _log_key(m_k) == _log_key(m_h)


def test_proc_missed_heartbeat_declares_shard_dead():
    """SIGSTOP freezes a worker without closing its pipe: only the
    heartbeat ladder can catch it. The monitor declares the shard dead,
    SIGKILLs the stopped process, and execution completes on the
    survivor."""
    table, plan = tg.tagged_table("ph", 32), tg.tagged_plan("ph")
    backend = tg.SleepBackend(tg.KindOracle(), delay_s=0.01, sleep_s=0.0)
    meter = bk.UsageMeter()
    disp = ShardedDispatcher(shards=2, driver="procs", concurrency=4,
                             backends={"m*": backend},
                             heartbeat_s=0.05, heartbeat_timeout_s=0.5)
    try:
        os.kill(disp._inner[0].client.pid, signal.SIGSTOP)
        res = ex.execute(plan, table, {"m*": backend}, default_tier="m*",
                         batch_size=1, morsel_size=MORSEL, meter=meter,
                         cache=rt.OutputCache(), dispatcher=disp)
        deadline = time.perf_counter() + 10.0
        while not disp.is_dead(0) and time.perf_counter() < deadline:
            time.sleep(0.05)
        assert disp.is_dead(0)
        assert disp.live_shards() == [1]
    finally:
        disp.close()
    ref, m_ref = _run_inproc(plan, table, backend, "simulated")
    assert tg.result_fingerprint(res) == tg.result_fingerprint(ref)
    assert _totals(meter) == _totals(m_ref)


def test_proc_graceful_close_terminates_workers():
    disp = ShardedDispatcher(shards=2, driver="procs", concurrency=4,
                             backends={"m*": tg.SleepBackend(
                                 tg.KindOracle(), delay_s=0.0)})
    procs = [d.client._proc for d in disp._inner]
    assert all(p.is_alive() for p in procs)
    disp.close()
    assert all(not p.is_alive() for p in procs)
    disp.close()                                  # idempotent


# -- chaos over the wire ---------------------------------------------------

def test_proc_chaos_run_matches_in_process_chaos():
    """FlakyBackend fault plans key off content-hashed logical identity,
    so a pickled copy in a worker draws the same plan: a retried chaos
    run over procs produces the threads driver's results, totals, and
    merged log byte-for-byte (the CallPolicy stays coordinator-side)."""
    table, plan = tg.tagged_table("pc", 32), tg.tagged_plan("pc")
    policy = rt.CallPolicy(retries=3)

    def mk():
        return tg.FlakyBackend(
            tg.SleepBackend(tg.KindOracle(), delay_s=0.01, sleep_s=0.0),
            error_rate=0.2, seed=7)

    res_thr, m_thr = _run_inproc(plan, table, mk(), "threads",
                                 call_policy=policy)
    meter = bk.UsageMeter()
    backend = mk()
    ctx = rt.ExecutionContext(backends={"m*": backend}, default_tier="m*",
                              batch_size=1, morsel_size=MORSEL,
                              meter=meter, procs=2, call_policy=policy)
    disp = ctx.make_dispatcher()
    try:
        res = ex.execute(plan, table, ctx, dispatcher=disp)
    finally:
        disp.close()
    assert tg.result_fingerprint(res) == tg.result_fingerprint(res_thr)
    assert _totals(meter) == _totals(m_thr)
    assert _log_key(meter) == _log_key(m_thr)


# -- serialization boundary ------------------------------------------------

def test_proc_fakes_pickle_roundtrip_and_seed_stability():
    oracle = tg.KindOracle()
    op = plan_ir.Operator(plan_ir.MAP, "annotate", "v", "a")
    sb = tg.SleepBackend(oracle, delay_s=0.01, sleep_s=0.0)
    sb2 = pickle.loads(pickle.dumps(sb))
    assert sb2.run_values(op, ["x"]) == sb.run_values(op, ["x"])

    gb = tg.GilBoundBackend(oracle, work_s=0.0)
    gb2 = pickle.loads(pickle.dumps(gb))
    assert gb2.run_values(op, ["x"]) == gb.run_values(op, ["x"])

    fb = tg.FlakyBackend(sb, error_rate=0.5, seed=3)
    fb2 = pickle.loads(pickle.dumps(fb))

    def draws(b):
        out = []
        for i in range(16):
            m = bk.UsageMeter()
            with m.keyed((0, i)):
                try:
                    b.run_values(op, [f"v{i}"], meter=m)
                    out.append("ok")
                except rt.TransientCallError:
                    out.append("err")
        return out

    assert draws(fb2) == draws(fb)                # same fault plan
    assert "err" in draws(fb) and "ok" in draws(fb)

    eo = tg.EmbeddingOracle(oracle, seed=5)
    eo2 = pickle.loads(pickle.dumps(eo))
    import numpy as np
    np.testing.assert_array_equal(eo2.encode_values(op, ["a", "b"]),
                                  eo.encode_values(op, ["a", "b"]))


def test_proc_usage_meter_pickles_with_logs_and_keys():
    m = bk.UsageMeter()
    with m.keyed((1, 2)):
        m.record("m*", bk.Usage(calls=2, tok_in=16.0, tok_out=8.0,
                                usd=0.01, latency_s=0.2),
                 per_call_latency_s=[0.1, 0.1], op_kind=plan_ir.MAP)
    m2 = pickle.loads(pickle.dumps(m))
    assert _totals(m2) == _totals(m)
    assert m2.call_log == m.call_log
    assert m2.call_keys == m.call_keys
    assert m2.call_ops == m.call_ops
    # lock and thread-local state are rebuilt per process
    with m2.keyed((9,)):
        m2.record("m*", bk.Usage(calls=1, latency_s=0.1))
    assert m2.call_keys[-1] == (9, 0)


def test_proc_unpicklable_backends_stay_coordinator_side():
    """A backend that cannot pickle (e.g. engine-backed) is not shipped;
    its calls run in-process through the inherited threads path, and the
    run still completes with correct results."""
    class Unpicklable(tg.SleepBackend):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.hostage = lambda: None           # defeats pickling

        def __getstate__(self):
            raise TypeError("cannot pickle engine state")

    backend = Unpicklable(tg.KindOracle(), delay_s=0.01, sleep_s=0.0)
    assert shippable_backends({"m*": backend}) == {}
    table, plan = tg.tagged_table("px", 16), tg.tagged_plan("px")
    res_ref, m_ref = _run_inproc(plan, table, backend, "simulated")
    res, m, _, stats = _run_procs(plan, table, backend, 2)
    assert tg.result_fingerprint(res) == tg.result_fingerprint(res_ref)
    assert _totals(m) == _totals(m_ref)
    assert sum(s["llm"] for s in stats) == 0      # nothing went remote


class JaxTouchingBackend(tg.SleepBackend):
    """Runs a JAX computation inside ``run_values``."""

    def run_values(self, op, values, meter=None, batch_size=1):
        import jax.numpy as jnp
        float(jnp.ones(2).sum())
        return super().run_values(op, values, meter=meter,
                                  batch_size=batch_size)


def test_proc_worker_never_initializes_a_jax_backend():
    """Workers are pinned to a JAX platform that does not exist: a JAX
    computation shipped to one fails loudly (and never falls back to the
    CPU or takes the coordinator's device), while the same backend runs
    in the coordinator."""
    backend = JaxTouchingBackend(tg.KindOracle(), delay_s=0.0)
    op = plan_ir.Operator(plan_ir.MAP, "annotate", "v", "a")
    assert backend.run_values(op, ["x"])              # coordinator: fine
    client = ProcessShardClient({"m*": backend}, 1)
    try:
        client.wait_ready()
        tag, err, _ = client.call("llm", ("m*", op, ["x"], 1, None, None))
    finally:
        client.close()
    assert tag == "err"
    assert isinstance(err, RuntimeError)
    assert NO_DEVICE_PLATFORM in str(err)


# -- occupancy (satellite bugfix) ------------------------------------------

def test_proc_sharded_simulated_occupancy_merges_base_tiers():
    disp = ShardedDispatcher(shards=2, driver="simulated", concurrency=4)
    try:
        assert disp.occupancy() == {}
        disp._sched.submit(_compose(0, "m*"), 5.0)
        disp._sched.submit(_compose(1, "m*"), 3.0)
        disp._sched.submit(_compose(0, "m2"), 1.0)
        occ = disp.occupancy()
        assert occ["m*"] == [pytest.approx(3.0), pytest.approx(5.0)]
        assert occ["m2"] == [pytest.approx(1.0)]
    finally:
        disp.close()


def test_proc_threads_occupancy_tracks_inflight_calls():
    disp = rt.ThreadPoolDispatcher(concurrency=4)
    release = threading.Event()
    started = threading.Event()

    def thunk():
        started.set()
        release.wait(5.0)
        return []

    try:
        assert disp.occupancy() == {}
        fan = disp.fanout("m*")
        runner = threading.Thread(target=fan, args=([thunk],))
        runner.start()
        assert started.wait(5.0)
        occ = disp.occupancy()
        assert list(occ) == ["m*"] and len(occ["m*"]) == 1
        assert occ["m*"][0] > 0.0
        release.set()
        runner.join(5.0)
        assert disp.occupancy() == {}
    finally:
        release.set()
        disp.close()


# -- wiring ----------------------------------------------------------------

def test_proc_serve_parser_and_context_wiring():
    from repro.launch import serve
    ap = serve.build_parser()
    assert ap.parse_args([]).procs == 0
    assert ap.parse_args(["--procs", "4"]).procs == 4

    with pytest.raises(ValueError, match="mutually exclusive"):
        rt.ExecutionContext(backends={}, procs=2, shards=2) \
            .make_dispatcher()

    backend = tg.SleepBackend(tg.KindOracle(), delay_s=0.0)
    ctx = rt.ExecutionContext(backends={"m*": backend}, procs=3,
                              per_tier_concurrency={"m*": 7})
    disp = ctx.make_dispatcher()
    try:
        assert isinstance(disp, ShardedDispatcher)
        assert disp.n_shards == 3 and disp.kind == "procs"
        assert all(isinstance(d, ProcessShardDispatcher)
                   for d in disp._inner)
        assert [disp.shard_of(i) for i in range(5)] == [0, 1, 2, 0, 1]
        assert [disp.shard_quota("m*", s) for s in range(3)] == [3, 2, 2]
    finally:
        disp.close()
