"""Serving engine: continuous batching correctness + scheduler behaviour."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.data.tokenizer import ByteTokenizer
from repro.engine import ContinuousBatcher, GenerationEngine
from repro.models import registry


@pytest.fixture(scope="module")
def served():
    cfg = reduced(get_config("qwen2-0.5b"))
    b = registry.build(cfg)
    params = b.init(jax.random.PRNGKey(0))
    return cfg, b, params


def gen_sequential(bundle, params, prompt, max_new, max_len=96):
    """Reference: single-request engine (n_slots=1)."""
    eng = GenerationEngine(bundle, params, max_len=max_len, n_slots=1)
    cb = ContinuousBatcher(eng)
    rid = cb.submit(prompt, max_new_tokens=max_new)
    return cb.run()[rid].output_ids


def test_continuous_batching_matches_sequential(served):
    _, bundle, params = served
    prompts = [f"semantic query number {i} about movies" for i in range(5)]
    want = [gen_sequential(bundle, params, p, 8) for p in prompts]

    eng = GenerationEngine(bundle, params, max_len=96, n_slots=3)
    cb = ContinuousBatcher(eng)
    rids = [cb.submit(p, max_new_tokens=8) for p in prompts]
    got = cb.run()
    for rid, w in zip(rids, want):
        assert got[rid].output_ids == w, rid


@pytest.mark.parametrize("n_tokens", [17, 33, 65])
def test_first_token_from_last_real_position(served, n_tokens):
    """Prefill right-pads to a multiple of PREFILL_ALIGN; the first
    generated token must come from the last real prompt position, i.e.
    equal the argmax of an unpadded prefill of the same prompt."""
    _, bundle, params = served
    prompt = "".join(chr(97 + (7 * i) % 26) for i in range(n_tokens - 1))
    ids = ByteTokenizer().encode(prompt)
    assert len(ids) == n_tokens
    logits, _ = bundle.prefill(params, {"tokens": jnp.asarray([ids])},
                               max_len=len(ids), dtype=jnp.float32)
    want = int(jnp.argmax(logits[0, -1]))

    eng = GenerationEngine(bundle, params, max_len=96, n_slots=2)
    cb = ContinuousBatcher(eng)
    rid = cb.submit(prompt, max_new_tokens=1)
    assert cb.run()[rid].output_ids == [want]


def test_more_requests_than_slots(served):
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=64, n_slots=2)
    cb = ContinuousBatcher(eng)
    rids = [cb.submit(f"req {i}", max_new_tokens=5) for i in range(9)]
    finished = cb.run()
    assert len(finished) == 9
    assert all(len(finished[r].output_ids) == 5 for r in rids)
    assert eng.stats["prefills"] == 9


def test_occupancy_improves_with_load(served):
    _, bundle, params = served
    eng1 = GenerationEngine(bundle, params, max_len=64, n_slots=4)
    cb1 = ContinuousBatcher(eng1)
    cb1.submit("only one request", max_new_tokens=6)
    cb1.run()
    eng2 = GenerationEngine(bundle, params, max_len=64, n_slots=4)
    cb2 = ContinuousBatcher(eng2)
    for i in range(12):
        cb2.submit(f"request {i}", max_new_tokens=6)
    cb2.run()
    assert eng2.occupancy > eng1.occupancy


def test_max_len_respected(served):
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=48, n_slots=1)
    cb = ContinuousBatcher(eng)
    rid = cb.submit("x" * 200, max_new_tokens=64)    # prompt+gen > max_len
    req = cb.run()[rid]
    assert len(req.prompt_ids) + len(req.output_ids) <= 48


def test_temperature_sampling_differs(served):
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=64, n_slots=1)
    cb = ContinuousBatcher(eng)
    r1 = cb.submit("hello", max_new_tokens=12, temperature=1.5)
    out1 = cb.run(key=jax.random.PRNGKey(0))[r1].output_ids
    eng2 = GenerationEngine(bundle, params, max_len=64, n_slots=1)
    cb2 = ContinuousBatcher(eng2)
    r2 = cb2.submit("hello", max_new_tokens=12, temperature=1.5)
    out2 = cb2.run(key=jax.random.PRNGKey(9))[r2].output_ids
    assert out1 != out2


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    s = "Nirvana: semantic ops über tables 🎬"
    assert tok.decode(tok.encode(s, bos=True, eos=True)) == s
    batch = tok.pad_batch([[1, 2], [3, 4, 5]], align=8)
    assert batch.shape == (2, 8)
    assert batch[0, 2] == tok.pad_id


def test_jax_backend_through_executor(served):
    from repro.core import executor as ex
    from repro.core import plan as P
    from repro.core.backends import UsageMeter
    from repro.core.cost import DEFAULT_TIERS
    from repro.engine import JAXBackend
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=128, n_slots=2)
    be = JAXBackend(DEFAULT_TIERS["m1"], eng, max_new_tokens=4)
    plan = P.LogicalPlan((P.Operator(P.FILTER, "Is it big?", "col"),))
    from repro.core.table import Table
    table = Table({"col": ["tiny", "huge", "medium"]})
    meter = UsageMeter()
    res = ex.execute(plan, table, {"m*": be}, default_tier="m*",
                     meter=meter)
    assert meter.calls("m1") == 3
    assert meter.total.latency_s > 0
    assert res.table is not None


def test_engine_counters_match_the_requests(served):
    """Three requests that all fit the slots at once: one token each at
    insert, then one per tick while live, so the counters follow from
    the requests' own lengths."""
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=96, n_slots=4)
    cb = ContinuousBatcher(eng)
    for i, n in enumerate((3, 5, 8)):
        cb.submit(f"counted request {i}", max_new_tokens=n)
    reqs = list(cb.run().values())
    outs = [len(r.output_ids) for r in reqs]
    prompts = [len(r.prompt_ids) for r in reqs]
    assert eng.stats["prefills"] == 3
    assert eng.stats["prompt_tokens"] == sum(prompts)
    # a request's tokens: the first at insert, then one per live tick
    assert eng.stats["prefills"] + eng.stats["live_slot_ticks"] == sum(outs)
    assert eng.stats["ticks"] == max(outs) - 1
    assert eng.stats["live_slot_ticks"] == sum(m - 1 for m in outs)
    # a live slot attends over its prompt and every output token so far,
    # the one it writes included
    assert eng.stats["context_tokens"] == sum(
        p + k for p, m in zip(prompts, outs) for k in range(1, m))
    assert eng.occupancy == pytest.approx(
        sum(m - 1 for m in outs) / ((max(outs) - 1) * 4))


def test_jax_backend_counters_under_concurrent_callers(served):
    """More callers than cores and a short switch interval: every call
    is counted once, and the lock waits lie inside the calls' time."""
    import sys
    import threading
    from repro.core import plan as P
    from repro.core.cost import DEFAULT_TIERS
    from repro.engine import JAXBackend
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=128, n_slots=4)
    be = JAXBackend(DEFAULT_TIERS["m1"], eng, max_new_tokens=3)
    op = P.Operator(P.FILTER, "Is it big?", "col")
    n_threads, per_thread = 12, 2
    errors = []

    def caller(k):
        try:
            for j in range(per_thread):
                assert len(be.run_values(op, [f"row {k}.{j}"])) == 1
        except Exception as e:                    # reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=caller, args=(k,))
                   for k in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    s = be.stats
    assert s["calls"] == n_threads * per_thread
    assert 0 < s["lock_wait_s"] <= s["call_s"]
    assert eng.stats["prefills"] == n_threads * per_thread
    assert eng.stats["ticks"] > 0


class KeptEngine(GenerationEngine):
    """``GenerationEngine`` that keeps every finished request."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.done = []

    def insert(self, req, slot):
        done = super().insert(req, slot)
        if done is not None:
            self.done.append(done)
        return done

    def decode_tick(self, key=None):
        done = super().decode_tick(key)
        self.done.extend(done)
        return done


def _run_callers(n, target, timeout=120):
    """``target(k)`` in ``n`` threads at once, k = 0..n-1: the errors
    they raised."""
    import threading
    errors, start = [], threading.Barrier(n)

    def caller(k):
        try:
            start.wait(timeout)
            target(k)
        except Exception as e:                    # reported by the caller
            errors.append(e)

    threads = [threading.Thread(target=caller, args=(k,)) for k in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in threads)
    return errors


def _await_waiters(lock, n, timeout=60):
    """Wait until ``n`` threads wait on the ``FairLock`` ``lock``."""
    import time
    deadline = time.perf_counter() + timeout
    while len(lock._waiters) < n:
        assert time.perf_counter() < deadline
        time.sleep(0.001)


def test_fair_lock_hands_over_to_the_oldest_waiter():
    """A holder that releases and takes the lock again at once queues
    behind the threads already waiting, which get it in arrival order."""
    import threading
    from repro.engine.jax_backend import FairLock
    lock, order = FairLock(), []

    def waiter(k):
        with lock:
            order.append(k)

    lock.acquire()
    threads = []
    for k in range(3):
        threads.append(threading.Thread(target=waiter, args=(k,)))
        threads[-1].start()
        _await_waiters(lock, k + 1)
    lock.release()
    assert not lock.acquire(False)          # handed to waiter 0
    lock.acquire()
    order.append("holder")
    lock.release()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert order == [0, 1, 2, "holder"]
    assert lock.acquire(False)
    lock.release()


def test_one_driver_steps_every_callers_requests(served):
    """Eight callers of one-row calls on 16 slots: each tick carries
    about eight live slots and each step serves about eight calls, and
    every request's tokens are those it gets sent alone."""
    from repro.core import plan as P
    from repro.core.cost import DEFAULT_TIERS
    from repro.engine import JAXBackend
    _, bundle, params = served
    eng = KeptEngine(bundle, params, max_len=96, n_slots=16)
    be = JAXBackend(DEFAULT_TIERS["m1"], eng, max_new_tokens=8)
    op = P.Operator(P.MAP, "Name it.", "col", output_column="name")
    n_threads, per_thread = 8, 3
    rows = [f"row {k}.{j}" for k in range(n_threads)
            for j in range(per_thread)]
    errors = _run_callers(n_threads, lambda k: [
        be.run_values(op, [rows[k * per_thread + j]])
        for j in range(per_thread)])
    assert not errors, errors
    assert be.stats["calls"] == len(rows)
    assert eng.stats["live_slot_ticks"] / eng.stats["ticks"] >= 4
    assert be.stats["step_calls"] / be.stats["steps"] >= 4
    together = {r.prompt: r.output_ids for r in eng.done}
    alone = KeptEngine(bundle, params, max_len=96, n_slots=16)
    lone = JAXBackend(DEFAULT_TIERS["m1"], alone, max_new_tokens=8)
    for row in rows:
        lone.run_values(op, [row])
    assert len(together) == len(alone.done) == len(rows)
    assert {r.prompt: r.output_ids for r in alone.done} == together
    assert lone.stats["step_calls"] == lone.stats["steps"]
    assert lone.stats["lock_wait_s"] == 0.0
    # a call with no rows needs no step
    steps = lone.stats["steps"]
    assert lone.run_values(op, []) == []
    assert lone.stats["steps"] == steps
    assert lone.stats["calls"] == len(rows) + 1


def test_a_failed_step_reaches_every_waiting_caller(served):
    """A decode tick that raises once: each caller whose request was in
    the batcher gets the error, no caller hangs, and the backend serves
    the next call as a fresh one would."""
    import threading
    from repro.core import plan as P
    from repro.core.cost import DEFAULT_TIERS
    from repro.engine import JAXBackend

    class Boom(RuntimeError):
        pass

    class FailingEngine(GenerationEngine):
        failed = False

        def decode_tick(self, key=None):
            if not self.failed:
                self.failed = True
                raise Boom("tick failed")
            return super().decode_tick(key)

    _, bundle, params = served
    eng = FailingEngine(bundle, params, max_len=96, n_slots=4)
    be = JAXBackend(DEFAULT_TIERS["m1"], eng, max_new_tokens=4)
    op = P.Operator(P.MAP, "Name it.", "col", output_column="name")
    n_threads = 3
    # hold the lock until every caller waits on it, so that all three
    # submit before the driver's first step
    be._lock.acquire()
    got = []
    runner = threading.Thread(target=lambda: got.extend(_run_callers(
        n_threads, lambda k: be.run_values(op, [f"row {k}"]))))
    runner.start()
    _await_waiters(be._lock, n_threads)
    be._lock.release()
    runner.join(timeout=120)
    assert not runner.is_alive()
    assert len(got) == n_threads and all(isinstance(e, Boom) for e in got)
    assert be.stats["calls"] == 0 and not eng.active.any()

    after = be.run_values(op, ["row 0"])
    fresh = JAXBackend(DEFAULT_TIERS["m1"],
                       GenerationEngine(bundle, params, max_len=96,
                                        n_slots=4), max_new_tokens=4)
    assert after == fresh.run_values(op, ["row 0"])
    assert be.stats["calls"] == 1


def test_programs_are_named(served):
    """The two programs lower to modules named for what they do, so a
    device trace tells prefill from decode."""
    from repro.engine.engine import jitted_steps
    _, bundle, params = served
    decode, prefill = jitted_steps(bundle, max_len=32, dtype=jnp.float32)
    cache = bundle.init_cache(2, 32, dtype=jnp.float32, per_slot_pos=True)
    token = jnp.zeros((2, 1), jnp.int32)
    batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
             "last_index": jnp.zeros((1,), jnp.int32)}
    assert "module @jit_engine_decode" in decode.lower(
        params, cache, token).as_text()
    assert "module @jit_engine_prefill" in prefill.lower(
        params, batch).as_text()


def _profiled_spans(tmp_path, work):
    """The ``engine.*`` host spans a profiler trace of ``work()`` holds:
    [(name, start_ns, end_ns, {arg: value})], by start."""
    import glob
    import os
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
              {k: v for k, v in e.stats})
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("engine.")]
    return sorted(spans, key=lambda s: s[1])


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_engine_spans_nest_and_count_the_work(served, tmp_path):
    """Each insert and tick is a span with one child per phase, a call
    holds its inserts and ticks, and the spans count what the counters
    count. A lone caller never waits for the lock."""
    from repro.core import plan as P
    from repro.core.cost import DEFAULT_TIERS
    from repro.engine import JAXBackend
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=128, n_slots=2)
    be = JAXBackend(DEFAULT_TIERS["m1"], eng, max_new_tokens=4)
    op = P.Operator(P.FILTER, "Is it big?", "col")
    spans = _profiled_spans(
        tmp_path, lambda: be.run_values(op, ["tiny", "huge", "medium"]))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)

    assert len(by["engine.insert"]) == eng.stats["prefills"] == 3
    assert len(by["engine.tick"]) == eng.stats["ticks"]
    assert sum(s[3]["live"] for s in by["engine.tick"]) == \
        eng.stats["live_slot_ticks"]
    assert sorted(s[3]["rid"] for s in by["engine.insert"]) == [0, 1, 2]
    assert {s[3]["slot"] for s in by["engine.insert"]} == {0, 1}
    assert all(s[3]["prompt_len"] > 0 for s in by["engine.insert"])
    for child in ("engine.prefill", "engine.splice", "engine.first_token"):
        assert len(by[child]) == 3
        assert all(_inside(s, by["engine.insert"]) for s in by[child])
    for child in ("engine.decode", "engine.tick_sync", "engine.tick_update"):
        assert len(by[child]) == eng.stats["ticks"]
        assert all(_inside(s, by["engine.tick"]) for s in by[child])
    call, = by["engine.call"]
    assert call[3] == {"kind": "filter", "rows": 3}
    assert all(_inside(s, [call])
               for s in by["engine.tick"] + by["engine.insert"])
    assert be.stats["calls"] == 1
    assert be.stats["lock_wait_s"] == 0.0
