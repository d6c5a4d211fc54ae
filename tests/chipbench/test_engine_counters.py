"""The engine's own counters and spans over the measured window of a
CPU-sized closed-loop cell: the run's record holds both, the counters
count the same ticks, live slots, context and prompt tokens as the
benchmark's recording engine, and ``chipbench/engine_readings.py`` reads
them beside the benchmark's spans."""
import json

from benchcase import tiny_root


def test_engine_counters_over_the_window_match_the_recording(tmp_path):
    from chipbench import harness
    root = tiny_root(tmp_path)
    runs = []
    res = harness.run_cell(root, "tiny.game", 3_000_000_019, 2.0, False,
                           on_run=runs.append)
    assert res["correct"] is True
    c = runs[0].counters
    t0, t1 = c["open"]["t"], c["close"]["t"]
    opened, closed = c["open"]["engine"], c["close"]["engine"]
    got = {k: closed[k] - opened[k] for k in closed}
    engine = runs[0].engine
    ticks = [(live, ctx) for t, live, ctx in engine.ticks if t0 <= t < t1]
    prefills = [n for t, n in engine.prefills if t0 <= t < t1]
    assert ticks and prefills
    assert got["ticks"] == len(ticks)
    assert got["live_slot_ticks"] == sum(live for live, _ in ticks)
    assert got["context_tokens"] == sum(ctx for _, ctx in ticks)
    assert got["prefills"] == len(prefills)
    assert got["prompt_tokens"] == sum(prefills)


def test_engine_readings_of_a_cpu_traced_run(tmp_path, capsys,
                                             monkeypatch):
    """The lock-wait share is read from the counters; the device-trace
    readings are left out on the CPU; the kept trace holds the engine's
    tick spans, each inside the benchmark's, so the two share a clock."""
    from chipbench import engine_readings, harness
    from chipbench import trace as tr
    monkeypatch.setattr(harness, "TRACE_S", 1.0)   # the window's last 1 s
    root = tiny_root(tmp_path / "root")
    kept = str(tmp_path / "kept.json.gz")
    rc = engine_readings.main(
        ["--workload", "tiny.game", "--seed", "3000000019", "--seconds",
         "2", "--keep", kept], root=root, require_chip=False,
        compile_cache=False)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["result"]["correct"] is True
    got = out["engine"]
    assert 0 < got["lock_wait_share"] <= 100
    assert got["rows_per_s_untraced"] > 0 and got["rows_per_s_traced"] > 0
    assert "tick_idle_ms" not in got and "decode_ms_per_tick" not in got
    t = tr.Trace.load(kept)
    (w0, w1), = [(s, e) for n, s, e in t.spans if n == tr.WINDOW_SPAN]
    # ticks within the traced window: a tick the profiler's start or stop
    # cuts may keep one of its two spans
    own = [(s, e) for n, s, e in t.spans
           if n == "engine.tick" and w0 <= s and e <= w1]
    bench = [(s, e) for n, s, e in t.spans if n == "bench.engine_tick"]
    assert own
    assert all(any(bs <= s and e <= be for bs, be in bench)
               for s, e in own)


def test_a_traced_run_holds_engine_spans_and_counters(tmp_path,
                                                      monkeypatch):
    """The run's record keeps the program's own spans beside the
    benchmark's, and its counters as copied at the window's open and
    close; ``step_calls_per_step.batch`` reads the latter."""
    from chipbench import harness
    from chipbench import trace as tr
    monkeypatch.setattr(harness, "TRACE_S", 1.0)   # the window's last 1 s
    root = tiny_root(tmp_path)
    runs = []
    res = harness.run_cell(root, "tiny.game", 3_000_000_019, 2.0, True,
                           on_run=runs.append)
    assert res["correct"] is True
    run = runs[0]
    names = {n for n, _, _ in run.trace.spans}
    assert {tr.WINDOW_SPAN, "bench.engine_tick", "engine.tick",
            "engine.insert", "engine.call"} <= names
    c = run.counters
    assert c["open"]["t"] <= run.window[0] < run.window[1] <= c["close"]["t"]
    for part in ("backend", "engine"):
        assert set(c["open"][part]) == set(c["close"][part])
        assert all(c["close"][part][k] >= c["open"][part][k]
                   for k in c["open"][part])
    assert c["close"]["backend"]["steps"] > c["open"]["backend"]["steps"]
    per_step = res["metrics"]["step_calls_per_step.batch"]["value"]
    assert 1 <= per_step <= run.cfg["deployment"]["concurrency"]
