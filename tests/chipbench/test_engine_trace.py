"""60 ms of a qwen2-0.5b.game-batch window recorded on one TPU v5e with
the program's own ``engine.*`` spans kept beside the benchmark's: the two
share one clock, the engine's programs are named, and the device idle
inside the engine's spans agrees with the benchmark's gap attribution."""
import numpy as np
import pytest

from benchcase import REPO

CHILDREN = {"engine.tick": ("engine.decode", "engine.tick_sync",
                            "engine.tick_update"),
            "engine.insert": ("engine.prefill", "engine.splice",
                              "engine.first_token")}


@pytest.fixture(scope="module")
def engine_trace():
    from chipbench import trace as tr
    return tr.Trace.load(str(REPO / "chipbench" / "testdata"
                             / "trace_v5e_game_engine_60ms.json.gz"))


def spans_of(t, name):
    return [(s, e) for n, s, e in t.spans if n == name]


def inside(iv, parents):
    return any(s <= iv[0] and iv[1] <= e for s, e in parents)


def test_programs_are_named_in_the_trace(engine_trace):
    mods = {m for ops in engine_trace.device_ops.values()
            for _, _, _, m in ops}
    assert any(m.startswith("jit_engine_decode") for m in mods)
    assert any(m.startswith("jit_engine_prefill") for m in mods)
    assert not any("lambda" in m for m in mods)


@pytest.mark.parametrize("parent", sorted(CHILDREN))
def test_engine_spans_nest_inside_the_benchmarks(engine_trace, parent):
    own = spans_of(engine_trace, parent)
    bench = spans_of(engine_trace, parent.replace("engine.", "bench.engine_"))
    assert own and len(own) == len(bench)
    assert all(inside(iv, bench) for iv in own)
    # each span the slice holds whole has one span of each child kind
    whole = [iv for iv in own if inside(iv, [engine_trace.window()])]
    assert whole
    for child in CHILDREN[parent]:
        kids = spans_of(engine_trace, child)
        assert all(inside(iv, own) for iv in kids)
        assert all(sum(inside(k, [iv]) for k in kids) == 1 for iv in whole)


@pytest.mark.parametrize("parent", sorted(CHILDREN))
def test_idle_inside_engine_spans_agrees_with_gap_attribution(
        engine_trace, parent):
    """Device idle inside the union of the engine's spans, on a
    1-microsecond grid, within 15 % of the idle seconds the benchmark's
    gap attribution gives the benchmark's span around them."""
    from chipbench import trace as tr
    t = engine_trace
    w = t.window()
    n = int(round((w[1] - w[0]) * 1e6))

    def grid(intervals):
        g = np.zeros(n, bool)
        for s, e in intervals:
            a = max(0, int(np.floor((s - w[0]) * 1e6)))
            b = min(n, int(np.ceil((e - w[0]) * 1e6)))
            g[a:b] = True
        return g

    from chipbench.engine_readings import span_idle_s
    busy = grid([(s, e) for ops in t.device_ops.values()
                 for _, s, e, _ in ops])
    idle = (~busy & grid(spans_of(t, parent))).sum() * 1e-6
    bench = dict(tr.attribute_gaps(t, w))[
        parent.replace("engine.", "bench.engine_")]
    assert idle > 0
    assert idle == pytest.approx(bench, rel=0.15)
    # the exact reduction against the grid, which rounds busy time out
    assert span_idle_s(t, w, parent) == pytest.approx(idle, rel=0.01)


def hand_trace():
    """Device busy [1, 2.5], [4, 4.5], [6, 7] in a window [0, 10]; the
    engine's spans nest inside the benchmark's, and the last insert runs
    past the window's end."""
    from chipbench import trace as tr
    ops = [("fusion.1", 1.0, 2.0, "jit_engine_decode"),
           ("fusion.2", 1.5, 2.5, "jit_engine_decode"),
           ("fusion.3", 4.0, 4.5, "jit_engine_prefill"),
           ("fusion.1", 6.0, 7.0, "jit_engine_decode")]
    spans = [("bench.window", 0.0, 10.0),
             ("bench.engine_tick", 2.5, 3.5),
             ("engine.tick", 2.6, 3.4),           # idle throughout: 0.8
             ("engine.tick_sync", 3.0, 3.3),      # 0.3
             ("bench.engine_insert", 4.1, 5.1),
             ("engine.insert", 4.2, 5.0),         # idle from 4.5: 0.5
             ("bench.engine_tick", 6.5, 8.0),
             ("engine.tick", 6.6, 7.8),           # idle from 7: 0.8
             ("engine.tick_sync", 6.9, 7.5),      # 0.5
             ("bench.engine_insert", 9.7, 10.5),
             ("engine.insert", 9.8, 10.4)]        # 0.2 in the window
    return tr.Trace({"/device:TPU:0": ops}, sorted(spans, key=lambda x: x[1]))


@pytest.mark.parametrize("name,idle,count", [
    ("engine.tick", 1.6, 2), ("engine.tick_sync", 0.8, 2),
    ("engine.insert", 0.7, 1), ("bench.engine_insert", 0.9, 1),
    ("engine.decode", 0.0, 0)])
def test_span_idle_and_count_by_hand(name, idle, count):
    from chipbench.engine_readings import span_count, span_idle_s
    t = hand_trace()
    assert span_idle_s(t, t.window(), name) == pytest.approx(idle)
    assert span_count(t, t.window(), name) == count


def window_run(t):
    """What ``engine_readings.readings`` reads of a run, around a trace
    alone: no counters, no calls."""
    import types
    return types.SimpleNamespace(window=t.window(), trace=t,
                                 trace_window=t.window(), counters=None,
                                 backend=types.SimpleNamespace(calls=[]))


def test_readings_of_a_trace_without_the_engine_spans():
    """The benchmark's first recorded trace predates the engine's spans:
    its busy time and gap attribution read as before, and the three
    device-trace readings have nothing to read."""
    from chipbench import trace as tr
    from chipbench.engine_readings import readings
    t = tr.Trace.load(str(REPO / "chipbench" / "testdata"
                          / "trace_v5e_game_40ms.json.gz"))
    got = readings(window_run(t))
    assert got["lock_wait_share"] is None
    assert got["ticks"] == got["inserts"] == 0
    for k in ("decode_ms_per_tick", "tick_idle_ms", "insert_idle_ms"):
        assert got[k] is None
    assert got["attributed_s"] == {
        k: v for k, v in tr.attribute_gaps(t, t.window())
        if k.startswith("bench.engine_")}


def test_readings_of_the_engine_trace(engine_trace):
    """Per tick: the decode program's device time, and device idle that
    agrees with the benchmark's gap attribution; per insert likewise."""
    from chipbench.engine_readings import readings
    got = readings(window_run(engine_trace))
    assert got["ticks"] == 5 and got["inserts"] == 1
    assert 6.0 < got["decode_ms_per_tick"] < 7.0
    for kind, n in (("tick", 5), ("insert", 1)):
        assert got[f"{kind}_idle_ms"] * n * 1e-3 == pytest.approx(
            got["attributed_s"][f"bench.engine_{kind}"], rel=0.15)
    # a tick's idle lies in its children and its own eager ops
    parts = sum(got["idle_s"][c] for c in ("engine.decode",
                                           "engine.tick_sync",
                                           "engine.tick_update"))
    assert parts <= got["idle_s"]["engine.tick"]
