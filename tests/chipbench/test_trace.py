"""The reduction from a device trace to busy time, idle gaps and kernel
time: on intervals worked out by hand, and on a trace recorded on a
TPU v5e and committed with the benchmark."""

import pytest

from benchcase import REPO


def hand_trace():
    from chipbench import trace as tr
    ops = [("fusion.1", 1.0, 2.0, "jit_step"),
           ("fusion.2", 1.5, 2.5, "jit_step"),               # overlap
           ("custom-call.1", 4.0, 4.5, "jit_rowwise_cosine_jit"),
           ("fusion.1", 6.0, 7.0, "jit_step"),
           ("fusion.3", 9.5, 11.0, "jit_step")]              # past the end
    spans = [("bench.window", 0.0, 10.0),
             ("bench.engine_tick", 2.5, 3.5),    # covers gap (2.5, 4) 2/3
             ("bench.submit", 4.5, 5.0),         # covers gap (4.5, 6) 1/3
             ("bench.backend_call", 4.5, 6.0)]   # ... and fully
    return tr.Trace({"/device:TPU:0": ops}, spans)


def test_busy_union_and_gaps_by_hand():
    from chipbench import trace as tr
    t = hand_trace()
    w = t.window()
    assert w == (0.0, 10.0)
    # busy: [1, 2.5] + [4, 4.5] + [6, 7] + [9.5, 10] = 1.5+0.5+1+0.5
    assert tr.busy_s(t, w) == pytest.approx(3.5)
    assert tr.gaps(t, w) == [(0.0, 1.0), (2.5, 4.0), (4.5, 6.0),
                             (7.0, 9.5)]


def test_per_kernel_time_and_top_ops():
    from chipbench import trace as tr
    t = hand_trace()
    w = t.window()
    assert tr.program_op_time(t, w, "rowwise_cosine") == pytest.approx(0.5)
    assert tr.program_op_time(t, w, "jit_step", "fusion.1") == \
        pytest.approx(2.0)
    top = dict(tr.top_ops(t, w))
    assert top["jit_step/fusion.1"] == pytest.approx(2.0)
    assert top["jit_step/fusion.3"] == pytest.approx(0.5)   # clipped at 10


def test_gap_attribution():
    from chipbench import trace as tr
    t = hand_trace()
    got = dict(tr.attribute_gaps(t, t.window()))
    assert got["bench.engine_tick"] == pytest.approx(1.5)
    assert got["bench.backend_call"] == pytest.approx(1.5)
    assert got["host.other"] == pytest.approx(1.0 + 2.5)
    assert sum(got.values()) == pytest.approx(10.0 - 3.5)


def test_loop_operations_do_not_count_twice():
    from chipbench import trace as tr
    ops = [("while.1", 0.0, 1.0, "p"), ("fusion.1", 0.1, 0.4, "p"),
           ("fusion.2", 0.5, 0.9, "p"), ("copy.1", 1.0, 1.2, "p")]
    t = tr.Trace({"/device:TPU:0": ops}, [("bench.window", 0.0, 2.0)])
    assert [o[0] for o in tr.leaves(ops)] == ["fusion.1", "fusion.2",
                                                "copy.1"]
    assert sum(tr.op_times(t, t.window()).values()) == pytest.approx(0.9)
    assert tr.busy_s(t, t.window()) == pytest.approx(1.2)
    assert tr.op_name("%fusion.12 = bf16[2]{0} fusion(x)") == "fusion.12"


def test_round_trip(tmp_path):
    from chipbench import trace as tr
    t = hand_trace()
    p = tmp_path / "t.json.gz"
    t.save(str(p))
    assert tr.Trace.load(str(p)) == t


def chip_trace():
    from chipbench import trace as tr
    return tr.Trace.load(str(REPO / "chipbench" / "testdata"
                             / "trace_v5e_game_40ms.json.gz"))


def test_recorded_chip_trace_busy_and_idle_by_brute_force():
    """40 ms of a codeqwen1.5-7b-l16 game-batch window recorded on one
    TPU v5e: the interval union against a 1-microsecond grid."""
    import numpy as np
    from chipbench import trace as tr
    t = chip_trace()
    w = t.window()
    ops = t.device_ops["/device:TPU:0"]
    assert len(ops) > 1000
    grid = np.zeros(int(round((w[1] - w[0]) * 1e6)), bool)
    for _, s, e, _ in ops:
        a = max(0, int(np.floor((s - w[0]) * 1e6)))
        b = min(len(grid), int(np.ceil((e - w[0]) * 1e6)))
        grid[a:b] = True
    busy = tr.busy_s(t, w)
    assert busy == pytest.approx(grid.sum() * 1e-6, abs=2e-6 * len(ops))
    idle = sum(e - s for s, e in tr.gaps(t, w))
    assert idle == pytest.approx((w[1] - w[0]) - busy, abs=1e-9)
    got = dict(tr.attribute_gaps(t, w))
    assert sum(got.values()) == pytest.approx(idle, abs=1e-9)
    assert set(got) <= set(tr.GAP_ORDER) | {"host.other"}


def test_recorded_chip_trace_kernel_times():
    from chipbench import trace as tr
    t = chip_trace()
    w = t.window()
    ops = t.device_ops["/device:TPU:0"]
    by_hand = sum(min(e, w[1]) - max(s, w[0]) for n, s, e, _ in ops
                  if n == "fusion.135" and e > w[0] and s < w[1])
    assert by_hand > 0
    assert dict(tr.op_times(t, w))["/fusion.135"] == pytest.approx(by_hand)
    # the decode loop contains its body's operations: counted once
    loops = [o for o in ops if o[0].startswith("while")]
    assert loops and not any(o in tr.leaves(ops) for o in loops)
    assert sum(tr.op_times(t, w).values()) <= tr.busy_s(t, w) + 1e-9
