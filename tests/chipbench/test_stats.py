"""Percentiles over all queries, and rows counted in the window."""
import types

import pytest


def _run(window=(10.0, 20.0), **kw):
    return types.SimpleNamespace(window=window, window_s=window[1] - window[0],
                                 in_window=lambda t: window[0] <= t < window[1],
                                 **kw)


def test_nearest_rank_percentile():
    from chipbench import stats
    xs = list(range(1, 101))
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile(xs, 90) == 90
    assert stats.percentile([3.0], 90) == 3.0
    assert stats.percentile([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_latency_percentiles_count_missing_queries(tmp_path):
    """A query that never answered counts with the wait it reached when
    the run stopped waiting, so it pushes the tail up instead of dropping
    out of the percentile."""
    from chipbench import harness, stats

    class Handle:
        def __init__(self, finished_s):
            self.finished_s = finished_s

        def result(self, timeout=None):
            if self.finished_s is None:
                raise TimeoutError
            return "answer"

        def done(self):
            return self.finished_s is not None

    recs = [harness.QueryRecord("q", "t0", 11.0 + i * 0.01, 0.0,
                                Handle(11.5 + i * 0.01), [], None)
            for i in range(19)]
    recs.append(harness.QueryRecord("q", "t0", 12.0, 0.0, Handle(None),
                                    [], None))
    harness.settle_open(recs, give_up=82.0)
    assert [r.ok for r in recs] == [True] * 19 + [False]
    lat = [r.latency_s for r in recs]
    assert lat[-1] == 70.0                   # due 12 s, given up at 82 s
    assert stats.percentile(lat, 50) == pytest.approx(0.5)
    assert stats.percentile(lat, 95) == pytest.approx(0.5)
    assert stats.percentile(lat + [80.0], 95) == 70.0


def test_rows_counted_per_call_finished_in_window(tmp_path):
    from chipbench import registry
    calls = [(9.0, 10.5, 32, 32, "filter"),    # begun before, ends inside
             (12.0, 13.0, 32, 32, "map"),
             (14.0, 15.0, 400, 1, "reduce"),   # one engine request
             (19.0, 21.0, 32, 32, "filter")]   # ends after the window
    run = _run(backend=types.SimpleNamespace(calls=calls))
    got = registry.metric_reader([tmp_path], "rows_per_s")(run)
    assert got == pytest.approx((32 + 32 + 1) / 10.0)


def test_slot_occupancy_over_window_ticks(tmp_path):
    from chipbench import registry
    ticks = [(9.5, 64, 0), (10.5, 32, 0), (11.0, 64, 0), (20.5, 0, 0)]
    run = _run(engine=types.SimpleNamespace(ticks=ticks, n_slots=64))
    got = registry.metric_reader([tmp_path], "slot_occupancy.batch")(run)
    assert got == pytest.approx(75.0)
