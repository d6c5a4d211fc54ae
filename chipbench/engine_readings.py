"""The engine tier's own spans and counters, read over one traced run of a
cell.

    python3 chipbench/engine_readings.py --workload <cell> --seed <n> \
        --seconds <s> [--keep <trace.json.gz>]

from the root of a checkout runs the cell as ``chipbench/run.py ...
--trace 1`` does, and reads the run's record: its trace, which holds the
program's host spans (``engine.*``) beside the benchmark's (``bench.*``),
and its copies of ``JAXBackend.stats`` and ``GenerationEngine.stats``
from the window's open and close (``Run.counters``). The last line of
standard output is one JSON object: the run's result, as ``run.py``
prints it, under ``result``, and under ``engine``:

- ``lock_wait_share``: per cent of the engine tier's call seconds that
  its callers spent blocked on the backend lock, over the window;
- ``decode_ms_per_tick``: device ms of the ``jit_engine_decode`` program
  per ``engine.tick`` span that ends in the traced part of the window;
- ``tick_idle_ms``, ``insert_idle_ms``: device-idle ms inside the
  ``engine.tick`` and ``engine.insert`` spans, per such span;
- ``ticks``, ``inserts``: those span counts;
- ``idle_s``: device-idle seconds inside each span kind of ``CHILDREN``
  and inside the benchmark's ``bench.engine_tick`` and
  ``bench.engine_insert``; ``attributed_s``: the idle seconds that
  ``trace.attribute_gaps`` gives those two;
- ``rows_per_s_untraced``, ``rows_per_s_traced``: rows of the calls that
  finished in the window's untraced and traced parts, over their seconds
  (the cost of tracing).

A reading is null where there is nothing to read: a program without the
counters or spans, a trace with no device operation (a CPU run), or no
tick or insert in the traced part.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
from typing import Optional

ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import harness, registry  # noqa: E402
from chipbench import trace as tr  # noqa: E402
from chipbench.measures import counter_growth  # noqa: E402

# each span kind that parents others, and its children; the eager ops of
# a tick between ``engine.decode`` and ``engine.tick_sync`` are the
# tick's own time
CHILDREN = {"engine.tick": ("engine.decode", "engine.tick_sync",
                            "engine.tick_update"),
            "engine.insert": ("engine.prefill", "engine.splice",
                              "engine.first_token")}
BENCH_SPANS = ("bench.engine_tick", "bench.engine_insert")


def span_idle_s(trace: tr.Trace, window: tr.Interval, name: str) -> float:
    """Seconds of ``window`` in which the first device ran nothing and a
    span called ``name`` was open: idle time inside the union of that span
    kind's intervals."""
    a = tr.gaps(trace, window)
    b = tr.union([(s, e) for n, s, e in trace.spans if n == name])
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):          # both sorted and disjoint
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_count(trace: tr.Trace, window: tr.Interval, name: str) -> int:
    """Spans called ``name`` that end inside ``window``."""
    return sum(1 for n, _, e in trace.spans
               if n == name and window[0] <= e < window[1])


def lock_wait_share(run) -> Optional[float]:
    call_s = counter_growth(run, "backend", "call_s")
    if call_s is None or call_s <= 0:
        return None
    return 100.0 * counter_growth(run, "backend", "lock_wait_s") / call_s


def rows_per_s(run, lo: float, hi: float) -> Optional[float]:
    """Rows of the engine tier's calls that finished in ``[lo, hi)``, over
    its seconds (``rows_per_s``'s count, on part of the window)."""
    if hi <= lo:
        return None
    return sum(p for _, t1, _, p, _ in run.backend.calls
               if lo <= t1 < hi) / (hi - lo)


def readings(run) -> dict:
    out = {"lock_wait_share": lock_wait_share(run)}
    # the traced part is the window's last TRACE_S seconds; on the host's
    # clock, which the trace's own clock is not
    w = run.window
    split = w[1] - min(harness.TRACE_S, w[1] - w[0])
    out["rows_per_s_untraced"] = rows_per_s(run, w[0], split)
    out["rows_per_s_traced"] = rows_per_s(run, split, w[1])
    t, tw = run.trace, run.trace_window
    if t is None or tw is None or not t.device_ops:
        return out
    ticks = span_count(t, tw, "engine.tick")
    inserts = span_count(t, tw, "engine.insert")
    idle = {k: span_idle_s(t, tw, k) for k in (
        *BENCH_SPANS, *[x for p, c in CHILDREN.items() for x in (p, *c)])}
    decode = tr.program_op_time(t, tw, "jit_engine_decode")
    out.update(
        ticks=ticks, inserts=inserts,
        decode_ms_per_tick=1e3 * decode / ticks if ticks else None,
        tick_idle_ms=1e3 * idle["engine.tick"] / ticks if ticks else None,
        insert_idle_ms=(1e3 * idle["engine.insert"] / inserts
                        if inserts else None),
        idle_s=idle,
        attributed_s={k: v for k, v in tr.attribute_gaps(t, tw)
                      if k in BENCH_SPANS})
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep", default=None,
                    help="also save the loaded trace here (.json.gz)")
    return ap.parse_args(argv)


def main(argv=None, root: pathlib.Path = ROOT, require_chip: bool = True,
         compile_cache: bool = True) -> int:
    args = parse(argv)
    t_start = harness.process_start_s()
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, args.workload)
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < int(cell["chips"])):
        print(f"engine_readings: the cell needs {cell['chips']} TPU "
              f"chip(s)", file=sys.stderr, flush=True)
        return 2
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    runs: list = []
    result = harness.run_cell(pathlib.Path(root), args.workload, args.seed,
                              args.seconds, True, t_start=t_start,
                              keep_trace=args.keep, on_run=runs.append)
    print(json.dumps({"result": result, "engine": readings(runs[0])}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
