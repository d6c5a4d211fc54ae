"""Find the highest rate an open-loop cell sustains: run its mix at each
of a few rates, one short window each, in this one process.

    python3 chipbench/sweep.py --workload <cell> --seconds 30 \
        --rates 1,2,3,4 --seed 7

For each rate it prints the median and 90th-percentile latency and the
mean latency of the queries due in the first and in the second half of
the window: where the second half waits much longer than the first, the
backlog grows and the rate is above what the cell sustains. The mix's
file is not changed; each rate runs from a copy of the benchmark's files.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 2
    from chipbench import harness, registry, stats
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    bench = registry.load_benchmark(ROOT)
    cell = registry.cell(bench, args.workload)
    mix = registry.mix([ROOT], cell["traffic"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tmp = pathlib.Path(tempfile.mkdtemp(prefix="chipbench-sweep-"))
        shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
        shutil.copytree(ROOT / "chipbench" / "configs",
                        tmp / "chipbench" / "configs")
        (tmp / "chipbench" / "mixes").mkdir()
        (tmp / "chipbench" / "mixes" / f"{mix['name']}.json").write_text(
            json.dumps(dict(mix, rate_qps=rate)))
        runs = []
        harness.run_cell(tmp, args.workload, args.seed + i, args.seconds,
                         False, on_run=runs.append)
        run = runs[0]
        mid = run.window[0] + run.window_s / 2
        lat = [q.latency_s for q in run.queries]
        first = [q.latency_s for q in run.queries if q.due < mid]
        second = [q.latency_s for q in run.queries if q.due >= mid]
        print(json.dumps({
            "rate_qps": rate, "queries": len(lat),
            "missing": sum(1 for q in run.queries if not q.ok),
            "p50_s": stats.percentile(lat, 50),
            "p90_s": stats.percentile(lat, 90),
            "mean_first_half_s": sum(first) / max(1, len(first)),
            "mean_second_half_s": sum(second) / max(1, len(second))}),
            flush=True)
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
