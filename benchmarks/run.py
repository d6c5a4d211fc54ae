"""Benchmark aggregator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]

Prints each table and a final ``name,value,derived`` CSV summary, writing
per-benchmark JSON artifacts under artifacts/bench/.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import common
from benchmarks.common import ROOT, TRAJECTORY, write_trajectory
from benchmarks import (appendix_d_search, bench_cascade, bench_coalesce,
                        bench_fault, bench_qos, bench_serve, bench_shard,
                        fig9_fig10_breakdown,
                        fig13_cardinality, fig14_batch_prompting,
                        roofline_report, table2_capability,
                        table4_runtime_cost, table5_quality,
                        table6_optimizer_overhead, table7_judge,
                        table8_semantics_ablation, table9_smart)
from repro.launch.compile_cache import enable_compile_cache

BENCHES = [
    ("bench_coalesce", lambda q: bench_coalesce.run(
        max_rows=48 if q else 96)),
    ("bench_shard", lambda q: bench_shard.run(
        max_rows=48 if q else 96)),
    ("bench_serve", lambda q: bench_serve.run(
        sleep_s=0.03 if q else 0.05)),
    ("bench_qos", lambda q: bench_qos.run(
        delay_s=0.015 if q else 0.02, floods=4 if q else 6,
        probes=4 if q else 6)),
    ("bench_cascade", lambda q: bench_cascade.run(
        n_rows=128 if q else 256)),
    ("bench_fault", lambda q: bench_fault.run(
        n_queries=12 if q else 24, n_rows=24 if q else 32)),
    ("table2_capability", lambda q: table2_capability.run(
        n=200 if q else 500)),
    ("table4_runtime_cost", lambda q: table4_runtime_cost.run(
        datasets=("movie",) if q else ("movie", "estate", "game"))),
    ("table5_quality", lambda q: table5_quality.run(
        datasets=("movie",) if q else ("movie", "estate", "game"))),
    ("table6_optimizer_overhead", lambda q: table6_optimizer_overhead.run()),
    ("table7_judge", lambda q: table7_judge.run(
        datasets=("movie",) if q else ("movie", "estate", "game"))),
    ("table8_semantics_ablation", lambda q: table8_semantics_ablation.run(
        datasets=("movie",) if q else ("movie", "estate"))),
    ("table9_smart", lambda q: table9_smart.run()),
    ("fig9_fig10_breakdown", lambda q: fig9_fig10_breakdown.run(
        datasets=("movie",) if q else ("movie", "estate", "game"))),
    ("fig13_cardinality", lambda q: fig13_cardinality.run()),
    ("fig14_batch_prompting", lambda q: fig14_batch_prompting.run(
        datasets=("movie",) if q else ("movie", "estate"))),
    ("appendix_d_search", lambda q: appendix_d_search.run(
        datasets=("movie",) if q else ("movie", "estate"))),
    ("roofline_report", lambda q: roofline_report.run()),
]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller datasets / fewer samples")
    ap.add_argument("--only", default="",
                    help="run a single benchmark by name substring")
    common.add_driver_arg(ap)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.driver:
        common.set_driver(args.driver)
    if args.coalesce is not None:
        common.set_coalesce(args.coalesce)
    if args.shards is not None:
        common.set_shards(args.shards)
    if args.cascade is not None:
        common.set_cascade(args.cascade)

    summary = []
    n_fail = 0
    for name, fn in BENCHES:
        if args.only and args.only not in name:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            fn(args.quick)
            status = "ok"
        except Exception as e:
            status = f"FAIL: {type(e).__name__}: {e}"
            traceback.print_exc(limit=4)
            n_fail += 1
        summary.append((name, round(time.time() - t0, 1), status))

    write_trajectory()

    print("\n===== summary (name,seconds,status) =====")
    for name, dt, status in summary:
        print(f"{name},{dt},{status}")
    if n_fail:
        sys.exit(1)


if __name__ == "__main__":
    main()
