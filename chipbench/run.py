"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

from the root of a checkout. The cell is looked up in ``BENCHMARK.json``;
its configuration and traffic mix are data files, its metrics small
readers (``chipbench/registry.py``). With ``--trace 0`` the last line of
standard output is the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics from a profiler trace of the window; both say whether
the window's output matched the plain references (``correct``), and the
last lines of standard error give each number compared beside its limit.

The run needs the accelerator: where JAX finds no TPU, or fewer chips
than the cell asks for, it exits with code 2 and prints no result.
``--control 1`` puts the references' lower-precision controls in the
program's place (the fp8 reference's first token at each served position,
the cascade's scores in bfloat16), so the same check has to read
``correct`` false: how the limits were shown to separate. The
benchmark's own runs leave it off.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None, root: pathlib.Path = ROOT,
         require_chip: bool = True, compile_cache: bool = True) -> int:
    args = parse(argv)
    root = pathlib.Path(root)
    if not (root / "BENCHMARK.json").is_file():
        return fail(f"no BENCHMARK.json under {root}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program (src/repro) under {ROOT}")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import harness, registry
    t_start = harness.process_start_s()
    bench = registry.load_benchmark(root)
    try:
        cell = registry.cell(bench, args.workload)
    except KeyError as e:
        return fail(str(e))

    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu"
                         or len(devs) < int(cell["chips"])):
        return fail(f"the cell needs {cell['chips']} TPU chip(s); JAX "
                    f"found {len(devs)} {devs[0].platform!r} device(s)")
    if compile_cache:
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    result = harness.run_cell(root, args.workload, args.seed, args.seconds,
                              bool(args.trace), control=bool(args.control),
                              t_start=t_start)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
