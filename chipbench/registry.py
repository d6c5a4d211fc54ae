"""Finds a cell's parts by name: its configuration and traffic mix (data
files), its configuration's architecture, and the reader of each metric
(one small module each).

A name maps to a file without code: ``chipbench/mixes/<mix>.json``,
``chipbench/archs/<model_type>.py`` for the configuration file's
``model_type``, and ``chipbench/metrics/<metric>.py`` with each ``.`` of
the metric's name read as ``_`` (``mfu.batch`` -> ``mfu_batch.py``).
Each is looked up under the checkout's root first and then beside this
module, so a new cell, mix, architecture or metric is a new file and an
entry in ``BENCHMARK.json``.

An architecture module provides ``dims(cfg)`` (its frozen sizes, with
``layers``), ``layout(dims)`` (its weight leaves, ``weights.Leaf``),
``program_config(cfg)`` (the program's ``ModelConfig``, every size from
the file), its plain reference ``hidden(dims, seed, tokens, fp8)`` and
``logits_at(dims, seed, x, at, fp8)`` (``reference.served_gaps`` calls
them), and ``prefill_flops(dims, n)`` and ``decode_flops(dims, live,
ctx_sum)``.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import Iterable, List

HERE = pathlib.Path(__file__).resolve().parent


def _dirs(roots: Iterable[pathlib.Path], sub: str) -> List[pathlib.Path]:
    return [pathlib.Path(r) / "chipbench" / sub for r in roots] + [HERE / sub]


def _find(roots, sub: str, filename: str) -> pathlib.Path:
    for d in _dirs(roots, sub):
        p = d / filename
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {sub}/{filename} under "
                            f"{[str(d) for d in _dirs(roots, sub)]}")


def load_benchmark(root: pathlib.Path) -> dict:
    return json.loads((pathlib.Path(root) / "BENCHMARK.json").read_text())


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, root: pathlib.Path, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((pathlib.Path(root) / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def mix(roots, name: str) -> dict:
    return json.loads(_find(roots, "mixes", f"{name}.json").read_text())


def metric_module_name(metric: str) -> str:
    return metric.replace(".", "_")


def _load(path: pathlib.Path, name: str):
    """The module at ``path``, imported as ``name`` (once per path: kept
    in ``sys.modules``, where dataclasses look their module up)."""
    mod = sys.modules.get(name)
    if mod is not None and mod.__file__ == str(path):
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(roots, metric: str):
    """The ``read(run)`` function of the metric's reader module."""
    name = metric_module_name(metric)
    return _load(_find(roots, "metrics", f"{name}.py"),
                 f"chipbench_metric_{name}").read


def arch(roots, cfg: dict):
    """The module of the configuration's architecture, found by its
    ``model_type``."""
    model_type = cfg["model_type"]
    return _load(_find(roots, "archs", f"{model_type}.py"),
                 f"chipbench_arch_{model_type}")


def metrics_for(bench: dict, section: str, workload: str) -> List[dict]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    the cell reports."""
    return [m for m in bench[section]
            if "workloads" not in m or workload in m["workloads"]]
