"""Quantities several metric readers share, each from one source."""
from __future__ import annotations

from chipbench import trace as tr


def window_prefills(run):
    return [n for t, n in run.engine.prefills if run.in_window(t)]


def window_ticks(run):
    return [(live, ctx) for t, live, ctx in run.engine.ticks
            if run.in_window(t)]


def engine_flops(run) -> float:
    """Operations the engine's work in the window needs (program_counter:
    the engine's own prefill and tick records), as the configuration's
    architecture counts them."""
    arch, dims = run.arch, run.dims
    total = sum(arch.prefill_flops(dims, n) for n in window_prefills(run))
    total += sum(arch.decode_flops(dims, live, ctx)
                 for live, ctx in window_ticks(run) if live)
    return float(total)


def counter_growth(run, part: str, key: str):
    """Growth over the window of the program's counter ``key`` in
    ``part`` (``backend``: ``JAXBackend.stats``, ``engine``:
    ``GenerationEngine.stats``); None where the run holds no such
    counter."""
    if run.counters is None:
        return None
    opened, closed = (run.counters[k][part] for k in ("open", "close"))
    if key not in closed:
        return None
    return closed[key] - opened[key]


def idle_share(run):
    """Per cent of the traced window in which the device ran nothing."""
    if run.trace is None or run.trace_window is None:
        return None
    w = run.trace_window
    return 100.0 * (1.0 - tr.busy_s(run.trace, w) / (w[1] - w[0]))


def mfu(run):
    if run.peaks is None:
        return None
    work = engine_flops(run)
    if work <= 0:
        return None
    return 100.0 * work / (run.window_s * run.peaks["bf16_flops"])

