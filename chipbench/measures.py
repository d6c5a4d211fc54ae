"""Quantities several metric readers share, each from one source."""
from __future__ import annotations

from chipbench import flops as fl
from chipbench import trace as tr


def window_prefills(run):
    return [n for t, n in run.engine.prefills if run.in_window(t)]


def window_ticks(run):
    return [(live, ctx) for t, live, ctx in run.engine.ticks
            if run.in_window(t)]


def engine_flops(run) -> float:
    """Operations the engine's work in the window needs (program_counter:
    the engine's own prefill and tick records)."""
    total = sum(fl.prefill_flops(run.dims, n) for n in window_prefills(run))
    total += sum(fl.decode_flops(run.dims, live, ctx)
                 for live, ctx in window_ticks(run) if live)
    return float(total)


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

