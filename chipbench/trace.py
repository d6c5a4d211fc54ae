"""From a profiler trace to the device's busy time, idle gaps and kernel
times.

``load_xplane`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps two lists on one clock: the device's operations (the ``XLA Ops``
line of each TPU plane) and the host spans of the benchmark
(``bench.*``) and of the program (``engine.*``), each written by
``jax.profiler.TraceAnnotation``. Everything else reduces those lists,
so the reduction can be checked on a small recorded trace without a
chip.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # (start_s, end_s)

SPAN_PREFIXES = ("bench.", "engine.")
WINDOW_SPAN = "bench.window"
# what the host was doing, most specific first: an idle gap goes to the
# first of these whose span covers most of it
GAP_ORDER = ("bench.engine_tick", "bench.engine_insert",
             "bench.cascade_call", "bench.backend_call", "bench.submit")


@dataclasses.dataclass
class Trace:
    # per device: [(name, start_s, end_s, program)], sorted by start;
    # program is the XLA module (jitted program) the operation ran in
    device_ops: Dict[str, List[Tuple[str, float, float, str]]]
    # [(name, start_s, end_s)] of the benchmark's and the program's host
    # spans
    spans: List[Tuple[str, float, float]]

    def window(self) -> Optional[Interval]:
        w = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        return (min(s for s, _ in w), max(e for _, e in w)) if w else None

    def to_json(self) -> dict:
        return {"device_ops": self.device_ops, "spans": self.spans}

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(x) for x in v]
                    for k, v in d["device_ops"].items()},
                   [tuple(x) for x in d["spans"]])

    def save(self, path: str) -> None:
        with gzip.open(path, "wt") as f:
            json.dump(self.to_json(), f)

    @classmethod
    def load(cls, path: str) -> "Trace":
        with gzip.open(path, "rt") as f:
            return cls.from_json(json.load(f))


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Tuple[str, float, float, str]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            line = lines.get("XLA Ops")
            if line is None:
                continue
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
            ops, k = [], 0
            for e in sorted(line.events, key=lambda e: e.start_ns):
                s0, s1 = e.start_ns, e.start_ns + e.duration_ns
                while k < len(mods) and mods[k][1] < s0:
                    k += 1
                mod = mods[k][2] if k < len(mods) and mods[k][0] <= s0 \
                    else ""
                ops.append((op_name(e.name), s0 * 1e-9, s1 * 1e-9, mod))
            device_ops[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    spans.sort(key=lambda x: x[1])
    return Trace(device_ops, spans)


def op_name(hlo: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def leaves(ops):
    """The operations that contain no other (a loop's body operations,
    not the loop), so that summed times count each interval once."""
    out = []
    for i, op in enumerate(ops):
        nxt = ops[i + 1] if i + 1 < len(ops) else None
        if nxt is not None and nxt[1] < op[2] and nxt[2] <= op[2]:
            continue
        out.append(op)
    return out


def clip(intervals: Sequence[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi and min(e, hi) > max(s, lo)]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Disjoint, sorted union of ``intervals``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace, window: Interval) -> float:
    """Seconds in ``window`` in which some operation ran, averaged over
    the devices in the trace."""
    if not trace.device_ops:
        return 0.0
    total = 0.0
    for ops in trace.device_ops.values():
        total += sum(e - s for s, e in union(
            clip([(s, e) for _, s, e, _ in ops], window)))
    return total / len(trace.device_ops)


def gaps(trace: Trace, window: Interval) -> List[Interval]:
    """Intervals of ``window`` in which the first device ran nothing."""
    if not trace.device_ops:
        return [window]
    ops = next(iter(trace.device_ops.values()))
    busy = union(clip([(s, e) for _, s, e, _ in ops], window))
    out, at = [], window[0]
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < window[1]:
        out.append((at, window[1]))
    return out


def _overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute_gaps(trace: Trace, window: Interval,
                   top: int = 10) -> List[Tuple[str, float]]:
    """Idle seconds by what the host was doing. Each gap goes to the first
    span kind in ``GAP_ORDER`` that covers half of it or more, else to the
    kind that covers most of it, else to ``host.other``. Largest first."""
    by_kind: Dict[str, List[Interval]] = collections.defaultdict(list)
    for name, s, e in trace.spans:
        if name in GAP_ORDER:
            by_kind[name].append((s, e))
    merged = {k: union(v) for k, v in by_kind.items()}
    totals: Dict[str, float] = collections.defaultdict(float)
    for g in gaps(trace, window):
        cover = {k: sum(_overlap(g, iv) for iv in merged.get(k, ()))
                 for k in GAP_ORDER}
        half = [k for k in GAP_ORDER if cover[k] >= 0.5 * (g[1] - g[0])]
        best = max(GAP_ORDER, key=lambda k: cover[k])
        name = half[0] if half else (best if cover[best] > 0
                                     else "host.other")
        totals[name] += g[1] - g[0]
    return sorted(totals.items(), key=lambda kv: -kv[1])[:top]


def op_times(trace: Trace, window: Interval) -> Dict[str, float]:
    """Device seconds per ``program/operation`` inside ``window``, summed
    over the devices, counting only operations that contain no other."""
    out: Dict[str, float] = collections.defaultdict(float)
    for ops in trace.device_ops.values():
        for name, s, e, mod in leaves(ops):
            out[f"{mod}/{name}"] += _overlap((s, e), window)
    return dict(out)


def top_ops(trace: Trace, window: Interval,
            top: int = 10) -> List[Tuple[str, float]]:
    return sorted(op_times(trace, window).items(),
                  key=lambda kv: -kv[1])[:top]


def program_op_time(trace: Trace, window: Interval, program: str,
                    op: str = "") -> float:
    """Device seconds, inside ``window``, of the operations that ran in a
    program whose name contains ``program`` and whose own name contains
    ``op``, summed over the devices."""
    return sum(_overlap((s, e), window)
               for ops in trace.device_ops.values()
               for name, s, e, mod in leaves(ops)
               if program in mod and op in name)
