"""The system under test, built through its public constructors.

Every LLM tier name maps to one ``JAXBackend`` (oracle-echo mode) over one
``GenerationEngine`` that holds the cell's model; the tier-0 cascade is a
``CascadeRouter`` over an ``EmbeddingBackend``; queries go to a
``QueryServer`` over an ``ExecutionContext`` with the threads driver. The
subclasses here only record what the benchmark reads (finished requests,
per-tick slot use, per-call rows and times) and wrap each call into a
layer in a profiler span; they change no result.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

import jax

from chipbench import weights as wts

from repro.core import backends as bk
from repro.core import cascade as casc_mod
from repro.core import plan as plan_ir
from repro.core import runtime as rt
from repro.core.cost_model import DEFAULT_TIERS, TIER_ORDER
from repro.engine.engine import GenerationEngine
from repro.engine.jax_backend import JAXBackend
from repro.models import registry

Span = jax.profiler.TraceAnnotation


class WindowStopped(RuntimeError):
    """Raised by the engine tier's calls once the measured window has
    closed in a cell whose queries outlast it: it stops them cleanly."""


def served_dtype(cfg: dict):
    return wts.DTYPES[cfg["torch_dtype"]]


def program_params(arch, cfg: dict, seed: int):
    """The served weights: the benchmark's seeded weights, in the layout
    of the configuration's architecture module ``arch``, put in the
    program's parameter tree; made by one jitted call in the served
    type."""
    dims = arch.dims(cfg)
    bundle = registry.build(arch.program_config(cfg))
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(0))
    flat = wts.make_all(arch.layout(dims), dims.layers, seed,
                        served_dtype(cfg))
    return bundle, wts.to_program_tree(shapes, flat)


class RecordingEngine(GenerationEngine):
    """``GenerationEngine`` that keeps, while ``recording`` is on, every
    finished request and one record per prefill and per decode tick."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.recording = False
        self.finished: List[Any] = []
        self.prefills: List[tuple] = []     # (t_end, prompt_len)
        self.ticks: List[tuple] = []        # (t_end, live, sum of ctx)

    def insert(self, req, slot):
        with Span("bench.engine_insert"):
            done = super().insert(req, slot)
        if self.recording:
            self.prefills.append((time.perf_counter(), len(req.prompt_ids)))
            if done is not None:
                self.finished.append(done)
        return done

    def decode_tick(self, key=None):
        live = [r for r in self.slot_req if r is not None]
        # context each live slot attends over at this tick (its cache
        # position + the token it writes)
        ctx = sum(len(r.prompt_ids) + len(r.output_ids) for r in live)
        with Span("bench.engine_tick"):
            done = super().decode_tick(key)
        if self.recording:
            self.ticks.append((time.perf_counter(), len(live), ctx))
            self.finished.extend(done)
        return done


@dataclasses.dataclass(eq=False)
class RecordingJAXBackend(JAXBackend):
    """``JAXBackend`` that logs every call (start, end, rows, engine
    requests, operator kind) and every call that failed, and refuses new
    calls once ``stop`` is set."""

    def __post_init__(self):
        self.calls: List[tuple] = []    # (t0, t1, rows, prompts, kind)
        self.errors: List[float] = []   # start of each failed call
        self.stop = threading.Event()
        self._log_lock = threading.Lock()

    def run_values(self, op: plan_ir.Operator, values: Sequence[Any],
                   meter: Optional[bk.UsageMeter] = None,
                   batch_size: int = 1) -> List[Any]:
        if self.stop.is_set():
            raise WindowStopped("measured window closed")
        t0 = time.perf_counter()
        try:
            with Span("bench.backend_call"):
                out = super().run_values(op, values, meter, batch_size)
        except Exception:
            with self._log_lock:
                self.errors.append(t0)
            raise
        prompts = 1 if op.kind == plan_ir.REDUCE else len(values)
        with self._log_lock:
            self.calls.append((t0, time.perf_counter(), len(values),
                               prompts, op.kind))
        return out


class RecordingEmbeddingBackend(casc_mod.EmbeddingBackend):
    """Tier-0 scoring backend that logs every kernel pass: its rows, its
    time, and the values, anchor key and scores for the check."""

    def __init__(self):
        super().__init__()
        self.recording = False
        self.passes: List[tuple] = []   # (t0, t1, rows)
        self.scored: List[tuple] = []   # (op, values, scores)
        self._log_lock = threading.Lock()

    def scores(self, op, values):
        t0 = time.perf_counter()
        with Span("bench.cascade_call"):
            out = super().scores(op, values)
        if self.recording and len(values):
            with self._log_lock:
                self.passes.append((t0, time.perf_counter(), len(values)))
                self.scored.append((op, list(values), out.copy()))
        return out


def build_context(cfg: dict, mix: dict, engine: GenerationEngine, oracle):
    """The served stack over ``engine``: returns (context, engine backend,
    embedding backend or None)."""
    dep = cfg["deployment"]
    tier = DEFAULT_TIERS[dep["tier"]]
    backend = RecordingJAXBackend(tier, engine, oracle=oracle,
                                  max_new_tokens=int(dep["max_new_tokens"]))
    backends: Dict[str, Any] = {name: backend for name in TIER_ORDER}
    router = embed = None
    cascade = mix.get("cascade")
    if cascade:
        embed = RecordingEmbeddingBackend()
        router = casc_mod.CascadeRouter(
            embed, default_bands=casc_mod.CascadeBands(
                lo=float(cascade["lo"]), hi=float(cascade["hi"])))
    ctx = rt.ExecutionContext(backends=backends, default_tier=dep["tier"],
                              concurrency=int(dep["concurrency"]),
                              morsel_size=int(dep["morsel_size"]),
                              driver="threads", cascade=router)
    return ctx, backend, embed


def build_engine(arch, cfg: dict, seed: int) -> RecordingEngine:
    dep = cfg["deployment"]
    bundle, params = program_params(arch, cfg, seed)
    return RecordingEngine(bundle, params, max_len=int(dep["max_len"]),
                           n_slots=int(dep["slots"]),
                           dtype=served_dtype(cfg))


def free_engine(engine: GenerationEngine) -> None:
    """Drop the engine's device arrays so the reference has the chip."""
    for leaf in jax.tree.leaves((engine.params, engine.cache,
                                 engine.last_token)):
        if isinstance(leaf, jax.Array):
            leaf.delete()
    engine.params = engine.cache = engine.last_token = None


def device_info() -> dict:
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0

