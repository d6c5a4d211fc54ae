"""One run of one cell: set-up, the measured window, the check, metrics.

``run_cell`` builds the system for the cell's configuration and mix,
warms up every shape the mix's traffic uses, drives the window (open
loop: queries due on a seeded schedule; closed loop: clients that each
wait for their answer), then checks what the window produced against the
plain references, and hands a :class:`Run` record to the metric readers.
Everything that depends on the model's architecture comes from the
configuration's architecture module (``registry.arch``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from chipbench import registry, semref, traffic
from chipbench import system as sysm
from chipbench import trace as tr

REF_SAMPLE = 32          # engine requests the reference re-runs
# The traced run traces the window's last TRACE_S seconds: a trace of all
# 51 s of a small model's window holds about 4 M device operations, and
# reading it took longer than a run may last.
TRACE_S = 10.0
NO_READING = 1e30        # a compared number with nothing to compare
CASCADE_SAMPLE = 64      # cascade passes the reference re-scores


@dataclasses.dataclass
class QueryRecord:
    name: str
    tenant: str
    due: float                 # host clock when it was due
    sent: float                # host clock when it was submitted
    handle: Any
    ops: List[dict]
    plan: Any
    latency_s: float = 0.0     # due -> answer; a missing one: due -> give-up
    ok: bool = False


@dataclasses.dataclass
class Run:
    workload: str
    cfg: dict
    mix: dict
    arch: Any                            # chipbench/archs/<model_type>.py
    dims: Any                            # arch.dims(cfg)
    seed: int
    seconds: float
    window: Tuple[float, float]          # host clock
    setup_s: float
    queries: List[QueryRecord]
    engine: Any                          # RecordingEngine (arrays freed)
    backend: Any                         # RecordingJAXBackend
    embed: Any                           # RecordingEmbeddingBackend | None
    device: dict
    # the program's counters at the window's open and close:
    # {"open"|"close": {"t": host clock, "backend": JAXBackend.stats,
    #                   "engine": GenerationEngine.stats}}
    counters: Optional[Dict[str, dict]] = None
    peaks: Optional[dict] = None
    trace: Optional[tr.Trace] = None
    trace_window: Optional[Tuple[float, float]] = None

    def in_window(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]


class CompileCounter:
    """Backend compiles, and persistent compile-cache hits and misses,
    seen by JAX's monitoring events."""

    def __init__(self):
        self.n = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def __str__(self) -> str:
        return (f"compiles {self.n}, cache hits {self.hits}, "
                f"misses {self.misses}")


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# warm-up
# ---------------------------------------------------------------------------

def prompt_buckets(evaluator, queries, table, max_len: int) -> List[int]:
    """Prefill lengths (the engine's 16-token buckets) the queries can
    send: each operator's prompt over every row that reaches it in the
    reference evaluation (a cascaded filter's rows may all escalate), and
    each reduce's one prompt over the rows that reach it."""
    from repro.engine.engine import PREFILL_ALIGN
    from repro.engine.jax_backend import render_prompt

    def bucket(prompt: str) -> int:
        n = min(len(prompt.encode()) + 1, max_len - 1)
        return -(-n // PREFILL_ALIGN) * PREFILL_ALIGN

    buckets, seen = set(), set()
    for q in queries:
        plan = traffic.build_plan(q, table)
        for spec, op, vals in evaluator.walk(q.op_dicts(), plan.ops):
            if spec["kind"] == "reduce":
                joined = "; ".join(str(v)[:60] for v in vals[:32])
                buckets.add(bucket(render_prompt(op, joined)))
                continue
            for v in vals:
                key = (op.instruction, semref.value_key(v))
                if key not in seen:
                    seen.add(key)
                    buckets.add(bucket(render_prompt(op, v)))
    return sorted(buckets)


def warm_engine(engine, buckets: List[int]) -> None:
    """Compile (or load) the prefill program of each bucket, the slot
    splice, and the decode tick with live slots; then empty the slots."""
    from repro.engine.engine import Request
    for i, b in enumerate(buckets):
        req = Request(-1 - i, "a" * (b - 2), max_new_tokens=2)
        engine.insert(req, i % engine.n_slots)
    engine.decode_tick()
    engine.decode_tick()
    engine.active[:] = False
    engine.slot_req = [None] * engine.n_slots
    jax.block_until_ready(engine.cache)


def warm_cascade(morsel: int, dim: int = 256) -> None:
    """The cascade's kernel at every row count a morsel can hand it."""
    from repro.kernels import ops as kops
    for m in range(1, morsel + 1):
        a = np.ones((m, dim), np.float32) / np.sqrt(dim)
        kops.rowwise_cosine(a, a)


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

def _sleep_until(t: float) -> None:
    while True:
        left = t - time.perf_counter()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


def traced_part(t_open: float, seconds: float, traced_span, stack) -> None:
    """Wait for the traced part of the window, then enter its span."""
    _sleep_until(t_open + max(0.0, seconds - TRACE_S))
    stack.enter_context(traced_span())


def open_loop(server, mix, table, seconds: float, seed: int,
              traced_span) -> Tuple[List[QueryRecord], Tuple[float, float],
                                    dict]:
    arrs = traffic.arrivals(mix, seconds, seed)
    plans = {}
    for a in arrs:
        if a.query not in plans:
            plans[a.query] = traffic.build_plan(a.query, table)
    lane = mix.get("lane", "interactive")
    recs: List[QueryRecord] = []
    t_open = time.perf_counter()
    t_traced, traced = t_open + max(0.0, seconds - TRACE_S), False
    with contextlib.ExitStack() as stack:
        for a in arrs:
            due = t_open + a.due_s
            if due >= t_traced and not traced:
                traced_part(t_open, seconds, traced_span, stack)
                traced = True
            _sleep_until(due)
            sent = time.perf_counter()
            with sysm.Span("bench.submit"):
                h = server.submit(plans[a.query], table, name=a.query.name,
                                  tenant=a.tenant, lane=lane)
            recs.append(QueryRecord(a.query.name, a.tenant, due, sent, h,
                                    a.query.op_dicts(), plans[a.query]))
        if not traced:
            traced_part(t_open, seconds, traced_span, stack)
        _sleep_until(t_open + seconds)
    t_close = time.perf_counter()
    late = traffic.lateness([r.due for r in recs], [r.sent for r in recs])
    return recs, (t_open, t_close), late


def settle_open(recs: List[QueryRecord], give_up: float) -> None:
    """Wait for every query due in the window; one that fails, is refused
    or has not answered by ``give_up`` counts as missing, with the
    latency it had reached then."""
    for r in recs:
        left = give_up - time.perf_counter()
        try:
            r.handle.result(timeout=max(0.0, left))
            r.ok = True
            r.latency_s = r.handle.finished_s - r.due
        except Exception:                         # failed, refused, late
            r.ok = False
            end = r.handle.finished_s if r.handle.done() else None
            r.latency_s = (end if end is not None else give_up) - r.due


def closed_loop(server, mix, table, engine, seconds: float, seed: int,
                traced_span, at_open) -> Tuple[List[QueryRecord],
                                                   Tuple[float, float],
                                                   dict]:
    """Clients that each send their next query when the last one answers.
    The window opens once the engine's slots have filled (or after the
    mix's ``warmup_s``), and closes ``seconds`` later."""
    seqs = traffic.client_sequences(mix, seed, length=64)
    lane = mix.get("lane", "batch")
    stop = threading.Event()
    recs: List[QueryRecord] = []
    lock = threading.Lock()

    def client(k: int, seq):
        for q in seq:
            if stop.is_set():
                return
            plan = traffic.build_plan(q, table)
            t = time.perf_counter()
            with sysm.Span("bench.submit"):
                h = server.submit(plan, table, name=q.name, tenant=f"t{k}",
                                  lane=lane)
            with lock:
                recs.append(QueryRecord(q.name, f"t{k}", t, t, h,
                                        q.op_dicts(), plan))
            try:
                h.result()
            except Exception:
                if stop.is_set():
                    return

    threads = [threading.Thread(target=client, args=(k, s), daemon=True,
                                name=f"bench-client-{k}")
               for k, s in enumerate(seqs)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    deadline = t0 + float(mix.get("warmup_s", 5.0))
    while time.perf_counter() < deadline and not engine.active.all():
        time.sleep(0.01)
    engine.recording = True
    at_open()
    t_open = time.perf_counter()
    with contextlib.ExitStack() as stack:
        traced_part(t_open, seconds, traced_span, stack)
        _sleep_until(t_open + seconds)
    t_close = time.perf_counter()
    return recs, (t_open, t_close), {"threads": threads, "stop": stop}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def sample_requests(engine, window, seed: int, n: int):
    done = [r for r in engine.finished
            if window[0] <= r.done_s < window[1] and r.output_ids]
    if not done:
        return []
    rng = np.random.default_rng(seed)
    longest = max(range(len(done)),
                  key=lambda i: len(done[i].prompt_ids)
                  + len(done[i].output_ids))
    rest = [i for i in range(len(done)) if i != longest]
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [(list(done[i].prompt_ids), list(done[i].output_ids))
            for i in pick]


def cascade_error(embed, window, seed: int, n: int, control=False):
    """Largest gap between a kernel score the window used and the
    reference's, over a seeded sample of the window's kernel passes.
    With ``control`` the scores compared are the reference's own,
    computed in bfloat16 (:func:`bf16_scores`)."""
    passes = [s for p, s in zip(embed.passes, embed.scored)
              if window[0] <= p[1] < window[1]]
    if not passes:
        return None
    rng = np.random.default_rng(seed)
    worst = 0.0
    for i in rng.permutation(len(passes))[:n]:
        op, values, got = passes[i]
        if control:
            got = bf16_scores(op.instruction, values)
        ref = semref.scores(op.instruction, values)
        worst = max(worst, float(np.max(np.abs(ref - got))))
    return worst


def bands(mix: dict) -> Optional[Tuple[float, float]]:
    c = mix.get("cascade")
    return (float(c["lo"]), float(c["hi"])) if c else None


def query_mismatches(recs: List[QueryRecord], evaluator) -> int:
    """Answered queries whose answer differs from the reference's."""
    from repro.core.executor import ROWID
    memo: Dict[str, Any] = {}
    bad = 0
    for r in recs:
        if not r.ok:
            continue
        key = json.dumps(r.ops, sort_keys=True)
        if key not in memo:
            memo[key] = evaluator.evaluate(r.ops, r.plan.ops)
        kind, want = memo[key]
        res = r.handle.result()
        if kind == "scalar":
            good = res.is_reduce and res.scalar == want
        else:
            good = (not res.is_reduce
                    and list(res.table.columns[ROWID]) == want)
        bad += 0 if good else 1
    return bad


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def process_start_s() -> float:
    """Host clock (perf_counter) at which this process started."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        age = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - max(0.0, age)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, *, control: bool = False,
             t_start: Optional[float] = None,
             keep_trace: Optional[str] = None, on_run=None) -> dict:
    """Everything after the device check. Returns the result object."""
    from repro.data import load_dataset
    from repro.launch.query_server import QueryServer

    t_start = process_start_s() if t_start is None else t_start
    bench = registry.load_benchmark(root)
    cell = registry.cell(bench, workload)
    cfg = registry.config(bench, root, cell["config"])
    mix = registry.mix([root], cell["traffic"])
    arch = registry.arch([root], cfg)
    dims = arch.dims(cfg)
    dep = cfg["deployment"]
    compiles = CompileCounter()

    def phase(name: str) -> None:
        log(f"set-up {time.perf_counter() - t_start:.3f}s: {name} done "
            f"({compiles})")

    phase("process start, imports, device")
    table, oracle = load_dataset(mix["dataset"])
    if mix.get("max_rows"):
        table = table.head(int(mix["max_rows"]))
    phase("table")
    engine = sysm.build_engine(arch, cfg, seed)
    jax.block_until_ready(engine.params)
    phase("weights")
    ctx, backend, embed = sysm.build_context(cfg, mix, engine, oracle)

    evaluator = semref.Evaluator(table, oracle, bands(mix))
    buckets = prompt_buckets(evaluator, traffic.queries(mix), table,
                             int(dep["max_len"]))
    phase(f"{len(buckets)} prefill buckets {buckets}")
    warm_engine(engine, buckets)
    if embed is not None:
        warm_cascade(int(dep["morsel_size"]))
    phase("programs warmed")

    tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    tracing = {"on": False}

    def traced_span():
        """Starts the profiler (in a traced run) and opens the span that
        marks the traced part of the window."""
        if trace and not tracing["on"]:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1         # keeps the bench.* spans
            jax.profiler.start_trace(tdir, profiler_options=opts)
            tracing["on"] = True
        return sysm.Span(tr.WINDOW_SPAN)

    def stop_trace():
        if tracing["on"]:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            tracing["on"] = False
            log(f"trace written in {time.perf_counter() - t0:.3f}s")

    marks, snaps = {}, {}

    def at_open():
        marks["c0"] = compiles.n
        snaps["open"] = counters(backend, engine)

    server = QueryServer(ctx)
    try:
        if mix["loop"] == "open":
            if embed is not None:
                embed.recording = True
            engine.recording = True
            at_open()
            recs, window, late = open_loop(server, mix, table, seconds, seed,
                                           traced_span)
            snaps["close"] = counters(backend, engine)
            in_window_compiles = compiles.n - marks["c0"]
            stop_trace()
            settle_open(recs, window[1] + float(
                mix.get("wait_after_close_s", 60.0)))
            log(f"generator lateness {json.dumps(late)}")
        else:
            recs, window, extra = closed_loop(
                server, mix, table, engine, seconds, seed, traced_span,
                at_open)
            snaps["close"] = counters(backend, engine)
            in_window_compiles = compiles.n - marks["c0"]
            extra["stop"].set()
            backend.stop.set()
            stop_trace()
            for t in extra["threads"]:
                t.join(timeout=120)
    finally:
        backend.stop.set()
        server.close()
    engine.recording = False
    setup_s = window[0] - t_start
    log(f"window {window[1] - window[0]:.3f}s, compiles in window "
        f"{in_window_compiles}, set-up {setup_s:.3f}s; in all {compiles}")

    mem_peak = sysm.memory_peak_bytes()
    device = sysm.device_info()
    device["memory_peak_bytes"] = mem_peak

    tr_obj = tr_window = None
    if trace:
        t0 = time.perf_counter()
        tr_obj = tr.load_xplane(tr.find_xplane(tdir))
        tr_window = tr_obj.window()
        log(f"trace read in {time.perf_counter() - t0:.3f}s: "
            f"{sum(map(len, tr_obj.device_ops.values()))} device "
            f"operations, {len(tr_obj.spans)} spans")
        if keep_trace:
            tr_obj.save(keep_trace)
    shutil.rmtree(tdir, ignore_errors=True)

    # ---- the check: program state freed first -----------------------------
    sysm.free_engine(engine)
    from chipbench import reference
    foreign = foreign_calls(ctx, backend)
    requests = sample_requests(engine, window, seed, REF_SAMPLE)
    checks: Dict[str, dict] = {}
    chk = cfg["check"]
    length = int(dep["max_len"]) + 16
    n_out = int(dep["max_new_tokens"])
    # With ``control`` the references' lower-precision controls stand in
    # for the program's output: the fp8 reference's first token at each
    # served position, and the cascade's scores in bfloat16. The same
    # comparisons then have to read them as not correct.
    gaps = None
    if requests:
        t0 = time.perf_counter()
        gaps = reference.served_gaps(arch, dims, seed, requests,
                                     length=length, n_out=n_out,
                                     control=control)
        log(f"reference: {len(requests)} requests, {len(gaps)} served "
            f"tokens{' (fp8 control)' if control else ''}, "
            f"{int((gaps > 0).sum())} not the reference's best, "
            f"{time.perf_counter() - t0:.3f}s")
    else:
        log("reference: the window finished no engine request")
    checks["logit_gap"] = {
        "value": NO_READING if gaps is None else float(gaps.max()),
        "limit": float(chk["logit_gap"])}
    if embed is not None:
        err = cascade_error(embed, window, seed, CASCADE_SAMPLE,
                            control=control)
        checks["cascade_score_err"] = {
            "value": NO_READING if err is None else err,
            "limit": float(mix["cascade"]["score_err"])}
    if mix["loop"] == "open":
        checks["query_mismatches"] = {
            "value": float(query_mismatches(recs, evaluator)),
            "limit": 0.0}
    checks["foreign_llm_calls"] = {"value": float(foreign), "limit": 0.0}
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    run = Run(workload, cfg, mix, arch, dims, seed, seconds, window,
              setup_s, recs, engine, backend, embed, device,
              counters=snaps, trace=tr_obj, trace_window=tr_window)
    if device["platform"] == "tpu":
        from chipbench import peaks
        run.peaks = peaks.peaks(device["kind"])
    if on_run is not None:
        on_run(run)
    t0 = time.perf_counter()
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in registry.metrics_for(bench, section, workload):
        value = registry.metric_reader([root], m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if trace and tr_obj is not None and tr_window is not None:
        device["busy_s"] = tr.busy_s(tr_obj, tr_window)
        device["window_s"] = tr_window[1] - tr_window[0]
    attempted, failed = counts(mix, recs, backend, window)
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and tr_obj is not None and tr_window is not None:
        result["breakdown"] = {
            "device_ops": [[n, t] for n, t in tr.top_ops(tr_obj, tr_window)],
            "idle_gaps": [[n, t] for n, t in
                          tr.attribute_gaps(tr_obj, tr_window)]}
    result["checks"] = checks
    log(f"metrics read in {time.perf_counter() - t0:.3f}s")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return result


def counters(backend, engine) -> dict:
    """Copies of the program's counters, taken under the backend's lock,
    which every write to them holds, so that no step is half counted."""
    with backend._lock:
        return {"t": time.perf_counter(), "backend": dict(backend.stats),
                "engine": dict(engine.stats)}


def foreign_calls(ctx, backend) -> int:
    """LLM calls in the server's bill that the engine tier did not serve."""
    from repro.core.cost_model import EMBED_TIER_NAME
    served = backend.tier.name
    return sum(u.calls for name, u in ctx.meter.by_tier.items()
               if name not in (served, EMBED_TIER_NAME))


def bf16_scores(instruction: str, values) -> np.ndarray:
    """The reference's scores with both embeddings in bfloat16: the
    control of the score check (the kernel computes in float32)."""
    import jax.numpy as jnp
    a = np.stack([semref.embed(v) for v in values])
    b = semref.embed(instruction)
    return np.asarray(jnp.sum(jnp.asarray(a, jnp.bfloat16)
                              * jnp.asarray(b, jnp.bfloat16), axis=1,
                              dtype=jnp.float32), np.float64)


def counts(mix, recs, backend, window) -> Tuple[int, int]:
    """(attempted, failed): the queries due in the window, or in a closed
    loop the engine tier's calls begun in it; and those that failed."""
    if mix["loop"] == "open":
        return len(recs), sum(1 for r in recs if not r.ok)
    begun = [c for c in backend.calls if window[0] <= c[0] < window[1]]
    failed = [t for t in backend.errors if window[0] <= t < window[1]]
    return len(begun) + len(failed), len(failed)
