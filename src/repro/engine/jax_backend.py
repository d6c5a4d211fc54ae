"""JAXBackend — a core.Backend whose tier is an actually-served JAX model.

Wires the Nirvana executor to the serving engine: each semantic-operator
record becomes a prompt; outputs come from real prefill+decode over a model
from the zoo (reduced configs on CPU; the full configs are exercised by the
dry-run). Usage is metered with *measured* wall-clock plus the tier's price
card, so end-to-end examples report true serving latency.

Untrained reduced models emit noise — examples use this backend to
demonstrate the real serving path, optionally composing it with the oracle
("echo" mode) so the analytics answer stays meaningful while latency/cost
numbers are real.

Thread-safety: ``run_values`` may be called from many worker threads at
once (the ``runtime.ThreadPoolDispatcher`` driver). All callers submit into
ONE shared :class:`ContinuousBatcher` and wait; one driver thread per
backend steps it for all of them — slot refill plus one decode tick per
``step()`` — while it has queued or live requests, and hands each caller
its requests when the last one finishes. So concurrent operators'
requests share each decode tick instead of taking turns on the engine.
The backend lock is a :class:`FairLock`: a caller that finds the driver
stepping gets the lock when that step ends, ahead of the driver's next.
The engine's programs run on the driver thread, so a caller's
thread-local JAX settings (``jax.default_matmul_precision`` and the
like) do not reach them.

``stats`` counts the calls, their seconds, the seconds callers spent
blocked on the backend lock, the driver's steps and the calls each step
served; each call is also a ``jax.profiler.TraceAnnotation`` span
(``engine.call``).
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Sequence, Set

from jax.profiler import TraceAnnotation

from repro.core import backends as bk
from repro.core import cost as cost_mod
from repro.core import plan as plan_ir
from repro.engine.engine import (ContinuousBatcher, GenerationEngine,
                                 Request)


def render_prompt(op: plan_ir.Operator, value: Any) -> str:
    head = {plan_ir.FILTER: "Answer true or false.",
            plan_ir.MAP: "Answer concisely.",
            plan_ir.REDUCE: "Aggregate the inputs.",
            plan_ir.RANK: "Score the input 0-9."}[op.kind]
    return f"{head}\nInstruction: {op.instruction}\nInput: {value}\nAnswer:"


class FairLock:
    """A lock handed to its waiters in the order they came: ``release``
    passes it straight to the longest waiter, so a thread that releases
    and takes it again at once queues behind those already waiting.
    Not re-entrant."""

    def __init__(self):
        self._mutex = threading.Lock()
        self._held = False
        self._waiters: Deque[threading.Lock] = collections.deque()

    def acquire(self, blocking: bool = True) -> bool:
        with self._mutex:
            if not self._held:
                self._held = True
                return True
            if not blocking:
                return False
            turn = threading.Lock()
            turn.acquire()
            self._waiters.append(turn)
        turn.acquire()                  # released by the handing ``release``
        return True

    def release(self) -> None:
        with self._mutex:
            if self._waiters:
                self._waiters.popleft().release()    # stays held: handed on
            else:
                self._held = False

    def __enter__(self) -> "FairLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


@dataclasses.dataclass(eq=False)
class _Call:
    """One ``run_values`` call's engine requests, as the driver serves
    them: ``done`` is set once every request finished (``out``) or a step
    failed (``error``)."""
    t0: float
    rids: List[int]
    out: Dict[int, Request] = dataclasses.field(default_factory=dict)
    error: Optional[Exception] = None
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)


@dataclasses.dataclass
class JAXBackend:
    tier: cost_mod.TierSpec
    engine: GenerationEngine
    oracle: Optional[Any] = None      # echo mode: answers from the oracle,
    max_new_tokens: int = 16          # latency/cost from the real engine
    # shared continuous batcher + the lock serializing engine access; every
    # run_values (possibly from many dispatcher threads) submits here, and
    # the driver thread (running while ``_driving``) steps it; ``_owner``
    # maps each request in the batcher to its call, so its values are the
    # calls not yet done. All written only while the lock is held.
    _lock: FairLock = dataclasses.field(
        default_factory=FairLock, init=False, repr=False, compare=False)
    _batcher: Optional[ContinuousBatcher] = dataclasses.field(
        default=None, init=False, repr=False, compare=False)
    _driving: bool = dataclasses.field(
        default=False, init=False, repr=False, compare=False)
    _owner: Dict[int, _Call] = dataclasses.field(
        default_factory=dict, init=False, repr=False, compare=False)
    # calls: finished calls (not failed ones); call_s: seconds from each
    # call's start to the collection of its requests; lock_wait_s:
    # seconds callers spent blocked acquiring the lock; steps: the
    # driver's steps; step_calls: calls with a request queued or live at
    # each step, summed over the steps. Written only while the lock is
    # held.
    stats: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {"calls": 0, "call_s": 0.0,
                                 "lock_wait_s": 0.0, "steps": 0,
                                 "step_calls": 0},
        init=False, repr=False, compare=False)

    def _wait_for_lock(self) -> None:
        """Block on the backend lock, found taken, and count the wait once
        it is held. Callers first try ``self._lock.acquire(False)``: an
        untaken lock is then taken with no clock read."""
        t0 = time.perf_counter()
        self._lock.acquire()
        self.stats["lock_wait_s"] += time.perf_counter() - t0

    def _submit(self, prompts: Sequence[str], t0: float) -> _Call:
        """Queue the call's prompts in the shared batcher, and start the
        driver if none runs."""
        if not self._lock.acquire(False):
            self._wait_for_lock()
        try:
            if self._batcher is None:
                self._batcher = ContinuousBatcher(self.engine)
            rids = [self._batcher.submit(p,
                                         max_new_tokens=self.max_new_tokens)
                    for p in prompts]
            call = _Call(t0, rids)
            if not rids:                        # nothing for the driver
                self.stats["calls"] += 1
                self.stats["call_s"] += time.perf_counter() - t0
                call.done.set()
                return call
            self._owner.update((r, call) for r in rids)
            if not self._driving:
                self._driving = True
                threading.Thread(target=self._drive, daemon=True,
                                 name=f"engine-driver-{self.tier.name}"
                                 ).start()
            return call
        finally:
            self._lock.release()

    def _drive(self) -> None:
        """Step the shared batcher while it has queued or live requests,
        handing each call its requests after the step that finishes the
        last. A step that raises fails every open call and empties the
        batcher and the engine's slots, so the next call starts clean.
        The driver's own waits for the lock are not callers' waits."""
        more = True
        while more:
            with self._lock:
                self.stats["steps"] += 1
                self.stats["step_calls"] += len(set(self._owner.values()))
                try:
                    more = self._batcher.step()
                except Exception as e:           # handed to the callers
                    ready, more = self._fail(e), False
                else:
                    ready = self._finish()
                if not more:
                    self._driving = False
            for call in ready:
                call.done.set()

    def _finish(self) -> List[_Call]:
        """Move the batcher's finished requests to their calls; count and
        return the calls now done. Under the lock."""
        ready = []
        now = time.perf_counter()
        for rid, req in self._batcher.finished.items():
            call = self._owner.pop(rid)
            call.out[rid] = req
            if len(call.out) == len(call.rids):
                self.stats["calls"] += 1
                self.stats["call_s"] += now - call.t0
                ready.append(call)
        self._batcher.finished.clear()
        return ready

    def _fail(self, err: Exception) -> Set[_Call]:
        """Give ``err`` to every open call and drop all their requests,
        queued, live or finished. Under the lock."""
        b, eng = self._batcher, self.engine
        b.queue.clear()
        b.finished.clear()
        eng.active[:] = False
        eng.slot_req = [None] * eng.n_slots
        ready = set(self._owner.values())
        self._owner.clear()
        for call in ready:
            call.error = err
        return ready

    @staticmethod
    def _collect(call: _Call) -> Dict[int, Request]:
        """Wait until the driver has served every request of ``call``;
        return them by request id, or raise the error of the step that
        failed them. The caller takes no lock here: the driver counted
        the call when it finished."""
        call.done.wait()
        if call.error is not None:
            raise call.error
        return call.out

    def run_values(self, op: plan_ir.Operator, values: Sequence[Any],
                   meter: Optional[bk.UsageMeter] = None,
                   batch_size: int = 1) -> List[Any]:
        t0 = time.perf_counter()
        with TraceAnnotation("engine.call", kind=op.kind, rows=len(values)):
            if op.kind == plan_ir.REDUCE:
                joined = "; ".join(str(v)[:60] for v in list(values)[:32])
                prompts = [render_prompt(op, joined)]
            else:
                prompts = [render_prompt(op, v) for v in values]

            call = self._submit(prompts, t0)
            finished = self._collect(call)
            rids = call.rids
            raw = [finished[r].text for r in rids]

            tok_in = sum(cost_mod.text_tokens(p) for p in prompts)
            tok_out = sum(len(finished[r].output_ids or []) for r in rids)
            if meter is not None:
                # per-call latencies are the *measured* per-request SERVICE
                # times (slot insert -> done) from the continuous batcher;
                # the event scheduler re-queues jobs itself, so sojourn
                # time (submit -> done) would double-count the slot-queue
                # wait
                per_call = [max(0.0, finished[r].done_s
                                - (finished[r].started_s
                                   or finished[r].submitted_s))
                            for r in rids]
                meter.record(self.tier.name, bk.Usage(
                    calls=len(prompts), tok_in=tok_in, tok_out=tok_out,
                    usd=self.tier.usd(tok_in, tok_out),
                    latency_s=sum(per_call)),
                    per_call_latency_s=per_call, op_kind=op.kind)

            if self.oracle is not None:
                if op.kind == plan_ir.REDUCE:
                    return [self.oracle.answer_reduce(op, values)]
                return [self.oracle.answer(op, v) for v in values]
            return self._parse(op, raw, values)

    def _parse(self, op: plan_ir.Operator, raw: List[str],
               values: Sequence[Any]) -> List[Any]:
        if op.kind == plan_ir.FILTER:
            return [r.strip().lower().startswith(("t", "y")) for r in raw]
        if op.kind == plan_ir.RANK:
            out = []
            for r in raw:
                digits = [c for c in r if c.isdigit()]
                out.append(int(digits[0]) if digits else 0)
            return out
        return raw
