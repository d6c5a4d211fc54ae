"""Serving engine: continuous-batching generation over the model zoo.

Slot-based runtime in the vLLM mold, adapted to JAX/TPU:

  * a fixed slot-batched decode cache (``init_cache(..., per_slot_pos=True)``)
    — every slot decodes at its own depth; KV writes are per-slot one-hot
    blends (models/attention.write_kv)
  * prefill runs per request (B=1, lengths bucketed to limit recompiles)
    and is *inserted* into the slot batch with dynamic_update_slice along
    the batch axis of every cache leaf
  * decode steps run over all slots every tick; finished/empty slots decode
    garbage that the next insert overwrites (the standard trade: one wasted
    lane beats a re-trace)

The engine is architecture-agnostic: GQA / MLA KV caches and SSM / hybrid
recurrent states all flow through the same Param-tree insert because cache
leaves carry their logical axes ("batch" marks the slot dim).

Each insert and tick is a ``jax.profiler.TraceAnnotation`` span named
``engine.*``, with a child span per phase, so a profiler trace shows which
part of the host path leaves the device idle; a span costs an enabled-check
when no profiler runs. ``GenerationEngine.stats`` counts the same work.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.data.tokenizer import ByteTokenizer
from repro.models import common as cm

PREFILL_ALIGN = 16


@dataclasses.dataclass
class Request:
    rid: int
    prompt: str
    max_new_tokens: int = 32
    temperature: float = 0.0
    # filled during processing
    prompt_ids: Optional[list] = None
    output_ids: Optional[list] = None
    slot: int = -1
    submitted_s: float = 0.0
    started_s: float = 0.0      # slot insert (service start, not enqueue)
    done_s: float = 0.0

    @property
    def text(self) -> str:
        return ByteTokenizer().decode(self.output_ids or [])


def _batch_index(p: cm.Param) -> int:
    return p.axes.index("batch")


def jitted_steps(bundle, *, max_len: int, dtype):
    """The engine's two compiled programs: ``engine_decode(params, cache,
    token)`` over every slot, and ``engine_prefill(params, batch)`` for one
    request. Their names name the modules in a device trace
    (``jit_engine_decode``, ``jit_engine_prefill``)."""
    def engine_decode(params, cache, token):
        return bundle.decode_step(params, cache, token, dtype=dtype)

    def engine_prefill(params, batch):
        return bundle.prefill(params, batch, max_len=max_len, dtype=dtype)

    return jax.jit(engine_decode), jax.jit(engine_prefill)


class GenerationEngine:
    def __init__(self, bundle, params, *, max_len: int = 256,
                 n_slots: int = 4, dtype=jnp.float32,
                 tokenizer: Optional[ByteTokenizer] = None):
        self.bundle = bundle
        self.params = params
        self.max_len = max_len
        self.n_slots = n_slots
        self.dtype = dtype
        self.tok = tokenizer or ByteTokenizer()
        self.cache = bundle.init_cache(n_slots, max_len, dtype=dtype,
                                       per_slot_pos=True)
        self.last_token = jnp.zeros((n_slots, 1), jnp.int32)
        self.active = np.zeros((n_slots,), bool)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self._decode_jit, self._prefill_jit = jitted_steps(
            bundle, max_len=max_len, dtype=dtype)
        # ticks; live_slot_ticks: live slots summed over ticks (each a token
        # appended to a request, besides the first, at insert);
        # context_tokens: positions the live slots attend over, summed over
        # ticks (prompt and output so far, the token written included)
        self.stats = {"ticks": 0, "live_slot_ticks": 0, "prefills": 0,
                      "prompt_tokens": 0, "context_tokens": 0}

    # ------------------------------------------------------------------
    def free_slots(self) -> List[int]:
        return [i for i in range(self.n_slots) if not self.active[i]]

    def insert(self, req: Request, slot: int) -> Optional[Request]:
        """Prefill one request and splice it into the slot batch. Returns
        the request if it finished at prefill (prompt fills the window)."""
        req.started_s = time.perf_counter()
        ids = self.tok.encode(req.prompt)[: self.max_len - 1]
        req.prompt_ids = ids
        req.output_ids = []
        req.slot = slot

        def splice(dst: cm.Param, src: cm.Param) -> cm.Param:
            if dst.axes == ("batch",) or dst.axes == ():   # pos vector
                return dst
            bi = _batch_index(dst)
            idx = [0] * dst.value.ndim
            idx[bi] = slot
            return cm.Param(jax.lax.dynamic_update_slice(
                dst.value, src.value.astype(dst.value.dtype), tuple(idx)),
                dst.axes)

        with TraceAnnotation("engine.insert", rid=req.rid, slot=slot,
                             prompt_len=len(ids)):
            tokens = self.tok.pad_batch([ids], align=PREFILL_ALIGN)
            # prefill right-pads the prompt: the first token is predicted
            # from the last real position; the next cache position is
            # len(ids)
            with TraceAnnotation("engine.prefill"):
                logits, cache1 = self._prefill_jit(
                    self.params, {"tokens": jnp.asarray(tokens),
                                  "last_index": jnp.asarray([len(ids) - 1],
                                                            jnp.int32)})
            with TraceAnnotation("engine.splice"):
                self.cache = jax.tree.map(splice, self.cache, cache1,
                                          is_leaf=cm.is_param)
                pos = self.cache["pos"].value.at[slot].set(len(ids))
                self.cache["pos"] = cm.Param(pos, ("batch",))
            with TraceAnnotation("engine.first_token"):
                nxt = jnp.argmax(logits[0, -1]).astype(jnp.int32)
                self.last_token = self.last_token.at[slot, 0].set(nxt)
                req.output_ids.append(int(nxt))
        self.stats["prefills"] += 1
        self.stats["prompt_tokens"] += len(ids)
        if (len(ids) + 1 >= self.max_len
                or len(req.output_ids) >= req.max_new_tokens):
            req.done_s = time.perf_counter()
            return req                      # finished at prefill
        self.active[slot] = True
        self.slot_req[slot] = req
        return None

    def decode_tick(self, key=None) -> List[Request]:
        """One decode step across all slots; returns finished requests."""
        live = int(self.active.sum())
        with TraceAnnotation("engine.tick", live=live):
            with TraceAnnotation("engine.decode"):
                logits, self.cache = self._decode_jit(
                    self.params, self.cache, self.last_token)
            # keep idle slots parked at position 0 (their writes are
            # overwritten by the next insert; parking avoids pos growing
            # past max_len)
            pos = self.cache["pos"].value
            pos = jnp.where(jnp.asarray(self.active), pos, 0)
            pos = jnp.minimum(pos, self.max_len - 1)
            self.cache["pos"] = cm.Param(pos, ("batch",))

            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            if key is not None:
                temps = np.array([self.slot_req[i].temperature
                                  if self.slot_req[i] else 0.0
                                  for i in range(self.n_slots)], np.float32)
                if (temps > 0).any():
                    g = jax.random.gumbel(key, logits[:, -1].shape)
                    samp = jnp.argmax(
                        logits[:, -1] / jnp.maximum(temps[:, None], 1e-6)
                        + g, axis=-1).astype(jnp.int32)
                    nxt = jnp.where(jnp.asarray(temps > 0), samp, nxt)
            self.last_token = nxt[:, None]

            with TraceAnnotation("engine.tick_sync"):
                nxt_host = np.asarray(nxt)
            done: List[Request] = []
            context = 0
            with TraceAnnotation("engine.tick_update"):
                for i in range(self.n_slots):
                    req = self.slot_req[i]
                    if req is None or not self.active[i]:
                        continue
                    context += len(req.prompt_ids) + len(req.output_ids)
                    req.output_ids.append(int(nxt_host[i]))
                    eos = nxt_host[i] == self.tok.eos_id
                    full = len(req.output_ids) >= req.max_new_tokens
                    over = (len(req.prompt_ids) + len(req.output_ids)
                            >= self.max_len)
                    if eos or full or over:
                        req.done_s = time.perf_counter()
                        self.active[i] = False
                        self.slot_req[i] = None
                        done.append(req)
        self.stats["ticks"] += 1
        self.stats["live_slot_ticks"] += live
        self.stats["context_tokens"] += context
        return done

    @property
    def occupancy(self) -> float:
        """Mean share of the slots that were live over the ticks."""
        n = max(1, self.stats["ticks"]) * self.n_slots
        return self.stats["live_slot_ticks"] / n


class ContinuousBatcher:
    """Request queue + slot scheduler over a GenerationEngine."""

    def __init__(self, engine: GenerationEngine):
        self.engine = engine
        self.queue: List[Request] = []
        self.finished: Dict[int, Request] = {}
        self._next_rid = 0

    def submit(self, prompt: str, max_new_tokens: int = 32,
               temperature: float = 0.0) -> int:
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens, temperature,
                      submitted_s=time.perf_counter())
        self.queue.append(req)
        return rid

    def _fill_slots(self) -> None:
        for slot in self.engine.free_slots():
            if not self.queue:
                break
            done = self.engine.insert(self.queue.pop(0), slot)
            if done is not None:
                self.finished[done.rid] = done

    def step(self, key=None) -> bool:
        """One scheduling round: fill free slots from the queue, then one
        decode tick. Returns True while work remains. This is the unit
        that ``JAXBackend``'s one driver thread runs under the backend's
        lock for every caller that shares the batcher: requests the
        callers submitted since the last step enter free slots here, so
        they batch together on the engine's slots. ``key`` seeds THIS
        tick's sampling only;
        a caller looping step() with temperature>0 requests must split a
        fresh subkey per call (as ``run`` does) or every tick reuses the
        same noise."""
        self._fill_slots()
        if self.engine.active.any():
            for req in self.engine.decode_tick(key):
                self.finished[req.rid] = req
        return bool(self.queue or self.engine.active.any())

    def run(self, key=None) -> Dict[int, Request]:
        """Drive to completion: fill free slots, tick, repeat — one
        ``step`` per round, splitting a fresh sampling subkey per tick."""
        while self.queue or self.engine.active.any():
            self._fill_slots()
            sub = None
            if key is not None and self.engine.active.any():
                key, sub = jax.random.split(key)
            self.step(sub)
        return self.finished
