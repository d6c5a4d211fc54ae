"""Smoke run of the semantic serving path on one TPU chip.

    python chip_smoke.py

Drives the main path once, in this one process, through the entry points
a user calls, at the full published width of qwen2-0.5b (the m1 tier's
model; random weights from a seed, f32 as the engine serves by default):

  (a) device check: JAX must report a TPU. Anything else, including a
      libtpu that failed to initialise and left JAX on the CPU, exits
      non-zero before any phase runs.
  (b) token serving: ``repro.launch.serve.main`` with ``--no-reduced``;
      every request must finish with in-vocabulary tokens.
  (c) reference check: the engine's greedy tokens for prompts whose
      lengths are not multiples of the prefill bucket, against a plain
      unpadded, uncached full-sequence forward (see ``reference_check``).
  (d) semantic workload: two movie queries through the streaming
      ``QueryServer`` with the tier-0 embedding cascade; every query
      completes, the engine prefills, every cascade pass succeeds, and
      the cascade's similarity kernel ran natively (``tpu_custom_call``)
      and agrees with a numpy reference.

Each phase prints its wall seconds, its backend compile seconds and
count, its trace/lower seconds (JAX's own monitoring events) and the
engine's stats. The last line is the
JSON verdict, printed only when every phase passed.
"""
from __future__ import annotations

import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if not os.path.isdir(os.path.join(SRC, "repro")):
    sys.exit(f"chip_smoke: no repro package under {SRC}; run it from a "
             "checkout of the repository")
sys.path.insert(0, SRC)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

ARCH = "qwen2-0.5b"
SEED = 0
SLOTS = 4
MAX_LEN = 256
MAX_NEW = 16
REQUESTS = 8
# prompt lengths in tokens (BOS included) for the reference check: none is
# a multiple of the engine's prefill bucket, so every prompt is padded
REF_LENGTHS = (21, 50, 83)
REF_STEPS = 4
# largest accepted gap, in logits, between the reference's best logit and
# its logit for the engine's token (0 when the tokens agree)
REF_TOL = 1e-3
# movie workload queries with cascade-eligible FILTER operators; q8 ends
# in a REDUCE, which the engine always serves
SEMANTIC_QUERIES = ("q2", "q8")
KERNEL_TOL = 1e-5


class CompileClock:
    """From JAX's monitoring events: seconds of backend compilation, the
    number of backend compiles, and seconds of tracing and lowering to
    MLIR (these can nest, so they are kept apart from compilation)."""

    def __init__(self):
        self.compile_s = 0.0
        self.compiles = 0
        self.trace_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1
        elif event.startswith("/jax/core/compile/"):
            self.trace_s += duration


def run_phase(name: str, clock: CompileClock, fn, *args) -> None:
    t0 = time.perf_counter()
    c0, n0, t0_trace = clock.compile_s, clock.compiles, clock.trace_s
    detail = fn(*args)
    print(f"[smoke] phase {name}: ok wall={time.perf_counter() - t0:.3f}s "
          f"compile={clock.compile_s - c0:.3f}s "
          f"compiles={clock.compiles - n0} "
          f"trace_lower={clock.trace_s - t0_trace:.3f}s {detail}",
          flush=True)


def check_device() -> dict:
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    print(f"[smoke] device platform={info['platform']} "
          f"kind={info['kind']} count={info['count']}", flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{dev.platform!r}); nothing was run")
    return info


def token_serving(argv) -> str:
    from repro.data.tokenizer import ByteTokenizer
    from repro.launch import serve
    args = serve.build_parser().parse_args(argv)
    cfg = serve.model_config(args.arch, args.reduced)
    finished = serve.main(argv)
    assert len(finished) == args.requests, finished.keys()
    for req in finished.values():
        out = req.output_ids
        assert 1 <= len(out) <= args.max_new, (req.rid, out)
        assert all(0 <= t < cfg.vocab_size for t in out), (req.rid, out)
        stopped = (out[-1] == ByteTokenizer.eos_id
                   or len(req.prompt_ids) + len(out) >= args.max_len)
        assert len(out) == args.max_new or stopped, (req.rid, out)
    return f"arch={cfg.name} requests={len(finished)}"


def reference_prompts(lengths, seed: int):
    """ASCII prompts of exactly ``n - 1`` characters (BOS makes ``n``
    tokens), cut from the seeded movie table's plots."""
    from repro.data import load_dataset
    table, _ = load_dataset("movie", seed=seed)
    text = " ".join(str(p) for p in table.columns["Plot"])
    text = text.encode("ascii", "ignore").decode()
    prompts, at = [], 0
    for n in lengths:
        prompts.append(text[at:at + n - 1])
        at += n
    return prompts


def reference_check(cfg, lengths=REF_LENGTHS, steps=REF_STEPS,
                    max_len=MAX_LEN, slots=SLOTS, seed=SEED) -> str:
    """The engine (padded prefill, slot-batched cached decode) against a
    plain reference: an unpadded, uncached full-sequence forward over
    prompt + the engine's own tokens, in f32 at the highest matmul
    precision. Under the causal mask, the logits at each position are
    those a rerun of the forward on the sequence up to that step gives.
    With both sides in f32 at the highest precision, they differ only in
    summation order, orders of magnitude below ``REF_TOL``; a token taken
    from a wrong position (a pad, a stale cache slot) misses the best
    logit by about the logits' spread, which is far above it."""
    from repro.data.tokenizer import ByteTokenizer
    from repro.engine import ContinuousBatcher, GenerationEngine
    from repro.engine.engine import PREFILL_ALIGN
    from repro.models import registry, transformer

    bundle = registry.build(cfg)
    params = bundle.init(jax.random.PRNGKey(seed))
    prompts = reference_prompts(lengths, seed)
    tok = ByteTokenizer()
    forward = jax.jit(
        lambda p, t: transformer.forward(p, cfg, t, dtype=jnp.float32))
    worst_gap, min_margin, exact = 0.0, float("inf"), 0
    with jax.default_matmul_precision("highest"):
        engine = GenerationEngine(bundle, params, max_len=max_len,
                                  n_slots=slots)
        batcher = ContinuousBatcher(engine)
        rids = [batcher.submit(p, max_new_tokens=steps) for p in prompts]
        done = batcher.run()
        for rid, prompt, n in zip(rids, prompts, lengths):
            ids, out = done[rid].prompt_ids, done[rid].output_ids
            assert ids == tok.encode(prompt) and len(ids) == n
            assert n % PREFILL_ALIGN and len(out) == steps, (n, out)
            seq = jnp.asarray([ids + out[:-1]], jnp.int32)
            logits = np.asarray(forward(params, seq))[0, n - 1:]
            for row, t in zip(logits, out):
                top2 = np.sort(row)[-2:]
                min_margin = min(min_margin, float(top2[1] - top2[0]))
                worst_gap = max(worst_gap, float(row.max() - row[t]))
                exact += int(t == int(row.argmax()))
    assert worst_gap <= REF_TOL, f"engine token misses the reference's " \
        f"best logit by {worst_gap} > {REF_TOL}"
    return (f"arch={cfg.name} prompts={list(lengths)} steps={steps} "
            f"exact={exact}/{len(lengths) * steps} "
            f"worst_gap={worst_gap:.3e} tol={REF_TOL:.0e} "
            f"min_ref_margin={min_margin:.3e} engine stats={engine.stats}")


def native_kernel_check(table, op, rows: int) -> float:
    """The cascade's similarity program at its morsel shape (``rows``) is
    the native kernel on this backend, and agrees with numpy on the
    served table's embeddings. Returns the largest absolute error."""
    from repro.core import semhash
    from repro.kernels import ops
    interpret = ops.interpret_mode()
    shape = jax.ShapeDtypeStruct((rows, semhash.DIM), jnp.float32)
    hlo = ops.rowwise_cosine_jit.lower(shape, shape, interpret=interpret) \
        .compile().as_text()
    assert not interpret and "tpu_custom_call" in hlo
    vals = semhash.embed(list(table.columns[op.input_column]))
    anchor = np.broadcast_to(semhash.embed_one(op.instruction), vals.shape)
    got = ops.rowwise_cosine(vals, anchor)
    err = float(np.max(np.abs(got - np.sum(vals * anchor, axis=1))))
    assert err <= KERNEL_TOL, err
    return err


def semantic_workload(argv, query_ids=SEMANTIC_QUERIES) -> str:
    from repro.data import WORKLOADS
    from repro.launch import serve

    args = serve.build_parser().parse_args(argv)
    by_id = {q.qid: q for q in WORKLOADS[args.semantic]}
    queries = [by_id[q] for q in query_ids]
    table, cfg, engine, ctx = serve.semantic_context(args)
    handles = serve.serve_queries(args, table, cfg, engine, ctx,
                                  queries=queries)
    assert len(handles) == len(queries)
    for h in handles:
        assert not h.rejected() and not h.failed(), h.name
        stats = h.result().cascade_stats
        assert stats is not None and stats["embed_calls"] > 0, (h.name, stats)
        assert stats["embed_failures"] == 0, (h.name, stats)
    assert engine.stats["prefills"] > 0, engine.stats

    err = native_kernel_check(table, queries[0].plan_for(table).ops[0],
                              rows=ctx.morsel_size)
    return (f"arch={cfg.name} rows={table.n_rows} "
            f"queries={[h.name for h in handles]} "
            f"cascade={[h.result().cascade_stats for h in handles]} "
            f"native_rowwise_cosine=True kernel_err={err:.3e} "
            f"engine stats={engine.stats}")


def main() -> None:
    device = check_device()
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[smoke] compile cache: {enable_compile_cache()}", flush=True)
    from repro.launch import serve
    clock = CompileClock()
    common = ["--slots", str(SLOTS), "--max-len", str(MAX_LEN),
              "--max-new", str(MAX_NEW), "--seed", str(SEED), "--no-reduced"]
    run_phase("b token-serving", clock, token_serving,
              ["--arch", ARCH, "--requests", str(REQUESTS)] + common)
    run_phase("c reference-check", clock, reference_check,
              serve.model_config(ARCH, reduced=False))
    run_phase("d semantic-workload", clock, semantic_workload,
              ["--semantic", "movie", "--cascade", "--serve", "2",
               "--requests", "8"] + common)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
