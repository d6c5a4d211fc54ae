"""Serving engine: continuous batching correctness + scheduler behaviour."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.data.tokenizer import ByteTokenizer
from repro.engine import ContinuousBatcher, GenerationEngine
from repro.models import registry


@pytest.fixture(scope="module")
def served():
    cfg = reduced(get_config("qwen2-0.5b"))
    b = registry.build(cfg)
    params = b.init(jax.random.PRNGKey(0))
    return cfg, b, params


def gen_sequential(bundle, params, prompt, max_new, max_len=96):
    """Reference: single-request engine (n_slots=1)."""
    eng = GenerationEngine(bundle, params, max_len=max_len, n_slots=1)
    cb = ContinuousBatcher(eng)
    rid = cb.submit(prompt, max_new_tokens=max_new)
    return cb.run()[rid].output_ids


def test_continuous_batching_matches_sequential(served):
    _, bundle, params = served
    prompts = [f"semantic query number {i} about movies" for i in range(5)]
    want = [gen_sequential(bundle, params, p, 8) for p in prompts]

    eng = GenerationEngine(bundle, params, max_len=96, n_slots=3)
    cb = ContinuousBatcher(eng)
    rids = [cb.submit(p, max_new_tokens=8) for p in prompts]
    got = cb.run()
    for rid, w in zip(rids, want):
        assert got[rid].output_ids == w, rid


@pytest.mark.parametrize("n_tokens", [17, 33, 65])
def test_first_token_from_last_real_position(served, n_tokens):
    """Prefill right-pads to a multiple of PREFILL_ALIGN; the first
    generated token must come from the last real prompt position, i.e.
    equal the argmax of an unpadded prefill of the same prompt."""
    _, bundle, params = served
    prompt = "".join(chr(97 + (7 * i) % 26) for i in range(n_tokens - 1))
    ids = ByteTokenizer().encode(prompt)
    assert len(ids) == n_tokens
    logits, _ = bundle.prefill(params, {"tokens": jnp.asarray([ids])},
                               max_len=len(ids), dtype=jnp.float32)
    want = int(jnp.argmax(logits[0, -1]))

    eng = GenerationEngine(bundle, params, max_len=96, n_slots=2)
    cb = ContinuousBatcher(eng)
    rid = cb.submit(prompt, max_new_tokens=1)
    assert cb.run()[rid].output_ids == [want]


def test_more_requests_than_slots(served):
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=64, n_slots=2)
    cb = ContinuousBatcher(eng)
    rids = [cb.submit(f"req {i}", max_new_tokens=5) for i in range(9)]
    finished = cb.run()
    assert len(finished) == 9
    assert all(len(finished[r].output_ids) == 5 for r in rids)
    assert eng.stats["prefills"] == 9


def test_occupancy_improves_with_load(served):
    _, bundle, params = served
    eng1 = GenerationEngine(bundle, params, max_len=64, n_slots=4)
    cb1 = ContinuousBatcher(eng1)
    cb1.submit("only one request", max_new_tokens=6)
    cb1.run()
    eng2 = GenerationEngine(bundle, params, max_len=64, n_slots=4)
    cb2 = ContinuousBatcher(eng2)
    for i in range(12):
        cb2.submit(f"request {i}", max_new_tokens=6)
    cb2.run()
    assert eng2.occupancy > eng1.occupancy


def test_max_len_respected(served):
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=48, n_slots=1)
    cb = ContinuousBatcher(eng)
    rid = cb.submit("x" * 200, max_new_tokens=64)    # prompt+gen > max_len
    req = cb.run()[rid]
    assert len(req.prompt_ids) + len(req.output_ids) <= 48


def test_temperature_sampling_differs(served):
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=64, n_slots=1)
    cb = ContinuousBatcher(eng)
    r1 = cb.submit("hello", max_new_tokens=12, temperature=1.5)
    out1 = cb.run(key=jax.random.PRNGKey(0))[r1].output_ids
    eng2 = GenerationEngine(bundle, params, max_len=64, n_slots=1)
    cb2 = ContinuousBatcher(eng2)
    r2 = cb2.submit("hello", max_new_tokens=12, temperature=1.5)
    out2 = cb2.run(key=jax.random.PRNGKey(9))[r2].output_ids
    assert out1 != out2


def test_tokenizer_roundtrip():
    tok = ByteTokenizer()
    s = "Nirvana: semantic ops über tables 🎬"
    assert tok.decode(tok.encode(s, bos=True, eos=True)) == s
    batch = tok.pad_batch([[1, 2], [3, 4, 5]], align=8)
    assert batch.shape == (2, 8)
    assert batch[0, 2] == tok.pad_id


def test_jax_backend_through_executor(served):
    from repro.core import executor as ex
    from repro.core import plan as P
    from repro.core.backends import UsageMeter
    from repro.core.cost import DEFAULT_TIERS
    from repro.engine import JAXBackend
    _, bundle, params = served
    eng = GenerationEngine(bundle, params, max_len=128, n_slots=2)
    be = JAXBackend(DEFAULT_TIERS["m1"], eng, max_new_tokens=4)
    plan = P.LogicalPlan((P.Operator(P.FILTER, "Is it big?", "col"),))
    from repro.core.table import Table
    table = Table({"col": ["tiny", "huge", "medium"]})
    meter = UsageMeter()
    res = ex.execute(plan, table, {"m*": be}, default_tier="m*",
                     meter=meter)
    assert meter.calls("m1") == 3
    assert meter.total.latency_s > 0
    assert res.table is not None
