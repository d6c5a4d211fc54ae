"""The check has to fail: the fp8 control in the reference's place, and
each fault a served cell can have, planted where its output is made."""
import json

import pytest

from benchcase import DATA, REPO, run_tiny, tiny_root


@pytest.fixture
def tiny(tmp_path):
    """A throwaway root with the CPU-sized cells ``tiny.movie`` and
    ``tiny.game``."""
    return tiny_root(tmp_path)


def _tiny():
    """The tiny configuration and its architecture module."""
    from chipbench import registry
    cfg = json.loads((DATA / "tiny.json").read_text())
    return cfg, registry.arch([REPO], cfg)


def _served(arch, cfg, seed, dtype):
    from chipbench import system as sysm
    from repro.engine.engine import ContinuousBatcher
    cfg = dict(cfg, torch_dtype=dtype)
    engine = sysm.build_engine(arch, cfg, seed)
    b = ContinuousBatcher(engine)
    rids = [b.submit(f"Answer true or false. Row {i}: " + "x" * (7 * i),
                     max_new_tokens=4) for i in range(8)]
    done = b.run()
    return [(done[r].prompt_ids, done[r].output_ids) for r in rids]


@pytest.mark.parametrize("seed", [11, 2**33 + 7, 3_000_000_019])
def test_fp8_control_fails_the_logit_limit(seed):
    from chipbench import reference
    cfg, arch = _tiny()
    dims = arch.dims(cfg)
    limit = cfg["check"]["logit_gap"]
    reqs = _served(arch, cfg, seed, "bfloat16")
    kw = dict(length=176, n_out=4)
    program = reference.served_gaps(arch, dims, seed, reqs, **kw).max()
    control = reference.served_gaps(arch, dims, seed, reqs, control=True,
                                    **kw).max()
    assert program <= limit < control, (program, limit, control)


def test_float32_engine_matches_the_reference_exactly():
    from chipbench import reference
    cfg, arch = _tiny()
    import jax
    with jax.default_matmul_precision("highest"):
        reqs = _served(arch, cfg, 5, "float32")
    gaps = reference.served_gaps(arch, arch.dims(cfg), 5, reqs, length=176,
                                 n_out=4)
    assert gaps.max() == 0.0


def test_altered_token_is_not_correct(tiny, capsys, monkeypatch):
    from repro.engine import engine as eng

    real = eng.GenerationEngine.decode_tick

    def altered(self, key=None):
        done = real(self, key)
        for req in list(self.slot_req) + done:
            if req is not None and len(req.output_ids) == 2:
                req.output_ids[-1] = (req.output_ids[-1] + 1) % 512
        return done

    monkeypatch.setattr(eng.GenerationEngine, "decode_tick", altered)
    rc, res = run_tiny(tiny, "tiny.game", seconds=2.0, capsys=capsys)
    assert rc == 0 and res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_answer_is_not_correct(tiny, capsys, monkeypatch):
    from repro.core import plan as plan_ir
    from repro.engine import jax_backend

    real = jax_backend.JAXBackend.run_values

    def altered(self, op, values, meter=None, batch_size=1):
        out = real(self, op, values, meter, batch_size)
        if op.kind == plan_ir.FILTER and out:
            out = [not out[0]] + list(out[1:])
        return out

    monkeypatch.setattr(jax_backend.JAXBackend, "run_values", altered)
    rc, res = run_tiny(tiny, "tiny.movie", seconds=2.0, capsys=capsys)
    assert rc == 0 and res["correct"] is False
    assert res["checks"]["query_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell,checks", [
    ("tiny.game", {"logit_gap"}),
    ("tiny.movie", {"logit_gap", "cascade_score_err"})])
def test_control_in_the_programs_place_is_not_correct(tiny, capsys, cell,
                                                      checks):
    """``--control 1``: the fp8 reference's tokens and the bfloat16 scores
    go through the run's own comparison, which reads them as wrong."""
    rc, res = run_tiny(tiny, cell, seconds=2.0, capsys=capsys, control=1)
    assert rc == 0 and res["correct"] is False
    failed = {n for n, c in res["checks"].items() if c["value"] > c["limit"]}
    assert failed == checks, res["checks"]
