"""Whole runs of CPU-sized cells through the harness: the result line's
shape, the checks, and the refusal to run without a chip."""
import shutil
import subprocess
import sys

import pytest

from benchcase import REPO, run_tiny, tiny_root


@pytest.fixture
def tiny(tmp_path):
    """A throwaway root with the CPU-sized cells ``tiny.movie`` and
    ``tiny.game``."""
    return tiny_root(tmp_path)


def test_open_loop_run_is_correct_and_complete(tiny, capsys):
    rc, res = run_tiny(tiny, "tiny.movie", seconds=2.0, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["attempted"] == 6 and res["failed"] == 0
    assert set(res["metrics"]) == {"rows_per_s", "setup_s"}
    assert res["metrics"]["rows_per_s"]["value"] > 0
    assert res["device"]["platform"] == "cpu"
    # the window's escalations and reduces reach the engine, and the
    # served tokens, the cascade's scores and the answers are compared
    assert set(res["checks"]) == {"logit_gap", "cascade_score_err",
                                  "query_mismatches", "foreign_llm_calls"}
    for name, c in res["checks"].items():
        assert c["value"] <= c["limit"], (name, c)


def test_closed_loop_traced_run_reports_layer_metrics(tiny, capsys,
                                                     monkeypatch):
    from chipbench import harness
    monkeypatch.setattr(harness, "TRACE_S", 1.0)   # the window's last 1 s
    rc, res = run_tiny(tiny, "tiny.game", seconds=2.0, trace=1,
                       capsys=capsys)
    assert rc == 0 and res["correct"] is True
    # mfu needs a chip's peaks: a CPU run leaves it out instead of
    # reporting a number against a guessed peak
    assert "mfu.batch" not in res["metrics"]
    assert 0 < res["metrics"]["slot_occupancy.batch"]["value"] <= 100
    assert 0.9 < res["device"]["window_s"] < 1.5
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert res["attempted"] > 0


def test_no_chip_means_no_result(tiny, capsys):
    from chipbench import run
    rc = run.main(["--workload", "tiny.movie", "--seed", "1",
                   "--seconds", "1"], root=tiny, compile_cache=False)
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with BENCHMARK.json and the benchmark's own files but
    not the program exits non-zero and prints nothing."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-0.5b.game-batch", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout == ""
