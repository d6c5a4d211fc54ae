"""Cells, configurations, mixes and metrics are found by name, and a new
one is a new file plus an entry, with no existing file edited."""
import json
import types

import pytest

from benchcase import REPO


def test_every_named_part_of_the_benchmark_exists():
    from chipbench import registry
    bench = registry.load_benchmark(REPO)
    for w in bench["workloads"]:
        cfg = registry.config(bench, REPO, w["config"])
        assert cfg["name"] == w["config"]
        assert registry.mix([REPO], w["traffic"])["name"] == w["traffic"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(registry.metric_reader([REPO], m["name"]))
    # the interactive mix left for a later cell is there too
    assert registry.mix([REPO], "movie-interactive")["loop"] == "open"


def test_metric_names_map_to_module_files():
    from chipbench import registry
    assert registry.metric_module_name("mfu.batch") == "mfu_batch"
    assert registry.metric_module_name("rows_per_s") == "rows_per_s"


def test_parts_added_in_another_root_are_found(tmp_path):
    from chipbench import registry
    (tmp_path / "chipbench" / "metrics").mkdir(parents=True)
    (tmp_path / "chipbench" / "mixes").mkdir(parents=True)
    (tmp_path / "chipbench" / "metrics" / "answers_per_s_new.py").write_text(
        "def read(run):\n    return len(run.queries) / run.window_s\n")
    mix = {"name": "new-mix", "dataset": "movie", "loop": "open",
           "rate_qps": 1.0, "templates": []}
    (tmp_path / "chipbench" / "mixes" / "new-mix.json").write_text(
        json.dumps(mix))
    reader = registry.metric_reader([tmp_path], "answers_per_s.new")
    assert reader(types.SimpleNamespace(queries=[1, 2, 3],
                                        window_s=2.0)) == 1.5
    assert registry.mix([tmp_path], "new-mix") == mix
    # the benchmark's own parts stay visible from the other root
    assert registry.mix([tmp_path], "game-batch")["name"] == "game-batch"
    with pytest.raises(FileNotFoundError):
        registry.metric_reader([tmp_path], "no_such_metric")


def test_metrics_for_a_cell():
    from chipbench import registry
    bench = registry.load_benchmark(REPO)
    for cell in ("qwen2-0.5b.game-batch", "codeqwen1.5-7b-l16.game-batch"):
        e2e = {m["name"] for m in registry.metrics_for(
            bench, "end_to_end", cell)}
        assert e2e == {"rows_per_s", "setup_s"}
        layer = {m["name"] for m in registry.metrics_for(
            bench, "per_layer", cell)}
        assert layer == {"slot_occupancy.batch", "mfu.batch",
                         "device_idle_share.batch",
                         "step_calls_per_step.batch"}
    bench["per_layer"].append({"name": "x.other", "workloads": ["c"]})
    assert "x.other" not in {m["name"] for m in registry.metrics_for(
        bench, "per_layer", "qwen2-0.5b.game-batch")}
