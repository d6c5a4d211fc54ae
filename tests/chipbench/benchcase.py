"""Helpers of the chip benchmark's CPU tests: the checkout's root on the
import path, and a throwaway root that holds CPU-sized cells."""
import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def tiny_root(tmp: pathlib.Path, movie_rows: int = 64,
              game_rows: int = 200, model_type: str = "") -> pathlib.Path:
    """A root whose BENCHMARK.json has the real metrics, each reported in
    both of two cells of the CPU-sized ``tiny`` configuration under
    cut-down copies of the two mixes (open and closed loop). With
    ``model_type``, the root also holds ``chipbench/archs/<model_type>.py``,
    a copy of the benchmark's qwen2 architecture, and ``tiny`` names it."""
    (tmp / "chipbench" / "mixes").mkdir(parents=True)
    tiny = json.loads((DATA / "tiny.json").read_text())
    if model_type:
        (tmp / "chipbench" / "archs").mkdir()
        shutil.copy(REPO / "chipbench" / "archs" / "qwen2.py",
                    tmp / "chipbench" / "archs" / f"{model_type}.py")
        tiny["model_type"] = model_type
    (tmp / "tiny.json").write_text(json.dumps(tiny))
    movie = json.loads((REPO / "chipbench/mixes/movie-interactive.json")
                       .read_text())
    movie.update(name="tiny-movie", rate_qps=3.0, max_rows=movie_rows,
                 wait_after_close_s=120.0)
    game = json.loads((REPO / "chipbench/mixes/game-batch.json").read_text())
    game.update(name="tiny-game", max_rows=game_rows, warmup_s=1.0)
    for m in (movie, game):
        (tmp / "chipbench" / "mixes" / f"{m['name']}.json").write_text(
            json.dumps(m))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests", "reduced": [],
                         "file": "tiny.json", "why": "CPU-sized"}]
    bench["workloads"] = [
        {"name": "tiny.movie", "config": "tiny", "traffic": "tiny-movie",
         "chips": 1, "why": "CPU-sized"},
        {"name": "tiny.game", "config": "tiny", "traffic": "tiny-game",
         "chips": 1, "why": "CPU-sized"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.movie", "tiny.game"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


def run_tiny(root, workload, seconds=2.0, seed=3_000_000_019, trace=0,
             capsys=None, control=0):
    """One CPU run of a tiny cell: (exit code, result or None)."""
    from chipbench import run
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace),
                   "--control", str(control)],
                  root=root, require_chip=False, compile_cache=False)
    out = capsys.readouterr().out.strip().splitlines() if capsys else []
    return rc, (json.loads(out[-1]) if out else None)
