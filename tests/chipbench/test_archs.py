"""A configuration's architecture is found by its ``model_type``, as mixes
and metrics are found by name: a new architecture is a new file under
``chipbench/archs/`` and a configuration that names it, with no existing
file edited; and a layout may stack some per-layer leaves less deep than
the model."""
import jax.numpy as jnp
import numpy as np
import pytest

from benchcase import REPO, run_tiny, tiny_root


def test_a_new_architecture_is_a_new_file(tmp_path, capsys):
    """A throwaway root holds ``archs/qwen2_copy.py``, found nowhere else,
    and a tiny configuration that names it: the closed-loop cell runs
    through ``run.main`` and its check holds."""
    from chipbench import registry
    bench_before = (REPO / "BENCHMARK.json").read_text()
    root = tiny_root(tmp_path, model_type="qwen2_copy")
    assert not (REPO / "chipbench" / "archs" / "qwen2_copy.py").exists()
    cfg = registry.config(registry.load_benchmark(root), root, "tiny")
    arch = registry.arch([root], cfg)
    assert arch.__file__ == str(root / "chipbench" / "archs"
                                / "qwen2_copy.py")
    rc, res = run_tiny(root, "tiny.game", seconds=2.0, capsys=capsys)
    assert rc == 0 and res["correct"] is True
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]
    assert (REPO / "BENCHMARK.json").read_text() == bench_before


def test_unknown_model_type_names_the_paths_searched(tmp_path):
    from chipbench import registry
    with pytest.raises(FileNotFoundError) as e:
        registry.arch([tmp_path], {"model_type": "no_such_arch"})
    msg = str(e.value)
    assert "archs/no_such_arch.py" in msg
    assert str(tmp_path / "chipbench" / "archs") in msg
    assert str(REPO / "chipbench" / "archs") in msg


def two_stacks():
    """One leading dense layer beside a three-layer stack, and globals."""
    from chipbench.weights import Leaf
    return [Leaf("embed/embedding", (32, 8), False, "embed", 8),
            Leaf("dense/norm/scale", (8,), True, "norm", depth=1),
            Leaf("dense/ffn/w", (8, 16), True, "dense", 8, depth=1),
            Leaf("moe/norm/scale", (8,), True, "norm"),
            Leaf("moe/attn/q/b", (2, 4), True, "bias"),
            Leaf("moe/ffn/w", (8, 4, 16), True, "dense", 8)]


@pytest.mark.parametrize("seed", [7, 2**33 + 5])
def test_a_shorter_stack_gives_the_same_numbers_both_ways(seed):
    """``make_all`` stacks each per-layer leaf to its own depth, and
    ``make_layer`` gives the same numbers one layer at a time."""
    from chipbench import weights as wts
    leaves = two_stacks()
    flat = wts.make_all(leaves, 3, seed, jnp.bfloat16)
    assert flat["dense/ffn/w"].shape == (1, 8, 16)
    assert flat["moe/ffn/w"].shape == (3, 8, 4, 16)
    assert flat["embed/embedding"].shape == (32, 8)
    for stack, depth in (("dense/", 1), ("moe/", 3)):
        mine = [x for x in leaves if x.name.startswith(stack)]
        for i in range(depth):
            got = wts.make_layer(mine, seed, i, jnp.bfloat16)
            assert set(got) == {x.name[len(stack):] for x in mine}
            for short, v in got.items():
                np.testing.assert_array_equal(
                    np.asarray(v, np.float32),
                    np.asarray(flat[stack + short][i], np.float32))
    # past the dense stack, a layer of the whole layout is the MoE stack's;
    # where both stacks hold it, their leaves would collide
    assert set(wts.make_layer(leaves, seed, 2)) == {
        "norm/scale", "attn/q/b", "ffn/w"}
    with pytest.raises(ValueError, match="two stacks"):
        wts.make_layer(leaves, seed, 0)
    np.testing.assert_array_equal(
        np.asarray(wts.make_global(leaves, seed, "embed/embedding",
                                   jnp.bfloat16), np.float32),
        np.asarray(flat["embed/embedding"], np.float32))
