"""Ahead-of-time compiles of the main path for one TPU v5e chip.

The TPU compiler is installed with jax; it compiles for a chip that is
described, not attached. These tests catch what interpret mode cannot (a
kernel the Mosaic compiler refuses, a program that does not fit the chip's
16 GiB of HBM) without running anything. The topology is described inside
the fixtures below, never at import: only one process at a time may load
the TPU library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.engine.engine import jitted_steps
from repro.kernels import ops
from repro.models import registry

V5E_HBM_BYTES = 16 * 1024 ** 3
EMBED_DIM = 256         # core.semhash.DIM, the cascade's embedding width


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(one_chip, tree):
    """Shapes of ``tree`` (arrays or ShapeDtypeStructs) placed on the
    described chip."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        tree)


def _hbm_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)


@pytest.mark.parametrize("m", [1, 129, 512])
def test_rowwise_cosine_compiles_natively(one_chip, m):
    a = _on(one_chip, jax.ShapeDtypeStruct((m, EMBED_DIM), jnp.float32))
    compiled = ops.rowwise_cosine_jit.lower(a, a, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cosine_matrix_compiles_natively(one_chip):
    a = _on(one_chip, jax.ShapeDtypeStruct((200, EMBED_DIM), jnp.float32))
    b = _on(one_chip, jax.ShapeDtypeStruct((300, EMBED_DIM), jnp.float32))
    compiled = ops.cosine_matrix_jit.lower(a, b, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen2_full(one_chip):
    """Full-width qwen2-0.5b (the m1 tier's model) as shapes only: the
    engine's own jitted steps and the shapes of its params and cache."""
    cfg = get_config("qwen2-0.5b")
    bundle = registry.build(cfg)
    dtype, slots, max_len = jnp.float32, 8, 512
    params = _on(one_chip, jax.eval_shape(bundle.init,
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(
        lambda: bundle.init_cache(slots, max_len, dtype=dtype,
                                  per_slot_pos=True)))
    decode, prefill = jitted_steps(bundle, max_len=max_len, dtype=dtype)
    return cfg, params, cache, decode, prefill, slots


def test_qwen2_full_decode_step_fits_one_chip(one_chip, qwen2_full):
    cfg, params, cache, decode, _, slots = qwen2_full
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (24, 896, 151936)
    token = _on(one_chip, jax.ShapeDtypeStruct((slots, 1), jnp.int32))
    compiled = decode.lower(params, cache, token).compile()
    assert 0 < _hbm_bytes(compiled) < V5E_HBM_BYTES


def test_qwen2_full_prefill_fits_one_chip(one_chip, qwen2_full):
    _, params, _, _, prefill, _ = qwen2_full
    batch = _on(one_chip, {
        "tokens": jax.ShapeDtypeStruct((1, 128), jnp.int32),
        "last_index": jax.ShapeDtypeStruct((1,), jnp.int32)})
    compiled = prefill.lower(params, batch).compile()
    assert 0 < _hbm_bytes(compiled) < V5E_HBM_BYTES
