"""Pallas kernels vs pure-jnp oracles: shape / dtype / flag sweeps.

Kernels run in interpret mode on CPU — the kernel bodies execute exactly
as they would on TPU (same BlockSpec tiling, same scratch carries)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref

RNG = np.random.default_rng(42)


def randn(*shape, dtype=jnp.float32):
    return jnp.asarray(RNG.normal(size=shape) * 0.5, dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 64, 4, 4, 32),      # MHA
    (2, 128, 8, 2, 32),     # GQA 4x
    (1, 96, 8, 1, 64),      # MQA, non-pow2 seq
    (2, 40, 4, 2, 16),      # needs padding (40 % 32 != 0)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, s, hq, hkv, d, dtype):
    q = randn(b, s, hq, d, dtype=dtype)
    k = randn(b, s, hkv, d, dtype=dtype)
    v = randn(b, s, hkv, d, dtype=dtype)
    got = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    want = ref.attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window", [8, 24, 64])
def test_flash_attention_sliding_window(window):
    q = randn(1, 96, 4, 32)
    k = randn(1, 96, 2, 32)
    v = randn(1, 96, 2, 32)
    got = ops.flash_attention(q, k, v, causal=True, window=window,
                              bq=32, bk=32)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_noncausal():
    q = randn(1, 64, 4, 32)
    k = randn(1, 64, 4, 32)
    v = randn(1, 64, 4, 32)
    got = ops.flash_attention(q, k, v, causal=False, bq=32, bk=32)
    want = ref.attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_flash_attention_matches_model_chunked_attention():
    """The XLA chunked path (models/attention.py) and the Pallas kernel
    must be interchangeable."""
    from repro.models.attention import chunked_attention
    q = randn(2, 64, 8, 32)
    k = randn(2, 64, 2, 32)
    v = randn(2, 64, 2, 32)
    a = chunked_attention(q, k, v, causal=True)
    b = ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,d,s", [
    (1, 4, 4, 32, 128),
    (3, 8, 2, 64, 256),
    (2, 4, 1, 32, 100),     # padding (100 % 64)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, hq, hkv, d, s, dtype):
    q = randn(b, 1, hq, d, dtype=dtype)
    kc = randn(b, s, hkv, d, dtype=dtype)
    vc = randn(b, s, hkv, d, dtype=dtype)
    lens = jnp.asarray(RNG.integers(1, s + 1, size=b), jnp.int32)
    got = ops.decode_attention(q, kc, vc, lens, bk=64)
    want = ref.decode_attention_ref(q, kc, vc, lens)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_decode_attention_scalar_len():
    q = randn(2, 1, 4, 32)
    kc = randn(2, 128, 2, 32)
    vc = randn(2, 128, 2, 32)
    got = ops.decode_attention(q, kc, vc, 77)
    want = ref.decode_attention_ref(q, kc, vc, jnp.full((2,), 77))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 16, 1, 8, 16),
    (2, 128, 4, 32, 2, 16, 32),
    (1, 96, 8, 16, 4, 8, 48),
])
def test_ssd_scan_sweep(b, s, h, p, g, n, chunk):
    dx = randn(b, s, h, p)
    dA = -jnp.abs(randn(b, s, h)) * 0.2
    B = randn(b, s, g, n)
    C = randn(b, s, g, n)
    y, st = ops.ssd_scan(dx, dA, B, C, chunk=chunk)
    y_ref, st_ref = ref.ssd_ref(dx, dA, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=3e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=3e-4)


def test_ssd_scan_initial_state_continuation():
    """Scanning [first half] then [second half from the carried state] must
    equal one full scan — the prefill-continuation invariant."""
    b, s, h, p, g, n = 1, 64, 2, 8, 1, 8
    dx = randn(b, s, h, p)
    dA = -jnp.abs(randn(b, s, h)) * 0.2
    B = randn(b, s, g, n)
    C = randn(b, s, g, n)
    y_full, st_full = ops.ssd_scan(dx, dA, B, C, chunk=16)
    y1, st1 = ops.ssd_scan(dx[:, :32], dA[:, :32], B[:, :32], C[:, :32],
                           chunk=16)
    y2, st2 = ops.ssd_scan(dx[:, 32:], dA[:, 32:], B[:, 32:], C[:, 32:],
                           initial_state=st1, chunk=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], 1)),
                               np.asarray(y_full), atol=3e-4)
    np.testing.assert_allclose(np.asarray(st2), np.asarray(st_full),
                               atol=3e-4)


def test_ssd_kernel_matches_model_chunked_path():
    """kernels.ssd_scan and models.ssm.ssd_chunked implement one schedule."""
    from repro.models.ssm import ssd_chunked
    b, s, h, p, g, n = 2, 64, 4, 16, 2, 8
    dx = randn(b, s, h, p)
    dA = -jnp.abs(randn(b, s, h)) * 0.2
    B = randn(b, s, g, n)
    C = randn(b, s, g, n)
    y1, st1 = ops.ssd_scan(dx, dA, B, C, chunk=16)
    y2, st2 = ssd_chunked(dx, dA, B, C, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=3e-4)
    np.testing.assert_allclose(np.asarray(st1), np.asarray(st2), atol=3e-4)


# ---------------------------------------------------------------------------
# similarity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n,d", [(128, 128, 256), (130, 70, 256),
                                   (16, 16, 64)])
def test_cosine_matrix_sweep(m, n, d):
    a = RNG.normal(size=(m, d)).astype(np.float32)
    b = RNG.normal(size=(n, d)).astype(np.float32)
    a /= np.linalg.norm(a, axis=1, keepdims=True)
    b /= np.linalg.norm(b, axis=1, keepdims=True)
    got = ops.cosine_matrix(a, b)
    np.testing.assert_allclose(got, np.asarray(ref.cosine_matrix_ref(a, b)),
                               atol=1e-5)


def test_rowwise_cosine():
    a = RNG.normal(size=(133, 256)).astype(np.float32)
    b = RNG.normal(size=(133, 256)).astype(np.float32)
    got = ops.rowwise_cosine(a, b)
    np.testing.assert_allclose(got,
                               np.asarray(ref.rowwise_cosine_ref(a, b)),
                               atol=1e-5)


# the similarity module itself (not the padding ops wrappers) must accept
# arbitrary M/N — morsels and cascade batches are rarely block multiples
@pytest.mark.parametrize("m", [1, 127, 129])
def test_cosine_matrix_arbitrary_rows(m):
    from repro.kernels import similarity as sim
    a = RNG.normal(size=(m, 256)).astype(np.float32)
    b = RNG.normal(size=(67, 256)).astype(np.float32)
    a /= np.linalg.norm(a, axis=1, keepdims=True)
    b /= np.linalg.norm(b, axis=1, keepdims=True)
    got = sim.cosine_matrix(a, b, interpret=True)
    assert got.shape == (m, 67)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.cosine_matrix_ref(a, b)),
                               atol=1e-5)


@pytest.mark.parametrize("m", [1, 127, 129])
def test_rowwise_cosine_arbitrary_rows(m):
    from repro.kernels import similarity as sim
    a = RNG.normal(size=(m, 256)).astype(np.float32)
    b = RNG.normal(size=(m, 256)).astype(np.float32)
    got = sim.rowwise_cosine(a, b, interpret=True)
    assert got.shape == (m,)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.rowwise_cosine_ref(a, b)),
                               atol=1e-5)


def test_semhash_uses_kernel_path():
    from repro.core import semhash
    xs = ["the quick brown fox", "a crime story", "N250m"]
    ys = ["the quick brown fox", "a thriller tale", "250 million naira"]
    eq = semhash.semantic_equal_batch(xs, ys, use_kernel=True)
    eq2 = semhash.semantic_equal_batch(xs, ys, use_kernel=False)
    assert list(eq) == list(eq2)
    assert eq[0]          # identical strings


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Interpret mode on the CPU, native on the TPU; any other backend
    raises instead of quietly running the kernels in the interpreter."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="cannot run on backend"):
            ops.interpret_mode()
    else:
        assert ops.interpret_mode() is interpret
