"""The operation and byte counters, against sums done by hand."""
import json

import pytest

from benchcase import REPO


def qwen2(name):
    """The configuration's architecture module (``archs/qwen2.py``) and
    its dims."""
    from chipbench import registry
    cfg = json.loads(
        (REPO / "chipbench" / "configs" / f"{name}.json").read_text())
    arch = registry.arch([REPO], cfg)
    assert arch.__file__.endswith("archs/qwen2.py")
    return arch, arch.dims(cfg)


def test_qwen2_weights_per_token():
    flops, d = qwen2("qwen2-0.5b")
    # per layer: q/k/v 896*(14+2+2)*64, o 14*64*896, SwiGLU 3*896*4864
    per_layer = 896 * 18 * 64 + 896 * 896 + 3 * 896 * 4864
    assert per_layer == 14_909_440
    assert flops.matmul_params(d) == 24 * per_layer == 357_826_560
    assert flops.head_flops(d) == 2 * 896 * 151_936


def test_codeqwen_stage_weights_and_published_size():
    flops, d = qwen2("codeqwen1.5-7b-l16")
    per_layer = 4096 * (32 + 8) * 128 + 4096 * 4096 + 3 * 4096 * 13440
    assert per_layer == 202_899_456
    assert flops.matmul_params(d) == 16 * per_layer
    # the published 7.25 B parameters: 32 such layers (with their biases
    # and norms) plus the untied embedding and head
    biases_norms = (32 + 8) * 128 + 2 * 4096
    total = 32 * (per_layer + biases_norms) + 2 * 92_416 * 4096 + 4096
    assert total == pytest.approx(7.25e9, rel=2e-3)


def test_prefill_and_decode_by_hand():
    flops, d = qwen2("qwen2-0.5b")
    m, h = flops.matmul_params(d), flops.head_flops(d)
    attn_per_key = 4 * 24 * 14 * 64
    # 3 prompt tokens see 1 + 2 + 3 keys; one head at the last token
    assert flops.prefill_flops(d, 3) == 2 * m * 3 + attn_per_key * 6 + h
    # two live slots at contexts 10 and 20
    assert flops.decode_flops(d, 2, 30) == 2 * (2 * m + h) + \
        attn_per_key * 30


def test_rowwise_cosine_cost():
    from chipbench import flops
    ops, nbytes = flops.rowwise_cosine_cost(32)
    assert ops == 2 * 32 * 256
    assert nbytes == 32 * 256 * 4 * 2 + 32 * 4


def test_peaks_are_keyed_by_device_kind():
    from chipbench import peaks
    p = peaks.peaks("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
