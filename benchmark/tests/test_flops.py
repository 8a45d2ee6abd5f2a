"""FLOP and byte functions against hand counts for both configurations,
reached the way a reader reaches them: through the configuration's family."""

import os

import pytest

from harness import cells, flops

QWEN = cells.load_json(os.path.join(cells.BENCH_DIR, "configs",
                                    "qwen2.5-1.5b.json"))
MISTRAL = cells.load_json(os.path.join(cells.BENCH_DIR, "configs",
                                       "mistral-7b-v0.3-l16.json"))
Q, M = cells.family(QWEN).counts, cells.family(MISTRAL).counts


def test_parameter_counts():
    # Qwen2.5-1.5B: 28 x (attn 1536x1536x2 + 1536x256x2 + biases 2048
    # + mlp 3x1536x8960 + norms 3072) + embedding 151936x1536 + final norm
    layer = 2 * 1536 * 1536 + 2 * 1536 * 256 + (1536 + 256 + 256) \
        + 3 * 1536 * 8960 + 2 * 1536
    assert Q.n_params(QWEN) == 28 * layer + 151936 * 1536 + 1536
    assert Q.n_params(QWEN) == pytest.approx(1.544e9, rel=1e-3)
    layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert M.n_params(MISTRAL) == 16 * layer + 2 * 32768 * 4096 + 4096
    assert M.n_params(MISTRAL) == pytest.approx(3.76e9, rel=2e-3)
    assert Q.weight_bytes(QWEN) / 2 ** 30 == pytest.approx(2.9, abs=0.05)
    assert M.weight_bytes(MISTRAL) / 2 ** 30 == pytest.approx(7.0,
                                                                  abs=0.05)


def test_token_flops_by_hand():
    mm = 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert Q.layer_matmul_params(QWEN) == mm
    assert Q.token_flops(QWEN, 100) == 28 * (2 * mm + 4 * 100 * 1536)
    # positions 10, 11, 12 attend 11, 12, 13 positions
    assert Q.span_flops(QWEN, 10, 3) == pytest.approx(sum(
        Q.token_flops(QWEN, c) for c in (11, 12, 13)))
    assert Q.head_flops(QWEN) == 2 * 1536 * 151936
    assert M.head_flops(MISTRAL) == 2 * 4096 * 32768


def test_kv_bytes_by_hand():
    assert Q.kv_bytes_per_token(QWEN) == 2 * 28 * 2 * 128 * 2 == 28672
    assert M.kv_bytes_per_token(MISTRAL) == 2 * 16 * 8 * 128 * 2 == 65536
    # 33 positions touch 3 blocks of 16
    assert Q.paged_attn_bytes(QWEN, 33, 16) == 3 * 16 * 28672
    # a row at 33 positions holds 33 tokens of cache, every layer
    assert Q.kv_bytes(QWEN, 33) == 33 * 28672
    assert M.kv_bytes(MISTRAL, 2048, kv_itemsize=1) == 2048 * 32768


def test_an_unknown_device_has_no_peak():
    assert flops.peak("TPU v5 lite")["flops_per_s_bf16"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("cpu")
