"""Operation and byte counts against counts by hand."""
import pytest

from chipbench import work

QWEN = {"hidden_size": 2048, "num_attention_heads": 16,
        "num_key_value_heads": 2, "intermediate_size": 11008,
        "num_hidden_layers": 36, "vocab_size": 151936}


def test_matmul_flops_per_token_qwen2_5_3b():
    # per layer: q 2048x2048, k and v 2048x256, o 2048x2048, MLP 3x2048x11008
    layer = 2048 * 2048 * 2 + 2048 * 256 * 2 + 3 * 2048 * 11008
    head = 2048 * 151936
    assert work.lm_matmul_flops_per_token(QWEN) == 2 * (36 * layer + head)
    # about 6.2 GFLOP per token
    assert work.lm_matmul_flops_per_token(QWEN) == pytest.approx(6.17e9,
                                                                 rel=1e-2)


def test_attention_and_step_flops():
    # scores and values: 2 x (16 heads x 128) x ctx multiply-adds per layer
    assert work.lm_attn_flops(QWEN, 100) == 36 * 4 * 16 * 128 * 100
    step = work.decode_step_flops(QWEN, [10, 20])
    assert step == 2 * work.lm_matmul_flops_per_token(QWEN) + \
        work.lm_attn_flops(QWEN, 30)


def test_flash_decode_work_counts_live_kv_only():
    f, b = work.flash_decode_work(QWEN, [100, 50], kv_bytes=4)
    assert f == 4 * 16 * 128 * 150
    # K and V of 2 heads x 128 at 4 B per live position, plus q and out rows
    assert b == 2 * 2 * 128 * 150 * 4 + 2 * (2 * 16 * 128 * 4)
    assert work.flash_decode_work(QWEN, [], kv_bytes=4) == (0, 0)


def test_least_time_names_its_bound():
    peak = work.peaks("TPU v5 lite")
    t, bound = work.least_time(197e12, 0, peak)
    assert (t, bound) == (pytest.approx(1.0), "compute")
    t, bound = work.least_time(0, 819e9, peak)
    assert (t, bound) == (pytest.approx(1.0), "memory")


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("cpu")
