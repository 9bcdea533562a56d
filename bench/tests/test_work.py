"""Operation and byte counts against values worked by hand."""

import json

import pytest

import work
from conftest import BENCH


def dims(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return work.dims(json.load(f))


def test_layer_params_match_the_published_sizes():
    # qwen2-0.5b: 896*14*64 + 2*896*2*64 + 14*64*896 + 3*896*4864
    assert work.layer_matmul_params(dims("qwen2-0.5b")) == 14_909_440
    # qwen1.5-110b: 8192*64*128*2 + 2*8192*8*128 + 3*8192*49152
    assert work.layer_matmul_params(dims("qwen1.5-110b-4l")) == 1_358_954_496


def test_decode_counts_live_rows_only():
    n = dims("qwen2-0.5b")
    got = work.decode(n, live=2, context_rows=30)
    # 24 layers x (2*2*14_909_440 + 4*14*64*30) + 2*2*151936*896
    assert got["flops"] == 24 * (4 * 14_909_440 + 4 * 896 * 30) \
        + 4 * 151936 * 896
    # bytes: layer weights, the head, two embedding rows, k and v rows
    assert got["bytes"] == 2 * (24 * 14_909_440 + 151936 * 896 + 2 * 896
                                + 24 * 2 * 2 * 64 * 30)


def test_prefill_counts_real_tokens_and_one_logits_row():
    n = dims("qwen1.5-110b-4l")
    p = 1_358_954_496
    mid = work.prefill(n, tokens=512, offset=1024, final=False)
    keys = 512 * 1024 + 512 * 513 // 2
    assert mid["flops"] == 4 * (2 * 512 * p + 4 * 64 * 128 * keys)
    last = work.prefill(n, tokens=100, offset=1536, final=True)
    keys = 100 * 1536 + 100 * 101 // 2
    assert last["flops"] == 4 * (2 * 100 * p + 4 * 64 * 128 * keys) \
        + 2 * 19008 * 8192
    assert last["bytes"] == 2 * (4 * p + 19008 * 8192 + 100 * 8192
                                 + 4 * 2 * 8 * 128 * 1636)


def test_least_time_is_the_larger_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    n = dims("qwen1.5-110b-4l")
    # a 512-token chunk is bound by compute, one decode token by bytes
    chunk = work.prefill(n, 512, 0, False)
    assert work.least_seconds(chunk, peak) == pytest.approx(
        chunk["flops"] / 197e12)
    step = work.decode(n, 1, 100)
    assert work.least_seconds(step, peak) == pytest.approx(
        step["bytes"] / 819e9)
    # about 5.6 TFLOP per 512-token chunk: 28 ms at peak
    assert 0.027 < chunk["flops"] / 197e12 < 0.030
