"""The FLOP and byte counts of the `gpt2` family against hand-worked
values, and the peaks."""

import json
import pathlib

import pytest

from benchmark import manifest, peaks

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "configs"
counts = manifest.load_family(manifest.BENCH_DIR, "gpt2")


def cfg(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,params,gflop", [
    # medium: 24 x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 50257
    #   = 353,453,056 matmul weights; 6 x that + 12 x 24 x 1024 x 1024 / 2
    ("gpt2-medium", 354_823_168, 2.272),
    # xl: 48 x (4 x 1600^2 + 2 x 1600 x 6400) + 1600 x 50257
    ("gpt2-xl", 1_557_611_200, 9.80),
])
def test_train_flops_per_token(name, params, gflop):
    c = cfg(name)
    assert counts.total_params(c) == params == c["params"]
    got = counts.train_flops_per_token(c, 1024) / 1e9
    assert abs(got - gflop) < 0.005, got


def test_medium_by_hand():
    c = cfg("gpt2-medium")
    assert counts.layer_matmul_params(c) == 12_582_912
    assert counts.matmul_params(c) == 353_453_056
    assert counts.train_flops_per_token(c, 1024) == \
        6 * 353_453_056 + 150_994_944
    # attention of one sequence, forward and backward
    assert counts.train_attention_flops_per_seq(c, 1024) == \
        6 * 24 * 1024 ** 3
    # the per-token attention term is the same count, spread over tokens
    assert counts.train_attention_flops_per_seq(c, 1024) / 1024 == \
        12 * 24 * 1024 * 1024 * 0.5


def test_forward_counts_add_up():
    c = cfg("gpt2-medium")
    # a prompt is its tokens one by one, the head once
    n = 37
    by_token = sum(counts.forward_flops_token(c, i + 1, head=(i == n - 1))
                   for i in range(n))
    assert abs(counts.prefill_flops(c, n) - by_token) < 1e-3 * by_token
    assert counts.kv_bytes_per_position(c) == 2 * 24 * 1024 * 2  # 98,304
    assert counts.decode_weight_bytes(c) == 353_453_056 * 2


def test_peaks_table():
    v5e = peaks.lookup("TPU v5 lite")
    assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)
    assert "v5e" in v5e.source
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")
