"""Operation and byte counts against hand-worked numbers, and the peaks
table."""

import json
import os

import pytest

from chipbench import roofline

HERE = os.path.dirname(os.path.abspath(__file__))


def config(name):
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_smollm_weights_per_step():
    c = config("smollm-360m")
    # 32 x (960 x 2560 q,k,v + 960 x 960 o + 3 x 960 x 2560 mlp) = 314,572,800
    assert roofline.matmul_params(c) == 314_572_800
    _, nbytes = roofline.decode_step(c, [0])
    # + head 49152 x 960 + 65 norms of 960, in bf16, + one embedding row
    assert nbytes == 2 * (314_572_800 + 47_185_920 + 62_400) + 1920
    assert nbytes / 1e9 == pytest.approx(0.72, abs=0.005)
    assert roofline.param_count(c) == 361_821_120


def test_granite_stage_bytes_and_params():
    c = config("granite-8b-s9")
    _, nbytes = roofline.decode_step(c, [0])
    assert nbytes / 1e9 == pytest.approx(4.33, abs=0.005)
    assert 2 * roofline.param_count(c) / 1e9 == pytest.approx(4.73,
                                                              abs=0.005)
    # K and V of one position: 9 layers x 2 x 8 heads x 128 x 2 bytes
    assert roofline.kv_bytes_per_position(c) == 36_864


def test_decode_flops_and_cache_bytes_grow_with_fill():
    c = config("smollm-360m")
    f0, b0 = roofline.decode_step(c, [1])
    f1, b1 = roofline.decode_step(c, [101])
    assert f1 - f0 == 100 * 4 * 32 * 15 * 64
    assert b1 - b0 == 100 * roofline.kv_bytes_per_position(c)
    assert f0 == 2 * (314_572_800 + 47_185_920) + 4 * 32 * 15 * 64
    two, _ = roofline.decode_step(c, [1, 101])
    assert two == f0 + f1


def test_prefill_flops():
    c = config("smollm-360m")
    n = 16
    assert roofline.prefill(c, n) == (2 * 314_572_800 * n
                                      + 4 * 32 * 15 * 64 * n * (n + 1) // 2
                                      + 2 * 47_185_920)


def test_least_time_is_the_binding_bound():
    peak = roofline.peaks("TPU v5 lite")
    assert peak["flops"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    assert roofline.least_time(197e12, 1.0, peak) == pytest.approx(1.0)
    assert roofline.least_time(1.0, 819e9, peak) == pytest.approx(1.0)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v9 imaginary")
