"""Operation and byte counts from shapes, against hand counts."""
import pytest

import counts
from reference import model_module


def test_peaks_known_and_unknown():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert counts.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        counts.peaks("cpu")


def test_roofline_picks_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_s(1000.0, 10.0, peak) == (10.0, "compute")
    assert counts.roofline_s(10.0, 1000.0, peak) == (100.0, "memory")


def test_hbm_bytes_skip_on_chip_buffers():
    shapes = [("f32", (2, 3), 0), ("bf16", (4,), 0), ("f32", (100,), 1)]
    assert counts.hbm_bytes(shapes) == 2 * 3 * 4 + 4 * 2


def test_ssd_scan_hand_count():
    # B=1, H=1, S=2, one chunk of l=2, P=1, N=1:
    # CBᵀ 2·4·1=8, mask 4, (·)x 2·4·1=8, read-out 2·2=4, scale 2,
    # update 2·2=4 + B·decay 2, state decay 2, sum 2  -> 36
    assert counts.ssd_scan(1, 2, 1, 1, 1, 2) == 36
    # two chunks and two heads double it twice
    assert counts.ssd_scan(1, 4, 2, 1, 1, 2) == 4 * 36


def test_sbc_hand_counts():
    assert counts.sbc_stats(8) == 8 * 8 * 128
    assert counts.sbc_apply(8) == 3 * 8 * 128


def test_mlp_train_flops_hand_count():
    cfg = {"input_dim": 4, "hidden": 3, "depth": 3, "classes": 2}
    # matmuls 4x3, 3x3, 3x2 = 12, 9, 6 MACs; forward 27, weight grads 27,
    # input grads of layers 2 and 3: 15  -> 2·69
    assert model_module("feel_mlp").train_flops_per_example(cfg) == 138


def test_mamba2_train_flops_hand_count():
    cfg = {"hidden": 4, "expand": 2, "d_state": 2, "head_dim": 4,
           "n_groups": 1, "d_conv": 2, "vocab": 3, "seq_len": 2,
           "depth": 1}
    # d_in 8, H 2, conv channels 12, projection 2·8 + 4 + 2 = 22:
    # per token 4·22 + 8·4 + 2·12 + 2·2·4·2 = 88 + 32 + 24 + 32 = 176,
    # head 4·3 = 12 -> 6 · 2 tokens · 188
    assert model_module("mamba2").train_flops_per_example(cfg) == 2256
