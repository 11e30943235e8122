"""The MFU FLOP count against the hand count, and the peaks table."""
import json
import os

import pytest

from chipbench import flops, peaks

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "configs")


def test_qwen3_l5_step_flops_match_the_hand_count():
    with open(os.path.join(CONFIGS, "qwen3-1.7b-L5.json")) as f:
        c = json.load(f)
    got = flops.dense_lm_train_flops(c, 4, 512)
    # hand count (PERF.md): tied head 6 x 311.2e6 x 2048 = 3.82e12, one
    # layer 6 x 50.3e6 x 2048 = 0.62e12, five layers and causal attention
    # come to about 7.0e12 per 2048-token step
    assert got["head"] == pytest.approx(3.82e12, rel=2e-3)
    assert got["layer"] == pytest.approx(0.62e12, rel=3e-3)
    assert got["attention"] == pytest.approx(
        5 * 3 * 4 * 4 * 512 * 512 / 2 * 16 * 128)
    assert got["total"] == pytest.approx(7.1e12, rel=0.02)


def test_peaks_table_knows_the_v5e_and_refuses_an_unknown_kind():
    assert peaks.peak("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak("TPU v9 imaginary")
