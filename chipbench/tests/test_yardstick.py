"""The yardstick's own arithmetic: FLOP counts, peaks, the trace reduction."""

import os
import sys

import pytest

from chipbench import flops, trace_reduce
from chipbench.tests import helpers

RECORDED = os.path.join(helpers.HERE, "data", "recorded_v5e.xplane.pb")


def test_flops_agree_with_the_programs_own_count():
    sys.path.insert(0, helpers.REPO)
    import bench

    for f in (512, 10240):
        assert flops.train_step_tflops(32, 60, f, 40, 128) == pytest.approx(
            bench.train_step_tflops(32, 60, f, 40, 128), rel=1e-12)
    assert flops.train_step_tflops(32, 60, 512, 40, 128) == pytest.approx(
        0.2272, abs=1e-4)
    assert flops.train_step_tflops(32, 60, 10240, 40, 128) == pytest.approx(
        3.67, abs=1e-2)


def test_unknown_device_kind_has_no_peak():
    assert flops.chip_peaks("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(KeyError):
        flops.chip_peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.chip_peaks("cpu")


def test_roofline_share_of_known_work():
    peaks = flops.chip_peaks("TPU v5 lite")
    share, bound = flops.roofline_share_pct(
        {"flops": 197e12, "bytes": 1.0}, 2.0, peaks)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = flops.roofline_share_pct(
        {"flops": 1.0, "bytes": 819e9}, 4.0, peaks)
    assert bound == "memory" and share == pytest.approx(25.0)


def _planes(events, spans=()):
    return [("/device:TPU:0", [("XLA Ops", list(events)),
                               ("Async XLA Ops", [("%copy-start", 0, 10**9)])]),
            ("/host:CPU", [("python3", list(spans))])]


def test_union_self_time_kernels_and_gap_attribution():
    kernel = '%k.1 = bf16[8] custom-call(x), custom_call_target="tpu_custom_call"'
    events = [
        ("%while.1 = () while(...)", 0, 100),         # holds the next three
        ("%fusion.1 = f32[8] fusion(...)", 10, 30),
        (kernel, 40, 20),
        ("%fusion.2 = f32[8] fusion(...)", 65, 10),
        ("%fusion.9 = f32[8] fusion(...)", 300, 50),   # after a gap of 200
        (kernel, 400, 100),                            # after a gap of 50
    ]
    spans = [("bench.outer", 90, 1000), ("bench.inner", 340, 60),
             ("other", 0, 1000)]
    r = trace_reduce.reduce_planes(_planes(events, spans))
    assert r["chips"] == 1
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx(250e-9)        # 100 + 50 + 100
    assert r["kernel_s"] == pytest.approx(120e-9)
    ops = dict(r["device_ops"])
    assert ops["%while.1"] == pytest.approx(40e-9)     # 100 less 30+20+10
    assert ops["%k.1"] == pytest.approx(120e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.outer"] == pytest.approx(200e-9)
    assert gaps["bench.inner"] == pytest.approx(50e-9)  # the innermost span


def test_a_trace_without_device_operations_is_refused():
    with pytest.raises(RuntimeError):
        trace_reduce.reduce_planes(
            [("/host:CPU", [("python3", [("bench.x", 0, 10)])])])


def test_recorded_trace_from_the_chip():
    """A trace recorded on a TPU v5e in a probe of PR 23 (a flagship-width
    predictor answering four series under a host span named
    ``bench.predict_series``), trimmed to the lines the reduction reads."""
    r = trace_reduce.reduce_file(RECORDED)
    assert r["chips"] == 1
    assert 0 < r["kernel_s"] < r["busy_s"] < r["window_s"]
    assert r["busy_s"] == pytest.approx(0.007332602, rel=1e-6)
    assert r["window_s"] == pytest.approx(0.034704151, rel=1e-6)
    assert r["kernel_s"] == pytest.approx(0.001862831, rel=1e-6)
    assert any(name.startswith("%QuantileGRU") for name, _ in r["device_ops"])
    assert r["idle_gaps"][0][0] == "bench.predict_series"
