"""The reader of the program's own counters (readers/program_counters.py):
nothing to read on a program that lacks the names, the three values on one
that has them, and all three in the result line of a traced rehearsal."""

import pytest

from chipbench.readers import program_counters
from chipbench.tests import helpers
from chipbench.tests.test_rehearsal import USE_RECORDED_TRACE

READERS = (program_counters.epoch_host_ms,
           program_counters.readbacks_per_epoch,
           program_counters.dispatches_per_epoch)


@pytest.fixture
def registry(monkeypatch):
    from deeprest_tpu.obs import metrics

    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def test_an_empty_registry_reads_as_nothing(registry):
    assert [read({}) for read in READERS] == [None, None, None]
    # the parent commit: the old counters are there, the new ones are not
    registry.counter("deeprest_train_superstep_dispatches_total").inc(9)
    registry.counter("deeprest_train_readbacks_total",
                     labelnames=("sink",)).inc(3, sink="epoch_losses")
    assert [read({}) for read in READERS] == [None, None, None]
    # the names there and no epoch finished yet
    registry.counter("deeprest_train_epochs_total")
    registry.gauge("deeprest_train_last_epoch_phase_seconds",
                   labelnames=("phase",))
    assert [read({}) for read in READERS] == [None, None, None]


def test_a_filled_registry_reads_as_the_three_values(registry):
    registry.counter("deeprest_train_epochs_total").inc(3)
    registry.counter("deeprest_train_superstep_dispatches_total").inc(9)
    readbacks = registry.counter("deeprest_train_readbacks_total",
                                 labelnames=("sink",))
    readbacks.inc(7, sink="log_boundary")
    readbacks.inc(3, sink="epoch_losses")
    last = registry.gauge("deeprest_train_last_epoch_phase_seconds",
                          labelnames=("phase",))
    for phase, seconds in (("plan_build", 0.001), ("plan_h2d", 0.002),
                           ("dispatch", 0.5), ("log_readback", 2.0),
                           ("device_wait", 1.5), ("loss_readback", 0.004)):
        last.set(seconds, phase=phase)
    host_ms, reads, dispatches = (read({}) for read in READERS)
    assert host_ms == pytest.approx(7.0)        # the waits are left out
    assert reads == pytest.approx(10 / 3)
    assert dispatches == pytest.approx(3.0)


def test_traced_rehearsal_reports_the_three_metrics(tmp_path):
    root = helpers.make_root(str(tmp_path))
    result, out = helpers.run_cell(root, "tiny-train", trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    metrics = result["metrics"]
    # 40 steps an epoch in supersteps of 8, no log lines: 5 dispatches and
    # the one loss readback an epoch, over warm-up, steady and traced epoch
    assert metrics["dispatches_per_epoch.train"] == {"value": 5.0,
                                                     "unit": "1/epoch"}
    assert metrics["readbacks_per_epoch.train"]["value"] == 1.0
    assert 0 < metrics["epoch_host_ms.train"]["value"] < 1e3
    assert metrics["epoch_host_ms.train"]["unit"] == "ms/epoch"
