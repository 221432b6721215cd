"""CPU rehearsal of the ``train_warm`` runner at a tiny size (run by hand
with the others: ``python -m pytest chipbench/tests -q``; not part of
tier-1).

As test_rehearsal.py: each run is a process of its own through
``run.run_cell(..., require_chip=False)``, and a result made here names the
platform ``cpu``.  The cell is added to helpers.make_root's throw-away root
as files: a configuration wide enough for the compact form on the CPU
(F = 512, 16 hot call paths in a table of 128), a mix of the
``train_warm`` runner over ``corpus_pair`` (12 of the 16 paths carried),
limits and the entries of BENCHMARK.json.
"""

import json
import os

import pytest

from chipbench.tests import helpers

# the program with the off-table pass left out, as
# control_on_chip_warm.py leaves it out on the chip
SKIPPED_PASS = """
from chipbench.tests import control_on_chip_warm
control_on_chip_warm.without_the_off_table_pass()
"""

USE_RECORDED_TRACE = f"""
import chipbench.trace_reduce as tr
tr.reduce_dir = lambda _dir: tr.reduce_file(
    {os.path.join(helpers.HERE, "data", "recorded_v5e.xplane.pb")!r})
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(str(tmp_path_factory.mktemp("chipbench-warm")))
    cb = os.path.join(root, "chipbench")
    helpers._write(os.path.join(cb, "configs", "tiny-warm.json"), {
        "name": "tiny-warm", "source": "test", "runners": ["train_warm"],
        "model": {**helpers.TINY_MODEL, "feature_dim": 512},
        "train": {"batch_size": 4, "window_size": 6, "sparse_feed": True,
                  "sparse_nnz_cap": 8, "steps_per_superstep": 8,
                  "log_every_steps": 0},
        "reduced": [], "assumed": {}})
    helpers._write(os.path.join(cb, "traffic", "tiny-drift.json"), {
        "name": "tiny-drift", "runner": "train_warm",
        "generator": "corpus_pair",
        "params": {"buckets": 400, "hot_paths": 16, "nnz_lo": 2, "nnz_hi": 6,
                   "day": 100, "resources": helpers.RESOURCES,
                   "carried_paths": 12}})
    helpers._write(os.path.join(cb, "limits", "tiny-retrain-drift.json"), {
        "limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "delta_norm_gap": 1e-2}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-warm", "source": "test",
         "file": "chipbench/configs/tiny-warm.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "tiny-retrain-drift", "config": "tiny-warm",
         "traffic": "tiny-drift", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tenk-retrain-drift" in m.get("workloads", ()):
            m["workloads"].append("tiny-retrain-drift")
    helpers._write(path, bench)
    return root


def test_warm_rehearsal(root):
    result, out = helpers.run_cell(root, "tiny-retrain-drift",
                                   seed=3_000_000_033)
    assert result["correct"], out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    # the restage: the same width, the four paths that moved left and
    # entered the table, nothing compiled for it or for the dispatch after
    assert "'restage': True, 'width': 128, 'left': 4, 'entered': 4" in out
    assert "(0 compilations after the first dispatch)" in out
    # every epoch began with the four retired rows stale: Adam over all F
    assert "'stale': 4.0" in out and "'updated': 512.0" in out


def test_warm_traced_run_reads_its_two_metrics_and_the_accepted_ones(root):
    result, out = helpers.run_cell(root, "tiny-retrain-drift", trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    metrics = result["metrics"]
    assert set(metrics) >= {
        "stale_rows_pct.train", "restage_ms.train",
        "proj_columns_pct.train", "adam_rows_pct.train",
        "device_idle_pct.train", "gru_kernel_ms.train", "xla_ops_ms.train",
        "epoch_host_ms.train", "readbacks_per_epoch.train",
        "dispatches_per_epoch.train"}, sorted(metrics)
    assert metrics["adam_rows_pct.train"]["value"] == 100.0
    assert metrics["proj_columns_pct.train"]["value"] == 25.0
    # of the four retired rows, those whose path the one prior step saw
    assert 0 < metrics["stale_rows_pct.train"]["value"] <= 100.0 * 4 / 512
    assert metrics["restage_ms.train"]["value"] > 0
    assert "train_steps_per_s" not in metrics


def test_the_skipped_off_table_pass_is_not_correct(root):
    """The control that is this cell's own: with the pass over the rows
    the restage retired left out, the check that crosses the restage
    fails at a w_ih leaf."""
    result, out = helpers.run_cell(root, "tiny-retrain-drift",
                                   prelude=SKIPPED_PASS)
    assert not result["correct"]
    lines = [ln for ln in out.splitlines()
             if "compare delta_norm_gap" in ln and "<-- OUT" in ln]
    assert lines and "w_ih" in lines[0], out[-3000:]


def test_an_older_program_reads_the_two_metrics_as_nothing():
    """The readers against a registry without the gauge and the kind (the
    parent commit laid over with this benchmark): nothing, no error."""
    from chipbench.readers import restage, stale_rows
    from deeprest_tpu.obs import metrics

    real, metrics.REGISTRY = metrics.REGISTRY, metrics.MetricsRegistry()
    try:
        assert restage.last_stage_ms({}) is None
        assert stale_rows.stale_pct({}) is None
        rows = metrics.REGISTRY.gauge("deeprest_train_optimizer_rows", "",
                                      labelnames=("kind",))
        rows.set(256, kind="updated")
        rows.set(10240, kind="total")
        assert stale_rows.stale_pct({}) is None
    finally:
        metrics.REGISTRY = real
