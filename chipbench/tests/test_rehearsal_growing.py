"""CPU rehearsal of the ``train_growing`` runner at a tiny size (run by hand
with the others: ``python -m pytest chipbench/tests -q``; not part of
tier-1, whose file is tests/test_growing.py).

As test_rehearsal_weeks.py: each run is a process of its own through
``run.run_cell(..., require_chip=False)``.  The cell is added to
helpers.make_root's throw-away root as files: F = 512 (the rule's bound is
256), four weeks of 100 / 150 / 200 / 300 live call paths, which pad to
tables of 128, 256 and 256 and then over the bound: three programs in one
life, as the 10k cell's 2,048 / 4,096 / 4,096 / dense.
"""

import json
import os

import pytest

from chipbench.tests import helpers
from chipbench.tests.test_rehearsal_warm import USE_RECORDED_TRACE

PARAMS = {"buckets": 400, "weeks": 4,
          "hot_paths_by_week": [100, 150, 200, 300], "nnz_lo": 24,
          "nnz_hi": 32, "day": 100, "resources": helpers.RESOURCES}
NEW_METRICS = ("superstep_programs.train", "program_switch_s.train")
LOST_HANDOVER = """
import chipbench.runners.train_growing as tg
from chipbench.tests.control_on_chip_growing import losing_the_compact_life
_checked = tg.checked_steps
tg.checked_steps = lambda *a, **kw: _checked(
    *a, at_handover=losing_the_compact_life, **kw)
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(str(tmp_path_factory.mktemp("chipbench-grow")))
    cb = os.path.join(root, "chipbench")
    helpers._write(os.path.join(cb, "configs", "tiny-growing.json"), {
        "name": "tiny-growing", "source": "test",
        "runners": ["train_growing"],
        "model": {**helpers.TINY_MODEL, "feature_dim": 512},
        "train": {"batch_size": 4, "window_size": 6, "sparse_feed": True,
                  "sparse_nnz_cap": 32, "steps_per_superstep": 8,
                  "log_every_steps": 0},
        "reduced": [], "assumed": {}})
    helpers._write(os.path.join(cb, "traffic", "tiny-weeks-growing.json"), {
        "name": "tiny-weeks-growing", "runner": "train_growing",
        "generator": "corpus_weeks_growing", "params": PARAMS})
    helpers._write(os.path.join(cb, "limits", "tiny-retrain-growing.json"), {
        "limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "delta_norm_gap": 1e-2}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-growing", "source": "test",
         "file": "chipbench/configs/tiny-growing.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "tiny-retrain-growing", "config": "tiny-growing",
         "traffic": "tiny-weeks-growing", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tenk-retrain-growing" in m.get("workloads", ()):
            m["workloads"].append("tiny-retrain-growing")
    helpers._write(path, bench)
    return root


def test_growing_rehearsal(root):
    result, out = helpers.run_cell(root, "tiny-retrain-growing",
                                   seed=3_000_000_054)
    assert result["correct"], out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    # four stagings of one trainer, three programs, the third staging keeps
    # the second's (what `left` counts between two tables of one width is
    # pad slots: dead columns, which carry no moment)
    for nth, (form, width, program) in enumerate(
            [("compact", 128, "new"), ("compact", 256, "new"),
             ("compact", 256, "same"), ("dense", 512, "new")], start=1):
        (line,) = [ln for ln in out.splitlines()
                   if f"staging {nth} of 4" in ln]
        assert f"'width': {width}" in line and f"'form': '{form}'" in line
        assert f"'program': '{program}'" in line, line
        if form == "dense":
            assert "'left': 0" in line      # it holds every column
    assert "week 3: program ('compact', 256) kept, compiled 0 staging + 0 " \
        "dispatching" in out
    assert "week 4: program ('dense', 512) NEW" in out
    assert "steps counted 5" in out
    assert "NOT CORRECT" not in out


def test_growing_traced_run_reads_the_two_new_metrics_and_the_accepted(root):
    result, out = helpers.run_cell(root, "tiny-retrain-growing", trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    metrics = result["metrics"]
    assert set(metrics) >= {
        *NEW_METRICS, "restage_ms.train",
        "proj_columns_pct.train", "adam_rows_pct.train",
        "proj_dead_columns_pct.train", "device_idle_pct.train",
        "gru_kernel_ms.train", "xla_ops_ms.train", "epoch_host_ms.train",
        "readbacks_per_epoch.train", "dispatches_per_epoch.train",
        "compilations.train", "updates_per_epoch.train"}, sorted(metrics)
    assert metrics["superstep_programs.train"]["value"] == 3
    assert metrics["program_switch_s.train"]["value"] > 0
    # the window's program is the dense form: every column contracted, Adam
    # over all rows, none of them stale
    assert metrics["adam_rows_pct.train"]["value"] == 100.0
    assert metrics["proj_columns_pct.train"]["value"] == 100.0
    assert "train_steps_per_s" not in metrics


def test_a_handover_that_loses_the_compact_life_is_not_correct(root):
    """The w_ih leaves' moments zeroed at the restage that takes the dense
    form: the check that crosses the handover fails at a w_ih leaf."""
    result, out = helpers.run_cell(root, "tiny-retrain-growing",
                                   prelude=LOST_HANDOVER)
    assert not result["correct"]
    lines = [ln for ln in out.splitlines()
             if "compare delta_norm_gap" in ln and "<-- OUT" in ln]
    assert lines and "w_ih" in lines[0], out[-3000:]
