"""CPU rehearsal of the ``train_weeks`` runner at a tiny size (run by hand
with the others: ``python -m pytest chipbench/tests -q``; not part of
tier-1).

As test_rehearsal_warm.py: each run is a process of its own through
``run.run_cell(..., require_chip=False)``.  The cell is added to
helpers.make_root's throw-away root as files: a configuration wide enough
for the compact form on the CPU whose table IS the live set (F = 512, 128
hot call paths, 24 to 31 of them a bucket), a mix of the ``train_weeks``
runner over ``corpus_weeks`` (four weeks, 96 of the 128 paths carried at
each of three releases: 96 retired columns, under the program's bound of
143 at 8 steps a dispatch), limits and the entries of BENCHMARK.json.
"""

import json
import os

import numpy as np
import pytest

from chipbench.tests import helpers
from chipbench.tests.test_rehearsal_warm import SKIPPED_PASS, USE_RECORDED_TRACE

PARAMS = {"buckets": 400, "hot_paths": 128, "nnz_lo": 24, "nnz_hi": 32,
          "day": 100, "resources": helpers.RESOURCES, "carried_paths": 96,
          "weeks": 4}
NEW_METRICS = ("rows_visited_pct.train", "off_table_trips_per_dispatch.train")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(str(tmp_path_factory.mktemp("chipbench-weeks")))
    cb = os.path.join(root, "chipbench")
    helpers._write(os.path.join(cb, "configs", "tiny-weeks.json"), {
        "name": "tiny-weeks", "source": "test", "runners": ["train_weeks"],
        "model": {**helpers.TINY_MODEL, "feature_dim": 512},
        "train": {"batch_size": 4, "window_size": 6, "sparse_feed": True,
                  "sparse_nnz_cap": 32, "steps_per_superstep": 8,
                  "log_every_steps": 0},
        "reduced": [], "assumed": {}})
    helpers._write(os.path.join(cb, "traffic", "tiny-weeks-drift.json"), {
        "name": "tiny-weeks-drift", "runner": "train_weeks",
        "generator": "corpus_weeks", "params": PARAMS})
    helpers._write(os.path.join(cb, "limits", "tiny-retrain-weeks.json"), {
        "limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "delta_norm_gap": 1e-2}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-weeks", "source": "test",
         "file": "chipbench/configs/tiny-weeks.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "tiny-retrain-weeks", "config": "tiny-weeks",
         "traffic": "tiny-weeks-drift", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tenk-retrain-live4k" in m.get("workloads", ()):
            m["workloads"].append("tiny-retrain-weeks")
    helpers._write(path, bench)
    return root


def test_weeks_rehearsal(root):
    result, out = helpers.run_cell(root, "tiny-retrain-weeks",
                                   seed=3_000_000_040)
    assert result["correct"], out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    # four stagings of one trainer, each restage 32 rows out and 32 in,
    # nothing compiled after the first dispatch, five steps counted
    assert "staging 1 of 4 {'restage': False, 'nth': 1, 'width': 128" in out
    for nth in (2, 3, 4):
        assert (f"staging {nth} of 4 {{'restage': True, 'nth': {nth}, "
                "'width': 128") in out
    assert out.count("'left': 32, 'entered': 32") == 3
    assert "(0 compilations after the first dispatch)" in out
    assert "steps counted 5" in out
    # every epoch began with the retired rows stale: row by row, two trips
    assert "'bound': 143.0" in out and "'trips': 2.0" in out
    assert "'updated': 512.0" in out and "'visited': 256.0" in out


def test_weeks_traced_run_reads_the_two_new_metrics_and_the_accepted(root):
    result, out = helpers.run_cell(root, "tiny-retrain-weeks", trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    metrics = result["metrics"]
    assert set(metrics) >= {
        *NEW_METRICS, "stale_rows_pct.train", "restage_ms.train",
        "proj_columns_pct.train", "adam_rows_pct.train",
        "proj_dead_columns_pct.train", "device_idle_pct.train",
        "gru_kernel_ms.train", "xla_ops_ms.train", "epoch_host_ms.train",
        "readbacks_per_epoch.train", "dispatches_per_epoch.train"
    }, sorted(metrics)
    assert metrics["adam_rows_pct.train"]["value"] == 100.0
    assert metrics["proj_columns_pct.train"]["value"] == 25.0
    assert metrics["proj_dead_columns_pct.train"]["value"] == 0.0
    stale = metrics["stale_rows_pct.train"]["value"] * 512 / 100
    assert 0.9 * 96 <= stale <= 96
    assert metrics["off_table_trips_per_dispatch.train"]["value"] == 2
    assert metrics["rows_visited_pct.train"]["value"] == 50.0
    assert "train_steps_per_s" not in metrics


def test_the_skipped_off_table_pass_is_not_correct(root):
    """With the pass over the rows the releases retired left out, the
    check that crosses the restages fails at a w_ih leaf."""
    result, out = helpers.run_cell(root, "tiny-retrain-weeks",
                                   prelude=SKIPPED_PASS)
    assert not result["correct"]
    lines = [ln for ln in out.splitlines()
             if "compare delta_norm_gap" in ln and "<-- OUT" in ln]
    assert lines and "w_ih" in lines[0], out[-3000:]


def test_two_weeks_are_corpus_pairs_to_the_bit():
    from chipbench.generators import corpus_pair, corpus_weeks

    model = {"feature_dim": 512, "num_metrics": 10}
    params = {**PARAMS, "weeks": 2}
    pair = corpus_pair.generate(
        {k: v for k, v in params.items() if k != "weeks"}, 3_000_000_040,
        model)
    weeks = corpus_weeks.generate(params, 3_000_000_040, model)
    assert len(weeks) == 2
    for mine, theirs in zip(weeks, (pair["prior"], pair["current"])):
        assert np.array_equal(mine["traffic"], theirs["traffic"])
        assert list(mine["resources"]) == list(theirs["resources"])
        for name, series in theirs["resources"].items():
            assert np.array_equal(mine["resources"][name], series)


def test_each_release_retires_columns_that_never_come_back():
    from chipbench.generators import corpus_weeks

    columns = corpus_weeks.hot_columns(PARAMS, 7, 512)
    assert len(columns) == 4
    seen = set(columns[0])
    for before, after in zip(columns, columns[1:]):
        moved = np.flatnonzero(before != after)
        assert len(moved) == 32 and len(set(after)) == 128
        # one of every run of four consecutive popularity ranks
        assert sorted(moved // 4) == list(range(32))
        assert not seen & set(after[moved])
        seen |= set(after)
    assert len(seen) == 128 + 3 * 32
    with pytest.raises(ValueError, match="never hot before"):
        corpus_weeks.hot_columns({**PARAMS, "weeks": 14}, 7, 512)


def test_an_older_program_reads_the_two_metrics_as_nothing():
    """The readers against a registry without the gauge, and with the
    gauge of the parent commit (``visited`` since PR 34, no ``trips``)."""
    from chipbench.readers import off_table_trips, rows_visited
    from deeprest_tpu.obs import metrics

    real, metrics.REGISTRY = metrics.REGISTRY, metrics.MetricsRegistry()
    try:
        assert rows_visited.visited_pct({}) is None
        assert off_table_trips.trips({}) is None
        rows = metrics.REGISTRY.gauge("deeprest_train_optimizer_rows", "",
                                      labelnames=("kind",))
        rows.set(10240, kind="updated")
        rows.set(10240, kind="total")
        assert rows_visited.visited_pct({}) is None
        rows.set(320, kind="visited")
        assert rows_visited.visited_pct({}) == 3.125
        assert off_table_trips.trips({}) is None
        rows.set(80, kind="trips")
        assert off_table_trips.trips({}) == 80
    finally:
        metrics.REGISTRY = real
