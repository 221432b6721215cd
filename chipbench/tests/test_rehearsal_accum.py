"""CPU rehearsal of the ``train_accum`` runner at a tiny size (run by hand
with the others: ``python -m pytest chipbench/tests -q``; not part of
tier-1, whose tests/test_accum8.py drives the runner's pieces in-process).

As test_rehearsal.py: each run is a process of its own through
``run.run_cell(..., require_chip=False)``, and a result made here names the
platform ``cpu``.  The cell is added to helpers.make_root's throw-away root
as files: a configuration wide enough for the compact form on the CPU
(F = 512, 16 hot call paths in a table of 128) at 4 microbatches of 4
windows an update, a mix of the ``train_accum`` runner over ``corpus``,
limits and the entries of BENCHMARK.json.
"""

import json
import os

import pytest

from chipbench.tests import helpers

USE_RECORDED_TRACE = f"""
import chipbench.trace_reduce as tr
tr.reduce_dir = lambda _dir: tr.reduce_file(
    {os.path.join(helpers.HERE, "data", "recorded_v5e.xplane.pb")!r})
"""

# the timed path broken underneath: the superstep loses the second
# microbatch of every update (its weights read zero)
LOST_MICROBATCH = """
import deeprest_tpu.train.trainer as T
_build = T.Trainer._build_programs
def _broken(self):
    _build(self)
    real = self._superstep
    def lossy(state, x, y, starts, weights, c):
        return real(state, x, y, starts, weights.at[:, 1::4].set(0.0), c)
    self._superstep = lossy
T.Trainer._build_programs = _broken
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(str(tmp_path_factory.mktemp("chipbench-accum")))
    cb = os.path.join(root, "chipbench")
    helpers._write(os.path.join(cb, "configs", "tiny-accum4.json"), {
        "name": "tiny-accum4", "source": "test", "runners": ["train_accum"],
        "model": {**helpers.TINY_MODEL, "feature_dim": 512},
        "train": {"batch_size": 4, "window_size": 6, "sparse_feed": True,
                  "sparse_nnz_cap": 8, "steps_per_superstep": 8,
                  "log_every_steps": 0, "grad_accum_windows": 4},
        "reduced": [], "assumed": {}})
    helpers._write(os.path.join(cb, "traffic", "tiny-corpus-accum4.json"), {
        "name": "tiny-corpus-accum4", "runner": "train_accum",
        "generator": "corpus",
        "params": {"buckets": 400, "hot_paths": 16, "nnz_lo": 2, "nnz_hi": 6,
                   "day": 100, "resources": helpers.RESOURCES}})
    helpers._write(os.path.join(cb, "limits", "tiny-train-accum4.json"), {
        "limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "delta_norm_gap": 1e-2}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-accum4", "source": "test",
         "file": "chipbench/configs/tiny-accum4.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "tiny-train-accum4", "config": "tiny-accum4",
         "traffic": "tiny-corpus-accum4", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tenk-train-accum8" in m.get("workloads", ()):
            m["workloads"].append("tiny-train-accum4")
    helpers._write(path, bench)
    return root


def test_accum_rehearsal(root):
    result, out = helpers.run_cell(root, "tiny-train-accum4",
                                   seed=3_000_000_048)
    assert result["correct"], out[-3000:]
    # 157 train windows: 40 microbatches of 4, 10 updates an epoch; a step
    # is a microbatch
    assert "40 microbatches of 4, 10 updates an epoch" in out
    assert result["failed"] == 0
    assert result["attempted"] > 0 and result["attempted"] % 40 == 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert "steps counted 10 updates counted 3" in out
    assert "updates/s of 4 microbatches" in out


def test_accum_traced_run_reads_its_two_metrics_and_the_accepted_ones(root):
    result, out = helpers.run_cell(root, "tiny-train-accum4", trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    metrics = result["metrics"]
    assert set(metrics) >= {
        "updates_per_epoch.train", "accum_carry_mb.train",
        "proj_columns_pct.train", "adam_rows_pct.train",
        "device_idle_pct.train", "gru_kernel_ms.train", "xla_ops_ms.train",
        "epoch_host_ms.train", "readbacks_per_epoch.train",
        "dispatches_per_epoch.train"}, sorted(metrics)
    assert metrics["updates_per_epoch.train"]["value"] == 10
    assert metrics["adam_rows_pct.train"]["value"] == 25.0
    # the table's 128 rows of the two w_ih leaves and the other leaves in
    # float32: 453,880 bytes (1,191,160 with the leaves' 512 rows)
    assert metrics["accum_carry_mb.train"]["value"] == pytest.approx(0.45388)
    assert "train_steps_per_s" not in metrics


def test_a_lost_microbatch_is_not_correct(root):
    result, out = helpers.run_cell(root, "tiny-train-accum4",
                                   prelude=LOST_MICROBATCH)
    assert not result["correct"]
    assert "<-- OUT" in out or "NOT CORRECT" in out, out[-3000:]
