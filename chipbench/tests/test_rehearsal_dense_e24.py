"""A CPU rehearsal shaped like `tt-train-dense` (run by hand with the rest:
``python -m pytest chipbench/tests -q``; not part of tier-1): the dense
feed, every call path hot, and E=24 experts at the kernels' own hidden size
through ``pallas_interpret``, so that the harness walks an expert grid of
three blocks as the cell walks one of twenty-five.

The configuration, the mix, the limits and the cell are added to the
throw-away root of ``helpers.make_root`` as new files; a result made here
names the platform ``cpu`` and none of its numbers is a device number.
"""

import json
import os

import pytest

from chipbench.tests import helpers, test_rehearsal

RESOURCES = ["cpu", "memory", "write-iops", "usage"]      # 6 components x 4
MODEL = dict(helpers.TINY_MODEL, feature_dim=32, num_metrics=24,
             hidden_size=128, rnn_backend="pallas_interpret")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(str(tmp_path_factory.mktemp("chipbench-e24")))
    cb = os.path.join(root, "chipbench")
    helpers._write(os.path.join(cb, "configs", "tiny-e24.json"), {
        "name": "tiny-e24", "source": "test", "runners": ["train"],
        "model": MODEL,
        # no sparse_feed: the dense feed, staged as it is on the chip
        "train": {"batch_size": 4, "window_size": 6, "device_data": "always",
                  "steps_per_superstep": 8, "log_every_steps": 0},
        "reduced": [], "assumed": {}})
    helpers._write(os.path.join(cb, "traffic", "tiny-dense.json"), {
        "name": "tiny-dense", "runner": "train", "generator": "corpus",
        "params": {"buckets": 200, "hot_paths": MODEL["feature_dim"],
                   "nnz_lo": 4, "nnz_hi": 12, "day": 100,
                   "resources": RESOURCES}})
    helpers._write(os.path.join(cb, "limits", "tiny-e24-dense.json"), {
        "limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "delta_norm_gap": 1e-2}})
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-e24", "source": "test", "reduced": [], "why": "test",
         "file": "chipbench/configs/tiny-e24.json"})
    bench["workloads"].append(
        {"name": "tiny-e24-dense", "config": "tiny-e24",
         "traffic": "tiny-dense", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny-e24-dense")
    helpers._write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


def test_the_dense_cell_was_added_as_files_only(root):
    test_rehearsal.test_cells_were_added_as_files_only(root)
    for added in ("configs/tiny-e24.json", "traffic/tiny-dense.json",
                  "limits/tiny-e24-dense.json"):
        assert os.path.exists(os.path.join(root, "chipbench", added))
        assert not os.path.exists(os.path.join(helpers.CHIPBENCH, added))


def test_dense_feed_rehearsal_walks_three_expert_blocks(root):
    result, out = helpers.run_cell(root, "tiny-e24-dense",
                                   seed=3_000_000_026)
    assert result["correct"], out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"
    # the dense base went to the device as it is: [buckets, F], no COO
    assert "phase seeded weights, staged corpus" in out
