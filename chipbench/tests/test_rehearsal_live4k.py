"""CPU rehearsal of `tenk-train-live4k` (by hand, with the other files here:
``python -m pytest chipbench/tests -q``): the cell's own configuration, mix
and limits files cut to a tiny size and run through the harness on both
sides of the sparse feed's rule, the reader of `proj_dead_columns_pct.train`
on a compact, a dense and a not-sparse staging, and the dropped-columns
control of chipbench/tests/control_on_chip_live4k.py.  A result made here
names the platform ``cpu``; none of its numbers is a device number."""

import json
import os

import pytest

from chipbench.tests import helpers
from chipbench.tests.test_rehearsal import USE_RECORDED_TRACE

METRIC = "proj_dead_columns_pct.train"
CELLS = {
    # cell: (hot paths of F=512, the form the rule takes, the metric)
    "tiny-live-compact": (16, "compact", 100.0 * (128 - 16) / 512),
    "tiny-live-dense": (200, "dense", 100.0 * (512 - 200) / 512),
}

DROPPED = """
from chipbench.tests import control_on_chip_live4k
control_on_chip_live4k.most_hit_half_only()
"""

SHOW_THE_STAGE_SPAN = """
import atexit
from deeprest_tpu import obs
obs.RECORDER.enabled = True
atexit.register(lambda: [print("STAGE", sorted(s.tags.items()))
                         for s in obs.RECORDER.drain()
                         if s.name == "train.stage"])
"""


def _load(*parts):
    with open(os.path.join(helpers.CHIPBENCH, *parts)) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal root plus the new cell's files at a tiny size: the
    configuration's `model` and `train` with the widths cut (F=512, E=10,
    H=8, B=4, W=6, float32), the mix with 400 buckets and a live set on
    either side of F // 4 = 128."""
    root = helpers.make_root(str(tmp_path_factory.mktemp("live4k")))
    cb = os.path.join(root, "chipbench")
    config = _load("configs", "endpoints-10k-live4k.json")
    config["name"] = "tiny-live"
    config["model"] = dict(config["model"], feature_dim=512, num_metrics=10,
                           hidden_size=8, compute_dtype="float32")
    config["train"] = dict(config["train"], batch_size=4, window_size=6,
                           sparse_nnz_cap=16, steps_per_superstep=8,
                           log_every_steps=0)
    helpers._write(os.path.join(cb, "configs", "tiny-live.json"), config)
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-live", "source": "test",
         "file": "chipbench/configs/tiny-live.json", "reduced": [],
         "why": "test"})
    with open(os.path.join(cb, "limits", "tiny-train-sparse.json")) as fh:
        limits = json.load(fh)
    for cell, (hot, _, _) in CELLS.items():
        mix = _load("traffic", "week-live4k.json")
        mix["name"] = cell
        mix["params"] = dict(mix["params"], buckets=400, hot_paths=hot,
                             nnz_lo=3, nnz_hi=12, day=100)
        helpers._write(os.path.join(cb, "traffic", cell + ".json"), mix)
        helpers._write(os.path.join(cb, "limits", cell + ".json"), limits)
        bench["workloads"].append(
            {"name": cell, "config": "tiny-live", "traffic": cell,
             "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "tenk-train-live4k" in m.get("workloads", ()):
            m["workloads"] += list(CELLS)
        if m["name"] == METRIC:
            m["workloads"].append("tiny-train")
    helpers._write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rehearsal_on_either_side_of_the_rule(root, cell):
    _, form, _ = CELLS[cell]
    result, out = helpers.run_cell(root, cell, seed=3_000_000_038,
                                   prelude=SHOW_THE_STAGE_SPAN)
    assert result["correct"], out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    (stage,) = [ln for ln in out.splitlines() if ln.startswith("STAGE ")]
    assert f"('form', '{form}')" in stage and "('bound', 128)" in stage


@pytest.mark.parametrize("cell", sorted(CELLS) + ["tiny-train"])
def test_traced_rehearsal_reports_the_dead_share(root, cell):
    result, out = helpers.run_cell(root, cell, trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    if cell == "tiny-train":            # the dense staged feed: no gauge set
        assert METRIC not in result["metrics"]
        return
    _, form, expected = CELLS[cell]
    assert result["metrics"][METRIC] == {"value": expected, "unit": "%"}
    assert result["metrics"]["proj_columns_pct.train"]["value"] == (
        25.0 if form == "compact" else 100.0)
    assert result["metrics"]["adam_rows_pct.train"]["value"] == (
        25.0 if form == "compact" else 100.0)


def test_a_table_of_half_the_live_paths_is_not_correct(root):
    """The broken path the configuration's guarantee names: 100 of the 200
    live call paths staged, the rest left out."""
    result, out = helpers.run_cell(root, "tiny-live-dense", prelude=DROPPED)
    assert not result["correct"]
    assert [ln for ln in out.splitlines()
            if "compare " in ln and "<-- OUT" in ln], out[-3000:]


def test_the_control_leaves_a_narrow_live_set_alone_but_for_its_half(root):
    """The control's patch takes half of ANY live set: 8 of 16 here."""
    result, out = helpers.run_cell(root, "tiny-live-compact", prelude=DROPPED)
    assert not result["correct"], out[-3000:]
