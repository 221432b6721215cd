"""The reader of the optimizer's row gauge (readers/adam_rows.py): nothing
to read on a program without the gauge, with an unset one or with ``total``
0, the share on one that trained on a staged sparse corpus, and the value in
the result line of a traced rehearsal on each side of the program's rule (a
dense staged feed: no gauge; a sparse base in its dense form: all F rows; a
compact one with moments on its table: the table's rows)."""

import json
import os

import pytest

from chipbench.readers import adam_rows
from chipbench.tests import helpers
from chipbench.tests.test_rehearsal import USE_RECORDED_TRACE

METRIC = "adam_rows_pct.train"
GAUGE = "deeprest_train_optimizer_rows"


@pytest.fixture
def registry(monkeypatch):
    from deeprest_tpu.obs import metrics

    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


@pytest.mark.parametrize("updated, total, expected", [
    (None, None, None),                 # the parent commit: no such gauge
    ((), (), None),                     # the gauge there, no epoch finished
    (0, 0, None),                       # total 0: nothing to divide by
    (256, 10240, 2.5), (10240, 10240, 100.0)])
def test_the_gauge_reads_as_the_share_or_as_nothing(registry, updated, total,
                                                    expected):
    if updated is not None:
        gauge = registry.gauge(GAUGE, labelnames=("kind",))
        if updated != ():
            gauge.set(updated, kind="updated")
            gauge.set(total, kind="total")
    got = adam_rows.updated_pct({})
    assert got is None if expected is None else got == pytest.approx(expected)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal root plus a sparse cell wide enough for the compact
    form (F=512, 16 hot paths: a table of 128), and the metric read in the
    three tiny cells."""
    root = helpers.make_root(str(tmp_path_factory.mktemp("adam")))
    cb = os.path.join(root, "chipbench")
    with open(os.path.join(cb, "configs", "tiny-sparse.json")) as fh:
        wide = json.load(fh)
    wide["name"] = "tiny-sparse-wide"
    wide["model"] = dict(wide["model"], feature_dim=512)
    helpers._write(os.path.join(cb, "configs", "tiny-sparse-wide.json"), wide)
    with open(os.path.join(cb, "limits", "tiny-train-sparse.json")) as fh:
        helpers._write(os.path.join(cb, "limits", "tiny-train-compact.json"),
                       json.load(fh))
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-sparse-wide", "source": "test",
         "file": "chipbench/configs/tiny-sparse-wide.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "tiny-train-compact", "config": "tiny-sparse-wide",
         "traffic": "tiny-corpus", "chips": 1, "why": "test"})
    cells = ["tiny-train", "tiny-train-sparse", "tiny-train-compact"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] == METRIC:
            m["workloads"] += cells
        elif "workloads" in m:
            m["workloads"].append("tiny-train-compact")
    helpers._write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


@pytest.mark.parametrize("cell, expected", [
    ("tiny-train", None),               # dense staged feed: no gauge set
    ("tiny-train-sparse", 100.0),       # 16 of 24 columns live: dense form
    ("tiny-train-compact", 25.0),       # a table of 128 of 512 rows
])
def test_traced_rehearsal_reports_the_share(root, cell, expected):
    result, out = helpers.run_cell(root, cell, trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    if expected is None:
        assert METRIC not in result["metrics"]
    else:
        assert result["metrics"][METRIC] == {"value": expected, "unit": "%"}
