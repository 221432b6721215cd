"""The reader of the projection's column gauge (readers/proj_columns.py):
nothing to read on a program without the gauge or on a dense staged feed,
the share on one that staged a sparse corpus, and the value in the result
line of a traced rehearsal on each side of the program's rule (a live set
that is most of F, and one narrow enough for the compact form)."""

import json
import os

import pytest

from chipbench.readers import proj_columns
from chipbench.tests import helpers
from chipbench.tests.test_rehearsal import USE_RECORDED_TRACE

METRIC = "proj_columns_pct.train"


@pytest.fixture
def registry(monkeypatch):
    from deeprest_tpu.obs import metrics

    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    return fresh


def test_no_gauge_or_an_unset_one_reads_as_nothing(registry):
    assert proj_columns.contracted_pct({}) is None
    registry.gauge("deeprest_train_projection_columns", labelnames=("kind",))
    assert proj_columns.contracted_pct({}) is None


@pytest.mark.parametrize("contracted, expected", [(256, 2.5), (10240, 100.0)])
def test_a_set_gauge_reads_as_the_share(registry, contracted, expected):
    gauge = registry.gauge("deeprest_train_projection_columns",
                           labelnames=("kind",))
    for kind, n in (("live", 256), ("contracted", contracted),
                    ("total", 10240)):
        gauge.set(n, kind=kind)
    assert proj_columns.contracted_pct({}) == pytest.approx(expected)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The rehearsal root plus a sparse cell wide enough for the compact
    form (F=512, 16 hot paths: a table of 128 = F/4), and the metric read
    in the three tiny cells."""
    root = helpers.make_root(str(tmp_path_factory.mktemp("proj")))
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
    ("tiny-train-compact", 25.0),       # 16 of 512 live: a table of 128
])
def test_traced_rehearsal_reports_the_share(root, cell, expected):
    result, out = helpers.run_cell(root, cell, trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    if expected is None:
        assert METRIC not in result["metrics"]
    else:
        assert result["metrics"][METRIC] == {"value": expected, "unit": "%"}
