"""CPU rehearsal of the ``train_mesh`` runner at a tiny size, on four of the
CPU's virtual devices (run by hand with the others: ``python -m pytest
chipbench/tests -q``; not part of tier-1).

As test_rehearsal.py: each run is a process of its own through
``run.run_cell(..., require_chip=False)``, and a result made here names the
platform ``cpu``.  The cell is added to helpers.make_root's throw-away root
as files: a configuration with a mesh, a mix of the ``train_mesh`` runner,
limits and the entries of BENCHMARK.json.
"""

import json
import os

import pytest

from chipbench.tests import helpers

SLICE = os.path.join(helpers.HERE, "data", "recorded_v5e_dp4_step.json")

FOUR_DEVICES = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
"""

# a CPU trace holds no device plane: the recorded step of four chips instead
USE_RECORDED_STEP = FOUR_DEVICES + f"""
import json
import chipbench.trace_reduce as tr
tr.find_xplane = lambda _dir: {SLICE!r}
def _read(path):
    with open(path) as fh:
        return [(p, [(line, [tuple(e) for e in events])
                     for line, events in lines])
                for p, lines in json.load(fh)["planes"]]
tr.read_planes = _read
"""

# The timed path broken underneath (the wrappers keep the jitted program's
# `lower`, which the trainer reads its collectives through): a step that
# returns its state unchanged ...
BROKEN_STEP = FOUR_DEVICES + """
import jax, jax.numpy as jnp
import deeprest_tpu.train.trainer as T
_build = T.Trainer._build_programs
def _broken(self):
    _build(self)
    real = self._superstep
    def unchanged(state, *args):
        _, losses = real(jax.tree.map(jnp.copy, state), *args)
        return state, losses
    unchanged.lower = real.lower
    self._superstep = unchanged
T.Trainer._build_programs = _broken
"""

# ... and the last chip's rows left out of the mean
BROKEN_MEAN = FOUR_DEVICES + """
import deeprest_tpu.train.trainer as T
_build = T.Trainer._build_programs
def _broken(self):
    _build(self)
    real = self._superstep
    def short(state, x, y, starts, weights, c):
        quarter = weights.shape[-1] // 4
        return real(state, x, y, starts,
                    weights.at[..., -quarter:].set(0.0), c)
    short.lower = real.lower
    self._superstep = short
T.Trainer._build_programs = _broken
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(str(tmp_path_factory.mktemp("chipbench-mesh")))
    cb = os.path.join(root, "chipbench")
    helpers._write(os.path.join(cb, "configs", "tiny-mesh.json"), {
        "name": "tiny-mesh", "source": "test", "runners": ["train_mesh"],
        "model": {**helpers.TINY_MODEL, "feature_dim": 512},
        "train": {"batch_size": 8, "window_size": 6, "sparse_feed": True,
                  "sparse_nnz_cap": 8, "steps_per_superstep": 8,
                  "log_every_steps": 0},
        "mesh": {"data": 4, "expert": 1, "model": 1},
        "reduced": [], "assumed": {}})
    helpers._write(os.path.join(cb, "traffic", "tiny-corpus-mesh.json"), {
        "name": "tiny-corpus-mesh", "runner": "train_mesh",
        "generator": "corpus",
        "params": {"buckets": 400, "hot_paths": 16, "nnz_lo": 2, "nnz_hi": 6,
                   "day": 100, "resources": helpers.RESOURCES}})
    helpers._write(os.path.join(cb, "limits", "tiny-train-mesh.json"), {
        "limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                   "delta_norm_gap": 1e-2}})
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    bench["configs"].append(
        {"name": "tiny-mesh", "source": "test",
         "file": "chipbench/configs/tiny-mesh.json", "reduced": [],
         "why": "test"})
    bench["workloads"].append(
        {"name": "tiny-train-mesh", "config": "tiny-mesh",
         "traffic": "tiny-corpus-mesh", "chips": 4, "why": "test"})
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("tiny-train-mesh")
    for m in bench["per_layer"]:             # wherever the real cell is named
        if "tenk-train-dp4" in m.get("workloads", ()):
            m["workloads"].append("tiny-train-mesh")
    helpers._write(path, bench)
    return root


def test_mesh_rehearsal(root):
    result, out = helpers.run_cell(root, "tiny-train-mesh",
                                   seed=3_000_000_031, prelude=FOUR_DEVICES)
    assert result["correct"], out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    assert result["device"] == {**result["device"], "platform": "cpu",
                                "count": 4}
    assert "mesh {'data': 4, 'expert': 1, 'model': 1}" in out
    # the compact feed and the row-wise Adam hold under the `data` axis
    assert "'contracted': 128.0" in out and "'updated': 128.0" in out


def test_mesh_traced_run_reads_the_collectives_and_the_accepted_metrics(root):
    result, out = helpers.run_cell(root, "tiny-train-mesh", trace=True,
                                   prelude=USE_RECORDED_STEP)
    assert result["correct"], out[-3000:]
    metrics = result["metrics"]
    # its own three; the two gauges whose lists name the cell; and the
    # seven that apply by runner name, read as the `train` run it is
    assert set(metrics) >= {
        "collective_ms.train", "collective_exposed_ms.train",
        "collective_mb_per_step.train",
        "proj_columns_pct.train", "adam_rows_pct.train",
        "device_idle_pct.train", "gru_kernel_ms.train", "xla_ops_ms.train",
        "epoch_host_ms.train", "readbacks_per_epoch.train",
        "dispatches_per_epoch.train"}, sorted(metrics)
    assert (0 < metrics["collective_exposed_ms.train"]["value"]
            <= metrics["collective_ms.train"]["value"])
    assert metrics["collective_mb_per_step.train"]["value"] > 0
    # the compact feed and the row-wise Adam under the mesh: 128 of 512
    assert metrics["proj_columns_pct.train"]["value"] == 25.0
    assert metrics["adam_rows_pct.train"]["value"] == 25.0
    assert "train_steps_per_s" not in metrics


@pytest.mark.parametrize("broken, number", [
    (BROKEN_STEP, "delta_norm_gap"), (BROKEN_MEAN, "loss_rel_gap")],
    ids=["step", "mean"])
def test_broken_mesh_path_is_not_correct(root, broken, number):
    result, out = helpers.run_cell(root, "tiny-train-mesh", prelude=broken)
    assert not result["correct"]
    assert [ln for ln in out.splitlines()
            if f"compare {number}" in ln and "<-- OUT" in ln], out[-3000:]


def test_fewer_devices_than_the_mesh_stops_at_once(root):
    with pytest.raises(RuntimeError, match="the mesh .* asks for 4"):
        helpers.run_cell(root, "tiny-train-mesh", prelude="""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
""")
