"""CPU rehearsals of the harness at tiny sizes (run by hand:
``python -m pytest chipbench/tests -q``; not part of tier-1).

Each run is a process of its own through ``run.run_cell(...,
require_chip=False)``: the harness's look for a chip is skipped, everything
else is the code a chip run drives.  A result made here names the platform
``cpu``; none of its numbers is a device number.
"""

import filecmp
import os

import pytest

from chipbench.tests import helpers

RECORDED = os.path.join(helpers.HERE, "data", "recorded_v5e.xplane.pb")

USE_RECORDED_TRACE = f"""
import chipbench.trace_reduce as tr
tr.reduce_dir = lambda _dir: tr.reduce_file({RECORDED!r})
"""

# the timed path broken underneath: a step that returns its state unchanged
BROKEN_STEP = """
import jax, jax.numpy as jnp
import deeprest_tpu.train.trainer as T
_build = T.Trainer._build_programs
def _broken(self):
    _build(self)
    real = self._superstep
    def unchanged(state, *args):
        _, losses = real(jax.tree.map(jnp.copy, state), *args)
        return state, losses
    self._superstep = unchanged
T.Trainer._build_programs = _broken
"""

# the timed path broken underneath: the last row of every batch left out
BROKEN_BATCH = """
import deeprest_tpu.train.trainer as T
_build = T.Trainer._build_programs
def _broken(self):
    _build(self)
    real = self._superstep
    def short(state, x, y, starts, weights, c):
        return real(state, x, y, starts, weights.at[..., -1].set(0.0), c)
    self._superstep = short
T.Trainer._build_programs = _broken
"""

# a harness that leaves its own copy of the weights on the device
SECOND_COPY = """
import chipbench.common as C
class _Ctx:
    peak = 100
    def memory_peak_bytes(self):
        return self.peak
ctx = _Ctx()
with C.harness_only(ctx, "nothing"):
    pass
try:
    with C.harness_only(ctx, "a second copy"):
        ctx.peak = 200
except RuntimeError as e:
    print("GUARD", e)
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return helpers.make_root(str(tmp_path_factory.mktemp("chipbench")))


def test_cells_were_added_as_files_only(root):
    """The throw-away configuration, mixes, limits, cells and per-layer
    metric are new files: every file the benchmark has is untouched."""
    cmp = filecmp.dircmp(helpers.CHIPBENCH, os.path.join(root, "chipbench"),
                         ignore=["__pycache__", "data"])

    def walk(c):
        assert not c.diff_files and not c.left_only, (c.left, c.diff_files,
                                                      c.left_only)
        for sub in c.subdirs.values():
            walk(sub)

    walk(cmp)
    assert "tiny.json" in cmp.subdirs["configs"].right_only
    assert "steps_in_slice.py" in cmp.subdirs["readers"].right_only


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-train-sparse"])
def test_train_rehearsal(root, cell):
    result, out = helpers.run_cell(root, cell, seed=3_000_000_019)
    assert result["correct"], out[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"train_steps_per_s", "hbm_peak_gb",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"


def test_train_traced_run_reads_layer_metrics_and_the_added_one(root):
    result, out = helpers.run_cell(root, "tiny-train", trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    assert result["metrics"]["steps_in_slice"]["value"] == 40
    assert "device_idle_pct.train" in result["metrics"]
    assert "train_steps_per_s" not in result["metrics"]
    assert result["device"]["busy_s"] > 0 and result["device"]["window_s"] > 0
    assert len(result["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("broken, number", [
    (BROKEN_STEP, "delta_norm_gap"), (BROKEN_BATCH, "loss_rel_gap")])
def test_broken_timed_path_is_not_correct(root, broken, number):
    result, out = helpers.run_cell(root, "tiny-train", prelude=broken)
    assert not result["correct"]
    assert [ln for ln in out.splitlines()
            if f"compare {number}" in ln and "<-- OUT" in ln], out[-3000:]


def test_harness_copy_that_raises_the_peak_fails_the_run(root):
    _, out = helpers.run_cell(root, "tiny-train", prelude=SECOND_COPY)
    assert "GUARD a second copy raised the peak of device memory" in out


def test_no_chip_means_no_result(root):
    """The command itself never runs without a TPU: non-zero, no result."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=helpers.REPO)
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", "tiny-train", "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, cwd=root, capture_output=True, text=True)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout
