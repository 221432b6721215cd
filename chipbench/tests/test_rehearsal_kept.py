"""CPU rehearsal of a run whose superstep came from the store of kept
executables (``deeprest_tpu/train/kept.py``, ISSUE 51; run by hand with the
others: ``python -m pytest chipbench/tests -q``; not part of tier-1, whose
tests/test_kept_executable.py holds the store itself).

As test_rehearsal.py: each run is a process of its own through
``run.run_cell(..., require_chip=False)`` on helpers.make_root's throw-away
root, whose compile cache, and so whose store, is the root's own
(``<root>/.jax_cache/deeprest-kept/``).  The first run compiles and writes,
the second loads.  Then the store is POISONED: a run whose superstep loses
the last window of every batch, in a way the store's key cannot see (the
function is replaced at run time, the files are as they were), writes its
executable under the honest key, and the next honest run loads it.  The
runner's check drives ``trainer._superstep`` and compares its three steps
with the float32 reference whatever made the executable, so that run is
refused.
"""

import json
import os

import pytest

from chipbench.tests import helpers

CELL = "tiny-train-sparse"

USE_RECORDED_TRACE = f"""
import chipbench.trace_reduce as tr
tr.reduce_dir = lambda _dir: tr.reduce_file(
    {os.path.join(helpers.HERE, "data", "recorded_v5e.xplane.pb")!r})
"""

# the program that is traced, compiled and KEPT loses the last window of
# every batch; no file of the package changes, so the key is the honest one
LOSSY_PROGRAM = """
import functools
import deeprest_tpu.train.kept as K
_init = K.KeptJit.__init__
def _lossy(self, fun, mesh, identity, **jit_kwargs):
    @functools.wraps(fun)
    def short(state, x, y, starts, weights, c):
        return fun(state, x, y, starts, weights.at[..., -1].set(0.0), c)
    _init(self, short, mesh, identity, **jit_kwargs)
K.KeptJit.__init__ = _lossy
"""


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = helpers.make_root(str(tmp_path_factory.mktemp("chipbench-kept")))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        bench = json.load(fh)
    for m in bench["per_layer"]:
        if m["name"] in ("superstep_loaded.train", "trace_s.train",
                         "superstep_build_s.train"):
            m["workloads"].append(CELL)
    helpers._write(path, bench)
    return root


def _kept(root):
    directory = os.path.join(root, ".jax_cache", "deeprest-kept")
    return [os.path.join(directory, name)
            for name in (os.listdir(directory)
                         if os.path.isdir(directory) else ())]


def test_the_second_run_loads_what_the_first_kept_and_a_poisoned_store_is_refused(
        root):
    # 1. nothing kept: the run traces, compiles, writes
    result, out = helpers.run_cell(root, CELL, seed=3_000_000_051)
    assert result["correct"], out[-3000:]
    (kept,) = _kept(root)

    # 2. the next process loads it: correct, and nothing of the superstep
    # was traced (what is left of tracing is init_state's and pin_state's)
    result, out = helpers.run_cell(root, CELL, seed=3_000_000_052, trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["correct"], out[-3000:]
    metrics = result["metrics"]
    assert metrics["superstep_loaded.train"]["value"] == 1
    assert metrics["superstep_build_s.train"]["value"] \
        < metrics["trace_s.train"]["value"] + 0.5
    assert _kept(root) == [kept]

    # 3. a lossy program under the honest key: itself not correct
    os.unlink(kept)
    result, out = helpers.run_cell(root, CELL, seed=3_000_000_053,
                                   prelude=LOSSY_PROGRAM)
    assert not result["correct"]
    assert _kept(root) == [kept]            # the same name: the same key

    # 4. an honest run loads the lossy executable, and is refused
    result, out = helpers.run_cell(root, CELL, seed=3_000_000_054, trace=True,
                                   prelude=USE_RECORDED_TRACE)
    assert result["metrics"]["superstep_loaded.train"]["value"] == 1
    assert not result["correct"]
    assert "<-- OUT" in out or "NOT CORRECT" in out, out[-3000:]
