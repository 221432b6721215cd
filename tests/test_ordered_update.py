"""The update ordered by leaf IS ``tx.update`` (ISSUE 56).

`apply_gradients` (train/trainer.py) runs the first w_ih leaf's Adam on its
own sub-tree, one ``optimization_barrier`` over Adam's ``count`` and that
leaf's results, then every other leaf's, so that on the chip each
direction's layer-0 weight-gradient dot carries its own leaf's fold
backward and Adam.  Held here, on the CPU at toy widths in float32: the
three feeds' supersteps and per-step programs against the plain update
written out in tests/test_sparse_adam.py section (g) (`_plain_steps`): every
leaf of params, ``mu``, ``nu``, ``count`` and the losses after three steps.
In-process to that file's tolerance (XLA:CPU contracts Adam's ``a*b + c*d``
into FMAs its own way in each program); this file as a script in a process
whose XLA:CPU has no FMA (``--exact``) holds the same cases to the bit.  And
the trees that take the plain path.  A file of its own so that xdist gives
it a worker beside tests/test_sparse_adam.py.
"""

import dataclasses
import os
import subprocess
import sys

import pytest

from test_live_columns import F
from test_sparse_adam import (
    BUILDERS, EXACT, ULPS, _plan, _setup, ordered_update_is_the_plain_update,
)

from deeprest_tpu.config import MeshConfig
from deeprest_tpu.models.qrnn import MASKED_PARAM_NAMES
from deeprest_tpu.parallel.mesh import make_mesh
from deeprest_tpu.train import trainer as trainer_module


@pytest.mark.parametrize("form", list(BUILDERS))
def test_the_ordered_update_is_the_plain_update(form):
    ordered_update_is_the_plain_update(form)


@pytest.fixture(scope="module")
def exact_run():
    """This file as a script in a process whose XLA:CPU may use no FMA:
    the three forms with a tolerance of exactly 0."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_cpu_max_isa=SSE4_2",
           "PYTHONPATH": os.pathsep.join(
               [root, os.path.join(root, "tests")])}
    return subprocess.run([sys.executable, __file__, "--exact"], env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("form", list(BUILDERS))
def test_without_fma_contraction_the_two_updates_are_equal_in_every_bit(
        exact_run, form):
    assert f"exact {form}: equal" in exact_run.stdout, (
        exact_run.stdout[-2000:] + exact_run.stderr[-4000:])


def _barriers(lowered) -> int:
    return lowered.as_text().count("optimization_barrier")


def test_one_w_ih_leaf_and_split_carried_rows_take_the_plain_update():
    """There is something to order only between two w_ih leaves whose
    gradients are whole dots.  A unidirectional model has one, and rows
    split over a ``data`` axis get their gradient from
    `sharding.project_split_rows`' ring: both lower with the dropout mask's
    barrier alone, where the bidirectional step on one device holds the
    update's too."""
    trainer, bundle, staged = _setup()
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    plan = _plan(trainer, bundle, 2)[2]
    assert _barriers(trainer._superstep.lower(state, *staged, *plan, 0)) == 2

    one_way = trainer_module.Trainer(
        dataclasses.replace(trainer.config, model=dataclasses.replace(
            trainer.config.model, bidirectional=False)),
        F, trainer.metric_names)
    state = one_way.init_state(one_way.sample_input(bundle), seed=1)
    assert MASKED_PARAM_NAMES[1] not in state.params
    staged = one_way.stage_dataset(bundle)
    assert _barriers(one_way._superstep.lower(
        state, *staged, *_plan(one_way, bundle, 2)[2], 0)) == 1

    split, bundle, staged = _setup(
        mesh=make_mesh(MeshConfig(data=2, expert=1, model=1)))
    assert trainer_module.carried_rows_split(split.mesh, 128) == 2
    state = split.init_state(split.sample_input(bundle), seed=1)
    assert _barriers(split._superstep.lower(
        state, *staged, *_plan(split, bundle, 2)[2], 0)) == 1


if __name__ == "__main__":
    assert "--exact" in sys.argv and EXACT and ULPS == 0
    for _form in BUILDERS:
        try:
            ordered_update_is_the_plain_update(_form)
            print(f"exact {_form}: equal", flush=True)
        except AssertionError as err:
            print(f"exact {_form}: NOT equal: {err}", flush=True)
