"""The control of `correct`, kept at a size a test run can hold: the
reference computed in the nearest precision below the configuration's, put
in the program's place, has to fail the cell's own limits.  On the chip, at
the cell's own size: ``chipbench/tests/control_on_chip.py`` (PERF.md has the
readings)."""

import json
import os

import jax
import pytest

from chipbench.generators import corpus
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train
from chipbench.tests import helpers

LIMITS = os.path.join(helpers.CHIPBENCH, "limits")
RESOURCES = helpers.RESOURCES
E, F, H, Q, W, B = 10, 256, 32, 3, 60, 8
MODEL = {"feature_dim": F, "num_metrics": E}
QUANTILES = (0.05, 0.5, 0.95)


def _limits(cell):
    with open(os.path.join(LIMITS, cell + ".json")) as fh:
        return json.load(fh)["limits"]


class _Train:           # the TrainConfig fields the check's rows read
    window_size, batch_size, train_split = W, B, 0.4


@pytest.mark.parametrize("seed", [1, 2, 3_000_000_007])
def test_fp8_reference_fails_the_train_cells_limits(seed):
    raw = corpus.generate({"buckets": 1200, "hot_paths": 64, "nnz_lo": 4,
                           "nnz_hi": 32, "resources": RESOURCES}, seed, MODEL)
    batches = train.check_batches(raw, _Train,
                                  train.check_starts(raw, _Train, seed))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    runs = {p: ref.train_three_steps(ref.init_params(key, E, F, H, Q),
                                     batches, 7, QUANTILES, 0.5, p)
            for p in ("f32", "bf16", "fp8")}
    limits = _limits("tenk-train-sparse")
    control = train.compare(runs["fp8"], runs["f32"])
    assert any(control[k] > 3 * limits[k] for k in limits), control
    stated = train.compare(runs["bf16"], runs["f32"])
    assert all(stated[k] <= limits[k] for k in limits), stated
