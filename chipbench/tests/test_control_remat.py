"""``control_on_chip_remat.py`` changes what the reference keeps, not what
it computes: with the map over experts rematerialised the three steps give
the same numbers, and the control it reads for `tt-train-dense` still
fails the cell's limits while the stated precision passes them."""

import jax
import pytest

from chipbench.generators import corpus
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train
from chipbench.tests import control_on_chip_remat
from chipbench.tests import test_control as plain_control
from chipbench.tests.test_control import E, F, H, Q, QUANTILES


def _runs(seed, precisions):
    """test_control's three steps, on a corpus whose every path is hot."""
    raw = corpus.generate({"buckets": 1200, "hot_paths": F, "nnz_lo": 32,
                           "nnz_hi": 64,
                           "resources": plain_control.RESOURCES},
                          seed, plain_control.MODEL)
    batches = train.check_batches(
        raw, plain_control._Train,
        train.check_starts(raw, plain_control._Train, seed))
    key = jax.random.PRNGKey(seed % (2 ** 31 - 1))
    return {p: ref.train_three_steps(ref.init_params(key, E, F, H, Q),
                                     batches, 7, QUANTILES, 0.5, p)
            for p in precisions}


@pytest.fixture()
def remat(monkeypatch):
    """The wrapper's patch for one test, with the jit caches emptied on
    both sides of it so that neither form is served the other's trace."""
    monkeypatch.setattr(jax.lax, "map", jax.lax.map)     # restored after
    jax.clear_caches()
    control_on_chip_remat.remat_expert_map()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("seed", [2, 3_000_000_007])
def test_rematerialised_map_gives_the_same_numbers(seed, request):
    plain = _runs(seed, ("f32", "fp8"))
    request.getfixturevalue("remat")
    again = _runs(seed, ("f32", "fp8"))
    for p in plain:
        gaps = train.compare(again[p], plain[p])
        assert all(gaps[k] <= 1e-6 for k in
                   ("loss_rel_gap", "grad_norm_gap", "delta_norm_gap")), gaps


def test_remat_control_fails_the_cells_limits(remat):
    limits = plain_control._limits("tt-train-dense")
    runs = _runs(1, ("f32", "bf16", "fp8"))
    control = train.compare(runs["fp8"], runs["f32"])
    assert any(control[k] > 3 * limits[k] for k in limits), control
    stated = train.compare(runs["bf16"], runs["f32"])
    assert all(stated[k] <= limits[k] for k in limits), stated
