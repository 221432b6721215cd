"""`endpoints-10k-warm` (ISSUE 33): a retrain from the last one's Adam state
on a corpus whose live call paths moved, held on the CPU at toy widths to
the plain reference ACROSS the restage, with the control that shows the
check bite, the restage's executable count, the `stale` and `visited`
kinds of the optimizer-rows gauge, the stage span, the pair generator and
the `off_table` scope.

On the chip the benchmark's cell `tenk-retrain-drift` makes comparison (i)
at the configuration's own widths in bfloat16 (chipbench/limits/); here it
is float32 at toy widths, through the runner's own functions.  No number
of this file is a device number.
"""

import functools

import jax
import numpy as np
import pytest

from chipbench import run as harness
from chipbench.generators import corpus_pair
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train as runner
from chipbench.runners import train_warm
from deeprest_tpu.config import Config, ModelConfig, TrainConfig
from deeprest_tpu.models.qrnn import MASKED_PARAM_NAMES
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.ops import scopes
from deeprest_tpu.ops.densify import compact_table
from deeprest_tpu.train import Trainer
from deeprest_tpu.train import trainer as trainer_module

RESOURCES = ["cpu", "memory", "write-iops", "write-tp", "usage"]
QUANTILES = (0.05, 0.5, 0.95)
SEED = 3_000_000_033           # as large as the driver's

# 2 components x 5 resources over 512 hashed call paths, 16 of them hot: on
# the CPU the feed takes the compact form with a table of 128, float32
E, F, H, W, B, HOT = 10, 512, 8, 6, 4, 16
DIMS = (E, F, H, len(QUANTILES))
# how many of the 16 hot paths keep their column from corpus A to corpus B
CASES = {"shared": 12, "same-table": 16, "disjoint": 0}

# Program and reference both compute in float32 here, the reference at
# `highest`: what is left is the order of the sums.  Read at this size over
# the three cases: at most 1.9e-7, 8.2e-7 and 6.2e-7.  The limits leave ten
# times that and no more: with the off-table pass left out `delta_norm_gap`
# reads 2e-2 to 3e-1 at a w_ih leaf (ten thousand times its limit).
TOLERANCE = {"loss_rel_gap": 2e-6, "grad_norm_gap": 1e-5,
             "delta_norm_gap": 1e-5}


class _Context:
    """What the runner's functions ask of ``run.Context``."""

    def __init__(self):
        self.compiles = harness.Compiles()
        self.logged = []

    def log(self, *parts):
        self.logged.append(parts)

    def memory_peak_bytes(self):
        return 0


def _gauge(kinds=("stale", "updated", "total")):
    rows = REGISTRY.get("deeprest_train_optimizer_rows")
    return {k: rows.value(kind=k) for k in kinds}


def _hot(raw):
    return np.flatnonzero(raw["traffic"].any(axis=0))


@functools.lru_cache(maxsize=None)
def _crossing(carried: int, skip_the_pass: bool = False):
    """The runner's phases 1 to 3 and 6 at the small size: one step on
    corpus A, ``stage_dataset(B)``, two steps on B, the reference's three
    steps on those batches; then one epoch on B for the gauge."""
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), sparse_feed=True,
                       sparse_nnz_cap=8, steps_per_superstep=8,
                       log_every_steps=0)
    mcfg = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                       quantiles=QUANTILES, dropout_rate=0.5,
                       compute_dtype="float32")
    pair = corpus_pair.generate(
        {"buckets": 400, "hot_paths": HOT, "nnz_lo": 2, "nnz_hi": 6,
         "day": 100, "resources": RESOURCES, "carried_paths": carried}, SEED,
        {"feature_dim": F, "num_metrics": E})
    bundles = [train_warm.dataset(pair[k], tcfg, F)
               for k in ("prior", "current")]
    starts = runner.check_starts(pair["current"], tcfg, SEED, bundles[1])
    rule = trainer_module.moments_off_table_are_zero
    try:
        if skip_the_pass:
            # the control: the loop's trip count forced to 0, from outside
            from chipbench.tests import control_on_chip_warm
            control_on_chip_warm.without_the_off_table_pass()
        trainer = Trainer(Config(model=mcfg, train=tcfg), F,
                          bundles[1].metric_names)
        ctx = _Context()
        key = jax.random.PRNGKey(tcfg.seed)
        state = train_warm.seeded_state(ctx, trainer, bundles[1], key, DIMS)
        fresh = int(trainer._stale_rows(
            state.opt_state, trainer.stage_dataset(bundles[0])[0].live))
        state, staged, program, compiled = train_warm.checked_steps(
            ctx, trainer, state, bundles, starts, key, DIMS)
    finally:
        trainer_module.moments_off_table_are_zero = rule
    executables = trainer._superstep._cache_size()
    # rows of a w_ih leaf that carry a moment and that B's table leaves out
    moment = np.zeros(F, bool)
    for name in MASKED_PARAM_NAMES:
        for tree in (state.opt_state[0].mu, state.opt_state[0].nu):
            moment |= np.asarray(tree[name] != 0).any(axis=(0, 2))
    moment[np.asarray(staged[0].live)] = False
    trainer.train_epoch(state, bundles[1], np.random.default_rng(0),
                        staged=staged)
    reference = ref.train_three_steps(
        ref.init_params(key, *DIMS),
        runner.check_batches(pair["prior"], tcfg, starts[:1])
        + runner.check_batches(pair["current"], tcfg, starts[1:]),
        tcfg.seed, QUANTILES, 0.5, "f32")
    restage = next(parts for parts in ctx.logged if parts[0] == "restage")
    return {"gaps": runner.compare(program, reference), "program": program,
            "reference": reference, "compiled": compiled,
            "executables": executables, "stale_before": fresh,
            "carrying": int(moment.sum()), "gauge": _gauge(),
            "visited": _gauge(("visited",))["visited"],
            "tags": restage[1], "stage_seconds": restage[2],
            "retired": np.setdiff1d(_hot(pair["prior"]),
                                    _hot(pair["current"])).size}


# -- (i) across the restage, the compact superstep is the reference ----------


@pytest.mark.parametrize("number", sorted(TOLERANCE))
@pytest.mark.parametrize("case", sorted(CASES))
def test_three_steps_across_the_restage_against_the_reference(case, number):
    out = _crossing(CASES[case])
    assert out["gaps"][number] <= TOLERANCE[number], out["gaps"]
    if number != "loss_rel_gap":
        key = number[:-len("_gap")]             # leaf by leaf, not the worst
        want = out["reference"][key]
        median = float(np.median(list(want.values())))
        for leaf, norm in want.items():
            assert (abs(out["program"][key][leaf] - norm)
                    <= TOLERANCE[number] * max(norm, median)), leaf
    assert out["program"]["steps_counted"] == runner.STEPS_CHECKED


# -- (ii) the control: the check bites ----------------------------------------


@pytest.mark.parametrize("case", ["shared", "disjoint"])
def test_without_the_off_table_pass_the_w_ih_leaves_fail(case):
    """The same three steps with the loop's trip count forced to 0: the
    rows the restage retired stop where step one left them.  Losses and
    the first gradient are the sound run's; the w_ih leaves' change is
    not the reference's."""
    gaps = _crossing(CASES[case], skip_the_pass=True)["gaps"]
    assert gaps["delta_norm_gap"] > 1000 * TOLERANCE["delta_norm_gap"], gaps
    assert gaps["delta_norm_gap_leaf"] in MASKED_PARAM_NAMES
    assert gaps["loss_rel_gap"] <= TOLERANCE["loss_rel_gap"]
    assert gaps["grad_norm_gap"] <= TOLERANCE["grad_norm_gap"]


# -- (iii) the restage reuses the one executable ------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_restage_compiles_nothing_at_an_unchanged_width(case):
    out = _crossing(CASES[case])
    assert out["compiled"] == 0 and out["executables"] == 1, out
    tags = out["tags"]
    assert tags["restage"] is True and tags["width"] == 128
    # rows of the table, pad slots among them: a retired path whose
    # column is one of B's lowest dead columns stays in the table as a pad
    assert out["retired"] == HOT - CASES[case]
    assert out["carrying"] <= tags["left"] == tags["entered"] \
        <= out["retired"]
    assert out["stage_seconds"][""] > 0


def test_a_wider_table_is_another_executable_and_says_so():
    """The probe (iii) reads does move when the shape does: a corpus whose
    live set needs the next table width adds one executable."""
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       sparse_feed=True, sparse_nnz_cap=8,
                       steps_per_superstep=8, log_every_steps=0)
    mcfg = ModelConfig(feature_dim=2048, num_metrics=E, hidden_size=H,
                       quantiles=QUANTILES, compute_dtype="float32")
    params = {"buckets": 400, "hot_paths": 16, "nnz_lo": 2, "nnz_hi": 6,
              "day": 100, "resources": RESOURCES, "carried_paths": 16}
    model = {"feature_dim": 2048, "num_metrics": E}
    narrow = corpus_pair.generate(params, 5, model)["prior"]
    wide = corpus_pair.generate(
        {**params, "hot_paths": 200, "carried_paths": 200, "nnz_hi": 8,
         "buckets": 600}, 5, model)["prior"]
    bundles = [train_warm.dataset(raw, tcfg, 2048) for raw in (narrow, wide)]
    trainer = Trainer(Config(model=mcfg, train=tcfg), 2048,
                      bundles[0].metric_names)
    state = trainer.init_state(trainer.sample_input(bundles[0]))
    rng = np.random.default_rng(0)
    state, _ = trainer.train_epoch(state, bundles[0], rng,
                                   staged=trainer.stage_dataset(bundles[0]))
    before = trainer._superstep._cache_size()
    staged = trainer.stage_dataset(bundles[1])
    assert staged[0].width == 256
    trainer.train_epoch(state, bundles[1], rng, staged=staged)
    assert (before, trainer._superstep._cache_size()) == (1, 2)


# -- (iv) the `stale` kind -----------------------------------------------------


@pytest.mark.parametrize("case", sorted(CASES))
def test_stale_rows_are_the_retired_rows_that_carry_a_moment(case):
    out = _crossing(CASES[case])
    assert out["stale_before"] == 0            # a state from init_state
    assert out["gauge"]["stale"] == out["carrying"] <= out["retired"]
    if CASES[case] == HOT:                     # B's table is A's: row-wise
        assert out["gauge"] == {"stale": 0, "updated": 128, "total": F}
    else:                                      # Adam over all F rows
        assert out["carrying"] > 0
        assert out["gauge"]["updated"] == out["gauge"]["total"] == F


@pytest.mark.parametrize("case", sorted(CASES))
def test_visited_is_the_table_and_the_stale_rows_to_the_chunk(case):
    """What the epoch's dispatches wrote of each w_ih leaf (ISSUE 34): the
    table's rows, and one chunk for the few retired rows that carry a
    moment, where ``updated`` still says F: the step IS Adam over all F
    rows, the other rows being fixed points of it."""
    out = _crossing(CASES[case])
    chunk = trainer_module._CHUNK
    assert 0 <= out["carrying"] <= chunk < F - 128
    assert out["visited"] == 128 + (chunk if out["carrying"] else 0)


# -- (v) the pair generator ---------------------------------------------------


@pytest.mark.parametrize("seed", [5, SEED, 2 ** 31 + 7])
def test_corpus_pair_moves_exactly_the_paths_it_says(seed):
    f, hot, carried = 2048, 256, 192
    params = {"buckets": 2016, "hot_paths": hot, "nnz_lo": 4, "nnz_hi": 32,
              "day": 288, "resources": RESOURCES, "carried_paths": carried}
    pair = corpus_pair.generate(params, seed,
                                {"feature_dim": f, "num_metrics": E})
    live = {k: _hot(raw) for k, raw in pair.items()}
    assert len(np.intersect1d(live["prior"], live["current"])) == carried
    for k, raw in pair.items():
        # exactly `hot` wide: no pad slot, so both take one executable
        assert len(compact_table(live[k].astype(np.int32), f)) == hot
        # the same multiset of row widths whatever the seed
        widths = np.sort((raw["traffic"] > 0).sum(axis=1))
        assert np.array_equal(widths, np.sort(4 + np.arange(2016) % 28))
        assert list(raw["resources"]) == list(pair["prior"]["resources"])
    # one application: a carried path loads the same component in both
    # weeks, so the weeks' cpu series follow their own traffic alike
    assert not np.array_equal(pair["prior"]["traffic"],
                              pair["current"]["traffic"])
    again = corpus_pair.generate(params, seed,
                                 {"feature_dim": f, "num_metrics": E})
    assert np.array_equal(again["current"]["traffic"],
                          pair["current"]["traffic"])


# -- (vi) the scope ------------------------------------------------------------


def test_off_table_names_the_pass_in_the_compact_superstep_only():
    from test_sparse_adam import _dense_feed, _plan, _setup

    assert scopes.OFF_TABLE in scopes.STEP_SCOPES

    def lowered(build):
        trainer, bundle, staged = build()
        state = trainer.init_state(trainer.sample_input(bundle), seed=1)
        return trainer._superstep.lower(
            state, *staged, *_plan(trainer, bundle, 2)[2],
            0).as_text(debug_info=True)

    compact, dense = lowered(_setup), lowered(_dense_feed)
    assert f"/{scopes.OFF_TABLE}/" in compact
    assert f"/{scopes.OFF_TABLE}/" not in dense
    assert f"/{scopes.OPTIMIZER}/" in compact and \
        f"/{scopes.OPTIMIZER}/" in dense
