"""`endpoints-10k-live4k-warm` (ISSUE 40): one trainer staged N times, a
quarter of its table's columns moved at each release, held on the CPU at
toy widths to the plain reference ACROSS every restage, with the
bookkeeping the deployment adds: one executable, the stale rows growing
release by release, the gauge's kinds ``bound`` and ``trips``, ``visited``,
the stage span's ``nth`` / ``left`` / ``entered``, the stagings counter,
both sides of ``off_table_bound``, and the ``set-up:`` line.

On the chip the benchmark's cell `tenk-retrain-live4k` makes the comparison
at the configuration's own widths in bfloat16 over six weeks
(chipbench/limits/); here it is float32 at toy widths, through the runner's
own functions.  No number of this file is a device number.
"""

import functools
import re

import jax
import numpy as np
import pytest

from chipbench import run as harness
from chipbench.generators import corpus_pair, corpus_weeks
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train as runner
from chipbench.runners import train_weeks
from deeprest_tpu.config import Config, ModelConfig, TrainConfig
from deeprest_tpu.models.qrnn import MASKED_PARAM_NAMES
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.train import Trainer
from deeprest_tpu.train import trainer as trainer_module

RESOURCES = ["cpu", "memory", "write-iops", "write-tp", "usage"]
QUANTILES = (0.05, 0.5, 0.95)
SEED = 3_000_000_040           # as large as the driver's

# 2 components x 5 resources over 512 hashed call paths, 128 of them hot
# (24 to 31 a bucket): on the CPU the feed takes the compact form and the
# table IS the live set, so a release that moves a quarter of the paths
# retires 32 rows and pads nothing
E, F, H, W, B, HOT, CARRIED = 10, 512, 8, 6, 4, 128, 96
MOVED = HOT - CARRIED
DIMS = (E, F, H, len(QUANTILES))
PARAMS = {"buckets": 400, "hot_paths": HOT, "nnz_lo": 24, "nnz_hi": 32,
          "day": 100, "resources": RESOURCES, "carried_paths": CARRIED}
MODEL = {"feature_dim": F, "num_metrics": E}

# float32 on both sides, the reference at `highest`: what is left is the
# order of the sums, over N + 1 steps (tests/test_warm_retrain.py's, which
# read at most 8.2e-7 over three)
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


def _hot(raw):
    return np.flatnonzero(raw["traffic"].any(axis=0))


def _touched(raw, starts) -> np.ndarray:
    """[F] booleans: the columns with a count in the windows at ``starts``
    (the rows of a w_ih leaf that a step on them gives a gradient)."""
    rows = np.unique((np.ravel(starts)[:, None] + np.arange(W)[None]).ravel())
    return raw["traffic"][rows].any(axis=0)


@functools.lru_cache(maxsize=None)
def _life(weeks: int, steps_per_superstep: int = 8):
    """The runner's phases 1 to 3 and 6 at the small size: a step on each
    prior week, two on the last, a restage between, the reference's steps
    on those batches; then one epoch on the last week for the gauge."""
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), sparse_feed=True,
                       sparse_nnz_cap=32,
                       steps_per_superstep=steps_per_superstep,
                       log_every_steps=0)
    mcfg = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                       quantiles=QUANTILES, dropout_rate=0.5,
                       compute_dtype="float32")
    raws = corpus_weeks.generate({**PARAMS, "weeks": weeks}, SEED, MODEL)
    bundles, starts = train_weeks.datasets(raws, tcfg, F, SEED)
    trainer = Trainer(Config(model=mcfg, train=tcfg), F,
                      bundles[-1].metric_names)
    ctx = _Context()
    key = jax.random.PRNGKey(tcfg.seed)
    stagings_before = REGISTRY.get(obs_setup.STAGINGS).value()
    state = train_weeks.seeded_state(ctx, trainer, bundles[-1], key, DIMS)
    state, staged, program, compiled, stagings = train_weeks.checked_steps(
        ctx, trainer, state, bundles, starts, key, DIMS)
    executables = trainer._superstep._cache_size()
    # rows of a w_ih leaf that carry a moment after the checked steps
    moment = np.zeros(F, bool)
    for name in MASKED_PARAM_NAMES:
        for tree in (state.opt_state[0].mu, state.opt_state[0].nu):
            moment |= np.asarray(tree[name] != 0).any(axis=(0, 2))
    trainer.train_epoch(state, bundles[-1], np.random.default_rng(0),
                        staged=staged)
    rows = REGISTRY.get(obs_setup.OPTIMIZER_ROWS)
    table = obs_setup.setup_table()
    reference = ref.train_three_steps(
        ref.init_params(key, *DIMS),
        train_weeks.reference_batches(raws, tcfg, starts), tcfg.seed,
        QUANTILES, 0.5, "f32")
    # by the raw weeks alone: the columns retired before each week, and of
    # them those that a checked step touched while they were hot
    hot = [_hot(raw) for raw in raws]
    touched = np.zeros(F, bool)
    retired = np.zeros(F, bool)
    expected, stale_by_week = [], []
    for k, raw in enumerate(raws):
        retired[np.setdiff1d(hot[k - 1], hot[k])] |= k > 0
        expected.append(int((retired & touched).sum()))
        stale_by_week.append(int((retired & moment).sum()))
        touched |= _touched(raw, starts[:1] if k < weeks - 1 else starts[1:])
    return {"gaps": runner.compare(program, reference), "program": program,
            "reference": reference, "compiled": compiled,
            "executables": executables, "stagings": stagings,
            "counted": REGISTRY.get(obs_setup.STAGINGS).value()
            - stagings_before,
            "stale_by_week": stale_by_week,
            "expected_by_week": expected,
            "gauge": {k: rows.value(kind=k) for k in (
                "stale", "bound", "trips", "updated", "visited", "total")},
            "table": table, "line": obs_setup.format_setup(table),
            "faults": train_weeks.faults(
                _mix(weeks), {
                    "rows": train_weeks.gauge(obs_setup.OPTIMIZER_ROWS),
                    "columns": train_weeks.gauge(
                        "deeprest_train_projection_columns"),
                    "compiled_early": compiled, "compiled": 0,
                    "executables": executables, "stagings": stagings,
                    "failed": 0, "attempted": 1},
                program, weeks + 1)}


def _mix(weeks):
    ctx = _Context()
    ctx.mix = {"params": {**PARAMS, "weeks": weeks}}
    return ctx


# -- (i) across every restage, the compact superstep is the reference --------


@pytest.mark.parametrize("number", sorted(TOLERANCE))
@pytest.mark.parametrize("weeks", [2, 4])
def test_a_step_a_week_across_the_restages_against_the_reference(weeks,
                                                                 number):
    out = _life(weeks)
    assert out["gaps"][number] <= TOLERANCE[number], out["gaps"]
    if number == "loss_rel_gap":
        assert len(out["program"]["losses"]) == weeks + 1 \
            == len(out["reference"]["losses"])
    else:
        key = number[:-len("_gap")]             # leaf by leaf, not the worst
        want = out["reference"][key]
        median = float(np.median(list(want.values())))
        for leaf, norm in want.items():
            assert (abs(out["program"][key][leaf] - norm)
                    <= TOLERANCE[number] * max(norm, median)), leaf
    assert out["program"]["steps_counted"] == weeks + 1


# -- (ii) N stagings, one executable, and what each staging says --------------


@pytest.mark.parametrize("weeks", [2, 4])
def test_n_stagings_reuse_the_one_executable_and_count_themselves(weeks):
    out = _life(weeks)
    assert out["compiled"] == 0 and out["executables"] == 1, out
    assert out["counted"] == weeks
    assert [t["nth"] for t in out["stagings"]] == list(range(1, weeks + 1))
    assert [t["restage"] for t in out["stagings"]] == [False] + [True] * (
        weeks - 1)
    assert "left" not in out["stagings"][0]
    for tags in out["stagings"][1:]:
        assert tags["width"] == HOT and tags["form"] == "compact"
        assert tags["left"] == tags["entered"] == MOVED


@pytest.mark.parametrize("weeks", [2, 4])
def test_the_stale_rows_grow_by_the_moved_columns_that_got_a_gradient(weeks):
    out = _life(weeks)
    # from the program's moments | from the raw weeks and the check's rows
    assert out["stale_by_week"] == out["expected_by_week"]
    growth = np.diff(out["expected_by_week"])
    assert out["expected_by_week"][0] == 0
    assert all(0.8 * MOVED <= g <= MOVED for g in growth), growth
    gauge = out["gauge"]
    assert gauge["stale"] == out["expected_by_week"][-1]
    assert gauge["updated"] == gauge["total"] == F


@pytest.mark.parametrize("weeks", [2, 4])
def test_the_gauge_says_the_bound_the_trips_and_the_rows_visited(weeks):
    gauge = _life(weeks)["gauge"]
    stale = int(gauge["stale"])
    bound = trainer_module.off_table_bound(F, HOT, 8)
    assert gauge["bound"] == bound == 143 and stale <= bound
    assert gauge["trips"] == -(-stale // 64) == weeks // 2
    assert gauge["trips"] == trainer_module.off_table_trips(F, HOT, 8, stale)
    assert gauge["visited"] == HOT + 64 * gauge["trips"]
    assert not [what for bad, what in _life(weeks)["faults"] if bad]


# -- (iii) either side of off_table_bound --------------------------------------


@pytest.mark.parametrize("steps, by_row", [(8, True), (2, False)])
@pytest.mark.parametrize("number", sorted(TOLERANCE))
def test_either_side_of_the_bound_is_the_reference(steps, by_row, number):
    """The same four weeks under a dispatch of 8 steps (a bound of 143: the
    some 96 stale rows go row by row, two trips) and of 2 (a bound of 37:
    the loop over the whole leaves)."""
    out = _life(4, steps)
    assert out["gaps"][number] <= TOLERANCE[number], out["gaps"]
    gauge = out["gauge"]
    assert gauge["bound"] == trainer_module.off_table_bound(F, HOT, steps)
    assert (gauge["stale"] <= gauge["bound"]) is by_row
    assert gauge["trips"] == (2 if by_row else F // 64)
    assert gauge["visited"] == (HOT + 128 if by_row else F)
    over = [what for bad, what in out["faults"] if bad]
    assert (not over) if by_row else (
        len(over) == 3 and "all-rows loop" in over[0])


def test_off_table_trips_by_the_shapes_alone():
    trips = trainer_module.off_table_trips
    assert trips(10240, 4096, 50, 0) == 0
    assert trips(10240, 4096, 50, 1) == 1
    assert trips(10240, 4096, 50, 5120) == 80
    assert trainer_module.off_table_bound(10240, 4096, 50) == 6080
    assert trips(10240, 4096, 50, 6080) == 95
    assert trips(10240, 4096, 50, 6081) == 160      # every chunk of F
    assert trainer_module.rows_visited(10240, 4096, 50, 5120) == 9216
    assert trainer_module.rows_visited(10240, 4096, 50, 6144) == 10240


# -- (iv) the set-up line and profile_epoch's `setup` --------------------------


def test_the_setup_table_and_line_carry_the_pass_and_the_staging():
    out = _life(4)
    table, line = out["table"], out["line"]
    stale = int(out["gauge"]["stale"])
    assert table["off_table"] == {"stale": stale, "bound": 143, "trips": 2}
    assert table["nth"] >= 4                # the process's, not a trainer's
    assert f"off the table stale {stale}, bound 143, trips 2" in line
    assert re.search(r"stage \d+\.\d{3} s \(nth \d+\)", line), line


def test_a_registry_without_the_kinds_says_no_pass():
    """Where nothing was staged compact the table leaves the entries out,
    and the line with them."""
    from deeprest_tpu.obs import metrics

    real, metrics.REGISTRY = metrics.REGISTRY, metrics.MetricsRegistry()
    obs_setup.REGISTRY, was = metrics.REGISTRY, obs_setup.REGISTRY
    try:
        table = obs_setup.setup_table()
        assert "off_table" not in table and "nth" not in table
        assert "off the table" not in obs_setup.format_setup(table)
    finally:
        metrics.REGISTRY, obs_setup.REGISTRY = real, was


# -- (v) the generator ----------------------------------------------------------


def test_two_weeks_are_the_pair_to_the_bit():
    pair = corpus_pair.generate(PARAMS, SEED, MODEL)
    weeks = corpus_weeks.generate({**PARAMS, "weeks": 2}, SEED, MODEL)
    for mine, theirs in zip(weeks, (pair["prior"], pair["current"])):
        assert np.array_equal(mine["traffic"], theirs["traffic"])
        for name, series in theirs["resources"].items():
            assert np.array_equal(mine["resources"][name], series)


@pytest.mark.parametrize("weeks", [3, 4])
def test_a_release_moves_a_quarter_to_columns_never_hot_before(weeks):
    columns = corpus_weeks.hot_columns({**PARAMS, "weeks": weeks}, SEED, F)
    seen = set(columns[0])
    for before, after in zip(columns, columns[1:]):
        moved = np.flatnonzero(before != after)
        assert len(moved) == MOVED and len(set(after)) == HOT
        assert sorted(moved // 4) == list(range(MOVED))
        assert not seen & set(after[moved])
        seen |= set(after)
    assert len(seen) == HOT + (weeks - 1) * MOVED


def test_more_releases_than_f_has_columns_for_raises():
    with pytest.raises(ValueError, match="never hot before"):
        corpus_weeks.hot_columns({**PARAMS, "weeks": 14}, SEED, F)


# -- (vi) the cell's files ------------------------------------------------------


def _load(*parts):
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, *parts)) as fh:
        return json.load(fh)


def test_the_configuration_is_endpoints_10k_letter_for_letter():
    mine = _load("chipbench", "configs", "endpoints-10k-live4k-warm.json")
    base = _load("chipbench", "configs", "endpoints-10k.json")
    assert mine["model"] == base["model"] and mine["train"] == base["train"]
    assert mine["runners"] == ["train_weeks"]
    assert mine["reduced"] == ["chips", "corpus_days", "ingest", "checkpoint"]
    assert set(mine["reduced"]) == set(mine["reduced_why"])
    assert set(mine["assumed"]) >= {"live_paths", "carried_paths", "weeks",
                                    "prior_training"}
    assert "GUARANTEE" in mine["deployment"]
    entry = {c["name"]: c for c in _load("BENCHMARK.json")["configs"]}[
        "endpoints-10k-live4k-warm"]
    assert entry["source"] == mine["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == mine["reduced"]


def test_the_mix_is_five_releases_under_the_programs_bound():
    mix = _load("chipbench", "traffic", "weeks-live4k-drift.json")
    live = _load("chipbench", "traffic", "week-live4k.json")
    assert (mix["runner"], mix["generator"]) == ("train_weeks",
                                                 "corpus_weeks")
    assert mix["params"] == dict(live["params"], carried_paths=3072, weeks=6)
    f = _load("chipbench", "configs",
              "endpoints-10k-live4k-warm.json")["model"]["feature_dim"]
    retired = train_weeks.retired_columns(mix["params"])
    assert retired == 5120 <= trainer_module.off_table_bound(f, 4096, 50)
    # a sixth release would retire every column that is left, over the bound
    assert 6 * 1024 > trainer_module.off_table_bound(f, 4096, 50) == 6080
    assert 5120 - 1024 < train_weeks.STALE_FLOOR * retired == 4608
    corpus_weeks.hot_columns(
        {**mix["params"], "weeks": 7}, 1, f)            # the probe's last
    with pytest.raises(ValueError):
        corpus_weeks.hot_columns({**mix["params"], "weeks": 8}, 1, f)


def test_the_cell_and_its_two_metrics_are_in_the_contract():
    bench = _load("BENCHMARK.json")
    cell = {c["name"]: c for c in bench["workloads"]}["tenk-retrain-live4k"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "endpoints-10k-live4k-warm", "weeks-live4k-drift", 1)
    assert len(cell["why"]) <= 200
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("train_steps_per_s", "hbm_peak_gb", "proj_columns_pct.train",
                 "adam_rows_pct.train", "stale_rows_pct.train",
                 "restage_ms.train", "proj_dead_columns_pct.train",
                 "init_state_s.train", "compile_s.train",
                 "compilations.train", "init_state_peak_gb.train",
                 "steady_hbm_gb.train", "gru_kernel_vmem_pct.train",
                 "dropout_draws_per_step.train"):
        assert "tenk-retrain-live4k" in metrics[name]["workloads"], name
    for name, unit in (("rows_visited_pct.train", "%"),
                       ("off_table_trips_per_dispatch.train", "1/dispatch")):
        m = metrics[name]
        assert m["workloads"] == ["tenk-retrain-drift", "tenk-retrain-live4k"]
        assert (m["unit"], m["better"], m["moves"], m["source"], m["layer"]
                ) == (unit, "lower", "train_steps_per_s", "program_counter",
                      "model outside the recurrence")
        spec = _load("chipbench", "layer_metrics", name + ".json")
        assert spec["runners"] == ["train"] and spec["name"] == name
    # two of eight since ISSUE 44 (the quota: a quarter, rounded down)
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 2
    limits = _load("chipbench", "limits", "tenk-retrain-live4k.json")
    assert set(limits["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                     "delta_norm_gap"}


@pytest.mark.parametrize("kinds, visited, trips", [
    (None, None, None),                                 # no gauge at all
    ({}, None, None),                                   # never set
    ({"updated": 10240, "total": 10240, "stale": 64}, None, None),  # PR 33
    ({"updated": 10240, "total": 10240, "stale": 64, "visited": 320},
     3.125, None),                                      # the parent commit
    ({"updated": 10240, "total": 10240, "stale": 5120, "visited": 9216,
      "bound": 6080, "trips": 80}, 90.0, 80),
    ({"updated": 4096, "total": 10240, "stale": 0, "visited": 4096,
      "bound": 6080, "trips": 0}, 40.0, 0)])
def test_the_two_readers_on_a_program_with_and_without_the_kinds(
        monkeypatch, kinds, visited, trips):
    from chipbench.readers import off_table_trips, rows_visited
    from deeprest_tpu.obs import metrics

    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    if kinds is not None:
        gauge = fresh.gauge(obs_setup.OPTIMIZER_ROWS, labelnames=("kind",))
        for kind, n in kinds.items():
            gauge.set(n, kind=kind)
    assert rows_visited.visited_pct({}) == visited
    assert off_table_trips.trips({}) == trips
