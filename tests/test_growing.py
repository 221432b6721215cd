"""`endpoints-10k-growing` (ISSUE 54): ONE trainer whose estate outgrows its
program, held on the CPU at toy widths to the plain reference ACROSS a
change of table and the handover from the compact form to the dense one,
with the books the trainer keeps ONCE A PROGRAM: the first dispatch (span,
set-up phase, throughput), the gauges `_publish_program` sets, the
optimizer-rows kinds, the counter of programs, the stage span's `program`.

F = 512 (the rule's bound is 256): live sets of 100 / 150 / 200 / 300 call
paths pad to tables of 128, 256 and 256 and then over the bound, so one
life dispatches three supersteps, as the 10k cell's 2,048 / 4,096 / 4,096 /
dense.  On the chip the benchmark's cell `tenk-retrain-growing` makes the
comparison at the configuration's own widths in bfloat16
(chipbench/limits/); here it is float32, through the runner's own
functions.  No number of this file is a device number.
"""

import functools
import json
import os

import jax
import numpy as np
import pytest

from chipbench import run as harness
from chipbench.generators import corpus, corpus_weeks_growing
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train as runner
from chipbench.runners import train_growing
from chipbench.tests.control_on_chip_growing import losing_the_compact_life
from deeprest_tpu.config import Config, ModelConfig, TrainConfig
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.ops.densify import compact_rule
from deeprest_tpu.train import Trainer
from test_live4k import QUANTILES, RESOURCES, TOLERANCE
from test_obs_layers import _compilations, _recorded

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 3_000_000_054           # as large as the driver's
E, F, H, W, B, K = 10, 512, 8, 6, 4, 16
DIMS = (E, F, H, len(QUANTILES))
LIVE = [100, 150, 200, 300]
PROGRAMS = [("compact", 128), ("compact", 256), ("compact", 256),
            ("dense", F)]
DISTINCT = list(dict.fromkeys(PROGRAMS))
PARAMS = {"buckets": 400, "weeks": 4, "hot_paths_by_week": LIVE,
          "nnz_lo": 3, "nnz_hi": 12, "day": 100, "resources": RESOURCES}
MODEL = {"feature_dim": F, "num_metrics": E}
SUPERSTEP = "train_superstep"


class _Context:
    """What the runner's functions ask of ``run.Context``."""

    def __init__(self):
        self.compiles = harness.Compiles()
        self.device = jax.devices()[0]
        self.logged = []

    def log(self, *parts):
        self.logged.append(" ".join(map(str, parts)))

    def memory_peak_bytes(self):
        return 0


def _configs():
    tcfg = TrainConfig(batch_size=B, window_size=W, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), sparse_feed=True,
                       sparse_nnz_cap=K, steps_per_superstep=8,
                       log_every_steps=0)
    mcfg = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                       quantiles=QUANTILES, dropout_rate=0.5,
                       compute_dtype="float32")
    return mcfg, tcfg


def _drifting_columns():
    """Growth WITH drift: the schedule's counts, but a quarter of a week's
    paths move to columns never hot before at each release, so rows that
    carry moments leave the table and are stale when the dense form takes
    over."""
    rng = np.random.default_rng([SEED, 2])
    spare = list(rng.permutation(F))
    columns = [np.array([spare.pop() for _ in range(LIVE[0])])]
    for live in LIVE[1:]:
        kept = columns[-1].copy()
        moved = np.arange(0, len(kept), 4)
        kept[moved] = [spare.pop() for _ in moved]
        grown = [spare.pop() for _ in range(live - len(kept))]
        columns.append(np.concatenate([kept, grown]).astype(np.int64))
    return columns


@functools.lru_cache(maxsize=None)
def _weeks(kind: str):
    if kind == "growth":
        return corpus_weeks_growing.generate(PARAMS, SEED, MODEL)
    week = {k: v for k, v in PARAMS.items()
            if k not in ("hot_paths_by_week", "weeks")}
    columns = _drifting_columns()
    return [corpus.generate(
                {**week, "hot_paths": len(cols)},
                corpus_weeks_growing._GrowingWeek(
                    np.random.SeedSequence([SEED, i]), cols, [SEED, 3],
                    len(columns[-1])), MODEL)
            for i, cols in enumerate(columns)]


@functools.lru_cache(maxsize=None)
def _crossing(kind: str, lost: bool = False):
    """The runner's phases 1 to 3 and 6 at the small size: a step on each
    prior week, two on the last, through ``trainer._superstep`` across the
    three restages; the reference's five steps on those batches."""
    mcfg, tcfg = _configs()
    raws = _weeks(kind)
    bundles, starts = train_growing.datasets(raws, tcfg, F, SEED)
    trainer = Trainer(Config(model=mcfg, train=tcfg), F,
                      bundles[-1].metric_names)
    ctx = _Context()
    key = jax.random.PRNGKey(tcfg.seed)
    state = train_growing.seeded_state(ctx, trainer, bundles[-1], key, DIMS)
    state, _staged, numbers, life = train_growing.checked_steps(
        ctx, trainer, state, bundles, starts, key, DIMS,
        at_handover=losing_the_compact_life if lost else None)
    stale_into_dense = int(trainer._stale_rows(
        state.opt_state, np.flatnonzero(raws[-1]["traffic"].any(axis=0))))
    reference = ref.train_three_steps(
        ref.init_params(key, *DIMS),
        train_growing.reference_batches(raws, tcfg, starts), tcfg.seed,
        QUANTILES, 0.5, "f32")
    return {"gaps": runner.compare(numbers, reference), "numbers": numbers,
            "reference": reference, "life": life, "logged": ctx.logged,
            "stale_into_dense": stale_into_dense,
            "executables": trainer._superstep._cache_size()}


# -- (a), (b): the result across the programs --------------------------------


@pytest.mark.parametrize("number", sorted(TOLERANCE))
@pytest.mark.parametrize("kind", ["growth", "growth_with_drift"])
def test_five_steps_across_three_programs_against_the_reference(kind, number):
    run = _crossing(kind)
    assert [w["program"] for w in run["life"]] == PROGRAMS
    assert [w["new"] for w in run["life"]] == [True, True, False, True]
    assert run["executables"] == 3
    assert run["numbers"]["steps_counted"] == 5
    assert run["gaps"][number] <= TOLERANCE[number], (run["gaps"],
                                                      run["numbers"])


def test_drift_carries_stale_rows_into_the_dense_form():
    """The variant is what it says: rows off the last week's live set carry
    moments when the dense form takes over (whole-leaf Adam steps them),
    and pure growth carries none."""
    assert _crossing("growth_with_drift")["stale_into_dense"] > 50
    assert _crossing("growth")["stale_into_dense"] == 0


@pytest.mark.parametrize("kind", ["growth", "growth_with_drift"])
def test_a_handover_that_loses_the_compact_life_fails(kind):
    """The w_ih leaves' moments zeroed at the restage that takes the dense
    form (a fresh optimizer for the new program): losses and the first
    gradient are the sound run's, the leaves' change is not, at a w_ih
    leaf, by orders over the tolerance."""
    sound, lost = _crossing(kind)["gaps"], _crossing(kind, lost=True)["gaps"]
    assert lost["delta_norm_gap"] > 1000 * TOLERANCE["delta_norm_gap"], lost
    assert lost["delta_norm_gap"] > 100 * sound["delta_norm_gap"]
    assert lost["delta_norm_gap_leaf"] in ("gru_fwd_w_ih", "gru_bwd_w_ih")
    assert lost["grad_norm_gap"] <= TOLERANCE["grad_norm_gap"]


# -- (c), (d), (e): the books, once a program, through train_epoch -----------


def _series(name):
    metric = REGISTRY.get(name)
    return {} if metric is None else {
        tuple(dict(zip(metric.labelnames, key)).items()): value
        for key, value in metric.series().items()}


@pytest.fixture(scope="module")
def life():
    """One trainer through ``train_epoch`` on week 1, 2, 3, 4 and week 1
    again: what each epoch left in the spans, the counters and the
    gauges."""
    mcfg, tcfg = _configs()
    raws = _weeks("growth")
    bundles, _ = train_growing.datasets(raws, tcfg, F, SEED)
    trainer = Trainer(Config(model=mcfg, train=tcfg), F,
                      bundles[-1].metric_names)
    state = trainer.init_state(trainer.sample_input(bundles[-1]))
    stops = []
    stop = trainer.throughput.stop
    trainer.throughput.stop = lambda steps: (stops.append(steps), stop(steps))
    counted_before = _series(obs_setup.SUPERSTEP_PROGRAMS)
    epochs = []
    for week in (0, 1, 2, 3, 0):
        before = {phase: _compilations(program=SUPERSTEP, phase=phase)
                  for phase in ("first_dispatch", "epoch")}
        out = []

        def run():
            staged = trainer.stage_dataset(bundles[week])
            out.append(trainer.train_epoch(state, bundles[week],
                                           np.random.default_rng(week),
                                           staged=staged))

        spans = _recorded(run)
        state, loss = out[0]
        assert np.isfinite(loss)
        (stage,) = [s for s in spans if s.name == "train.stage"]
        epochs.append({
            "stage": dict(stage.tags),
            "first": [dict(s.tags) for s in spans
                      if s.name == "train.first_dispatch"
                      and s.tags["program"] == SUPERSTEP],
            "compiled": {phase: _compilations(program=SUPERSTEP, phase=phase)
                         - n for phase, n in before.items()},
            "steps": len(trainer._last_epoch_losses),
            "measured": stops[-1],
            "program_bytes": dict(_series(obs_setup.PROGRAM_BYTES)),
            "rows": {dict(k)["kind"]: int(v) for k, v in
                     _series(obs_setup.OPTIMIZER_ROWS).items()},
            "line": obs_setup.format_setup(obs_setup.setup_table()),
        })
    # (the programs another test file of this worker counted stay put)
    counted = {k: v - counted_before.get(k, 0)
               for k, v in _series(obs_setup.SUPERSTEP_PROGRAMS).items()
               if v != counted_before.get(k, 0)}
    return {"epochs": epochs, "counted": counted,
            "executables": trainer._superstep._cache_size()}


def test_each_new_program_has_one_first_dispatch_and_no_other_epoch(life):
    first = [e["first"] for e in life["epochs"]]
    assert [len(f) for f in first] == [1, 1, 0, 1, 0]
    assert [(f[0]["nth_program"], f[0]["form"], f[0]["width"])
            for f in first if f] == [(1, "compact", 128), (2, "compact", 256),
                                     (3, "dense", F)]
    assert life["executables"] == 3


def test_the_stage_span_says_new_same_or_back(life):
    stages = [e["stage"] for e in life["epochs"]]
    assert [s["program"] for s in stages] == ["new", "new", "same", "new",
                                              "back"]
    assert [(s["form"], s["width"]) for s in stages] == PROGRAMS + [
        PROGRAMS[0]]
    # left and entered hold across the change of form, both ways: the dense
    # form holds every column, so nothing leaves on the way in
    assert "left" not in stages[0]
    assert (stages[3]["left"], stages[3]["entered"]) == (0, F - 256)
    assert (stages[4]["left"], stages[4]["entered"]) == (F - 128, 0)


def test_a_new_programs_compilations_are_first_dispatch_not_epoch(life):
    compiled = [e["compiled"] for e in life["epochs"]]
    assert [c["first_dispatch"] for c in compiled] == [1, 1, 0, 1, 0]
    assert [c["epoch"] for c in compiled] == [0] * 5


def test_throughput_starts_after_each_first_dispatch(life):
    """An epoch that met a new program measures every step but its first
    dispatch's (8 of them here); the others measure all."""
    for epoch, new in zip(life["epochs"], [1, 1, 0, 1, 0]):
        assert epoch["measured"] == epoch["steps"] - 8 * new, epoch


def test_the_program_gauges_follow_the_program(life):
    """`deeprest_train_program_bytes` is read again at every change of
    program, the way back included: three distinct executables' numbers,
    the fifth epoch's the first's."""
    found = [e["program_bytes"] for e in life["epochs"]]
    assert all(found), found
    assert found[1] == found[2]             # a restage that kept the program
    assert found[0] != found[1] != found[3] != found[0]
    assert found[4] == found[0]


def test_the_optimizer_rows_follow_the_program(life):
    rows = [e["rows"] for e in life["epochs"]]
    for found, (form, width) in zip(rows, PROGRAMS + [PROGRAMS[0]]):
        assert found["total"] == F
        if form == "dense":
            # no table: the three kinds are 0, not the last table's
            assert (found["stale"], found["bound"], found["trips"]) == (
                0, 0, 0)
            assert found["updated"] == found["visited"] == F
    # pure growth: no row off a table carries a moment until the way back
    assert [r["stale"] for r in rows[:3]] == [0, 0, 0]
    assert [r["updated"] for r in rows[:3]] == [128, 256, 256]
    assert rows[2]["bound"] > 0
    # back on the table of 128 the dense epoch's rows are stale
    assert rows[4]["stale"] > 0 and rows[4]["updated"] == F


def test_the_counter_has_one_increment_a_program(life):
    assert life["counted"] == {
        (("form", form), ("width", str(width))): 1.0
        for form, width in DISTINCT}


def test_the_set_up_line_names_the_programs(life):
    lines = [e["line"] for e in life["epochs"]]
    assert "programs " not in lines[0]      # one program: nothing to say
    assert "programs 2 (compact 128, compact 256), first dispatched in " \
        in lines[1]
    assert "programs 3 (compact 128, compact 256, dense), first " \
        "dispatched in " in lines[3]
    assert lines[4].count("programs 3 (") == 1      # the way back adds none
    table = obs_setup.setup_table()
    assert [(p["nth"], p["form"], p["width"]) for p in table["programs"]] == [
        (n, *program) for n, program in enumerate(DISTINCT, start=1)]
    assert all(p["seconds"] > 0 for p in table["programs"])


# -- (f): the generator --------------------------------------------------------


def test_the_weeks_live_sets_are_nested_and_as_scheduled():
    columns = corpus_weeks_growing.hot_columns(PARAMS, SEED, F)
    assert [len(c) for c in columns] == LIVE
    for before, after in zip(columns, columns[1:]):
        assert np.array_equal(after[:len(before)], before)
        assert len(set(after)) == len(after)
    for raw, cols in zip(_weeks("growth"), columns):
        # every hot path was hit, and nothing else
        assert set(np.flatnonzero(raw["traffic"].any(axis=0))) == set(cols)
    assert [compact_rule(n, F)[0] for n in LIVE] == [128, 256, 256, 512]


def test_the_generator_is_the_seeds():
    again = corpus_weeks_growing.generate(PARAMS, SEED, MODEL)
    other = corpus_weeks_growing.generate(PARAMS, SEED + 1, MODEL)
    for mine, same, differs in zip(_weeks("growth"), again, other):
        assert np.array_equal(mine["traffic"], same["traffic"])
        assert list(mine["resources"]) == list(same["resources"])
        assert all(np.array_equal(mine["resources"][k], same["resources"][k])
                   for k in mine["resources"])
        assert not np.array_equal(mine["traffic"], differs["traffic"])


@pytest.mark.parametrize("schedule, words", [
    ([100, 150, 140, 300], "never loses a path"),
    ([100, 150, 200, F + 1], f"F is {F}"),
    ([100, 150, 200], "hot_paths_by_week"),
])
def test_the_generator_refuses_a_schedule(schedule, words):
    with pytest.raises(ValueError, match=words):
        corpus_weeks_growing.hot_columns(
            {**PARAMS, "hot_paths_by_week": schedule}, SEED, F)


# -- (g): the contract, as files -------------------------------------------------


def _load(*parts):
    with open(os.path.join(REPO, *parts)) as fh:
        return json.load(fh)


def test_the_configuration_is_endpoints_10k_warm_across_programs():
    mine = _load("chipbench", "configs", "endpoints-10k-growing.json")
    warm = _load("chipbench", "configs", "endpoints-10k-warm.json")
    wide = _load("chipbench", "configs", "endpoints-10k-live4k-warm.json")
    assert mine["model"] == warm["model"] and mine["train"] == warm["train"]
    assert mine["runners"] == ["train_growing"] and mine["chips"] == 1
    assert mine["reduced"] == warm["reduced"] == [
        "chips", "corpus_days", "ingest", "checkpoint"]
    assert set(mine) >= set(wide) | {"guarantee"}
    assert set(mine["assumed"]) >= {"live_paths_by_week", "kept_paths",
                                    "weeks", "prior_training"}
    for words in ("plain Adam on every row of every leaf",
                  "no moment is reset, zeroed, thresholded or dropped",
                  "ACROSS programs"):
        assert words in mine["guarantee"], words
    for words in ("table of 2,048", "table of 4,096", "dense form"):
        assert words in mine["deployment"], words
    ModelConfig(**dict(mine["model"],
                       quantiles=tuple(mine["model"]["quantiles"])))
    TrainConfig(**mine["train"])


def test_the_mix_is_the_issues_schedule_and_crosses_both_edges():
    mix = _load("chipbench", "traffic", "weeks-growing.json")
    assert (mix["runner"], mix["generator"]) == ("train_growing",
                                                 "corpus_weeks_growing")
    live4k = _load("chipbench", "traffic", "week-live4k.json")["params"]
    assert mix["params"] == {
        **{k: v for k, v in live4k.items() if k != "hot_paths"},
        "weeks": 4, "hot_paths_by_week": [1536, 2304, 3456, 5184]}
    f = _load("chipbench", "configs",
              "endpoints-10k-growing.json")["model"]["feature_dim"]
    assert train_growing.expected_programs(mix["params"], f) == [
        ("compact", 2048), ("compact", 4096), ("compact", 4096),
        ("dense", 10240)]


def test_the_cell_and_its_metrics_are_in_the_contract():
    bench = _load("BENCHMARK.json")
    cell = bench["workloads"][-1]
    assert cell == {**cell, "name": "tenk-retrain-growing",
                    "config": "endpoints-10k-growing",
                    "traffic": "weeks-growing", "chips": 1}
    assert len(cell["why"]) <= 200
    entry = bench["configs"][-1]
    assert (entry["name"], entry["file"], entry["reduced"]) == (
        "endpoints-10k-growing", "chipbench/configs/endpoints-10k-growing.json",
        ["chips", "corpus_days", "ingest", "checkpoint"])
    assert len(entry["source"]) <= 200 and entry["source"] == _load(
        "chipbench", "configs", "endpoints-10k-growing.json")["source"]
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    new = ("superstep_programs.train", "program_switch_s.train")
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(new[0])        # later issues append behind them
    assert names[at:at + 2] == list(new)
    for name in new:
        spec = _load("chipbench", "layer_metrics", name + ".json")
        assert metrics[name] == {
            "name": name, "unit": spec["unit"], "better": "lower",
            "source": "program_counter", "layer": "trainer and feed",
            "moves": "setup_s", "workloads": ["tenk-retrain-growing"]}
        assert spec["runners"] == ["train"]
        assert spec["reader"].split(":")[0] == "programs"
    # every list `tenk-train-alllive` is in (the dense form's window), and
    # the restage's and the two of the memory rule; appended, so last
    listed = [name for name, m in metrics.items()
              if "tenk-train-alllive" in m.get("workloads", ())]
    assert len(listed) == 20        # ISSUE 56's counter lists every cell
    for name in listed + ["restage_ms.train", "superstep_temporaries_gb.train",
                          "program_reserved_gb.train"]:
        assert metrics[name]["workloads"][-1] == "tenk-retrain-growing", name
    # PERF.md section 7: its reader says nothing of this cell on the parent
    assert "tenk-retrain-growing" not in metrics["stale_rows_pct.train"][
        "workloads"]
    limits = _load("chipbench", "limits", "tenk-retrain-growing.json")
    assert limits["cell"] == "tenk-retrain-growing"
    assert set(limits["limits"]) == {"loss_rel_gap", "grad_norm_gap",
                                     "delta_norm_gap"}


def test_the_two_readers_read_the_programs_series(monkeypatch):
    from chipbench.readers import programs
    from deeprest_tpu.obs import metrics

    fresh = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", fresh)
    # an older program: nothing, no error
    assert programs.superstep_programs({}) is None
    assert programs.program_switch_s({}) is None
    counter = fresh.counter(obs_setup.SUPERSTEP_PROGRAMS,
                            labelnames=("form", "width"))
    seconds = fresh.gauge(obs_setup.SUPERSTEP_FIRST_DISPATCH_SECONDS,
                          labelnames=("nth", "form", "width"))
    for nth, (form, width) in enumerate(DISTINCT, start=1):
        counter.inc(form=form, width=width)
        seconds.set(float(nth), nth=nth, form=form, width=width)
    assert programs.superstep_programs({}) == 3
    assert programs.program_switch_s({}) == 2.0 + 3.0


# -- (h): the runner's rule for compilations ---------------------------------


def _week(program, new, staging=0, dispatch=0):
    return {"program": program, "new": new, "compiled_staging": staging,
            "compiled_dispatch": dispatch, "tags": {}}


def _faults(life, booked=(("first_dispatch", 3),), **over):
    booked = dict(booked)

    class Ctx:
        mix = {"params": PARAMS}
        config = {"model": MODEL}

    out = {"life": life, "executables": 3, "compiled_warm_up": 0,
           "compiled": 0, "failed": 0, "attempted": 40,
           "rows": {"stale": 0, "updated": F},
           "columns": {"total": F, "contracted": F},
           "counted": {p: 1 for p in DISTINCT}, **over}
    # a registry of its own: what other tests of this process booked to
    # the phase `epoch` is not this run's
    from deeprest_tpu.obs import metrics

    real, metrics.REGISTRY = metrics.REGISTRY, metrics.MetricsRegistry()
    try:
        for phase, n in booked.items():
            metrics.REGISTRY.counter(
                obs_setup.COMPILATIONS,
                labelnames=("program", "phase", "cache")).inc(
                    n, program=SUPERSTEP, phase=phase, cache="miss")
        return [what for bad, what in train_growing.faults(
            Ctx, out, {"steps_counted": 5}, 5) if bad]
    finally:
        metrics.REGISTRY = real


def test_a_compilation_at_a_new_program_passes():
    life = [_week(p, new, dispatch=int(new)) for p, new in
            zip(PROGRAMS, [True, True, False, True])]
    assert _faults(life) == []
    # an older program keeps no counter and is held to its executables
    assert _faults(life, counted=None) == []


@pytest.mark.parametrize("over, words", [
    ({"life": 2}, "compilations in week 3, whose program"),
    ({"compiled": 1}, "1 compilations inside the window"),
    ({"compiled_warm_up": 2}, "between the warm-up epoch's first and last"),
    ({"executables": 2}, "2 executables of the superstep for the 3"),
    ({"counted": {("compact", 128): 1, ("dense", F): 2}},
     "the program counted the first dispatches"),
    ({"life": "early"}, "the life staged the programs"),
    ({"rows": {"stale": 7, "updated": F}}, "7 stale rows"),
    ({"booked": {"first_dispatch": 1, "epoch": 2}},
     "booked 2 compilations of train_superstep to the set-up phase `epoch`"),
])
def test_what_fails_a_run_of_the_growing_cell(over, words):
    life = [_week(p, new, dispatch=int(new)) for p, new in
            zip(PROGRAMS, [True, True, False, True])]
    if over.get("life") == 2:
        life[2] = _week(PROGRAMS[2], False, staging=1)
        over = {}
    elif over.get("life") == "early":
        life[2] = _week(("dense", F), True, dispatch=1)
        over = {}
    found = _faults(life, **over)
    assert len(found) >= 1 and any(words in what for what in found), found
