"""The layer table of deeprest_tpu/obs/profiler.py (ISSUE 24): the named
scopes inside the compiled train step, the program's spans on the
profiler's clock, the trainer's epoch phases as spans and counters, and the
trace reduction held to the yardstick's on a recorded v5e trace.

Everything here runs on the CPU: it checks names, counts, arithmetic and
plumbing.  No number of it is a device number.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import make_series_buckets

from deeprest_tpu import obs
from deeprest_tpu.config import Config, FeaturizeConfig, ModelConfig, TrainConfig
from deeprest_tpu.data.featurize import featurize_buckets
from deeprest_tpu.models.qrnn import MASKED_PARAM_NAMES
from deeprest_tpu.obs import profiler
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.ops import scopes
from deeprest_tpu.train import Trainer, prepare_dataset
from deeprest_tpu.train.trainer import EPOCH_PHASES, INIT_PHASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(REPO, "chipbench", "tests", "data",
                        "recorded_v5e.xplane.pb")
BATCH, LOG_EVERY, SUPERSTEP = 8, 4, 3
NAMES = frozenset(scopes.STEP_SCOPES + scopes.KERNELS)
# a superstep on a base without a compact table has no off-table pass, and
# one of one microbatch an update accumulates nothing (tests/test_accum8.py
# holds `accumulate` to the superstep of several)
NO_TABLE = tuple(s for s in scopes.STEP_SCOPES
                 if s not in (scopes.OFF_TABLE, scopes.ACCUMULATE))


def _staged_trainer(featurize: FeaturizeConfig):
    cfg = Config(
        model=ModelConfig(hidden_size=8, dropout_rate=0.1),
        train=TrainConfig(batch_size=BATCH, window_size=10, seed=0,
                          log_every_steps=LOG_EVERY,
                          steps_per_superstep=SUPERSTEP,
                          device_data="always", sparse_feed=True,
                          sparse_nnz_cap=48))
    data = featurize_buckets(make_series_buckets(200, seed=5), featurize)
    bundle = prepare_dataset(data, cfg.train)
    trainer = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state = trainer.init_state(trainer.sample_input(bundle))
    staged = trainer.stage_dataset(bundle)
    assert staged is not None
    return {"trainer": trainer, "bundle": bundle, "state": state,
            "staged": staged, "rng": np.random.default_rng(0)}


@pytest.fixture(scope="module")
def tiny():
    """A sparse-feed trainer, its corpus staged: the path the benchmark's
    cell takes (gather + densify inside the superstep).  Every call path
    of the corpus is a column, so the feed keeps the dense form."""
    return _staged_trainer(FeaturizeConfig(round_to=8))


@pytest.fixture(scope="module")
def tiny_compact():
    """The same corpus hashed into 512 columns: the live paths are few of
    F and the feed takes the compact form (ops/densify.py), as the
    benchmark's cell does."""
    out = _staged_trainer(FeaturizeConfig(hash_features=True, capacity=512))
    assert out["staged"][0].live is not None
    return out


def _epoch(tiny):
    tiny["state"], loss = tiny["trainer"].train_epoch(
        tiny["state"], tiny["bundle"], tiny["rng"], staged=tiny["staged"])
    return loss


# -- (a) names inside the compiled step -------------------------------------


def test_scope_map_of_the_compiled_superstep_holds_every_scope(tiny):
    trainer = tiny["trainer"]
    _epoch(tiny)
    # the text is of what the epoch dispatched: the driver's own choice of
    # program, on its own arguments
    program, args = trainer._dispatched
    assert program is trainer._superstep and args[:2] == tiny["staged"]
    text = trainer._dispatched_program_text(tiny["state"])
    assert profiler.module_name(text) == "jit_train_superstep"
    table = profiler.scope_table(text, NAMES)
    found = set(table.values())
    for scope in NO_TABLE:
        assert (scope, "fwd") in found, scope
    assert (scopes.OFF_TABLE, "fwd") not in found
    for scope in ("mask", "in_proj", "recurrence", "mixing", "heads", "loss"):
        assert (scope, "bwd") in found, scope
    # Of the instructions JAX named (the compiler's own layout copies
    # carry no op_name and no scope can claim them), under a tenth fall
    # to `other`: the superstep loop's bookkeeping around the step.
    computations, inner, _ = profiler._parse_hlo(text)
    named = [profiler._scope_of(op_name, NAMES)[0]
             for comp, instructions in computations.items()
             if comp not in inner
             for _, opcode, op_name, _ in instructions
             if op_name and opcode not in profiler._NO_EVENT]
    assert named.count(profiler.OTHER) < 0.10 * len(named), (
        named.count(profiler.OTHER), len(named), len(table))
    # a fusion that holds another scope's work says so
    fused = profiler.fused_scopes(text, NAMES)
    assert all(name in table and profiler.OTHER not in held
               for name, held in fused.items())


@pytest.mark.parametrize("op_name, expected", [
    ("jit(step)/transpose(jvp(QuantileGRU))/in_proj/btf,efg->etbg/dot_general",
     ("in_proj", "bwd")),
    ("jit(step)/while/body/jvp(loss)/mul", ("loss", "fwd")),
    ("jit(step)/transpose(jvp(loss))/mul", ("loss", "bwd")),
    ("jit(step)/gather/densify/scatter-add", ("densify", "fwd")),
    ("jit(step)/jvp(QuantileGRU)/recurrence/gru_kernel_fwd/pallas_call",
     ("gru_kernel_fwd", "fwd")),
    # the primitive at the end is not a scope, though it shares the name
    ("jit(step)/jvp(Embed)/gather", ("other", "-")),
    # under no name it was given there is ONE `other`, whatever the pass
    ("jit(step)/transpose(jvp(Embed))/mul", ("other", "-")),
    ("", ("other", "-")),
])
def test_scope_of_an_op_name(op_name, expected):
    assert profiler._scope_of(op_name, NAMES) == expected


def test_the_profiler_knows_no_name_it_is_not_given():
    op_name = "jit(step)/transpose(jvp(Model))/in_proj/dot_general"
    assert profiler._scope_of(op_name, {"Model"}) == ("Model", "bwd")
    assert profiler._scope_of(op_name, ()) == ("other", "-")


# -- (b) two reducers, one answer -------------------------------------------


def test_layer_table_agrees_with_the_yardstick_on_the_recorded_trace():
    sys.path.insert(0, REPO)
    from chipbench import trace_reduce

    theirs = trace_reduce.reduce_file(RECORDED)
    ours = profiler.layer_table_of(profiler.read_planes(RECORDED), steps=4)
    for key in ("busy_s", "window_s", "kernel_s"):
        assert ours[key] == pytest.approx(theirs[key], rel=1e-12), key
    assert ours["chips"] == theirs["chips"] == 1
    assert sum(r["seconds"] for r in ours["rows"]) == pytest.approx(
        ours["busy_s"], rel=1e-9)
    # no map: one `other` row and the kernels under the names the trace
    # has for them (recorded before the pallas_calls had a name=, so the
    # flax module's), which is the yardstick's kernel time again; no
    # program span was on the trace
    rows = {r["scope"]: r for r in ours["rows"]}
    assert set(rows) > {profiler.OTHER} and all(
        "QuantileGRU" in k for k in set(rows) - {profiler.OTHER}), set(rows)
    assert sum(r["seconds"] for k, r in rows.items()
               if k != profiler.OTHER) == pytest.approx(theirs["kernel_s"])
    assert rows[profiler.OTHER]["ms_per_step"] == pytest.approx(
        1e3 * (ours["busy_s"] - ours["kernel_s"]) / 4)
    assert [g["span"] for g in ours["idle_gaps"]] == [profiler.UNATTRIBUTED]
    assert ours["idle_gaps"][0]["seconds"] == pytest.approx(
        ours["window_s"] - ours["busy_s"], rel=1e-9)


# -- (c) spans on the profiler's clock --------------------------------------


def test_enabled_span_enters_a_trace_annotation(monkeypatch):
    import jax.profiler

    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    rec = obs.SpanRecorder(capacity=4, enabled=True)
    with rec.span("batch.dispatch", component="deeprest-batcher"):
        assert seen == [("enter", "deeprest-batcher/batch.dispatch")]
    assert seen[-1] == ("exit", "deeprest-batcher/batch.dispatch")
    rec.enabled = False
    with rec.span("batch.dispatch", component="deeprest-batcher"):
        pass
    assert len(seen) == 2


def test_disabled_span_does_not_import_jax():
    code = ("import sys\n"
            "from deeprest_tpu import obs\n"
            "from deeprest_tpu.obs import profiler, phases\n"
            "with obs.span('x', component='deeprest-test'):\n"
            "    pass\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n"
            "obs.configure(enabled=True)\n"
            "with obs.span('x', component='deeprest-test'):\n"
            "    pass\n"
            "assert 'jax' in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]


# -- (d) the epoch's phases as counters -------------------------------------


def _phase_values(metric_name):
    metric = REGISTRY.get(metric_name)
    return {p: metric.value(phase=p) for p in EPOCH_PHASES}


def _totals():
    readbacks = REGISTRY.get("deeprest_train_readbacks_total").series()
    return {
        "epochs": REGISTRY.get("deeprest_train_epochs_total").value(),
        "dispatches": REGISTRY.get(
            "deeprest_train_superstep_dispatches_total").value(),
        "readbacks": sum(readbacks.values()),
    }


def test_two_epochs_fill_the_phase_counters(tiny):
    trainer, bundle = tiny["trainer"], tiny["bundle"]
    num_steps = -(-bundle.num_train_windows // BATCH)
    chunks = -(-num_steps // SUPERSTEP)
    assert trainer._superstep_len(num_steps) == SUPERSTEP and chunks > 2
    _epoch(tiny)        # compiles; its seconds must not stay in the gauge
    start_step = trainer._global_step
    before = _totals()
    first = _phase_values("deeprest_train_last_epoch_phase_seconds")
    _epoch(tiny)
    after = _totals()
    last = _phase_values("deeprest_train_last_epoch_phase_seconds")

    assert set(last) == set(EPOCH_PHASES)
    assert all(v > 0 for v in last.values()), last
    assert last != first
    assert after["epochs"] - before["epochs"] == 1
    assert after["dispatches"] - before["dispatches"] == chunks
    # one readback per chunk in which a multiple of LOG_EVERY falls, and
    # the epoch's loss readback
    bounds = [min(start_step + (c + 1) * SUPERSTEP, start_step + num_steps)
              for c in range(chunks)]
    crossings = sum(lo // LOG_EVERY != hi // LOG_EVERY
                    for lo, hi in zip([start_step] + bounds, bounds))
    assert after["readbacks"] - before["readbacks"] == crossings + 1


def _recorded(run):
    """``run()`` with the recorder on; the spans it left."""
    prev = obs.RECORDER.enabled
    obs.RECORDER.clear()
    obs.RECORDER.enabled = True
    try:
        run()
    finally:
        obs.RECORDER.enabled = prev
    return obs.RECORDER.drain()


def test_epoch_spans_are_children_of_one_epoch_span(tiny):
    spans = [s for s in _recorded(lambda: _epoch(tiny))
             if s.component == "deeprest-trainer"]
    epoch = [s for s in spans if s.name == "train.epoch"]
    assert len(epoch) == 1
    phases = [s for s in spans
              if s.name not in ("train.epoch", "train.first_dispatch")]
    assert {s.name for s in phases} == set(EPOCH_PHASES)
    assert all(s.parent_id == epoch[0].span_id for s in phases)


def test_nested_phase_is_timed_exclusively_and_a_failed_unit_publishes_nothing(
        hand_clock):
    now = hand_clock
    reg = obs.MetricsRegistry()
    clock = obs.PhaseClock(
        "unit", "deeprest-test", ("outer", "inner", "unused"),
        last_seconds=reg.gauge("s_last", labelnames=("phase",)),
        units_total=reg.counter("units"))
    with clock.unit() as phase:
        with phase("outer"):
            now[0] += 1.0
            with phase("inner"):
                now[0] += 2.0
            now[0] += 0.5
    assert clock.last_seconds.series() == {
        ("outer",): 1.5, ("inner",): 2.0, ("unused",): 0.0}
    with pytest.raises(ValueError):
        with clock.unit() as phase:
            with phase("nope"):
                pass
    assert clock.units_total.value() == 1


def test_the_compact_superstep_carries_every_scope(tiny_compact):
    """The compact form (ISSUE 25) keeps every name, `densify`, `mask` and
    `in_proj` among them, so `profile_epoch`'s table keeps its rows.  One
    lowering of the superstep on the staged arguments; nothing runs."""
    from deeprest_tpu.parallel.distributed import stage_plan

    trainer, bundle = tiny_compact["trainer"], tiny_compact["bundle"]
    starts, weights, _ = trainer._epoch_plan(
        bundle.num_train_windows, np.random.default_rng(0), SUPERSTEP)
    lowered = trainer._superstep.lower(
        tiny_compact["state"], *tiny_compact["staged"],
        *stage_plan(trainer.mesh, starts, weights), 0)
    text = lowered.compile().as_text()
    found = set(profiler.scope_table(text, NAMES).values())
    for scope in NO_TABLE:
        assert (scope, "fwd") in found, scope
    # The pass over the whole leaves has its own name (ISSUE 33).  Held in
    # the lowered text: a name is metadata and no part of the compile
    # cache's key, so an executable cached by an older checkout comes back
    # under the names it was compiled with.
    assert f"/{scopes.OFF_TABLE}/" in lowered.as_text(debug_info=True)
    for scope in ("mask", "in_proj", "recurrence", "mixing", "heads", "loss"):
        assert (scope, "bwd") in found, scope


def test_staging_sets_the_projection_columns_gauge(tiny, tiny_compact):
    """`deeprest_train_projection_columns` (-> `proj_columns_pct.train`):
    set once per staged sparse corpus, on either side of the rule."""
    gauge = REGISTRY.get("deeprest_train_projection_columns")

    def read():
        return [gauge.value(kind=k) for k in ("live", "contracted", "total")]

    dense, compact = tiny["staged"][0], tiny_compact["staged"][0]
    tiny["trainer"].stage_dataset(tiny["bundle"])
    live, contracted, total = read()
    assert dense.live is None
    assert 0 < live <= contracted == total == dense.capacity
    tiny_compact["trainer"].stage_dataset(tiny_compact["bundle"])
    live, contracted, total = read()
    assert 0 < live <= contracted == compact.width == 128
    assert total == compact.capacity == 512


# -- (e) idle gaps under the innermost program span -------------------------


def test_gaps_go_to_the_innermost_covering_program_span():
    ms = 1_000_000
    ops = [("%fusion.1 = f32[] fusion()", 0, 10 * ms),
           ("%gru_kernel_fwd.2 = f32[] custom-call(), "
            'custom_call_target="tpu_custom_call"', 12 * ms, 8 * ms),
           ("%fusion.1 = f32[] fusion()", 30 * ms, 10 * ms),
           ("%fusion.9 = f32[] fusion()", 45 * ms, 5 * ms),
           ("%fusion.9 = f32[] fusion()", 60 * ms, 1 * ms)]
    modules = [("jit_train_superstep(123)", 0, 50 * ms),
               ("jit_concatenate(9)", 60 * ms, 1 * ms)]
    host = [("deeprest-trainer/train.epoch", 0, 52 * ms),
            ("deeprest-trainer/dispatch", 0, 25 * ms),
            ("deeprest-trainer/log_readback", 20 * ms, 3 * ms),
            ("bench.train_epoch", 0, 100 * ms),
            ("not-ours", 40 * ms, 5 * ms)]
    planes = [("/device:TPU:0", [("XLA Modules", modules), ("XLA Ops", ops),
                                 ("Async XLA Ops", [("x", 0, 90 * ms)])]),
              ("/host:CPU", [("main/1", host)])]
    scope_map = {"fusion.1": ("optimizer", "fwd"), "fusion.9": ("loss", "bwd"),
              "gru_kernel_fwd.2": ("gru_kernel_fwd", "fwd")}
    table = profiler.layer_table_of(
        planes, scopes=scope_map, fused={"fusion.1": ("mask",)},
        module="jit_train_superstep", steps=2)
    gaps = {g["span"]: g for g in table["idle_gaps"]}
    # 10-12 under dispatch, 20-30 (middle 25) on dispatch's edge, 40-45
    # under the epoch alone (a foreign span is not ours), 50-60 under none
    assert gaps["deeprest-trainer/dispatch"]["gaps"] == 2
    assert gaps["deeprest-trainer/dispatch"]["seconds"] == pytest.approx(0.012)
    assert gaps["deeprest-trainer/train.epoch"]["seconds"] == pytest.approx(0.005)
    assert gaps[profiler.UNATTRIBUTED]["longest_s"] == pytest.approx(0.010)
    assert "deeprest-trainer/log_readback" not in gaps
    rows = {(r["scope"], r["pass"]): r for r in table["rows"]}
    assert rows["optimizer", "fwd"]["seconds"] == pytest.approx(0.020)
    assert rows["optimizer", "fwd"]["in_fusions_that_also_hold"] == {
        "mask": pytest.approx(0.020)}
    assert rows["optimizer", "fwd"]["ms_per_step"] == pytest.approx(10.0)
    assert rows["gru_kernel_fwd", "fwd"]["seconds"] == pytest.approx(0.008)
    assert rows["loss", "bwd"]["seconds"] == pytest.approx(0.005)
    # the same instruction name in another program is not the map's
    assert rows["other", "-"]["seconds"] == pytest.approx(0.001)
    assert table["kernel_s"] == pytest.approx(0.008)
    assert table["busy_s"] == pytest.approx(0.034)
    assert table["window_s"] == pytest.approx(0.061)
    assert sum(r["seconds"] for r in table["rows"]) == pytest.approx(
        table["busy_s"])
    # a log readback that does cover a gap takes it from dispatch
    host[2] = ("deeprest-trainer/log_readback", 10 * ms, 3 * ms)
    again = profiler.layer_table_of(planes, scopes=scope_map)
    gaps = {g["span"]: g["seconds"] for g in again["idle_gaps"]}
    assert gaps["deeprest-trainer/log_readback"] == pytest.approx(0.002)
    # without a module every operation goes through the map; without a map
    # a kernel still goes by the name its pallas_call gave it
    assert {(r["scope"], r["pass"]): r["seconds"] for r in again["rows"]}[
        "loss", "bwd"] == pytest.approx(0.006)
    bare = profiler.layer_table_of(planes)
    assert {r["scope"]: r["seconds"] for r in bare["rows"]} == {
        "other": pytest.approx(0.026), "gru_kernel_fwd": pytest.approx(0.008)}


def test_a_trace_with_no_device_gives_rows_and_no_seconds():
    table = profiler.layer_table_of(
        [("/host:CPU", [("main/1", [("deeprest-trainer/train.epoch", 0, 5)])])],
        scopes={"fusion.1": ("optimizer", "fwd")})
    assert table["chips"] == 0 and table["idle_pct"] is None
    assert table["busy_s"] == table["window_s"] == table["kernel_s"] == 0
    assert {(r["scope"], r["seconds"]) for r in table["rows"]} == {
        ("optimizer", 0.0), ("other", 0.0)}
    assert table["idle_gaps"] == []


# -- (f) the operator's entries ---------------------------------------------


def test_profile_epoch_reads_the_trace_it_opens(tiny, tmp_path):
    trainer = tiny["trainer"]
    epochs = REGISTRY.get("deeprest_train_epochs_total").value()
    tiny["state"], table = trainer.profile_epoch(
        tiny["state"], tiny["bundle"], tiny["rng"], tiny["staged"],
        str(tmp_path))
    assert obs.RECORDER.enabled is False
    assert REGISTRY.get("deeprest_train_epochs_total").value() == epochs + 1
    assert os.path.exists(table["trace"])
    assert table["steps"] == len(trainer._last_epoch_losses) > 0
    found = {r["scope"] for r in table["rows"]}
    assert found >= set(NO_TABLE) | {profiler.OTHER}
    assert [r["pass"] for r in table["rows"]
            if r["scope"] == profiler.OTHER] == ["-"]      # one row
    assert set(table["phases"]) == set(EPOCH_PHASES)
    assert set(table["setup"]["init_state_seconds"]) == set(INIT_PHASES)
    assert table["setup"]["program_bytes"]["arguments"] > 0
    assert table["chips"] == 0         # the CPU has no device plane
    # the spans did reach the trace: the host plane holds the epoch's
    names = {n for _, lines in profiler.read_planes(table["trace"])
             for _, events in lines for n, _, _ in events}
    assert {"deeprest-trainer/train.epoch",
            "deeprest-trainer/loss_readback"} <= names
    assert "optimizer" in profiler.format_table(table)


def test_profile_epoch_lowers_what_the_per_step_driver_dispatched(tmp_path):
    """The host-feed driver (no staged corpus, no superstep) records its
    own program too: the scope map is never built from another branch's
    executable."""
    cfg = Config(model=ModelConfig(hidden_size=8, dropout_rate=0.1),
                 train=TrainConfig(batch_size=BATCH, window_size=10, seed=0,
                                   log_every_steps=0, device_data="off"))
    data = featurize_buckets(make_series_buckets(60, seed=5),
                             FeaturizeConfig(round_to=8))
    bundle = prepare_dataset(data, cfg.train)
    trainer = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state = trainer.init_state(trainer.sample_input(bundle))
    rng = np.random.default_rng(0)
    state, _ = trainer.train_epoch(state, bundle, rng)
    program, args = trainer._dispatched
    assert program is trainer._train_step and len(args) == 3
    state, table = trainer.profile_epoch(state, bundle, rng, None,
                                         str(tmp_path))
    assert profiler.module_name(
        trainer._dispatched_program_text(state)) == "jit_train_step"
    found = {r["scope"] for r in table["rows"]}
    assert found >= set(NO_TABLE) - {"gather", "densify"}
    assert table["phases"]["plan_build"] == 0.0 < table["phases"]["dispatch"]


def test_train_profile_dir_writes_layers_json(tmp_path, capsys):
    from deeprest_tpu.cli import main

    raw, feats = str(tmp_path / "raw.jsonl"), str(tmp_path / "input.npz")
    out = str(tmp_path / "prof")
    assert main(["simulate", "--scenario=normal", "--ticks=90",
                 f"--out={raw}"]) == 0
    assert main(["featurize", f"--raw={raw}", f"--out={feats}",
                 "--round-to=8"]) == 0
    assert main(["train", f"--features={feats}", "--epochs=2",
                 "--batch-size=16", "--window=20", "--hidden-size=8",
                 "--no-baselines", "--device-data=always",
                 f"--profile-dir={out}"]) == 0
    printed = capsys.readouterr().out
    assert "host phase loss_readback" in printed
    # one line on what came before the first steady step, after epoch 0
    assert printed.count("set-up: init_state ") == 1
    assert printed.index("set-up:") < printed.index("epoch 0:")
    # the second epoch was the one traced: its table comes before its line
    assert printed.index("epoch 0:") < printed.index("host phase") \
        < printed.index("epoch 1:")
    with open(os.path.join(out, "layers.json")) as fh:
        table = json.load(fh)
    rows = {r["scope"] for r in table["rows"]}
    assert rows >= set(NO_TABLE) - {"densify"}    # a dense corpus
    assert set(table["phases"]) == set(EPOCH_PHASES)
    assert {"init_state_seconds", "stage_seconds", "first_dispatch_seconds",
            "compilations", "program_bytes"} <= set(table["setup"])
    assert os.path.exists(table["trace"])


# -- (e) set-up: init_state, the first dispatches, every compilation --------


def test_init_state_sets_its_four_phases(tiny):
    trainer, bundle = tiny["trainer"], tiny["bundle"]
    gauge = REGISTRY.get(obs_setup.INIT_STATE_SECONDS)
    spans = _recorded(
        lambda: trainer.init_state(trainer.sample_input(bundle)))
    seconds = {p: gauge.value(phase=p) for p in INIT_PHASES}
    assert set(k[0] for k in gauge.series()) == set(INIT_PHASES)
    assert all(v > 0 for v in seconds.values()), seconds
    ours = [s for s in spans if s.component == "deeprest-trainer"]
    whole = [s for s in ours if s.name == "train.init_state"]
    assert len(whole) == 1
    phases = [s for s in ours if s.name in INIT_PHASES]
    assert [s.name for s in phases] == list(INIT_PHASES)
    assert all(s.parent_id == whole[0].span_id for s in phases)
    # the CPU reports no device memory: nothing is set, nothing raises
    assert REGISTRY.get(obs_setup.DEVICE_BYTES).series() == {}


def _compilations(**labels):
    found = REGISTRY.get(obs_setup.COMPILATIONS)
    return sum(v for k, v in (found.series() if found else {}).items()
               if all(dict(zip(found.labelnames, k))[name] == value
                      for name, value in labels.items()))


def test_the_compile_listener_counts_under_the_open_phase(tiny):
    """One listener a process (the first Trainer installed it): a fresh
    jitted function's compilation goes to the phase open on this thread,
    to ``other`` outside one, under its own name once the listener has
    been given it; a second call compiles nothing and adds nothing."""
    import jax

    def fresh_for_the_listener(x):
        return x * 3 + 1

    def named_for_the_listener(x):
        return x * 5 + 1

    args = np.arange(7, dtype=np.float32)
    before = _compilations()
    seconds = sum(REGISTRY.get(obs_setup.COMPILE_SECONDS).series().values())

    def under_a_span():
        with obs.RECORDER.span("test.unit", "deeprest-test"):
            jax.jit(fresh_for_the_listener)(args)

    with obs_setup.phase("stage"):
        assert obs_setup.current_phase() == "stage"
        spans = _recorded(under_a_span)
    assert obs_setup.current_phase() == obs_setup.OTHER
    assert _compilations() == before + 1
    assert _compilations(program="other", phase="stage") >= 1
    assert sum(REGISTRY.get(obs_setup.COMPILE_SECONDS).series().values()) \
        > seconds
    compiled = [s for s in spans if (s.component, s.name)
                == ("deeprest-jax", "compile")]
    assert len(compiled) == 1 and compiled[0].tags["program"] == "other"
    assert compiled[0].tags["cache"] in ("hit", "miss", "uncached")
    unit = [s for s in spans if s.name == "test.unit"]
    assert compiled[0].parent_id == unit[0].span_id

    with obs_setup.phase("stage"):
        jax.jit(fresh_for_the_listener)(args)       # compiled already
    assert _compilations() == before + 1

    obs_setup.install(["named_for_the_listener"])   # no second listener
    outside = _compilations(program="named_for_the_listener", phase="other")
    # outside every span: counted, and no trace of its own
    assert not _recorded(lambda: jax.jit(named_for_the_listener)(args))
    assert _compilations() == before + 2
    assert _compilations(program="named_for_the_listener",
                         phase="other") == outside + 1


def test_first_dispatch_is_set_once_for_a_trainer():
    """A fresh trainer's first epoch: one ``train.first_dispatch`` span
    for each program the main path dispatches, its compilation counted in
    that phase under the program's name; the second epoch sets nothing
    again."""
    fresh = _staged_trainer(FeaturizeConfig(hash_features=True, capacity=512))
    gauge = REGISTRY.get(obs_setup.FIRST_DISPATCH_SECONDS)
    was = {p: _compilations(program=p, phase="first_dispatch")
           for p in ("train_superstep", "stale_rows")}
    spans = _recorded(lambda: _epoch(fresh))
    first = [s.tags["program"] for s in spans
             if s.name == "train.first_dispatch"]
    assert sorted(first) == ["stale_rows", "train_superstep"]
    assert "pin_state" in {k[0] for k in gauge.series()}    # init_state's
    assert all(_compilations(program=p, phase="first_dispatch") == n + 1
               for p, n in was.items())
    seconds = dict(gauge.series())
    assert seconds[("train_superstep",)] > 0
    epoch_compiles = _compilations(phase="epoch")
    spans = _recorded(lambda: _epoch(fresh))
    assert not [s for s in spans if s.name == "train.first_dispatch"]
    assert dict(gauge.series()) == seconds
    # a compilation in phase `epoch` after the first epoch is a recompile
    assert _compilations(phase="epoch") == epoch_compiles
    assert REGISTRY.get("deeprest_train_jit_executables") is None


def test_setup_table_and_its_line(tiny):
    table = obs_setup.setup_table()
    assert list(table["init_state_seconds"]) == list(INIT_PHASES)
    rows = table["compilations"]
    assert rows == sorted(rows, key=lambda r: -(
        r["seconds"] + r["trace_seconds"] + r["lower_seconds"]))
    assert all(r["misses"] <= r["count"] for r in rows)
    assert sum(r["count"] for r in rows) == _compilations()
    json.dumps(table)                       # layers.json carries it
    line = obs_setup.format_setup(table)
    assert line.startswith("set-up: init_state ") and "\n" not in line
    assert "compilations in" in line


# -- (e') tracing and lowering beside the compilations (ISSUE 50) -----------

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_STAGE_COUNTERS = (obs_setup.TRACE_SECONDS, obs_setup.LOWER_SECONDS,
                   obs_setup.COMPILE_SECONDS)


def _stage_series(registry=REGISTRY):
    """{counter: {(program, phase): seconds}} of the three stage counters."""
    return {name: dict(found.series()) if found else {}
            for name in _STAGE_COUNTERS
            for found in [registry.get(name)]}


@pytest.fixture
def by_hand(monkeypatch):
    """A listener nobody registered with jax, fed by hand, counting into a
    registry of its own: ``feed(events)`` takes ``(event, fun_name,
    seconds)`` for an end and ``(event, fun_name)`` for a start."""
    from deeprest_tpu.obs import metrics

    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(obs_setup, "REGISTRY", registry)
    listener = obs_setup._Listener()
    listener.programs.add("train_superstep")

    def feed(events):
        for event, fun_name, *seconds in events:
            if seconds:
                listener.ended(event, seconds[0], fun_name=fun_name)
            else:
                listener.began(event, 0.0, fun_name=fun_name)

    return feed, registry


@pytest.mark.parametrize("spelling, program", [
    ("train_superstep", "train_superstep"),         # tracing's
    ("jit_train_superstep", "train_superstep"),     # a module's name
    ("jit(train_superstep)", "train_superstep"),    # lowering's, compiling's
    ("jit(somebody_elses)", "other"),
    ("_where", "other"),
    (None, "other"),
])
def test_the_listener_resolves_every_spelling_of_a_program(by_hand, spelling,
                                                           program):
    feed, registry = by_hand
    feed([(_TRACE, spelling), (_TRACE, spelling, 2.0),
          (_LOWER, spelling), (_LOWER, spelling, 0.5),
          (_COMPILE, spelling), (_COMPILE, spelling, 0.25)])
    assert _stage_series(registry) == {
        obs_setup.TRACE_SECONDS: {(program, "other"): 2.0},
        obs_setup.LOWER_SECONDS: {(program, "other"): 0.5},
        obs_setup.COMPILE_SECONDS: {(program, "other"): 0.25}}
    assert registry.get(obs_setup.COMPILATIONS).series() == {
        (program, "other", "uncached"): 1.0}


def _nested_trace(feed):
    feed([(_TRACE, "train_superstep"), (_TRACE, "_where"),
          (_TRACE, "_broadcast_arrays"), (_TRACE, "_broadcast_arrays", 0.25),
          (_TRACE, "_where", 0.5), (_TRACE, "add"), (_TRACE, "add", 0.125),
          (_TRACE, "train_superstep", 8.0)])
    return {obs_setup.TRACE_SECONDS: {("train_superstep", "other"): 8.0}}


def _trace_inside_a_lower(feed):
    feed([(_LOWER, "jit(train_superstep)"), (_TRACE, "custom_vjp_rule"),
          (_TRACE, "custom_vjp_rule", 0.5),
          (_LOWER, "jit(train_superstep)", 1.5),
          (_TRACE, "later"), (_TRACE, "later", 0.25)])
    return {obs_setup.LOWER_SECONDS: {("train_superstep", "other"): 1.5},
            obs_setup.TRACE_SECONDS: {("other", "other"): 0.25}}


def _compilation_inside_a_trace(feed):
    # tracing compiles and runs a constant: that program's own three stages
    # inside the superstep's trace; then the superstep's own compilation
    feed([(_TRACE, "train_superstep"),
          (_TRACE, "iota"), (_TRACE, "iota", 0.125),
          (_LOWER, "jit(iota)"), (_LOWER, "jit(iota)", 0.125),
          (_COMPILE, "jit(iota)"), (_COMPILE, "jit(iota)", 0.5),
          (_COMPILE, "jit(iota)"), (_COMPILE, "jit(iota)", 0.25),
          (_TRACE, "train_superstep", 8.0),
          (_COMPILE, "jit(train_superstep)"),
          (_COMPILE, "jit(train_superstep)", 0.625)])
    return {obs_setup.TRACE_SECONDS: {("train_superstep", "other"): 7.25},
            obs_setup.COMPILE_SECONDS: {("other", "other"): 0.75,
                                        ("train_superstep", "other"): 0.625}}


def _under_a_phase(feed):
    with obs_setup.phase("init_state"):
        feed([(_TRACE, "zeros"), (_TRACE, "zeros", 0.5)])
        with obs_setup.phase("first_dispatch"):
            feed([(_LOWER, "jit_train_superstep"),
                  (_LOWER, "jit_train_superstep", 1.0)])
    feed([(_TRACE, "zeros"), (_TRACE, "zeros", 0.25)])
    return {obs_setup.TRACE_SECONDS: {("other", "init_state"): 0.5,
                                      ("other", "other"): 0.25},
            obs_setup.LOWER_SECONDS: {
                ("train_superstep", "first_dispatch"): 1.0}}


def _an_end_with_no_start(feed):
    # the listener was installed inside an open trace: its end is nobody's,
    # and what follows is counted as ever
    feed([(_TRACE, "begun_before", 3.0), (_TRACE, "train_superstep"),
          (_TRACE, "train_superstep", 1.0)])
    return {obs_setup.TRACE_SECONDS: {("train_superstep", "other"): 1.0}}


def _more_compiled_than_traced(feed):
    # clocks differ: self time is never negative
    feed([(_TRACE, "train_superstep"), (_COMPILE, "jit(iota)"),
          (_COMPILE, "jit(iota)", 0.5), (_TRACE, "train_superstep", 0.25)])
    return {obs_setup.TRACE_SECONDS: {("train_superstep", "other"): 0.0},
            obs_setup.COMPILE_SECONDS: {("other", "other"): 0.5}}


@pytest.mark.parametrize("events", [
    _nested_trace, _trace_inside_a_lower, _compilation_inside_a_trace,
    _under_a_phase, _an_end_with_no_start, _more_compiled_than_traced],
    ids=lambda f: f.__name__.strip("_"))
def test_only_the_outermost_stage_is_counted_as_self_time(by_hand, events):
    feed, registry = by_hand
    want = events(feed)
    found = {name: series for name, series in _stage_series(registry).items()
             if series}
    assert found == want


def test_the_listener_keeps_two_threads_apart(by_hand):
    """A trace open on one thread holds nothing of another's: the other's
    own trace is outermost there, and its compilation is subtracted from
    neither."""
    import threading

    feed, registry = by_hand
    opened, finished = threading.Event(), threading.Event()

    def first():
        feed([(_TRACE, "train_superstep")])
        opened.set()
        assert finished.wait(timeout=30)
        feed([(_TRACE, "train_superstep", 8.0)])

    def second():
        assert opened.wait(timeout=30)
        with obs_setup.phase("stage"):
            feed([(_TRACE, "elsewhere"), (_TRACE, "elsewhere", 0.5),
                  (_COMPILE, "jit(elsewhere)"),
                  (_COMPILE, "jit(elsewhere)", 2.0)])
        finished.set()

    threads = [threading.Thread(target=first), threading.Thread(target=second)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert _stage_series(registry) == {
        obs_setup.TRACE_SECONDS: {("train_superstep", "other"): 8.0,
                                  ("other", "stage"): 0.5},
        obs_setup.LOWER_SECONDS: {},
        obs_setup.COMPILE_SECONDS: {("other", "stage"): 2.0}}


def _superstep_seconds():
    return {name: sum(v for (program, _), v in series.items()
                      if program == "train_superstep")
            for name, series in _stage_series().items()}


def test_a_trainer_traces_and_lowers_its_superstep_once():
    """A real tiny trainer after ``init_state`` and staging: the first
    epoch adds trace and lower seconds under ``train_superstep``, the
    second adds nothing (a retrace in a later epoch would show here, with
    its name)."""
    fresh = _staged_trainer(FeaturizeConfig(hash_features=True, capacity=512))
    was = _superstep_seconds()
    init = _stage_series()[obs_setup.TRACE_SECONDS]
    assert init.get(("other", "init_state"), 0) > 0     # model.init's
    _epoch(fresh)
    first = _superstep_seconds()
    assert first[obs_setup.TRACE_SECONDS] > was[obs_setup.TRACE_SECONDS]
    assert first[obs_setup.LOWER_SECONDS] > was[obs_setup.LOWER_SECONDS]
    assert _stage_series()[obs_setup.TRACE_SECONDS][
        ("train_superstep", "first_dispatch")] > 0
    _epoch(fresh)
    assert _superstep_seconds() == first


def test_the_three_stages_of_a_call_fit_inside_its_wall_time():
    """A function that sleeps 50 ms in its Python body: at least that is
    tracing, and trace + lower + compile is no more than the call took
    (every nested ``jnp`` helper's trace inside it counted once)."""
    import time

    import jax
    import jax.numpy as jnp

    def sleeps_while_traced(x):
        time.sleep(0.05)
        return jnp.where(x > 0, jnp.sin(x), 0.0).sum() + jnp.arange(3.0).sum()

    obs_setup.install(["sleeps_while_traced"])
    args = jnp.ones(5)
    args.block_until_ready()
    began = time.perf_counter()
    with obs_setup.phase("stage"):
        jax.jit(sleeps_while_traced)(args).block_until_ready()
    wall = time.perf_counter() - began
    seconds = [series.get(("sleeps_while_traced", "stage"), 0.0)
               for series in _stage_series().values()]
    assert seconds[0] >= 0.05, seconds
    assert all(v > 0 for v in seconds), seconds
    assert sum(seconds) <= wall, (seconds, wall)


def test_an_outermost_stage_is_a_span_and_a_nested_one_is_not():
    import jax
    import jax.numpy as jnp

    def spanned_for_the_listener(x):
        return jnp.where(x > 0, x, 0.0).sum()       # `_where` traces inside

    def unspanned_for_the_listener(x):
        return jnp.where(x > 0, x, 1.0).sum()

    obs_setup.install(["spanned_for_the_listener",
                       "unspanned_for_the_listener"])
    args = np.arange(5, dtype=np.float32)

    def under_a_span():
        with obs.RECORDER.span("test.unit", "deeprest-test"):
            jax.jit(spanned_for_the_listener)(args)

    spans = _recorded(under_a_span)
    unit = [s for s in spans if s.name == "test.unit"]
    ours = [s for s in spans if s.component == "deeprest-jax"]
    assert [s.name for s in ours] == ["trace", "lower", "compile"]
    assert all(s.tags["program"] == "spanned_for_the_listener" for s in ours)
    assert all(s.parent_id == unit[0].span_id for s in ours)
    assert _stage_series()[obs_setup.TRACE_SECONDS][
        ("spanned_for_the_listener", "other")] >= ours[0].duration_s * 0.5

    # outside every span: counted, and no trace of its own; with the
    # recorder off: counted, and no span at all
    assert not _recorded(lambda: jax.jit(spanned_for_the_listener)(args[:3]))
    assert not obs.RECORDER.enabled
    obs.RECORDER.clear()
    with obs.RECORDER.span("test.unit", "deeprest-test"):
        jax.jit(unspanned_for_the_listener)(args)
    assert not obs.RECORDER.drain()
    found = _stage_series()
    assert all(found[name][("unspanned_for_the_listener", "other")] > 0
               for name in _STAGE_COUNTERS)


def test_setup_table_rows_carry_the_three_stages(by_hand):
    feed, registry = by_hand
    with obs_setup.phase("first_dispatch"):
        feed([(_TRACE, "train_superstep"), (_TRACE, "train_superstep", 7.9),
              (_LOWER, "jit(train_superstep)"),
              (_LOWER, "jit(train_superstep)", 1.5),
              (_COMPILE, "jit(train_superstep)"),
              (_COMPILE, "jit(train_superstep)", 0.61)])
    with obs_setup.phase("epoch"):          # a retrace whose program is cached
        feed([(_TRACE, "train_superstep"), (_TRACE, "train_superstep", 9.0)])
    feed([(_COMPILE, "jit(iota)"), (_COMPILE, "jit(iota)", 0.25)])
    table = obs_setup.setup_table()
    assert table["compilations"] == [
        {"program": "train_superstep", "phase": "first_dispatch", "count": 1,
         "misses": 1, "kept": 0, "seconds": 0.61, "trace_seconds": 7.9,
         "lower_seconds": 1.5},
        {"program": "train_superstep", "phase": "epoch", "count": 0,
         "misses": 0, "kept": 0, "seconds": 0.0, "trace_seconds": 9.0,
         "lower_seconds": 0.0},
        {"program": "other", "phase": "other", "count": 1, "misses": 1,
         "kept": 0, "seconds": 0.25, "trace_seconds": 0.0,
         "lower_seconds": 0.0}]
    line = obs_setup.format_setup(table)
    assert ("train_superstep in first_dispatch 1 in 0.610 s, 1 missed, "
            "traced 7.900 s, lowered 1.500 s") in line
    assert "train_superstep in epoch 0 in 0.000 s, traced 9.000 s" in line
    assert ("2 compilations in 0.860 s, 2 not from the cache, traced "
            "16.900 s, lowered 1.500 s (") in line


def test_setup_table_says_kept_where_the_superstep_was_loaded(by_hand):
    """ISSUE 51: an executable train/kept.py loaded is counted where the
    cache's load was (one compilation, its seconds, the phase open on the
    thread) with ``cache="kept"``: no miss, and nothing traced or lowered
    beside it."""
    feed, registry = by_hand
    with obs_setup.phase("first_dispatch"):
        obs_setup.count_kept_load("train_superstep", 0.33)
        feed([(_COMPILE, "jit(stale_rows)"), (_COMPILE, "jit(stale_rows)", 0.1)])
    assert registry.get(obs_setup.COMPILATIONS).series() == {
        ("train_superstep", "first_dispatch", "kept"): 1.0,
        ("other", "first_dispatch", "uncached"): 1.0}
    assert obs_setup.compilations_of("train_superstep") == {"kept": 1}
    assert obs_setup.compilations_of("pin_state") == {}
    table = obs_setup.setup_table()
    assert table["compilations"][0] == {
        "program": "train_superstep", "phase": "first_dispatch", "count": 1,
        "misses": 0, "kept": 1, "seconds": 0.33, "trace_seconds": 0.0,
        "lower_seconds": 0.0}
    line = obs_setup.format_setup(table)
    assert ("train_superstep in first_dispatch 1 in 0.330 s, 1 kept, "
            "traced 0.000 s, lowered 0.000 s") in line
    assert ("2 compilations in 0.430 s, 1 not from the cache, 1 kept, "
            "traced 0.000 s") in line


def test_the_kept_reader(monkeypatch):
    """chipbench/readers/kept.py: the superstep's ``loaded`` count; 0 from a
    process that traced; nothing (not an error) from a program without the
    counter: the parent's."""
    from chipbench.readers import kept
    from deeprest_tpu.obs import metrics

    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", registry)
    assert kept.superstep_loaded({}) is None
    counter = registry.counter(obs_setup.KEPT_EXECUTABLES,
                               labelnames=("program", "result"))
    assert kept.superstep_loaded({}) is None
    counter.inc(program="train_superstep", result="miss")
    counter.inc(program="train_superstep", result="stored")
    assert kept.superstep_loaded({}) == 0
    counter.inc(program="train_superstep", result="loaded")
    counter.inc(program="another_program", result="loaded")
    assert kept.superstep_loaded({}) == 1


_STAGE_SERIES = {
    obs_setup.TRACE_SECONDS: {("other", "other"): 9.0,
                              ("other", "init_state"): 3.0,
                              ("train_superstep", "other"): 8.0,
                              ("train_superstep", "epoch"): 0.5},
    obs_setup.LOWER_SECONDS: {("other", "other"): 4.0,
                              ("other", "init_state"): 1.0,
                              ("train_superstep", "other"): 1.5},
    obs_setup.COMPILE_SECONDS: {("other", "other"): 2.0,
                                ("stale_rows", "first_dispatch"): 0.125,
                                ("train_superstep", "other"): 0.25},
}


@pytest.mark.parametrize("reader, expected", [
    ("trace_s", 11.5), ("lower_s", 2.5), ("superstep_build_s", 10.25)])
def test_the_stage_readers(monkeypatch, reader, expected):
    """chipbench/readers/setup_stages.py: the sum bar ``other``/``other``
    (the harness's own), the superstep's three stages in every phase;
    nothing (not an error) from a program without the counters: the
    parent's, which has the compile counter alone."""
    from chipbench.readers import setup_stages
    from deeprest_tpu.obs import metrics

    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", registry)
    read = getattr(setup_stages, reader)
    assert read({}) is None
    made = {}
    for name in _STAGE_COUNTERS[::-1]:      # the parent's counter first
        made[name] = registry.counter(name, labelnames=("program", "phase"))
        if name == obs_setup.COMPILE_SECONDS:
            for key, value in _STAGE_SERIES[name].items():
                made[name].inc(value, program=key[0], phase=key[1])
        assert read({}) is None     # the parent's; registered, never moved
    for name in _STAGE_COUNTERS[:2]:
        for key, value in _STAGE_SERIES[name].items():
            made[name].inc(value, program=key[0], phase=key[1])
    assert read({}) == pytest.approx(expected)


_KERNEL_BYTES = {("gru_kernel_fwd", "vmem"): 1.0, ("gru_kernel_fwd", "hbm"): 2.0,
                 ("gru_kernel_bwd", "hbm"): 5.0}


@pytest.mark.parametrize("reader, metric, labels, series, expected", [
    ("setup:init_state_s", obs_setup.INIT_STATE_SECONDS, ("phase",),
     {("model_init",): 3.0, ("pin",): 0.5}, 3.5),
    ("setup:compile_s", obs_setup.COMPILE_SECONDS, ("program", "phase"),
     {("other", "other"): 9.0, ("other", "init_state"): 2.0,
      ("train_superstep", "other"): 5.0}, 7.0),
    ("setup:compilations", obs_setup.COMPILATIONS,
     ("program", "phase", "cache"),
     {("other", "other", "hit"): 4, ("other", "init_state", "hit"): 40,
      ("train_superstep", "other", "miss"): 1}, 41),
    ("setup:init_state_peak_gb", obs_setup.DEVICE_BYTES, ("at", "kind"),
     {("init_state", "peak"): 8.9e9, ("init_state", "in_use"): 7.2e9,
      ("first_epoch", "in_use"): 4.5e9}, 8.9),
    ("setup:steady_hbm_gb", obs_setup.DEVICE_BYTES, ("at", "kind"),
     {("init_state", "peak"): 8.9e9, ("first_epoch", "in_use"): 4.5e9,
      ("first_epoch", "peak"): 8.9e9}, 4.5),
    ("setup:gru_kernel_vmem_pct", obs_setup.KERNEL_OPERAND_BYTES,
     ("kernel", "space"), _KERNEL_BYTES, 12.5),
    # ISSUE 36: how often the compiled step draws the dropout mask
    ("dropout_draws:draws_per_step", "deeprest_train_dropout_draws", (),
     {(): 1.0}, 1.0),
    # ISSUE 41: the arrays a compiled step reverses in time round the
    # recurrence kernels; a gauge SET to 0 is a reading, not nothing
    ("time_reversals:reversals_per_step", obs_setup.TIME_REVERSALS, (),
     {(): 0.0}, 0.0),
    ("time_reversals:reversals_per_step", obs_setup.TIME_REVERSALS, (),
     {(): 5.0}, 5.0),
    # ISSUE 42: the passes a compiled step makes over a recurrence kernel's
    # operand or result only to cut or to sum it; 0 is a reading too
    ("kernel_edge_passes:passes_per_step", obs_setup.KERNEL_EDGE_PASSES, (),
     {(): 0.0}, 0.0),
    ("kernel_edge_passes:passes_per_step", obs_setup.KERNEL_EDGE_PASSES, (),
     {(): 3.0}, 3.0),
    # ISSUE 56: the layer-0 weight-gradient dots a compiled step runs only
    # to hand the gradient over; 0 is a reading too
    ("bare_weight_grad_dots:dots_per_step", obs_setup.BARE_WEIGHT_GRAD_DOTS,
     (), {(): 0.0}, 0.0),
    ("bare_weight_grad_dots:dots_per_step", obs_setup.BARE_WEIGHT_GRAD_DOTS,
     (), {(): 1.0}, 1.0),
])
def test_the_setup_readers(monkeypatch, reader, metric, labels, series,
                           expected):
    """chipbench/readers/setup.py, dropout_draws.py, time_reversals.py,
    kernel_edge_passes.py and bare_weight_grad_dots.py: nothing (not an error) from a program without the gauge or with the
    gauge never set, the value with it set."""
    import importlib

    from deeprest_tpu.obs import metrics

    registry = metrics.MetricsRegistry()
    monkeypatch.setattr(metrics, "REGISTRY", registry)
    module, func = reader.split(":")
    read = getattr(importlib.import_module(f"chipbench.readers.{module}"),
                   func)
    assert read({}) is None
    made = (registry.counter if metric.endswith("_total")
            else registry.gauge)(metric, labelnames=labels)
    assert read({}) is None
    for key, value in series.items():
        made.inc(value, **dict(zip(labels, key)))
    assert read({}) == pytest.approx(expected)


# -- (g) reversals in time round the recurrence kernels (ISSUE 41) ----------

_REVERSALS_HLO = """HloModule jit_train_superstep, entry_computation_layout={()->f32[]}

%fused_flip (p: bf16[40,60,32,384]) -> bf16[40,60,32,384] {
  %p = bf16[40,60,32,384]{3,2,1,0} parameter(0)
  %rev.1 = bf16[40,60,32,384]{3,2,1,0} reverse(%p), dimensions={1}, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/recurrence/rev"}
  ROOT %neg = bf16[40,60,32,384]{3,2,1,0} negate(%rev.1), metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/recurrence/neg"}
}

%fused_elsewhere (p: f32[60,8]) -> f32[60,8] {
  %p = f32[60,8]{1,0} parameter(0)
  ROOT %rev.2 = f32[60,8]{1,0} reverse(%p), dimensions={0}, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/heads/rev"}
}

ENTRY %main (a: bf16[40,60,32,384], b: bf16[40,60,32,128], c: f32[1], d: f32[60,8]) -> f32[] {
  %a = bf16[40,60,32,384]{3,2,1,0} parameter(0)
  %b = bf16[40,60,32,128]{3,2,1,0} parameter(1)
  %c = f32[1]{0} parameter(2)
  %d = f32[60,8]{1,0} parameter(3)
  %fusion.1 = bf16[40,60,32,384]{3,2,1,0} fusion(%a), kind=kLoop, calls=%fused_flip, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/recurrence/neg"}
  %fusion.2 = f32[60,8]{1,0} fusion(%d), kind=kLoop, calls=%fused_elsewhere, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/heads/rev"}
  %rev.9 = bf16[40,60,32,128]{3,2,1,0:T(8,128)(2,1)S(1)} reverse(%b), dimensions={1}, metadata={op_name="jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))/recurrence/rev"}
  %rev.3 = f32[1]{0} reverse(%c), dimensions={0}, metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/recurrence/rev"}
  %copy.4 = bf16[40,60,32,128]{3,2,1,0} copy(%rev.9), metadata={op_name="jit(train_superstep)/while/body/jvp(QuantileGRU)/recurrence/copy"}
  ROOT %zero = f32[] constant(0)
}
"""


def test_time_reversals_names_the_reversals_under_the_scope():
    """Fused into a neighbour (``rev.1``) or an operation of its own
    (``rev.9``, the backward pass's, in VMEM), each counts; a reversal of
    one element, another scope's, and a copy do not; a text without any
    gives none."""
    assert profiler.time_reversals(_REVERSALS_HLO, "recurrence") == [
        "rev.1", "rev.9"]
    assert profiler.time_reversals(_REVERSALS_HLO, "heads") == ["rev.2"]
    assert profiler.time_reversals(_REVERSALS_HLO, "mixing") == []
    flipless = _REVERSALS_HLO.replace(" reverse(", " copy(")
    assert profiler.time_reversals(flipless, "recurrence") == []


def test_first_epoch_sets_the_time_reversals_gauge(tiny):
    """``deeprest_train_time_reversals`` is what ``time_reversals`` finds
    in the text of the executable the epoch dispatched, and a count of 0 is
    SET (here the scan backend's, which indexes and reverses nothing);
    ``profile_epoch``'s ``setup`` and the ``set-up:`` line carry it."""
    _epoch(tiny)
    gauge = REGISTRY.get(obs_setup.TIME_REVERSALS)
    counted = profiler.time_reversals(
        tiny["trainer"]._dispatched_program_text(tiny["state"]),
        scopes.RECURRENCE)
    assert gauge.series() == {(): float(len(counted))}
    table = obs_setup.setup_table()
    assert table["time_reversals"] == len(counted)
    assert f"{len(counted)} reversals in time a step" \
        in obs_setup.format_setup(table)


# -- (h) passes at the recurrence kernels' edge (ISSUE 42) ------------------

_STEP = "jit(train_superstep)/while/body/transpose(jvp(QuantileGRU))"
_EDGE_HLO = f"""HloModule jit_train_superstep, entry_computation_layout={{()->f32[]}}

%add (a: bf16[], b: bf16[]) -> bf16[] {{
  %a = bf16[] parameter(0)
  %b = bf16[] parameter(1)
  ROOT %sum = bf16[] add(%a, %b), metadata={{op_name="{_STEP}/in_proj/reduce_sum"}}
}}

%fused_split (p: bf16[40,60,32,256]) -> (bf16[40,60,32,128], bf16[40,60,32,128]) {{
  %p = bf16[40,60,32,256]{{3,2,1,0}} parameter(0)
  %split.8 = bf16[40,60,32,128]{{3,2,1,0}} slice(%p), slice={{[0:40], [0:60], [0:32], [0:128]}}, metadata={{op_name="{_STEP}/recurrence/split"}}
  %split.9 = bf16[40,60,32,128]{{3,2,1,0}} slice(%p), slice={{[0:40], [0:60], [0:32], [128:256]}}, metadata={{op_name="{_STEP}/recurrence/split"}}
  ROOT %tuple.1 = (bf16[40,60,32,128]{{3,2,1,0}}, bf16[40,60,32,128]{{3,2,1,0}}) tuple(%split.8, %split.9)
}}

%fused_sum (p: bf16[40,60,32,384]) -> bf16[40,384] {{
  %p = bf16[40,60,32,384]{{3,2,1,0}} parameter(0)
  %zero = bf16[] constant(0)
  ROOT %reduce_sum.3 = bf16[40,384]{{1,0}} reduce(%p, %zero), dimensions={{1,2}}, to_apply=%add, metadata={{op_name="{_STEP}/in_proj/reduce_sum"}}
}}

%fused_dot (p: bf16[40,60,32,384], q: bf16[32,60,512]) -> (f32[40,512,384], bf16[40,384]) {{
  %p = bf16[40,60,32,384]{{3,2,1,0}} parameter(0)
  %q = bf16[32,60,512]{{2,1,0}} parameter(1)
  %zero = bf16[] constant(0)
  %dw = f32[40,512,384]{{2,1,0}} convolution(%q, %p), dim_labels=0fb_0io->b0f, metadata={{op_name="{_STEP}/in_proj/btf,efg->etbg/dot_general"}}
  %reduce_sum.4 = bf16[40,384]{{1,0}} reduce(%p, %zero), dimensions={{1,2}}, to_apply=%add, metadata={{op_name="{_STEP}/in_proj/reduce_sum"}}
  ROOT %tuple.2 = (f32[40,512,384]{{2,1,0}}, bf16[40,384]{{1,0}}) tuple(%dw, %reduce_sum.4)
}}

ENTRY %main (d: bf16[40,60,32,256], x: bf16[32,60,512], m: bf16[40,60,32,384]) -> f32[] {{
  %d = bf16[40,60,32,256]{{3,2,1,0}} parameter(0)
  %x = bf16[32,60,512]{{2,1,0}} parameter(1)
  %m = bf16[40,60,32,384]{{3,2,1,0}} parameter(2)
  %zero = bf16[] constant(0)
  %fusion.201 = (bf16[40,60,32,128]{{3,2,1,0}}, bf16[40,60,32,128]{{3,2,1,0:T(8,128)(2,1)S(1)}}) fusion(%d), kind=kLoop, calls=%fused_split, metadata={{op_name="{_STEP}/recurrence/split"}}
  %half.0 = bf16[40,60,32,128]{{3,2,1,0}} get-tuple-element(%fusion.201), index=0, metadata={{op_name="{_STEP}/recurrence/split"}}
  %half.1 = bf16[40,60,32,128]{{3,2,1,0}} slice(%d), slice={{[0:40], [0:60], [0:32], [128:256]}}, metadata={{op_name="{_STEP}/recurrence/split"}}
  %elsewhere = bf16[40,60,32,128]{{3,2,1,0}} slice(%d), slice={{[0:40], [0:60], [0:32], [0:128]}}, metadata={{op_name="{_STEP}/mixing/split"}}
  %gru_kernel_bwd.6 = (bf16[40,60,32,384]{{3,2,1,0}}, f32[40,512]{{1,0}}) custom-call(%m, %half.0), custom_call_target="tpu_custom_call", metadata={{op_name="{_STEP}/recurrence/gru_kernel_bwd/pallas_call"}}
  %gru_kernel_bwd.7 = (bf16[40,60,32,384]{{3,2,1,0}}, f32[40,512]{{1,0}}) custom-call(%m, %half.1), custom_call_target="tpu_custom_call", metadata={{op_name="{_STEP}/recurrence/gru_kernel_bwd/pallas_call"}}
  %gru_kernel_fwd.1 = bf16[40,60,32,384]{{3,2,1,0}} custom-call(%m), custom_call_target="tpu_custom_call", metadata={{op_name="{_STEP}/recurrence/gru_kernel_fwd/pallas_call"}}
  %dproj.6 = bf16[40,60,32,384]{{3,2,1,0}} get-tuple-element(%gru_kernel_bwd.6), index=0
  %dproj.7 = bf16[40,60,32,384]{{3,2,1,0}} get-tuple-element(%gru_kernel_bwd.7), index=0
  %reduce.56 = bf16[40,384]{{1,0:T(8,128)(2,1)S(1)}} reduce(%dproj.6, %zero), dimensions={{1,2}}, to_apply=%add, metadata={{op_name="{_STEP}/in_proj/reduce_sum"}}
  %fusion.57 = bf16[40,384]{{1,0}} fusion(%dproj.7), kind=kInput, calls=%fused_sum, metadata={{op_name="{_STEP}/in_proj/reduce_sum"}}
  %fusion.17 = (f32[40,512,384]{{2,1,0}}, bf16[40,384]{{1,0}}) fusion(%dproj.6, %x), kind=kOutput, calls=%fused_dot, metadata={{op_name="{_STEP}/in_proj/btf,efg->etbg/dot_general"}}
  %reduce.9 = bf16[40,384]{{1,0}} reduce(%m, %zero), dimensions={{1,2}}, to_apply=%add, metadata={{op_name="{_STEP}/in_proj/reduce_sum"}}
  %reduce.8 = bf16[40,384]{{1,0}} reduce(%gru_kernel_fwd.1, %zero), dimensions={{1,2}}, to_apply=%add, metadata={{op_name="{_STEP}/heads/reduce_sum"}}
  ROOT %out = f32[] constant(0)
}}
"""


def test_kernel_edge_passes_names_the_split_and_the_sums_of_a_result():
    """What counts: the fusion a ``split`` under the scope became (once,
    not once a slice inside it) and a slice of it left alone; a ``reduce``
    over a result of the named kernel, alone (``reduce.56``) or fused
    (``fusion.57``).  What does not: a ``split`` under another scope, a
    sum that rides in a dot's fusion (no pass of its own), a sum over an
    array no kernel wrote or over another kernel's result."""
    found = profiler.kernel_edge_passes(_EDGE_HLO, "recurrence",
                                        "gru_kernel_bwd")
    assert found == ["fusion.201", "half.1", "reduce.56", "fusion.57"]
    assert profiler.kernel_edge_passes(_EDGE_HLO, "mixing",
                                       "gru_kernel_fwd") == [
        "elsewhere", "reduce.8"]
    assert profiler.kernel_edge_passes(_EDGE_HLO, "heads", "no_kernel") == []


def test_first_epoch_sets_the_kernel_edge_passes_gauge(tiny):
    """``deeprest_train_kernel_edge_passes`` is what ``kernel_edge_passes``
    finds in the text of the executable the epoch dispatched, and a count
    of 0 is SET (here the scan backend's, which calls no kernel);
    ``profile_epoch``'s ``setup`` and the ``set-up:`` line carry it."""
    _epoch(tiny)
    gauge = REGISTRY.get(obs_setup.KERNEL_EDGE_PASSES)
    counted = profiler.kernel_edge_passes(
        tiny["trainer"]._dispatched_program_text(tiny["state"]),
        scopes.RECURRENCE, scopes.GRU_KERNEL_BWD)
    assert gauge.series() == {(): float(len(counted))}
    table = obs_setup.setup_table()
    assert table["kernel_edge_passes"] == len(counted)
    assert f"{len(counted)} passes at the kernels' edge a step" \
        in obs_setup.format_setup(table)


def test_first_epoch_sets_the_bare_weight_grad_dots_gauge(tiny):
    """``deeprest_train_bare_weight_grad_dots`` is what
    ``bare_weight_grad_dots`` finds in the text of the executable the epoch
    dispatched for the shapes of the w_ih leaves the step differentiates,
    and a count of 0 is SET (XLA:CPU keeps a dot a ``dot``: the count is
    the chip's to make); ``profile_epoch``'s ``setup`` and the ``set-up:``
    line carry it (ISSUE 56)."""
    _epoch(tiny)
    trainer, state = tiny["trainer"], tiny["state"]
    leaves = [a.shape for name, a in state.params.items()
              if name in MASKED_PARAM_NAMES]
    assert len(leaves) == 2
    counted = profiler.bare_weight_grad_dots(
        trainer._dispatched_program_text(state), scopes.IN_PROJ, leaves)
    gauge = REGISTRY.get(obs_setup.BARE_WEIGHT_GRAD_DOTS)
    assert gauge.series() == {(): float(len(counted))}
    table = obs_setup.setup_table()
    assert table["bare_weight_grad_dots"] == len(counted)
    assert f"{len(counted)} bare weight-gradient dots a step" \
        in obs_setup.format_setup(table)


# -- the seam to the benchmark ----------------------------------------------

# The program series that `chipbench/readers/*.py` (and the runners beside
# them) read BY NAME, each with the label names the reader indexes and the
# label values it asks for that a CPU run sets.  A copy, on purpose: a PR
# that renames a series or a kind fails here, before the ledger writes
# `null` under `per_layer`.  What only a chip or a mesh sets (memory
# statistics, the compiler's memory spaces, collectives) has no row here.
_BENCHMARK_SERIES = [
    ("deeprest_train_accumulation", ("kind",),
     [{"kind": k} for k in ("microbatches", "carry_bytes")]),
    ("deeprest_train_optimizer_updates_total", (), [{}]),
    ("deeprest_compilations_total", ("program", "phase"),
     [{"program": "train_superstep", "phase": "first_dispatch"}]),
    ("deeprest_compile_seconds_total", ("program", "phase"),
     [{"program": "train_superstep", "phase": "first_dispatch"}]),
    ("deeprest_trace_seconds_total", ("program", "phase"),
     [{"program": "train_superstep", "phase": "first_dispatch"},
      {"program": "other", "phase": "init_state"}]),
    ("deeprest_lower_seconds_total", ("program", "phase"),
     [{"program": "train_superstep", "phase": "first_dispatch"},
      {"program": "other", "phase": "init_state"}]),
    # ISSUE 51: what the store of kept executables did for the superstep
    # (`loaded`, the value the reader asks for, takes a second process)
    ("deeprest_train_kept_executables_total", ("program", "result"),
     [{"program": "train_superstep"}]),
    ("deeprest_train_bare_weight_grad_dots", (), [{}]),
    ("deeprest_train_collective_bytes", (), []),
    ("deeprest_train_device_bytes", ("at", "kind"), []),
    ("deeprest_train_dropout_draws", (), [{}]),
    ("deeprest_train_epochs_total", (), [{}]),
    ("deeprest_train_init_state_seconds", ("phase",),
     [{"phase": p} for p in ("model_init", "shard", "opt_init", "pin")]),
    ("deeprest_train_kernel_edge_passes", (), [{}]),
    ("deeprest_train_kernel_operand_bytes", ("space",), []),
    ("deeprest_train_last_epoch_phase_seconds", ("phase",),
     [{"phase": p} for p in ("plan_build", "plan_h2d", "loss_readback")]),
    ("deeprest_train_last_stage_seconds", (), [{}]),
    ("deeprest_train_optimizer_rows", ("kind",),
     [{"kind": k} for k in ("total", "updated", "visited", "stale",
                            "trips", "bound", "per_chip")]),
    ("deeprest_train_projection_gather_pieces", (), []),    # a mesh's
    ("deeprest_train_projection_columns", ("kind",),
     [{"kind": k} for k in ("live", "contracted", "total", "padded",
                            "bound")]),
    ("deeprest_train_readbacks_total", (), [{"sink": "epoch_losses"}]),
    ("deeprest_train_superstep_dispatches_total", (), [{}]),
    # ISSUE 54: the supersteps a life dispatched for the first time, and
    # the seconds of each (`chipbench/readers/programs.py` sums the one
    # and indexes the other by `nth`)
    ("deeprest_train_superstep_programs_total", ("form", "width"),
     [{"form": "compact"}]),
    ("deeprest_train_superstep_first_dispatch_seconds", ("nth",),
     [{"nth": "1", "form": "compact"}]),
    ("deeprest_train_time_reversals", (), [{}]),
]


@pytest.fixture(scope="module")
def compact_after_an_epoch(tiny_compact):
    _epoch(tiny_compact)
    return tiny_compact


@pytest.mark.parametrize("name, indexed, set_on_the_cpu", _BENCHMARK_SERIES,
                         ids=[row[0] for row in _BENCHMARK_SERIES])
def test_the_series_the_benchmark_reads_by_name(compact_after_an_epoch, name,
                                                indexed, set_on_the_cpu):
    """After `init_state`, staging and one staged sparse epoch on the
    compact form: the series is registered under the reader's name with
    the label names the reader indexes, and holds a row for every label
    value the reader asks for."""
    metric = REGISTRY.get(name)
    assert metric is not None, f"{name} is not registered"
    assert set(indexed) <= set(metric.labelnames), metric.labelnames
    rows = [dict(zip(metric.labelnames, key)) for key in metric.series()]
    for want in set_on_the_cpu:
        assert any(want.items() <= row.items() for row in rows), (want, rows)
