"""The superstep's executable kept from one process to the next (ISSUE 51,
``deeprest_tpu/train/kept.py``), held on the CPU at a small size.

A second ``Trainer`` built inside one test stands for the next process: a
new ``KeptJit`` has traced nothing and holds no executable, so whatever it
dispatches it loaded from the store or traced itself.  Every test has a
store of its own (tests/conftest.py).  On the CPU only an executable the
backend compiled in this process is written (``kept.whole``), and the suite
shares a warm compilation cache, so the tests that need a file turn jax's
cache off while the first trainer compiles (``backend_compiles``).

(a) the four programs of the benchmark's kinds (compact, dense, G = 8, a
    mesh ``data=2``): the second trainer loads, traces nothing and leaves
    the bits of a trainer that ran the jit;
(b) the key: every field of ``ModelConfig`` and ``TrainConfig`` but the seed,
    the source digest, a version, another table width, another mesh;
(c) files that are cut short or not ours, a directory that cannot be
    written: the epoch runs;
(d) an executable jax's cache loaded is never written thin;
(e) writers at once leave one whole file;
(f) the jit's surface on a loaded executable.

No number of this file is a device number.
"""

import dataclasses
import os
import shutil
import threading

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from deeprest_tpu.config import (
    Config, FeaturizeConfig, MeshConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import featurize_buckets
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.parallel.distributed import stage_plan
from deeprest_tpu.parallel.mesh import make_mesh
from deeprest_tpu.train import Trainer, kept, prepare_dataset

from conftest import make_series_buckets
from test_live_columns import E, F, _bundle, _corpus, _trainer
from test_superstep import SMALL

PROGRAM = "train_superstep"


# -- what the program recorded -------------------------------------------------


def _results() -> dict:
    """``{result: count}`` of the superstep's first calls so far."""
    metric = REGISTRY.get(obs_setup.KEPT_EXECUTABLES)
    if metric is None:
        return {}
    return {key[1]: int(n) for key, n in metric.series().items()
            if key[0] == PROGRAM}


def _since(before: dict) -> dict:
    return {k: n - before.get(k, 0) for k, n in _results().items()
            if n != before.get(k, 0)}


_events: list = []


def _heard(event, _seconds, fun_name=None, **_kw):
    if "jaxpr_trace" in event and PROGRAM in str(fun_name):
        _events.append(event)


@pytest.fixture
def trace_events():
    """jax's trace events of the superstep since the test began (one
    listener a process, registered by the first test that asks)."""
    if not getattr(_heard, "registered", False):
        jax.monitoring.register_event_duration_secs_listener(_heard)
        _heard.registered = True
    _events.clear()
    return _events


@pytest.fixture
def backend_compiles():
    """jax's persistent cache off: what a trainer compiles here the backend
    compiled, so it is whole and may be written."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


# -- the small programs --------------------------------------------------------


def _sparse(hot: int = 100):
    cols, vals, y, _ = _corpus(hot)
    return _bundle(cols, vals, y)


def _dense():
    data = featurize_buckets(make_series_buckets(160, seed=2),
                             FeaturizeConfig(round_to=8))
    return prepare_dataset(data, SMALL.train)


KINDS = {            # (the compact program: `kept_once`, below)
    "dense": (_dense, lambda b: Trainer(SMALL, b.feature_dim, b.metric_names)),
    # eight microbatches unrolled: one direction and no dropout, whose
    # threefry rounds are most of what XLA:CPU compiles here (14 s with it)
    "accum8": (_dense, lambda b: Trainer(
        Config(model=dataclasses.replace(SMALL.model, dropout_rate=0.0,
                                         bidirectional=False),
               train=dataclasses.replace(SMALL.train, grad_accum_windows=8,
                                         steps_per_superstep=8)),
        b.feature_dim, b.metric_names)),
    "data2": (_sparse, lambda b: _trainer(
        mesh=make_mesh(MeshConfig(data=2)))),
}


def _two_epochs(trainer, bundle, seed=3):
    """(state, per-step losses) after two epochs on the staged corpus."""
    staged = trainer.stage_dataset(bundle)
    state = trainer.init_state(trainer.sample_input(bundle), seed=seed)
    rng = np.random.default_rng(7)
    losses = []
    for _ in range(2):
        state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
        losses.append(trainer._last_epoch_losses.copy())
    return state, np.concatenate(losses)


def _assert_bit_equal(got, want):
    (state, losses), (state0, losses0) = got, want
    np.testing.assert_array_equal(losses, losses0)
    assert int(state.step) == int(state0.step)
    for a, b in zip(jax.tree.leaves((state.params, state.opt_state)),
                    jax.tree.leaves((state0.params, state0.opt_state))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def kept_once(tmp_path_factory):
    """A store that holds the compact program's executable, what the
    trainer that wrote it computed, and its bundle: copied by the tests
    that need a file, so that none sees what another wrote."""
    directory = tmp_path_factory.mktemp("kept-once") / kept.SUBDIR
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    was, kept.store_dir = kept.store_dir, lambda: str(directory)
    try:
        bundle = _sparse()
        before = _results()
        ran = _two_epochs(_trainer(), bundle)
        assert _since(before) == {"miss": 1, "stored": 1}
    finally:
        kept.store_dir = was
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    (name,) = os.listdir(directory)
    return {"dir": str(directory), "file": name, "bundle": bundle, "ran": ran}


@pytest.fixture
def store(kept_once, tmp_path):
    """The test's own store (conftest's), holding a copy of that file."""
    directory = tmp_path / kept.SUBDIR
    shutil.copytree(kept_once["dir"], directory)
    return str(directory / kept_once["file"])


# -- (a) a second trainer loads, traces nothing, and leaves the same bits -----


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_second_trainer_loads_and_leaves_the_same_bits(
        kind, backend_compiles, trace_events):
    make_bundle, make_trainer = KINDS[kind]
    bundle = make_bundle()
    before = _results()
    jit_ran = _two_epochs(make_trainer(bundle), bundle)
    assert _since(before) == {"miss": 1, "stored": 1}
    assert trace_events                     # the first one traced
    _loads_and_leaves(make_trainer(bundle), bundle, jit_ran, trace_events)


def test_a_second_compact_trainer_loads_and_leaves_the_same_bits(
        store, kept_once, trace_events):
    _loads_and_leaves(_trainer(), kept_once["bundle"], kept_once["ran"],
                      trace_events)


def _loads_and_leaves(second, bundle, jit_ran, trace_events):
    trace_events.clear()
    before = _results()
    loaded_ran = _two_epochs(second, bundle)
    assert _since(before) == {"loaded": 1}
    assert trace_events == []
    assert second._superstep._cache_size() == 1
    _assert_bit_equal(loaded_ran, jit_ran)


def test_the_load_is_counted_where_the_caches_load_was(store, kept_once):
    def kept_rows():
        return [r for r in obs_setup.setup_table().get("compilations", ())
                if r["program"] == PROGRAM and r["kept"]]

    was = sum(r["kept"] for r in kept_rows())
    _two_epochs(_trainer(), kept_once["bundle"])
    rows = kept_rows()
    assert sum(r["kept"] for r in rows) == was + 1
    # the load is no miss of the cache, and it happened in the first dispatch
    assert {r["phase"] for r in rows} == {"first_dispatch"}
    assert all(r["misses"] + r["kept"] <= r["count"] for r in rows)
    line = obs_setup.format_setup(obs_setup.setup_table())
    assert f"{PROGRAM} in first_dispatch" in line and " kept, traced" in line


# -- (b) the key ---------------------------------------------------------------


def _perturbed(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, int):
        return value + 1
    if isinstance(value, float):
        return value / 2 + 0.01
    if isinstance(value, tuple):
        return value + value[:1]
    return 7 if value == "auto" else f"{value}-other"


FIELDS = [(part, f.name) for part, cls in (("model", ModelConfig),
                                           ("train", TrainConfig))
          for f in dataclasses.fields(cls)]


def _where(trainer, like):
    """(file, key) the trainer's superstep computes for ``like``'s
    arguments: the key is made before anything is traced, from the outside
    alone, so another trainer's arguments do."""
    return trainer._superstep._where(like)


@pytest.fixture(scope="module")
def small_args():
    bundle = _dense()
    trainer = Trainer(SMALL, bundle.feature_dim, bundle.metric_names)
    staged = trainer.stage_dataset(bundle)
    state = trainer.init_state(trainer.sample_input(bundle))
    return bundle, (state, *staged, 0)


@pytest.mark.parametrize("part, name", FIELDS,
                         ids=[f"{p}.{n}" for p, n in FIELDS])
def test_every_config_field_but_the_seed_is_in_the_key(part, name, small_args):
    bundle, args = small_args
    # (`elastic` asks for snapshots)
    base = dataclasses.replace(SMALL, train=dataclasses.replace(
        SMALL.train, snapshot_every_steps=1))
    section = getattr(base, part)
    other = dataclasses.replace(base, **{part: dataclasses.replace(
        section, **{name: _perturbed(getattr(section, name))})})

    def where(config):
        return _where(Trainer(config, bundle.feature_dim,
                              bundle.metric_names), args)

    (path, key), (other_path, other_key) = where(base), where(other)
    if (part, name) == ("train", "seed"):
        # the seed makes the state and the shuffles, which are arguments:
        # the benchmark's runs, a seed each, share one executable
        assert (other_path, other_key) == (path, key)
    elif (part, name) in (("model", "feature_dim"), ("model", "num_metrics")):
        # the trainer takes these two from the corpus, not from the config
        assert other_key["identity"] != key["identity"]
    else:
        assert other_path != path                    # a miss
        assert other_key["identity"] != key["identity"]
        assert {k for k in key if key[k] != other_key[k]} == {"identity"}


def test_the_corpus_dimensions_are_in_the_key(small_args):
    bundle, args = small_args
    base = _where(Trainer(SMALL, bundle.feature_dim, bundle.metric_names),
                  args)
    for dims in ((bundle.feature_dim + 8, bundle.metric_names),
                 (bundle.feature_dim, bundle.metric_names + ["one_more"])):
        path, key = _where(Trainer(SMALL, *dims), args)
        assert path != base[0] and key["identity"] != base[1]["identity"]


def _arguments(trainer, bundle):
    """The arguments of the epoch's first dispatch, nothing dispatched."""
    staged = trainer.stage_dataset(bundle)
    state = trainer.init_state(trainer.sample_input(bundle), seed=3)
    steps = -(-bundle.num_train_windows // trainer.config.train.batch_size)
    starts, weights, _ = trainer._epoch_plan(
        bundle.num_train_windows, np.random.default_rng(7),
        trainer._superstep_len(steps))
    return (state, *staged, *stage_plan(trainer.mesh, starts, weights), 0)


def _load(trainer, args):
    """(what the superstep's first call would load, what it counted)."""
    superstep = trainer._superstep
    before = _results()
    loaded = superstep._load(*superstep._where(args), args)
    return loaded, _since(before)


def test_a_file_of_another_version_or_option_is_stale(
        store, kept_once, monkeypatch):
    trainer = _trainer()
    args = _arguments(trainer, kept_once["bundle"])
    loaded, counted = _load(trainer, args)
    assert loaded is not None and counted == {"loaded": 1}
    real = kept.versions
    partitionable = jax.config.jax_threefry_partitionable

    def option(m):
        jax.config.update("jax_threefry_partitionable", not partitionable)

    for part, patch in (
            ("source", lambda m: m.setattr(kept, "source_digest",
                                           lambda: "edited")),
            ("versions", lambda m: m.setattr(
                kept, "versions", lambda client: {
                    **real(client), "jaxlib": "0.0.0-upgraded"})),
            ("options", lambda m: m.setenv("LIBTPU_INIT_ARGS",
                                           "--a_flag_of_the_compiler")),
            ("options", option)):
        try:
            with monkeypatch.context() as m:
                patch(m)
                path, key = trainer._superstep._where(args)
                assert path == store        # the same shapes: the same file
                assert {k for k, v in kept._read(store)[1][0].items()
                        if key[k] != v} == {part}
                assert _load(trainer, args) == (None, {"stale": 1})
        finally:
            jax.config.update("jax_threefry_partitionable", partitionable)


def test_a_stale_file_is_traced_over_and_replaced(
        store, kept_once, monkeypatch, trace_events, backend_compiles):
    monkeypatch.setattr(kept, "source_digest", lambda: "edited")
    before = _results()
    ran = _two_epochs(_trainer(), kept_once["bundle"])
    assert _since(before) == {"stale": 1, "stored": 1} and trace_events
    _assert_bit_equal(ran, kept_once["ran"])
    # one file for the program and its shapes: the directory does not grow
    assert os.listdir(os.path.dirname(store)) == [os.path.basename(store)]
    assert kept._read(store)[1][0]["source"] == "edited"


def test_another_table_width_is_a_miss_and_traced(store, kept_once,
                                                  trace_events):
    # 200 live call paths pad to a table of 256 where 100 pad to 128
    before = _results()
    _two_epochs(_trainer(), _sparse(200))
    since = _since(before)
    assert since.pop("miss") == 1 and "loaded" not in since
    assert trace_events
    assert os.path.exists(store)                # the other key's file stays


def test_the_executable_is_written_before_it_first_runs(
        store, kept_once, backend_compiles, monkeypatch):
    """XLA:CPU cannot serialise an executable whose sort has run once
    (``UNIMPLEMENTED: `LessThan` is not serializable``; the superstep sorts
    its stale rows), so off the TPU the write comes BEFORE the first
    dispatch and not beside it, where a loaded machine let the dispatch win
    (ISSUE 55's tier-1 run lost this file's seventeen cases to it, ISSUE 56's one): when
    `_keep` runs, the jit has dispatched nothing, and the call after it
    compiles nothing again.  (On the TPU the write stays beside the
    dispatch: ahead costs the call a second trace there.)"""
    os.remove(store)
    dispatched = []
    keep = kept.KeptJit._keep

    def watched(self, *args, **kwargs):
        dispatched.append(self._jit._cache_size())
        return keep(self, *args, **kwargs)

    monkeypatch.setattr(kept.KeptJit, "_keep", watched)
    trainer = _trainer()
    before = _results()
    state, _ = trainer._superstep(*_arguments(trainer, kept_once["bundle"]))
    assert dispatched == [0]
    assert _since(before) == {"miss": 1, "stored": 1}
    assert os.path.exists(store) and int(state.step) > 0
    assert trainer._superstep._cache_size() == 1


def test_another_mesh_is_a_miss(store, kept_once):
    trainer = _trainer(mesh=make_mesh(MeshConfig(data=2)))
    args = _arguments(trainer, kept_once["bundle"])
    path, key = trainer._superstep._where(args)
    assert path != store and key["mesh"] != kept._read(store)[1][0]["mesh"]
    assert _load(trainer, args) == (None, {"miss": 1})


# -- (c) files that cannot be used, a store that cannot be written ------------


def _damaged(data: bytes, damage: str) -> bytes:
    if damage == "cut":
        return data[:len(data) // 2]
    if damage == "noise":
        return np.random.default_rng(0).bytes(4096)
    if damage == "not-ours":
        return b"\x80\x04N."                    # a pickle, of None
    # one bit deep in the payload
    return data[:-1000] + bytes([data[-1000] ^ 1]) + data[-999:]


@pytest.mark.parametrize("damage", ["cut", "noise", "not-ours", "flipped"])
def test_a_damaged_file_is_unreadable(damage, store, kept_once):
    with open(store, "rb") as fh:
        data = fh.read()
    with open(store, "wb") as fh:
        fh.write(_damaged(data, damage))
    trainer = _trainer()
    args = _arguments(trainer, kept_once["bundle"])
    assert _load(trainer, args) == (None, {"unreadable": 1})


def test_a_payload_the_backend_refuses_is_unreadable_and_the_state_whole(
        store, kept_once):
    """The key matches and the backend's deserialiser refuses the payload:
    nothing was dispatched, the donated state has not been touched."""
    key, out_tree, payload = kept._read(store)[1]
    kept._write(store, (key, out_tree, payload[:len(payload) // 2]))
    trainer = _trainer()
    args = _arguments(trainer, kept_once["bundle"])
    assert _load(trainer, args) == (None, {"unreadable": 1})
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(args[0]))


def test_over_a_file_cut_short_the_epoch_runs(store, kept_once,
                                              trace_events):
    with open(store, "rb") as fh:
        data = fh.read()
    with open(store, "wb") as fh:
        fh.write(_damaged(data, "cut"))
    before = _results()
    ran = _two_epochs(_trainer(), kept_once["bundle"])
    # (the suite's compilation cache served the compile: on the CPU such an
    # executable is not written, and the cut file stays)
    assert _since(before).pop("unreadable") == 1 and trace_events
    _assert_bit_equal(ran, kept_once["ran"])


def test_a_store_that_cannot_be_written_is_unsupported_and_the_epoch_runs(
        kept_once, tmp_path, monkeypatch, backend_compiles):
    blocker = tmp_path / "a-file-where-the-directory-would-be"
    blocker.write_bytes(b"")
    monkeypatch.setattr(kept, "store_dir",
                        lambda: str(blocker / kept.SUBDIR))
    before = _results()
    ran = _two_epochs(_trainer(), kept_once["bundle"])
    assert _since(before) == {"miss": 1, "unsupported": 1}
    _assert_bit_equal(ran, kept_once["ran"])


def test_a_function_replaced_at_run_time_keeps_the_store_out(
        store, kept_once, monkeypatch):
    """What the sources' digest cannot see: a caller's function in the
    package's namespace (the benchmark's controls break the program this
    way on purpose, in files no test can reach).  Such a process neither
    loads the honest executable nor writes its own under the honest key."""
    import deeprest_tpu.train.trainer as T

    assert kept.replaced_at_run_time() == []    # conftest's store_dir is
    # this module's own name, which is not looked at
    trainer = _trainer()
    args = _arguments(trainer, kept_once["bundle"])
    assert trainer._superstep._where(args) is not None
    real = T.moments_off_table_are_zero
    monkeypatch.setattr(T, "moments_off_table_are_zero",
                        lambda opt_state, live: real(opt_state, live))
    monkeypatch.setattr(T.Trainer, "_stale_rows_of_a_test",
                        lambda self: None, raising=False)
    assert kept.replaced_at_run_time() == [
        "deeprest_tpu.train.trainer.moments_off_table_are_zero",
        "deeprest_tpu.train.trainer.Trainer._stale_rows_of_a_test"]
    assert trainer._superstep._where(args) is None
    before = _results()
    state, _ = trainer._superstep(*args)
    assert _since(before) == {"unsupported": 1} and int(state.step) > 0
    assert os.listdir(os.path.dirname(store)) == [os.path.basename(store)]


def test_without_a_cache_directory_nothing_is_kept(kept_once, monkeypatch):
    from conftest import STORE_DIR_OF_THE_PROGRAM

    monkeypatch.setattr(kept, "store_dir", STORE_DIR_OF_THE_PROGRAM)
    was = jax.config.jax_compilation_cache_dir
    assert kept.store_dir() == os.path.join(was, kept.SUBDIR)
    trainer = _trainer()
    args = _arguments(trainer, kept_once["bundle"])
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        assert kept.store_dir() is None
        before = _results()
        state, _ = trainer._superstep(*args)
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert _since(before) == {"unsupported": 1}
    assert int(state.step) > 0 and trainer._superstep._cache_size() == 1


# -- (d) an executable jax's cache loaded is never written thin ---------------


def test_an_executable_the_cache_loaded_is_never_written_thin(
        kept_once, tmp_path, monkeypatch):
    """ISSUE 51's finding 3.  XLA:CPU serialises an executable that jax's
    persistent cache loaded as a reference to symbols of the process that
    compiled it: a later process loads it and fails at the first readback,
    after the donation.  A trainer whose superstep was a cache hit either
    writes a payload that the next trainer runs to the same bits, or
    nothing."""
    from conftest import STORE_DIR_OF_THE_PROGRAM

    monkeypatch.setattr(kept, "store_dir", STORE_DIR_OF_THE_PROGRAM)
    was = jax.config.jax_compilation_cache_dir
    floor = (jax.config.jax_persistent_cache_min_compile_time_secs,
             jax.config.jax_persistent_cache_min_entry_size_bytes)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    try:
        bundle = kept_once["bundle"]
        before = _results()
        _two_epochs(_trainer(), bundle)              # compiles, writes
        assert _since(before) == {"miss": 1, "stored": 1}
        (name,) = os.listdir(kept.store_dir())
        os.unlink(os.path.join(kept.store_dir(), name))

        before = _results()
        hit = obs_setup.compilations_of(PROGRAM).get("hit", 0)
        _two_epochs(_trainer(), bundle)              # jax's cache hits
        assert obs_setup.compilations_of(PROGRAM)["hit"] == hit + 1
        wrote = os.listdir(kept.store_dir())
        assert _since(before) == (
            {"miss": 1, "stored": 1} if wrote else
            {"miss": 1, "unsupported": 1})

        before = _results()
        ran = _two_epochs(_trainer(), bundle)        # never fails
        if wrote:
            assert _since(before) == {"loaded": 1}
        _assert_bit_equal(ran, kept_once["ran"])
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor[0])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          floor[1])
        compilation_cache.reset_cache()


def test_only_the_tpu_writes_what_the_cache_loaded():
    assert kept.whole("cpu", fresh=True) and kept.whole("tpu", fresh=True)
    assert kept.whole("tpu", fresh=False)
    assert not kept.whole("cpu", fresh=False)


# -- (e) writers at once --------------------------------------------------------


def test_writers_at_once_leave_one_whole_file(tmp_path):
    path = str(tmp_path / kept.SUBDIR / "train_superstep-shared.bin")
    entries = [({"writer": i}, None, bytes([i]) * (1 << 20)) for i in range(8)]
    seen, failed = [], []

    def write(entry):
        try:
            for _ in range(6):
                kept._write(path, entry)
                seen.append(kept._read(path))
        except Exception as e:      # noqa: BLE001 - reported below
            failed.append(e)

    threads = [threading.Thread(target=write, args=(e,)) for e in entries]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not failed and not any(t.is_alive() for t in threads)
    # every read between the writes saw one writer's whole entry
    assert len(seen) == 48
    assert all(result == "found" and found in entries
               for result, found in seen)
    assert os.listdir(os.path.dirname(path)) == [os.path.basename(path)]


# -- (f) the jit's surface on a loaded executable ------------------------------


def test_a_loaded_superstep_answers_as_the_jit_does(store, kept_once,
                                                    trace_events):
    import inspect

    bundle = kept_once["bundle"]
    trainer = _trainer()
    superstep = trainer._superstep
    assert superstep.__name__ == PROGRAM
    assert list(inspect.signature(superstep).parameters) == [
        "state", "x_base", "y_base", "starts_plan", "weights_plan", "chunk"]
    state = trainer.init_state(trainer.sample_input(bundle), seed=3)
    rng = np.random.default_rng(7)
    for _ in range(2):                              # two stagings, one key
        staged = trainer.stage_dataset(bundle)
        state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
    assert superstep._cache_size() == 1 and trainer._superstep is superstep
    program, args = trainer._dispatched
    assert program is superstep
    compiled = program.lower(state, *args).compile()
    assert compiled is trainer._dispatched_executable(state)
    assert "ENTRY" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    assert trace_events == []
    # what only a real lowering has is asked of the jit, which then traces
    assert "stablehlo" in program.lower(state, *args).as_text()
    assert trace_events
    # a caller that traces the program (jax.make_jaxpr) gets the function
    assert "scan" in str(jax.make_jaxpr(superstep)(state, *args))
