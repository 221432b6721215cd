"""Chaos-hardening gates (ROADMAP item 7, dynamic half).

Two halves, matching the tentpole:

**Training** — the kill-at-step-K resume parity matrix: a run preempted
mid-epoch (inside a superstep, on the per-step path, mid-grad-accum
group) or between epochs, resumed via ``Trainer.resume_training`` from
its cursor snapshot, must be BIT-IDENTICAL to the uninterrupted run at
every later step — including a resume onto a SHRUNK mesh (where the
restored state at K is bit-exact cross-mesh and the continued trajectory
matches within the pinned GSPMD ulp envelope, the round-12 discipline).
Plus the torn-write simulation for the fsync'd checkpoint format.

**Serving** — the router's replica health layer: per-request deadlines
turn a dead worker into a typed ``ReplicaDeadError``; retries happen
ONLY for requests that provably never produced a response (no
double-execution); ejection after consecutive failures (or confirmed
death); background probe reboots process replicas and rejoins them; a
SIGKILLed worker under live traffic costs at most one retried request,
never a hang or a wrong answer.

The full kill-under-load storm (HTTP load + scheduled SIGKILLs +
resource-census leak audit) lives in benchmarks/chaos_bench.py; its
quick arm runs here under the slow marker and the committed
chaos_bench.json gate is pinned below in tier-1.
"""

import json
import os
import signal
import threading
import time

import numpy as np
import pytest
import jax

from router_test_support import W, build_tiny

from deeprest_tpu.config import (
    Config, FeaturizeConfig, MeshConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import featurize_buckets
from deeprest_tpu.parallel import (
    DeviceLossError, FaultInjector, NoValidMeshError, RemeshExhaustedError,
)
from deeprest_tpu.parallel.mesh import make_mesh, shrink_mesh_config
from deeprest_tpu.serve import ReplicaDeadError, ReplicaRouter, RouterConfig
from deeprest_tpu.serve.replica import ProcessReplica
from deeprest_tpu.serve.server import ServingError
from deeprest_tpu.train import Trainer, prepare_dataset
from deeprest_tpu.train.checkpoint import (
    latest_cursor_step, list_steps, restore_checkpoint, save_checkpoint,
)

from conftest import make_series_buckets

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# training: kill-at-step-K resume parity


class _SimulatedPreemption(BaseException):
    """Raised from the on_step hook to model SIGKILL at a step boundary
    (BaseException so no training-path handler can swallow it — like the
    real signal, nothing downstream gets to clean up)."""


def _tiny_config(ckpt_dir, snapshot_every=2, superstep=2, accum=1,
                 epochs=2, snapshot_keep=0, **train_kw):
    # snapshot_keep=0 (unlimited) by default: the resume-parity matrix
    # restores HISTORICAL steps (e.g. the kill-time snapshot from the
    # uninterrupted twin), which the retention GC would otherwise prune;
    # the GC has its own pinned tests below.
    return Config(
        model=ModelConfig(hidden_size=8, dropout_rate=0.5),
        train=TrainConfig(
            num_epochs=epochs, batch_size=16, window_size=12,
            eval_stride=12, eval_max_cycles=2, seed=0,
            device_data="always", steps_per_superstep=superstep,
            grad_accum_windows=accum, log_every_steps=0,
            checkpoint_dir=str(ckpt_dir),
            snapshot_every_steps=snapshot_every,
            snapshot_keep=snapshot_keep, **train_kw))


@pytest.fixture(scope="module")
def corpus():
    buckets = make_series_buckets(140, seed=7)
    return featurize_buckets(buckets, FeaturizeConfig(round_to=8))


def _leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(state)]


def _assert_bit_identical(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


def _run_killed_then_resume(corpus, cfg_a_dir, cfg_b_dir, kill_at,
                            superstep=2, accum=1, mesh=None,
                            resume_mesh=None, snapshot_every=2):
    """Shared matrix driver: uninterrupted run A, run B preempted at the
    first step boundary >= kill_at, fresh-trainer resume of B.  Returns
    (trainer_a, state_a, hist_a, trainer_c, state_c, hist_c)."""
    cfg = _tiny_config(cfg_a_dir, snapshot_every=snapshot_every,
                       superstep=superstep, accum=accum)
    bundle = prepare_dataset(corpus, cfg.train)

    mesh_a = make_mesh(mesh) if mesh is not None else None
    tr_a = Trainer(cfg, bundle.feature_dim, bundle.metric_names,
                   mesh=mesh_a)
    state_a, hist_a = tr_a.fit(bundle)

    cfg_b = _tiny_config(cfg_b_dir, snapshot_every=snapshot_every,
                         superstep=superstep, accum=accum)
    mesh_b = make_mesh(mesh) if mesh is not None else None
    tr_b = Trainer(cfg_b, bundle.feature_dim, bundle.metric_names,
                   mesh=mesh_b)

    def preempt(global_step):
        if global_step >= kill_at:
            raise _SimulatedPreemption

    with pytest.raises(_SimulatedPreemption):
        tr_b.fit(bundle, on_step=preempt)

    # "new process": a FRESH trainer (fresh jit caches, fresh rng
    # plumbing), possibly on a different (shrunk) mesh
    mesh_c = make_mesh(resume_mesh) if resume_mesh is not None else mesh_b
    tr_c = Trainer(cfg_b, bundle.feature_dim, bundle.metric_names,
                   mesh=mesh_c)
    state_c, hist_c = tr_c.resume_training(bundle)
    return bundle, tr_a, state_a, hist_a, tr_c, state_c, hist_c


@pytest.mark.parametrize("superstep", [1, 2],
                         ids=["per-step-path", "superstep-path"])
def test_kill_inside_epoch_resume_bit_identical(corpus, tmp_path,
                                                superstep):
    """Kill mid-epoch (inside a superstep / between per-step dispatches);
    resume on the same mesh is bit-identical at the final step, and the
    final epoch's eval loss matches exactly."""
    _, _, state_a, hist_a, _, state_c, hist_c = _run_killed_then_resume(
        corpus, tmp_path / "a", tmp_path / "b", kill_at=3,
        superstep=superstep)
    _assert_bit_identical(state_a, state_c)
    assert hist_a[-1].test_loss == hist_c[-1].test_loss
    # the resumed history covers the interrupted epoch onward
    assert hist_c[0].epoch <= 1 and hist_c[-1].epoch == hist_a[-1].epoch


def test_kill_at_epoch_boundary_resume_bit_identical(corpus, tmp_path):
    """Kill after epoch 0 completed (its epoch-end snapshot already
    points the cursor at epoch 1, step 0): the resume replays nothing —
    it starts the next epoch exactly where the uninterrupted run did."""
    # cadence larger than the epoch so the ONLY snapshot is the
    # epoch-end one; kill on epoch 1's first step boundary
    epoch_steps = 4            # ceil(n_train_windows / 16), pinned below
    _, _, state_a, hist_a, tr_c, state_c, hist_c = \
        _run_killed_then_resume(
            corpus, tmp_path / "a", tmp_path / "b",
            kill_at=epoch_steps + 1, snapshot_every=100)
    assert hist_c[0].epoch == 1          # resumed AT the boundary
    _assert_bit_identical(state_a, state_c)
    assert hist_a[-1].test_loss == hist_c[-1].test_loss
    # epoch 1 trained from its start: full-epoch train means agree too
    assert hist_a[-1].train_loss == hist_c[-1].train_loss


def test_kill_mid_grad_accum_resume_bit_identical(corpus, tmp_path):
    """G=2 window-coalesced accumulation: the kill lands with a
    coalesced group un-snapshotted; the resume replays whole groups from
    the cursor and stays bit-identical (the group structure — summed
    grads, per-group dropout streams — survives preemption)."""
    _, _, state_a, hist_a, _, state_c, hist_c = _run_killed_then_resume(
        corpus, tmp_path / "a", tmp_path / "b", kill_at=3,
        superstep=2, accum=2)
    _assert_bit_identical(state_a, state_c)
    assert hist_a[-1].test_loss == hist_c[-1].test_loss


def test_kill_and_resume_on_shrunk_mesh(corpus, tmp_path):
    """Preempted on a 2×2×2 slice, resumed on the 1×1×1 that remains.

    The honest cross-mesh contract (the round-12 discipline — FULL bit
    parity ACROSS mesh shapes is physically unattainable, GSPMD's split
    contractions re-associate float adds, and Adam amplifies the ulps):
    (1) the state at the kill point restores BIT-exactly onto the shrunk
    mesh (assembly by global index), proven against the uninterrupted
    run's snapshot of the same step; (2) the resumed continuation on the
    shrunk mesh is DETERMINISTIC — two independent resumes from the same
    snapshot are bit-identical, i.e. resume-from-kill ≡ the
    uninterrupted continuation on the remaining mesh; (3) the resumed
    run reaches the uninterrupted run's final step with finite losses.
    (Same-mesh resume, where bit parity with the uninterrupted run IS
    attainable, is pinned by the tests above.)"""
    import shutil

    cube = MeshConfig(data=2, expert=2, model=2)
    cfg = _tiny_config(tmp_path / "a")
    bundle = prepare_dataset(corpus, cfg.train)
    tr_a = Trainer(cfg, bundle.feature_dim, bundle.metric_names,
                   mesh=make_mesh(cube))
    state_a, hist_a = tr_a.fit(bundle)

    cfg_b = _tiny_config(tmp_path / "b")
    tr_b = Trainer(cfg_b, bundle.feature_dim, bundle.metric_names,
                   mesh=make_mesh(cube))

    def preempt(global_step):
        if global_step >= 3:
            raise _SimulatedPreemption

    with pytest.raises(_SimulatedPreemption):
        tr_b.fit(bundle, on_step=preempt)
    kill_step = latest_cursor_step(str(tmp_path / "b"))
    assert kill_step is not None
    # freeze a pristine copy of the kill-time directory: the first
    # resume writes its own (newer) snapshots into b
    shutil.copytree(tmp_path / "b", tmp_path / "b2")

    # (1) cross-mesh restore exactness: the killed run's snapshot at K
    # assembles onto 1×1×1 bit-identical to the UNINTERRUPTED run's
    # snapshot of the same step (the two runs were bit-equal up to K)
    shrunk = Trainer(cfg_b, bundle.feature_dim, bundle.metric_names)
    t1 = shrunk.init_state(shrunk.sample_input(bundle))
    from_b, _ = restore_checkpoint(str(tmp_path / "b"), t1,
                                   step=kill_step)
    t2 = shrunk.init_state(shrunk.sample_input(bundle))
    from_a, _ = restore_checkpoint(str(tmp_path / "a"), t2,
                                   step=kill_step)
    _assert_bit_identical(from_a, from_b)

    # (2)+(3) two independent shrunk-mesh resumes agree bit-for-bit and
    # finish at the uninterrupted run's final step
    tr_c = Trainer(cfg_b, bundle.feature_dim, bundle.metric_names)
    state_c, hist_c = tr_c.resume_training(bundle,
                                           directory=str(tmp_path / "b"))
    tr_d = Trainer(cfg_b, bundle.feature_dim, bundle.metric_names)
    state_d, hist_d = tr_d.resume_training(bundle,
                                           directory=str(tmp_path / "b2"))
    _assert_bit_identical(state_c, state_d)
    assert [h.test_loss for h in hist_c] == [h.test_loss for h in hist_d]
    assert int(np.asarray(state_c.step)) == int(np.asarray(state_a.step))
    assert all(np.isfinite(h.train_loss) for h in hist_c)


def test_resume_without_snapshot_raises(corpus, tmp_path):
    cfg = _tiny_config(tmp_path, snapshot_every=0)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    with pytest.raises(FileNotFoundError, match="cursor"):
        tr.resume_training(bundle)


# ---------------------------------------------------------------------------
# elastic remeshing: survive device loss IN-PROCESS (round 20)
#
# The parity spec: the post-remesh trajectory must be BIT-IDENTICAL to
# the round-17 kill-process-and-resume_training-on-the-survivor-mesh
# path at the same snapshot (same rng cursor, same skip-forward).  The
# reference below uses the SAME FaultInjector without the elastic
# barrier — the loss raises before any cursor bookkeeping, exactly the
# crash a real device loss is — and a fresh trainer on the shrunk mesh
# resumes, so both paths restore the same newest durable snapshot.


def _run_elastic_vs_restart_resume(corpus, tmp_path, *, superstep, accum,
                                   losses):
    cfg_ref = _tiny_config(tmp_path / "ref", superstep=superstep,
                           accum=accum)
    bundle = prepare_dataset(corpus, cfg_ref.train)
    schedule = sorted(losses.items())

    # the round-17 restart-resume reference chain: one "process" per loss
    data_axis = 8
    state_ref = hist_ref = None
    kill_anchors = []          # latest cursor step AT each kill instant
    for i in range(len(schedule) + 1):
        tr = Trainer(cfg_ref, bundle.feature_dim, bundle.metric_names,
                     mesh=make_mesh(MeshConfig(data=data_axis)))
        if i < len(schedule):
            tr.install_fault_injector(FaultInjector(dict([schedule[i]])))
        try:
            if i == 0:
                state_ref, hist_ref = tr.fit(bundle)
            else:
                state_ref, hist_ref = tr.resume_training(bundle)
            break
        except DeviceLossError:
            kill_anchors.append(latest_cursor_step(str(tmp_path / "ref")))
            data_axis = shrink_mesh_config(
                MeshConfig(data=data_axis),
                data_axis - schedule[i][1]).data

    # elastic: ONE trainer, same schedule, recovery in-process
    cfg_e = _tiny_config(tmp_path / "e", superstep=superstep, accum=accum,
                         elastic=True, remesh_backoff_ms=1.0)
    tr_e = Trainer(cfg_e, bundle.feature_dim, bundle.metric_names,
                   mesh=make_mesh(MeshConfig(data=8)))
    tr_e.install_fault_injector(FaultInjector(dict(schedule)))
    state_e, hist_e = tr_e.fit(bundle)
    # both paths restored from the SAME durable anchor at every loss
    assert [r["restored_step"] for r in tr_e.remesh_history] \
        == kill_anchors
    return state_ref, hist_ref, tr_e, state_e, hist_e


@pytest.mark.parametrize("superstep,accum",
                         [(1, 1), (2, 1), (2, 2)],
                         ids=["per-step", "mid-superstep",
                              "mid-grad-accum"])
def test_elastic_remesh_bit_identical_to_restart_resume(
        corpus, tmp_path, superstep, accum):
    """Kill 4 of 8 devices at step 3 (per-step dispatch, mid-superstep,
    and mid-coalesced-group): the in-process remesh continues
    bit-identical to the kill-and-resume_training reference on the same
    survivor mesh, restoring the same snapshot."""
    state_ref, hist_ref, tr_e, state_e, hist_e = \
        _run_elastic_vs_restart_resume(
            corpus, tmp_path, superstep=superstep, accum=accum,
            losses={3: 4})
    _assert_bit_identical(state_ref, state_e)
    assert hist_ref[-1].test_loss == hist_e[-1].test_loss
    assert tr_e.remesh_count == 1
    assert tr_e.last_remesh["mesh"] == {"data": 4, "expert": 1,
                                        "model": 1}
    # obs: the recovery legs were measured
    assert tr_e.last_remesh["recovery_s"] > 0


def test_elastic_double_loss_shrinks_twice(corpus, tmp_path):
    """Two losses in one run (8 -> 4 -> 2), the second mid-epoch-1:
    still bit-identical to the twice-restarted reference chain."""
    state_ref, hist_ref, tr_e, state_e, hist_e = \
        _run_elastic_vs_restart_resume(
            corpus, tmp_path, superstep=2, accum=1, losses={3: 4, 7: 2})
    _assert_bit_identical(state_ref, state_e)
    assert hist_ref[-1].test_loss == hist_e[-1].test_loss
    assert tr_e.remesh_count == 2
    assert [r["mesh"]["data"] for r in tr_e.remesh_history] == [4, 2]


def test_elastic_attempt_budget_is_bounded(corpus, tmp_path):
    """More losses than remesh_max_attempts surfaces the typed
    RemeshExhaustedError (chaining the device loss) instead of
    respinning forever."""
    cfg = _tiny_config(tmp_path, elastic=True, remesh_backoff_ms=1.0,
                       remesh_max_attempts=1)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names,
                 mesh=make_mesh(MeshConfig(data=8)))
    tr.install_fault_injector(FaultInjector({2: 2, 5: 2}))
    with pytest.raises(RemeshExhaustedError) as exc:
        tr.fit(bundle)
    assert isinstance(exc.value.__cause__, DeviceLossError)
    assert tr.remesh_count == 1          # the budgeted recovery happened


def test_elastic_no_valid_mesh_is_typed(corpus, tmp_path):
    """Losing below expert*model devices cannot rebuild (the expert/
    model axes carry the parameter partitioning): NoValidMeshError, not
    a respin, not a silent shrink of the wrong axis."""
    cfg = _tiny_config(tmp_path, elastic=True, remesh_backoff_ms=1.0)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names,
                 mesh=make_mesh(MeshConfig(data=4, expert=2)))
    tr.install_fault_injector(FaultInjector({2: 7}))
    with pytest.raises(NoValidMeshError, match="expert"):
        tr.fit(bundle)


def test_elastic_requires_snapshots():
    """The config refuses elastic without a snapshot cadence (nothing to
    restore from), and fit refuses it without a checkpoint_dir."""
    with pytest.raises(ValueError, match="elastic"):
        TrainConfig(elastic=True)                # no snapshot cadence
    cfg = TrainConfig(elastic=True, snapshot_every_steps=2)
    assert cfg.elastic                           # cadence alone is valid


def test_elastic_fit_requires_checkpoint_dir(corpus):
    cfg = Config(
        model=ModelConfig(hidden_size=8, dropout_rate=0.5),
        train=TrainConfig(num_epochs=1, batch_size=16, window_size=12,
                          eval_stride=12, eval_max_cycles=2,
                          device_data="always", log_every_steps=0,
                          elastic=True, snapshot_every_steps=2))
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    with pytest.raises(ValueError, match="checkpoint_dir"):
        tr.fit(bundle)


def test_elastic_loss_before_first_snapshot_restarts_in_process(
        corpus, tmp_path):
    """A loss before anything durable exists re-inits on the shrunk mesh
    (what a restarted process would be forced to do) and completes."""
    cfg = _tiny_config(tmp_path, snapshot_every=100, elastic=True,
                       remesh_backoff_ms=1.0)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names,
                 mesh=make_mesh(MeshConfig(data=8)))
    tr.install_fault_injector(FaultInjector({1: 4}))
    state, hist = tr.fit(bundle)
    assert tr.remesh_count == 1
    assert tr.last_remesh["restored_step"] is None
    assert all(np.isfinite(h.train_loss) for h in hist)
    # the full run happened on the shrunk mesh from step 0
    assert int(np.asarray(state.step)) == 8


def test_stream_elastic_remesh_defers_refresh(tmp_path):
    """The StreamingTrainer joins the same barrier: a device loss
    mid-fine-tune remeshes + restores, the interrupted refresh DEFERS
    through it and completes (never dropped), and a DriftController-
    style queued trigger survives the remesh."""
    from deeprest_tpu.train.stream import StreamConfig, StreamingTrainer
    from deeprest_tpu.data.schema import Bucket, MetricSample

    cfg = Config(
        model=ModelConfig(feature_dim=32, hidden_size=8,
                          dropout_rate=0.0),
        train=TrainConfig(batch_size=8, window_size=6, seed=0,
                          eval_stride=1, eval_max_cycles=2,
                          log_every_steps=0, snapshot_every_steps=2,
                          steps_per_superstep=1, device_data="always",
                          elastic=True, remesh_backoff_ms=1.0),
        mesh=MeshConfig(data=8))
    st = StreamingTrainer(
        cfg, StreamConfig(refresh_buckets=30, finetune_epochs=1,
                          history_max=64, eval_holdout=4),
        ckpt_dir=str(tmp_path),
        feature_config=FeaturizeConfig(hash_features=True, capacity=32))
    rng = np.random.default_rng(0)

    def feed(n):
        for _ in range(n):
            st.ingest(Bucket(traces=[], metrics=[
                MetricSample("svc", "cpu", float(rng.random()))]))

    feed(40)
    r1 = st.refresh()
    assert dict(st.trainer.mesh.shape)["data"] == 8
    # queue an out-of-cadence trigger, then lose half the mesh during
    # the refresh it fires
    st.request_refresh("manual")
    st.trainer.install_fault_injector(
        FaultInjector({st.trainer._global_step + 2: 4}))
    feed(40)
    assert st.ready()
    r2 = st.refresh()
    assert r2.trigger == "manual"        # the queued trigger survived
    assert r2.refresh == r1.refresh + 1  # the refresh completed
    assert st.trainer.remesh_count == 1
    assert dict(st.trainer.mesh.shape)["data"] == 4
    assert np.isfinite(r2.eval_loss)
    assert not st.trainer.remesh_in_flight


# ---------------------------------------------------------------------------
# snapshot retention GC (snapshot_keep)


def test_snapshot_retention_gc_bounds_cursor_snapshots(corpus, tmp_path):
    """snapshot_every_steps used to accumulate checkpoints unboundedly;
    snapshot_keep prunes the oldest cursor snapshots after each durable
    newer save, never the restore target."""
    cfg = _tiny_config(tmp_path, snapshot_every=1, snapshot_keep=2)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state, _ = tr.fit(bundle)
    from deeprest_tpu.train.checkpoint import _has_full_cursor, load_sidecar

    cursor_steps = [s for s in list_steps(str(tmp_path))
                    if _has_full_cursor(load_sidecar(str(tmp_path), s,
                                                     missing_ok=True))]
    assert len(cursor_steps) == 2        # pinned: exactly keep survive
    assert latest_cursor_step(str(tmp_path)) == max(cursor_steps)
    # the retained newest restores fine
    template = tr.init_state(tr.sample_input(bundle))
    restored, extra = restore_checkpoint(str(tmp_path), template,
                                         step=max(cursor_steps))
    assert extra["train_cursor"]["global_step"] == max(cursor_steps)


def test_snapshot_gc_spares_non_cursor_checkpoints(corpus, tmp_path):
    """Epoch-cadence / refresh checkpoints (no full cursor) are other
    consumers' property: the GC never touches them, however old."""
    from deeprest_tpu.train.checkpoint import prune_cursor_snapshots

    cfg = _tiny_config(tmp_path / "gc", snapshot_every=0)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state = tr.init_state(tr.sample_input(bundle))
    # an OLD plain checkpoint (no cursor), then newer cursor snapshots
    save_checkpoint(str(tmp_path / "gc"), state, 1, {"plain": True})
    for step in (5, 6, 7):
        save_checkpoint(
            str(tmp_path / "gc"), state, step,
            {"train_cursor": {"epoch": 0, "steps_done": step,
                              "rng_state": {"state": step},
                              "global_step": step}})
    pruned = prune_cursor_snapshots(str(tmp_path / "gc"), keep=1)
    assert pruned == [5, 6]
    assert list_steps(str(tmp_path / "gc")) == [1, 7]


def test_snapshot_gc_never_races_a_concurrent_restore(corpus, tmp_path):
    """Pruning only ever deletes steps BELOW the newest `keep`, so a
    restore of the current target proceeds untouched while the GC runs;
    and keep < 1 is refused outright."""
    from deeprest_tpu.train.checkpoint import prune_cursor_snapshots

    cfg = _tiny_config(tmp_path, snapshot_every=1, snapshot_keep=0)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    tr.fit(bundle)
    target = latest_cursor_step(str(tmp_path))
    template = tr.init_state(tr.sample_input(bundle))
    results = {}

    def restore_loop():
        out, _ = restore_checkpoint(str(tmp_path), template, step=target)
        results["state"] = out

    t = threading.Thread(target=restore_loop)
    t.start()
    prune_cursor_snapshots(str(tmp_path), keep=1)
    t.join(timeout=120)
    assert not t.is_alive() and "state" in results
    assert latest_cursor_step(str(tmp_path)) == target
    with pytest.raises(ValueError, match=">= 1"):
        prune_cursor_snapshots(str(tmp_path), keep=0)


def test_elastic_cli_help_covers_flags(capsys):
    from deeprest_tpu.cli import build_parser

    for sub in ("train", "stream"):
        with pytest.raises(SystemExit):
            build_parser().parse_args([sub, "--help"])
        out = capsys.readouterr().out
        for flag in ("--elastic", "--remesh-max-attempts",
                     "--remesh-backoff-ms", "--snapshot-keep"):
            assert flag in out, f"{sub} --help missing {flag}"


# ---------------------------------------------------------------------------
# checkpoint durability: torn-write simulation


def test_torn_shard_restore_raises_cleanly(corpus, tmp_path):
    """Truncate one shard file under a published checkpoint: restore
    must raise a diagnosable ValueError, never load garbage into the
    trainer (the failure mode the pre-rename fsync exists to prevent
    for crashes; this simulates the already-torn artifact)."""
    cfg = _tiny_config(tmp_path / "ck", snapshot_every=0)
    bundle = prepare_dataset(corpus, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state = tr.init_state(tr.sample_input(bundle))
    path = save_checkpoint(str(tmp_path / "ck"), state, 1, {"v": 1})
    arrays = os.path.join(path, "arrays")
    # tear the LARGEST shard (a params matrix — mid-file truncation)
    victim = max((os.path.join(arrays, f) for f in os.listdir(arrays)),
                 key=os.path.getsize)
    size = os.path.getsize(victim)
    with open(victim, "r+b") as f:
        f.truncate(size // 2)
    template = tr.init_state(tr.sample_input(bundle))
    with pytest.raises(ValueError, match="truncated|corrupt"):
        restore_checkpoint(str(tmp_path / "ck"), template, step=1)


def test_stream_snapshot_rides_full_sidecar(corpus, tmp_path):
    """Mid-refresh stream snapshots carry the FULL stream sidecar
    (metric set, stats, refresh counter, ring watermark), so a stream
    killed mid-refresh resumes from them like from any refresh
    checkpoint."""
    from deeprest_tpu.train.stream import StreamConfig, StreamingTrainer
    from deeprest_tpu.data.schema import Bucket, MetricSample

    cfg = Config(
        model=ModelConfig(feature_dim=32, hidden_size=8,
                          dropout_rate=0.0),
        train=TrainConfig(batch_size=8, window_size=6, seed=0,
                          eval_stride=1, eval_max_cycles=2,
                          log_every_steps=0, snapshot_every_steps=2,
                          steps_per_superstep=1))
    st = StreamingTrainer(
        cfg, StreamConfig(refresh_buckets=30, finetune_epochs=1,
                          history_max=64, eval_holdout=4),
        ckpt_dir=str(tmp_path),
        feature_config=FeaturizeConfig(hash_features=True, capacity=32))
    rng = np.random.default_rng(0)
    for t in range(40):
        st.ingest(Bucket(
            traces=[], metrics=[MetricSample("svc", "cpu",
                                             float(rng.random()))]))
    st.refresh()
    steps = list_steps(str(tmp_path))
    assert steps, "refresh wrote no checkpoints"
    # every step (mid-refresh snapshot or refresh-end save) must carry
    # the stream keys + ring watermark; snapshots also carry the light
    # cursor (epoch=None — streams do not plan-replay)
    from deeprest_tpu.train.checkpoint import load_sidecar

    saw_watermark = False
    for step in steps:
        extra = load_sidecar(str(tmp_path), step)
        assert "metric_names" in extra and "x_stats" in extra
        wm = extra.get("stream_ring_watermark")
        if wm is not None:
            saw_watermark = True
            assert wm["ingested_total"] == 40
            assert wm["retained_buckets"] == 40
    assert saw_watermark
    # a resumed stream adopts the watermark
    st2 = StreamingTrainer(
        cfg, StreamConfig(refresh_buckets=30, finetune_epochs=1,
                          history_max=64, eval_holdout=4),
        ckpt_dir=str(tmp_path),
        feature_config=FeaturizeConfig(hash_features=True, capacity=32))
    assert st2._ingested_total == 40


# ---------------------------------------------------------------------------
# router health: ejection, bounded retry, probe-and-rejoin (fake replicas)


class _FakeReplica:
    """Minimal replica implementing the router protocol with scriptable
    failures — the fast, deterministic half of the chaos matrix."""

    kind = "thread"

    def __init__(self, name, fail_times=0, retriable=True, alive=True,
                 result="ok"):
        self.name = name
        self.device = None
        self.fail_times = fail_times
        self.retriable = retriable
        self.alive_flag = alive
        self.result = result
        self.calls = 0
        self.restarts = 0
        self._meta = {
            "metric_names": ["m0"], "window_size": W, "feature_dim": 6,
            "quantiles": [0.05, 0.5, 0.95], "median_index": 1,
            "delta_mask": None,
        }

    def outstanding(self):
        return 0

    def available(self):
        return True

    def alive(self):
        return self.alive_flag

    def served_requests(self):
        return self.calls

    def served_windows(self):
        return self.calls

    def predict_series(self, traffic, integrate=True):
        self.calls += 1
        if self.fail_times > 0:
            self.fail_times -= 1
            raise ReplicaDeadError(f"{self.name} down",
                                   replica=self.name,
                                   retriable=self.retriable)
        return self.result

    def predict_series_many(self, series_list, integrate=True):
        return [self.predict_series(s, integrate) for s in series_list]

    def drain(self):
        pass

    def resume(self):
        pass

    def wait_idle(self, timeout_s=0):
        return True

    def close(self):
        pass

    def stats(self):
        return {"name": self.name, "kind": self.kind,
                "outstanding_windows": 0,
                "served_requests": self.calls, "served_windows": 0,
                "state": "live"}


def _router(replicas, **cfg):
    cfg.setdefault("probe_interval_s", 30.0)   # probe parked off-stage
    return ReplicaRouter(list(replicas), config=RouterConfig(**cfg))


def test_retry_on_survivor_after_worker_death():
    dead = _FakeReplica("r0", fail_times=5, retriable=True, alive=False)
    good = _FakeReplica("r1", result="good")
    router = _router([dead, good], retry_budget=1, eject_after_failures=3)
    try:
        outs = {router.predict_series(np.zeros((W, 6))) for _ in range(4)}
        assert outs == {"good"}
        stats = router.router_stats()
        by_name = {r["name"]: r for r in stats["replicas"]}
        # confirmed-dead replica ejects on its FIRST failure
        assert by_name["r0"]["health"]["ejected"] is True
        assert stats["health"]["ejections"] == 1
        assert stats["health"]["retries"] >= 1
        # after ejection, dispatch never touches r0 again
        calls_before = dead.calls
        router.predict_series(np.zeros((W, 6)))
        assert dead.calls == calls_before
    finally:
        router.close()


def test_non_retriable_failure_is_503_without_retry():
    """A deadline expiry on a LIVE worker must never re-execute: the
    router answers 503 and the survivor sees no retried call."""
    wedged = _FakeReplica("r0", fail_times=1, retriable=False, alive=True)
    bystander = _FakeReplica("r1")
    router = _router([wedged, bystander], retry_budget=3,
                     eject_after_failures=1)
    try:
        # make the wedged replica the deterministic first pick
        router.eject("r1")
        with pytest.raises(ServingError) as exc:
            router.predict_series(np.zeros((W, 6)))
        assert exc.value.status == 503
        assert "double-execution" in str(exc.value)
        assert bystander.calls == 0
    finally:
        router.close()


def test_retry_budget_exhaustion_is_fast_503():
    all_dead = [_FakeReplica(f"r{i}", fail_times=10, retriable=True,
                             alive=False) for i in range(3)]
    router = _router(all_dead, retry_budget=1, eject_after_failures=1)
    try:
        with pytest.raises(ServingError) as exc:
            router.predict_series(np.zeros((W, 6)))
        assert exc.value.status == 503
        # fast because bounded: total attempts are budget + 1
        assert sum(r.calls for r in all_dead) == 2
    finally:
        router.close()


def test_all_replicas_ejected_sheds_fast_until_rejoin():
    r = _FakeReplica("r0", result="back")
    router = _router([r], eject_after_failures=1, probe_interval_s=0.3)
    try:
        router.eject("r0", reason="chaos schedule")
        with pytest.raises(ServingError) as exc:
            router.predict_series(np.zeros((W, 6)))
        assert exc.value.status == 503
        assert r.calls == 0, "an ejected plane sheds without dispatching"
        # the probe rejoins the thread replica (no restart to perform)
        deadline = time.monotonic() + 5.0
        while True:
            stats = router.router_stats()
            if stats["replicas"][0]["health"]["ejected"] is False:
                break
            assert time.monotonic() < deadline, "probe never rejoined"
            time.sleep(0.02)
        assert router.predict_series(np.zeros((W, 6))) == "back"
        assert stats["health"]["rejoins"] == 1
    finally:
        router.close()


def test_consecutive_failure_threshold_ejects_and_probe_restarts():
    class _FakeProcessReplica(_FakeReplica):
        kind = "process"

        def restart(self):
            self.restarts += 1
            self.fail_times = 0
            self.alive_flag = True

    flaky = _FakeProcessReplica("p0", fail_times=2, retriable=True,
                                alive=True)
    good = _FakeReplica("r1", result="ok")
    router = _router([flaky, good], retry_budget=1,
                     eject_after_failures=2, probe_interval_s=0.05)
    try:
        # two failures (each retried onto r1) reach the threshold; the
        # RR tie-break alternates picks, so a few requests guarantee p0
        # is dispatched (and fails) twice
        for _ in range(6):
            assert router.predict_series(np.zeros((W, 6))) == "ok"
        deadline = time.monotonic() + 5.0
        while flaky.restarts == 0:
            assert time.monotonic() < deadline, "probe never restarted p0"
            time.sleep(0.02)
        deadline = time.monotonic() + 5.0
        while router.router_stats()["replicas"][0]["health"]["ejected"]:
            assert time.monotonic() < deadline
            time.sleep(0.02)
        assert router.router_stats()["health"]["rejoins"] == 1
    finally:
        router.close()


# ---------------------------------------------------------------------------
# real worker subprocesses: deadline, SIGKILL mid-request, rejoin


def _proc_spec(delay_s=0.0):
    spec = {"factory": "router_test_support:build_slow",
            "kwargs": {"delay_s": delay_s, "ladder": [8]},
            "sys_path": [os.path.dirname(os.path.abspath(__file__))]}
    if not delay_s:
        spec["factory"] = "router_test_support:build_tiny"
        spec["kwargs"] = {"ladder": [8]}
    return spec


def test_process_replica_deadline_turns_wedge_into_typed_error():
    """A worker that outlives the per-request deadline while staying
    ALIVE surfaces ReplicaDeadError(retriable=False) — the wedged-worker
    half of the satellite bug (the dead-worker half is covered by the
    SIGKILL test: the reader fails the future on pipe EOF)."""
    traffic = np.random.default_rng(0).random((W, 6)).astype(np.float32)
    rep = ProcessReplica(_proc_spec(delay_s=30.0), name="p0",
                         boot_timeout_s=300.0, request_timeout_s=1.0)
    try:
        # the worker would answer after 30 s: an error at all is the
        # 1 s deadline's
        with pytest.raises(ReplicaDeadError) as exc:
            rep.predict_series(traffic)
        assert exc.value.retriable is False
        assert "alive" in str(exc.value)
        assert rep.alive()
        assert rep.outstanding() == 0
    finally:
        rep.close()
    assert not rep.alive()


def test_sigkill_mid_request_retries_on_survivor_and_rejoins():
    """The end-to-end chaos contract on real workers: SIGKILL one mid-
    request → the in-flight request re-dispatches onto the survivor and
    returns a byte-identical answer (never a hang, never a wrong
    answer); the dead replica ejects, the probe reboots it, and the
    plane is whole again — with no leaked children after close."""
    import multiprocessing

    traffic = np.random.default_rng(0).random((2 * W, 6)).astype(
        np.float32)
    reference = build_tiny(ladder=(8,)).predict_series(traffic)

    baseline_children = len(multiprocessing.active_children())
    spec = _proc_spec(delay_s=1.5)
    reps = []
    try:
        for i in range(2):
            reps.append(ProcessReplica(spec, name=f"p{i}",
                                       boot_timeout_s=300.0,
                                       request_timeout_s=20.0))
        router = ReplicaRouter(
            reps, config=RouterConfig(retry_budget=1,
                                      eject_after_failures=1,
                                      probe_interval_s=0.2,
                                      replica_timeout_s=20.0))
        result = {}

        def client():
            result["out"] = router.predict_series(traffic)

        t = threading.Thread(target=client)
        t.start()
        # wait until the request is in flight on one replica, then
        # SIGKILL that worker mid-predict
        deadline = time.monotonic() + 30.0
        victim = None
        while victim is None:
            assert time.monotonic() < deadline, "request never dispatched"
            for rep in reps:
                if rep.outstanding() > 0:
                    victim = rep
                    break
            time.sleep(0.01)
        os.kill(victim._proc.pid, signal.SIGKILL)
        t.join(timeout=60.0)
        assert not t.is_alive(), "request hung past every deadline"
        assert np.array_equal(result["out"], reference), \
            "retried answer diverged from the healthy plane"
        # the victim ejected; the probe reboots and rejoins it
        deadline = time.monotonic() + 120.0
        while True:
            stats = router.router_stats()
            by_name = {r["name"]: r for r in stats["replicas"]}
            h = by_name[victim.name]["health"]
            if not h["ejected"] and victim.alive():
                break
            assert time.monotonic() < deadline, \
                f"victim never rejoined: {stats['health']}"
            time.sleep(0.2)
        assert stats["health"]["ejections"] >= 1
        assert stats["health"]["retries"] >= 1
        assert stats["health"]["rejoins"] >= 1
        # the rebooted worker serves byte-identically
        assert np.array_equal(router.predict_series(traffic), reference)
        router.close()
        reps = []          # close() reaped them
    finally:
        for rep in reps:
            rep.close()
    # no zombie children: everything reaped back to the baseline
    deadline = time.monotonic() + 10.0
    while len(multiprocessing.active_children()) > baseline_children:
        assert time.monotonic() < deadline, "leaked worker subprocesses"
        time.sleep(0.1)


# ---------------------------------------------------------------------------
# the storm gate (committed artifact pin + slow full run)


def test_committed_chaos_bench_gates():
    """The committed benchmarks/chaos_bench.json is the acceptance
    evidence for the storm: zero wrong answers, errors only fast
    429/503, no request past its deadline envelope, automatic rejoin,
    a clean post-storm thread/process/fd/device-buffer census, and (v2)
    the elastic arm's bit-identical-to-restart-resume remesh gates."""
    with open(os.path.join(REPO, "benchmarks", "chaos_bench.json"),
              encoding="utf-8") as f:
        committed = json.load(f)
    assert committed["schema_version"] == 2
    assert committed["pass"] is True
    for arm_name in ("thread", "process"):
        arm = committed["arms"][arm_name]
        assert arm["wrong_answers"] == 0, arm_name
        assert arm["other_status"] == 0, arm_name
        assert arm["ok"] >= 1
        assert arm["max_request_wall_s"] <= arm["envelope_s"]
        assert arm["ejections"] >= 1 and arm["rejoins"] >= 1
        assert arm["recovery_s"] <= arm["recovery_envelope_s"]
        assert arm["leak"]["clean"] is True
        # v2: the census sees device memory — a closed plane must free
        # its replica stacks' buffers (the collector-pin leak this
        # column caught on its first run)
        assert (arm["leak"]["after"]["device_buffers"]
                <= arm["leak"]["before"]["device_buffers"]), arm_name
    elastic = committed["arms"]["elastic"]
    assert elastic["pass"] is True
    assert elastic["bit_identical"] is True
    assert elastic["executables_flat"] is True
    assert elastic["remeshes"] >= 3           # storms all three paths
    assert elastic["max_recovery_s"] <= elastic["recovery_envelope_s"]
    assert elastic["leak"]["clean"] is True
    for cell_name, cell in elastic["scenarios"].items():
        assert cell["remeshes"] == cell["expected_remeshes"], cell_name
        assert cell["bit_identical"] is True, cell_name
        assert cell["final_test_loss_equal"] is True, cell_name


@pytest.mark.slow
def test_chaos_bench_quick_storm(tmp_path):
    """The live storm, quick arm: SIGKILLs + scheduled ejections under
    HTTP load, asserting the same gates the committed record pins."""
    import subprocess
    import sys

    out = tmp_path / "chaos_bench.json"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "chaos_bench.py"),
         "--quick", "--out", str(out)],
        capture_output=True, text=True, timeout=900, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["pass"] is True
    assert result["quick"] is True
    for name, arm in result["arms"].items():
        assert arm["leak"]["clean"] is True, name
        if name == "elastic":
            assert arm["bit_identical"] is True
            assert arm["executables_flat"] is True
        else:
            assert arm["wrong_answers"] == 0, name
