"""`endpoints-10k-accum8` (ISSUE 48): G microbatches to one optimizer update
through THE superstep, held on the CPU at a small size in float32.

The deployment's guarantee: every update is plain Adam on the gradient of
the weighted mean pinball loss over ALL real windows of the update's G
microbatches, what one batch of G x B windows gives, each microbatch under
its own kept mask; on a compact base the carried rows and row-wise Adam as
at G = 1.  Held here:

(a) the accumulated gradient (Adam's first moment after one update) against
    the gradient of ONE batch of G x B windows through the plain reference's
    forward under the concatenated masks, a ragged group and a padded
    microbatch included, with dropout and without: the mean over the real
    windows, at a tolerance no chip control reaches;
(b) three updates of the window's own compiled superstep, the third ragged,
    against chipbench/reference/qrnn_accum_ref.py by the `train_accum`
    runner's own functions, and the four controls of its limits;
(c) on a compact base with STALE rows, two dispatches of G = 2 against the
    dense form's whole-leaf Adam: the off-table pass counts updates;
(d) on a virtual mesh `data` = 2 and 4 (the carried rows split), G = 2 gives
    the state one device gives;
(e) at G = 1 the superstep lowers to the program it lowered to before;
(f) what the program records: the counter, the gauge, the scope, the epoch
    span's tag, the `set-up:` line, the optimizer rows' gauge.

No number of this file is a device number.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.generators import corpus
from chipbench.reference import qrnn_accum_ref as accum_ref
from chipbench.reference import qrnn_ref as ref
from chipbench.runners import train_accum as runner
from deeprest_tpu.config import Config, MeshConfig, ModelConfig, TrainConfig
from deeprest_tpu.obs import metrics
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.ops import scopes
from deeprest_tpu.parallel.distributed import stage_plan, stage_sparse_base
from deeprest_tpu.parallel.mesh import make_mesh
from deeprest_tpu.train import Trainer
from test_live_columns import ATOL, B, F, RTOL, _bundle, _corpus, _trainer
from test_obs_layers import _recorded
from test_sparse_adam import (
    PARENT_SHA1, _leaves, _setup, _stale_rows_for, _with_moments_at,
    superstep_sha1,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESOURCES = ["cpu", "memory", "write-iops", "write-tp", "usage"]
QUANTILES = (0.05, 0.5, 0.95)
SEED = 3_000_000_048           # as large as the driver's


# -- (a) the accumulated gradient is one batch's ------------------------------


def _weights(g: int) -> np.ndarray:
    """``[1, G, B]``: whole microbatches, then a ragged one (a quarter of
    its windows real) and, from G = 4 on, a padded one."""
    weights = np.ones((1, g, B), np.float32)
    ragged = g - 1 if g < 4 else g - 2
    weights[0, ragged, B // 4:] = 0.0
    weights[0, ragged + 1:] = 0.0
    return weights


@pytest.mark.parametrize("rate", [0.5, 0.0], ids=["dropout", "no-dropout"])
@pytest.mark.parametrize("g", [2, 4])
def test_the_accumulated_gradient_is_one_batch_of_g_times_b_windows(g, rate):
    cols, vals, y, _ = _corpus(100)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(dropout=rate, grad_accum_windows=g,
                       steps_per_superstep=g)
    x_base, y_base = staged = trainer.stage_dataset(bundle)
    assert x_base.live is not None               # the compact form
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    params = jax.tree.map(jnp.array, state.params)
    rng, step = jnp.array(state.rng), int(state.step)    # state is donated
    weights = _weights(g)
    starts = np.random.default_rng(2).choice(
        bundle.num_train_windows, (1, g, B), replace=False).astype(np.int32)

    got, losses = trainer._superstep(
        state, *staged, *stage_plan(trainer.mesh, starts, weights), 0)
    assert int(got.opt_state[0].count) == 1
    assert int(got.step) == step + int((weights.sum(axis=2) > 0).sum())
    accumulated = {k: np.asarray(v) / (1 - ref.ADAM["b1"])
                   for k, v in got.opt_state[0].mu.items()}

    # ONE batch of G x B windows: the same rows, dense, through the plain
    # reference's forward under the microbatches' masks side by side
    idx = starts.reshape(-1)[:, None] + np.arange(bundle.window_size)
    x = jnp.asarray(_dense_rows(cols, vals)[idx])             # [GB, W, F]
    targets = jnp.asarray(y[idx])                             # [GB, W, E]
    w = jnp.asarray(weights.reshape(-1))
    e, h = params["mask_w1"].shape
    update_key = jax.random.fold_in(rng, step)
    keep = None if not rate else jnp.concatenate([
        ref.dropout_keep(jax.random.fold_in(update_key, i),
                         (e, B, bundle.window_size, 2 * h), rate)
        for i in range(g)], axis=1)

    def one_batch(p):
        preds = ref.forward(p, x, "f32", keep, rate)
        q = jnp.asarray(QUANTILES, jnp.float32)
        err = targets[..., None] - preds
        per = jnp.sum(jnp.maximum((q - 1.0) * err, q * err), axis=-1)
        return jnp.sum(w * jnp.mean(per, axis=(1, 2))) / jnp.sum(w)

    want = jax.jit(jax.grad(one_batch))(params)
    for name, z in want.items():
        z = np.asarray(z)
        gap = np.linalg.norm(accumulated[name] - z) / np.linalg.norm(z)
        assert gap <= 1e-6, (name, gap)
    # each real microbatch's loss is its own mean, a padded one's is 0
    real = weights[0].sum(axis=1) > 0
    assert (np.asarray(losses)[:g][real] > 0).all()
    assert (np.asarray(losses)[:g][~real] == 0).all()


def _dense_rows(cols, vals):
    """[T, F] normalised rows by the bundle's rule (global min 0, max)."""
    rows = np.zeros((len(cols), F), np.float32)
    np.add.at(rows, (np.arange(len(cols))[:, None], cols), vals)
    return rows / np.float32(vals.max())


# -- (b) three updates against the reference, by the runner's functions -------

# 2 components x 5 resources over 512 hashed call paths of which 100 are
# hot (the compact form, a table of 128), float32; G = 4 is the fewest
# microbatches the check's ragged group can be cut from.
E_B, H_B, W_B, B_B, K_B, G_B = 10, 8, 6, 4, 16, 4
DIMS = (E_B, F, H_B, len(QUANTILES))
# Program and reference both compute in float32 here, the reference at
# `highest`: what is left is the order of the sums.  Read at this size:
# 1.3e-7 (loss), 5.6e-7 (first gradient), 1.5e-7 (the leaves' change); the
# limits leave some ten times that and no more (tests/test_live4k.py's
# rule).  The controls read, where they show: a lost microbatch 0.25 (first
# gradient); the ignored weights 0.0071 (change; nothing
# else can see the last update); the sum 3.0 = G - 1 and 0.55.
TOLERANCE = {"loss_rel_gap": 2e-6, "grad_norm_gap": 5e-6,
             "delta_norm_gap": 2e-6}


@pytest.fixture(scope="module")
def three_updates():
    """The `train_accum` runner's phases 1 to 3 and 6 at the small size."""
    mcfg = ModelConfig(feature_dim=F, num_metrics=E_B, hidden_size=H_B,
                       quantiles=QUANTILES, dropout_rate=0.5,
                       compute_dtype="float32")
    tcfg = TrainConfig(batch_size=B_B, window_size=W_B, train_split=0.4,
                       seed=SEED % (2 ** 31 - 1), sparse_feed=True,
                       sparse_nnz_cap=K_B, steps_per_superstep=8,
                       log_every_steps=0, grad_accum_windows=G_B)
    raw = corpus.generate(
        {"buckets": 400, "hot_paths": 100, "nnz_lo": 3, "nnz_hi": 12,
         "day": 100, "resources": RESOURCES}, SEED,
        {"feature_dim": F, "num_metrics": E_B})
    bundle = runner.dataset(raw, tcfg, F)
    starts, weights = runner.check_starts(raw, tcfg, SEED, bundle)
    assert starts.shape == weights.shape == (3, G_B, B_B)
    assert weights.sum(axis=2).tolist() == [[4] * 4, [4] * 4, [4, 1, 0, 0]]
    real = starts[weights > 0]
    assert len(set(real.tolist())) == len(real) == 37

    trainer = Trainer(Config(model=mcfg, train=tcfg), bundle.feature_dim,
                      bundle.metric_names)
    key = jax.random.PRNGKey(tcfg.seed)
    state = trainer.init_state(trainer.sample_input(bundle))
    seeded = ref.init_params(key, *DIMS)
    state = state.replace(params={
        k: jax.device_put(seeded[k], state.params[k].sharding)
        for k in state.params})
    staged = trainer.stage_dataset(bundle)
    assert staged[0].live is not None

    class Ctx:                  # what `checked_updates` asks of a context
        @staticmethod
        def memory_peak_bytes():
            return 0

    num_steps = -(-bundle.num_train_windows // B_B)
    state, program = runner.checked_updates(
        Ctx, trainer, state, staged, starts, weights, num_steps, key, DIMS)
    groups = runner.check_groups(raw, tcfg, starts)

    def reference(precision="f32", control=None):
        return accum_ref.train_three_updates(
            ref.init_params(key, *DIMS), groups, weights, tcfg.seed,
            QUANTILES, 0.5, precision, control)

    return {"program": program, "reference": reference, "f32": reference()}


def test_three_updates_through_the_superstep_against_the_reference(
        three_updates):
    program, f32 = three_updates["program"], three_updates["f32"]
    assert program["steps_counted"] == f32["steps"] == 10
    assert program["updates_counted"] == f32["updates"] == 3
    assert len(program["losses"]) == len(f32["losses"]) == 10
    gaps = runner.compare(program, f32)
    for number, limit in TOLERANCE.items():
        assert gaps[number] <= limit, (gaps, program, f32)


@pytest.mark.parametrize("control, number, at_least", [
    # a microbatch's add of four lost: the first gradient some 3/4 as long
    ("lost_microbatch", "grad_norm_gap", 0.2),
    # the ragged third update's microbatch of 1 window counted as one of 4:
    # only the last update moves, so only the leaves' change can show it
    ("ignored_weights", "delta_norm_gap", 0.005),
    # the sum where the guarantee means: the first gradient G times as long
    ("summed", "grad_norm_gap", G_B - 1 - 0.01),
])
def test_the_controls_of_the_limits_are_seen(three_updates, control, number,
                                             at_least):
    gaps = runner.compare(three_updates["reference"](control=control),
                          three_updates["f32"])
    assert gaps[number] >= at_least > 1000 * TOLERANCE[number], gaps


def test_the_cells_files_say_what_the_runner_reads():
    """The configuration is `endpoints-10k`'s model and train letter for
    letter plus the one key; the mix is `week-sparse`'s generator and
    params; the cell is in the benchmark with both new metrics."""
    def load(*path):
        with open(os.path.join(REPO, *path)) as fh:
            return json.load(fh)

    base = load("chipbench", "configs", "endpoints-10k.json")
    cfg = load("chipbench", "configs", "endpoints-10k-accum8.json")
    assert cfg["model"] == base["model"]
    assert cfg["train"] == {**base["train"], "grad_accum_windows": 8}
    assert cfg["runners"] == ["train_accum"] and cfg["chips"] == 1
    mix, sparse = (load("chipbench", "traffic", n + ".json")
                   for n in ("week-sparse-accum8", "week-sparse"))
    assert (mix["generator"], mix["params"]) == (sparse["generator"],
                                                 sparse["params"])
    assert mix["runner"] == "train_accum"
    bench = load("BENCHMARK.json")
    # ISSUE 54's cell was appended behind it
    cell = bench["workloads"][-2]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "tenk-train-accum8", "endpoints-10k-accum8", "week-sparse-accum8", 1)
    listed = {m["name"]: m["workloads"]
              for m in bench["end_to_end"] + bench["per_layer"]
              if "workloads" in m}
    for name, cells in listed.items():
        assert ("tenk-train-accum8" in cells) == (
            "tenk-train-sparse" in cells or name == "accum_carry_mb.train")
    assert listed["accum_carry_mb.train"] == ["tenk-train-accum8"]
    assert len(listed["updates_per_epoch.train"]) == len(bench["workloads"])
    assert set(load("chipbench", "limits", "tenk-train-accum8.json")[
        "limits"]) == set(TOLERANCE)


# -- (c) stale rows: the off-table pass counts updates ------------------------


def test_stale_rows_move_by_updates_not_microbatches():
    """A compact base, moments at rows off its table (a chunk and a row of
    them), G = 2, two dispatches of 4 and of 3 real microbatches (the last
    group one microbatch): the stale rows get four zero-gradient Adam
    updates, two a dispatch, as the dense form's whole-leaf Adam gives them
    on the same plan, and not the seven that microbatches would count."""
    def run(dense: bool):
        trainer, bundle, staged = _setup_g2()
        table = np.asarray(staged[0].live)
        if dense:
            cols, vals, _, _ = _corpus(100)
            mn = np.zeros((1,), np.float32)
            rg = np.array([vals.max()], np.float32)
            staged = (stage_sparse_base(trainer.mesh, cols, vals, mn, rg, F),
                      staged[1])
        rows = _stale_rows_for(64 + 1, table)
        state = _with_moments_at(
            trainer.init_state(trainer.sample_input(bundle), seed=1), rows)
        before = _leaves(state)
        plan = _plan_g2(trainer, bundle, 7)
        for c in range(2):
            state, _ = trainer._superstep(state, *staged, *plan, c)
        return before, state, rows

    before, want, rows = run(dense=True)
    _, got, _ = run(dense=False)
    assert int(got.step) == int(want.step) == 7
    assert int(got.opt_state[0].count) == int(want.opt_state[0].count) == 4
    got, want = _leaves(got), _leaves(want)
    for name, z in want.items():
        np.testing.assert_allclose(got[name], z, rtol=RTOL, atol=ATOL,
                                   err_msg=name)
        if "w_ih" in name and ".params" in name:
            # off the table no gradient enters: the same four steps in both
            # forms (to what an FMA contracts), and they did move
            np.testing.assert_allclose(
                got[name][:, rows], z[:, rows], rtol=0.0, err_msg=name,
                atol=2 * float(np.spacing(np.abs(z).max())))
            assert (got[name][:, rows] != before[name][:, rows]).any()


def _setup_g2(mesh=None):
    cols, vals, y, _ = _corpus(100)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(mesh=mesh, grad_accum_windows=2, steps_per_superstep=4)
    staged = trainer.stage_dataset(bundle)
    assert staged[0].width == 128
    return trainer, bundle, staged


def _plan_g2(trainer, bundle, steps: int, seed: int = 5):
    """A staged ``[2, 4, B]`` plan of ``steps`` real microbatches."""
    rng = np.random.default_rng(seed)
    starts = rng.integers(0, bundle.num_train_windows,
                          (8, B)).astype(np.int32)
    weights = np.zeros((8, B), np.float32)
    weights[:steps] = 1.0
    return stage_plan(trainer.mesh, starts.reshape(2, 4, B),
                      weights.reshape(2, 4, B))


# -- (d) under a mesh whose `data` axis splits the carried rows ----------------


@pytest.mark.parametrize("data", [2, 4])
def test_accumulation_under_a_data_axis_gives_one_devices_state(data):
    def run(mesh):
        trainer, bundle, staged = _setup_g2(mesh)
        state = trainer.init_state(trainer.sample_input(bundle), seed=1)
        plan = _plan_g2(trainer, bundle, 7)
        losses = []
        for c in range(2):
            state, chunk = trainer._superstep(state, *staged, *plan, c)
            losses.append(np.asarray(chunk))
        return trainer, state, np.concatenate(losses)

    _, want, want_losses = run(None)
    trainer, got, got_losses = run(make_mesh(MeshConfig(data=data)))
    from deeprest_tpu.parallel.sharding import carried_rows_split

    assert carried_rows_split(trainer.mesh, 128) == data
    assert int(got.step) == 7 and int(got.opt_state[0].count) == 4
    np.testing.assert_allclose(got_losses, want_losses, rtol=1e-6)
    got, want = _leaves(got), _leaves(want)
    for name, z in want.items():
        np.testing.assert_allclose(got[name], z, rtol=2e-4, atol=1e-7,
                                   err_msg=name)


# -- (e) at G = 1 nothing changes ----------------------------------------------


def test_one_microbatch_an_update_is_the_superstep_of_before():
    """G is a static of the trace: with ``grad_accum_windows=1`` spelled out
    the compact superstep lowers to the text pinned before the knob reached
    it (tests/test_sparse_adam.py holds the three feeds' digests), and
    nothing of it is under ``accumulate``."""
    def build():
        cols, vals, y, _ = _corpus(100)
        bundle = _bundle(cols, vals, y)
        trainer = _trainer(steps_per_superstep=4, grad_accum_windows=1)
        return trainer, bundle, trainer.stage_dataset(bundle)

    assert superstep_sha1(build) == PARENT_SHA1["sparse-compact"]
    assert superstep_sha1(_setup) == PARENT_SHA1["sparse-compact"]


# -- (f) what the program records ----------------------------------------------


@pytest.fixture
def registry(monkeypatch):
    monkeypatch.setattr(metrics, "REGISTRY", metrics.MetricsRegistry())
    monkeypatch.setattr(obs_setup, "REGISTRY", metrics.REGISTRY)
    return metrics.REGISTRY


def test_an_epoch_under_accumulation_records_what_the_benchmark_reads(
        registry, tmp_path):
    trainer, bundle, staged = _setup_g2()
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    steps = -(-bundle.num_train_windows // B)
    spans = _recorded(lambda: trainer.train_epoch(
        state, bundle, np.random.default_rng(0), staged=staged))
    (epoch,) = [s for s in spans if s.name == "train.epoch"]
    assert epoch.tags["accum"] == 2
    # updates: a group a pair of microbatches, a chunk's ragged last one too
    chunks = [min(4, steps - c) for c in range(0, steps, 4)]
    assert registry.get("deeprest_train_optimizer_updates_total").value() \
        == sum(-(-real // 2) for real in chunks)
    assert registry.get("deeprest_train_epochs_total").value() == 1
    gauge = registry.get(obs_setup.ACCUMULATION)
    assert gauge.value(kind="microbatches") == 2
    # the accumulator: the carried rows of the two w_ih leaves, not the leaves
    params = trainer.init_state(trainer.sample_input(bundle), seed=1).params
    want = sum(a.size * 4 for k, a in params.items() if "w_ih" not in k) \
        + sum(a.size // F * 128 * 4 for k, a in params.items()
              if "w_ih" in k)
    assert gauge.value(kind="carry_bytes") == want
    # Adam on the table's rows, as at one microbatch an update
    rows = registry.get(obs_setup.OPTIMIZER_ROWS)
    assert (rows.value(kind="updated"), rows.value(kind="visited"),
            rows.value(kind="total")) == (128, 128, F)
    table = obs_setup.setup_table()
    assert table["accumulation"] == {"microbatches": 2, "carry_bytes": want}
    assert (f"accumulation 2 microbatches an update, carry "
            f"{want / 1e6:.1f} MB") in obs_setup.format_setup(table)
    # profile_epoch's table is by the microbatch; the adds carry the scope
    # `accumulate` in the program (the compiler may fuse every one of them
    # into a consumer of another name, Adam's here, and then the table has
    # no row of that name: the unrolled microbatches leave it the choice)
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    state, table = trainer.profile_epoch(
        state, bundle, np.random.default_rng(0), staged, str(tmp_path))
    assert table["steps"] == steps
    assert scopes.OPTIMIZER in {r["scope"] for r in table["rows"]}
    assert table["setup"]["accumulation"]["microbatches"] == 2
    program, args = trainer._dispatched
    assert f"/{scopes.ACCUMULATE}/" in program.lower(state, *args).as_text(
        debug_info=True)


def test_one_microbatch_an_update_carries_nothing(registry):
    cols, vals, y, _ = _corpus(100)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(steps_per_superstep=4)
    staged = trainer.stage_dataset(bundle)
    state = trainer.init_state(trainer.sample_input(bundle), seed=1)
    spans = _recorded(lambda: trainer.train_epoch(
        state, bundle, np.random.default_rng(0), staged=staged))
    (epoch,) = [s for s in spans if s.name == "train.epoch"]
    assert epoch.tags["accum"] == 1
    steps = -(-bundle.num_train_windows // B)
    assert registry.get("deeprest_train_optimizer_updates_total").value() \
        == steps                                   # a step is an update
    gauge = registry.get(obs_setup.ACCUMULATION)
    assert (gauge.value(kind="microbatches"),
            gauge.value(kind="carry_bytes")) == (1, 0)
    table = obs_setup.setup_table()
    assert "accumulation" not in table
    assert "accumulation" not in obs_setup.format_setup(table)


def test_the_readers_of_the_two_new_metrics(registry):
    """chipbench/readers: nothing from a program without the series (the
    parent), the ratio and the megabytes from one with them."""
    from chipbench.readers import accum_carry, updates

    assert updates.per_epoch({}) is None and accum_carry.carry_mb({}) is None
    registry.counter("deeprest_train_optimizer_updates_total", "").inc(48)
    registry.counter("deeprest_train_epochs_total", "").inc(3)
    gauge = registry.gauge(obs_setup.ACCUMULATION, "", labelnames=("kind",))
    gauge.set(1, kind="microbatches")
    gauge.set(0, kind="carry_bytes")
    assert updates.per_epoch({}) == 16
    assert accum_carry.carry_mb({}) is None        # one microbatch an update
    gauge.set(8, kind="microbatches")
    gauge.set(259_768_968, kind="carry_bytes")
    assert accum_carry.carry_mb({}) == pytest.approx(259.768968)
