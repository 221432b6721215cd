"""Row folds and gradient accumulation: the recurrence over G·B rows
against G calls of B rows (the serving page fold relies on rows being
independent), the superstep under accumulation against one update on the
gradient of the mean loss over the group's real windows, the VMEM block-plan re-validation at fat row
counts, serve-side page coalescing vs the pinned host reference, and the
no-recompile probes.

The parity bar: a row fold is the same arithmetic on independent rows, so
it is held to conftest's `assert_fold_equal` — FOLD_ULPS f32 ulp of the
reference's largest magnitude, see there for why not bit equality.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeprest_tpu.config import (
    Config, FeaturizeConfig, InferConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import featurize_buckets
from deeprest_tpu.train import Trainer, prepare_dataset

from conftest import assert_fold_equal, make_series_buckets


SMALL = Config(
    model=ModelConfig(hidden_size=8, dropout_rate=0.1),
    train=TrainConfig(num_epochs=2, batch_size=16, window_size=12,
                      eval_stride=12, eval_max_cycles=4, seed=0,
                      device_data="always"),
)


@pytest.fixture(scope="module")
def bundle():
    buckets = make_series_buckets(160, seed=2)
    data = featurize_buckets(buckets, FeaturizeConfig(round_to=8))
    return prepare_dataset(data, SMALL.train)


def trainer_with(bundle, **train_kw):
    cfg = Config(model=SMALL.model,
                 train=dataclasses.replace(SMALL.train, **train_kw))
    return Trainer(cfg, bundle.feature_dim, bundle.metric_names)


def run_epochs(trainer, bundle, *, epochs, seed=3):
    staged = trainer.stage_dataset(bundle)
    assert staged is not None
    state = trainer.init_state(bundle.x_train, seed=seed)
    rng = np.random.default_rng(7)
    per_step = []
    for _ in range(epochs):
        state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
        per_step.append(trainer._last_epoch_losses.copy())
    return state, per_step


def assert_states_bit_equal(a, b):
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(jax.tree.leaves(a.opt_state), jax.tree.leaves(b.opt_state)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert int(a.step) == int(b.step)


def assert_states_fold_equal(a, b):
    for x, y in zip(jax.tree.leaves((a.params, a.opt_state)),
                    jax.tree.leaves((b.params, b.opt_state))):
        assert_fold_equal(x, y)
    assert int(a.step) == int(b.step)


# ---------------------------------------------------------------------------
# config validation
# ---------------------------------------------------------------------------


def test_config_rejects_bad_accum():
    with pytest.raises(ValueError, match="grad_accum_windows"):
        TrainConfig(grad_accum_windows=0)
    with pytest.raises(ValueError, match="grad_accum_windows"):
        TrainConfig(grad_accum_windows=True)
    TrainConfig(grad_accum_windows=4)
    with pytest.raises(ValueError, match="coalesce_pages"):
        InferConfig(coalesce_pages=0)
    InferConfig(coalesce_pages=4)


def test_superstep_len_multiple_of_g(bundle):
    t = trainer_with(bundle, grad_accum_windows=4, steps_per_superstep=6)
    assert t._superstep_len(100) % 4 == 0 and t._superstep_len(100) >= 4
    # an epoch shorter than G still yields one full (padded) group
    assert t._superstep_len(1) == 4


def test_accum_requires_staged_feed(bundle):
    t = trainer_with(bundle, grad_accum_windows=2)
    state = t.init_state(bundle.x_train, seed=3)
    with pytest.raises(ValueError, match="grad_accum_windows"):
        t.train_epoch(state, bundle, np.random.default_rng(7), staged=None)


# ---------------------------------------------------------------------------
# ops-level row fold
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["scan", "pallas_interpret"])
def test_gru_rows_fold_equal_per_group(backend):
    """G window batches folded into the rows of ONE recurrence == G
    standalone calls on both backends (rows are independent;
    assert_fold_equal)."""
    from deeprest_tpu.ops.gru import bidirectional_gru, gru, init_gru_params

    rng = np.random.default_rng(0)
    e, f, h, g, b, t = 2, 8, 128, 3, 8, 7
    fwd = init_gru_params(jax.random.PRNGKey(1), e, f, h)
    bwd = init_gru_params(jax.random.PRNGKey(2), e, f, h)
    x = jnp.asarray(rng.standard_normal((g, b, t, f)), jnp.float32)
    flat = x.reshape(g * b, t, f)

    out = gru(fwd, flat, backend=backend)
    assert out.shape == (e, g * b, t, h)
    outb = bidirectional_gru(fwd, bwd, flat, backend=backend)
    for gi in range(g):
        rows = slice(gi * b, (gi + 1) * b)
        assert_fold_equal(out[:, rows], gru(fwd, x[gi], backend=backend))
        assert_fold_equal(
            outb[:, rows], bidirectional_gru(fwd, bwd, x[gi], backend=backend))


# ---------------------------------------------------------------------------
# VMEM block-plan re-validation at fat rows
# ---------------------------------------------------------------------------


def test_block_plan_fat_rows_flagship():
    """The footprint model at the coalesced row counts (flagship E=40,
    T=60, H=128): production bf16 TRAINING fits through G=4 (time blocks
    shrink to absorb the fatter rows), G=8 training exceeds scoped VMEM
    even at the minimum legal block (the documented coalescing cap), and
    bf16 INFERENCE fits through G=8 (the serve-side fold)."""
    from deeprest_tpu.ops import pallas_gru

    for g, expect_fit in ((1, True), (2, True), (4, True), (8, False)):
        plan = pallas_gru.block_plan(40, 60, 32 * g, 128,
                                     dtype=jnp.bfloat16, training=True)
        assert plan["fits"] is expect_fit, (g, plan)
        assert plan["e_blk"] % 8 == 0 or plan["e_blk"] == 40
        assert plan["t_blk"] >= 1
        assert plan["b_padded"] >= 32 * g
    infer8 = pallas_gru.block_plan(40, 60, 256, 128,
                                   dtype=jnp.bfloat16, training=False)
    assert infer8["fits"], infer8
    # the plan predicts the same blocking the kernel call would choose:
    # its byte model is the kernels' own (shared helpers), so a fitting
    # plan means the compile-time chooser cannot OOM scoped VMEM
    assert plan["budget"] == pallas_gru._VMEM_BUDGET


def test_block_plan_matches_kernel_execution():
    """A fat-row batch runs through the REAL (interpret-mode) kernel at
    a shape whose block plan fits — fwd and VJP."""
    from deeprest_tpu.ops import pallas_gru
    from deeprest_tpu.ops.gru import gru, init_gru_params

    e, f, h, g, b, t = 2, 8, 128, 4, 8, 7
    plan = pallas_gru.block_plan(e, t, g * b, h, training=True)
    assert plan["fits"]
    params = init_gru_params(jax.random.PRNGKey(0), e, f, h)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((g * b, t, f)),
                    jnp.float32)

    def loss(p):
        return jnp.sum(gru(p, x, backend="pallas_interpret") ** 2)

    grads = jax.grad(loss)(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree.leaves(grads))


# ---------------------------------------------------------------------------
# grad-accum superstep parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("g", [2, 4])
def test_accum_update_is_one_update_on_the_summed_gradients(bundle, g):
    """One accumulated update == the optimizer applied once to the gradient
    of the MEAN loss over the group's real windows: the sum, in microbatch
    order, of G per-microbatch gradients taken at the same parameters, each
    weighted by its share n_g / N of the group's real windows (what one
    batch of G x B windows gives; until ISSUE 48 the program summed the
    microbatches' mean-loss gradients unweighted, G times the mean), dropout
    on with key fold_in(fold_in(rng, step), g); a zero-weight pad microbatch
    adds nothing and is not counted, and a ragged one counts by its real
    windows."""
    from deeprest_tpu.ops.quantile import pinball_loss
    from deeprest_tpu.parallel.distributed import stage_plan

    t = trainer_with(bundle, grad_accum_windows=g, steps_per_superstep=g)
    x_base, y_base = staged = t.stage_dataset(bundle)
    state = t.init_state(bundle.x_train, seed=3)
    b, w = SMALL.train.batch_size, SMALL.train.window_size
    starts = np.random.default_rng(1).integers(
        0, bundle.num_train_windows, (1, g, b)).astype(np.int32)
    weights = np.ones((1, g, b), np.float32)
    weights[0, -1] = 0.0                         # the last microbatch is pad
    if g > 2:
        weights[0, -2, b // 4:] = 0.0            # the one before it ragged
    share = weights[0].sum(axis=1) / weights[0].sum()
    params0 = jax.tree.map(np.asarray, state.params)
    opt0, rng0, step0 = state.opt_state, state.rng, int(state.step)
    key = jax.random.fold_in(rng0, step0)

    def micro(params, i):
        idx = starts[0, i][:, None] + np.arange(w)[None, :]
        preds = t.model.apply(
            {"params": params}, x_base[idx], deterministic=False,
            rngs={"dropout": jax.random.fold_in(key, i)})
        loss = pinball_loss(preds, y_base[idx], SMALL.model.quantiles,
                            sample_weight=jnp.asarray(weights[0, i]),
                            allow_empty=True)
        return loss * share[i], loss

    losses, total = [], None
    for i in range(g):
        (_, loss), grads = jax.jit(
            jax.value_and_grad(micro, has_aux=True), static_argnums=1)(
                params0, i)
        losses.append(float(loss))
        total = grads if total is None else jax.tree.map(jnp.add, total, grads)
    updates, _ = t.tx.update(total, jax.tree.map(jnp.asarray, opt0))
    want = jax.tree.map(lambda p, u: p + u, params0, updates)

    got, got_losses = t._superstep(
        state, *staged, *stage_plan(t.mesh, starts, weights), 0)
    assert_fold_equal(got_losses, np.asarray(losses, np.float32))
    assert losses[-1] == 0.0
    for a, r in zip(jax.tree.leaves(got.params), jax.tree.leaves(want)):
        assert_fold_equal(a, r)
    assert int(got.step) == step0 + g - 1        # REAL microbatches only
    assert int(got.opt_state[0].count) == 1      # ONE update


def test_accum_g1_config_uses_historical_superstep(bundle):
    """There is ONE superstep since ISSUE 48, and grad_accum_windows=1 (the
    default) IS it: G is a static of its trace, at 1 nothing of the G>1
    machinery is traced (no ``accumulate`` scope, no inner loop over
    microbatches), and it matches the per-step loop bit for bit exactly as
    before."""
    from deeprest_tpu.ops import scopes

    t1 = trainer_with(bundle, grad_accum_windows=1, steps_per_superstep=3)
    t_step = trainer_with(bundle, steps_per_superstep=1)
    assert not hasattr(t1, "_accum_superstep")
    s1, _ = run_epochs(t1, bundle, epochs=2)
    s_step, _ = run_epochs(t_step, bundle, epochs=2)
    assert_states_bit_equal(s1, s_step)
    state = t1.init_state(bundle.x_train, seed=3)
    assert scopes.ACCUMULATE not in t1._dispatched_program_text(state)
    t2 = trainer_with(bundle, grad_accum_windows=2, steps_per_superstep=4)
    s2, _ = run_epochs(t2, bundle, epochs=1)
    assert scopes.ACCUMULATE in t2._dispatched_program_text(
        t2.init_state(bundle.x_train, seed=3))


def test_accum_one_executable_across_epochs(bundle):
    """The no-recompile probe at G>1: epochs of chunks — full and ragged,
    fresh epoch plans — reuse ONE superstep executable."""
    t = trainer_with(bundle, grad_accum_windows=2, steps_per_superstep=4)
    staged = t.stage_dataset(bundle)
    state = t.init_state(bundle.x_train, seed=3)
    rng = np.random.default_rng(7)
    state, _ = t.train_epoch(state, bundle, rng, staged=staged)
    probe = getattr(t._superstep, "_cache_size", None)
    if not callable(probe):
        pytest.skip("jax version exposes no jit cache probe")
    assert probe() == 1
    for _ in range(2):
        state, _ = t.train_epoch(state, bundle, rng, staged=staged)
    assert probe() == 1
    # G is a plan-shape static: a DIFFERENT G is its own trainer/executable
    # (test_accum_update_is_one_update_on_the_summed_gradients exercises
    # G=2 and G=4; each holds the invariant independently).


def test_accum_smoke_fit(bundle):
    """End-to-end: a 2-epoch Trainer.fit with coalesced updates on,
    exercising plan staging, the accum scan, ragged padding, eval."""
    cfg = Config(model=SMALL.model,
                 train=dataclasses.replace(SMALL.train, grad_accum_windows=2,
                                           steps_per_superstep="auto",
                                           num_epochs=2))
    t = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state, history = t.fit(bundle)
    assert len(history) == 2
    assert all(np.isfinite(h.train_loss) for h in history)
    assert all(np.isfinite(h.test_loss) for h in history)
    assert int(state.step) == 2 * 4
    assert t._last_epoch_losses.shape == (4,)


# ---------------------------------------------------------------------------
# serve-side page coalescing
# ---------------------------------------------------------------------------


def _tiny_serving():
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.models.qrnn import QuantileGRU

    rng = np.random.default_rng(0)
    e, f, w = 4, 8, 6
    cfg = ModelConfig(feature_dim=f, num_metrics=e, hidden_size=8)
    model = QuantileGRU(config=cfg)
    params = dict(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, w, f), jnp.float32))["params"])
    apply_fn = lambda p, x: model.apply({"params": p}, x, deterministic=True)
    xs = rng.random((100, f)).astype(np.float32) * 5
    x_stats = MinMaxStats(min=xs.min(0), max=xs.max(0))
    y_stats = MinMaxStats(min=np.zeros(e, np.float32),
                          max=np.ones(e, np.float32))
    dm = np.zeros(e, bool)
    dm[1] = True
    series = [rng.random((t, f)).astype(np.float32) * 5
              for t in (37, 18, 64, 6, 29)]
    return apply_fn, params, x_stats, y_stats, w, dm, series


def test_fused_engine_page_coalescing_parity_and_dispatch_reduction():
    """coalesce_pages folds consecutive pages into one dispatch: same
    numerics contract as the uncoalesced engine (non-delta equal to the
    pinned host reference — assert_fold_equal, since the reference
    batches a series' windows by its own row count — and delta within the
    documented tolerance), fewer dispatches, fatter rows, and only
    super-rung executables added."""
    from deeprest_tpu.serve.fused import FusedRolledEngine
    from deeprest_tpu.serve.predictor import rolled_prediction_reference

    apply_fn, params, x_stats, y_stats, w, dm, series = _tiny_serving()
    japply = jax.jit(apply_fn)
    ref_apply = lambda x: np.asarray(japply(params, jnp.asarray(x)))

    def engine(coalesce):
        return FusedRolledEngine(apply_fn, x_stats, y_stats, w,
                                 params=params, delta_mask=dm,
                                 median_index=1, page_windows=8,
                                 coalesce_pages=coalesce)

    eng1, eng4 = engine(1), engine(4)
    assert eng4.rungs == (8, 16, 24, 32, 64)      # super-rungs 16/24/32
    out1 = eng1.predict_many(series)
    out4 = eng4.predict_many(series)
    nd = ~dm
    for s, a, b in zip(series, out1, out4):
        ref = rolled_prediction_reference(ref_apply, x_stats, y_stats, w,
                                          s, delta_mask=dm, median_index=1)
        assert_fold_equal(a[:, nd], ref[:, nd])
        assert_fold_equal(b[:, nd], ref[:, nd])
        np.testing.assert_allclose(b[:, dm], ref[:, dm], rtol=2e-5,
                                   atol=1e-5)
    s1, s4 = eng1.stats(), eng4.stats()
    assert s4["pages"] < s1["pages"]               # dispatch reduction
    assert s4["max_dispatch_rows"] > s1["max_dispatch_rows"]
    assert s4["coalesce_pages"] == 4
    # repeat traffic adds ZERO new executables (rungs already compiled)
    before = eng4.cache_size()
    eng4.predict_many(series)
    if before is not None:
        assert eng4.cache_size() == before


def test_fused_engine_coalesce_validation():
    from deeprest_tpu.serve.fused import FusedRolledEngine

    apply_fn, params, x_stats, y_stats, w, dm, _ = _tiny_serving()
    with pytest.raises(ValueError, match="coalesce_pages"):
        FusedRolledEngine(apply_fn, x_stats, y_stats, w, params=params,
                          delta_mask=dm, median_index=1,
                          coalesce_pages=0)


def test_shape_ladder_super_rungs():
    from deeprest_tpu.serve.batcher import ShapeLadder

    lad = ShapeLadder(lambda x: x, (8, 16, 32, 64), coalesce_groups=4)
    assert lad.base_ladder == (8, 16, 32, 64)
    assert lad.ladder == (8, 16, 32, 64, 128, 192, 256)
    assert lad.max_rung == 256
    assert lad.rung_for(100) == 128
    assert lad.stats()["coalesce_groups"] == 4
    # default: unchanged behavior
    plain = ShapeLadder(lambda x: x, (8, 16, 32, 64))
    assert plain.ladder == plain.base_ladder == (8, 16, 32, 64)
    with pytest.raises(ValueError, match="coalesce_groups"):
        ShapeLadder(lambda x: x, (8,), coalesce_groups=0)


def test_predictor_coalesce_plumbing(tmp_path):
    """coalesce_pages / coalesce_groups survive the checkpoint round-trip
    into a Predictor (CLI serve/predict path)."""
    from deeprest_tpu.serve.predictor import Predictor

    buckets = make_series_buckets(120, seed=5)
    data = featurize_buckets(buckets, FeaturizeConfig(round_to=8))
    cfg = Config(model=ModelConfig(hidden_size=8),
                 train=dataclasses.replace(SMALL.train, num_epochs=1))
    bundle = prepare_dataset(data, cfg.train)
    tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state, _ = tr.fit(bundle, num_epochs=1)
    ck = str(tmp_path / "ck")
    tr.save(ck, state, bundle)

    pred = Predictor.from_checkpoint(ck, coalesce_pages=2,
                                     coalesce_groups=2)
    assert pred.fused is not None
    assert pred.fused.coalesce_pages == 2
    assert pred.ladder.ladder[-1] == 2 * pred.ladder.base_ladder[-1]
    t = np.random.default_rng(0).random(
        (3 * bundle.window_size + 5, bundle.feature_dim)).astype(np.float32)
    out = pred.predict_series(t)
    assert out.shape == (len(t), len(bundle.metric_names), 3)
    assert np.isfinite(out).all()
