"""Live-column compaction of the layer-0 projection (ISSUE 25).

A staged sparse corpus whose live call paths, padded to a power of two, are
at most half of F is staged in the compact form (ops/densify.py): windows of
the live columns only, and a model that contracts over them.  Leaving exact
zeros out of a sum changes its order, not its value, so the compact form is
held to the dense form at float32 tolerance: predictions, loss, every
gradient leaf, and Adam's state after three steps, dead rows included.  The
choice between the forms is the trainer's, from the corpus and the mesh.

Everything here runs on the CPU at a small F: parity and plumbing, no rate.
"""

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprest_tpu.config import Config, MeshConfig, ModelConfig, TrainConfig
from deeprest_tpu.data.windows import MinMaxStats
from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.ops import densify
from deeprest_tpu.ops.densify import (
    MIN_COMPACT_WIDTH, SparseBase, compact_rows, compact_table,
    densify_compare, densify_coo, densify_rows, gather_densify_normalize,
    live_columns,
)
from deeprest_tpu.parallel.distributed import stage_sparse_base
from deeprest_tpu.parallel.mesh import make_mesh
from deeprest_tpu.train import Trainer
from deeprest_tpu.train.data import DatasetBundle

F, E, H, W, B, K, T = 512, 4, 8, 10, 8, 16, 160
RTOL, ATOL = 2e-5, 2e-6       # float32 sums over F in another order


def _corpus(hot: int):
    """[T, K] padded-COO rows over ``hot`` (at most 2 T) of the F columns,
    [T, E] targets."""
    rng = np.random.default_rng(0)
    hot_cols = np.sort(rng.choice(F, hot, replace=False))
    cols = np.zeros((T, K), np.int32)
    vals = np.zeros((T, K), np.float32)
    for t in range(T):      # rows walk the hot columns: each one is seen
        n = rng.integers(2, min(K, hot))
        cols[t, :n] = hot_cols[(2 * t + np.arange(n)) % hot]
        vals[t, :n] = rng.integers(1, 50, n)
    return cols, vals, rng.random((T, E)).astype(np.float32), hot_cols


def _bundle(cols, vals, y) -> DatasetBundle:
    """A sparse-only bundle (the streaming tier's shape): no dense windows
    or base, global min-max statistics."""
    n = T - W
    split = int(0.6 * n)
    x_stats = MinMaxStats(min=np.float32(0.0), max=np.float32(vals.max()))
    y_windows = np.stack([y[i:i + W] for i in range(n)])
    return DatasetBundle(
        x_train=None, y_train=y_windows[:split], x_test=None,
        y_test=y_windows[split:], x_stats=x_stats,
        y_stats=MinMaxStats(min=np.zeros((E,), np.float32),
                            max=np.ones((E,), np.float32)),
        metric_names=[f"m{i}" for i in range(E)], split=split,
        window_size=W, x_base=None, y_base=y, x_cols=cols, x_vals=vals,
        x_nnz=(vals != 0).sum(axis=1).astype(np.int32), sparse_capacity=F,
        n_train=split, n_test=n - split)


def _trainer(mesh=None, dropout: float = 0.1, **train_kw) -> Trainer:
    cfg = Config(
        model=ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                          dropout_rate=dropout),
        train=TrainConfig(batch_size=B, window_size=W, seed=0,
                          log_every_steps=0, device_data="always",
                          sparse_feed=True, sparse_nnz_cap=K, **train_kw))
    return Trainer(cfg, F, [f"m{i}" for i in range(E)], mesh=mesh)


def _gauge():
    g = REGISTRY.get("deeprest_train_projection_columns")
    return {k: g.value(kind=k) for k in ("live", "contracted", "total")}


# -- the table ---------------------------------------------------------------


def test_table_is_sorted_padded_with_dead_columns_and_a_power_of_two():
    live = np.array([3, 7, 300, 511], np.int32)
    table = compact_table(live, F)
    assert len(table) == MIN_COMPACT_WIDTH
    assert np.all(np.diff(table) > 0) and set(live) <= set(table)
    assert table.min() >= 0 and table.max() < F and table.dtype == np.int32
    assert len(compact_table(np.arange(129, dtype=np.int32), F)) == 256
    # a table over half of F is no table: the dense form stays
    assert compact_table(np.arange(257, dtype=np.int32), F) is None
    assert compact_table(live, 2 * MIN_COMPACT_WIDTH - 1) is None


def test_a_never_seen_column_that_the_statistics_shift_is_live():
    cols, vals, _, hot = _corpus(20)
    mn, rg = np.zeros((F,), np.float32), np.ones((F,), np.float32)
    unseen = int(np.setdiff1d(np.arange(F), hot)[5])
    assert list(live_columns(cols, vals, mn, rg, F)) == list(hot)
    mn[unseen] = 2.0        # carried over from another span: (0 - 2) / 1
    assert unseen in live_columns(cols, vals, mn, rg, F)
    rg[unseen] = 0.0        # a degenerate range passes the raw 0 through
    assert unseen not in live_columns(cols, vals, mn, rg, F)
    # one scalar statistic that shifts zero makes every column live
    assert len(live_columns(cols, vals, np.float32(1.0), np.float32(2.0),
                            F)) == F
    with pytest.raises(ValueError, match="outside"):
        live_columns(cols + F, vals, mn, rg, F)


def test_compact_windows_are_the_dense_windows_at_the_table(monkeypatch):
    cols, vals, _, hot = _corpus(40)
    rng = np.random.default_rng(1)
    mn = np.where(rng.random(F) < 0.5, 0.0, 0.5).astype(np.float32)
    rg = rng.uniform(1.0, 9.0, F).astype(np.float32)
    mn[~np.isin(np.arange(F), hot)] = 0.0
    table = compact_table(live_columns(cols, vals, mn, rg, F), F)
    ranks = compact_rows(cols, vals, table)
    assert np.array_equal(table[ranks][vals != 0], cols[vals != 0])
    mesh = make_mesh(MeshConfig())
    dense = stage_sparse_base(mesh, cols, vals, mn, rg, F)
    compact = stage_sparse_base(mesh, cols, vals, mn, rg, F, live=table)
    assert dense.live is None and dense.width == F
    assert compact.width == len(table) == MIN_COMPACT_WIDTH
    idx = jnp.arange(12).reshape(2, 6)
    x = np.asarray(gather_densify_normalize(dense, idx))
    xc = np.asarray(gather_densify_normalize(compact, idx))
    np.testing.assert_array_equal(xc, x[..., table])
    # a table wider than the compare form's bound goes through the scatter
    monkeypatch.setattr(densify, "COMPARE_MAX_WIDTH", MIN_COMPACT_WIDTH - 1)
    np.testing.assert_array_equal(gather_densify_normalize(compact, idx), xc)
    # every column left out is exactly zero in the dense windows
    assert not x[..., np.setdiff1d(np.arange(F), table)].any()
    with pytest.raises(ValueError, match="live table"):
        compact_rows(cols, vals, table[table != hot[0]])


# -- the rule ----------------------------------------------------------------


def test_the_rule_picks_the_form_from_the_corpus_and_the_mesh():
    narrow, wide = _corpus(100), _corpus(257)
    trainer = _trainer()
    staged = trainer.stage_dataset(_bundle(*narrow[:3]))
    assert isinstance(staged, tuple) and len(staged) == 2
    # a table of 128 is a quarter of F: compact
    assert isinstance(staged[0], SparseBase) and staged[0].width == 128
    assert np.all(np.diff(np.asarray(staged[0].live)) > 0)
    assert _gauge() == {"live": 100, "contracted": 128, "total": F}
    # a live set that pads to 512 is over half of F: the dense form, untouched
    base = trainer.stage_dataset(_bundle(*wide[:3]))[0]
    assert base.live is None and base.width == F
    assert np.array_equal(np.asarray(base.cols), wide[0])
    assert _gauge() == {"live": 257, "contracted": F, "total": F}
    # F sharded over the mesh's model axis: the dense form
    sharded = _trainer(mesh=make_mesh(MeshConfig(data=1, model=2)))
    assert sharded.stage_dataset(_bundle(*narrow[:3]))[0].live is None
    assert _gauge() == {"live": 100, "contracted": F, "total": F}
    # data parallelism does not shard F: compact, and a step runs
    dp = _trainer(mesh=make_mesh(MeshConfig(data=2)))
    base, y_base = dp.stage_dataset(_bundle(*narrow[:3]))
    assert base.width == 128
    state = dp.init_state(np.zeros((1, W, F), np.float32))
    _, loss = dp._train_step_indexed(
        state, base, y_base, jnp.arange(B, dtype=jnp.int32),
        jnp.ones((B,), jnp.float32))
    assert np.isfinite(float(loss))
    # the superstep the benchmark drives keeps its arguments
    assert list(inspect.signature(trainer._superstep).parameters) == [
        "state", "x_base", "y_base", "starts_plan", "weights_plan", "chunk"]


def test_a_dense_base_never_sees_a_table():
    from conftest import make_series_buckets
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import featurize_buckets
    from deeprest_tpu.train import prepare_dataset

    tc = TrainConfig(batch_size=B, window_size=W, device_data="always")
    data = featurize_buckets(make_series_buckets(60, seed=5),
                             FeaturizeConfig(hash_features=True, capacity=F))
    bundle = prepare_dataset(data, tc)
    trainer = Trainer(Config(model=ModelConfig(hidden_size=H), train=tc),
                      bundle.feature_dim, bundle.metric_names)
    x_base, y_base = trainer.stage_dataset(bundle)
    assert not isinstance(x_base, SparseBase) and x_base.shape[-1] == F


# -- parity of the model and of the step --------------------------------------


@pytest.mark.parametrize("dtype, rtol, atol", [("float32", 2e-5, 1e-6),
                                               ("bfloat16", 2e-2, 2e-3)])
def test_compact_call_agrees_with_the_dense_call(dtype, rtol, atol):
    """``live_cols`` (ISSUE 25): the layer-0 projection over the live
    columns only, pad slots among them, against the dense call on the
    input those columns come from: predictions, loss and every gradient
    leaf, which keeps its dense shape and is zero at the columns left out."""
    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.ops.quantile import pinball_loss

    cfg = ModelConfig(feature_dim=64, num_metrics=3, hidden_size=4,
                      compute_dtype=dtype)
    model = QuantileGRU(config=cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 5, 64)))
    rng = np.random.default_rng(0)
    live = np.sort(rng.choice(64, 9, replace=False))
    table = np.sort(np.concatenate(          # 16 slots: 7 name dead columns
        [live, np.setdiff1d(np.arange(64), live)[:7]])).astype(np.int32)
    x = np.zeros((2, 5, 64), np.float32)
    x[..., live] = rng.random((2, 5, 9))
    y = jnp.asarray(rng.random((2, 5, 3), np.float32))
    params = variables["params"]

    def loss_fn(params, xb, live_cols):
        preds = model.apply({"params": params}, xb, live_cols=live_cols)
        return pinball_loss(preds, y, cfg.quantiles), preds

    (want, want_preds), want_g = jax.value_and_grad(
        loss_fn, has_aux=True)(params, jnp.asarray(x), None)
    (got, got_preds), got_g = jax.jit(jax.value_and_grad(
        loss_fn, has_aux=True))(params, jnp.asarray(x[..., table]),
                                jnp.asarray(table))
    np.testing.assert_allclose(got_preds, want_preds, rtol=rtol, atol=atol)
    np.testing.assert_allclose(got, want, rtol=rtol)
    assert set(got_g) == set(want_g)
    dead = np.setdiff1d(np.arange(64), live)
    for name, g in got_g.items():
        assert g.shape == want_g[name].shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, want_g[name], rtol=rtol, atol=atol,
                                   err_msg=name)
        if name.endswith("w_ih"):
            assert not np.asarray(g)[:, dead].any(), name
            assert np.asarray(g)[:, live].any(), name
    with pytest.raises(ValueError, match="live columns"):
        model.apply({"params": params}, jnp.asarray(x),
                    live_cols=jnp.asarray(table))


def _three_steps(trainer, base, y):
    state = trainer.init_state(np.zeros((1, W, F), np.float32), seed=0)
    rng = np.random.default_rng(3)
    y = jnp.asarray(y)
    losses = []
    for _ in range(3):
        starts = jnp.asarray(rng.integers(0, T - W, (B,)).astype(np.int32))
        state, loss = trainer._train_step_indexed(
            state, base, y, starts, jnp.ones((B,), jnp.float32))
        losses.append(float(loss))
    return state, losses


def test_adam_after_three_steps_agrees_leaf_by_leaf_dead_rows_included():
    cols, vals, y, hot = _corpus(100)
    trainer = _trainer()
    compact = trainer.stage_dataset(_bundle(cols, vals, y))[0]
    assert compact.width == 128 and len(hot) < compact.width  # pad slots
    mn, rg = np.zeros((1,), np.float32), np.array([vals.max()], np.float32)
    dense = stage_sparse_base(trainer.mesh, cols, vals, mn, rg, F)
    got, got_losses = _three_steps(trainer, compact, y)
    want, want_losses = _three_steps(trainer, dense, y)
    np.testing.assert_allclose(got_losses, want_losses, rtol=RTOL)
    dead = np.setdiff1d(np.arange(F), hot)
    for tree in ("params", "opt_state"):
        a, b = getattr(got, tree), getattr(want, tree)
        assert jax.tree.structure(a) == jax.tree.structure(b)
        for (path, x), z in zip(jax.tree.leaves_with_path(a),
                                jax.tree.leaves(b)):
            assert x.shape == z.shape and x.dtype == z.dtype, path
            np.testing.assert_allclose(np.asarray(x), np.asarray(z),
                                       rtol=RTOL, atol=ATOL,
                                       err_msg=str(path))
    # a dead row of w_ih sees a zero gradient in both forms: Adam's own
    # arithmetic on it (moments that stay 0, no step) is bit for bit the same
    for name in ("gru_fwd_w_ih", "gru_bwd_w_ih"):
        np.testing.assert_array_equal(
            np.asarray(got.params[name])[:, dead],
            np.asarray(want.params[name])[:, dead])
        assert not np.asarray(got.opt_state[0].mu[name])[:, dead].any()
        assert np.asarray(got.opt_state[0].mu[name])[:, hot].any()


def test_accumulation_on_a_compact_base_agrees_with_its_dense_form():
    cols, vals, y, _ = _corpus(60)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(grad_accum_windows=2, steps_per_superstep=4)
    compact = trainer.stage_dataset(bundle)
    assert compact[0].width == 128
    mn, rg = np.zeros((1,), np.float32), np.array([vals.max()], np.float32)
    dense = (stage_sparse_base(trainer.mesh, cols, vals, mn, rg, F),
             compact[1])

    def run(staged):
        state = trainer.init_state(trainer.sample_input(bundle), seed=1)
        state, _ = trainer.train_epoch(state, bundle,
                                       np.random.default_rng(5), staged=staged)
        return state, trainer._last_epoch_losses

    want, want_losses = run(dense)
    got, got_losses = run(compact)
    np.testing.assert_allclose(got_losses, want_losses, rtol=RTOL)
    for (path, x), z in zip(jax.tree.leaves_with_path(got.params),
                            jax.tree.leaves(want.params)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(z), rtol=RTOL,
                                   atol=ATOL, err_msg=str(path))


def test_evaluate_on_a_compact_base_agrees_with_the_dense_form():
    cols, vals, y, _ = _corpus(60)
    bundle = _bundle(cols, vals, y)
    trainer = _trainer(eval_stride=2, eval_max_cycles=2)
    state = trainer.init_state(trainer.sample_input(bundle), seed=2)
    compact = trainer.stage_dataset(bundle)
    mn, rg = np.zeros((1,), np.float32), np.array([vals.max()], np.float32)
    dense = (stage_sparse_base(trainer.mesh, cols, vals, mn, rg, F),
             compact[1])
    got, _ = trainer.evaluate(state, bundle, staged=compact)
    want, _ = trainer.evaluate(state, bundle, staged=dense)
    assert got == pytest.approx(want, rel=RTOL)


def test_densify_rows_reference_agrees_with_the_compact_gather():
    cols, vals, _, _ = _corpus(30)
    table = compact_table(live_columns(cols, vals, np.float32(0),
                                       np.float32(1), F), F)
    base = SparseBase(cols=jnp.asarray(compact_rows(cols, vals, table)),
                      vals=jnp.asarray(vals), mn=jnp.zeros((1,)),
                      rg=jnp.ones((1,)), live=jnp.asarray(table), capacity=F)
    got = np.asarray(gather_densify_normalize(base, jnp.arange(T)))
    np.testing.assert_array_equal(got, densify_rows(cols, vals, F)[:, table])
    # the compare form of the densify and the scatter give the same bits
    np.testing.assert_array_equal(
        densify_compare(jnp.asarray(cols), jnp.asarray(vals), F),
        densify_coo(jnp.asarray(cols), jnp.asarray(vals), F))
    assert dataclasses.replace(base, live=None).width == F
