"""Sparse-first pipeline parity (round 15): padded-COO traffic from
featurization through the on-device densify must be BIT-IDENTICAL to the
dense reference at every layer — extract_sparse↔extract, SparseSeriesRing↔
SeriesRing, sparse-staged train↔dense-staged train, sparse fused serving↔
dense fused serving — with the K-cap overflow raising loudly and the serve
plane compiling nothing new once warmed."""

import dataclasses
import functools

import numpy as np
import pytest

from deeprest_tpu.config import (
    Config, FeaturizeConfig, InferConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import CallPathSpace, featurize_buckets
from deeprest_tpu.data.windows import MinMaxStats, minmax_fit, sliding_windows
from deeprest_tpu.ops.densify import (
    densify_rows, sparse_minmax, sparsify_rows,
)
from deeprest_tpu.train.data import (
    SeriesRing, SparseSeriesRing, prepare_dataset,
)
from deeprest_tpu.train.trainer import Trainer

from conftest import make_series_buckets


# ---------------------------------------------------------------------------
# extract_sparse ↔ extract


@pytest.mark.parametrize("hash_mode", [True, False])
def test_extract_sparse_bit_identical_to_dense(hash_mode):
    buckets = make_series_buckets(40, seed=3)
    if hash_mode:
        cfg = FeaturizeConfig(hash_features=True, capacity=256)
    else:
        cfg = FeaturizeConfig(round_to=8)
    dense_space = CallPathSpace(config=cfg)
    sparse_space = CallPathSpace(config=cfg)
    if not hash_mode:
        dense_space.observe(buckets)
        sparse_space.observe(buckets)
    for b in buckets:
        ref = dense_space.extract(b.traces)
        cols, vals = sparse_space.extract_sparse(b.traces)
        # unique ascending columns, integral float32 counts
        assert cols.dtype == np.int32 and vals.dtype == np.float32
        assert np.all(np.diff(cols) > 0)
        assert np.all(vals >= 1.0)
        rebuilt = densify_rows(cols[None], vals[None],
                               dense_space.capacity)[0]
        np.testing.assert_array_equal(rebuilt, ref)


def test_extract_sparse_golden_hash_columns():
    """Hash-mode sparse columns come from the same seeded FNV-1a the
    golden vectors pin (test_featurize.GOLDEN_HASHES), so the sparse path
    cannot drift from the cross-language wire format."""
    from test_featurize import GOLDEN_HASHES

    from deeprest_tpu.data.schema import Span

    path, seed, expect = GOLDEN_HASHES[0]          # ("a_/op",)
    comp, op = path[0].split("_", 1)
    cap = 512
    space = CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=cap, hash_seed=seed))
    cols, vals = space.extract_sparse([Span(component=comp, operation=op)])
    assert list(cols) == [expect % cap]
    assert list(vals) == [1.0]


def test_extract_sparse_empty_traces():
    space = CallPathSpace(config=FeaturizeConfig(hash_features=True,
                                                 capacity=128))
    cols, vals = space.extract_sparse([])
    assert len(cols) == 0 and len(vals) == 0
    np.testing.assert_array_equal(densify_rows(cols[None], vals[None], 128),
                                  np.zeros((1, 128), np.float32))


# ---------------------------------------------------------------------------
# host sparsify/densify round trip + K-cap policy


def test_sparsify_rows_round_trip_and_overflow():
    rng = np.random.default_rng(0)
    dense = np.zeros((13, 64), np.float32)
    for t in range(13):
        nz = rng.choice(64, size=rng.integers(0, 9), replace=False)
        dense[t, nz] = rng.integers(1, 100, size=len(nz))
    cols, vals, nnz = sparsify_rows(dense, 16)
    np.testing.assert_array_equal(densify_rows(cols, vals, 64), dense)
    assert nnz.max() <= 16
    fat = np.ones((1, 64), np.float32)
    with pytest.raises(ValueError, match="sparse nnz cap"):
        sparsify_rows(fat, 16)


def test_sparse_ring_cap_overflow_raises_loudly():
    ring = SparseSeriesRing(8, 128, nnz_cap=4)
    with pytest.raises(ValueError, match="nnz cap 4"):
        ring.append_sparse(np.arange(5, dtype=np.int32),
                           np.ones(5, np.float32))


# ---------------------------------------------------------------------------
# SparseSeriesRing ↔ SeriesRing across wrap/eviction


def test_sparse_ring_densify_parity_across_wrap_and_eviction():
    rng = np.random.default_rng(1)
    maxlen, cap, k = 16, 96, 8
    dense_ring = SeriesRing(maxlen, cap)
    sparse_ring = SparseSeriesRing(maxlen, cap, k)
    # 3.5× maxlen appends: exercises eviction AND both rings' compaction
    # memmoves (the 2× buffer wraps at 2·maxlen appends).
    for t in range(56):
        row = np.zeros(cap, np.float32)
        nz = rng.choice(cap, size=rng.integers(0, k + 1), replace=False)
        row[nz] = rng.integers(1, 50, size=len(nz))
        dense_ring.append(row)
        cols, vals, nnz = sparsify_rows(row[None], k)
        sparse_ring.append_sparse(cols[0, :nnz[0]], vals[0, :nnz[0]])
        assert len(sparse_ring) == len(dense_ring)
        np.testing.assert_array_equal(sparse_ring.densify(),
                                      dense_ring.view())
    cols_v, vals_v, nnz_v = sparse_ring.view()
    assert cols_v.shape == (maxlen, k) and nnz_v.shape == (maxlen,)
    sparse_ring.clear()
    assert len(sparse_ring) == 0


@pytest.mark.parametrize("rows", [1024, 30 * 24 * 60],
                         ids=["1024-rows", "a-month-of-minutes"])
def test_sparse_ring_is_much_smaller_than_dense(rows):
    # the memory-ceiling claim at the 10k width, in ring-resident bytes
    # (the dense ring's are computed: a month of them is 3.5 GB)
    sparse = SparseSeriesRing(rows, 10240, 64)
    assert sparse.maxlen == rows
    dense_bytes = 2 * rows * 10240 * 4            # SeriesRing 2× buffer
    assert dense_bytes / sparse.nbytes > 20


# ---------------------------------------------------------------------------
# the feed's bytes at the published widths (W=60, K=64), from the arrays
# prepare_dataset hands the staged feed


@functools.lru_cache(maxsize=None)
def _published_width_bundle(capacity: int, rows: int = 150):
    from deeprest_tpu.data.featurize import FeaturizedData

    rng = np.random.default_rng(capacity)
    traffic = np.zeros((rows, capacity), np.float32)
    for row in traffic:                 # 4-31 hot call paths a bucket
        hot = rng.choice(capacity, size=rng.integers(4, 32), replace=False)
        row[hot] = rng.integers(1, 200, size=len(hot))
    data = FeaturizedData(
        traffic=traffic,
        resources={"svc_cpu": rng.random(rows).astype(np.float32)},
        invocations={"general": np.ones(rows, np.float32)},
        space=CallPathSpace(config=FeaturizeConfig(
            hash_features=True, capacity=capacity)).freeze())
    return prepare_dataset(data, TrainConfig(
        window_size=60, device_data="always", sparse_feed=True,
        sparse_nnz_cap=64))


_PUBLISHED_WIDTHS = [512, 2048, 10240]


@pytest.mark.parametrize("capacity", _PUBLISHED_WIDTHS)
def test_window_feed_bytes_dense_against_padded_coo(capacity):
    """What one window costs to ship: [W, F] float32 against W padded-COO
    rows of K int32 columns and K float32 counts, F / 2K times less."""
    b = _published_width_bundle(capacity)
    w = b.window_size
    assert b.x_cols.dtype == np.int32 and b.x_vals.dtype == np.float32
    assert b.x_cols.shape[1:] == b.x_vals.shape[1:] == (64,)
    dense = b.x_base[:w].nbytes
    sparse = b.x_cols[:w].nbytes + b.x_vals[:w].nbytes
    assert dense == w * capacity * 4 and sparse == w * 64 * 8
    assert dense // sparse == capacity // 128
    if capacity == 10240:
        assert dense // sparse >= 20


@pytest.mark.parametrize("capacity", _PUBLISHED_WIDTHS)
def test_staged_base_bytes_dense_against_padded_coo(capacity):
    """What the staged feed ships once: the [T, F] base against the COO
    rows with their lengths; the ratio does not depend on T."""
    b = _published_width_bundle(capacity)
    rows = len(b.x_base)
    assert b.sparse_capacity == capacity and b.x_nnz.shape == (rows,)
    dense = b.x_base.nbytes
    sparse = b.x_cols.nbytes + b.x_vals.nbytes + b.x_nnz.nbytes
    assert sparse == rows * (64 * 8 + 4)
    assert dense * (64 * 8 + 4) == sparse * capacity * 4
    if capacity == 10240:
        assert dense / sparse >= 20


# ---------------------------------------------------------------------------
# sparse_minmax ↔ minmax_fit


def test_sparse_minmax_bit_identical_to_dense_fit():
    rng = np.random.default_rng(2)
    t, cap, k, w = 40, 64, 8, 6
    dense = np.zeros((t, cap), np.float32)
    # include a column present in EVERY row (nonzero min) and quiet cols
    dense[:, 7] = rng.integers(3, 9, size=t)
    for i in range(t):
        nz = rng.choice(cap, size=rng.integers(0, k - 1), replace=False)
        dense[i, nz] = rng.integers(1, 100, size=len(nz))
    cols, vals, nnz = sparsify_rows(dense, k + 2)
    windows = sliding_windows(dense, w)
    split = len(windows) - 4
    ref = minmax_fit(windows, split, axis=(0, 1))
    got = sparse_minmax(cols, vals, nnz, split + w - 1, cap)
    np.testing.assert_array_equal(got.min, ref.min)
    np.testing.assert_array_equal(got.max, ref.max)
    assert got.min.shape == ref.min.shape == (1, cap)


# ---------------------------------------------------------------------------
# train: sparse staged feed ≡ dense staged feed, bit for bit


def _train_cfg(sparse: bool, **kw) -> Config:
    tc = TrainConfig(num_epochs=2, batch_size=8, window_size=10,
                     eval_stride=4, eval_max_cycles=3, seed=0,
                     log_every_steps=0, device_data="always",
                     sparse_feed=sparse, sparse_nnz_cap=48, **kw)
    return Config(model=ModelConfig(hidden_size=8, dropout_rate=0.1),
                  train=tc)


def _run_train(data, cfg: Config, compact: bool | None = None):
    bundle = prepare_dataset(data, cfg.train)
    trainer = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state = trainer.init_state(np.zeros(
        (1, cfg.train.window_size, bundle.feature_dim), np.float32))
    staged = trainer.stage_dataset(bundle)
    assert staged is not None
    if compact is not None:     # which form the trainer's rule chose
        assert (staged[0].live is not None) == compact
    rng = np.random.default_rng(0)
    losses = []
    for _ in range(cfg.train.num_epochs):
        state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
        losses.append(trainer._last_epoch_losses.copy())
    eval_loss, report = trainer.evaluate(state, bundle, staged=staged)
    return np.concatenate(losses), eval_loss, report


@pytest.mark.parametrize("featurize, compact, rtol", [
    # a corpus whose live paths are most of F: the sparse feed densifies
    # to the same [.., F] windows, bit for bit
    (FeaturizeConfig(round_to=8), False, 0.0),
    # the same paths hashed into a space of 512: the feed takes the
    # compact form (ops/densify.py) and the layer-0 sums leave their exact
    # zeros out, in another order: float32 tolerance over two epochs
    (FeaturizeConfig(hash_features=True, capacity=512), True, 1e-4),
])
def test_train_superstep_sparse_loss_parity(featurize, compact, rtol):
    buckets = make_series_buckets(80, seed=5)
    data = featurize_buckets(buckets, featurize)
    dense_losses, dense_eval, dense_rep = _run_train(data,
                                                     _train_cfg(False))
    sparse_losses, sparse_eval, sparse_rep = _run_train(
        data, _train_cfg(True), compact=compact)
    np.testing.assert_allclose(sparse_losses, dense_losses, rtol=rtol,
                               atol=0.0)
    assert sparse_eval == pytest.approx(dense_eval, rel=rtol, abs=0.0)
    for m, per in dense_rep.items():
        assert sparse_rep[m]["deepr"]["median"] == pytest.approx(
            per["deepr"]["median"], rel=10 * rtol, abs=0.0)


def test_sparse_feed_requires_staged_feed():
    with pytest.raises(ValueError, match="sparse_feed"):
        TrainConfig(sparse_feed=True, device_data="off")
    buckets = make_series_buckets(60, seed=5)
    data = featurize_buckets(buckets, FeaturizeConfig(round_to=8))
    cfg = _train_cfg(True)
    bundle = prepare_dataset(data, cfg.train)
    sparse_only = dataclasses.replace(bundle, x_train=None, x_test=None,
                                      x_base=None, n_train=bundle.split,
                                      n_test=len(bundle.x_test))
    trainer = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state = trainer.init_state(trainer.sample_input(sparse_only))
    with pytest.raises(ValueError, match="staged"):
        trainer.train_epoch(state, sparse_only, np.random.default_rng(0),
                            staged=None)
    with pytest.raises(ValueError, match="staged"):
        trainer.evaluate(state, sparse_only, staged=None)


def test_stream_sparse_refresh_parity():
    """StreamingTrainer with the padded-COO ring reproduces the dense
    stream's refresh losses bit-for-bit (dense runs staged too, so both
    sides drive the same superstep; staged≡host is pinned elsewhere)."""
    from deeprest_tpu.train.stream import StreamConfig, StreamingTrainer

    buckets = make_series_buckets(90, seed=7)

    def run(sparse):
        tc = TrainConfig(batch_size=8, window_size=6, seed=0,
                         eval_stride=1, eval_max_cycles=2,
                         log_every_steps=0, device_data="always",
                         sparse_feed=sparse, sparse_nnz_cap=64)
        cfg = Config(model=ModelConfig(feature_dim=128, hidden_size=8),
                     train=tc)
        st = StreamingTrainer(
            cfg, StreamConfig(refresh_buckets=30, finetune_epochs=1,
                              eval_holdout=2),
            feature_config=FeaturizeConfig(hash_features=True,
                                           capacity=128))
        out = []
        for b in buckets:
            st.ingest(b)
            if st.ready():
                r = st.refresh()
                out.append((r.train_loss, r.eval_loss))
        assert isinstance(st.traffic,
                          SparseSeriesRing if sparse else SeriesRing)
        return out

    dense, sparse = run(False), run(True)
    assert len(dense) >= 2
    assert dense == sparse


# ---------------------------------------------------------------------------
# serve: fused sparse path ≡ fused dense path, zero new executables


def _serve_fixture(sparse: bool, k: int = 16):
    import jax

    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.serve.predictor import Predictor

    rng = np.random.default_rng(0)
    f, e, w = 64, 3, 10
    mc = ModelConfig(feature_dim=f, num_metrics=e, hidden_size=8)
    params = dict(QuantileGRU(config=mc).init(
        jax.random.PRNGKey(0), np.zeros((1, w, f), np.float32))["params"])
    dense = np.zeros((37, f), np.float32)
    for t in range(37):
        nz = rng.choice(f, size=rng.integers(1, 8), replace=False)
        dense[t, nz] = rng.integers(1, 50, size=len(nz))
    x_stats = MinMaxStats(min=np.zeros((1, f), np.float32),
                          max=dense.max(0, keepdims=True).astype(np.float32))
    y_stats = MinMaxStats(min=np.zeros((1, e), np.float32),
                          max=np.full((1, e), 5.0, np.float32))
    names = ["c0_cpu", "c1_cpu", "c2_usage"]
    pred = Predictor(params, mc, x_stats, y_stats, names, w,
                     delta_mask=np.array([False, False, True]),
                     sparse_feed=sparse, sparse_nnz_cap=k)
    return pred, dense


def test_fused_sparse_predict_bit_identical():
    dense_pred, traffic = _serve_fixture(False)
    sparse_pred, _ = _serve_fixture(True)
    cols, vals, _ = sparsify_rows(traffic, 16)
    for integrate in (True, False):
        ref = dense_pred.predict_series(traffic, integrate=integrate)
        got = sparse_pred.predict_series_sparse(cols, vals,
                                                integrate=integrate)
        np.testing.assert_array_equal(got, ref)
    # multi-series fold (the what-if backbone) matches too
    many_ref = dense_pred.predict_series_many([traffic, traffic[:20]])
    many_got = sparse_pred.predict_series_many_sparse(
        [(cols, vals), (cols[:20], vals[:20])])
    for a, b in zip(many_ref, many_got):
        np.testing.assert_array_equal(b, a)


def test_dense_entry_auto_routes_sparse_on_sparse_feed_backend():
    """A sparse_feed backend converts DENSE wire inputs (HTTP JSON,
    featurized corpora) to COO host-side and ships the small pages —
    bit-identical outputs, sparse program actually exercised; a row over
    the K cap falls back to the dense feed (warned once, never a 500)."""
    dense_pred, traffic = _serve_fixture(False)
    sparse_pred, _ = _serve_fixture(True)
    ref = dense_pred.predict_series(traffic)
    got = sparse_pred.predict_series(traffic)       # dense entry!
    np.testing.assert_array_equal(got, ref)
    probe = getattr(sparse_pred.fused._jit_sparse, "_cache_size", None)
    if callable(probe):
        assert probe() >= 1                         # COO pages shipped
    many = sparse_pred.predict_series_many([traffic, traffic[:20]])
    for a, b in zip(dense_pred.predict_series_many([traffic, traffic[:20]]),
                    many):
        np.testing.assert_array_equal(b, a)
    # fat row: dense fallback, still bit-exact
    fat = np.array(traffic, copy=True)
    fat[3, :] = 1.0                                 # 64 nonzeros > K=16
    np.testing.assert_array_equal(sparse_pred.predict_series(fat),
                                  dense_pred.predict_series(fat))


def test_apply_windows_sparse_parity_and_fallback():
    from deeprest_tpu.data.windows import minmax_apply

    dense_pred, traffic = _serve_fixture(False)
    sparse_pred, _ = _serve_fixture(True)
    w = dense_pred.window_size
    wins = np.stack([traffic[i:i + w] for i in range(20)])
    wc, wv, _ = sparsify_rows(wins, 16)
    ref = dense_pred.apply_windows(
        minmax_apply(wins, dense_pred.x_stats).astype(np.float32))
    np.testing.assert_array_equal(sparse_pred.apply_windows_sparse(wc, wv),
                                  ref)
    # a dense-only backend still serves sparse callers (host densify)
    np.testing.assert_array_equal(dense_pred.apply_windows_sparse(wc, wv),
                                  ref)
    cols, vals, _ = sparsify_rows(traffic, 16)
    np.testing.assert_array_equal(
        dense_pred.predict_series_sparse(cols, vals),
        dense_pred.predict_series(traffic))


def test_sparse_serve_zero_new_executables_after_warmup():
    sparse_pred, traffic = _serve_fixture(True)
    cols, vals, _ = sparsify_rows(traffic, 16)
    # warm: mixed lengths hit the rung set (fused sparse program) + the
    # laddered sparse apply
    sparse_pred.predict_series_sparse(cols, vals)
    sparse_pred.predict_series_sparse(cols[:25], vals[:25])
    w = sparse_pred.window_size
    wins = np.stack([traffic[i:i + w] for i in range(20)])
    wc, wv, _ = sparsify_rows(wins, 16)
    sparse_pred.apply_windows_sparse(wc, wv)          # rung 32
    sparse_pred.apply_windows_sparse(wc[:9], wv[:9])  # rung 16
    warmed = sparse_pred.jit_cache_size()
    assert warmed is not None and warmed >= 1
    # steady state: new lengths inside the warmed rungs compile NOTHING
    sparse_pred.predict_series_sparse(cols[:30], vals[:30])
    sparse_pred.predict_series_sparse(cols[:22], vals[:22])
    sparse_pred.apply_windows_sparse(wc[:11], wv[:11])
    assert sparse_pred.jit_cache_size() == warmed
    stats = sparse_pred.jit_cache_stats()
    assert stats["apply_sparse"] is not None


def test_fused_engine_rejects_mismatched_k():
    sparse_pred, traffic = _serve_fixture(True, k=16)
    cols, vals, _ = sparsify_rows(traffic, 8)   # wrong K: falls back...
    ref = sparse_pred.predict_series(traffic)
    got = sparse_pred.predict_series_sparse(cols, vals)
    np.testing.assert_array_equal(got, ref)     # ...bit-exactly (host densify)
    with pytest.raises(ValueError, match="nnz cap"):
        sparse_pred.fused.predict_many_sparse([(cols, vals)])


# ---------------------------------------------------------------------------
# distributed COO feed


def test_feed_global_coo_shapes_and_divisibility():
    import jax

    from deeprest_tpu.parallel.distributed import feed_global_coo
    from deeprest_tpu.parallel.mesh import make_mesh

    mesh = make_mesh()
    cols = np.zeros((8, 5, 4), np.int32)
    vals = np.ones((8, 5, 4), np.float32)
    c, v = feed_global_coo(mesh, cols, vals)
    assert isinstance(c, jax.Array) and c.shape == cols.shape
    np.testing.assert_array_equal(np.asarray(v), vals)
    with pytest.raises(ValueError, match="disagree"):
        feed_global_coo(mesh, cols, vals[:, :, :3])
