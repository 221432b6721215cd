"""Fused device-resident rolled inference (serve/fused.py): parity with the
pinned host-loop reference, the prefix-sum delta carry (ragged tails,
multi-page carry threading, multi-series folds), the batched what-if entry,
and the zero-post-warmup-compile guarantee.

Quick tier: random-init models at tiny dims — the numerics contract does
not depend on trained weights (same rationale as test_serve_batch.py).

Numerics contract pinned here (acceptance criteria of the fused pipeline):
- non-delta metrics: BIT-EXACT vs rolled_prediction_reference on CPU;
- delta metrics: <= 1e-5 relative tolerance (the on-device invert may
  contract to FMA and the prefix sum re-associates the reference's
  sequential float32 carry adds);
- integrate=False (the anomaly detector's increment-space path): BIT-EXACT.
"""

import numpy as np
import pytest

from conftest import assert_fold_equal

from deeprest_tpu.config import ModelConfig
from deeprest_tpu.data.windows import MinMaxStats
from deeprest_tpu.serve import ExportedPredictor, Predictor, export_predictor
from deeprest_tpu.serve.predictor import rolled_prediction_reference

F, E, H, W = 6, 3, 8, 8
DELTA = np.array([True, False, True])
DELTA_RTOL = 1e-5


def make_predictor(delta_mask=None, ladder=(2, 4, 8), x_degenerate=False,
                   **kw):
    import jax

    from deeprest_tpu.models.qrnn import QuantileGRU

    mc = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, W, F), np.float32),
                        deterministic=True)["params"]
    # degenerate x range exercises the MinMaxStats pass-through guard on
    # device (max == min → values pass unchanged)
    x_stats = (MinMaxStats(min=np.float32(0.5), max=np.float32(0.5))
               if x_degenerate else
               MinMaxStats(min=np.float32(0.2), max=np.float32(0.9)))
    return Predictor(
        params, mc,
        x_stats=x_stats,
        y_stats=MinMaxStats(min=np.linspace(1, 2, E).astype(np.float32),
                            max=np.linspace(3, 7, E).astype(np.float32)),
        metric_names=[f"c{i}_{'usage' if DELTA[i] else 'cpu'}"
                      for i in range(E)],
        window_size=W, delta_mask=delta_mask, ladder=ladder, **kw)


def reference(pred, traffic, integrate=True):
    return rolled_prediction_reference(
        pred.apply_windows, pred.x_stats, pred.y_stats, W, traffic,
        delta_mask=pred.delta_mask if integrate else None,
        median_index=pred.median_index())


@pytest.fixture(scope="module")
def pred_delta():
    return make_predictor(delta_mask=DELTA)


@pytest.fixture(scope="module")
def pred_plain():
    return make_predictor()


# ---------------------------------------------------------------------------
# Fused vs reference parity matrix


@pytest.mark.parametrize("length", [
    W,               # single window
    W + 3,           # ragged right-aligned tail
    3 * W,           # window-multiple, one page
    5 * W + 5,       # ragged, multiple pages (page = top rung 8 windows)
    20 * W + 7,      # many pages: carry threads across page boundaries
])
def test_fused_matches_reference(pred_delta, length):
    rng = np.random.default_rng(length)
    x = rng.random((length, F)).astype(np.float32)
    ref = reference(pred_delta, x)
    got = pred_delta.predict_series(x)
    nd = ~DELTA
    np.testing.assert_array_equal(got[:, nd], ref[:, nd],
                                  err_msg="non-delta columns must be "
                                          "bit-exact vs the host loop")
    np.testing.assert_allclose(got[:, DELTA], ref[:, DELTA],
                               rtol=DELTA_RTOL, atol=0)
    # increment space (anomaly's domain) is bit-exact: no carry involved
    np.testing.assert_array_equal(
        pred_delta.predict_series(x, integrate=False),
        reference(pred_delta, x, integrate=False))


def test_fused_no_delta_fully_bit_exact(pred_plain):
    rng = np.random.default_rng(0)
    for length in (W, 4 * W + 2, 11 * W + 5):
        x = rng.random((length, F)).astype(np.float32)
        np.testing.assert_array_equal(pred_plain.predict_series(x),
                                      reference(pred_plain, x))


def test_fused_degenerate_x_range_passthrough():
    pred = make_predictor(delta_mask=DELTA, x_degenerate=True)
    rng = np.random.default_rng(1)
    x = rng.random((3 * W + 2, F)).astype(np.float32)
    ref = reference(pred, x)
    got = pred.predict_series(x)
    np.testing.assert_array_equal(got[:, ~DELTA], ref[:, ~DELTA])
    np.testing.assert_allclose(got[:, DELTA], ref[:, DELTA],
                               rtol=DELTA_RTOL, atol=0)


def test_fused_short_series_raises(pred_plain):
    with pytest.raises(ValueError, match="window"):
        pred_plain.predict_series(np.zeros((W - 1, F), np.float32))


def test_fused_disabled_falls_back_to_reference():
    pred = make_predictor(delta_mask=DELTA, fused=False)
    assert pred.fused is None
    rng = np.random.default_rng(2)
    x = rng.random((2 * W + 3, F)).astype(np.float32)
    np.testing.assert_array_equal(pred.predict_series(x), reference(pred, x))


# ---------------------------------------------------------------------------
# Multi-series folding (the scenario×window batch axis)


def test_fold_matches_per_series(pred_delta):
    """Folding several series into shared pages must not change results:
    non-delta equal (row-independent model; conftest.assert_fold_equal for
    why a few ulp and not bits once the row count differs), the per-series
    carry reset within the documented delta tolerance."""
    rng = np.random.default_rng(3)
    xs = [rng.random((t, F)).astype(np.float32)
          for t in (3 * W, 2 * W + 5, W, 9 * W + 1)]
    singles = [pred_delta.predict_series(x) for x in xs]
    folded = pred_delta.predict_series_many(xs)
    assert [o.shape for o in folded] == [s.shape for s in singles]
    for singl, fold in zip(singles, folded):
        assert_fold_equal(fold[:, ~DELTA], singl[:, ~DELTA])
        np.testing.assert_allclose(fold[:, DELTA], singl[:, DELTA],
                                   rtol=DELTA_RTOL, atol=0)


def test_fold_carry_isolation(pred_delta):
    """A scenario's integration rollout must not leak into the next one
    sharing its page: permuting batch-mates changes nothing."""
    rng = np.random.default_rng(4)
    a = rng.random((2 * W, F)).astype(np.float32)
    b = (10.0 * rng.random((2 * W, F))).astype(np.float32)
    out_ab = pred_delta.predict_series_many([a, b])
    out_ba = pred_delta.predict_series_many([b, a])
    np.testing.assert_allclose(out_ab[0], out_ba[1], rtol=DELTA_RTOL, atol=0)
    np.testing.assert_allclose(out_ab[1], out_ba[0], rtol=DELTA_RTOL, atol=0)


def test_predict_series_many_empty_and_fallback(pred_delta):
    assert pred_delta.predict_series_many([]) == []
    no_fused = make_predictor(delta_mask=DELTA, fused=False)
    rng = np.random.default_rng(5)
    xs = [rng.random((2 * W, F)).astype(np.float32) for _ in range(2)]
    outs = no_fused.predict_series_many(xs)
    for x, o in zip(xs, outs):
        np.testing.assert_array_equal(o, reference(no_fused, x))


# ---------------------------------------------------------------------------
# Zero post-warmup compiles / cache probes / routing


def test_mixed_lengths_and_sweeps_compile_nothing_new(pred_delta):
    rng = np.random.default_rng(6)
    # warm every fused rung (pages chunk at `page`; a long series walks
    # the tail rungs too)
    for rung in pred_delta.fused.rungs:
        pred_delta.predict_series(
            rng.random((rung * W, F)).astype(np.float32))
        pred_delta.predict_series(
            rng.random((rung * W, F)).astype(np.float32), integrate=False)
    cache = pred_delta.jit_cache_size()
    if cache is None:
        pytest.skip("no jit cache probe on this jax version")
    for length in (W, W + 1, 2 * W + 3, 7 * W + 5):
        pred_delta.predict_series(rng.random((length, F)).astype(np.float32))
        pred_delta.predict_series(
            rng.random((length, F)).astype(np.float32), integrate=False)
    for s_count in (1, 2, 5):
        pred_delta.predict_series_many(
            [rng.random((W + i, F)).astype(np.float32)
             for i in range(s_count)])
    assert pred_delta.jit_cache_size() == cache
    stats = pred_delta.jit_cache_stats()
    assert stats["fused"] >= 1


def test_batcher_routing_keeps_small_series_coalescable():
    """With a MicroBatcher attached, single-dispatch-sized series keep the
    coalescing path; longer series take the fused engine."""
    from deeprest_tpu.serve import BatcherConfig, MicroBatcher

    pred = make_predictor(ladder=(2, 4))
    batcher = MicroBatcher(pred.ladder,
                           BatcherConfig(max_batch=4, max_linger_s=0.0))
    try:
        pred.attach_batcher(batcher)
        rng = np.random.default_rng(7)
        before = pred.fused.stats()["windows"]
        pred.predict_series(rng.random((2 * W, F)).astype(np.float32))
        assert pred.fused.stats()["windows"] == before     # coalesced path
        assert batcher.stats()["windows"] >= 2
        pred.predict_series(rng.random((6 * W, F)).astype(np.float32))
        assert pred.fused.stats()["windows"] == before + 6  # fused path
    finally:
        pred.attach_batcher(None)
        batcher.close()


def test_page_windows_override():
    pred = make_predictor(delta_mask=DELTA, page_windows=3)
    assert pred.fused.page == 3
    assert 3 in pred.fused.rungs
    rng = np.random.default_rng(8)
    x = rng.random((7 * W + 4, F)).astype(np.float32)   # 8 windows → 3 pages
    ref = reference(pred, x)
    got = pred.predict_series(x)
    # pages of 3 rows against the reference's own batch: assert_fold_equal
    assert_fold_equal(got[:, ~DELTA], ref[:, ~DELTA])
    np.testing.assert_allclose(got[:, DELTA], ref[:, DELTA],
                               rtol=DELTA_RTOL, atol=0)
    assert pred.fused.stats()["pages"] == 3


# ---------------------------------------------------------------------------
# ExportedPredictor over the fused path


@pytest.fixture(scope="module")
def exported(pred_delta, tmp_path_factory):
    art = str(tmp_path_factory.mktemp("artifact"))
    export_predictor(pred_delta, art)
    return ExportedPredictor.load(art, ladder=(2, 4, 8))


def test_exported_fused_parity(pred_delta, exported):
    """Artifact vs in-process parity over the fused path: delta metrics,
    ragged lengths (t not a multiple of W·page), and integrate=False.
    Different executables (StableHLO module vs in-process apply) → the
    documented serving tolerance, not bit equality."""
    rng = np.random.default_rng(9)
    for length in (W, 3 * W + 5, 9 * W + 2):
        x = rng.random((length, F)).astype(np.float32)
        np.testing.assert_allclose(
            exported.predict_series(x), pred_delta.predict_series(x),
            rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            exported.predict_series(x, integrate=False),
            pred_delta.predict_series(x, integrate=False),
            rtol=1e-5, atol=1e-5)


def test_exported_fused_vs_own_reference(exported):
    """The artifact's fused path must match ITS OWN host-loop reference
    bit-exactly on non-delta columns (same executable both sides)."""
    rng = np.random.default_rng(10)
    x = rng.random((4 * W + 3, F)).astype(np.float32)
    ref = reference(exported, x)
    got = exported.predict_series(x)
    np.testing.assert_array_equal(got[:, ~DELTA], ref[:, ~DELTA])
    np.testing.assert_allclose(got[:, DELTA], ref[:, DELTA],
                               rtol=DELTA_RTOL, atol=0)
    assert exported.jit_cache_size() >= 1


def test_exported_fold(exported, pred_delta):
    rng = np.random.default_rng(11)
    xs = [rng.random((t, F)).astype(np.float32) for t in (2 * W, W + 5)]
    a = exported.predict_series_many(xs)
    b = pred_delta.predict_series_many(xs)
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# What-if: batched scenarios, sweep grid, scaling-factor conventions


class _StubSpace:
    capacity = F


class _StubSynth:
    space = _StubSpace()
    endpoints = ["ep"]

    def synthesize_series(self, program, seed=0):
        rng = np.random.default_rng(seed)
        scale = np.asarray([p.get("ep", 0) for p in program], np.float32)
        return (rng.random((len(program), F), np.float32)
                * (0.05 + 0.01 * scale[:, None]))


def test_estimate_many_matches_sequential_estimates(pred_delta):
    from deeprest_tpu.serve import WhatIfEstimator

    est = WhatIfEstimator(pred_delta, _StubSynth())
    programs = [[{"ep": 5}] * (2 * W), [{"ep": 20}] * (3 * W + 4)]
    batched = est.estimate_many(programs, seed=7)
    singles = [est.estimate(programs[i], seed=7 + i) for i in range(2)]
    for got, want in zip(batched, singles):
        assert set(got) == set(want)
        for metric in want:
            for q in want[metric]:
                np.testing.assert_allclose(got[metric][q], want[metric][q],
                                           rtol=DELTA_RTOL, atol=0)


def test_sweep_grid_shapes(pred_delta):
    from deeprest_tpu.serve import WhatIfEstimator

    est = WhatIfEstimator(pred_delta, _StubSynth())
    records = est.sweep([{"ep": 10}] * (2 * W), factors=[0.5, 1.0, 2.0],
                        seed=0)
    assert [r["factor"] for r in records] == [0.5, 1.0, 2.0]
    for r in records:
        assert set(r["peaks"]) == set(pred_delta.metric_names)
        for metric, per_q in r["peaks"].items():
            assert set(per_q) == {"q05", "q50", "q95"}
            assert all(np.isfinite(v) for v in per_q.values())
    with pytest.raises(ValueError, match="factor"):
        est.sweep([{"ep": 1}] * W, factors=[])


def test_scaling_factor_zero_peak_conventions():
    """Satellite: absolute metrics with both peaks zero must report 1.0
    (no change), not inf; zero baseline with real load stays inf."""
    from deeprest_tpu.serve import WhatIfEstimator

    class ZeroPred:
        feature_dim = F
        metric_names = ["m_cpu"]
        quantiles = (0.05, 0.5, 0.95)
        delta_mask = None
        window_size = W

        def __init__(self):
            self.peaks = {}

        def predict_series_many(self, xs):
            # peak encodes the per-call scale of the stub synth series
            return [np.full((len(x), 1, 3),
                            0.0 if float(x.max()) < 1e-4 else 1.0,
                            np.float32)
                    for x in xs]

    class ZeroSynth:
        space = _StubSpace()
        endpoints = ["ep"]

        def synthesize_series(self, program, seed=0):
            scale = sum(p.get("ep", 0) for p in program)
            return np.full((len(program), F),
                           1e-6 if scale == 0 else 1.0, np.float32)

    est = WhatIfEstimator(ZeroPred(), ZeroSynth())
    idle = [{"ep": 0}] * W
    busy = [{"ep": 9}] * W
    assert est.scaling_factor(idle, idle)["m_cpu"] == 1.0
    assert est.scaling_factor(idle, busy)["m_cpu"] == float("inf")
    assert est.scaling_factor(busy, busy)["m_cpu"] == 1.0
