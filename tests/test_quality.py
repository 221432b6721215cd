"""Model-quality observability (ISSUE 13 / ROADMAP item 6): the verdict
state machine, the online monitors, their batch-recompute parity, and the
full drift→retrain→hot-reload loop under live load.

Every behavior here is a design decision (the reference never monitors
its own model quality — drift is detected by a human noticing bad
capacity answers), pinned against obs/quality.py's documented contracts.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
from conftest import make_series_buckets

from deeprest_tpu.config import (
    Config, FeaturizeConfig, ModelConfig, QualityConfig, TrainConfig,
)
from deeprest_tpu.data.schema import Bucket, MetricSample, Span
from deeprest_tpu.data.windows import MinMaxStats
from deeprest_tpu.obs.quality import (
    VERDICT_ANOMALY, VERDICT_DRIFT, VERDICT_OK, FeatureDriftMonitor,
    HysteresisVerdict, QualityMonitor, WindowBackend,
)
from deeprest_tpu.train.stream import (
    DriftController, StreamConfig, StreamingTrainer,
)

CAPACITY = 32
WINDOW = 6


# ---------------------------------------------------------------------------
# HysteresisVerdict: the enter/sustain/exit matrix + flap suppression


def test_hysteresis_enters_only_after_sustained_windows():
    m = HysteresisVerdict(enter=0.5, exit=0.2, sustain_enter=3,
                          sustain_exit=2)
    assert not m.update(0.9)
    assert not m.update(0.9)
    assert m.update(0.9)          # third consecutive window enters
    assert m.transitions == 1


def test_hysteresis_noisy_single_windows_never_flap():
    m = HysteresisVerdict(enter=0.5, exit=0.2, sustain_enter=2,
                          sustain_exit=2)
    # alternating over/under the enter threshold: the streak resets
    # every other window, so the machine never activates
    for score in (0.9, 0.1) * 20:
        assert not m.update(score)
    assert m.transitions == 0


def test_hysteresis_band_between_thresholds_holds_state():
    m = HysteresisVerdict(enter=0.5, exit=0.2, sustain_enter=1,
                          sustain_exit=2)
    assert m.update(0.9)                       # active
    # scores in (exit, enter) neither sustain an exit nor re-enter:
    # the state HOLDS (this is the hysteresis band)
    for score in (0.3, 0.4, 0.45, 0.3) * 5:
        assert m.update(score)
    assert m.transitions == 1


def test_hysteresis_exit_requires_sustained_quiet():
    m = HysteresisVerdict(enter=0.5, exit=0.2, sustain_enter=1,
                          sustain_exit=3)
    m.update(0.9)
    assert m.update(0.1) and m.update(0.1)     # 2 quiet: still active
    assert not m.update(0.1)                   # third quiet exits
    assert m.transitions == 2


def test_hysteresis_exit_streak_resets_on_spike():
    m = HysteresisVerdict(enter=0.5, exit=0.2, sustain_enter=1,
                          sustain_exit=2)
    m.update(0.9)
    m.update(0.1)
    m.update(0.3)      # inside the band: exit streak resets
    assert m.update(0.1)                       # only 1 quiet again
    assert not m.update(0.1)
    assert m.transitions == 2


def test_hysteresis_validates_thresholds():
    with pytest.raises(ValueError):
        HysteresisVerdict(enter=0.2, exit=0.5)
    with pytest.raises(ValueError):
        HysteresisVerdict(enter=0.5, exit=0.2, sustain_enter=0)


# ---------------------------------------------------------------------------
# FeatureDriftMonitor: streaming sparse PSI/KS


def _sparse_rows(rng, cols_pool, n_rows, scale=8.0):
    rows = []
    for _ in range(n_rows):
        k = rng.integers(1, len(cols_pool) + 1)
        cols = np.sort(rng.choice(cols_pool, size=k, replace=False))
        vals = rng.poisson(scale, size=k).astype(np.float32) + 1.0
        rows.append((cols.astype(np.int32), vals))
    return rows


def test_drift_monitor_same_distribution_scores_near_zero():
    rng = np.random.default_rng(0)
    pool = np.array([2, 5, 9, 17])
    mon = FeatureDriftMonitor()
    mon.set_reference(_sparse_rows(rng, pool, 200))
    s = mon.compare(_sparse_rows(rng, pool, 100))
    assert s.psi < 0.1 and s.columns_over == 0


def test_drift_monitor_flags_added_and_removed_columns():
    rng = np.random.default_rng(1)
    mon = FeatureDriftMonitor()
    mon.set_reference(_sparse_rows(rng, np.array([2, 5, 9]), 200))
    # topology change: column 9 vanished, columns 20/21 appeared
    s = mon.compare(_sparse_rows(rng, np.array([2, 20, 21]), 100))
    assert s.psi > 0.5
    assert s.columns_over >= 2          # the appeared/vanished columns
    assert s.columns == 5               # union of both windows


def test_drift_monitor_flags_count_scale_shift():
    # same columns, 8x the per-bucket counts (a composition shift onto
    # the same call paths)
    rng = np.random.default_rng(2)
    pool = np.array([3, 7])
    mon = FeatureDriftMonitor()
    mon.set_reference(_sparse_rows(rng, pool, 200, scale=4.0))
    s = mon.compare(_sparse_rows(rng, pool, 100, scale=32.0))
    assert s.psi > 0.5 and s.ks_max > 0.3


def test_drift_monitor_dense_rows_match_sparse_rows():
    rng = np.random.default_rng(3)
    pool = np.array([1, 4, 6])
    sparse = _sparse_rows(rng, pool, 50)
    dense = []
    for cols, vals in sparse:
        row = np.zeros((CAPACITY,), np.float32)
        row[cols] = vals
        dense.append(row)
    a, b = FeatureDriftMonitor(), FeatureDriftMonitor()
    a.set_reference(sparse)
    b.set_reference(dense)
    rng2 = np.random.default_rng(4)
    live_sparse = _sparse_rows(rng2, pool, 30)
    live_dense = []
    for cols, vals in live_sparse:
        row = np.zeros((CAPACITY,), np.float32)
        row[cols] = vals
        live_dense.append(row)
    sa, sb = a.compare(live_sparse), b.compare(live_dense)
    assert sa.psi == sb.psi and sa.ks_max == sb.ks_max


def test_drift_monitor_compare_requires_reference():
    with pytest.raises(RuntimeError):
        FeatureDriftMonitor().compare([])


# ---------------------------------------------------------------------------
# QualityMonitor: sweeps, calibration parity, verdict precedence


class _FakeBackend:
    """Deterministic serving surface: the q50 band tracks the traffic
    row-sum, q05/q95 bracket it; wide enough that in-distribution
    observations are covered."""

    def __init__(self, metric_names, window_size=WINDOW,
                 feature_dim=CAPACITY, gain=1.0):
        self.metric_names = list(metric_names)
        self.window_size = window_size
        self.feature_dim = feature_dim
        self.quantiles = (0.05, 0.50, 0.95)
        self.delta_mask = None
        self.y_stats = MinMaxStats(
            min=np.zeros((len(metric_names),), np.float32),
            max=np.ones((len(metric_names),), np.float32))
        self.gain = gain
        self.calls = 0

    def median_index(self):
        return 1

    def predict_series(self, traffic, integrate=True):
        self.calls += 1
        base = traffic.sum(axis=1, keepdims=True) * self.gain   # [T, 1]
        e = len(self.metric_names)
        med = np.repeat(base, e, axis=1)                        # [T, E]
        preds = np.stack([med * 0.5, med, med * 1.5 + 1.0], axis=-1)
        return preds.astype(np.float32)


def _observe_rows(monitor, rng, n, level=8.0):
    rows = []
    for _ in range(n):
        cols = np.array([1, 3], np.int32)
        vals = rng.poisson(level, size=2).astype(np.float32) + 1.0
        y = np.array([float(vals.sum())], np.float32)   # in-band by design
        monitor.observe(cols, vals, y)
        rows.append(((cols.copy(), vals.copy()), y.copy()))
    return rows


def test_sweep_requires_reference_and_window():
    qc = QualityConfig(enabled=True, min_sweep_buckets=4)
    m = QualityMonitor(["svc_cpu"], qc)
    backend = _FakeBackend(["svc_cpu"])
    assert m.sweep(backend)["armed"] is False       # no reference
    rng = np.random.default_rng(0)
    _observe_rows(m, rng, 2)
    m.rebase_reference()
    assert m.sweep(backend)["armed"] is False       # < window buckets


def test_coverage_monitor_parity_vs_batch_recompute():
    """The rolling coverage/pinball aggregates must equal a batch
    recompute over the SAME windows through the SAME aligned bands —
    bit-equal, not approximately (the monitor stores exact per-sweep
    integer covered counts and float64 pinball sums)."""
    from deeprest_tpu.serve.anomaly import AnomalyDetector

    names = ["svc_cpu", "db_wiops"]
    qc = QualityConfig(enabled=True, min_sweep_buckets=WINDOW,
                       calibration_sweeps=3, live_window=16)
    m = QualityMonitor(names, qc)
    backend = _FakeBackend(names)
    rng = np.random.default_rng(7)

    windows = []       # the exact trailing window of each sweep
    all_rows = []

    def obs(n):
        for _ in range(n):
            cols = np.array([1, 3], np.int32)
            vals = rng.poisson(8.0, size=2).astype(np.float32) + 1.0
            y = np.array([float(vals.sum()),
                          float(vals.sum()) * 2.0], np.float32)
            m.observe(cols, vals, y)
            all_rows.append(((cols, vals), y))

    obs(WINDOW * 2)
    m.rebase_reference()
    for _ in range(5):                  # > calibration_sweeps: rolls over
        obs(WINDOW)
        out = m.sweep(backend)
        assert out["armed"]
        windows.append(list(all_rows[-WINDOW:]))

    # batch recompute over the LAST calibration_sweeps windows
    covered = np.zeros(2, np.int64)
    total = 0
    pin_sum = np.zeros(2, np.float64)
    qs = np.asarray(sorted(backend.quantiles))
    for win in windows[-qc.calibration_sweeps:]:
        traffic = np.zeros((WINDOW, CAPACITY), np.float32)
        for i, ((cols, vals), _) in enumerate(win):
            traffic[i, cols] = vals
        observed = np.stack([y for _, y in win])
        det = AnomalyDetector(backend, tolerance=qc.anomaly_tolerance,
                              min_run=qc.anomaly_min_run)
        bands = det.aligned(traffic, observed)
        scale = np.maximum(
            bands.scale,
            np.asarray(backend.y_stats.range, np.float32).reshape(-1))
        margin = qc.anomaly_tolerance * scale
        covered += ((bands.observed >= bands.preds[..., 0] - margin)
                    & (bands.observed
                       <= bands.preds[..., -1] + margin)).sum(axis=0)
        total += WINDOW
        err = bands.observed[..., None] - bands.preds
        pin_sum += np.maximum((qs - 1.0) * err, qs * err).sum(
            axis=-1).sum(axis=0, dtype=np.float64)

    assert np.array_equal(m.calibration.coverage(), covered / total)
    assert np.array_equal(m.calibration.pinball(), pin_sum / total)
    # and the verdict surface reports the same numbers
    v = m.verdicts()
    for e, name in enumerate(names):
        assert v["metrics"][name]["coverage"] == round(
            float(covered[e] / total), 4)


def test_anomaly_verdict_fires_and_drift_takes_precedence():
    names = ["svc_cpu"]
    qc = QualityConfig(enabled=True, min_sweep_buckets=WINDOW,
                       sustain_enter=2, sustain_exit=2,
                       drift_enter=0.5, drift_exit=0.2,
                       live_window=2 * WINDOW)
    m = QualityMonitor(names, qc)
    backend = _FakeBackend(names)
    rng = np.random.default_rng(0)
    _observe_rows(m, rng, 2 * WINDOW)
    m.rebase_reference()

    # in-band, in-reference: everything ok
    for _ in range(2):
        _observe_rows(m, rng, WINDOW)
        m.sweep(backend)
    assert m.verdicts()["metrics"]["svc_cpu"]["state"] == VERDICT_OK

    # excess WITHOUT feature drift (same traffic columns/levels, observed
    # far above the band): anomaly verdict after sustain_enter sweeps
    for _ in range(2):
        for _ in range(WINDOW):
            cols = np.array([1, 3], np.int32)
            vals = rng.poisson(8.0, size=2).astype(np.float32) + 1.0
            m.observe(cols, vals,
                      np.array([float(vals.sum()) * 50.0], np.float32))
        m.sweep(backend)
    assert m.verdicts()["metrics"]["svc_cpu"]["state"] == VERDICT_ANOMALY
    assert m.any_active(VERDICT_ANOMALY)

    # now the traffic DISTRIBUTION shifts too: feature drift activates
    # and takes precedence — the band is no longer trustworthy, so the
    # metric reads drift, not anomaly (the temporal-disambiguation rule).
    # Three rounds, so a full live_window of the new regime is retained
    # before the post-refresh rebase below.
    for _ in range(3):
        for _ in range(WINDOW):
            cols = np.array([20, 25, 28], np.int32)
            vals = (rng.poisson(30.0, size=3).astype(np.float32) + 1.0)
            m.observe(cols, vals, np.array([500.0], np.float32))
        m.sweep(backend)
    v = m.verdicts()
    assert v["feature_drift"]["state"] == VERDICT_DRIFT
    assert v["metrics"]["svc_cpu"]["state"] == VERDICT_DRIFT
    assert not m.any_active(VERDICT_ANOMALY)     # masked by drift

    # model refresh: anomaly/calibration machines reset; drift machine
    # survives until its reference re-anchors
    m.on_model_refresh()
    v = m.verdicts()
    assert v["metrics"]["svc_cpu"]["state"] == VERDICT_DRIFT
    # the retrained model's baseline is the RECENT (shifted) traffic —
    # the regime continues, the reference now matches it, drift exits
    m.rebase_reference()
    for _ in range(2):
        for _ in range(WINDOW):
            cols = np.array([20, 25, 28], np.int32)
            vals = (rng.poisson(30.0, size=3).astype(np.float32) + 1.0)
            m.observe(cols, vals, np.array([500.0], np.float32))
        m.sweep(backend)
    assert m.verdicts()["feature_drift"]["state"] == VERDICT_OK


def test_monitor_publishes_prometheus_gauges():
    from deeprest_tpu.obs import metrics as obs_metrics

    names = ["svc_cpu"]
    qc = QualityConfig(enabled=True, min_sweep_buckets=WINDOW,
                       live_window=16)
    m = QualityMonitor(names, qc)
    backend = _FakeBackend(names)
    rng = np.random.default_rng(0)
    _observe_rows(m, rng, 2 * WINDOW)
    m.rebase_reference()
    _observe_rows(m, rng, WINDOW)
    assert m.sweep(backend)["armed"]
    text = obs_metrics.REGISTRY.render()
    for needle in ("deeprest_quality_sweeps_total",
                   "deeprest_feature_drift_psi",
                   'deeprest_quality_band_coverage{metric="svc_cpu"}',
                   'deeprest_quality_verdict{metric="svc_cpu"}'):
        assert needle in text, needle


# ---------------------------------------------------------------------------
# WindowBackend: parity with the pinned host-loop reference


def test_window_backend_matches_reference_single_window():
    import jax

    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.serve.predictor import rolled_prediction_reference

    mc = ModelConfig(feature_dim=8, num_metrics=2, hidden_size=8,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    rng = np.random.default_rng(0)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, WINDOW, 8), np.float32),
                        deterministic=True)["params"]
    x_stats = MinMaxStats(min=np.zeros((1, 8), np.float32),
                          max=np.full((1, 8), 10.0, np.float32))
    y_stats = MinMaxStats(min=np.zeros((2,), np.float32),
                          max=np.asarray([5.0, 9.0], np.float32))
    apply_fn = jax.jit(lambda p, x: model.apply({"params": p}, x,
                                                deterministic=True))
    wb = WindowBackend(apply_fn, params, x_stats, y_stats,
                       ["a_cpu", "b_cpu"], mc.quantiles, WINDOW)
    traffic = rng.random((WINDOW, 8)).astype(np.float32) * 4.0
    got = wb.predict_series(traffic, integrate=False)
    want = rolled_prediction_reference(
        lambda x: apply_fn(params, x), x_stats, y_stats, WINDOW, traffic)
    np.testing.assert_array_equal(got, want)
    assert wb.feature_dim == 8


# ---------------------------------------------------------------------------
# The e2e loop: drift flagged → retrain → rolling reload → recovery


def _shifted_bucket(rng):
    """Post-shift traffic: new services/call paths, same metric keyset
    (the frozen-metric-set stream contract), consistent resource law so
    a RETRAINED model can cover it."""
    n = 3 + int(rng.poisson(4))
    traces = [Span(component="gateway", operation="/new",
                   children=[Span("fresh-svc", "/read",
                                  children=[Span("fresh-db", "/find")])])
              for _ in range(n)]
    metrics = [
        MetricSample("gateway", "cpu", 5.0 * n + rng.normal(0, 0.5)),
        MetricSample("store-db", "wiops", rng.normal(0, 1.0)),
    ]
    return Bucket(metrics=metrics, traces=traces)


def _stream_config(**kw):
    return StreamConfig(**{**dict(refresh_buckets=24, finetune_epochs=1,
                                  history_max=256, eval_holdout=2,
                                  poll_interval_s=0.05), **kw})


def _trainer_config():
    return Config(
        model=ModelConfig(feature_dim=CAPACITY, hidden_size=8),
        train=TrainConfig(batch_size=8, window_size=WINDOW, seed=0,
                          eval_stride=1, eval_max_cycles=2,
                          log_every_steps=0),
    )


def test_drift_to_retrain_to_reload_loop(tmp_path):
    """The acceptance loop: an injected composition shift is flagged at
    /v1/verdict within the budgeted sweeps, the DriftController fires a
    retrain on the retained rings, the new params roll into the router
    via rolling_reload_from with ZERO mixed-params responses under live
    load, and post-reload band coverage recovers."""
    from deeprest_tpu.serve.predictor import Predictor
    from deeprest_tpu.serve.router import ReplicaRouter
    from deeprest_tpu.serve.server import (
        PredictionServer, PredictionService,
    )

    ckpt = str(tmp_path / "ckpts")
    st = StreamingTrainer(
        _trainer_config(), _stream_config(), ckpt_dir=ckpt,
        feature_config=FeaturizeConfig(hash_features=True,
                                       capacity=CAPACITY))
    qc = QualityConfig(enabled=True, sweep_every_buckets=6,
                       live_window=24, min_sweep_buckets=WINDOW,
                       sustain_enter=2, sustain_exit=2,
                       drift_enter=0.3, drift_exit=0.12,
                       retrain_cooldown_buckets=40, reference_window=48)

    # Phase 1: train the plane on the pre-shift regime.
    pre_results = []
    controller = None     # attached after the router exists

    for b in make_series_buckets(60, seed=3):
        st.ingest(b)
        if st.ready():
            pre_results.append(st.refresh())
    assert pre_results and pre_results[-1].checkpoint_path

    # The serving plane: two thread replicas behind the routing front.
    pred = Predictor.from_checkpoint(ckpt)
    router = ReplicaRouter.build(pred, 2)
    reload_paths = []

    def reload_into_router(path):
        fresh = Predictor.from_checkpoint(ckpt)
        router.rolling_reload_from(fresh, reason="drift")
        reload_paths.append(path)

    controller = DriftController(st, qc, reload_fn=reload_into_router)
    # Arm the monitor from the phase-1 state (normally the first refresh
    # after attach does this; do it explicitly so sweeps start now).
    controller.on_refresh(pre_results[-1])
    assert controller.monitor is not None

    # The verdict surface: the controller's monitor backs GET /v1/verdict
    # on a server over the ROUTER (one plane, one truth).
    service = PredictionService(router, backend="router-under-test")
    service.attach_quality(controller.monitor)
    server = PredictionServer(service, port=0).start()
    host, port = server.address
    base = f"http://{host}:{port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return json.loads(r.read())

    # Live load: concurrent predicts through the router for the whole
    # drift→retrain→reload window; every response must byte-match ONE
    # model's output (params swap atomically per replica — never mixed).
    probe = np.tile(
        np.linspace(0.0, 4.0, CAPACITY, dtype=np.float32), (WINDOW, 1))
    legal = [router.predict_series(probe).tobytes()]
    stop = threading.Event()
    bad: list = []
    served = [0]

    def load_loop():
        while not stop.is_set():
            out = router.predict_series(probe).tobytes()
            served[0] += 1
            if out not in legal:
                # a reload may have landed between our snapshot and this
                # call: accept the CURRENT newest params once
                fresh = router.predict_series(probe).tobytes()
                if out == fresh:
                    legal.append(out)
                else:
                    bad.append(out)

    loader = threading.Thread(target=load_loop, daemon=True)
    loader.start()

    # Phase 2: the composition shift.
    rng = np.random.default_rng(0)
    post_results = []
    for _ in range(130):
        st.ingest(_shifted_bucket(rng))
        if st.ready():
            post_results.append(st.refresh())
    # run until the drift verdict has exited and the post-reload
    # calibration window has real sweeps in it (the recovery gates below)
    extra = 0
    while extra < 160 and (
            controller.monitor.any_active(VERDICT_DRIFT)
            or controller.monitor.calibration.sweeps < 2):
        st.ingest(_shifted_bucket(rng))
        extra += 1
        if st.ready():
            post_results.append(st.refresh())
    stop.set()
    loader.join(timeout=30)

    # -- the gates -------------------------------------------------------
    events = controller.monitor.events
    drift_enter = next((b for b, s, state in events
                        if s == "feature_drift" and state == VERDICT_DRIFT),
                       None)
    assert drift_enter is not None, events
    # detection latency: the live window must fill with post-shift data
    # (the drift machine is gated until both windows are full-width),
    # then sustain_enter + 2 sweeps may pass before the verdict flips
    budget = (qc.live_window
              + qc.sweep_every_buckets * (qc.sustain_enter + 2))
    assert drift_enter <= budget, (drift_enter, budget)

    assert controller.stats["retrains_triggered"] >= 1, controller.stats
    assert any(r.trigger == "drift" for r in post_results)
    assert controller.stats["reloads"] >= 1 and reload_paths
    assert router.router_stats()["rolling_reloads"] >= 1

    # zero mixed-params responses under live load
    assert served[0] > 0
    assert not bad, f"{len(bad)} mixed-params responses"

    # the verdict surface: drift exited after the loop adapted, and the
    # rolling band coverage recovered against the retrained model
    v = get("/v1/verdict")
    assert v["armed"] and v["sweeps"] >= 3
    assert v["feature_drift"]["state"] == VERDICT_OK, v["feature_drift"]
    exit_ev = [b for b, s, state in events
               if s == "feature_drift" and state == VERDICT_OK]
    assert exit_ev, events
    cov = [m["coverage"] for m in v["metrics"].values()
           if m["coverage"] is not None]
    assert cov and min(cov) >= 0.5, v["metrics"]

    # the reason-labeled reload counter saw the drift reloads
    from deeprest_tpu.obs import metrics as obs_metrics
    text = obs_metrics.REGISTRY.render()
    assert 'deeprest_router_reloads_by_reason_total{reason="drift"}' \
        in text
    server.stop()     # closes the service, which closes the router


def test_clean_corpus_produces_zero_verdicts(tmp_path):
    """The false-positive gate: a MATURE plane on a clean continuation
    of its training regime must never enter drift OR anomaly (an
    immature plane legitimately self-reports calibration drift — that is
    the model_warmup_refreshes knob's reason to exist)."""
    st = StreamingTrainer(
        _trainer_config(), _stream_config(finetune_epochs=3),
        ckpt_dir=None,
        feature_config=FeaturizeConfig(hash_features=True,
                                       capacity=CAPACITY))
    # Small windows (24 live rows over a Poisson diurnal) carry a PSI
    # noise floor around ~0.4; the topology-shift signal is >1.0, so the
    # enter threshold sits between them (production defaults use
    # 120-row windows with a much lower floor).
    qc = QualityConfig(enabled=True, sweep_every_buckets=6,
                       live_window=24, min_sweep_buckets=WINDOW,
                       sustain_enter=2, sustain_exit=2,
                       drift_enter=0.6, drift_exit=0.3,
                       model_warmup_refreshes=5,
                       reference_window=48)
    controller = DriftController(st, qc)
    for b in make_series_buckets(200, seed=3):
        st.ingest(b)
        if st.ready():
            st.refresh()
    assert controller.stats["sweeps"] >= 5
    assert controller.stats["retrains_triggered"] == 0, controller.stats
    assert controller.monitor is not None
    assert controller.monitor.model_armed     # matured and armed...
    assert controller.monitor.events == []    # ...and never flapped
    v = controller.monitor.verdicts()
    assert v["states"][VERDICT_DRIFT] == 0
    assert v["states"][VERDICT_ANOMALY] == 0


# ---------------------------------------------------------------------------
# A topology shift with an anomaly riding the drifted regime, on the
# simulator's corpus: when the loop flags what, counted in buckets and sweeps


_CYCLE = 30                       # buckets a traffic cycle
_SHIFT_AT = 8 * _CYCLE            # the AFTER topology takes over
_ANOMALY_AT = _SHIFT_AT + 5 * _CYCLE
_BUCKETS = _SHIFT_AT + 10 * _CYCLE


@pytest.fixture(scope="module")
def anomaly_mid_drift():
    """One stream through trainer and controller: eight services grow to
    fourteen at ``_SHIFT_AT``; from ``_ANOMALY_AT``, after the loop has had
    time to retrain through the drift, one store of the new topology is
    attacked.  Windows span whole traffic cycles (the generator re-draws
    the API mix a cycle, so a shorter window reads phase as drift) and the
    enter threshold sits between the mix's natural churn (PSI up to ~0.85)
    and the shift's signal (1.7-3.5)."""
    from deeprest_tpu.workload.scenarios import normal_scenario
    from deeprest_tpu.workload.simulator import (
        build_shifted_app, simulate_drift_corpus_iter,
    )
    from deeprest_tpu.workload.telemetry import Anomaly

    capacity, window = 128, 8
    sc = normal_scenario(seed=0)
    sc.calls_per_user, sc.base_users = 0.5, 40.0
    sc.peak_range, sc.cycle_len = (56.0, 80.0), _CYCLE
    before, after, endpoints = build_shifted_app(sc, 8, 14, 4, seed=0)
    store = next(c for c in after.components
                 if c.endswith(("-mongodb", "-redis")))
    buckets = simulate_drift_corpus_iter(
        sc, _BUCKETS, _SHIFT_AT, before, after, endpoints,
        anomalies=[Anomaly(kind="ransomware", component=store,
                           start=_ANOMALY_AT, end=_BUCKETS, magnitude=8.0)])
    qc = QualityConfig(
        enabled=True, sweep_every_buckets=_CYCLE // 2,
        live_window=2 * _CYCLE, reference_window=4 * _CYCLE,
        min_sweep_buckets=window, sustain_enter=2, sustain_exit=2,
        drift_enter=1.0, drift_exit=0.5,
        calibration_enter=0.5, calibration_exit=0.25,
        retrain_cooldown_buckets=3 * _CYCLE, model_warmup_refreshes=4)
    st = StreamingTrainer(
        Config(model=ModelConfig(feature_dim=capacity, hidden_size=8),
               train=TrainConfig(batch_size=8, window_size=window, seed=0,
                                 eval_stride=1, eval_max_cycles=2,
                                 log_every_steps=0)),
        StreamConfig(refresh_buckets=40, finetune_epochs=2,
                     history_max=360, eval_holdout=2),
        ckpt_dir=None,
        feature_config=FeaturizeConfig(hash_features=True,
                                       capacity=capacity))
    controller = DriftController(st, qc)
    events, seen = [], 0          # (stream bucket, stream, state)
    for i, bucket in enumerate(buckets):
        st.ingest(bucket)
        if st.ready():
            st.refresh()
        if controller.monitor is not None:
            fresh = controller.monitor.events[seen:]
            seen += len(fresh)
            events.extend((i, s, state) for _, s, state in fresh)
    return dict(events=events, qc=qc, store=store,
                stats=controller.stats)


def test_shift_flagged_inside_the_budget_and_never_before_it(
        anomaly_mid_drift):
    """Zero drift flags before the shift; the flag within the sweeps it
    takes the live window to refill with the new regime, plus the sustain
    and two of slack; a retrain fired and the verdict left drift after."""
    r = anomaly_mid_drift
    qc, drift = r["qc"], [b for b, s, state in r["events"]
                          if s == "feature_drift" and state == VERDICT_DRIFT]
    assert drift and min(drift) >= _SHIFT_AT, drift
    sweeps = (drift[0] - _SHIFT_AT) / qc.sweep_every_buckets
    budget = (qc.live_window + qc.sweep_every_buckets
              * (qc.sustain_enter + 2)) / qc.sweep_every_buckets
    assert sweeps <= budget, (sweeps, budget)
    assert r["stats"]["retrains_triggered"] >= 1
    assert any(b > drift[0] for b, s, state in r["events"]
               if s == "feature_drift" and state == VERDICT_OK)


def test_anomaly_mid_drift_flagged_on_the_attacked_stores_metrics(
        anomaly_mid_drift):
    """What survives the retrained model is the anomaly: flagged at or
    after its start, on metrics of the attacked store and of no other
    component."""
    r = anomaly_mid_drift
    flagged = [(b, s) for b, s, state in r["events"]
               if state == VERDICT_ANOMALY and b >= _ANOMALY_AT]
    assert flagged, r["events"][-20:]
    assert all(s.startswith(r["store"]) for _, s in flagged), flagged
    assert not any(state == VERDICT_ANOMALY and s.startswith(r["store"])
                   for b, s, state in r["events"] if b < _ANOMALY_AT)


def test_manual_override_suppresses_auto_retrain():
    st = StreamingTrainer(
        _trainer_config(), _stream_config(), ckpt_dir=None,
        feature_config=FeaturizeConfig(hash_features=True,
                                       capacity=CAPACITY))
    qc = QualityConfig(enabled=True, sweep_every_buckets=6,
                       live_window=24, min_sweep_buckets=WINDOW,
                       sustain_enter=2, drift_enter=0.15, drift_exit=0.05,
                       auto_retrain=False, reference_window=48)
    controller = DriftController(st, qc)
    for b in make_series_buckets(60, seed=3):
        st.ingest(b)
        if st.ready():
            st.refresh()
    rng = np.random.default_rng(0)
    for _ in range(60):
        st.ingest(_shifted_bucket(rng))
        if st.ready():
            st.refresh()
    assert controller.monitor.any_active(VERDICT_DRIFT)
    assert controller.stats["retrains_triggered"] == 0
    assert controller.stats["suppressed"].get("manual-override", 0) >= 1
    # the human pulls the trigger instead
    controller.force_retrain()
    assert st.ready()
    r = st.refresh()
    assert r.trigger == "manual"


def test_cli_help_covers_quality_flags(capsys):
    from deeprest_tpu.cli import build_parser

    with pytest.raises(SystemExit):
        build_parser().parse_args(["serve", "--help"])
    out = capsys.readouterr().out
    for flag in ("--verdict-raw", "--verdict-sweep-every",
                 "--verdict-live-window"):
        assert flag in out, f"serve --help missing {flag}"
    with pytest.raises(SystemExit):
        build_parser().parse_args(["stream", "--help"])
    out = capsys.readouterr().out
    for flag in ("--drift-detect", "--drift-sweep-every",
                 "--drift-live-window", "--drift-reference-window",
                 "--drift-enter", "--drift-exit",
                 "--drift-cooldown-buckets", "--no-drift-auto-retrain"):
        assert flag in out, f"stream --help missing {flag}"
    with pytest.raises(SystemExit):
        build_parser().parse_args(["simulate", "--help"])
    out = capsys.readouterr().out
    for flag in ("--shift-at", "--services-after"):
        assert flag in out, f"simulate --help missing {flag}"


def test_verdict_endpoint_503_without_monitor():
    from deeprest_tpu.serve.server import PredictionServer, PredictionService

    names = ["svc_cpu"]
    service = PredictionService(_FakeBackend(names), backend="fake")
    server = PredictionServer(service, port=0).start()
    host, port = server.address
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://{host}:{port}/v1/verdict", timeout=10)
        assert exc.value.code == 503
    finally:
        server.stop()


def test_verdict_ingestor_feeds_surface_over_http(tmp_path):
    """The serve-side half: a VerdictIngestor tails a growing collector
    JSONL, auto-arms its reference, sweeps through the service's backend
    snapshot, and GET /v1/verdict + /healthz surface the state."""
    from deeprest_tpu.data.featurize import CallPathSpace
    from deeprest_tpu.serve.server import (
        PredictionServer, PredictionService, VerdictIngestor,
    )
    from deeprest_tpu.train.stream import BucketTailer

    raw = str(tmp_path / "raw.jsonl")
    buckets = make_series_buckets(40, seed=3)
    space = CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=CAPACITY)).freeze()
    names = ["gateway_cpu", "store-db_wiops"]
    backend = _FakeBackend(names, window_size=WINDOW,
                           feature_dim=CAPACITY, gain=10.0)
    service = PredictionService(backend, backend="fake")
    qc = QualityConfig(enabled=True, sweep_every_buckets=4,
                       live_window=8, min_sweep_buckets=WINDOW)
    monitor = QualityMonitor(names, qc)
    tailer = BucketTailer(raw)
    ingestor = VerdictIngestor(service, tailer, space, monitor,
                               poll_interval_s=0.02).start()
    service.attach_quality(monitor, ingestor)
    server = PredictionServer(service, port=0).start()
    host, port = server.address
    base = f"http://{host}:{port}"

    def get(path):
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return json.loads(r.read())

    def append(batch):
        with open(raw, "ab") as f:
            for b in batch:
                f.write((json.dumps(b.to_dict(),
                                    separators=(",", ":")) + "\n").encode())

    def wait_sweeps(n, deadline_s=30.0):
        deadline = time.monotonic() + deadline_s
        v = get("/v1/verdict")
        while time.monotonic() < deadline and v.get("sweeps", 0) < n:
            time.sleep(0.05)
            v = get("/v1/verdict")
        return v

    # phase 1 arms the reference + first sweep; phase 2 is new data the
    # cadence sweeps again on
    append(buckets[:24])
    v = wait_sweeps(1)
    assert v["sweeps"] >= 1, v
    append(buckets[24:])
    v = wait_sweeps(2)
    assert v["sweeps"] >= 2, v
    assert set(v["metrics"]) == set(names)
    h = get("/healthz")
    assert h["quality"]["sweeps"] >= 2
    assert ingestor.errors == 0
    server.stop()     # service.close() stops the ingestor
    assert ingestor._thread is None
