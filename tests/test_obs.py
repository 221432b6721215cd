"""deeprest_tpu/obs: spans, metrics, profiler, and the self-ingestion
loop (ISSUE 9).

Covers the acceptance surface: span propagation across thread AND
process replicas, the /metrics Prometheus exposition (golden), the
disabled-mode zero-allocation probe, the profiler window, and the full
self-ingestion round trip — the plane's own spans → Jaeger JSON +
Prometheus JSON → data/ingest bucketize → the standard featurizer → a
trained model predicting → the WhatIfEstimator estimating the
estimator's own endpoint.
"""

import json
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from router_test_support import E, F, W, build_tiny  # noqa: E402

from deeprest_tpu import obs  # noqa: E402
from deeprest_tpu.obs import export as obs_export  # noqa: E402
from deeprest_tpu.obs.metrics import (  # noqa: E402
    Counter, Gauge, Histogram, MetricsRegistry, Stopwatch,
)
from deeprest_tpu.obs.spans import NULL_SPAN, SpanRecorder  # noqa: E402


@pytest.fixture
def recorder_on():
    """Enable the process-default recorder for one test, restoring the
    disabled default (other test files rely on spans being free)."""
    prev = obs.RECORDER.enabled
    obs.RECORDER.clear()
    obs.RECORDER.enabled = True
    yield obs.RECORDER
    obs.RECORDER.enabled = prev
    obs.RECORDER.clear()


# ---------------------------------------------------------------------------
# spans


def test_span_records_and_nests():
    rec = SpanRecorder(capacity=16, enabled=True)
    with rec.span("outer", component="svc") as outer:
        with rec.span("inner", component="svc") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
    spans = rec.snapshot()
    assert [s.name for s in spans] == ["inner", "outer"]
    assert spans[0].parent_id == spans[1].span_id
    assert spans[1].parent_id is None
    assert spans[0].duration_s >= 0 and spans[0].start_s > 0


def test_span_explicit_parent_and_tags():
    rec = SpanRecorder(capacity=16, enabled=True)
    with rec.span("root", component="a") as root:
        ctx = root.context
    with rec.span("worker", component="b", parent=ctx) as sp:
        sp.tag(windows=3)
    worker = rec.snapshot()[-1]
    assert worker.trace_id == ctx[0] and worker.parent_id == ctx[1]
    assert worker.tags == {"windows": 3}


def test_span_error_tagged():
    rec = SpanRecorder(capacity=4, enabled=True)
    with pytest.raises(ValueError):
        with rec.span("boom"):
            raise ValueError("x")
    assert rec.snapshot()[0].tags["error"] == "ValueError"


def test_ring_capacity_newest_win():
    rec = SpanRecorder(capacity=3, enabled=True)
    for i in range(7):
        with rec.span(f"s{i}"):
            pass
    spans = rec.snapshot()
    assert [s.name for s in spans] == ["s4", "s5", "s6"]
    st = rec.stats()
    assert st["recorded"] == 7 and st["retained"] == 3 and st["evicted"] == 4


def test_disabled_is_singleton_and_zero_allocation():
    rec = SpanRecorder(capacity=4, enabled=False)
    assert rec.span("a") is NULL_SPAN and rec.span("b") is NULL_SPAN
    with rec.span("a"):
        pass
    assert len(rec) == 0
    # allocation probe: the disabled fast path (span() + enter/exit) must
    # allocate nothing — warm up, then assert the allocated-block count
    # does not grow across many iterations.
    def loop(n):
        for _ in range(n):
            with rec.span("probe"):
                pass

    loop(1000)                      # warm caches/frames
    before = sys.getallocatedblocks()
    loop(10_000)
    after = sys.getallocatedblocks()
    assert after - before <= 8, (before, after)


def test_ingest_round_trips_dicts():
    rec = SpanRecorder(capacity=8, enabled=True)
    with rec.span("x", component="c") as sp:
        sp.tag(k="v")
    blobs = [s.to_dict() for s in rec.drain()]
    assert len(rec) == 0
    rec2 = SpanRecorder(capacity=8)
    rec2.ingest(json.loads(json.dumps(blobs)))
    got = rec2.snapshot()[0]
    assert got.name == "x" and got.tags == {"k": "v"}


def test_set_capacity_in_place():
    rec = SpanRecorder(capacity=8, enabled=True)
    for i in range(6):
        with rec.span(f"s{i}"):
            pass
    rec.set_capacity(2)
    assert [s.name for s in rec.snapshot()] == ["s4", "s5"]


# ---------------------------------------------------------------------------
# metrics


def test_metrics_exposition_golden():
    reg = MetricsRegistry()
    c = reg.counter("app_requests_total", "requests by route",
                    labelnames=("route",))
    c.inc(route="/a")
    c.inc(2, route="/b")
    g = reg.gauge("app_depth", "queue depth")
    g.set(3)
    h = reg.histogram("app_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    assert reg.render() == (
        "# HELP app_depth queue depth\n"
        "# TYPE app_depth gauge\n"
        "app_depth 3\n"
        "# HELP app_requests_total requests by route\n"
        "# TYPE app_requests_total counter\n"
        'app_requests_total{route="/a"} 1\n'
        'app_requests_total{route="/b"} 2\n'
        "# HELP app_seconds latency\n"
        "# TYPE app_seconds histogram\n"
        'app_seconds_bucket{le="0.1"} 1\n'
        'app_seconds_bucket{le="1"} 2\n'
        'app_seconds_bucket{le="+Inf"} 2\n'
        "app_seconds_sum 0.55\n"
        "app_seconds_count 2\n"
    )


def test_metrics_semantics():
    c = Counter("c_total")
    c.inc()
    c.inc(2.5)
    assert c.value() == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    with pytest.raises(ValueError):
        c.inc(tenant="x")           # undeclared label
    g = Gauge("g")
    g.set(5)
    g.dec(2)
    g.set_max(1)
    assert g.value() == 3
    g.set_max(9)
    assert g.value() == 9
    h = Histogram("h", buckets=(1.0,))
    h.observe(0.5)
    h.observe(2.0)
    snap = h.snapshot()
    assert snap["count"] == 2 and snap["sum"] == 2.5
    assert snap["buckets"][1.0] == 1


def test_registry_expose_and_collectors():
    reg = MetricsRegistry()
    first = Counter("plane_total")
    first.inc(5)
    reg.expose(first)
    second = Counter("plane_total")     # a rebuilt plane's fresh counter
    second.inc(1)
    reg.expose(second)
    assert "plane_total 1" in reg.render()
    assert first.value() == 5           # the old instance still counts

    reg.register_collector("view", lambda sink: sink.gauge(
        "view_depth", 7, help="a render-time view"))
    assert "view_depth 7" in reg.render()
    reg.register_collector("boom", lambda sink: 1 / 0)
    out = reg.render()                  # a broken view must not kill scrape
    assert "deeprest_collector_errors_total" in out
    assert "view_depth 7" in out


def test_registry_kind_mismatch_rejected():
    reg = MetricsRegistry()
    reg.counter("m_total")
    with pytest.raises(ValueError):
        reg.gauge("m_total")


def test_stopwatch(hand_clock):
    sw = Stopwatch()
    hand_clock[0] += 0.25
    assert sw.elapsed() == 0.25
    h = Histogram("sw_seconds")
    assert sw.observe_into(h) == 0.25
    assert h.snapshot()["count"] == 1
    sw.restart()
    assert sw.elapsed() == 0.0


# ---------------------------------------------------------------------------
# propagation through the serving plane


def test_span_propagation_thread_replicas(recorder_on):
    from deeprest_tpu.serve.router import ReplicaRouter

    router = ReplicaRouter.build(build_tiny(), 2)
    traffic = np.random.default_rng(0).random((W * 2, F), np.float32)
    with obs.span("request", component="deeprest-predictor") as root:
        trace = root.trace_id
        router.predict_series(traffic)
    names = {s.name: s for s in recorder_on.snapshot()}
    assert {"request", "router.dispatch", "replica.predict",
            "fused.predict"} <= set(names)
    assert all(s.trace_id == trace for s in names.values())
    # parent chain: request -> dispatch -> replica -> fused
    assert names["router.dispatch"].parent_id == names["request"].span_id
    assert (names["replica.predict"].parent_id
            == names["router.dispatch"].span_id)
    assert (names["fused.predict"].parent_id
            == names["replica.predict"].span_id)
    router.close()


def _one_served_request():
    """One request as the HTTP handler opens it: root span + the engine's
    own ``fused.predict``.  Counted by the engine's dispatch statistics."""
    pred = build_tiny()
    traffic = np.random.default_rng(0).random((W * 3, F), np.float32)
    pred.predict_series(traffic)                     # compile outside

    def run():
        with obs.span("/v1/predict", component="deeprest-predictor"):
            return np.asarray(pred.predict_series(traffic))

    return run, lambda: pred.fused.stats()["pages"], {
        "/v1/predict", "fused.predict"}, True


def _one_train_epoch():
    """One epoch of a toy trainer on the host feed.  Counted by the
    registry's always-live series."""
    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.obs.metrics import REGISTRY
    from deeprest_tpu.train import Trainer
    from deeprest_tpu.train.data import DatasetBundle

    names = [f"c{i}_cpu" for i in range(E)]
    cfg = Config(model=ModelConfig(feature_dim=F, num_metrics=E,
                                   hidden_size=8, dropout_rate=0.0),
                 train=TrainConfig(batch_size=8, window_size=W,
                                   log_every_steps=0))
    trainer = Trainer(cfg, F, names)
    rng = np.random.default_rng(0)
    x = rng.random((24, W, F), np.float32)
    y = rng.random((24, W, E), np.float32)
    stats = MinMaxStats(min=np.float32(0.0), max=np.float32(1.0))
    bundle = DatasetBundle(x_train=x, y_train=y, x_test=x[:4], y_test=y[:4],
                           x_stats=stats, y_stats=stats, metric_names=names,
                           split=24, window_size=W)
    box = {"state": trainer.init_state(x)}

    def run():
        box["state"], loss = trainer.train_epoch(
            box["state"], bundle, np.random.default_rng(1))
        return np.asarray(loss)

    run()                                            # compile outside
    return run, lambda: REGISTRY.get(
        "deeprest_train_epochs_total").value(), {"train.epoch"}, False


@pytest.mark.parametrize("path", [_one_served_request, _one_train_epoch],
                         ids=["serve", "train"])
def test_recorder_off_leaves_no_span_and_the_counters_still_advance(path):
    """The two hot paths with the recorder off and then on: off, the ring
    stays empty while the path's counter advances; on, the same call
    leaves the path's spans and counts the same (and a request, which
    moves no state, answers the same bytes)."""
    run, counter, expected, repeatable = path()
    assert obs.RECORDER.enabled is False             # the process default
    obs.RECORDER.clear()
    c0 = counter()
    off = run()
    c1 = counter()
    assert len(obs.RECORDER) == 0
    assert c1 > c0
    obs.RECORDER.enabled = True
    try:
        on = run()
        names = {s.name for s in obs.RECORDER.snapshot()}
    finally:
        obs.RECORDER.enabled = False
        obs.RECORDER.clear()
    assert expected <= names, names
    assert counter() - c1 == c1 - c0
    assert on.shape == off.shape and np.all(np.isfinite(on))
    assert not repeatable or np.array_equal(on, off)


def test_span_propagation_batcher_worker(recorder_on):
    from deeprest_tpu.serve.batcher import BatcherConfig, MicroBatcher

    pred = build_tiny()
    batcher = MicroBatcher(pred.ladder, BatcherConfig(max_batch=8,
                                                      max_linger_s=0.0))
    pred.attach_batcher(batcher)
    traffic = np.random.default_rng(0).random((W, F), np.float32)
    with obs.span("request", component="deeprest-predictor") as root:
        trace = root.trace_id
        pred.predict_series(traffic)
    batcher.close()
    dispatch = [s for s in recorder_on.snapshot()
                if s.name == "batch.dispatch"]
    assert dispatch, "worker-thread dispatch span missing"
    # the submitting request's captured context crossed the thread
    assert dispatch[0].trace_id == trace
    assert dispatch[0].tags["requests"] >= 1


def test_span_propagation_process_replica(recorder_on):
    from deeprest_tpu.serve.replica import ProcessReplica

    spec = {"factory": "router_test_support:build_tiny",
            "kwargs": {"ladder": [8]},
            "sys_path": [os.path.dirname(os.path.abspath(__file__))]}
    rep = ProcessReplica(spec, name="p0", boot_timeout_s=300.0)
    try:
        traffic = np.random.default_rng(0).random((W * 2, F), np.float32)
        with obs.span("request", component="deeprest-predictor") as root:
            trace = root.trace_id
            rep.predict_series(traffic)
        # forwarded over the duplex pipe by the worker, ingested by the
        # parent's reader thread
        deadline = time.monotonic() + 10.0
        worker_spans = []
        while time.monotonic() < deadline:
            worker_spans = [s for s in recorder_on.snapshot()
                            if s.name == "replica.worker"]
            if worker_spans:
                break
            time.sleep(0.05)
        assert worker_spans, "child spans never crossed the pipe"
        assert worker_spans[0].trace_id == trace
        # the child's own fused-engine span rode along too
        fused = [s for s in recorder_on.snapshot()
                 if s.name == "fused.predict"]
        assert fused and fused[0].trace_id == trace
    finally:
        rep.close()


# ---------------------------------------------------------------------------
# HTTP surface: /metrics, /v1/spans, /v1/profile


@pytest.fixture
def live_server(recorder_on):
    from deeprest_tpu.serve.server import PredictionServer, PredictionService

    service = PredictionService(build_tiny(), backend="test")
    server = PredictionServer(service, port=0).start()
    yield server
    server.stop()


def _get(server, path: str):
    import urllib.request

    host, port = server.address
    return urllib.request.urlopen(f"http://{host}:{port}{path}", timeout=30)


def _post(server, path: str, payload: dict):
    import urllib.request

    host, port = server.address
    req = urllib.request.Request(
        f"http://{host}:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=60)


def test_metrics_endpoint_prometheus_text(live_server):
    traffic = np.random.default_rng(0).random((W, F), np.float32)
    _post(live_server, "/v1/predict", {"traffic": traffic.tolist()}).read()
    time.sleep(0.2)     # the handler notes the request AFTER replying
    resp = _get(live_server, "/metrics")
    assert resp.headers["Content-Type"].startswith("text/plain")
    body = resp.read().decode()
    assert "# TYPE deeprest_http_requests_total counter" in body
    assert ('deeprest_http_requests_total{route="/v1/predict",code="200"} 1'
            in body)
    assert "deeprest_http_request_seconds_bucket" in body
    assert "deeprest_obs_spans_recorded_total" in body
    assert "deeprest_fused_windows_total" in body


def test_spans_endpoint_jaeger_json(live_server):
    traffic = np.random.default_rng(0).random((W, F), np.float32)
    _post(live_server, "/v1/predict", {"traffic": traffic.tolist()}).read()
    time.sleep(0.2)                     # root span commits post-reply
    payload = json.loads(_get(live_server, "/v1/spans").read())
    assert payload["data"], "no traces exported"
    trace = payload["data"][0]
    ops = {s["operationName"] for s in trace["spans"]}
    assert "/v1/predict" in ops
    services = {p["serviceName"] for p in trace["processes"].values()}
    assert "deeprest-predictor" in services


def test_healthz_carries_obs_stats(live_server):
    h = json.loads(_get(live_server, "/healthz").read())
    assert h["obs"]["enabled"] is True
    assert h["obs"]["capacity"] == obs.RECORDER.capacity


def test_profile_route_captures_trace(live_server, tmp_path):
    out = str(tmp_path / "trace")
    body = json.loads(_post(live_server, "/v1/profile",
                            {"seconds": 0.2, "out_dir": out}).read())
    assert body["trace_dir"] == os.path.abspath(out)
    # jax.profiler writes a plugins/profile tree under the dir
    found = [os.path.join(r, f) for r, _, fs in os.walk(out) for f in fs]
    assert found, "profiler wrote nothing"
    # ... and the window is read back (no device plane on the CPU)
    layers = body["layers"]
    assert layers["trace"] in found and layers["chips"] == 0
    assert [r["scope"] for r in layers["rows"]] == ["other"]
    # bad payloads are client errors, not 500s
    import urllib.error

    with pytest.raises(urllib.error.HTTPError) as err:
        _post(live_server, "/v1/profile", {"seconds": -1})
    assert err.value.code == 400


def test_capture_answers_with_the_trace_when_it_cannot_be_read(
        tmp_path, monkeypatch):
    """The window was opened and written: a reader's failure must not turn
    POST /v1/profile into an error (before the table it returned the
    directory; it still does)."""
    from deeprest_tpu.obs import profiler

    def unreadable(path):
        raise RuntimeError("not an xplane")

    monkeypatch.setattr(profiler, "read_planes", unreadable)
    out = profiler.capture(str(tmp_path / "t"), 0.05)
    assert out["trace_dir"] == str(tmp_path / "t")
    assert out["layers"] is None
    assert out["layers_error"] == "RuntimeError: not an xplane"
    assert profiler._capture_lock.acquire(blocking=False)   # released
    profiler._capture_lock.release()
    monkeypatch.undo()
    again = profiler.capture(str(tmp_path / "t2"), 0.05)
    assert again["layers"]["chips"] == 0 and "layers_error" not in again


def test_profiler_busy_is_409():
    from deeprest_tpu.obs import profiler

    with pytest.raises(ValueError):
        profiler.capture("/tmp/x", 0)
    # simulate a held window
    assert profiler._capture_lock.acquire(blocking=False)
    try:
        with pytest.raises(profiler.ProfilerBusy):
            profiler.capture("/tmp/x", 0.1)
    finally:
        profiler._capture_lock.release()


# ---------------------------------------------------------------------------
# self-ingestion: spans -> Jaeger JSON -> bucketize -> featurize -> predict


def test_export_jaeger_shape_roundtrip():
    rec = SpanRecorder(capacity=64, enabled=True)
    with rec.span("/v1/predict", component="deeprest-predictor"):
        with rec.span("router.dispatch", component="deeprest-router"):
            pass
    payload = obs_export.spans_to_jaeger(rec.snapshot())
    from deeprest_tpu.data.ingest import jaeger_traces

    trees = jaeger_traces(payload)
    assert len(trees) == 1              # one rooted tree per trace
    _, root = trees[0]
    assert root.component == "deeprest-predictor"
    assert root.operation == "/v1/predict"
    assert [c.component for c in root.children] == ["deeprest-router"]


def test_export_prometheus_busy_counter():
    rec = SpanRecorder(capacity=64, enabled=True)
    for _ in range(3):
        with rec.span("op", component="svc"):
            pass
    payload = obs_export.spans_to_prometheus(rec.snapshot())
    from deeprest_tpu.data.ingest import prometheus_series

    samples = prometheus_series(payload)
    assert samples, "busy counter produced no samples"
    assert all(s[1] == "svc" and s[2] == "cpu" and s[4] == "counter"
               for s in samples)
    values = [s[3] for s in samples]
    assert values == sorted(values)     # cumulative counter


def test_self_ingestion_roundtrip(recorder_on, tmp_path):
    """The acceptance loop: drive the plane, export its spans through the
    STANDARD ingest pipeline, featurize, train, predict — and let the
    WhatIfEstimator estimate the estimator's own endpoint."""
    from deeprest_tpu.config import (
        Config, FeaturizeConfig, ModelConfig, TrainConfig,
    )
    from deeprest_tpu.data.featurize import featurize_buckets
    from deeprest_tpu.data.ingest import ingest_files
    from deeprest_tpu.data.synthesize import TraceSynthesizer
    from deeprest_tpu.serve.whatif import WhatIfEstimator
    from deeprest_tpu.train import Trainer, prepare_dataset

    # 1. the plane's own traffic: serve real predictions, two request-
    #    rate phases so the corpus carries a traffic gradient
    pred = build_tiny()
    rng = np.random.default_rng(0)
    traffic = rng.random((W * 2, F), np.float32)
    for phase_sleep in (0.0, 0.004):
        for _ in range(60):
            with obs.span("/v1/predict", component="deeprest-predictor"):
                pred.predict_series(traffic)
            if phase_sleep:
                time.sleep(phase_sleep)
    spans = recorder_on.snapshot()
    assert len(spans) >= 120

    # 2. export through the standard file pipeline (what `deeprest
    #    ingest --traces ... --prom ...` consumes)
    jaeger_path = str(tmp_path / "obs_spans.json")
    prom_path = str(tmp_path / "obs_busy.json")
    obs_export.write_jaeger_json(spans, jaeger_path)
    obs_export.write_prometheus_json(spans, prom_path)
    t0 = min(s.start_s for s in spans)
    t1 = max(s.start_s + s.duration_s for s in spans)
    bucket_s = max((t1 - t0) / 48, 1e-4)
    buckets = ingest_files([jaeger_path], [prom_path], bucket_s)
    assert len(buckets) >= 40
    assert any(b.traces for b in buckets)
    assert any(m.value > 0 for b in buckets for m in b.metrics)

    # 3. the standard featurizer accepts the corpus
    data = featurize_buckets(buckets, FeaturizeConfig(round_to=8))
    assert data.traffic.shape[0] == len(buckets)
    assert "deeprest-predictor_cpu" in data.metric_names

    # 4. train a tiny estimator on the plane's own corpus and predict
    cfg = Config(model=ModelConfig(feature_dim=data.traffic.shape[1],
                                   num_metrics=len(data.metric_names),
                                   hidden_size=8, dropout_rate=0.0),
                 train=TrainConfig(num_epochs=2, batch_size=8,
                                   window_size=8, eval_stride=1,
                                   eval_max_cycles=4, train_split=0.5,
                                   log_every_steps=0))
    bundle = prepare_dataset(data, cfg.train)
    trainer = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
    state, history = trainer.fit(bundle)
    assert np.isfinite(history[-1].train_loss)
    preds = trainer.predict(state, bundle.x_test[:4])
    assert np.all(np.isfinite(preds))

    # 5. close the paper's loop: the estimator estimates ITSELF — the
    #    what-if endpoint vocabulary is the plane's own serving route
    synth = TraceSynthesizer(
        featurize_buckets(buckets, FeaturizeConfig(round_to=8)).space
    ).fit(buckets)
    assert "deeprest-predictor_/v1/predict" in synth.endpoints

    from deeprest_tpu.serve.predictor import Predictor

    self_pred = Predictor(
        params=state.params, model_config=trainer.model_config,
        x_stats=bundle.x_stats, y_stats=bundle.y_stats,
        metric_names=bundle.metric_names, window_size=8,
        delta_mask=bundle.delta_mask)
    est = WhatIfEstimator(self_pred, synth)
    program = [{"deeprest-predictor_/v1/predict": 5}] * 12
    bands = est.estimate(program, seed=0)
    series = bands["deeprest-predictor_cpu"]["q50"]
    assert len(series) == 12 and np.all(np.isfinite(series))
