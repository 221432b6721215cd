"""Micro-batching engine (serve/batcher.py): cross-request coalescing is
invisible in results, the shape ladder bounds the jit cache under ragged
series lengths, the flush policy honors max-batch and the linger deadline,
and the batcher-disabled fallback still serves.

Quick tier: the model is random-init at tiny dims — batching semantics do
not depend on trained weights, and the trained-model serving paths are
covered by the slow-tier test_serve/test_export_serve suites.
"""

import http.client
import json
import threading

import numpy as np
import pytest

from deeprest_tpu.config import ModelConfig
from deeprest_tpu.data.windows import MinMaxStats
from deeprest_tpu.serve import (
    BatcherConfig, MicroBatcher, PredictionServer, PredictionService,
    Predictor, ShapeLadder,
)
from deeprest_tpu.serve.batcher import BatcherClosed

F, E, H, W = 6, 3, 8, 8


def make_predictor(ladder):
    import jax

    from deeprest_tpu.models.qrnn import QuantileGRU

    mc = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, W, F), np.float32),
                        deterministic=True)["params"]
    return Predictor(
        params, mc,
        x_stats=MinMaxStats(min=np.float32(0.0), max=np.float32(1.0)),
        y_stats=MinMaxStats(min=np.zeros((E,), np.float32),
                            max=np.ones((E,), np.float32)),
        metric_names=[f"c{i}_cpu" for i in range(E)],
        window_size=W, ladder=ladder)


@pytest.fixture(scope="module")
def pred8():
    """Single-rung ladder: every dispatch shares ONE executable, so
    batched-vs-sequential results can be compared bit-for-bit (different
    compiled batch shapes are explicitly NOT bit-equal — see
    test_serve.test_rolled_prediction_batching_invariant)."""
    return make_predictor(ladder=(8,))


@pytest.fixture(scope="module")
def pred_multi():
    return make_predictor(ladder=(2, 4, 8))


@pytest.fixture
def traffic():
    return np.random.default_rng(0).random((2 * W, F)).astype(np.float32)


# ---------------------------------------------------------------------------
# Result invariance


def test_concurrent_batched_results_byte_identical(pred8, traffic):
    """Windows coalesced across concurrent requests must demultiplex to
    results byte-identical to the sequential (no-batcher) path."""
    reference = pred8.predict_series(traffic)     # direct laddered path
    service = PredictionService(
        pred8, None, backend="t",
        batching=BatcherConfig(max_batch=8, max_linger_s=0.005))
    try:
        results: dict[int, np.ndarray] = {}

        def worker(i):
            out = service.predict({"traffic": traffic.tolist()})
            results[i] = np.asarray(out["predictions"], np.float32)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = service.batcher.stats()
        assert stats["submitted"] >= 6
        for i, got in results.items():
            assert np.array_equal(got, reference), f"request {i} diverged"
    finally:
        service.close()


def test_batcher_error_propagates_to_futures():
    def exploding(x):
        raise RuntimeError("kaboom")

    mb = MicroBatcher(ShapeLadder(exploding, (4,)),
                      BatcherConfig(max_batch=4, max_linger_s=0.0,
                                    max_queue=8))
    try:
        fut = mb.submit(np.zeros((2, W, F), np.float32))
        with pytest.raises(RuntimeError, match="kaboom"):
            fut.result(timeout=10)
        assert mb.stats()["errors"] >= 1
    finally:
        mb.close()


# ---------------------------------------------------------------------------
# Shape ladder / jit cache


def test_ragged_lengths_trigger_no_new_compiles(pred_multi):
    """After warming the ladder rungs AND the fused per-rung executables,
    mixed (ragged) series lengths must reuse them: zero new jit
    compilations on either serving path."""
    from deeprest_tpu.serve.predictor import rolled_prediction_reference

    for rung in pred_multi.ladder.ladder:                       # warmup
        pred_multi.ladder(np.zeros((rung, W, F), np.float32))
    rng = np.random.default_rng(1)
    # warm every fused rung too (a series long enough to hit the top rung
    # pages through all smaller tail rungs as well)
    for rung in pred_multi.fused.rungs:
        pred_multi.predict_series(
            rng.random((rung * W, F)).astype(np.float32))
    warm = pred_multi.ladder.stats()
    cache_warm = pred_multi.jit_cache_size()
    for length in (W, W + 1, 2 * W + 3, 3 * W + 5, 5 * W + 7, 8 * W + 2):
        # fused path (the predict_series default with no batcher attached)
        out = pred_multi.predict_series(
            rng.random((length, F)).astype(np.float32))
        assert out.shape == (length, E, 3)
        assert np.isfinite(out).all()
        # pinned host path through the shape ladder
        ref = rolled_prediction_reference(
            pred_multi.apply_windows, pred_multi.x_stats,
            pred_multi.y_stats, W,
            rng.random((length, F)).astype(np.float32))
        assert ref.shape == (length, E, 3)
    after = pred_multi.ladder.stats()
    assert after["rung_compiles"] == warm["rung_compiles"]
    assert after["compiled_rungs"] == list(pred_multi.ladder.ladder)
    assert after["rung_hits"] > warm["rung_hits"]
    if cache_warm is not None:                 # jax-version-dependent probe
        assert pred_multi.jit_cache_size() == cache_warm
        # the combined probe covers the fused program too (satellite:
        # jit_cache_size must not miss the fused rolled executables)
        stats = pred_multi.jit_cache_stats()
        assert stats["fused"] >= 1 and stats["apply"] >= 1
    # padding really happened (ragged tails were absorbed, not compiled)
    assert after["padded_windows"] > warm["padded_windows"]
    fused = pred_multi.fused.stats()
    assert fused["dispatched_rungs"] == list(pred_multi.fused.rungs)


def test_ladder_oversize_chunks_split():
    seen = []

    def apply_fn(x):
        seen.append(len(x))
        return np.zeros((len(x), W, E, 3), np.float32)

    ladder = ShapeLadder(apply_fn, (2, 4))
    out = ladder(np.arange(9 * W * F, dtype=np.float32).reshape(9, W, F))
    assert out.shape == (9, W, E, 3)
    assert seen == [4, 4, 2]       # 4+4+1, last chunk padded 1→2
    with pytest.raises(ValueError, match="bad shape ladder"):
        ShapeLadder(apply_fn, ())


# ---------------------------------------------------------------------------
# Flush policy


class _GatedApply:
    """Stub apply that can hold the worker inside a dispatch, letting the
    test stage a backlog deterministically."""

    def __init__(self):
        self.batches = []
        self.gate = threading.Event()
        self.gate.set()

    def __call__(self, x):
        self.gate.wait(timeout=10)
        self.batches.append(len(x))
        return np.zeros((len(x), W, E, 3), np.float32)


def test_flush_honors_max_batch():
    stub = _GatedApply()
    stub.gate.clear()
    mb = MicroBatcher(ShapeLadder(stub, (4,)),
                      BatcherConfig(max_batch=4, max_linger_s=0.01,
                                    max_queue=64))
    try:
        futs = [mb.submit(np.zeros((2, W, F), np.float32)) for _ in range(5)]
        stub.gate.set()
        for f in futs:
            assert f.result(timeout=10).shape == (2, W, E, 3)
        stats = mb.stats()
        # 10 windows at max_batch=4 cannot ride one flush
        assert stats["batches"] >= 3
        assert stats["max_batch_windows"] <= 4
        assert stats["coalesced_batches"] >= 1
        assert max(stub.batches) <= 4
    finally:
        mb.close()


def test_lone_request_flushes_at_linger_deadline():
    stub = _GatedApply()
    mb = MicroBatcher(ShapeLadder(stub, (8,)),
                      BatcherConfig(max_batch=8, max_linger_s=0.15,
                                    max_queue=64))
    try:
        mb.apply(np.zeros((2, W, F), np.float32))
        # a lone submission waits out the linger window (no co-arrivals):
        # the flush is counted to the deadline, not to a full batch…
        s = mb.stats()
        assert (s["flush_linger"], s["flush_full"]) == (1, 0)
        # …and a full batch flushes as full, not at the deadline
        mb.apply(np.zeros((8, W, F), np.float32))
        s = mb.stats()
        assert (s["flush_linger"], s["flush_full"]) == (1, 1)
    finally:
        mb.close()


def test_config_validation():
    with pytest.raises(ValueError, match="max_queue"):
        BatcherConfig(max_batch=64, max_queue=8)
    with pytest.raises(ValueError, match="max_batch"):
        BatcherConfig(max_batch=0)


# ---------------------------------------------------------------------------
# Fallbacks and lifecycle


def test_batcher_disabled_fallback_still_serves(pred8, traffic):
    service = PredictionService(pred8, None, backend="bare")
    assert service.batcher is None
    out = service.predict({"traffic": traffic.tolist()})
    assert np.asarray(out["predictions"]).shape == (len(traffic), E, 3)
    health = service.healthz()
    assert health["ok"] and health["batcher"] is None
    assert health["shape_ladder"]["ladder"] == [8]


def test_closed_batcher_falls_back_to_direct_path(pred8, traffic):
    service = PredictionService(
        pred8, None, backend="t",
        batching=BatcherConfig(max_batch=8, max_linger_s=0.0))
    service.batcher.close()
    with pytest.raises(BatcherClosed):
        service.batcher.submit(np.zeros((1, W, F), np.float32))
    # apply_windows catches BatcherClosed and uses the ladder directly
    out = service.predict({"traffic": traffic.tolist()})
    assert np.asarray(out["predictions"]).shape == (len(traffic), E, 3)
    service.close()
    assert pred8.batcher is None or True   # service.close() detaches safely


def test_whatif_scaling_concurrent_path_matches_sequential():
    """With a batcher attached, scaling_factor estimates both traffic
    programs concurrently (their windows coalesce); the factors must be
    identical to the sequential path."""
    from deeprest_tpu.serve import WhatIfEstimator

    class StubSpace:
        capacity = 4

    class StubSynth:
        space = StubSpace()
        endpoints = ["e"]

        def synthesize_series(self, prog, seed=0):
            t = np.arange(len(prog), dtype=np.float32)
            scale = sum(p.get("e", 0) for p in prog) / max(len(prog), 1)
            return np.tile((t * 0.1 + scale)[:, None], (1, 4))

    class StubPred:
        feature_dim = 4
        metric_names = ["m_cpu"]
        quantiles = (0.05, 0.5, 0.95)
        delta_mask = None
        window_size = 2
        batcher = None

        def predict_series(self, x):
            base = x[:, :1]                          # [T, 1]
            return np.stack([base * f for f in (0.9, 1.0, 1.1)], axis=-1)

    pred = StubPred()
    est = WhatIfEstimator(pred, StubSynth())
    base = [{"e": 2}] * 6
    hypo = [{"e": 6}] * 6
    sequential = est.scaling_factor(base, hypo)
    pred.batcher = object()                          # truthy → thread pool
    concurrent = est.scaling_factor(base, hypo)
    assert concurrent == sequential
    assert concurrent["m_cpu"] > 1.0


# ---------------------------------------------------------------------------
# Wire protocol over real HTTP


def test_http_roundtrip_with_batcher_unchanged_protocol(pred8, traffic):
    """Concurrent HTTP clients through the batcher: same response fields
    and values as the in-process path; /healthz exposes queue depth and
    ladder hit stats."""
    reference = pred8.predict_series(traffic)
    service = PredictionService(
        pred8, None, backend="http-test",
        batching=BatcherConfig(max_batch=8, max_linger_s=0.005))
    server = PredictionServer(service, port=0).start()
    try:
        host, port = server.address

        def rpc(method, path, payload=None):
            conn = http.client.HTTPConnection(host, port, timeout=30)
            body = json.dumps(payload).encode() if payload is not None else None
            conn.request(method, path, body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            out = json.loads(resp.read())
            conn.close()
            return resp.status, out

        results = {}

        def worker(i):
            results[i] = rpc("POST", "/v1/predict",
                             {"traffic": traffic.tolist()})

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for status, body in results.values():
            assert status == 200
            assert body["metric_names"] == pred8.metric_names
            np.testing.assert_array_equal(
                np.asarray(body["predictions"], np.float32), reference)

        status, health = rpc("GET", "/healthz")
        assert status == 200 and health["ok"]
        b = health["batcher"]
        assert b["submitted"] >= 4
        assert "queue_depth_windows" in b and "flush_linger" in b
        assert b["shape_ladder"]["compiled_rungs"] == [8]
    finally:
        server.stop()
