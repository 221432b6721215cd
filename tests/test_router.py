"""Multi-replica serving plane (serve/replica.py + serve/router.py +
deploy/autoscaler.py): byte-identical routing, bounded admission with the
429/Retry-After contract, weighted per-tenant fairness, zero-downtime
rolling reload with the no-mixed-params probe, shared-stack executable
accounting on virtual devices, worker-subprocess replicas, and the
self-sizing control loop.

Quick tier: random-init tiny models (routing semantics do not depend on
trained weights), single-rung ladders where byte-identity is asserted.
"""

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

from router_test_support import E, F, W, build_tiny

from deeprest_tpu.serve import (
    AdmissionError, EngineReplica, PredictionServer, PredictionService,
    ReplicaRouter, RouterConfig, clone_backend,
)
from deeprest_tpu.serve.router import WeightedAdmission


@pytest.fixture(scope="module")
def pred8():
    """Single-rung ladder: every dispatch shares one executable shape, so
    routed results compare byte-for-byte against the direct path."""
    return build_tiny(ladder=(8,))


@pytest.fixture
def traffic():
    return np.random.default_rng(0).random((2 * W, F)).astype(np.float32)


# ---------------------------------------------------------------------------
# Routing correctness


@pytest.mark.parametrize("n", [2, 4])
def test_routed_results_byte_identical(pred8, traffic, n):
    """Every replica must serve results byte-identical to the
    single-replica path, concurrently, at N in {2, 4}."""
    reference = pred8.predict_series(traffic)
    router = ReplicaRouter.build(pred8, n)
    try:
        results: dict[int, np.ndarray] = {}

        def worker(i):
            results[i] = router.predict_series(traffic)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(3 * n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(results) == 3 * n
        for i, got in results.items():
            assert np.array_equal(got, reference), f"request {i} diverged"
        stats = router.router_stats()
        assert stats["num_replicas"] == n
        assert sum(r["served_requests"]
                   for r in stats["replicas"]) == 3 * n
    finally:
        router.close()


@pytest.mark.parametrize("clients", [4, 8])
def test_two_replicas_share_a_closed_loop_of_http_clients(traffic, clients):
    """Closed-loop clients over real HTTP against two replicas whose
    requests outlast the clients' start: both replicas serve, every answer
    is a 200 carrying the direct path's numbers, every request was
    admitted and none shed, and what the replicas served sums to what the
    clients sent."""
    import http.client

    from router_test_support import build_slow

    each = 2
    reference = build_tiny(ladder=(8,)).predict_series(traffic)
    router = ReplicaRouter(
        [EngineReplica(build_slow(delay_s=0.2, ladder=(8,)), name=f"r{i}")
         for i in range(2)],
        config=RouterConfig(admission_depth=clients, max_wait_s=30.0))
    service = PredictionService(router, None, backend="two-replicas")
    server = PredictionServer(service, port=0).start()
    try:
        payload = json.dumps({"traffic": traffic.tolist()}).encode()
        barrier = threading.Barrier(clients)
        answers, errors = [], []

        def client():
            barrier.wait()
            for _ in range(each):
                try:
                    conn = http.client.HTTPConnection(*server.address,
                                                      timeout=60)
                    conn.request(
                        "POST", "/v1/predict", body=payload,
                        headers={"Content-Type": "application/json"})
                    resp = conn.getresponse()
                    answers.append((resp.status, json.loads(resp.read())))
                    conn.close()
                except Exception as exc:
                    errors.append(repr(exc))

        threads = [threading.Thread(target=client) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        requests = clients * each
        assert not errors, errors
        assert len(answers) == requests
        assert {status for status, _ in answers} == {200}
        want = service.predict({"traffic": traffic.tolist()})["predictions"]
        np.testing.assert_array_equal(np.asarray(want, np.float32),
                                      reference)
        assert all(body["predictions"] == want for _, body in answers)
        stats = router.router_stats()
        served = [r["served_requests"] for r in stats["replicas"]]
        assert len(served) == 2 and min(served) > 0
        assert sum(served) == requests + 1          # + the reference call
        adm = stats["admission"]
        assert adm["depth"] == clients
        assert adm["admitted"] >= requests and adm["rejected"] == 0
    finally:
        server.stop()


def test_router_exposes_serving_protocol(pred8):
    router = ReplicaRouter.build(pred8, 2)
    try:
        assert router.metric_names == pred8.metric_names
        assert router.window_size == pred8.window_size
        assert router.feature_dim == pred8.feature_dim
        assert router.quantiles == pred8.quantiles
        assert router.median_index() == pred8.median_index()
    finally:
        router.close()


def test_least_outstanding_work_prefers_idle_replica(pred8):
    """A replica with work in flight must not receive the next request
    while an idle one exists."""
    router = ReplicaRouter.build(pred8, 2)
    try:
        busy, idle = router.replicas
        busy._begin(100)       # synthetic outstanding windows
        try:
            for _ in range(4):
                assert router._pick() is idle
        finally:
            busy._end(100, requests=0)
    finally:
        router.close()


# ---------------------------------------------------------------------------
# Admission control


class _GatedBackend:
    """Minimal serving backend whose predict blocks on a gate — lets the
    tests hold admission slots deterministically."""

    metric_names = [f"c{i}_cpu" for i in range(E)]
    window_size = W
    feature_dim = F
    quantiles = (0.05, 0.5, 0.95)
    delta_mask = None
    space_dict = None
    batcher = None

    def __init__(self):
        self.gate = threading.Event()
        self.gate.set()
        self.calls = 0

    def median_index(self):
        return 1

    def attach_batcher(self, b):
        self.batcher = b

    def predict_series(self, traffic, integrate=True):
        self.gate.wait(timeout=30)
        self.calls += 1
        return np.zeros((len(traffic), E, 3), np.float32)

    def predict_series_many(self, series_list, integrate=True):
        return [self.predict_series(s, integrate) for s in series_list]


def test_admission_fast_429_with_retry_after(traffic):
    """Beyond the depth (and with no wait budget) requests fail fast with
    429 + Retry-After over real HTTP — not a hung connection."""
    stub = _GatedBackend()
    stub.gate.clear()
    router = ReplicaRouter(
        [EngineReplica(stub, name="r0")],
        config=RouterConfig(admission_depth=1, max_wait_s=0.0,
                            retry_after_s=0.123))
    service = PredictionService(router, None, backend="adm-test")
    server = PredictionServer(service, port=0).start()
    try:
        import http.client

        payload = json.dumps({"traffic": traffic.tolist()}).encode()

        statuses = {}

        def client(i):
            conn = http.client.HTTPConnection(*server.address, timeout=30)
            conn.request("POST", "/v1/predict", body=payload,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            statuses[i] = (resp.status, resp.getheader("Retry-After"),
                           json.loads(body))
            conn.close()

        t0 = threading.Thread(target=client, args=(0,))
        t0.start()
        deadline = time.monotonic() + 10
        while router.admission.stats()["inflight"] < 1:
            assert time.monotonic() < deadline, "first request never admitted"
            time.sleep(0.01)
        t1 = threading.Thread(target=client, args=(1,))
        t1.start()
        t1.join(timeout=10)
        assert not t1.is_alive()
        status, retry_after, body = statuses[1]
        assert status == 429
        assert retry_after == "0.123"
        assert "saturated" in body["error"]
        stub.gate.set()
        t0.join(timeout=10)
        assert statuses[0][0] == 200
        adm = router.admission.stats()
        assert adm["rejected"] == 1 and adm["admitted"] == 1
    finally:
        stub.gate.set()
        server.stop()


def test_admission_bounded_wait_grants_when_slot_frees():
    """A short wait budget absorbs a micro-burst instead of rejecting."""
    adm = WeightedAdmission(RouterConfig(admission_depth=1, max_wait_s=5.0))
    first = adm.try_acquire("a")
    granted = []

    def waiter():
        with adm.try_acquire("b"):
            granted.append("b")

    t = threading.Thread(target=waiter)
    t.start()
    deadline = time.monotonic() + 5
    while adm.stats()["waiting"] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.005)
    first.__exit__(None, None, None)
    t.join(timeout=5)
    assert granted == ["b"]
    assert adm.stats()["inflight"] == 0


def test_admission_wait_timeout_turns_429():
    adm = WeightedAdmission(RouterConfig(admission_depth=1, max_wait_s=0.05,
                                         retry_after_s=0.01))
    ticket = adm.try_acquire("a")
    with pytest.raises(AdmissionError) as exc:
        adm.try_acquire("b")
    assert exc.value.status == 429
    assert exc.value.headers.get("Retry-After") == "0.010"
    ticket.__exit__(None, None, None)
    stats = adm.stats()
    assert stats["rejected"] == 1 and stats["waiting"] == 0


# ---------------------------------------------------------------------------
# Per-tenant fairness


def test_weighted_round_robin_fairness_under_skew():
    """With tenants a (weight 3) and b (weight 1) both saturating a
    single-slot plane, grants must converge to ~3:1 — the light tenant is
    not starved by the heavy one's queue depth."""
    adm = WeightedAdmission(RouterConfig(
        admission_depth=1, max_wait_s=30.0, max_waiting=64,
        tenant_weights={"a": 3.0, "b": 1.0}))
    order: list[str] = []
    order_lock = threading.Lock()
    hold = adm.try_acquire("a")     # freeze the slot while queues build

    def worker(tenant):
        with adm.try_acquire(tenant):
            with order_lock:
                order.append(tenant)

    # the heavy tenant floods 12 waiters, the light one 4
    threads = [threading.Thread(target=worker, args=("a",))
               for _ in range(12)]
    threads += [threading.Thread(target=worker, args=("b",))
                for _ in range(4)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10
    while adm.stats()["waiting"] < 16:
        assert time.monotonic() < deadline, "waiters never queued"
        time.sleep(0.005)
    hold.__exit__(None, None, None)     # release: grants drain in WRR order
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    # every b grant should land within its weight share: in the first 8
    # grants, b (weight 1 of 4) gets ~2 — at least one, i.e. NOT starved
    # behind all 12 a-waiters as FIFO would do
    first8 = order[:8]
    assert first8.count("b") >= 1, f"light tenant starved: {order}"
    # and over the full drain the 3:1 ratio holds while both queues are
    # occupied: b's 4 grants complete before a's queue (12) is done
    assert max(i for i, t in enumerate(order) if t == "b") < len(order) - 1
    stats = adm.stats()
    assert stats["tenants"]["a"]["admitted"] == 13
    assert stats["tenants"]["b"]["admitted"] == 4


# ---------------------------------------------------------------------------
# Rolling reload


def test_rolling_reload_no_mixed_params_under_live_load(traffic):
    """Under continuous load, every response during a rolling reload must
    equal EITHER the old params' output or the new params' output — never
    a mixture — and no request may fail."""
    pred_a = build_tiny(scale=1.0, ladder=(8,))
    pred_b = build_tiny(scale=1.5, ladder=(8,))
    ref_a = pred_a.predict_series(traffic)
    ref_b = pred_b.predict_series(traffic)
    assert not np.allclose(ref_a, ref_b)

    router = ReplicaRouter.build(pred_a, 2)
    try:
        stop = threading.Event()
        outputs: list[np.ndarray] = []
        failures: list[BaseException] = []
        lock = threading.Lock()

        def load():
            while not stop.is_set():
                try:
                    out = router.predict_series(traffic)
                except BaseException as exc:
                    with lock:
                        failures.append(exc)
                    return
                with lock:
                    outputs.append(out)

        threads = [threading.Thread(target=load) for _ in range(4)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 10
        while len(outputs) < 8:         # live traffic flowing pre-reload
            assert time.monotonic() < deadline
            time.sleep(0.01)
        router.rolling_reload_from(pred_b)
        with lock:
            count_at_reload = len(outputs)
        deadline = time.monotonic() + 10
        while len(outputs) < count_at_reload + 8:   # and post-reload
            assert time.monotonic() < deadline
            time.sleep(0.01)
        stop.set()
        for t in threads:
            t.join(timeout=10)
        assert not failures, failures
        n_a = n_b = 0
        for out in outputs:
            if np.array_equal(out, ref_a):
                n_a += 1
            elif np.array_equal(out, ref_b):
                n_b += 1
            else:
                raise AssertionError(
                    "a response matched NEITHER the old nor the new "
                    "params bit-exactly — mixed state observed")
        assert n_a >= 1 and n_b >= 1    # the swap really happened mid-load
        assert router.router_stats()["rolling_reloads"] == 1
        # the router's metadata re-probed from the fresh backend
        assert router.metric_names == pred_b.metric_names
    finally:
        router.close()


# ---------------------------------------------------------------------------
# Executable accounting on virtual devices


def test_zero_new_executables_per_replica_beyond_first(traffic):
    """Replicas landing on the SAME (virtual) device share one stack:
    adding replicas must add zero compiled executables."""
    import jax

    pred = build_tiny(ladder=(8,))
    dev0 = jax.devices()[0]
    for rung in pred.ladder.ladder:                      # warm the ladder
        pred.ladder(np.zeros((rung, W, F), np.float32))
    pred.predict_series(traffic)                         # warm the fused path
    cache_warm = pred.jit_cache_size()
    assert cache_warm is not None and cache_warm >= 1

    router = ReplicaRouter.build(pred, 4, devices=[dev0])
    try:
        stacks = {id(r.backend()) for r in router.replicas}
        assert stacks == {id(pred)}      # one shared stack, four replicas
        for _ in range(6):
            out = router.predict_series(traffic)
            assert out.shape == (len(traffic), E, 3)
        assert pred.jit_cache_size() == cache_warm
        assert router.jit_cache_size() == cache_warm
    finally:
        # shared-stack close must not be applied 4x; router dedupes
        router.close()


def test_distinct_devices_get_distinct_stacks(pred8):
    import jax

    devices = jax.devices()
    assert len(devices) >= 2            # conftest forces 8 virtual devices
    router = ReplicaRouter.build(pred8, 2, devices=devices[:2])
    try:
        stacks = {id(r.backend()) for r in router.replicas}
        assert len(stacks) == 2
        clone = [r.backend() for r in router.replicas
                 if r.backend() is not pred8]
        assert len(clone) == 1          # replica 0 reuses the base stack
        assert clone[0].metric_names == pred8.metric_names
    finally:
        router.close()


def test_clone_backend_matches_base(pred8, traffic):
    clone = clone_backend(pred8)
    assert np.array_equal(clone.predict_series(traffic),
                          pred8.predict_series(traffic))
    assert clone.ladder.base_ladder == pred8.ladder.base_ladder


# ---------------------------------------------------------------------------
# Scale actuation + autoscaler


def _load_autoscaler():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "deploy"))
    import autoscaler as mod
    return mod


def test_scale_to_grows_and_shrinks(pred8, traffic):
    router = ReplicaRouter.build(pred8, 1)
    try:
        assert router.scale_to(3) == 3
        assert len(router.replicas) == 3
        ref = pred8.predict_series(traffic)
        for _ in range(6):
            assert np.array_equal(router.predict_series(traffic), ref)
        assert router.scale_to(1) == 1
        assert len(router.replicas) == 1
        assert np.array_equal(router.predict_series(traffic), ref)
    finally:
        router.close()


class _SwapBatcher:
    def __init__(self):
        self.closed = 0

    def close(self):
        self.closed += 1


class _SwapStack:
    """Minimal backend surface for reload_backend: a batcher slot."""

    def __init__(self):
        self.batcher = _SwapBatcher()

    def attach_batcher(self, b):
        self.batcher = b


def test_reload_backend_swap_chain_under_concurrent_reloads():
    """Dynamic twin of the graftrace RC003 finding on
    EngineReplica.reload_backend: the old shape read ``old`` under one
    acquire and published under ANOTHER, so two concurrent reloads could
    both read the same ``old`` — the loser's published stack retired
    silently, its batcher never detached or closed.  With the single
    critical section the published stacks form an exact swap chain:
    every retired stack's batcher is closed exactly once, and only the
    final stack's batcher survives."""
    base = _SwapStack()
    replica = EngineReplica(base, name="swap")
    fresh = [_SwapStack() for _ in range(120)]
    batchers = {id(s): s.batcher for s in [base] + fresh}

    def worker(chunk):
        for s in chunk:
            replica.reload_backend(s)

    threads = [threading.Thread(target=worker, args=(fresh[i::4],))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    final = replica.backend()
    assert batchers[id(final)].closed == 0, \
        "the live stack's batcher must survive"
    retired = [b for sid, b in batchers.items() if sid != id(final)]
    assert sorted(b.closed for b in retired) == [1] * len(retired), \
        "every retired stack must be closed exactly once (no silent " \
        "retirement, no double close)"


def test_scale_to_concurrent_growth_never_overshoots():
    """Dynamic twin of the graftrace RC003 finding on
    ReplicaRouter.scale_to: the grow path measured the plane under one
    acquire and extended under another, so N concurrent scale_to(k)
    calls could overshoot to ``1 + N*(k-1)`` replicas.  The publish
    section now revalidates the room left before extending."""
    import jax

    stack = _SwapStack()
    stack.batcher = None
    stack.metric_names = ["c0_cpu"]
    stack.window_size = W
    stack.feature_dim = F
    stack.quantiles = (0.5,)
    stack.delta_mask = None
    stack.median_index = lambda: 0

    class _Lead:
        def __init__(self, name, device):
            self.name = name
            self.device = device

        def backend(self):
            return stack

        def drain(self):
            pass

        def close(self):
            pass

    # one seed replica per device so growth reuses stacks instead of
    # cloning (the fake stack is not cloneable, and cloning is not what
    # this hammer exercises)
    seeds = [_Lead(f"r{i}", d) for i, d in enumerate(jax.devices())]
    target = len(seeds) + 5
    router = ReplicaRouter(seeds)
    try:
        threads = [threading.Thread(target=router.scale_to, args=(target,))
                   for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(router.replicas) == target, \
            "concurrent growth must cap at the requested size"
    finally:
        router.close()


def test_autoscaler_measured_basis_scales_with_demand(pred8, traffic):
    mod = _load_autoscaler()
    router = ReplicaRouter.build(pred8, 1)
    try:
        asc = mod.Autoscaler(
            router,
            mod.AutoscalerConfig(min_replicas=1, max_replicas=4,
                                 capacity_rps_per_replica=10.0,
                                 target_utilization=0.5),
            actuate=True)
        asc.sample(now=0.0)
        for _ in range(20):
            router.predict_series(traffic)
        decision = asc.step(now=1.0)     # ~20 rps -> ceil(20/5) = 4
        assert decision["desired"] == 4 and decision["applied"]
        assert len(router.replicas) == 4
        assert decision["basis"]["mode"] == "measured"
        # the decision is emitted to /healthz via router stats
        service = PredictionService(router, None, backend="asc")
        health = service.healthz()
        assert health["router"]["autoscaler"]["desired"] == 4
        # demand vanishes -> scale back to the floor... the window still
        # holds the peak, so trim the history first
        with asc._lock:
            asc._samples.clear()
        asc.sample(now=10.0)
        decision = asc.step(now=20.0)
        assert decision["desired"] == 1
        assert len(router.replicas) == 1
    finally:
        router.close()


def test_autoscaler_model_basis_dogfoods_whatif(pred8):
    """The creative close: the replica count follows the model's own
    what-if capacity estimate of the serving plane's traffic."""
    mod = _load_autoscaler()

    class StubEstimator:
        def __init__(self):
            self.programs = []

        def estimate(self, program, seed=0):
            self.programs.append(program)
            # predicted utilization proportional to requested rps
            rps = program[0]["serve_/v1/predict"]
            series = np.full((len(program),), 0.9 * rps, np.float32)
            return {"predictor_cpu": {"q50": series}}

    router = ReplicaRouter.build(pred8, 1)
    try:
        est = StubEstimator()
        asc = mod.Autoscaler(
            router,
            mod.AutoscalerConfig(min_replicas=1, max_replicas=8,
                                 endpoint="serve_/v1/predict",
                                 metric="predictor_cpu",
                                 unit_capacity=3.0,
                                 target_utilization=1.0),
            estimator=est, actuate=False)
        decision = asc.desired_replicas(mean_rps=10.0, peak_rps=10.0)
        # peak_predicted = 9.0 -> ceil(9 / 3) = 3 replicas
        assert decision["desired"] == 3
        assert decision["basis"]["mode"] == "model"
        assert est.programs[0][0] == {"serve_/v1/predict": 10}
    finally:
        router.close()


def test_autoscaler_writes_k8s_manifest(pred8, tmp_path):
    import shutil

    import yaml

    mod = _load_autoscaler()
    src = os.path.join(os.path.dirname(__file__), "..", "deploy", "k8s",
                       "predictor.yaml")
    manifest = tmp_path / "predictor.yaml"
    shutil.copy(src, manifest)
    router = ReplicaRouter.build(pred8, 1)
    try:
        asc = mod.Autoscaler(
            router,
            mod.AutoscalerConfig(min_replicas=1, max_replicas=8,
                                 capacity_rps_per_replica=1.0),
            manifest_path=str(manifest), actuate=False)
        asc.write_manifest(5)
        with open(manifest) as f:
            docs = list(yaml.safe_load_all(f))
        dep = next(d for d in docs if d["kind"] == "Deployment")
        assert dep["spec"]["replicas"] == 5
        assert dep["metadata"]["name"] == "deeprest-predictor"
    finally:
        router.close()


def test_service_maybe_reload_rolls_the_router(pred8, traffic):
    """With a router backend, the service's checkpoint-reload hook must
    roll the whole plane (drain/swap/re-admit) instead of swapping one
    predictor reference."""
    pred_b = build_tiny(scale=2.0, ladder=(8,))
    ref_b = pred_b.predict_series(traffic)

    class OneShotReloader:
        def __init__(self, fresh):
            self._fresh = fresh

        def poll(self):
            fresh, self._fresh = self._fresh, None
            return fresh

    router = ReplicaRouter.build(pred8, 2)
    service = PredictionService(router, None, backend="roll",
                                reloader=OneShotReloader(pred_b))
    try:
        service.maybe_reload()
        assert service.healthz()["reloads"] == 1
        assert service.healthz()["router"]["rolling_reloads"] == 1
        out = service.predict({"traffic": traffic.tolist()})
        assert np.array_equal(np.asarray(out["predictions"], np.float32),
                              ref_b)
    finally:
        service.close()


def test_serve_help_covers_replica_flags(capsys):
    from deeprest_tpu.cli import build_parser

    with pytest.raises(SystemExit):
        build_parser().parse_args(["serve", "--help"])
    out = capsys.readouterr().out
    for flag in ("--replicas", "--replica-mode", "--admission-depth",
                 "--tenant-weights", "--autoscale", "--autoscale-manifest",
                 "--admission-wait-ms", "--replica-timeout-ms",
                 "--eject-after-failures", "--retry-budget"):
        assert flag in out, f"serve --help missing {flag}"


# ---------------------------------------------------------------------------
# Worker-subprocess replicas


def test_process_replicas_refused_when_the_parent_holds_the_chip(monkeypatch):
    """A chip belongs to one process: a parent on an accelerator cannot
    start workers that need it, and says so before it spawns any."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = {"factory": "router_test_support:build_tiny"}
    with pytest.raises(RuntimeError, match="a chip belongs to one process"):
        ReplicaRouter.build_process(spec, 2)
    # workers that the spec sends to the CPU need no chip: not refused
    # (the count check is the next one, so nothing is spawned here either)
    with pytest.raises(ValueError, match="replica count"):
        ReplicaRouter.build_process({**spec, "jax_platform": "cpu"}, 0)


def test_process_replica_same_interface_and_results(traffic):
    """One worker subprocess behind the replica interface: byte-identical
    predictions, outstanding accounting, clean shutdown."""
    from deeprest_tpu.serve.replica import ProcessReplica

    reference = build_tiny(ladder=(8,)).predict_series(traffic)
    spec = {"factory": "router_test_support:build_tiny",
            "kwargs": {"ladder": [8]},
            "sys_path": [os.path.dirname(os.path.abspath(__file__))]}
    rep = ProcessReplica(spec, name="p0", boot_timeout_s=300.0)
    try:
        assert rep.window_size == W
        out = rep.predict_series(traffic)
        assert np.array_equal(out, reference)
        outs = rep.predict_series_many([traffic, traffic])
        assert all(np.array_equal(o, reference) for o in outs)
        assert rep.outstanding() == 0
        stats = rep.stats()
        assert stats["kind"] == "process" and stats["served_requests"] == 3
        # the router speaks the same protocol over process replicas
        router = ReplicaRouter([rep])
        assert router.window_size == W
        assert np.array_equal(router.predict_series(traffic), reference)
    finally:
        rep.close()
    # public liveness probe: the worker is reaped AND its parent-side
    # resources (Popen sentinel fd) released
    assert not rep.alive()
