"""Fleet tier (serve/fleet.py + serve/aot.py): many apps, one serving
plane.  PredictorPool admission/sharing with the flat executable ledger,
LRU spill to host memory with bit-exact device_put restore, per-tenant
hot reload with reason-labeled invalidation counters, AOT executable
serialization, the tenant-aware HTTP surfaces (/v1/predict, /v1/verdict,
/healthz, /metrics), the worker boot-handshake ``fleet`` key, and the
fleet-tier chaos coverage (replica death mid-rolling-reload, pool
eviction under live load).

Quick tier: random-init tiny models on single-rung ladders so every
claim is byte-exact, same as tests/test_router.py.
"""

import json
import os
import tempfile
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from router_test_support import F, W, build_tiny

from deeprest_tpu.config import FleetConfig, QualityConfig
from deeprest_tpu.serve import (
    PredictionServer, PredictionService, ReplicaRouter,
)
from deeprest_tpu.serve.fleet import PredictorPool, UnknownTenantError


@pytest.fixture
def traffic():
    return np.random.default_rng(0).random((2 * W, F)).astype(np.float32)


# ---------------------------------------------------------------------------
# Pool mechanics: admission, sharing, the flat ledger


def test_admission_shares_executables_flat_ledger(traffic):
    pool = PredictorPool(hbm_budget=8, aot=False)
    pool.admit("a", build_tiny(scale=1.0, ladder=(8,)))
    ref_a = pool.resolve("a").predictor().predict_series(traffic)
    after_one = pool.jit_cache_size()
    for i, name in enumerate(("b", "c", "d")):
        pool.admit(name, build_tiny(scale=2.0 + i, ladder=(8,)))
        pool.resolve(name).predictor().predict_series(traffic)
    # executables key by shape, not params: the count is FLAT in tenants
    assert pool.jit_cache_size() == after_one
    # ...and the tenants still answer with their OWN params
    out_b = pool.resolve("b").predictor().predict_series(traffic)
    assert not np.array_equal(ref_a, out_b)
    st = pool.stats()
    assert st["tenants"] == 4 and st["admissions"] == 4


def test_admit_rejects_duplicates_and_mismatched_geometry():
    pool = PredictorPool(hbm_budget=4, aot=False)
    pool.admit("a", build_tiny(ladder=(8,)))
    with pytest.raises(ValueError, match="reload"):
        pool.admit("a", build_tiny(ladder=(8,)))
    # a different ladder cannot share the template's executables —
    # admission must refuse, not silently compile a second program set
    with pytest.raises(ValueError):
        pool.admit("other", build_tiny(ladder=(4,)))


def test_unknown_tenant_raises_and_counts():
    pool = PredictorPool(hbm_budget=2, aot=False)
    pool.admit("a", build_tiny(ladder=(8,)))
    with pytest.raises(UnknownTenantError):
        pool.resolve("ghost")
    assert pool.stats()["unknown_tenants"] == 1


def test_spill_restore_bit_exact_no_compile(traffic):
    pool = PredictorPool(hbm_budget=1, aot=False)
    pool.admit("a", build_tiny(scale=1.0, ladder=(8,)))
    ref = np.asarray(pool.resolve("a").predictor().predict_series(traffic))
    pool.freeze()
    pool.admit("b", build_tiny(scale=2.0, ladder=(8,)))   # evicts a
    assert not pool.peek("a").resident
    assert pool.peek("a")._tenant_spill is not None        # host tier
    entry = pool.resolve("a")                              # device_put back
    assert entry.resident and entry._tenant_spill is None
    got = np.asarray(entry.predictor().predict_series(traffic))
    assert np.array_equal(ref, got)
    pool.assert_frozen()                                   # no compile
    st = pool.stats()
    assert st["spills"] >= 1 and st["restores"] == 1


def test_reload_swaps_params_and_counts_invalidations(traffic):
    pool = PredictorPool(hbm_budget=2, aot=False)
    pool.admit("a", build_tiny(scale=1.0, ladder=(8,)))
    before = np.asarray(pool.resolve("a").predictor().predict_series(traffic))
    pool.freeze()
    pool.reload("a", build_tiny(scale=3.0, ladder=(8,)), reason="drift")
    pool.reload("a", build_tiny(scale=4.0, ladder=(8,)), reason="drift")
    pool.reload("a", build_tiny(scale=5.0, ladder=(8,)), reason="manual")
    after = np.asarray(pool.resolve("a").predictor().predict_series(traffic))
    assert not np.array_equal(before, after)
    pool.assert_frozen()          # hot swaps never mint executables
    counts = pool.peek("a").invalidations()
    assert counts == {"drift": 2, "manual": 1}
    counts["drift"] = 99          # accessor returns a COPY
    assert pool.peek("a").invalidations()["drift"] == 2
    with pytest.raises(UnknownTenantError):
        pool.reload("ghost", build_tiny(ladder=(8,)))


def test_frozen_ledger_trips_on_growth(traffic):
    pool = PredictorPool(hbm_budget=2, aot=False)
    pool.admit("a", build_tiny(ladder=(8,)))
    pool.freeze()
    # a fresh rung dispatch after freeze IS a post-warmup compile
    pool.resolve("a").predictor().predict_series(traffic)
    with pytest.raises(RuntimeError, match="jit cache grew post-freeze"):
        pool.assert_frozen()


# ---------------------------------------------------------------------------
# AOT executable serialization (serve/aot.py)


def test_aot_admission_loads_instead_of_compiling(traffic):
    from deeprest_tpu.serve.aot import export_aot

    src = build_tiny(scale=1.0, ladder=(8,))
    ref = np.asarray(src.predict_series(traffic))
    with tempfile.TemporaryDirectory() as ckpt:
        export_aot(src, ckpt)
        pool = PredictorPool(hbm_budget=2, aot=True)
        tgt = build_tiny(scale=1.0, ladder=(8,))
        pool.admit("a", tgt, checkpoint_path=ckpt)
        st = pool.stats()["aot"]
        assert st["loaded"] == 1 and st["compile_fallbacks"] == 0
        assert st["bytes"] > 0
        got = np.asarray(
            pool.resolve("a").predictor().predict_series(traffic))
        assert np.array_equal(ref, got)
        # deserialized executables never touch the lazy jit cache
        assert pool.jit_cache_size() == 0


def test_aot_fingerprint_mismatch_falls_back_to_compile(traffic):
    from deeprest_tpu.serve.aot import export_aot

    with tempfile.TemporaryDirectory() as ckpt:
        export_aot(build_tiny(ladder=(8,)), ckpt)
        pool = PredictorPool(hbm_budget=2, aot=True)
        # different ladder -> different fingerprint: load must refuse and
        # the pool must count the compile fallback, not crash
        pool.admit("a", build_tiny(ladder=(4,)), checkpoint_path=ckpt)
        st = pool.stats()["aot"]
        assert st["loaded"] == 0 and st["compile_fallbacks"] == 1
        assert "rungs" in (st["last_reason"] or "")
        out = pool.resolve("a").predictor().predict_series(traffic)
        assert out.shape[0] == len(traffic)          # lazy path still serves


def test_load_aot_serves_another_tenants_params_without_the_lazy_jit(
        traffic):
    """Artifacts are params-agnostic: exported from one tenant, loaded into
    a predictor of the same architecture with OTHER params, they answer
    bit for bit what that predictor's own compile answers, every rung
    loaded and the lazy jit cache untouched (serve/aot.py, "Identical
    lowering")."""
    from deeprest_tpu.serve.aot import export_aot, load_aot

    compiled = np.asarray(
        build_tiny(scale=2.0, ladder=(8,)).predict_series(traffic))
    with tempfile.TemporaryDirectory() as ckpt:
        manifest = export_aot(build_tiny(scale=1.0, ladder=(8,)), ckpt)
        assert manifest["entries"]
        assert all(e["bytes"] > 0 for e in manifest["entries"])
        tgt = build_tiny(scale=2.0, ladder=(8,))
        res = load_aot(tgt, ckpt)
        assert res["loaded"] == len(manifest["entries"])
        assert res["fallback_rungs"] == [] and res["reason"] is None
        assert res["bytes"] == sum(e["bytes"] for e in manifest["entries"])
        assert np.array_equal(
            compiled, np.asarray(tgt.predict_series(traffic)))
        assert tgt.jit_cache_size() == 0


# ---------------------------------------------------------------------------
# Fleet-tier chaos coverage (satellite 2)


def test_replica_death_mid_rolling_reload_survivors_byte_identical(traffic):
    """Kill a replica mid-rolling-reload with tenant traffic in flight:
    every tenant response stays byte-identical to its own model."""
    pool = PredictorPool(hbm_budget=2, aot=False)
    ta, tb = (build_tiny(scale=1.0, ladder=(8,)),
              build_tiny(scale=2.0, ladder=(8,)))
    pool.admit("a", ta)
    pool.admit("b", tb)
    router = ReplicaRouter.build(build_tiny(ladder=(8,)), 2)
    try:
        router.attach_fleet(pool)
        ref_a = router.predict_series(traffic, tenant="a").tobytes()
        ref_b = router.predict_series(traffic, tenant="b").tobytes()
        pool.freeze()
        bad: list = []
        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                if router.predict_series(traffic, tenant="a").tobytes() \
                        != ref_a:
                    bad.append("a")
                if router.predict_series(traffic, tenant="b").tobytes() \
                        != ref_b:
                    bad.append("b")

        th = threading.Thread(target=hammer, daemon=True)
        th.start()
        reloader = threading.Thread(
            target=lambda: router.rolling_reload_from(
                build_tiny(ladder=(8,)), reason="manual"),
            daemon=True)
        reloader.start()
        name = router.router_stats()["replicas"][0]["name"]
        router.eject(name, reason="chaos: killed mid-reload")
        reloader.join(timeout=60)
        stop.set()
        th.join(timeout=60)
        assert not bad, f"tenant responses diverged: {bad}"
        assert not reloader.is_alive()
        pool.assert_frozen()
        # the kill is recorded in the cumulative counter — the live
        # `ejected` flag may already be False again (the probe rejoins
        # thread replicas within probe_interval_s, by design)
        stats = router.router_stats()
        assert any(r["health"]["ejections"] >= 1
                   for r in stats["replicas"])
    finally:
        router.close()


def test_eviction_under_live_load_restores_without_compile(traffic):
    """hbm_budget=1 with two tenants hammered concurrently: every access
    of one evicts the other, every response stays byte-identical, and no
    restore ever compiles or touches disk (there is no checkpoint)."""
    pool = PredictorPool(hbm_budget=1, aot=False)
    pool.admit("a", build_tiny(scale=1.0, ladder=(8,)))
    ref = {"a": np.asarray(
        pool.resolve("a").predictor().predict_series(traffic))}
    pool.freeze()
    pool.admit("b", build_tiny(scale=2.0, ladder=(8,)))
    ref["b"] = np.asarray(
        pool.resolve("b").predictor().predict_series(traffic))
    bad: list = []

    def churn(tenant):
        for _ in range(12):
            got = np.asarray(
                pool.resolve(tenant).predictor().predict_series(traffic))
            if not np.array_equal(got, ref[tenant]):
                bad.append(tenant)

    threads = [threading.Thread(target=churn, args=(t,))
               for t in ("a", "b", "a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not bad, f"eviction churn corrupted tenants: {bad}"
    pool.assert_frozen()
    st = pool.stats()
    assert st["spills"] > 0 and st["restores"] > 0
    assert st["resident"] == 1          # the budget held


def test_twelve_apps_over_a_budget_of_four_hold_ledger_and_residency(
        traffic):
    """More apps than the device budget, reached at random: the executable
    ledger frozen after ONE app's warm-up holds for all twelve, the LRU
    keeps residency at the budget (a tier, not a leak) with spills and
    restores both counted, and the sampled apps answer after the storm
    what they answered before it, bit for bit."""
    apps, budget = 12, 4
    pool = PredictorPool(hbm_budget=budget, aot=False)
    for i in range(apps):
        pool.admit(f"app{i:02d}",
                   build_tiny(scale=1.0 + 0.01 * i, ladder=(8,)))
    pool.resolve("app00").predictor().predict_series(traffic)
    warm = pool.freeze()
    refs = {f"app{i:02d}": np.asarray(
        pool.resolve(f"app{i:02d}").predictor().predict_series(traffic))
        for i in range(apps)}
    assert pool.assert_frozen() == warm            # zero compiles an app
    assert len({r.tobytes() for r in refs.values()}) == apps
    before = pool.stats()
    rng = np.random.default_rng(23)
    for _ in range(60):
        pool.resolve(f"app{int(rng.integers(0, apps)):02d}") \
            .predictor().predict_series(traffic)
    for name in ("app00", "app01", "app02"):
        assert np.array_equal(refs[name], np.asarray(
            pool.resolve(name).predictor().predict_series(traffic)))
    pool.assert_frozen()
    after = pool.stats()
    assert after["spills"] > before["spills"]
    assert after["restores"] > before["restores"]
    assert after["resident"] == budget
    assert after["resident"] + after["spilled"] == apps


def test_tenant_answers_unmoved_by_a_noisy_neighbours_load_and_reload(
        traffic):
    """Tenant A read eight times while tenant B is hammered from another
    thread and hot-swapped mid-storm: A's bytes never move, B's do, B's
    invalidation is counted once under its reason, nothing compiles."""
    pool = PredictorPool(hbm_budget=2, aot=False)
    pool.admit("a", build_tiny(scale=1.0, ladder=(8,)))
    pool.admit("b", build_tiny(scale=2.0, ladder=(8,)))

    def answer(tenant):
        return np.asarray(
            pool.resolve(tenant).predictor().predict_series(traffic))

    ref_a, b_before = answer("a"), answer("b")
    pool.freeze()
    assert all(np.array_equal(ref_a, answer("a")) for _ in range(3))
    stop, swapped, errors = threading.Event(), threading.Event(), []

    def hammer():
        try:
            for k in range(10_000):
                if stop.is_set():
                    return
                answer("b")
                if k == 2:
                    pool.reload("b", build_tiny(scale=3.0, ladder=(8,)),
                                reason="storm-reload")
                    swapped.set()
        except Exception as exc:      # surfaced below, not swallowed
            errors.append(repr(exc))
            swapped.set()

    th = threading.Thread(target=hammer, daemon=True)
    th.start()
    concurrent = [np.array_equal(ref_a, answer("a")) for _ in range(8)]
    assert swapped.wait(timeout=60)
    concurrent.append(np.array_equal(ref_a, answer("a")))
    stop.set()
    th.join(timeout=60)
    assert not errors, errors
    assert all(concurrent)
    assert not np.array_equal(b_before, answer("b"))
    assert pool.peek("b").invalidations() == {"storm-reload": 1}
    assert pool.peek("a").invalidations() == {}
    pool.assert_frozen()


# ---------------------------------------------------------------------------
# HTTP surfaces: X-Tenant on /v1/predict and /v1/verdict, /healthz fleet
# views, per-tenant /metrics rollup (satellite 1)


@pytest.fixture(scope="module")
def fleet_server():
    base_pred = build_tiny(scale=1.0, ladder=(8,))
    pool = PredictorPool(hbm_budget=4, aot=False,
                         quality_config=QualityConfig(enabled=True),
                         top_k_tenants=2)
    pool.admit("default", base_pred)
    pool.admit("blue", build_tiny(scale=2.0, ladder=(8,)))
    pool.admit("green", build_tiny(scale=3.0, ladder=(8,)))
    pool.admit("violet", build_tiny(scale=4.0, ladder=(8,)))
    service = PredictionService(base_pred, backend="fleet-test")
    service.attach_fleet(pool)
    server = PredictionServer(service, port=0).start()
    host, port = server.address
    yield {"base": f"http://{host}:{port}", "pool": pool,
           "service": service}
    server.stop()


def _get(url, tenant=None):
    headers = {"X-Tenant": tenant} if tenant else {}
    with urllib.request.urlopen(
            urllib.request.Request(url, headers=headers), timeout=30) as r:
        return json.loads(r.read())


def _post(url, payload, tenant=None):
    headers = {"Content-Type": "application/json"}
    if tenant:
        headers["X-Tenant"] = tenant
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(), headers=headers)
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read())


def test_predict_header_selects_the_model(fleet_server, traffic):
    base, pool = fleet_server["base"], fleet_server["pool"]
    payload = {"traffic": traffic.tolist()}
    body_default = _post(base + "/v1/predict", payload)
    body_blue = _post(base + "/v1/predict", payload, tenant="blue")
    assert body_default["tenant"]["name"] == "default"
    assert body_blue["tenant"]["name"] == "blue"
    expect = pool.peek("blue").predictor().predict_series(traffic)
    np.testing.assert_array_equal(
        np.asarray(body_blue["predictions"], np.float32),
        np.asarray(expect, np.float32))
    assert not np.array_equal(np.asarray(body_blue["predictions"]),
                              np.asarray(body_default["predictions"]))
    assert (body_blue["tenant"]["params_digest"]
            == pool.peek("blue").key[1])


def test_predict_unknown_tenant_is_404(fleet_server, traffic):
    with pytest.raises(urllib.error.HTTPError) as err:
        _post(fleet_server["base"] + "/v1/predict",
              {"traffic": traffic.tolist()}, tenant="ghost")
    assert err.value.code == 404
    assert "not admitted" in json.loads(err.value.read())["error"]


def test_verdict_honors_tenant_header(fleet_server):
    base = fleet_server["base"]
    body = _get(base + "/v1/verdict", tenant="blue")
    assert body["tenant"]["name"] == "blue"
    assert body["tenant"]["invalidations"] == {}
    assert "metrics" in body and "states" in body
    # per-tenant monitors: default's verdict is a DIFFERENT object
    assert _get(base + "/v1/verdict")["tenant"]["name"] == "default"
    with pytest.raises(urllib.error.HTTPError) as err:
        _get(base + "/v1/verdict", tenant="ghost")
    assert err.value.code == 404


def test_verdict_503_when_pool_has_no_quality():
    pool = PredictorPool(hbm_budget=2, aot=False)   # quality off
    pred = build_tiny(ladder=(8,))
    pool.admit("default", pred)
    service = PredictionService(pred, backend="no-quality")
    try:
        service.attach_fleet(pool)
        from deeprest_tpu.serve.server import ServingError
        with pytest.raises(ServingError, match="quality"):
            service.verdict("default")
    finally:
        service.close()


def test_healthz_fleet_view_with_pool(fleet_server):
    body = _get(fleet_server["base"] + "/healthz")
    fleet = body["fleet"]
    assert fleet["pool"]["hbm_budget"] == 4
    assert fleet["pool"]["tenants"] == 4
    # per-tenant quant/digest map (the boot handshake's single global
    # pair grown per-tenant) with top-K + __other__ cardinality bound
    tenants = fleet["tenants"]
    named = {k: v for k, v in tenants.items() if k != "__other__"}
    assert len(named) == 2                      # top_k_tenants=2
    assert tenants["__other__"]["tenants"] == 2
    for meta in named.values():
        assert set(meta) == {"quant", "params_digest", "resident"}
        assert meta["quant"] == "off" and meta["params_digest"]
    # existing key shapes unchanged (round-14 style views, not moves)
    assert body["quant"]["mode"] == "off"
    assert body["ok"] is True


def test_healthz_fleet_view_without_pool():
    pred = build_tiny(ladder=(8,))
    service = PredictionService(pred, backend="solo")
    try:
        out = service.healthz()
    finally:
        service.close()
    fleet = out["fleet"]
    assert fleet["pool"] is None
    assert fleet["tenants"] == {"default": {
        "quant": "off",
        "params_digest": pred.params_digest(),
        "resident": True,
    }}


def test_metrics_per_tenant_rollup_bounded(fleet_server, traffic):
    base = fleet_server["base"]
    # the registry's "serving" collector is replace-by-name (newest
    # plane owns /metrics); earlier tests built throwaway services, so
    # re-assert this module's plane before reading the exposition
    from deeprest_tpu.obs import metrics as obs_metrics

    svc = fleet_server["service"]
    obs_metrics.REGISTRY.register_collector("serving",
                                            svc._collect_metrics)
    # give the top-K ranking something to rank by
    _post(base + "/v1/predict", {"traffic": traffic.tolist()},
          tenant="blue")
    with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
        text = r.read().decode()
    assert "deeprest_fleet_tenants 4" in text
    assert "deeprest_fleet_spills_total" in text
    assert "deeprest_fleet_restores_total" in text
    assert 'deeprest_quality_tenant_sweeps_total{tenant="blue"}' in text
    # bounded cardinality: top-K named tenants + ONE __other__ rollup
    assert 'tenant="__other__"' in text
    named = {line.split('tenant="')[1].split('"')[0]
             for line in text.splitlines()
             if line.startswith("deeprest_quality_tenant_verdict{")}
    assert len(named) <= 3                      # 2 named + __other__


# ---------------------------------------------------------------------------
# Boot handshake + backend override on process replicas (satellite 5)


def test_process_replica_boot_handshake_fleet_key(traffic):
    from deeprest_tpu.serve.replica import ProcessReplica

    expected = build_tiny(ladder=(8,))
    spec = {"factory": "router_test_support:build_tiny",
            "kwargs": {"ladder": [8]},
            "sys_path": [os.path.dirname(os.path.abspath(__file__))]}
    rep = ProcessReplica(spec, name="p0", boot_timeout_s=300.0)
    try:
        meta = rep.fleet_meta()
        assert meta == {"tenants": {"default": {
            "quant": "off",
            "params_digest": expected.params_digest(),
        }}}
        # the fleet tier needs in-process backends: the override must be
        # a loud error, not params silently shipped over the pipe
        with pytest.raises(ValueError, match="in-process"):
            rep.predict_series(traffic, backend=expected)
        router = ReplicaRouter([rep])
        with pytest.raises(ValueError, match="fleet"):
            router.attach_fleet(PredictorPool(hbm_budget=2, aot=False))
    finally:
        rep.close()


# ---------------------------------------------------------------------------
# FleetConfig (config.py)


def test_fleet_config_defaults_and_validation():
    cfg = FleetConfig()
    assert (cfg.enabled, cfg.hbm_budget, cfg.aot,
            cfg.top_k_tenants, cfg.quality) == (False, 4, True, 8, True)
    with pytest.raises(ValueError, match="hbm_budget"):
        FleetConfig(hbm_budget=0)
    with pytest.raises(ValueError, match="top_k_tenants"):
        FleetConfig(top_k_tenants=-1)
    with pytest.raises(ValueError, match="hbm_budget"):
        FleetConfig(hbm_budget=True)


def test_fleet_config_from_dict_round_trip():
    from deeprest_tpu.config import Config

    cfg = Config.from_dict(
        {"fleet": {"enabled": True, "hbm_budget": 2, "aot": False}})
    assert cfg.fleet.enabled and cfg.fleet.hbm_budget == 2
    assert not cfg.fleet.aot
    assert Config.from_dict({}).fleet == FleetConfig()
