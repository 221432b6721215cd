"""Capacity-surface plane (serve/surface.py): mix-space matching,
interpolation parity, the LRU + byte bounds, /v1/whatif interception,
reload-eager invalidation under concurrent reads, and the CLI surface.

Fast tier by design: a deterministic stub synthesizer over build_tiny's
feature space keeps every test dispatch-cheap; the real corpus→space→
synthesizer pipeline is under the sixteen concurrent readers below.
"""

import threading

import numpy as np
import pytest

from router_test_support import F, W, build_tiny

from deeprest_tpu.config import SurfaceConfig
from deeprest_tpu.serve import MixSpace, PredictionService, ServingError
from deeprest_tpu.serve.surface import peaks_from_series


class StubSynthesizer:
    """Deterministic what-if synthesizer over a two-endpoint vocabulary
    in build_tiny's F-dim feature space: counts land in fixed columns
    (plus a derived half-weight column), one seeded noise channel makes
    seed-sensitivity observable, unknown endpoints raise KeyError — the
    TraceSynthesizer contract, minus the corpus fit."""

    ENDPOINTS = ("svc_/a", "svc_/b")

    class _Space:
        capacity = F

    def __init__(self):
        self.space = self._Space()
        self.endpoints = list(self.ENDPOINTS)
        self._lock = threading.Lock()
        self.calls = 0

    def synthesize_series(self, traffic, seed: int = 0):
        with self._lock:
            self.calls += 1
        rng = np.random.default_rng(seed)
        x = np.zeros((len(traffic), F), np.float32)
        for t, step in enumerate(traffic):
            for ep, n in step.items():
                if ep not in self.endpoints:
                    raise KeyError(f"unknown API endpoint {ep!r}")
                i = self.endpoints.index(ep)
                x[t, i] = float(n)
                x[t, i + 2] = 0.5 * float(n)
            x[t, 4] = rng.random()
        return x


GRID = (0.5, 1.0, 2.0)
BASE = [{"svc_/a": 10, "svc_/b": 4}] * W


def make_service(pred=None, synth=None, **cfg_kwargs):
    kwargs = dict(enabled=True, grid=GRID, jitter=3, warm_async=False)
    kwargs.update(cfg_kwargs)
    return PredictionService(pred or build_tiny(),
                             synth or StubSynthesizer(),
                             surface=SurfaceConfig(**kwargs))


@pytest.fixture
def service():
    svc = make_service()
    yield svc
    svc.close()


# -- MixSpace ----------------------------------------------------------


def test_mixspace_axes_and_vertices():
    ms = MixSpace(BASE, GRID, max_axes=3, seed=0)
    assert ms.axes == ("svc_/a", "svc_/b")
    assert ms.num_vertices == len(GRID) ** 2
    verts = ms.vertices()
    assert len(verts) == 9 and verts[0] == (0.5, 0.5)
    # vertex programs follow sweep()'s int(round(n * s)) convention
    assert ms.program_at((2.0, 0.5))[0] == {"svc_/a": 20, "svc_/b": 2}


def test_mixspace_axis_cap_collapses_to_shared():
    ms = MixSpace(BASE, GRID, max_axes=1)
    assert ms.axes == ("*",)
    assert ms.program_at((2.0,))[0] == {"svc_/a": 20, "svc_/b": 8}


def test_mixspace_match_roundtrip_and_snap():
    ms = MixSpace(BASE, GRID, max_axes=3)
    # any generated point matches back inside its rounding interval
    for scales in [(0.5, 0.5), (2.0, 1.0), (1.3, 1.7), (0.6, 1.9)]:
        got = ms.match(ms.program_at(scales))
        assert got is not None
        assert all(abs(g - s) <= 0.5 / 4 + 1e-9
                   for g, s in zip(got, scales))
    # exact grid vertices snap back to the grid value exactly
    assert ms.match(ms.program_at((2.0, 0.5))) == (2.0, 0.5)
    # non-scalings don't match: different key set / stray count
    assert ms.match([{"svc_/a": 10}] * W) is None
    bad = [dict(s) for s in ms.program_at((1.0, 1.0))]
    bad[3]["svc_/b"] += 3
    assert ms.match(bad) is None
    # different length
    assert ms.match(BASE[:-1]) is None


def test_mixspace_key_is_canonical():
    a = MixSpace(BASE, GRID, max_axes=3, seed=0)
    b = MixSpace([dict(s) for s in BASE], list(GRID), max_axes=3, seed=0)
    assert a.key == b.key
    assert MixSpace(BASE, GRID, max_axes=3, seed=1).key != a.key


# -- surface answers ----------------------------------------------------


def test_vertex_reads_are_bit_exact(service):
    """A grid-vertex query answers with the EXACT bytes a direct
    estimate at the space's seed produces — interpolation at a vertex
    takes the stored slice, no arithmetic."""
    r = service.whatif_surface(
        {"base_traffic": BASE, "factor": 1.0, "wait": True})
    assert r["surface"]["hit"] is True
    ms = MixSpace(BASE, GRID, max_axes=3, seed=0)
    pred = service._snapshot()[0]
    for scales in [(0.5, 0.5), (2.0, 2.0), (1.0, 2.0)]:
        prog = ms.program_at(scales)
        hit = service.surface.lookup_program(pred, prog)
        assert hit is not None
        direct = service.whatif.estimate_many_raw([prog], seeds=[0])[0]
        np.testing.assert_array_equal(hit[0], direct)


def test_parity_envelope_pinned(service):
    """The measured surface-vs-direct envelope on held-out jitter mixes:
    documented tolerance 0.5 (worst gap, relative to each capacity
    series' dynamic range) for the coarse 3-point grid over the tiny
    random-init model; a denser grid measures lower
    (test_denser_grid_tightens_parity)."""
    r = service.whatif_surface(
        {"base_traffic": BASE, "factor": 1.5, "wait": True})
    parity = r["surface"]["parity"]
    assert parity["probes"] == 3
    assert 0.0 <= parity["mean_rel_err"] <= parity["max_rel_err"] <= 0.5


def test_denser_grid_tightens_parity():
    coarse = make_service(jitter=8)
    dense = make_service(jitter=8,
                         grid=(0.5, 0.75, 1.0, 1.25, 1.5, 1.75, 2.0))
    try:
        pc = coarse.whatif_surface(
            {"base_traffic": BASE, "factor": 1.5,
             "wait": True})["surface"]["parity"]
        pd = dense.whatif_surface(
            {"base_traffic": BASE, "factor": 1.5,
             "wait": True})["surface"]["parity"]
        assert pd["max_rel_err"] < pc["max_rel_err"]
    finally:
        coarse.close()
        dense.close()


def test_surface_peaks_match_sweep_semantics(service):
    """/v1/whatif/surface peaks at a vertex equal sweep()'s convention
    applied to the direct series (growth for delta metrics, plain peak
    otherwise)."""
    r = service.whatif_surface(
        {"base_traffic": BASE, "factor": 2.0, "wait": True})
    pred = service._snapshot()[0]
    ms = MixSpace(BASE, GRID, max_axes=3)
    direct = service.whatif.estimate_many_raw(
        [ms.program_at((2.0, 2.0))], seeds=[0])[0]
    expect = peaks_from_series(direct, pred.metric_names, pred.quantiles,
                               pred.delta_mask)
    assert r["peaks"] == expect


def test_frontier_fallback_out_of_hull(service):
    """Out-of-hull queries answer from a direct estimate of the exact
    queried program (full model fidelity), flagged as frontier."""
    r = service.whatif_surface(
        {"base_traffic": BASE, "factor": 8.0, "wait": True})
    assert r["surface"]["hit"] is False
    assert r["surface"]["frontier"] is True
    assert r["surface"]["in_hull"] is False
    ms = MixSpace(BASE, GRID, max_axes=3)
    pred = service._snapshot()[0]
    direct = service.whatif.estimate_many_raw(
        [ms.program_at((8.0, 8.0))], seeds=[0])[0]
    assert r["peaks"] == peaks_from_series(
        direct, pred.metric_names, pred.quantiles, pred.delta_mask)


def test_whatif_route_interception(service):
    """In-space /v1/whatif programs answer from the surface (additive
    "surface" response key; estimates equal the interpolated series);
    non-matching programs and mismatched seeds fall through to the
    direct path with hit=False."""
    service.whatif_surface(
        {"base_traffic": BASE, "factor": 1.0, "wait": True})
    ms = MixSpace(BASE, GRID, max_axes=3)
    prog = ms.program_at((2.0, 1.0))
    hit = service.whatif_estimate({"expected_traffic": prog})
    assert hit["surface"]["hit"] is True
    assert hit["surface"]["scales"] == [2.0, 1.0]
    direct = service.whatif.estimate_many_raw([prog], seeds=[0])[0]
    got = hit["estimates"]["c0_cpu"]["q50"]
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  direct[:, 0, 1])
    # a different synthesis seed must NOT read the seed-0 surface
    miss = service.whatif_estimate({"expected_traffic": prog, "seed": 3})
    assert miss["surface"]["hit"] is False
    # an unrelated program falls through too
    other = service.whatif_estimate(
        {"expected_traffic": [{"svc_/a": 7}] * W})
    assert other["surface"]["hit"] is False
    s = service.surface.stats()
    assert s["hits"] >= 1 and s["misses"] >= 2


def test_sixteen_concurrent_readers_of_a_warmed_surface_all_hit():
    """The real pipeline (simulated corpus → call-path space → fitted
    synthesizer) behind a warmed surface, read by sixteen threads at grid
    vertices and at mixes inside the hull: every answer interpolated
    (zero misses, counted by the readers and by the manager), the parity
    envelope measured, no executable added; the same route without a
    surface answers with no ``surface`` key."""
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace, featurize_buckets
    from deeprest_tpu.data.synthesize import TraceSynthesizer
    from deeprest_tpu.workload import normal_scenario, simulate_corpus

    scn = normal_scenario(0)
    scn.calls_per_user = 0.3
    corpus = simulate_corpus(scn, 40)
    space = CallPathSpace(config=FeaturizeConfig(round_to=8))
    featurize_buckets(corpus, space=space)
    synth = TraceSynthesizer(space).fit(corpus)
    pred = build_tiny(f=space.capacity, h=16, w=12)
    grid = (0.5, 1.0, 2.0, 4.0)
    eps = sorted(synth.endpoints)[:2]
    base = [{eps[0]: 10, eps[1]: 30}] * 24
    cached = PredictionService(pred, synth, surface=SurfaceConfig(
        enabled=True, grid=grid, max_axes=2, jitter=4, warm_async=False))
    direct = PredictionService(pred, synth)
    try:
        r = cached.whatif_surface(
            {"base_traffic": base, "factor": 1.0, "wait": True})
        assert r["surface"]["hit"] is True
        ms = MixSpace(base, grid, max_axes=2)
        scales = list(ms.vertices()) + [(0.7, 1.3), (1.5, 2.5),
                                        (1.0, 3.0), (2.2, 1.1)]
        pool = [ms.program_at(s) for s in scales]
        out = direct.whatif_estimate({"expected_traffic": pool[0]})
        assert "surface" not in out
        warm = pred.jit_cache_size()
        before = cached.surface.stats()
        readers, each = 16, 25
        missed = [0] * readers
        barrier = threading.Barrier(readers)

        def reader(tid):
            barrier.wait()
            for j in range(each):
                got = cached.whatif_estimate(
                    {"expected_traffic": pool[(tid * 5 + j) % len(pool)]})
                missed[tid] += not got["surface"]["hit"]

        threads = [threading.Thread(target=reader, args=(tid,))
                   for tid in range(readers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        after = cached.surface.stats()
        assert sum(missed) == 0
        assert after["misses"] == before["misses"]
        assert after["hits"] - before["hits"] == readers * each
        assert after["builds"] == before["builds"] == 1
        assert 0.0 <= after["parity_max_rel_err"] <= 0.5
        assert pred.jit_cache_size() == warm
    finally:
        cached.close()
        direct.close()


def test_baseline_memoized_across_scaling_calls(service):
    """Satellite: WhatIfEstimator memoizes per (program, seed) — the
    baseline of repeated scaling_factor/sweep calls synthesizes and
    predicts once per snapshot, not once per call."""
    est = service.whatif
    synth = est.synthesizer
    hypo1 = [{"svc_/a": 20, "svc_/b": 8}] * W
    hypo2 = [{"svc_/a": 30, "svc_/b": 12}] * W
    est.scaling_factor(BASE, hypo1)
    calls_after_first = synth.calls
    assert calls_after_first == 2                 # baseline + hypothetical
    est.scaling_factor(BASE, hypo2)
    assert synth.calls == calls_after_first + 1   # baseline was memoized
    est.scaling_factor(BASE, hypo2)
    assert synth.calls == calls_after_first + 1   # fully cached call
    assert est.raw_cache_hits >= 3
    # sweep shares the same memo: factor 1.0 IS the baseline program and
    # factor 2.0 reproduces hypo1 exactly — no new synthesis at all
    est.sweep(BASE, [1.0, 2.0])
    assert synth.calls == calls_after_first + 1


def test_memoized_results_are_immutable(service):
    est = service.whatif
    raw = est.estimate_many_raw([BASE], seeds=[0])[0]
    with pytest.raises(ValueError):
        raw[0, 0, 0] = 1.0


# -- LRU / memory bounds ------------------------------------------------


def test_lru_eviction_under_load():
    svc = make_service(max_surfaces=2)
    try:
        # counts chosen so no base is an int-rounded in-hull scaling of
        # another (10 = 20 x 0.5 would alias into a survivor's space and
        # legitimately keep answering after the eviction)
        bases = [[{"svc_/a": n, "svc_/b": 4}] * W for n in (10, 23, 31)]
        for b in bases:
            svc.whatif_surface({"base_traffic": b, "factor": 1.0,
                                "wait": True})
        s = svc.surface.stats()
        assert s["surfaces"] == 2 and s["evictions"] == 1
        # oldest surface is gone: its vertex program misses now
        ms0 = MixSpace(bases[0], GRID, max_axes=3)
        pred = svc._snapshot()[0]
        assert svc.surface.lookup_program(
            pred, ms0.program_at((1.0, 1.0))) is None
        # newest is resident
        ms2 = MixSpace(bases[2], GRID, max_axes=3)
        assert svc.surface.lookup_program(
            pred, ms2.program_at((1.0, 1.0))) is not None
    finally:
        svc.close()


def test_byte_budget_refuses_oversized_spaces():
    svc = make_service(max_bytes=1024)       # smaller than one surface?
    try:
        est_bytes = svc.surface.estimated_bytes(
            MixSpace(BASE, GRID, max_axes=3), svc._snapshot()[0])
        assert est_bytes > 1024
        with pytest.raises(ServingError, match="too large"):
            svc.whatif_surface({"base_traffic": BASE, "factor": 1.0,
                                "wait": True})
        assert svc.surface.stats()["surfaces"] == 0
    finally:
        svc.close()


# -- invalidation correctness -------------------------------------------


def test_drift_reload_invalidates_eagerly(service):
    service.whatif_surface({"base_traffic": BASE, "factor": 1.0,
                            "wait": True})
    assert service.surface.stats()["surfaces"] == 1
    service.reload_from(build_tiny(scale=2.0), reason="drift")
    s = service.surface.stats()
    assert s["surfaces"] == 0 and s["invalidations"] == 1
    assert service.surface._m_invalidations.value(reason="drift") == 1.0


def test_no_pre_reload_surface_after_swap_under_concurrent_reads():
    """The byte-checked no-mixed-params guarantee extended to cached
    answers: reader threads hammer an in-space /v1/whatif while the
    backend hot-swaps (reason="drift").  Every response STARTED after
    reload_from returns must either miss or interpolate a surface whose
    params_hash is the NEW backend's digest — and its bytes must equal
    the new backend's direct estimate, never the old surface's.
    (Responses started BEFORE the swap may legitimately finish on the
    old snapshot — the round-13 rule; the readers here only provide
    live concurrent load.)"""
    pred_a, pred_b = build_tiny(scale=1.0), build_tiny(scale=2.0)
    svc = make_service(pred=pred_a)
    try:
        svc.whatif_surface({"base_traffic": BASE, "factor": 1.0,
                            "wait": True})
        ms = MixSpace(BASE, GRID, max_axes=3)
        prog = ms.program_at((2.0, 2.0))
        old_hash = pred_a.params_digest()
        new_hash = pred_b.params_digest()
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                svc.whatif_estimate({"expected_traffic": prog})

        threads = [threading.Thread(target=reader) for _ in range(4)]
        for t in threads:
            t.start()
        svc.reload_from(pred_b, reason="drift")
        # --- the swap is complete from here on: no response may carry
        # the old surface (a hit is allowed ONLY off a new-params build,
        # e.g. one the misses above auto-warmed) ---
        for _ in range(50):
            r = svc.whatif_estimate({"expected_traffic": prog})
            meta = r["surface"]
            if meta["hit"]:
                assert meta["params_hash"] == new_hash != old_hash, meta
                direct_b = svc.whatif.estimate_many_raw(
                    [prog], seeds=[0])[0]
                got = np.asarray(
                    [[r["estimates"][m][f"q{int(q * 100):02d}"]
                      for q in pred_b.quantiles]
                     for m in pred_b.metric_names],
                    np.float32).transpose(2, 0, 1)
                np.testing.assert_array_equal(got, direct_b)
        stop.set()
        for t in threads:
            t.join(timeout=10.0)
        # warming the NEW surface and reading it byte-checks against the
        # new backend's own direct estimate
        svc.whatif_surface({"base_traffic": BASE, "factor": 1.0,
                            "wait": True})
        hit = svc.surface.lookup_program(pred_b, prog)
        assert hit is not None
        assert hit[1]["params_hash"] == pred_b.params_digest() != old_hash
        direct_b = svc.whatif.estimate_many_raw([prog], seeds=[0])[0]
        np.testing.assert_array_equal(hit[0], direct_b)
    finally:
        svc.close()


def test_stale_build_dropped_when_reload_lands_midbuild():
    """A build that STARTED before a reload must not publish after it:
    the epoch check at insert discards it (counted)."""
    svc = make_service()
    try:
        mgr = svc.surface
        pred = svc._snapshot()[0]
        space = MixSpace(BASE, GRID, max_axes=3)
        # simulate the race deterministically: invalidate between build
        # start (epoch capture) and insert by monkey-wrapping the
        # estimator call
        est = svc.whatif
        real = est.estimate_many_raw

        def racing(*a, **k):
            out = real(*a, **k)
            mgr.invalidate(reason="drift")
            return out

        est.estimate_many_raw = racing
        got = mgr._build(pred, est, space, mode="sync")
        assert got is None
        s = mgr.stats()
        assert s["surfaces"] == 0 and s["stale_builds_dropped"] == 1
    finally:
        svc.close()


def test_params_digest_stable_and_distinct():
    a, a2, b = build_tiny(), build_tiny(), build_tiny(scale=2.0)
    assert a.params_digest() == a2.params_digest()
    assert a.params_digest() != b.params_digest()
    assert a.params_digest() is a.params_digest()      # cached


def test_async_warm_serves_frontier_then_hits():
    svc = make_service(warm_async=True)
    try:
        r = svc.whatif_surface({"base_traffic": BASE, "factor": 1.5})
        assert r["surface"]["hit"] is False
        assert r["surface"]["frontier"] is True       # direct answer
        # the warm build runs on a background thread; join it
        for t in list(svc.surface._threads):
            t.join(timeout=30.0)
        r2 = svc.whatif_surface({"base_traffic": BASE, "factor": 1.5})
        assert r2["surface"]["hit"] is True
    finally:
        svc.close()


# -- wiring: healthz, routes, CLI ---------------------------------------


def test_healthz_surface_key_shape(service):
    service.whatif_surface({"base_traffic": BASE, "factor": 1.0,
                            "wait": True})
    out = service.healthz()["surface"]
    for key in ("enabled", "surfaces", "bytes", "max_surfaces",
                "max_bytes", "inflight_warms", "epoch", "hits", "misses",
                "frontier", "builds", "invalidations", "evictions",
                "stale_builds_dropped", "build_errors",
                "parity_max_rel_err"):
        assert key in out, key
    assert out["enabled"] is True and out["surfaces"] == 1
    assert out["parity_max_rel_err"] is not None


def test_healthz_has_no_surface_key_when_disabled():
    svc = PredictionService(build_tiny(), StubSynthesizer())
    try:
        assert "surface" not in svc.healthz()
        with pytest.raises(ServingError, match="--surface"):
            svc.whatif_surface({"base_traffic": BASE, "factor": 1.0})
    finally:
        svc.close()


def test_surface_route_validation(service):
    with pytest.raises(ServingError, match="exactly one"):
        service.whatif_surface({"base_traffic": BASE})
    with pytest.raises(ServingError, match="exactly one"):
        service.whatif_surface({"base_traffic": BASE, "factor": 1.0,
                                "scales": {"svc_/a": 2.0}})
    with pytest.raises(ServingError, match="not an axis"):
        service.whatif_surface({"base_traffic": BASE,
                                "scales": {"nope_/x": 2.0}})
    with pytest.raises(ServingError):
        service.whatif_surface({"base_traffic": "nope", "factor": 1.0})


def test_serve_help_pins_surface_flags(capsys):
    from deeprest_tpu.cli import build_parser

    with pytest.raises(SystemExit):
        build_parser().parse_args(["serve", "--help"])
    out = capsys.readouterr().out
    for flag in ("--surface", "--surface-grid", "--surface-max-axes",
                 "--surface-jitter", "--surface-max-surfaces",
                 "--surface-max-bytes-mb", "--surface-sync"):
        assert flag in out, flag


def test_drift_controller_reason_probe():
    from deeprest_tpu.train.stream import _accepts_reason

    assert _accepts_reason(None) is False
    assert _accepts_reason(lambda p: None) is False
    assert _accepts_reason(lambda p, reason="manual": None) is True
    assert _accepts_reason(lambda p, **kw: None) is True

    class Svc:
        def reload(self, path, reason="manual"):
            pass

    assert _accepts_reason(Svc().reload) is True


def test_reload_reason_threads_into_router_stats():
    """reload_from(reason=...) reaches rolling_reload_from and the
    router's last_reload_reason observability field."""

    class FakeRouter:
        def __init__(self, inner):
            self._inner = inner
            self.seen_reason = None

        def __getattr__(self, name):
            return getattr(self._inner, name)

        def rolling_reload_from(self, fresh, reason="watch"):
            self.seen_reason = reason

    router = FakeRouter(build_tiny())
    svc = make_service(pred=router)
    try:
        svc.whatif_surface({"base_traffic": BASE, "factor": 1.0,
                            "wait": True})
        svc.reload_from(build_tiny(scale=2.0), reason="drift")
        assert router.seen_reason == "drift"
        s = svc.surface.stats()
        assert s["surfaces"] == 0 and s["invalidations"] == 1
    finally:
        svc.close()


def test_surface_config_validation():
    with pytest.raises(ValueError, match="grid"):
        SurfaceConfig(grid=(1.0,))
    with pytest.raises(ValueError, match="grid"):
        SurfaceConfig(grid=(2.0, 1.0))
    with pytest.raises(ValueError, match="jitter"):
        SurfaceConfig(jitter=-1)
    with pytest.raises(ValueError, match="max_surfaces"):
        SurfaceConfig(max_surfaces=0)
    from deeprest_tpu.config import Config

    cfg = Config.from_dict({"surface": {"enabled": True,
                                        "grid": [0.5, 1, 2]}})
    assert cfg.surface.enabled and cfg.surface.grid == (0.5, 1.0, 2.0)
