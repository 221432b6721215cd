#!/usr/bin/env python
"""fleet_bench: many apps, one serving plane (round 23, serve/fleet.py).

Four arms over the REAL multi-tenant pool — PredictorPool admitting N
random-init apps (distinct parameter trees, identical architecture)
into one fused-engine executable set:

- **ledger** — admit every app, warm the ladder ONCE through the
  template, freeze the jit-cache ledger, then dispatch every app.  The
  headline claim of the fleet tier: executables key by shape, not
  params, so the compiled-executable count stays FLAT in the number of
  apps and ZERO executables appear after warmup (``assert_frozen``).
- **churn** — an LRU storm with the working set larger than
  ``hbm_budget``: random tenant access, spilled tenants restored by
  ``device_put`` from the host tier (never disk, never a compile).
  Gates: honest spill/restore counters (both nonzero), post-storm
  outputs bit-identical to pre-storm references, the ledger still
  frozen, and p99 request latency bounded by a multiple of the warm
  median (restore cost must not blow the tail).
- **isolation** — tenant A's responses byte-checked bit-identical
  with and WITHOUT tenant B hammering the same plane from another
  thread, including a mid-storm hot reload of tenant B.  This is the
  contract TN001 (analysis/rules_fleet.py) guards statically.
- **aot** — cold-start with serialized executables (serve/aot.py)
  vs compile-from-scratch on a fresh engine, plus pool admission
  loading the sidecar (``compile_fallbacks`` must stay 0).  Honest-CPU
  footnote: CPU compiles of these graphs take fractions of a second
  while TPU compiles take orders of magnitude longer, so the speedup
  measured here says nothing about the chip (not measured on the
  chip).

Run ``python benchmarks/fleet_bench.py --out benchmarks/fleet_bench.json``
(the committed artifact; ``make fleet-bench``).  ``--quick`` is the
tier-1 smoke (tests/test_fleet_bench.py); ``--headline`` prints one
JSON line with ``fleet_apps`` + ``fleet_cold_start_ms`` +
``fleet_spill_restore_ms`` for bench.py (schema v14).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

P99_FACTOR = 100.0     # churn p99 <= factor * warm median: the restore
#                        path (host->device device_put) must stay in the
#                        same regime as a warm dispatch, not a compile
#                        (~100x would still catch a recompile, which is
#                        1000x+ on these graphs)
AOT_GATE_QUICK = 1.0   # AOT cold start must at least match a from-
AOT_GATE_FULL = 1.5    # scratch compile; the full shapes must beat it
T = 96                 # request series length (buckets)


def _build_world(quick: bool):
    """One random-init architecture -> a factory of per-app Predictors
    with DISTINCT parameter trees (scaled copies: distinct digests,
    identical avals, so executables are shareable but outputs differ)."""
    import jax

    from deeprest_tpu.config import ModelConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.serve.predictor import Predictor

    apps = 12 if quick else 100
    budget = 4 if quick else 16
    w, e = 12, 3
    f, h = (96, 48) if quick else (256, 64)
    mc = ModelConfig(feature_dim=f, num_metrics=e, hidden_size=h,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    base = model.init(jax.random.PRNGKey(0),
                      np.zeros((1, w, f), np.float32),
                      deterministic=True)["params"]

    def make(i: int) -> Predictor:
        scale = np.float32(1.0 + 0.01 * i)
        params = jax.tree_util.tree_map(lambda x: x * scale, base)
        return Predictor(
            params, mc,
            x_stats=MinMaxStats(min=np.float32(0.0), max=np.float32(1.0)),
            y_stats=MinMaxStats(min=np.zeros((e,), np.float32),
                                max=np.ones((e,), np.float32)),
            metric_names=[f"c{i}_cpu" for i in range(e)],
            window_size=w, ladder=(8,))

    return apps, budget, make, w, f


def _name(i: int) -> str:
    return f"app{i:03d}"


def measure_ledger(pool, make, apps: int, traffic) -> dict:
    """Admit every app, warm once, freeze; every later dispatch — all
    N apps included — must reuse the frozen executable set."""
    t0 = time.perf_counter()
    for i in range(apps):
        pool.admit(_name(i), make(i))
    admit_s = time.perf_counter() - t0
    pool.resolve(_name(0)).predictor().predict_series(traffic)  # warmup
    cache_after_warmup = pool.freeze()
    for i in range(apps):
        pool.resolve(_name(i)).predictor().predict_series(traffic)
    cache_after_all = pool.assert_frozen()
    out = {
        "apps": apps,
        "hbm_budget": pool.hbm_budget,
        "admit_ms_per_app": round(admit_s / apps * 1e3, 3),
        "jit_cache_after_warmup": cache_after_warmup,
        "jit_cache_after_all_apps": cache_after_all,
        "per_app_compiles": (None if cache_after_warmup is None
                             else cache_after_all - cache_after_warmup),
    }
    out["ok"] = out["per_app_compiles"] == 0
    return out


def measure_churn(pool, apps: int, traffic, quick: bool) -> dict:
    """LRU storm with working set > hbm_budget: random access, honest
    spill/restore counters, bit-exact post-storm outputs, bounded p99."""
    rng = np.random.default_rng(23)
    sample = [_name(i) for i in (0, 1, 2)]
    refs = {t: np.asarray(
        pool.resolve(t).predictor().predict_series(traffic))
        for t in sample}
    before = pool.stats()
    n = 150 if quick else 400
    warm_ms, restore_ms, request_ms = [], [], []
    for _ in range(n):
        tenant = _name(int(rng.integers(0, apps)))
        was_resident = pool.peek(tenant).resident
        t0 = time.perf_counter()
        entry = pool.resolve(tenant)               # restores if spilled
        t1 = time.perf_counter()
        out = entry.predictor().predict_series(traffic)
        t2 = time.perf_counter()
        (warm_ms if was_resident else restore_ms).append((t1 - t0) * 1e3)
        request_ms.append((t2 - t0) * 1e3)
        del out
    after = pool.stats()
    bitexact = all(
        np.array_equal(refs[t], np.asarray(
            pool.resolve(t).predictor().predict_series(traffic)))
        for t in sample)
    pool.assert_frozen()
    p99 = float(np.percentile(request_ms, 99))
    warm_median = float(np.median([m for m in request_ms]))
    out = {
        "requests": n,
        "spills": after["spills"] - before["spills"],
        "restores": after["restores"] - before["restores"],
        "evictions": after["evictions"] - before["evictions"],
        "resident": after["resident"],
        "spilled": after["spilled"],
        "restore_ms_median": round(float(np.median(restore_ms)), 3)
        if restore_ms else None,
        "request_ms_median": round(warm_median, 3),
        "request_ms_p99": round(p99, 3),
        "p99_over_median": round(p99 / max(warm_median, 1e-9), 2),
        "post_storm_bit_exact": bitexact,
    }
    out["ok"] = (out["spills"] > 0 and out["restores"] > 0 and bitexact
                 and out["p99_over_median"] <= P99_FACTOR)
    return out


def measure_isolation(pool, make, traffic, apps: int) -> dict:
    """Tenant A byte-checked bit-identical with vs without tenant B
    load from another thread, including a mid-storm reload of B."""
    a, b = _name(0), _name(1)
    ref = np.asarray(pool.resolve(a).predictor().predict_series(traffic))
    solo = [bool(np.array_equal(ref, np.asarray(
        pool.resolve(a).predictor().predict_series(traffic))))
        for _ in range(3)]

    b_before = np.asarray(pool.resolve(b).predictor().predict_series(traffic))
    stop = threading.Event()
    errors: list[str] = []

    def hammer():
        k = 0
        while not stop.is_set():
            try:
                pool.resolve(b).predictor().predict_series(traffic)
            except Exception as exc:  # surfaced as a gate failure
                errors.append(repr(exc))
                return
            k += 1
            if k == 3:   # mid-storm hot swap of the NOISY tenant
                try:
                    pool.reload(b, make(apps + 7), reason="storm-reload")
                except Exception as exc:
                    errors.append(repr(exc))
                    return

    th = threading.Thread(target=hammer, daemon=True)
    th.start()
    concurrent = []
    for _ in range(8):
        got = np.asarray(pool.resolve(a).predictor().predict_series(traffic))
        concurrent.append(bool(np.array_equal(ref, got)))
    stop.set()
    th.join(timeout=30)
    b_after = np.asarray(pool.resolve(b).predictor().predict_series(traffic))
    pool.assert_frozen()
    out = {
        "solo_bit_identical": all(solo),
        "concurrent_bit_identical": all(concurrent),
        "b_reload_took_effect": not np.array_equal(b_before, b_after),
        "b_invalidations": pool.peek(b).invalidations(),
        "hammer_errors": errors,
    }
    out["ok"] = (all(solo) and all(concurrent)
                 and out["b_reload_took_effect"] and not errors)
    return out


def measure_aot(make, traffic, quick: bool) -> dict:
    """Serialized-executable cold start vs compile-from-scratch, plus
    pool admission loading the sidecar (fallback counter must stay 0)."""
    from deeprest_tpu.serve.aot import export_aot, load_aot
    from deeprest_tpu.serve.fleet import PredictorPool

    out: dict = {}
    with tempfile.TemporaryDirectory() as ckpt:
        t0 = time.perf_counter()
        manifest = export_aot(make(0), ckpt)
        out["export_ms"] = round((time.perf_counter() - t0) * 1e3, 1)
        out["executables"] = len(manifest["entries"])
        out["artifact_bytes"] = sum(e["bytes"] for e in manifest["entries"])

        # compile-from-scratch cold start: fresh engine, lazy jit
        cold = make(1)
        t0 = time.perf_counter()
        ref = np.asarray(cold.predict_series(traffic))
        compile_ms = (time.perf_counter() - t0) * 1e3

        # AOT cold start: fresh engine, deserialize + first dispatch
        warm = make(1)
        t0 = time.perf_counter()
        res = load_aot(warm, ckpt)
        got = np.asarray(warm.predict_series(traffic))
        aot_ms = (time.perf_counter() - t0) * 1e3
        out["aot_loaded"] = res["loaded"]
        out["aot_fallback_rungs"] = res["fallback_rungs"]
        out["compile_cold_start_ms"] = round(compile_ms, 1)
        out["aot_cold_start_ms"] = round(aot_ms, 1)
        out["speedup"] = round(compile_ms / max(aot_ms, 1e-9), 1)
        out["bit_identical_vs_compiled"] = bool(np.array_equal(ref, got))
        out["lazy_jit_untouched"] = warm.jit_cache_size() == 0

        # pool admission loads the sidecar instead of compiling
        pool = PredictorPool(hbm_budget=2, aot=True)
        pool.admit("a", make(2), checkpoint_path=ckpt)
        st = pool.stats()["aot"]
        out["pool_admission"] = {
            "loaded": st["loaded"],
            "compile_fallbacks": st["compile_fallbacks"],
        }
    gate = AOT_GATE_QUICK if quick else AOT_GATE_FULL
    out["ok"] = (res["loaded"] > 0 and not res["fallback_rungs"]
                 and out["bit_identical_vs_compiled"]
                 and out["lazy_jit_untouched"]
                 and st["compile_fallbacks"] == 0
                 and out["speedup"] >= gate)
    out["footnote"] = (
        "honest-CPU: XLA:CPU compiles these graphs in fractions of a "
        "second; the compile-vs-deserialize gap on the TPU is not "
        "measured on the chip")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="tier-1 smoke: fewer apps, fewer requests")
    ap.add_argument("--headline", action="store_true",
                    help="print one JSON line for bench.py (schema v14)")
    args = ap.parse_args(argv)

    from deeprest_tpu.serve.fleet import PredictorPool

    t0 = time.perf_counter()
    apps, budget, make, w, f = _build_world(args.quick)
    rng = np.random.default_rng(7)
    traffic = rng.random((T, f)).astype(np.float32)

    pool = PredictorPool(hbm_budget=budget, aot=False)
    ledger = measure_ledger(pool, make, apps, traffic)
    churn = measure_churn(pool, apps, traffic, args.quick)
    isolation = measure_isolation(pool, make, traffic, apps)
    aot = measure_aot(make, traffic, args.quick)

    record = {
        "bench": "fleet_bench",
        "mode": "quick" if args.quick else "full",
        "shapes": {"window": w, "feature_dim": f, "apps": apps,
                   "hbm_budget": budget},
        "ledger": ledger,
        "churn": churn,
        "isolation": isolation,
        "aot": aot,
        "p99_factor": P99_FACTOR,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.headline:
        print(json.dumps({
            "fleet_apps": ledger["apps"],
            "fleet_cold_start_ms": aot["aot_cold_start_ms"],
            "fleet_spill_restore_ms": churn["restore_ms_median"],
        }))
    else:
        print(json.dumps(record, indent=2, sort_keys=True))

    failures = []
    if not ledger["ok"]:
        failures.append(
            f"per-app compiles after warmup: {ledger['per_app_compiles']}")
    if not churn["ok"]:
        failures.append(
            f"churn gate: spills={churn['spills']} "
            f"restores={churn['restores']} "
            f"bit_exact={churn['post_storm_bit_exact']} "
            f"p99/median={churn['p99_over_median']}")
    if not isolation["ok"]:
        failures.append(f"isolation gate: {isolation}")
    if not aot["ok"]:
        failures.append(
            f"aot gate: speedup={aot['speedup']}x "
            f"fallbacks={aot['pool_admission']['compile_fallbacks']}")
    if failures:
        print(f"fleet_bench GATES FAILED: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
