#!/usr/bin/env python
"""Rolled-inference benchmark: host-loop reference vs fused device pipeline.

Measures the serving-side prediction path (serve/fused.py vs the pinned
``rolled_prediction_reference`` host loop) on a serving-realistic
random-init model — load benching needs the compute graph, not trained
weights (same rationale as benchmarks/serve_bench.py):

- series throughput (series/s) at T ∈ {1h, 1d, 30d} of one-minute
  buckets (W=60), three ways: the host loop, the fused engine called
  per-series, and the fused engine with all series FOLDED into shared
  pages (``predict_series_many`` — the multi-scenario capability the
  host loop structurally lacks);
- device-dispatch counts per series for both paths (the host loop pays
  O(windows / max_batch) blocking iterations; the fused path one
  dispatch per page with the integration carry chained on device);
- what-if sweep scaling S ∈ {1, 4, 16} scenarios at the 1-day shape:
  sequential host-loop trains vs one folded fused train;
- a zero-post-warmup-compile probe across every mixed length and sweep
  size exercised (``new_compiles_after_warmup`` must be 0).

Usage:  python benchmarks/infer_bench.py [--quick] [--out PATH]
        (--quick drops the 30-day shape and shrinks repeat counts; it is
        wired into tier-1 via tests/test_infer_bench.py.  --headline
        prints only the 1-day fused windows/s line bench.py consumes.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Serving-realistic shape (serve_bench precedent for F/E; H=128 is the
# reference model's hidden size), flagship window of one-minute buckets.
F, E, H, W = 64, 8, 128, 60
LADDER = (8, 16, 32, 64)
SHAPES = {"1h": 60, "1d": 1440, "30d": 43200}
QUICK_SHAPES = ("1h", "1d")
SWEEP_SIZES = (1, 4, 16)
PAGE_SWEEP = (8, 16, 32, 64)
REPEATS = {"1h": 64, "1d": 10, "30d": 2}
QUICK_REPEATS = {"1h": 8, "1d": 3, "30d": 1}


def make_predictor(page_windows=None):
    import jax

    from deeprest_tpu.config import ModelConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.serve.predictor import Predictor

    mc = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, W, F), np.float32),
                        deterministic=True)["params"]
    delta = np.zeros((E,), bool)
    delta[::4] = True           # a quarter of the metrics are delta-trained
    return Predictor(
        params, mc,
        x_stats=MinMaxStats(min=np.float32(0.0), max=np.float32(4.0)),
        y_stats=MinMaxStats(min=np.zeros((E,), np.float32),
                            max=np.linspace(1.0, 5.0, E).astype(np.float32)),
        metric_names=[f"comp{i // 2}_{'usage' if i % 4 == 0 else 'cpu'}"
                      for i in range(E)],
        window_size=W, delta_mask=delta, ladder=LADDER,
        page_windows=page_windows)


def host_loop(pred, series):
    from deeprest_tpu.serve.predictor import rolled_prediction_reference

    return rolled_prediction_reference(
        pred.apply_windows, pred.x_stats, pred.y_stats, pred.window_size,
        series, delta_mask=pred.delta_mask,
        median_index=pred.median_index())


def _time(fn, reps: int) -> float:
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return time.perf_counter() - t0


def warmup(pred, rng) -> None:
    """Compile every ladder rung and every fused page/tail rung up front,
    so measurements (and the zero-new-compile probe) see a warm cache."""
    for rung in pred.ladder.ladder:
        pred.ladder(np.zeros((rung, W, F), np.float32))
    for rung in pred.fused.rungs:
        pred.fused.predict_many([rng.random((rung * W, F), np.float32)])
        pred.fused.predict_many([rng.random((rung * W, F), np.float32)],
                                integrate=False)


def measure_shape(pred, t: int, reps: int, rng) -> dict:
    from deeprest_tpu.serve.fused import plan_windows

    series = [rng.random((t, F), np.float32) for _ in range(reps)]
    # shape-specific warm pass (everything is rung-warm already; this
    # warms OS/allocator state for the series size)
    host_loop(pred, series[0])
    pred.fused.predict_many([series[0]])
    ladder0 = pred.ladder.stats()["calls"]
    fused0 = pred.fused.stats()["pages"]

    host_s = _time(lambda: [host_loop(pred, s) for s in series], 1)
    ladder1 = pred.ladder.stats()["calls"]
    single_s = _time(
        lambda: [pred.fused.predict_many([s]) for s in series], 1)
    single1 = pred.fused.stats()["pages"]
    folded_s = _time(lambda: pred.fused.predict_many(series), 1)
    fused1 = pred.fused.stats()["pages"]

    n_windows = len(plan_windows([t], W))
    return {
        "series_len": t,
        "windows_per_series": n_windows,
        "repeats": reps,
        "host_loop_series_per_sec": round(reps / host_s, 3),
        "fused_series_per_sec": round(reps / single_s, 3),
        "fused_folded_series_per_sec": round(reps / folded_s, 3),
        "fused_vs_host": round(host_s / single_s, 3),
        "fused_folded_vs_host": round(host_s / folded_s, 3),
        "host_dispatches_per_series": (ladder1 - ladder0) / reps,
        "fused_pages_per_series": (single1 - fused0) / reps,
        "fused_pages_folded": fused1 - single1,
        "fused_windows_per_sec": round(n_windows * reps / folded_s, 1),
    }


def measure_sweep(pred, t: int, sizes, rng) -> list[dict]:
    out = []
    for s_count in sizes:
        series = [rng.random((t, F), np.float32) for _ in range(s_count)]
        host_loop(pred, series[0])                  # warm
        pred.fused.predict_many(series)
        seq_s = _time(lambda: [host_loop(pred, s) for s in series], 1)
        fold_s = _time(lambda: pred.fused.predict_many(series), 1)
        out.append({
            "scenarios": s_count,
            "series_len": t,
            "sequential_host_s": round(seq_s, 4),
            "folded_fused_s": round(fold_s, 4),
            "speedup": round(seq_s / fold_s, 3),
        })
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--headline", action="store_true",
                    help="print only the 1-day fused windows/s record "
                         "(bench.py's rolled_windows_per_sec source)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax

    # CPU-only by design (the quick tier runs inside tier-1); the record's
    # "platform" key says so.
    jax.config.update("jax_platforms", "cpu")

    pred = make_predictor()
    rng = np.random.default_rng(0)
    warmup(pred, rng)
    shapes = QUICK_SHAPES if args.quick else tuple(SHAPES)
    reps = QUICK_REPEATS if args.quick else REPEATS

    records = {}
    for name in shapes:
        records[name] = measure_shape(pred, SHAPES[name], reps[name], rng)
    sweep_sizes = SWEEP_SIZES[:2] if args.quick else SWEEP_SIZES
    sweep = measure_sweep(pred, SHAPES["1d"], sweep_sizes, rng)

    # page-size sweep at the 1-day shape: the data behind the CPU
    # auto-page choice (per-window cost is cache-bound, not
    # occupancy-bound, on XLA CPU)
    page_sweep = []
    if not args.quick:
        for page in PAGE_SWEEP:
            p2 = make_predictor(page_windows=page)
            x = rng.random((SHAPES["1d"], F), np.float32)
            p2.fused.predict_many([x])                      # warm
            dt = _time(lambda: p2.fused.predict_many([x]), 3) / 3
            page_sweep.append({"page_windows": page,
                               "series_s": round(dt, 4),
                               "series_per_sec": round(1.0 / dt, 3)})

    # zero-post-warmup-compile probe: warmup() compiled every rung both
    # engines use; replaying mixed ragged lengths and sweep sizes must
    # compile nothing new.
    cache_before = pred.jit_cache_size()
    probe_rng = np.random.default_rng(1)
    for t in (W, W + 7, 3 * W + 5, 11 * W + 2, 2 * SHAPES["1h"] + 13):
        pred.fused.predict_many([probe_rng.random((t, F), np.float32)])
        pred.fused.predict_many([probe_rng.random((t, F), np.float32)],
                                integrate=False)
        host_loop(pred, probe_rng.random((t, F), np.float32))
    for s_count in sweep_sizes:
        pred.fused.predict_many(
            [probe_rng.random((SHAPES["1h"], F), np.float32)
             for _ in range(s_count)])
    cache_after = pred.jit_cache_size()
    new_compiles = (None if cache_before is None
                    else cache_after - cache_before)

    result = {
        "schema_version": 1,
        "quick": args.quick,
        "model": {"F": F, "E": E, "H": H, "W": W,
                  "ladder": list(LADDER),
                  "page_windows": pred.fused.page,
                  "delta_metrics": int(np.sum(pred.delta_mask))},
        "platform": jax.devices()[0].platform,
        "shapes": records,
        "sweep_1d": sweep,
        "page_sweep_1d": page_sweep,
        "new_compiles_after_warmup": new_compiles,
        "jit_cache": pred.jit_cache_stats(),
        "note": ("host_loop is rolled_prediction_reference through the "
                 "shape ladder (the seed's only path).  fused_series/s "
                 "calls the fused engine once per series; "
                 "fused_folded_series/s folds the whole series batch "
                 "into shared pages (predict_series_many) — the "
                 "capability the host loop structurally lacks, and the "
                 "honest basis for multi-series/multi-scenario "
                 "throughput claims."),
    }
    if args.headline:
        print(json.dumps({"rolled_windows_per_sec":
                          records["1d"]["fused_windows_per_sec"]}))
        return
    blob = json.dumps(result, indent=2)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(blob + "\n")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
