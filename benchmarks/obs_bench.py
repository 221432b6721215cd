#!/usr/bin/env python
"""obs overhead gate: serve + train hot paths with obs off vs on.

The observability subsystem's contract (ISSUE 9) is near-zero cost when
disabled and a hard <=3% budget when enabled.  This bench measures both
hot paths A/B:

- **serve**: ``Predictor.predict_series`` over a multi-window series,
  wrapped in the same request-root span the HTTP handler opens — so the
  enabled run pays exactly the production span set (request root +
  fused-engine span) plus the always-on metric counters.
- **train**: ``Trainer.train_epoch`` on the host-feed path — the
  enabled/disabled delta here is the span recorder flag only, since the
  train-plane metrics (Throughput publish, readback/dispatch counters)
  are per-epoch and always on.

Methodology: interleaved A/B trials (off, on, off, on, ...) so clock
drift hits both modes equally; each mode's rate is the MEDIAN over its
trials; predict_series returns numpy (host-materialized, inherently
synced) and train_epoch ends in ``block_until_ready`` + a stacked loss
readback, so every timed region closes at a host-visible edge — the
honest-sync discipline (PERF.md).  Overhead below measurement noise can
come out negative; it clamps to 0.

Run ``python benchmarks/obs_bench.py --out benchmarks/obs_bench.json``
(the committed artifact; ``make obs-bench``).  ``--quick`` is the tier-1
smoke (tests/test_obs_bench.py) with a relaxed budget — CPU timing noise
at tiny trial counts must not flake the suite; the committed full run
asserts the real 3% budget.  ``--headline`` prints one JSON line with
``obs_overhead_pct`` for bench.py (schema v8).
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BUDGET_PCT = 3.0
QUICK_BUDGET_PCT = 15.0      # tier-1 smoke: schema + plumbing, not timing

# Serve-path shape: window/hidden sized so a call costs milliseconds of
# real model work (the production regime the budget is about — the
# reference serving shapes are W=60, H=128); the train path stays tiny
# because its obs delta is per-epoch, not per-step.
W, F, E, H = 16, 8, 3, 64


def _build_predictor():
    import jax
    import numpy as np

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
    return Predictor(
        params, mc,
        x_stats=MinMaxStats(min=np.float32(0.0), max=np.float32(1.0)),
        y_stats=MinMaxStats(min=np.zeros((E,), np.float32),
                            max=np.ones((E,), np.float32)),
        metric_names=[f"c{i}_cpu" for i in range(E)],
        window_size=W, ladder=(8,))


def _ab_rates(run_once, trials: int, units: int, best: bool = False):
    """Interleaved off/on trials → (off_rate, on_rate) medians; with
    ``best`` (``--quick``) each side's fastest trial instead: the tier-1
    smoke shares its cores with five other test workers, whose load only
    ever slows a trial, and a median of three ~60 ms trials swings past
    the smoke's budget on that alone."""
    from deeprest_tpu import obs

    rates = {False: [], True: []}
    for _ in range(trials):
        for enabled in (False, True):
            obs.configure(enabled=enabled)
            t0 = time.perf_counter()
            run_once()
            rates[enabled].append(units / (time.perf_counter() - t0))
    obs.configure(enabled=False)
    pick = max if best else statistics.median
    return pick(rates[False]), pick(rates[True])


def _overhead_pct(off_rate: float, on_rate: float) -> float:
    return max(0.0, (off_rate / on_rate - 1.0) * 100.0)


def measure_serve(quick: bool) -> dict:
    import numpy as np

    from deeprest_tpu import obs

    pred = _build_predictor()
    rng = np.random.default_rng(0)
    series = rng.random((W * 20, F), np.float32)     # 20 windows/call
    calls = 10 if quick else 40

    def run_once():
        for _ in range(calls):
            # the production span set: request root (what the HTTP
            # handler opens) + the engine's own fused.predict span
            with obs.span("/v1/predict", component="deeprest-predictor"):
                pred.predict_series(series)

    run_once()                                       # warm the jit cache
    obs.RECORDER.clear()
    off, on = _ab_rates(run_once, trials=3 if quick else 5, units=calls,
                        best=quick)
    return {"off_calls_per_sec": round(off, 2),
            "on_calls_per_sec": round(on, 2),
            "windows_per_call": 20,
            "overhead_pct": round(_overhead_pct(off, on), 3)}


def measure_train(quick: bool) -> dict:
    import numpy as np

    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.train import Trainer
    from deeprest_tpu.train.data import DatasetBundle

    n = 96 if quick else 256
    cfg = Config(model=ModelConfig(feature_dim=F, num_metrics=E,
                                   hidden_size=H, dropout_rate=0.0),
                 train=TrainConfig(batch_size=16, window_size=W,
                                   log_every_steps=0))
    trainer = Trainer(cfg, F, [f"c{i}_cpu" for i in range(E)])
    rng = np.random.default_rng(0)
    x = rng.random((n, W, F), np.float32)
    y = rng.random((n, W, E), np.float32)
    stats = MinMaxStats(min=np.float32(0.0), max=np.float32(1.0))
    bundle = DatasetBundle(
        x_train=x, y_train=y, x_test=x[:4], y_test=y[:4],
        x_stats=stats, y_stats=stats,
        metric_names=[f"c{i}_cpu" for i in range(E)],
        split=n, window_size=W)
    state_box = {"state": trainer.init_state(x)}
    data_rng = np.random.default_rng(1)
    steps = -(-n // 16)

    def run_once():
        state_box["state"], _ = trainer.train_epoch(
            state_box["state"], bundle, data_rng)

    run_once()                                       # warm the jit cache
    off, on = _ab_rates(run_once, trials=3 if quick else 5, units=steps,
                        best=quick)
    return {"off_steps_per_sec": round(off, 2),
            "on_steps_per_sec": round(on, 2),
            "steps_per_epoch": steps,
            "overhead_pct": round(_overhead_pct(off, on), 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="tier-1 smoke sizes + relaxed noise budget")
    ap.add_argument("--headline", action="store_true",
                    help="print only the bench.py headline JSON line")
    ap.add_argument("--out", default=None, help="write the full record here")
    args = ap.parse_args(argv)

    import jax

    serve = measure_serve(args.quick)
    train = measure_train(args.quick)
    budget = QUICK_BUDGET_PCT if args.quick else BUDGET_PCT
    worst = max(serve["overhead_pct"], train["overhead_pct"])
    record = {
        "schema_version": 1,
        "quick": args.quick,
        "platform": jax.devices()[0].platform,
        "device_kind": getattr(jax.devices()[0], "device_kind",
                               jax.devices()[0].platform),
        "shape": {"W": W, "F": F, "E": E, "H": H},
        "serve": serve,
        "train": train,
        "obs_overhead_pct": round(worst, 3),
        "budget_pct": budget,
        "pass": worst <= budget,
        "note": ("overhead = off/on median-rate ratio over interleaved "
                 "A/B trials; disabled mode is the baseline by "
                 "construction (span() returns a no-op singleton — the "
                 "zero-allocation probe in tests/test_obs.py pins its "
                 "cost), so 'off' IS the ~0% disabled measurement"),
    }
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(record, f, indent=2, sort_keys=True)
    if args.headline:
        print(json.dumps({"obs_overhead_pct": record["obs_overhead_pct"]}))
    else:
        print(json.dumps(record))
    # the asserted budget: enabled observability must stay within 3% of
    # disabled on both hot paths (relaxed under --quick: timing noise at
    # smoke sizes is not a product regression)
    assert worst <= budget, (
        f"obs overhead {worst:.2f}% exceeds the {budget}% budget "
        f"(serve {serve['overhead_pct']}%, train {train['overhead_pct']}%)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
