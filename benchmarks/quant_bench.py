#!/usr/bin/env python
"""quant_bench: the quantized serving path (round 22, ops/quantize.py).

Four arms over the REAL fused serving pipeline at each quant mode
(off / int8 / bf16) on a random-init model with flagship-ish shapes
(a trained checkpoint changes none of what this measures — bytes,
parity, and executable counts are properties of the graph):

- **bytes** — ``weight_bytes`` of the serving weight tree per mode.
  The headline claim: int8 stores every GRU/dense weight matrix
  per-output-channel symmetric int8, so the tree is >=3.5x smaller
  than f32 (the f32 scale row amortizes over the contraction dim);
  bf16 is ~2x.  This is EXACT arithmetic, not a timing.
- **parity** — ``predict_series`` through the fused engine at int8 /
  bf16 vs the f32 reference on a held-out series: the max |diff| must
  sit inside the mode's pinned envelope budget (measured at quantize
  time on the deterministic probe, x2 margin — the envelope transfers
  from probe to serving path or the contract is broken).
- **compiles** — ``jit_cache_size()`` must be IDENTICAL across all
  three modes after the same warmup (dequant-at-use lives inside the
  existing executables; quantization must not grow the ladder), and a
  second timed pass must add ZERO executables at every mode.
- **coldstart** — host->device transfer of the serving weight tree
  (the tenant-swap / reload unit): bytes are exact (the >=3.5x), the
  timing rides along as a collapse guard only.  Honest-CPU footnote:
  on the CPU backend per-leaf dispatch overhead dominates a memcpy of
  megabyte trees, so the wall-clock win here is a FRACTION of the
  byte win; the byte ratio is what a host->HBM path moves (not measured
  on the chip).

Throughput rides along un-gated except for collapse (int8 must stay
within 2x of f32): on CPU the dequant multiply ADDS work per dispatch
— the serving win is weight bandwidth on accelerators, and this bench
does not claim it from CPU.

Run ``python benchmarks/quant_bench.py --out benchmarks/quant_bench.json``
(the committed artifact; ``make quant-bench``).  ``--quick`` is the
tier-1 smoke (tests/test_quant_bench.py); ``--headline`` prints one
JSON line with ``quant_weight_bytes`` + ``quant_parity_max`` for
bench.py (schema v13).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BYTES_GATE_INT8 = 3.5
BYTES_GATE_BF16 = 1.9
THROUGHPUT_COLLAPSE = 0.5      # int8 serving must stay within 2x of f32
COLDSTART_COLLAPSE_FULL = 0.6  # quantized device_put must not be SLOWER
COLDSTART_COLLAPSE_QUICK = 0.25   # quick shapes: per-leaf overhead
# dominates kilobyte memcpys and the int8 tree has MORE leaves
# (data+scale per weight), so quick only catches order-of-magnitude
# collapse; the full run's megabyte tree is the guarded number
T = 96                         # parity/throughput series length (buckets)


def _build_world(quick: bool):
    """random-init model at flagship-ish shapes -> one Predictor per
    quant mode, all sharing the SAME f32 parameter tree."""
    import jax

    from deeprest_tpu.config import ModelConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.serve.predictor import Predictor

    w, e = 12, 3
    f, h = (96, 48) if quick else (768, 128)
    mc = ModelConfig(feature_dim=f, num_metrics=e, hidden_size=h,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, w, f), np.float32),
                        deterministic=True)["params"]

    def make(mode: str) -> Predictor:
        return Predictor(
            params, mc,
            x_stats=MinMaxStats(min=np.float32(0.0), max=np.float32(1.0)),
            y_stats=MinMaxStats(min=np.zeros((e,), np.float32),
                                max=np.ones((e,), np.float32)),
            metric_names=[f"c{i}_cpu" for i in range(e)],
            window_size=w, ladder=(8,), quant=mode)

    return params, make, w, f


def measure_bytes(preds: dict) -> dict:
    """Exact serving-weight-tree byte accounting per mode."""
    from deeprest_tpu.ops import quantize as quant_ops

    by_mode = {m: quant_ops.weight_bytes(p.params)
               for m, p in preds.items()}
    out = {
        "weight_bytes": by_mode,
        "ratio_int8": round(by_mode["off"] / by_mode["int8"], 2),
        "ratio_bf16": round(by_mode["off"] / by_mode["bf16"], 2),
    }
    out["ok"] = (out["ratio_int8"] >= BYTES_GATE_INT8
                 and out["ratio_bf16"] >= BYTES_GATE_BF16)
    return out


def measure_parity(preds: dict, feature_dim: int) -> dict:
    """Fused-path serving outputs vs the f32 reference on a held-out
    series (NOT the quantize-time probe), checked against each mode's
    stored envelope budget — the product contract under test."""
    rng = np.random.default_rng(7)
    traffic = rng.random((T, feature_dim)).astype(np.float32)
    ref = np.asarray(preds["off"].predict_series(traffic), np.float64)
    out = {"modes": {}}
    ok = True
    for mode in ("int8", "bf16"):
        pred = preds[mode]
        got = np.asarray(pred.predict_series(traffic), np.float64)
        diff = float(np.max(np.abs(got - ref)))
        budget = max(pred.parity_envelope["budget"].values())
        measured = max(pred.parity_envelope["measured"].values())
        within = diff <= budget
        ok = ok and within
        out["modes"][mode] = {
            "serving_max_abs_diff": diff,
            "envelope_measured_max": measured,
            "envelope_budget_max": budget,
            "within_envelope": within,
            "cells": len(pred.parity_envelope["budget"]),
        }
    out["ok"] = ok
    return out


def measure_compiles(preds: dict, feature_dim: int) -> dict:
    """Executable-count flatness: identical across modes after the same
    warmup, and zero added by a second (timed) serving pass."""
    rng = np.random.default_rng(11)
    traffic = rng.random((T, feature_dim)).astype(np.float32)
    for p in preds.values():                     # identical warmup
        p.predict_series(traffic)
    before = {m: p.jit_cache_size() for m, p in preds.items()}
    for p in preds.values():
        p.predict_series(traffic)
        p.predict_series(traffic[: T // 2])      # second rung reuse
    after = {m: p.jit_cache_size() for m, p in preds.items()}
    flat = len(set(before.values())) == 1
    # the half-length series pages through the SAME rung-8 ladder, so
    # the second pass must add nothing at any mode
    frozen = all(after[m] == before[m] for m in preds)
    return {"after_warmup": before, "after_timed_pass": after,
            "flat_across_modes": flat, "zero_post_warmup": frozen,
            "ok": flat and frozen}


def measure_coldstart(preds: dict, reps: int, quick: bool) -> dict:
    """Tenant-swap transfer: device_put the serving weight tree.  Bytes
    are the exact claim; the CPU timing is a collapse guard only (see
    module docstring footnote)."""
    import jax

    from deeprest_tpu.ops import quantize as quant_ops

    def put_once(tree) -> float:
        t0 = time.perf_counter()
        on_dev = jax.device_put(tree)
        jax.tree_util.tree_map(
            lambda x: x.block_until_ready() if hasattr(
                x, "block_until_ready") else x, on_dev)
        return time.perf_counter() - t0

    out = {"modes": {}}
    for mode, pred in preds.items():
        host_tree = jax.tree_util.tree_map(np.asarray, pred.params)
        put_once(host_tree)                      # warm dispatch path
        best = min(put_once(host_tree) for _ in range(reps))
        out["modes"][mode] = {
            "weight_bytes": quant_ops.weight_bytes(pred.params),
            "device_put_ms": round(best * 1e3, 3),
        }
    ratio = (out["modes"]["off"]["device_put_ms"]
             / max(out["modes"]["int8"]["device_put_ms"], 1e-9))
    out["int8_speedup"] = round(ratio, 2)
    gate = COLDSTART_COLLAPSE_QUICK if quick else COLDSTART_COLLAPSE_FULL
    out["ok"] = ratio >= gate
    out["footnote"] = (
        "CPU backend: per-leaf dispatch overhead dominates megabyte "
        "memcpys, so wall-clock tracks the 3.9x byte win only loosely "
        "here; the byte ratio is what a host->HBM path moves (not "
        "measured on the chip)")
    return out


def measure_throughput(preds: dict, feature_dim: int,
                       reps: int) -> dict:
    """Fused serving windows/sec per mode — reported, NOT claimed: on
    CPU dequant adds FLOPs per dispatch; the win is TPU bandwidth."""
    rng = np.random.default_rng(13)
    traffic = rng.random((T, feature_dim)).astype(np.float32)
    out = {"modes": {}}
    for mode, pred in preds.items():
        pred.predict_series(traffic)             # warm
        t0 = time.perf_counter()
        for _ in range(reps):
            pred.predict_series(traffic)
        wall = time.perf_counter() - t0
        windows = (T - pred.window_size + 1) * reps
        out["modes"][mode] = {
            "windows_per_sec": round(windows / wall, 1)}
    ratio = (out["modes"]["int8"]["windows_per_sec"]
             / max(out["modes"]["off"]["windows_per_sec"], 1e-9))
    out["int8_vs_f32"] = round(ratio, 2)
    out["ok"] = ratio >= THROUGHPUT_COLLAPSE
    out["footnote"] = (
        "honest-CPU: the dequant multiply ADDS work per dispatch on "
        "CPU — a serving speedup on an accelerator is not measured on "
        "the chip and is never claimed from this number")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None)
    ap.add_argument("--quick", action="store_true",
                    help="tier-1 smoke: small shapes, fewer reps")
    ap.add_argument("--headline", action="store_true",
                    help="print one JSON line for bench.py (schema v13)")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    _, make, w, f = _build_world(args.quick)
    preds = {m: make(m) for m in ("off", "int8", "bf16")}
    nbytes = measure_bytes(preds)
    parity = measure_parity(preds, f)
    compiles = measure_compiles(preds, f)
    coldstart = measure_coldstart(preds, reps=5 if args.quick else 30,
                                  quick=args.quick)
    throughput = measure_throughput(preds, f,
                                    reps=3 if args.quick else 20)

    record = {
        "bench": "quant_bench",
        "mode": "quick" if args.quick else "full",
        "shapes": {"window": w, "feature_dim": f,
                   "hidden": preds["off"].model_config.hidden_size},
        "bytes": nbytes,
        "parity": parity,
        "compiles": compiles,
        "coldstart": coldstart,
        "throughput": throughput,
        "bytes_gate_int8": BYTES_GATE_INT8,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    if args.out:
        with open(args.out, "w", encoding="utf-8") as fh:
            json.dump(record, fh, indent=2, sort_keys=True)
            fh.write("\n")
    if args.headline:
        print(json.dumps({
            "quant_weight_bytes": nbytes["weight_bytes"]["int8"],
            "quant_parity_max":
                parity["modes"]["int8"]["envelope_measured_max"],
        }))
    else:
        print(json.dumps(record, indent=2, sort_keys=True))

    failures = []
    if not nbytes["ok"]:
        failures.append(
            f"bytes ratio int8 {nbytes['ratio_int8']}x < "
            f"{BYTES_GATE_INT8}x (bf16 {nbytes['ratio_bf16']}x)")
    if not parity["ok"]:
        failures.append(f"serving drift outside envelope: "
                        f"{parity['modes']}")
    if not compiles["ok"]:
        failures.append(f"executable counts not flat/frozen: {compiles}")
    if not coldstart["ok"]:
        failures.append(
            f"coldstart collapse: int8 {coldstart['int8_speedup']}x")
    if not throughput["ok"]:
        failures.append(
            f"throughput collapse: int8 {throughput['int8_vs_f32']}x")
    if failures:
        print(f"quant_bench GATES FAILED: {failures}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
