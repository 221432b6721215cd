#!/usr/bin/env python
"""Mesh-shape scaling sweep: measured multi-chip training (schema v7).

The 2×2×2 (data, expert, model) mesh has been CORRECT since the
MULTICHIP_r05 dryruns, but no scaling number was ever banked — bench.py
measured one chip (ROADMAP item 1).  This sweep trains the same
configuration across a list of mesh shapes and records honest-sync
steps/s per shape plus the scaling efficiency vs the single-device
baseline.

Two operating modes, SAME code path:

- **Virtual CPU mesh** (``--virtual``, what ``make bench-multichip`` and
  the committed ``MULTICHIP_r06.json`` run): 8 XLA host-platform devices
  carved out of one CPU.  This measures the PLUMBING — per-host sharded
  feeding, GSPMD collectives, rule-table shardings — with real numbers
  attached, but the 8 "devices" share one socket's cores, so
  ``scaling_efficiency`` is structurally ≤ 1/n_devices-ish and is NOT a
  hardware claim (the same honesty note as the round-11 CPU coalescing
  result).  What it proves: the sharded step runs, feeds, and syncs at
  every shape, and the relative shape-vs-shape ordering on one host.
- **Real accelerators** (no flag; not measured on the chip yet): the
  actual data×expert×model scaling curve, plus the flagship-shape
  aggregate MFU (``flagship_mfu``) against n_devices × the chip's public
  bf16 peak.

Measurement (the bench.py schema-v6 discipline, kept verbatim): every
timed trial structurally ends in a host readback of an element of the
UPDATED params before the clock stops, and a trial ledger asserts it —
dispatch rate is not throughput.

Output: one JSON object (also written to ``--out``) with per-shape
records and the headline keys ``mesh_shape`` / ``multichip_steps_per_sec``
/ ``scaling_efficiency`` / ``flagship_mfu``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Sweep order: single-device baseline first (it anchors the efficiency
# column), then pure DP, the flagship 2×2×2, and the two mixed shapes
# that isolate EP and TP scaling.
DEFAULT_SHAPES = ((1, 1, 1), (8, 1, 1), (2, 2, 2), (4, 2, 1), (2, 1, 4))

# Measurement sizes.  The virtual CPU mesh times 8-way collectives on one
# socket, so the quick tier keeps the model small enough that a full
# sweep lands inside the make-target time budget; the accelerator tier
# runs the flagship shape (BASELINE.json config 2).
QUICK = {"B": 32, "T": 16, "F": 256, "E": 8, "H": 64, "dtype": "float32",
         "warmup": 2, "steps": 10, "trials": 2}
FULL = {"B": 32, "T": 60, "F": 512, "E": 40, "H": 128, "dtype": "bfloat16",
        "warmup": 5, "steps": 50, "trials": 3}


def measure_shapes(shapes, sizes) -> list[dict]:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from deeprest_tpu.config import Config, MeshConfig, ModelConfig, TrainConfig
    from deeprest_tpu.parallel.distributed import feed_global_batch
    from deeprest_tpu.parallel.mesh import make_mesh
    from deeprest_tpu.train import Trainer

    B, T, F, E, H = (sizes[k] for k in ("B", "T", "F", "E", "H"))
    metric_names = [f"comp{i // 5}_res{i % 5}" for i in range(E)]
    rng = np.random.default_rng(0)
    x = rng.random((B, T, F), np.float32)
    y = rng.random((B, T, E), np.float32)
    w = np.ones((B,), np.float32)

    # Honest-sync ledger (bench.py schema-v6 contract): the ONLY way a
    # trial is timed ends in an updated-params readback.
    ledger = {"started": 0, "synced": 0}

    def timed_trial(run, state):
        ledger["started"] += 1
        t0 = time.perf_counter()
        state = run(state)
        v = float(jnp.ravel(jax.tree.leaves(state.params)[0])[0])
        elapsed = time.perf_counter() - t0
        if not np.isfinite(v):
            raise RuntimeError(f"non-finite params after timed trial ({v})")
        ledger["synced"] += 1
        return elapsed, state

    records = []
    for d, e, m in shapes:
        if d * e * m > len(jax.devices()):
            records.append({"mesh_shape": [d, e, m],
                            "error": f"needs {d * e * m} devices, "
                                     f"{len(jax.devices())} available"})
            continue
        cfg = Config(
            model=ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                              compute_dtype=sizes["dtype"]),
            train=TrainConfig(batch_size=B, window_size=T),
            mesh=MeshConfig(data=d, expert=e, model=m),
        )
        trainer = Trainer(cfg, F, metric_names)
        state = trainer.init_state(x)
        # The per-host sharded feed (the code path a pod runs): the batch
        # shards over the mesh's data axis, targets/weights alongside —
        # NOT a replicated jnp.asarray, which would measure DP without
        # its input pipeline.
        x_d = feed_global_batch(trainer.mesh, x)
        y_d = feed_global_batch(trainer.mesh, y)
        w_d = feed_global_batch(trainer.mesh, w)
        for _ in range(sizes["warmup"]):
            state, loss = trainer._train_step(state, x_d, y_d, w_d)
        lv = float(loss)
        if not np.isfinite(lv):
            raise RuntimeError(f"non-finite warmup loss {lv} at {d}x{e}x{m}")

        best = 0.0
        for _ in range(sizes["trials"]):
            def run_steps(st):
                for _ in range(sizes["steps"]):
                    st, _l = trainer._train_step(st, x_d, y_d, w_d)
                return st

            elapsed, state = timed_trial(run_steps, state)
            best = max(best, sizes["steps"] / elapsed)
        records.append({
            "mesh_shape": [d, e, m],
            "n_devices": d * e * m,
            "steps_per_sec": round(best, 3),
            "cache_size": trainer._train_step._cache_size(),
        })
        print(f"mesh {d}x{e}x{m}: {best:.3f} steps/s "
              f"(cache={records[-1]['cache_size']})", file=sys.stderr)
    expected = sum(sizes["trials"] for r in records if "error" not in r)
    assert ledger["started"] == ledger["synced"] == expected, (
        ledger, expected)
    return records


def measure_main(args) -> dict:
    import jax

    sizes = QUICK if args.quick else FULL
    shapes = tuple(tuple(s) for s in args.shapes) or DEFAULT_SHAPES
    records = measure_shapes(shapes, sizes)

    dev = jax.devices()[0]
    platform = dev.platform
    base = next((r for r in records
                 if r.get("mesh_shape") == [1, 1, 1] and "error" not in r),
                None)
    ok = [r for r in records if "error" not in r and r["n_devices"] > 1]
    best = max(ok, key=lambda r: r["steps_per_sec"]) if ok else None
    out = {
        "schema_version": 7,
        "metric": "multichip_train_steps_per_sec",
        "platform": platform,
        "device_kind": getattr(dev, "device_kind", platform),
        "n_devices": len(jax.devices()),
        "dtype": sizes["dtype"],
        "shape": {k: sizes[k] for k in ("B", "T", "F", "E", "H")},
        "sweep": records,
        "measurement_note": (
            "honest-sync: every timed trial ends in an updated-params host "
            "readback, asserted by the trial ledger (bench.py schema-v6 "
            "discipline)"),
    }
    if best is not None:
        out["mesh_shape"] = best["mesh_shape"]
        out["multichip_steps_per_sec"] = best["steps_per_sec"]
        if base is not None:
            # Strong scaling at a fixed global batch: perfect = n_devices×
            # the single-device rate.  On the virtual CPU mesh the
            # "devices" share one socket, so this is a plumbing proof, not
            # a hardware claim — the per-record column lets the reader see
            # every shape, not just the winner.
            for r in ok:
                r["scaling_efficiency"] = round(
                    r["steps_per_sec"]
                    / (base["steps_per_sec"] * r["n_devices"]), 4)
            out["scaling_efficiency"] = best["scaling_efficiency"]
            out["single_device_steps_per_sec"] = base["steps_per_sec"]
    if platform != "cpu" and best is not None:
        from bench import chip_peak_tflops, train_step_tflops

        step_tf = train_step_tflops(sizes["B"], sizes["T"], sizes["F"],
                                    sizes["E"], sizes["H"])
        peak = chip_peak_tflops(out["device_kind"])
        n = best["n_devices"]
        out["flagship_mfu"] = (
            round(100 * step_tf * best["steps_per_sec"] / (peak * n), 2))
    else:
        out["flagship_mfu"] = None
        out["flagship_mfu_note"] = (
            "aggregate MFU is an accelerator quantity (chip peak × "
            "n_devices); the virtual CPU mesh has no peak to anchor to, "
            "and the real value is not measured on the chip")
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="small model + short trials (the make "
                         "bench-multichip time budget)")
    ap.add_argument("--virtual", action="store_true",
                    help="force an 8-device virtual CPU mesh (sets "
                         "XLA_FLAGS host-platform device count; must be "
                         "given before jax initializes, i.e. always via "
                         "this CLI)")
    ap.add_argument("--shapes", default=None,
                    help="comma-separated D.E.M list, e.g. 1.1.1,2.2.2 "
                         "(default: the standard five-shape sweep)")
    ap.add_argument("--out", default=None, help="also write the JSON here")
    args = ap.parse_args()

    if args.virtual:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"
    args.shapes = ([tuple(int(v) for v in s.split("."))
                    for s in args.shapes.split(",")]
                   if args.shapes else [])
    for s in args.shapes:
        if len(s) != 3 or min(s) < 1:
            ap.error(f"bad shape {s}: want D.E.M with axes >= 1")

    result = measure_main(args)
    line = json.dumps(result)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2, sort_keys=True)
    print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
