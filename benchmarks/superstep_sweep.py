"""Superstep fusion sweep: steps/s vs steps-per-superstep S.

Times the staged training path at S ∈ {1, 8, 32, epoch} with honest
readback sync, answering the sizing question behind
``TrainConfig.steps_per_superstep``: how much does fusing K per-step jit
dispatches into ceil(K/S) ``lax.scan`` supersteps buy?  S=1 is the
per-step indexed dispatch loop (one jit call + one [B] index feed per
step — the pre-superstep production path); larger S amortizes Python
dispatch, per-step feeds, and sync opportunities across the scan.

Run: python benchmarks/superstep_sweep.py [--out results.json] [--flagship]

Default shape is CPU-tractable (the CPU backend pays XLA's scalar-loop
gather on the staged path — see TrainConfig.device_data — so the sweep
isolates DISPATCH amortization, which is backend-independent);
``--flagship`` switches to the B32 T60 F512 E40 H128 bf16 headline shape
for on-chip runs (not measured on the chip yet).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

EPOCH_STEPS = 64                 # K: dispatches per "epoch" at S=1
SWEEP = (1, 8, 32, "epoch")

SMALL_SHAPE = dict(B=32, T=60, F=256, E=8, H=64, dtype="float32")
FLAGSHIP_SHAPE = dict(B=32, T=60, F=512, E=40, H=128, dtype="bfloat16")


def main() -> None:
    out_path = None
    if "--out" in sys.argv:
        i = sys.argv.index("--out")
        if i + 1 >= len(sys.argv):
            sys.exit("--out requires a path argument")
        out_path = sys.argv[i + 1]
    shape = FLAGSHIP_SHAPE if "--flagship" in sys.argv else SMALL_SHAPE

    import jax
    import jax.numpy as jnp

    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.train import Trainer

    B, T, F, E, H = (shape[k] for k in ("B", "T", "F", "E", "H"))
    cfg = Config(
        model=ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                          compute_dtype=shape["dtype"]),
        train=TrainConfig(batch_size=B, window_size=T),
    )
    trainer = Trainer(cfg, F, [f"m{i}" for i in range(E)])

    rng = np.random.default_rng(0)
    base_len = 512 + T
    xb = rng.random((base_len, F), np.float32)
    if shape["dtype"] == "bfloat16":
        import ml_dtypes

        xb = xb.astype(ml_dtypes.bfloat16)
    x_base = jnp.asarray(xb)
    y_base = jnp.asarray(rng.random((base_len, E), np.float32))

    state = trainer.init_state(rng.random((1, T, F), np.float32))
    # Sync: a host readback of an updated-params element, as bench.py's
    # timed_trial does.
    sync_leaf = lambda s: float(jnp.ravel(jax.tree.leaves(s.params)[0])[0])

    def plan(k, s):
        c = -(-k // s)
        sp = np.zeros((c * s, B), np.int32)
        wp = np.zeros((c * s, B), np.float32)
        sp[:k] = rng.integers(0, base_len - T, size=(k, B))
        wp[:k] = 1.0
        return (jnp.asarray(sp.reshape(c, s, B)),
                jnp.asarray(wp.reshape(c, s, B)))

    dev = jax.devices()[0]
    results = {
        "schema_version": 1,
        "metric": "superstep_steps_per_sec by S",
        "platform": dev.platform,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "shape": shape,
        "epoch_steps": EPOCH_STEPS,
        "note": ("S=1 is the per-step indexed dispatch loop; S>1 runs "
                 "ceil(K/S) lax.scan supersteps over a device-resident "
                 "plan (zero-weight padded ragged tail), honest "
                 "readback-synced; all variants share one staged base "
                 "series and identical step math (bit-exact parity is "
                 "tested in tests/test_superstep.py)"),
        "results": {},
    }

    for s_cfg in SWEEP:
        s = EPOCH_STEPS if s_cfg == "epoch" else s_cfg
        key = "epoch" if s_cfg == "epoch" else f"S{s_cfg}"
        try:
            if s == 1:
                starts = rng.integers(0, base_len - T,
                                      size=(EPOCH_STEPS, B)).astype(np.int32)
                w = np.ones((B,), np.float32)
                state, _ = trainer._train_step_indexed(          # compile
                    state, x_base, y_base, jnp.asarray(starts[0]),
                    jnp.asarray(w))
                _ = sync_leaf(state)
                t0 = time.perf_counter()
                for i in range(EPOCH_STEPS):
                    state, _ = trainer._train_step_indexed(
                        state, x_base, y_base, jnp.asarray(starts[i]),
                        jnp.asarray(w))
                _ = sync_leaf(state)
            else:
                sp, wp = plan(EPOCH_STEPS, s)
                state, _ = trainer._superstep(state, x_base, y_base,
                                              sp, wp, 0)         # compile
                _ = sync_leaf(state)
                t0 = time.perf_counter()
                for c in range(sp.shape[0]):
                    state, _ = trainer._superstep(state, x_base, y_base,
                                                  sp, wp, c)
                _ = sync_leaf(state)
            sps = EPOCH_STEPS / (time.perf_counter() - t0)
            results["results"][key] = round(sps, 3)
        except Exception as exc:    # one failing config must not sink the sweep
            results["results"][key] = {"error": str(exc)[:200]}
        print(key, results["results"][key], flush=True)

    base = results["results"].get("S1")
    if isinstance(base, float) and base > 0:
        results["speedup_vs_per_step"] = {
            k: round(v / base, 3) for k, v in results["results"].items()
            if isinstance(v, float)
        }
    print(json.dumps(results, indent=2))
    if out_path:
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(results, f, indent=2)


if __name__ == "__main__":
    main()
