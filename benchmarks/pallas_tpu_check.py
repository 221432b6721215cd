#!/usr/bin/env python
"""Prove the pallas GRU kernel on real hardware: numerics vs scan + speedup.

Round-1 verdict: the kernel (including its hand-written VJP) had only ever
executed in interpret mode on CPU.  This script runs both backends of
ops/gru.py on the live backend, asserts forward and gradient agreement, and
records a kernel-vs-scan step-time comparison at the flagship shape.  It is
invoked by bench.py whenever the measured platform is an accelerator, and
writes its findings to --out as JSON.
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

B, T, F, E, H = 32, 60, 512, 40, 128
FWD_TOL = 2e-5
GRAD_TOL = 2e-4
TIMING_STEPS = 50   # sized so the end-of-loop readback sync is <3% of a trial


def _max_err(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32))))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--out", required=True)
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp

    from deeprest_tpu.ops.gru import bidirectional_gru, init_gru_params

    platform = jax.devices()[0].platform
    key = jax.random.PRNGKey(0)
    kf, kb, kx = jax.random.split(key, 3)
    fwd = init_gru_params(kf, E, F, H)
    bwd = init_gru_params(kb, E, F, H)
    x = jax.random.uniform(kx, (B, T, F), jnp.float32)

    def loss_fn(backend):
        def fn(fwd, bwd, x):
            out = bidirectional_gru(fwd, bwd, x, backend=backend)
            return jnp.sum(out * out), out
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1), has_aux=True))

    scan_fn = loss_fn("scan")
    pallas_fn = loss_fn("pallas")

    (scan_loss, scan_out), scan_grads = scan_fn(fwd, bwd, x)
    (pallas_loss, pallas_out), pallas_grads = pallas_fn(fwd, bwd, x)
    jax.block_until_ready((scan_out, pallas_out))

    fwd_err = _max_err(scan_out, pallas_out)
    # Weight grads accumulate over B*T terms, so compare relative to scale.
    grad_err = max(
        _max_err(sg, pg) / (float(np.max(np.abs(np.asarray(sg)))) + 1.0)
        for st, pt in zip(scan_grads, pallas_grads)
        for sg, pg in zip(st, pt)
    )

    def time_fn(fn):
        # Sync via host readback of the loss scalar.
        fn(fwd, bwd, x)  # compile
        (l, o), g = fn(fwd, bwd, x)
        float(l)
        t0 = time.perf_counter()
        for _ in range(TIMING_STEPS):
            (l, o), g = fn(fwd, bwd, x)
        float(l)
        return (time.perf_counter() - t0) / TIMING_STEPS * 1e3

    scan_ms = time_fn(scan_fn)
    pallas_ms = time_fn(pallas_fn)

    ok = fwd_err < FWD_TOL and grad_err < GRAD_TOL
    result = {
        "platform": platform,
        "shape": {"B": B, "T": T, "F": F, "E": E, "H": H},
        "fwd_max_abs_err": fwd_err,
        "grad_max_abs_err": grad_err,
        "numerics_ok": ok,
        "scan_fwd_bwd_ms": round(scan_ms, 3),
        "pallas_fwd_bwd_ms": round(pallas_ms, 3),
        "pallas_speedup_vs_scan": round(scan_ms / pallas_ms, 3) if pallas_ms else None,
    }
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(result, f, indent=2)
    print(json.dumps(result))
    if not ok:
        raise SystemExit(f"pallas numerics mismatch: fwd={fwd_err} grad={grad_err}")


if __name__ == "__main__":
    main()
