"""Pallas GRU kernel tuning experiments (diagnostic).

Times recurrence variants at the flagship shape with honest readback sync,
to pick the production configuration of ops/pallas_gru.py:

- fused bidirectional (both directions stacked on the expert axis, ONE
  kernel invocation, the backward direction's proj pre-flipped — the
  production path in rounds 4-10, REVERTED to two calls in round 11:
  ops/gru.py BIDIR_FUSED) vs two sequential single-direction calls;
- E_BLK (experts per grid program) × T_BLK (time steps per program) sweep
  at the fused E=80 stacking;
- f32 vs bf16 recurrence dots (weights+hidden cast to bf16 for the MXU,
  f32 accumulate) — f32 matmul peak is ~1/4 of bf16 on v5e;
- forward-only AND fwd+bwd (custom-VJP) timings: the backward kernel does
  3 dots/step vs the forward's 1, so a tuning decision made on forward
  times alone could pessimize training;
- ``--coalesce`` (round 11): the window-coalescing G sweep — G ∈
  {1, 2, 4, 8} independent window batches folded into the B (row) axis of
  ONE recurrence, × LOOP_ORDER × STASH_GATES at production bf16 on TPU —
  plus the VMEM block-plan fit table at the fatter row counts.

On a TPU the full on-chip sweep runs (not measured on the chip yet).  On
the CPU backend a reduced, honestly-labeled variant runs instead: the
coalescing G sweep on the lax.scan recurrence (the production CPU path —
real compute, the committed evidence for the coalesced row-fattening win)
and a fused-vs-unfused bidirectional check through the INTERPRET-mode
pallas kernel (numerics-grade only: interpret timings measure the
interpreter, not the MXU — the fused-vs-unfused DECISION cites the banked
on-chip round-3/4 headline numbers, see decision_basis in the output).

Run: python benchmarks/kernel_tuning.py [--out results.json] [--coalesce]
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

B, T, F, E, H = 32, 60, 512, 40, 128
E2 = 2 * E                      # fused bidirectional stacking
COALESCE_GS = (1, 2, 4, 8)      # window-coalescing factors (G·B rows)


def block_plan_table():
    """VMEM block-plan fit at the coalesced row counts — the round-11
    re-validation of the footprint model at fat B, platform-independent
    (no compilation; ops/pallas_gru.block_plan)."""
    import jax.numpy as jnp

    from deeprest_tpu.ops import pallas_gru

    table = {}
    for g in COALESCE_GS:
        for dtype, training in ((jnp.bfloat16, True), (jnp.bfloat16, False),
                                (jnp.float32, True)):
            plan = pallas_gru.block_plan(E, T, B * g, H, dtype=dtype,
                                         training=training)
            key = (f"G{g}_{'bf16' if dtype == jnp.bfloat16 else 'f32'}"
                   f"_{'train' if training else 'infer'}")
            table[key] = {
                "rows": B * g, "e_blk": plan["e_blk"],
                "t_blk": plan["t_blk"],
                "block_mib": round(plan["block_bytes"] / 2 ** 20, 2),
                "fits_vmem": plan["fits"],
            }
    return table


def coalesce_scan_sweep(iters: int = 8):
    """The recurrence-dominated coalescing sweep on the lax.scan backend
    (the production CPU recurrence — real compiled compute, honest
    readback sync): G independent B=32 window batches as ONE G·B-row
    fwd+bwd vs G sequential thin calls.  F is small so the sweep times the
    recurrence, not the hoisted projection (flagship FLOPs are ~80%
    projection; the MXU-occupancy problem under attack lives in the
    per-step [B,H]x[H,3H] dot)."""
    import jax
    import jax.numpy as jnp

    from deeprest_tpu.ops.gru import gru, gru_coalesced, init_gru_params

    f_small = 64
    rng = np.random.default_rng(0)
    params = init_gru_params(jax.random.PRNGKey(0), E, f_small, H)
    out = {"shape": {"B": B, "T": T, "F": f_small, "E": E, "H": H},
           "iters": iters, "backend": "scan"}

    def bwd_ready(fn):
        jitted = jax.jit(jax.value_and_grad(
            lambda p, xx: jnp.sum(fn(p, xx) ** 2)))

        def run(xx):
            loss, grads = jitted(params, xx)
            # honest sync: read back a grad element (the last value the
            # backward produces), not just the loss
            return float(jnp.ravel(jax.tree.leaves(grads)[0])[0])

        return run

    base_rate = None
    for g in COALESCE_GS:
        x = jnp.asarray(rng.standard_normal((g, B, T, f_small)), jnp.float32)
        if g == 1:
            run = bwd_ready(lambda p, xx: gru(p, xx[0], backend="scan"))
        else:
            run = bwd_ready(lambda p, xx: gru_coalesced(p, xx,
                                                        backend="scan"))
        run(x)                                   # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            v = run(x)
        elapsed = time.perf_counter() - t0
        assert np.isfinite(v)
        rate = iters * g / elapsed               # microbatch steps / s
        entry = {"microbatch_steps_per_sec": round(rate, 3),
                 "recurrence_rows": g * B}
        if g == 1:
            base_rate = rate
        else:
            entry["speedup_vs_g1"] = round(rate / base_rate, 3)
        out[f"G{g}"] = entry
        print(f"coalesce G{g}", entry, flush=True)
    return out


def make_fwd_call(e_blk_target: int, t_blk: int, bf16_dot: bool = False):
    """A standalone forward-recurrence pallas_call with the given blocking,
    mirroring ops/pallas_gru._fwd_call (time-OUTER, expert-INNER loop)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from deeprest_tpu.ops import pallas_gru

    def kernel(proj_ref, w_ref, b_ref, h0_ref, out_ref, h_scr):
        t = pl.program_id(1)

        @pl.when(t == 0)
        def _init():
            h_scr[...] = h0_ref[...].astype(jnp.float32)

        n_e = proj_ref.shape[0]
        dot_t = jnp.bfloat16 if bf16_dot else jnp.float32
        hs = [h_scr[i] for i in range(n_e)]
        ws = [w_ref[i].astype(dot_t) for i in range(n_e)]
        bs = [b_ref[i].astype(jnp.float32) for i in range(n_e)]
        for tt in range(t_blk):
            for i in range(n_e):
                gates_h = (
                    jax.lax.dot_general(hs[i].astype(dot_t), ws[i],
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                    + bs[i]
                )
                xproj = proj_ref[i, tt].astype(jnp.float32)
                xr, xz, xn = jnp.split(xproj, 3, axis=-1)
                hr, hz, hn = jnp.split(gates_h, 3, axis=-1)
                r = jax.nn.sigmoid(xr + hr)
                z = jax.nn.sigmoid(xz + hz)
                n = jnp.tanh(xn + r * hn)
                hs[i] = (1.0 - z) * n + z * hs[i]
                out_ref[i, tt] = hs[i].astype(out_ref.dtype)
        for i in range(n_e):
            h_scr[i] = hs[i]

    def call(proj, w_hh, b_hh, h0):
        e, t, b, g3 = proj.shape
        h = g3 // 3
        assert t % t_blk == 0, (t, t_blk)
        eb = e // e_blk_target if e % e_blk_target == 0 else 1
        e_blk = e // eb
        grid = (eb, t // t_blk)
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[
                pl.BlockSpec((e_blk, t_blk, b, g3), lambda i, j: (i, j, 0, 0)),
                pl.BlockSpec((e_blk, h, g3), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((e_blk, g3), lambda i, j: (i, 0)),
                pl.BlockSpec((e_blk, b, h), lambda i, j: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((e_blk, t_blk, b, h),
                                   lambda i, j: (i, j, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((e, t, b, h), jnp.float32),
            scratch_shapes=[pltpu.VMEM((e_blk, b, h), jnp.float32)],
            compiler_params=pallas_gru.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
            ),
        )(proj, w_hh, b_hh, h0)

    return call


def bidir_interpret_check():
    """Fused-vs-unfused bidirectional through the INTERPRET-mode kernel at
    a reduced shape: proves both paths stay numerically exact against the
    scan spec and records wall times for the record.  Interpret timings
    measure the pallas interpreter, not the MXU — they CANNOT settle the
    fused-vs-unfused question; the decision field cites the banked on-chip
    evidence (PERF.md 'Measured so far')."""
    import jax
    import jax.numpy as jnp

    import importlib

    # deeprest_tpu.ops re-exports the gru FUNCTION, shadowing the module
    # on attribute access — importlib reaches the module unambiguously.
    gru_mod = importlib.import_module("deeprest_tpu.ops.gru")
    from deeprest_tpu.ops.gru import bidirectional_gru, init_gru_params

    e, b, t, f, h = 8, 16, 12, 32, 128
    kf, kb, kx = jax.random.split(jax.random.PRNGKey(0), 3)
    fwd = init_gru_params(kf, e, f, h)
    bwd = init_gru_params(kb, e, f, h)
    x = jax.random.normal(kx, (b, t, f), jnp.float32)
    ref = np.asarray(bidirectional_gru(fwd, bwd, x, backend="scan"))

    out = {"shape": {"E": e, "B": b, "T": t, "F": f, "H": h}}
    default = gru_mod.BIDIR_FUSED
    try:
        for fused in (False, True):
            gru_mod.BIDIR_FUSED = fused
            fn = jax.jit(lambda xx: bidirectional_gru(
                fwd, bwd, xx, backend="pallas_interpret"))
            got = np.asarray(fn(x))              # compile + readback
            t0 = time.perf_counter()
            for _ in range(3):
                got = np.asarray(fn(x))
            ms = (time.perf_counter() - t0) / 3 * 1e3
            key = "fused_bidir" if fused else "unfused_bidir"
            out[key] = {
                "interpret_ms": round(ms, 2),
                "max_err_vs_scan": float(np.max(np.abs(got - ref))),
            }
            print(key, out[key], flush=True)
    finally:
        gru_mod.BIDIR_FUSED = default
    return out


# The round-11 fused-vs-unfused bidirectional DECISION and its basis —
# recorded in every result JSON this script writes so the artifact is
# self-describing (satellite of ISSUE 6; PERF.md 'Round 11').
BIDIR_DECISION = {
    "decision": "unfused (two gru_recurrence calls per layer) is the "
                "production default; ops/gru.py BIDIR_FUSED=0 executes "
                "the revert PERF.md committed to",
    "decision_basis": "two July 2026 builder runs on older kernels and "
                      "another toolchain, not reproduced: unfused 122.0 "
                      "steps/s vs fused 117.2 steps/s at production "
                      "bf16; direction fusion "
                      "never demonstrated a win, and the round-11 "
                      "window coalescing attacks the same per-call "
                      "overhead with G x the row occupancy instead",
    "reopen_with": "DEEPREST_GRU_BIDIR_FUSED=1 + this script on-chip",
}


def cpu_main(out_path, coalesce: bool):
    """The CPU-feasible subset, honestly labeled (see module docstring)."""
    results = {
        "platform": "cpu",
        "note": "CPU run: scan-backend coalescing sweep is real compiled "
                "compute; interpret-mode pallas numbers are "
                "numerics-grade only (they time the interpreter, not the "
                "MXU)",
        "bidir": {**bidir_interpret_check(), **BIDIR_DECISION},
        "vmem_block_plan": block_plan_table(),
    }
    if coalesce:
        results["coalesce_scan"] = coalesce_scan_sweep()
    print(json.dumps(results, indent=2, default=str))
    if out_path:
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(results, f, indent=2, default=str)


def main():
    # Parse argv BEFORE the multi-minute sweep so a malformed --out fails
    # at startup, not after all the work is done.
    out_path = None
    if "--out" in sys.argv:
        i = sys.argv.index("--out")
        if i + 1 >= len(sys.argv):
            sys.exit("--out requires a path argument")
        out_path = sys.argv[i + 1]
    coalesce = "--coalesce" in sys.argv

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        cpu_main(out_path, coalesce)
        return

    from deeprest_tpu.ops import pallas_gru

    rng = np.random.default_rng(0)
    results = {"shape": {"B": B, "T": T, "E": E, "H": H, "fused_E": E2}}

    def measure(fn, args, iters=50):
        # Sync by summing the first output leaf: works for array outputs
        # AND the 0-d loss of value_and_grad (indexing [..., 0] would not).
        out = fn(*args)
        _ = float(jnp.sum(jax.tree.leaves(out)[0]))  # compile + readback sync
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        _ = float(jnp.sum(jax.tree.leaves(out)[0]))
        return (time.perf_counter() - t0) / iters * 1e3

    t_padded = pallas_gru.pad_time(T)

    def mk(e):
        proj = jnp.asarray(rng.standard_normal((e, t_padded, B, 3 * H)),
                           jnp.float32)
        w_hh = jnp.asarray(rng.standard_normal((e, H, 3 * H)) * 0.05,
                           jnp.float32)
        b_hh = jnp.asarray(rng.standard_normal((e, 3 * H)) * 0.05, jnp.float32)
        h0 = jnp.zeros((e, B, H), jnp.float32)
        return proj, w_hh, b_hh, h0

    args40, args80 = mk(E), mk(E2)
    # The flagship's actual dtypes (ops/gru.py _pad_weights): proj and
    # W_hh bf16, b_hh and h0 f32 — selects the bf16-dot kernel path.
    def to_bf16(a):
        proj, w, b, h0 = a
        return (proj.astype(jnp.bfloat16), w.astype(jnp.bfloat16), b, h0)

    def record(key, fn, a):
        # One config OOMing scoped VMEM must not kill the sweep (the f32
        # fwd+bwd at E_BLK=8 did exactly that before the footprint-aware
        # block chooser landed in ops/pallas_gru.py).
        try:
            results[key] = round(measure(fn, a), 3)
        except Exception as exc:
            results[key] = {"error": str(exc)[:160]}
        print(key, results[key], flush=True)

    # Production path: forward and fwd+bwd through the custom VJP.
    prod = jax.jit(functools.partial(pallas_gru.gru_recurrence,
                                     interpret=False))
    try:
        ref80 = np.asarray(prod(*args80))
    except Exception as exc:        # sweep still records timings without it
        results["ref80_error"] = str(exc)[:160]
        ref80 = None
    record("prod_fwd_E40_ms", prod, args40)
    record("prod_fwd_fusedE80_ms", prod, args80)
    record("prod_fwd_fusedE80_bf16_ms", prod, to_bf16(args80))

    train_like = jax.jit(jax.value_and_grad(
        lambda p, w, b, h: jnp.sum(
            pallas_gru.gru_recurrence(p, w, b, h, False) ** 2),
        argnums=(0, 1, 2, 3)))
    record("prod_fwdbwd_E40_ms", train_like, args40)
    record("prod_fwdbwd_E40_bf16_ms", train_like, to_bf16(args40))
    record("prod_fwdbwd_fusedE80_ms", train_like, args80)
    record("prod_fwdbwd_fusedE80_bf16_ms", train_like, to_bf16(args80))
    # two sequential E=40 calls ≈ the old unfused bidirectional cost
    # (the bf16 pair is the comparison that decides whether direction
    # fusion actually pays at the flagship dtype)
    for suffix in ("", "_bf16"):
        v = results.get(f"prod_fwdbwd_E40{suffix}_ms")
        if isinstance(v, float):
            results[f"unfused_equiv_fwdbwd{suffix}_ms"] = round(2 * v, 3)
    print(json.dumps(results, indent=2), flush=True)

    # Round-5 knob sweep on the training path at the production dtype:
    # STASH_GATES (backward recompute dot vs extra [E,T,B,3H] stream) ×
    # LOOP_ORDER (expert-inner MXU pipelining vs time-inner weight reuse,
    # applied to BOTH kernels).  Forward-only timings ride along because
    # the knobs move different fractions of fwd vs bwd work.  The flags
    # are read at trace time, so each config gets a fresh jit; restore is
    # try/finally so an interrupt cannot leak a non-default config into
    # later sweep phases.
    default_stash, default_order = pallas_gru.STASH_GATES, pallas_gru.LOOP_ORDER
    try:
        for stash, order in itertools.product(
                (True, False), ("expert_inner", "time_inner")):
            pallas_gru.STASH_GATES = stash
            pallas_gru.LOOP_ORDER = order
            fn = jax.jit(jax.value_and_grad(
                lambda p, w, b, h: jnp.sum(
                    pallas_gru.gru_recurrence(p, w, b, h, False) ** 2),
                argnums=(0, 1, 2, 3)))
            record(f"fwdbwd_bf16_stash{int(stash)}_{order}_ms", fn,
                   to_bf16(args80))
            if stash:   # forward has no stash dimension; time only orders
                fwd = jax.jit(functools.partial(pallas_gru.gru_recurrence,
                                                interpret=False))
                record(f"fwd_bf16_{order}_ms", fwd, to_bf16(args80))
    finally:
        pallas_gru.STASH_GATES = default_stash
        pallas_gru.LOOP_ORDER = default_order

    # Blocking sweep at the fused stacking.  E candidates are the pallas-
    # tileable expert blocks (multiples of 8 dividing E2 — a 20-wide block
    # fails lowering: the expert axis is the sublane of the 2-D f32 bias
    # block); bf16 rows use bf16 proj/W inputs so the timed DMA stream
    # matches the production bf16 path, not double it.
    for e_blk, t_blk, bf16 in itertools.product(
            (8, 16, 40), (6, 10, 12), (False, True)):
        if E2 % e_blk or t_padded % t_blk:
            continue
        key = f"E{e_blk}_T{t_blk}_{'bf16' if bf16 else 'f32'}"
        sweep_args = to_bf16(args80) if bf16 else args80
        try:
            call = jax.jit(make_fwd_call(e_blk, t_blk, bf16_dot=bf16))
            ms = measure(call, sweep_args)
            entry = {"ms": round(ms, 3)}
            if ref80 is not None:
                entry["max_err"] = float(np.max(np.abs(
                    np.asarray(call(*sweep_args)) - ref80)))
            results[key] = entry
        except Exception as exc:
            results[key] = {"error": str(exc)[:160]}
        print(key, results[key], flush=True)

    results["bidir_decision"] = BIDIR_DECISION
    results["vmem_block_plan"] = block_plan_table()

    if coalesce:
        # Window-coalescing sweep at production bf16 (round 11): G window
        # batches folded into the B (row) axis of ONE gru_recurrence,
        # fwd+bwd through the custom VJP, × LOOP_ORDER × STASH_GATES.
        # E=40 matches the post-revert production call (one direction per
        # invocation).  Rows are G·32; the block plan above predicts
        # which configs fit scoped VMEM (G=8 training does not — record()
        # keeps an OOM from killing the sweep).  Compare per-microbatch:
        # ms(G)/G vs ms(G=1).
        def mk_rows(rows):
            proj = jnp.asarray(rng.standard_normal((E, t_padded, rows, 3 * H)),
                               jnp.float32)
            w_hh = jnp.asarray(rng.standard_normal((E, H, 3 * H)) * 0.05,
                               jnp.float32)
            b_hh = jnp.asarray(rng.standard_normal((E, 3 * H)) * 0.05,
                               jnp.float32)
            h0 = jnp.zeros((E, rows, H), jnp.float32)
            return proj, w_hh, b_hh, h0

        default_stash = pallas_gru.STASH_GATES
        default_order = pallas_gru.LOOP_ORDER
        try:
            for g in (1, 2, 4, 8):
                args_g = to_bf16(mk_rows(B * g))
                for stash, order in itertools.product(
                        (True, False), ("expert_inner", "time_inner")):
                    pallas_gru.STASH_GATES = stash
                    pallas_gru.LOOP_ORDER = order
                    fn = jax.jit(jax.value_and_grad(
                        lambda p, w, b, h: jnp.sum(
                            pallas_gru.gru_recurrence(p, w, b, h, False) ** 2),
                        argnums=(0, 1, 2, 3)))
                    record(f"coalesce_G{g}_rows{B * g}_stash{int(stash)}"
                           f"_{order}_bf16_ms", fn, args_g)
        finally:
            pallas_gru.STASH_GATES = default_stash
            pallas_gru.LOOP_ORDER = default_order
        # per-microbatch speedups for the default knobs, where measured
        base = results.get("coalesce_G1_rows32_stash1_expert_inner_ms")
        if isinstance(base, float):
            for g in (2, 4, 8):
                v = results.get(f"coalesce_G{g}_rows{B * g}_stash1"
                                "_expert_inner_ms")
                if isinstance(v, float):
                    results[f"coalesce_G{g}_speedup_per_microbatch"] = round(
                        g * base / v, 3)

    print(json.dumps(results, indent=2, default=str))
    if out_path:
        with open(out_path, "w", encoding="utf-8") as f:
            json.dump(results, f, indent=2, default=str)


if __name__ == "__main__":
    main()
