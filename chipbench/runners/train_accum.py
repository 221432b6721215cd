"""Runner ``train_accum``: runner ``train`` for a configuration whose
optimizer update is made of G microbatches (``train.grad_accum_windows``),
as ``deeprest_tpu train --grad-accum-windows G`` runs it: the same
``Trainer``, ``init_state``, ``stage_dataset``, whole epochs through
``train_epoch`` on the staged corpus, the same ONE compiled superstep.  The
phases, the window, the peak and the evidence are those of
chipbench/runners/train.py (read its docstring first); this file says only
what differs.

- **The program** has ONE superstep, and at ``grad_accum_windows`` G > 1
  it is what ``train_epoch`` dispatches.  A program that keeps a second one
  for G > 1 (the parent of ISSUE 48) stops with a plain error before it has
  made a state: the check would drive another program than the window runs.
- **A step** is what the program's ``state.step`` counts: one MICROBATCH of
  ``batch_size`` windows, forward and backward, G of them to an update.  So
  ``train_steps_per_s`` reads in the unit of the cells beside it (steps of
  32 windows), ``attempted`` / ``failed`` stay per-step losses, and the
  window's log line gives updates a second and windows a second beside it.
- **The check** drives the window's own compiled superstep as there, with
  an UPDATE where that runner has a step: a plan of the epoch's shape whose
  first chunk holds one real group of G microbatches and whose second holds
  two, the third update a ragged group as an epoch's last is: G - 3 whole
  microbatches, one of B / 4 real windows, two padded.  At G = 8, B = 32:
  8 + 8 + 6 microbatches, 680 window starts that all differ.  Kept: the 22
  microbatch losses, the first ACCUMULATED gradient's norm per leaf (Adam's
  first moment after one update) and the norm of each leaf's change after
  the three; the program must have counted 22 steps and 3 updates.
- **The reference** is chipbench/reference/qrnn_accum_ref.py: the update
  written from one batch of G x B windows (float32, the weighted mean over
  the group's real windows, each microbatch under its own kept mask), made
  after the window with the program's state freed.
- **Evidence**: ``steps`` and ``kernel_work_per_step`` are BOTH by the
  microbatch (the recurrence kernels see B rows a call, four calls a
  microbatch, whatever G is).
- **The accepted per-layer metrics** without a ``workloads`` list apply by
  the runner's name, and their files list ``train``; this run is a train
  run and its evidence is that runner's, so once it is made ``run`` names
  the mix's runner ``train``, as runners/train_mesh.py does (which see;
  the line goes when the runners are folded, ROADMAP D19 / B14).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench.common import harness_only, judge, phase, traced
from chipbench.runners.train import (
    _reference_inputs, _trainer_surface, _windows, compare,
)
from chipbench.runners.train_warm import dataset, seeded_state

UPDATES_CHECKED = 3


def _one_superstep(trainer) -> None:
    """The check drives ``trainer._superstep``, so that has to be the
    program the epochs of the window dispatch.  A program that makes its
    accumulated updates in a second superstep (``_accum_superstep``, until
    ISSUE 48: whole-leaf gradients and Adam, the microbatches' gradients
    summed and not averaged) is not one this runner can hold to the
    configuration's guarantee; it stops here, at once."""
    if hasattr(trainer, "_accum_superstep"):
        raise RuntimeError(
            "this program's Trainer keeps a second superstep for "
            "grad_accum_windows > 1 (_accum_superstep); the train_accum "
            "runner checks and times the ONE superstep")


def check_layout(g: int, b: int) -> np.ndarray:
    """The real windows of each microbatch of the three checked updates,
    ``[3, G]``: two whole groups, and a ragged one as the last group of an
    epoch is (G - 3 whole microbatches, one of B / 4 windows, two padded)."""
    if g < 4 or b < 4:
        raise RuntimeError("the check's ragged group needs G >= 4, B >= 4")
    real = np.full((UPDATES_CHECKED, g), b, np.int64)
    real[2, g - 3] = b // 4
    real[2, g - 2:] = 0
    return real


def check_starts(raw, tcfg, seed, bundle=None):
    """The rows of the three checked updates: ``(starts [3, G, B], weights
    [3, G, B])``; the starts under a nonzero weight all differ and are
    drawn from the seed, the others are 0 (in bounds, in no sum)."""
    from deeprest_tpu.config import LEVEL_RESOURCES

    inputs = _reference_inputs(
        raw, {"window_size": tcfg.window_size,
              "train_split": tcfg.train_split}, set(LEVEL_RESOURCES))
    if bundle is not None and (
            inputs["split"] != bundle.num_train_windows
            or inputs["names"] != bundle.metric_names):
        raise RuntimeError("reference and program disagree on the split")
    g, b = tcfg.grad_accum_windows, tcfg.batch_size
    real = check_layout(g, b)
    weights = (np.arange(b)[None, None, :] < real[:, :, None]).astype(
        np.float32)
    pick = np.random.default_rng(seed + 1)
    starts = np.zeros(weights.shape, np.int32)
    starts[weights > 0] = pick.choice(inputs["split"], size=int(real.sum()),
                                      replace=False)
    return starts, weights


def check_plan(trainer, starts, weights, num_steps: int):
    """The host plan ``[C, S, B]`` of the epoch's shape for the three
    updates: the first group opens chunk 0, the other two open chunk 1."""
    g, b = starts.shape[1:]
    s_len = trainer._superstep_len(num_steps)
    chunks = -(-num_steps // s_len)
    if chunks < 2 or s_len < 2 * g:
        raise RuntimeError("the corpus is too short for the check's plan")
    plan_starts = np.zeros((chunks, s_len, b), np.int32)
    plan_weights = np.zeros((chunks, s_len, b), np.float32)
    for plan, rows in ((plan_starts, starts), (plan_weights, weights)):
        plan[0, :g] = rows[0]
        plan[1, :2 * g] = rows[1:].reshape(2 * g, b)
    return plan_starts, plan_weights


def check_groups(raw, tcfg, starts):
    """The reference's microbatches for those rows, normalised by its own
    rule from the raw corpus: a list a group of ``(x, y)`` a microbatch."""
    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.config import LEVEL_RESOURCES

    w = tcfg.window_size
    inputs = _reference_inputs(
        raw, {"window_size": w, "train_split": tcfg.train_split},
        set(LEVEL_RESOURCES))
    return [[(_windows(*inputs["x"], s, w, ref),
              _windows(*inputs["y"], s, w, ref)) for s in group]
            for group in starts]


def checked_updates(ctx, trainer, state, staged, starts, weights,
                    num_steps: int, key, dims):
    """Phase 3: the three updates through ``trainer._superstep``.  Returns
    (state, the numbers of the check)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.parallel.distributed import stage_plan

    g = starts.shape[1]
    real = weights.sum(axis=2) > 0                               # [3, G]
    plan = stage_plan(trainer.mesh,
                      *check_plan(trainer, starts, weights, num_steps))

    @jax.jit
    def first_grad_norms(opt_state):
        mu = opt_state[0].mu
        return {k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1 - ref.ADAM["b1"])
                for k, v in mu.items()}

    @jax.jit
    def delta_norms(params, key):
        start = ref.init_params(key, *dims)
        return ref.leaf_norms({k: params[k] - start[k] for k in params})

    superstep = trainer._superstep
    state, losses0 = superstep(state, *staged, *plan, 0)
    jax.block_until_ready(state)
    with harness_only(ctx, "reading the first gradient's norms"):
        grad_norm = {k: float(v) for k, v in
                     first_grad_norms(state.opt_state).items()}
    state, losses1 = superstep(state, *staged, *plan, 1)
    jax.block_until_ready(state)
    with harness_only(ctx, "reading the norms of the parameters' change"):
        delta = {k: float(v) for k, v in
                 delta_norms(state.params, key).items()}
    losses = np.concatenate([np.asarray(losses0)[:g],
                             np.asarray(losses1)[:2 * g]])
    program = {
        "losses": [float(x) for x in losses[real.reshape(-1)]],
        "grad_norm": grad_norm, "delta_norm": delta,
        "steps_counted": int(state.step),
        "updates_counted": int(state.opt_state[0].count),
    }
    return state, program


def _program(ctx, raw, mcfg, tcfg, key, dims):
    """Phases 2 to 5.  Everything the program holds on the device lives in
    this function, so that it is freed when it returns."""
    import jax
    import jax.numpy as jnp

    from chipbench import flops
    from deeprest_tpu.config import Config
    from deeprest_tpu.train.trainer import Trainer

    e, f, h, q = dims
    w, b, g = tcfg.window_size, tcfg.batch_size, tcfg.grad_accum_windows
    t = time.perf_counter()
    bundle = dataset(raw, tcfg, f)
    n_train = bundle.num_train_windows
    num_steps = -(-n_train // b)
    updates = -(-num_steps // g)
    starts, weights = check_starts(raw, tcfg, ctx.seed, bundle)
    t = phase(ctx, f"dataset ({n_train} train windows, {num_steps} "
                   f"microbatches of {b}, {updates} updates an epoch)", t)

    # 2. one trainer, one state; the seeded weights in its own weights' place
    trainer = Trainer(Config(model=mcfg, train=tcfg), bundle.feature_dim,
                      bundle.metric_names)
    _trainer_surface(trainer)
    _one_superstep(trainer)
    state = seeded_state(ctx, trainer, bundle, key, dims)
    t = phase(ctx, "trainer, init_state and the seeded weights", t)
    staged = trainer.stage_dataset(bundle)
    if staged is None:
        raise RuntimeError("the corpus was not staged on the device")
    jax.block_until_ready(staged)
    t = phase(ctx, "staged corpus", t)

    # 3. the first three updates, through the window's own superstep
    state, program = checked_updates(ctx, trainer, state, staged, starts,
                                     weights, num_steps, key, dims)
    t = phase(ctx, "first three updates through the window's superstep", t)

    # 4. warm-up: one whole epoch
    epoch_rng = np.random.default_rng(ctx.seed + 2)

    def epoch(st):
        with jax.profiler.TraceAnnotation("bench.train_epoch"):
            st, _ = trainer.train_epoch(st, bundle, epoch_rng, staged=staged)
        return st, trainer._last_epoch_losses

    state, losses = epoch(state)
    t = phase(ctx, f"warm-up epoch (loss {float(np.mean(losses)):.5f})", t)

    # 5. the window (set-up's garbage is collected before it, not in it)
    gc.collect()
    compiles0 = ctx.compiles.count
    attempted = failed = 0
    evidence = None
    t_start = time.perf_counter()
    setup_s = t_start - ctx.t0
    epoch_ends = [t_start]
    if not ctx.trace:
        while True:
            state, losses = epoch(state)
            attempted += len(losses)
            failed += int(np.sum(~np.isfinite(losses)))
            epoch_ends.append(time.perf_counter())
            elapsed = epoch_ends[-1] - t_start
            if elapsed >= ctx.seconds:
                break
    else:
        state, losses = epoch(state)                 # steady, untraced
        (state, losses), reduced = traced(lambda: epoch(state))
        elapsed = time.perf_counter() - t_start
        attempted, failed = len(losses), int(np.sum(~np.isfinite(losses)))
        # steps and work BOTH by the microbatch: the kernels see B rows
        work = flops.gru_kernel_work(
            b, w, e, h, training=True,
            act_bytes=jnp.dtype(mcfg.compute_dtype).itemsize)
        evidence = {"trace": reduced, "runner": "train", "steps": attempted,
                    "kernel_work_per_step": work}
    compiled = ctx.compiles.count - compiles0
    peak_bytes = ctx.memory_peak_bytes()

    rate = attempted / elapsed
    tflop = flops.train_step_tflops(b, w, f, e, h, q)
    ctx.log(f"window: {attempted} steps (microbatches of {b} windows) in "
            f"{elapsed:.3f} s: {rate:.3f} steps/s, "
            f"{rate * updates / num_steps:.3f} updates/s of {g} "
            f"microbatches, {rate * n_train / num_steps:.1f} windows/s, "
            f"{tflop:.4f} TFLOP a step"
            + ("" if ctx.trace or ctx.peaks is None else
               f", MFU {100 * rate * tflop / ctx.peaks['bf16_tflops']:.2f}% "
               f"of {ctx.peaks['bf16_tflops']} TFLOP/s"))
    if len(epoch_ends) > 1:
        ctx.log("seconds of each epoch of the window: "
                + " ".join(f"{x:.3f}" for x in np.diff(epoch_ends)))
    ctx.log(f"compile cache: {ctx.compiles.hits} hits, "
            f"{ctx.compiles.misses} misses in this process")
    phase(ctx, "window", t_start)
    values = {"setup_s": setup_s, "hbm_peak_gb": peak_bytes / 1e9}
    if not ctx.trace:
        values["train_steps_per_s"] = rate
    return {"program": program, "starts": starts, "weights": weights,
            "compiled": compiled, "attempted": attempted, "failed": failed,
            "values": values, "evidence": evidence,
            "memory_peak_bytes": peak_bytes}


def verdict(ctx, program: dict, reference: dict) -> bool:
    """`correct` for the three updates: the limits, and the two counts."""
    ctx.log("losses program", program["losses"], "reference",
            reference["losses"], "steps counted", program["steps_counted"],
            "updates counted", program["updates_counted"])
    correct = judge(ctx, compare(program, reference))
    for what, got, want in (
            ("steps", program["steps_counted"], reference["steps"]),
            ("updates", program["updates_counted"], reference["updates"])):
        if got != want:
            ctx.log(f"NOT CORRECT: the program counted {got} {what} for "
                    f"{want}")
            correct = False
    return correct


def run(ctx) -> dict:
    import jax

    from chipbench.reference import qrnn_accum_ref as accum_ref
    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.config import ModelConfig, TrainConfig

    t = time.perf_counter()
    model = dict(ctx.config["model"])
    model["quantiles"] = tuple(model["quantiles"])
    tcfg = TrainConfig(seed=ctx.key_seed(), **ctx.config.get("train", {}))
    mcfg = ModelConfig(**model)
    dims = (mcfg.num_metrics, mcfg.feature_dim, mcfg.hidden_size,
            len(mcfg.quantiles))
    key = jax.random.PRNGKey(ctx.key_seed())

    # 1. corpus
    raw = ctx.generator().generate(ctx.mix["params"], ctx.seed, model)
    phase(ctx, f"corpus ({len(raw['traffic'])} buckets)", t)

    out = _program(ctx, raw, mcfg, tcfg, key, dims)          # 2 to 5
    gc.collect()

    # 6. the reference's three updates, after the program's state is freed
    t = phase(ctx, "program freed", time.perf_counter())
    reference = accum_ref.train_three_updates(
        ref.init_params(key, *dims), check_groups(raw, tcfg, out["starts"]),
        out["weights"], ctx.key_seed(), mcfg.quantiles, mcfg.dropout_rate,
        "f32")
    correct = verdict(ctx, out.pop("program"), reference)
    phase(ctx, "reference and comparison (after the window; in neither "
               "setup_s nor the window)", t)
    if out["compiled"]:
        ctx.log(f"NOT CORRECT: {out['compiled']} compilations inside the "
                "window")
        correct = False
    if out["failed"]:
        ctx.log(f"NOT CORRECT: {out['failed']} of {out['attempted']} steps "
                "with a non-finite loss")
        correct = False
    # the accepted metrics that apply by runner name read this run as the
    # `train` run it is (see the docstring)
    ctx.mix["runner"] = "train"
    return {"correct": correct, **out}
