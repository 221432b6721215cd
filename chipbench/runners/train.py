"""Runner ``train``: whole epochs through ``Trainer.train_epoch`` on the staged
corpus, as ``deeprest_tpu train`` runs them.

Order of a run (each phase logged with its seconds, the device memory in
use and its peak so far):

1. corpus from the seed (the mix's generator), ``prepare_dataset``;
2. the program: one ``Trainer`` and one state from ``init_state``, then the
   seeded weights in the place of the trainer's own (the old leaves are
   dropped first, so no second copy is resident), the corpus staged;
3. its first three steps through the window's own compiled superstep and
   staged feed (a plan of the epoch's shape whose first chunk holds one
   real step and whose second holds two; padded steps are skipped by the
   program's own rule), on 3 x B rows that all differ: each step's loss,
   the first gradient's norm per leaf (from Adam's first moment after one
   step) and the norm of each leaf's change after the three are kept;
4. warm-up: one whole epoch, which dispatches every program of the window;
5. the window: whole epochs back to back until ``--seconds`` have passed,
   closed by the epoch's own ``block_until_ready`` and loss readback.  The
   same trainer and state as 3 and 4.  No compilation may happen in it;
6. the peak of device memory is read, the program's state is freed, and
   only then the plain reference (chipbench/reference/qrnn_ref.py) makes
   the same weights from the seed, normalises the same rows by its own
   rule and takes three Adam steps in float32; 3's numbers are compared
   with its.  So the peak is the program's own, and the reference's
   seconds are in neither ``setup_s`` nor the window.

What the harness itself puts on the device during 2 and 3 (the seeded
weights, the norms it reads) may not raise the peak: a run in which it
does fails, because ``hbm_peak_gb`` would no longer be the program's.

With ``--trace 1`` step 5 is one untraced epoch and one epoch under
``jax.profiler``, reduced by chipbench/trace_reduce.py.

``PROGRAM_SURFACE`` lists what of the program this runner calls; three of
the names are private to ``Trainer`` (the compiled superstep, its length
rule and the epoch's per-step losses), because the check has to drive the
window's own compiled call and nothing public reaches it.  A PR that
renames one gets a plain error that names it.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench.common import harness_only, judge, phase, traced

STEPS_CHECKED = 3
PROGRAM_SURFACE = ("mesh", "sample_input", "init_state", "stage_dataset",
                   "train_epoch", "_superstep", "_superstep_len",
                   "_last_epoch_losses")


def _reference_inputs(raw, train, level_names):
    """Normalised base series by the published rule, made from the raw
    corpus alone: min-max over the rows the train windows cover; level
    resources as per-bucket increments."""
    w = int(train["window_size"])
    traffic = raw["traffic"]
    names = list(raw["resources"])
    targets = np.stack([raw["resources"][k] for k in names], axis=-1)
    is_level = np.asarray([n.rsplit("_", 1)[-1] in level_names for n in names])
    inc = np.array(targets, np.float32, copy=True)
    inc[1:, is_level] = targets[1:, is_level] - targets[:-1, is_level]
    inc[0, is_level] = 0.0
    n_windows = len(traffic) - w
    split = int(n_windows * float(train["train_split"]))
    span = split + w - 1
    x_lo, x_hi = np.float32(traffic[:span].min()), np.float32(traffic[:span].max())
    y_lo, y_hi = inc[:span].min(axis=0), inc[:span].max(axis=0)
    return {"split": split, "x": (traffic, x_lo, x_hi), "y": (inc, y_lo, y_hi),
            "names": names}


def _windows(base, lo, hi, starts, w, ref):
    rows = np.stack([base[s:s + w] for s in starts])
    return ref.minmax(rows, lo, hi).astype(np.float32)


def check_starts(raw, tcfg, seed, bundle=None):
    """The rows of the three checked steps: 3 x B window starts that all
    differ, drawn from the seed."""
    from deeprest_tpu.config import LEVEL_RESOURCES

    inputs = _reference_inputs(
        raw, {"window_size": tcfg.window_size,
              "train_split": tcfg.train_split}, set(LEVEL_RESOURCES))
    if bundle is not None and (
            inputs["split"] != bundle.num_train_windows
            or inputs["names"] != bundle.metric_names):
        raise RuntimeError("reference and program disagree on the split")
    pick = np.random.default_rng(seed + 1)
    return pick.choice(inputs["split"], size=STEPS_CHECKED * tcfg.batch_size,
                       replace=False).astype(np.int32).reshape(
                           STEPS_CHECKED, tcfg.batch_size)


def check_batches(raw, tcfg, starts):
    """The reference's batches for those rows, normalised by its own rule
    from the raw corpus."""
    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.config import LEVEL_RESOURCES

    w = tcfg.window_size
    inputs = _reference_inputs(
        raw, {"window_size": w, "train_split": tcfg.train_split},
        set(LEVEL_RESOURCES))
    return [(_windows(*inputs["x"], s, w, ref),
             _windows(*inputs["y"], s, w, ref)) for s in starts]


def compare(program: dict, reference: dict) -> dict:
    """The numbers `correct` reads.  Losses: the widest relative gap of the
    three.  Norms, by the worst leaf: the gap between the program's norm and
    the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    out = {"loss_rel_gap": max(
        abs(p - r) / abs(r)
        for p, r in zip(program["losses"], reference["losses"]))}
    for key in ("grad_norm", "delta_norm"):
        ref_norms = reference[key]
        median = float(np.median(list(ref_norms.values())))
        worst, leaf = max(
            (abs(program[key][k] - ref_norms[k]) / max(ref_norms[k], median), k)
            for k in ref_norms)
        out[f"{key}_gap"] = worst
        out[f"{key}_gap_leaf"] = leaf
    return out


def _trainer_surface(trainer) -> None:
    missing = [n for n in PROGRAM_SURFACE if not hasattr(trainer, n)]
    if missing:
        raise RuntimeError(
            f"the train runner drives Trainer through {PROGRAM_SURFACE}; "
            f"this program's Trainer has no {missing}")


def _program(ctx, raw, mcfg, tcfg, key, dims):
    """Phases 2 to 5.  Everything the program holds on the device lives in
    this function, so that it is freed when it returns."""
    import jax
    import jax.numpy as jnp

    from chipbench import flops
    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.config import Config, FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace, FeaturizedData
    from deeprest_tpu.parallel.distributed import stage_plan
    from deeprest_tpu.train.data import prepare_dataset
    from deeprest_tpu.train.trainer import Trainer

    e, f, h, q = dims
    w, b = tcfg.window_size, tcfg.batch_size
    t = time.perf_counter()
    rows = len(raw["traffic"])
    space = CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=f)).freeze()
    data = FeaturizedData(traffic=raw["traffic"], resources=raw["resources"],
                          invocations={"general": np.ones(rows, np.float32)},
                          space=space)
    bundle = prepare_dataset(data, tcfg)
    n_train = bundle.num_train_windows
    num_steps = -(-n_train // b)
    starts = check_starts(raw, tcfg, ctx.seed, bundle)
    t = phase(ctx, f"dataset ({n_train} train windows, {num_steps} steps an "
                   "epoch)", t)

    # 2. one trainer, one state; the seeded weights in its own weights' place
    trainer = Trainer(Config(model=mcfg, train=tcfg), bundle.feature_dim,
                      bundle.metric_names)
    _trainer_surface(trainer)
    state = jax.block_until_ready(
        trainer.init_state(trainer.sample_input(bundle)))
    t = phase(ctx, "trainer and init_state", t)
    with harness_only(ctx, "installing the seeded weights"):
        placement = {k: v.sharding for k, v in state.params.items()}
        if {k: v.shape for k, v in state.params.items()} != {
                k: shape for k, (shape, _) in
                ref.param_shapes(e, f, h, q).items()}:
            raise RuntimeError("parameter leaves differ from the reference's")
        state = state.replace(params={})         # the old leaves go first
        seeded = ref.init_params(key, e, f, h, q)
        state = jax.block_until_ready(state.replace(params={
            k: jax.device_put(seeded[k], placement[k]) for k in placement}))
        del seeded
    staged = trainer.stage_dataset(bundle)
    if staged is None:
        raise RuntimeError("the corpus was not staged on the device")
    jax.block_until_ready(staged)
    t = phase(ctx, "seeded weights, staged corpus", t)

    # 3. the first three steps, through the window's own superstep
    s_len = trainer._superstep_len(num_steps)
    chunks = -(-num_steps // s_len)
    if chunks < 2 or s_len < 2:
        raise RuntimeError("the corpus is too short for the check's plan")
    plan_starts = np.zeros((chunks, s_len, b), np.int32)
    plan_weights = np.zeros((chunks, s_len, b), np.float32)
    plan_starts[0, 0], plan_starts[1, 0], plan_starts[1, 1] = starts
    plan_weights[0, 0] = plan_weights[1, 0] = plan_weights[1, 1] = 1.0
    plan = stage_plan(trainer.mesh, plan_starts, plan_weights)

    @jax.jit
    def first_grad_norms(opt_state):
        mu = opt_state[0].mu
        return {k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1 - ref.ADAM["b1"])
                for k, v in mu.items()}

    @jax.jit
    def delta_norms(params, key):
        start = ref.init_params(key, e, f, h, q)
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
    program = {
        "losses": [float(losses0[0]), float(losses1[0]), float(losses1[1])],
        "grad_norm": grad_norm, "delta_norm": delta,
        "steps_counted": int(state.step),
    }
    t = phase(ctx, "first three steps through the window's superstep", t)

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
        work = flops.gru_kernel_work(
            b, w, e, h, training=True,
            act_bytes=jnp.dtype(mcfg.compute_dtype).itemsize)
        evidence = {"trace": reduced, "runner": "train", "steps": attempted,
                    "kernel_work_per_step": work}
    compiled = ctx.compiles.count - compiles0
    peak_bytes = ctx.memory_peak_bytes()

    rate = attempted / elapsed
    tflop = flops.train_step_tflops(b, w, f, e, h, q)
    ctx.log(f"window: {attempted} steps in {elapsed:.3f} s, "
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
    return {"program": program, "starts": starts, "compiled": compiled,
            "attempted": attempted, "failed": failed, "values": values,
            "evidence": evidence, "memory_peak_bytes": peak_bytes}


def run(ctx) -> dict:
    import jax

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

    # 6. the reference's three steps, after the program's state is freed
    t = phase(ctx, "program freed", time.perf_counter())
    batches = check_batches(raw, tcfg, out["starts"])
    reference = ref.train_three_steps(
        ref.init_params(key, *dims), batches, ctx.key_seed(),
        mcfg.quantiles, mcfg.dropout_rate, "f32")
    program = out.pop("program")
    ctx.log("losses program", program["losses"], "reference",
            reference["losses"], "steps counted", program["steps_counted"])
    correct = judge(ctx, compare(program, reference))
    phase(ctx, "reference and comparison (after the window; in neither "
               "setup_s nor the window)", t)
    if program["steps_counted"] != STEPS_CHECKED:
        ctx.log(f"NOT CORRECT: the program counted "
                f"{program['steps_counted']} steps for {STEPS_CHECKED}")
        correct = False
    if out["compiled"]:
        ctx.log(f"NOT CORRECT: {out['compiled']} compilations inside the "
                "window")
        correct = False
    if out["failed"]:
        ctx.log(f"NOT CORRECT: {out['failed']} of {out['attempted']} steps "
                "with a non-finite loss")
        correct = False
    return {"correct": correct, **out}
