"""Runner ``train_warm``: a retrain from the last one's state on a corpus
whose live call paths moved, as every refresh of ``deeprest_tpu stream``
and every resumed ``deeprest_tpu train`` runs it: one ``Trainer``,
``stage_dataset(prior week)``, training, ``stage_dataset(current week)`` on
the same trainer and state, whole epochs through ``train_epoch`` on the
current week.  The phases, the log, the window, the comparison and the
evidence are those of chipbench/runners/train.py (read its docstring
first); this file says only what differs.

- **The corpus** is a pair (chipbench/generators/corpus_pair.py): the prior
  week and the current one, two bundles.
- **The check crosses the restage.**  The plan of the epoch's shape is
  ``train``'s: chunk 0 is one real step, on 32 rows of the PRIOR week with
  the prior week staged; then the current week is restaged; chunk 1 is two
  real steps on 64 rows of the CURRENT week, through the same compiled
  superstep.  After step one the state carries Adam moments on the prior
  table's rows, so steps two and three run the superstep's pass over all
  rows: a program that skipped the rows retired from the table would pass
  ``train``'s check and fails this one (``delta_norm_gap`` at the w_ih
  leaves; chipbench/tests/control_on_chip_warm.py shows it).
- **The reference** is chipbench/reference/qrnn_ref.py as it stands: three
  plain dense Adam steps in float32 on [the prior week's 32 rows, the
  current week's 32, the current week's 32], each normalised from its own
  raw corpus.
- **Warm-up and window** run on the current week from the state the check
  left: every epoch is a retrain from a carried state.
- **What else fails a run**: a compilation between the superstep's first
  dispatch and the warm-up epoch (the restage and the second dispatch must
  reuse the one executable; the harness compiles its own two norms before
  that first dispatch), more than one executable in the superstep's jit
  cache after the window, a compilation in the window, the gauge
  ``deeprest_train_optimizer_rows{kind="stale"}`` at 0 after the warm-up
  epoch (the cell would not be running what it is for), or ``updated``
  not what the program's rule says while a row is stale: F.  (A program
  older than the ``stale`` kind, the parent commit laid over with this
  benchmark, is held to ``updated`` alone.)
- **The accepted per-layer metrics** that apply by runner name are read
  here as in ``train_mesh.py``: the evidence is the ``train`` runner's key
  for key and ``run`` ends by naming the mix's runner ``train`` (ROADMAP
  D19 folds the three runners and deletes the line).

``dataset``, ``seeded_state`` and ``checked_steps`` are what
chipbench/tests/control_on_chip_warm.py drives over many seeds.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench.common import harness_only, judge, phase, traced
from chipbench.runners.train import (
    STEPS_CHECKED, _trainer_surface, check_batches, check_starts, compare,
)
from chipbench.runners.train_mesh import _gauge as gauge


def dataset(raw, tcfg, f):
    """One week's bundle, as ``train`` makes its corpus's."""
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace, FeaturizedData
    from deeprest_tpu.train.data import prepare_dataset

    space = CallPathSpace(config=FeaturizeConfig(
        hash_features=True, capacity=f)).freeze()
    return prepare_dataset(FeaturizedData(
        traffic=raw["traffic"], resources=raw["resources"],
        invocations={"general": np.ones(len(raw["traffic"]), np.float32)},
        space=space), tcfg)


def seeded_state(ctx, trainer, bundle, key, dims, seed=None):
    """A state from ``init_state`` with the seeded weights in the place of
    the trainer's own (the old leaves dropped first)."""
    import jax

    from chipbench.reference import qrnn_ref as ref

    state = jax.block_until_ready(
        trainer.init_state(trainer.sample_input(bundle), seed=seed))
    with harness_only(ctx, "installing the seeded weights"):
        placement = {k: v.sharding for k, v in state.params.items()}
        if {k: v.shape for k, v in state.params.items()} != {
                k: shape for k, (shape, _) in
                ref.param_shapes(*dims).items()}:
            raise RuntimeError("parameter leaves differ from the reference's")
        state = state.replace(params={})         # the old leaves go first
        seeded = ref.init_params(key, *dims)
        state = jax.block_until_ready(state.replace(params={
            k: jax.device_put(seeded[k], placement[k]) for k in placement}))
    return state


def checked_steps(ctx, trainer, state, bundles, starts, key, dims):
    """Phase 3: stage the prior week, one real step on it, restage the
    current week, two real steps on it, all through ``trainer._superstep``.
    Returns (state, the current week staged, the numbers of the check, what
    the restage and the second dispatch compiled)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.obs import spans
    from deeprest_tpu.parallel.distributed import stage_plan

    prior, current = bundles
    b = trainer.config.train.batch_size
    num_steps = -(-current.num_train_windows // b)
    s_len = trainer._superstep_len(num_steps)
    chunks = -(-num_steps // s_len)
    if chunks < 2 or s_len < 2:
        raise RuntimeError("the corpus is too short for the check's plan")
    plan_starts = np.zeros((chunks, s_len, b), np.int32)
    plan_weights = np.zeros((chunks, s_len, b), np.float32)
    plan_starts[0, 0], plan_starts[1, 0], plan_starts[1, 1] = starts
    plan_weights[0, 0] = plan_weights[1, 0] = plan_weights[1, 1] = 1.0
    plan = stage_plan(trainer.mesh, plan_starts, plan_weights)

    def first_grad_norms(opt_state):
        mu = opt_state[0].mu
        return {k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1 - ref.ADAM["b1"])
                for k, v in mu.items()}

    def delta_norms(params, key):
        start = ref.init_params(key, *dims)
        return ref.leaf_norms({k: params[k] - start[k] for k in params})

    # the harness's own two programs, compiled before the superstep's first
    # dispatch so that every compilation after it is the program's
    first_grad_norms = jax.jit(first_grad_norms).lower(
        state.opt_state).compile()
    delta_norms = jax.jit(delta_norms).lower(state.params, key).compile()

    staged = trainer.stage_dataset(prior)
    if staged is None:
        raise RuntimeError("the prior week was not staged on the device")
    superstep = trainer._superstep
    state, losses0 = superstep(state, *staged, *plan, 0)
    jax.block_until_ready(state)
    compiles0 = ctx.compiles.count
    with harness_only(ctx, "reading the first gradient's norms"):
        grad_norm = {k: float(v) for k, v in
                     first_grad_norms(state.opt_state).items()}

    # the restage: the same trainer, the same state, the recorder on for the
    # stage span's tags alone
    was, spans.RECORDER.enabled = spans.RECORDER.enabled, True
    try:
        staged = trainer.stage_dataset(current)
    finally:
        spans.RECORDER.enabled = was
    stage = [s for s in spans.RECORDER.snapshot() if s.name == "train.stage"]
    jax.block_until_ready(staged)
    ctx.log("restage", dict(stage[-1].tags) if stage else "no span",
            gauge("deeprest_train_last_stage_seconds"),
            "projection columns", gauge("deeprest_train_projection_columns"))

    state, losses1 = superstep(state, *staged, *plan, 1)
    jax.block_until_ready(state)
    compiled = ctx.compiles.count - compiles0
    with harness_only(ctx, "reading the norms of the parameters' change"):
        delta = {k: float(v) for k, v in
                 delta_norms(state.params, key).items()}
    program = {
        "losses": [float(losses0[0]), float(losses1[0]), float(losses1[1])],
        "grad_norm": grad_norm, "delta_norm": delta,
        "steps_counted": int(state.step),
    }
    return state, staged, program, compiled


def _program(ctx, pair, mcfg, tcfg, key, dims):
    """Phases 2 to 5.  Everything the program holds on the device lives in
    this function, so that it is freed when it returns."""
    import jax
    import jax.numpy as jnp

    from chipbench import flops
    from deeprest_tpu.config import Config
    from deeprest_tpu.train.trainer import Trainer

    e, f, h, q = dims
    w, b = tcfg.window_size, tcfg.batch_size
    t = time.perf_counter()
    bundles = [dataset(pair[k], tcfg, f) for k in ("prior", "current")]
    bundle = bundles[1]
    n_train = bundle.num_train_windows
    num_steps = -(-n_train // b)
    starts = check_starts(pair["prior"], tcfg, ctx.seed, bundles[0])
    if not np.array_equal(
            starts, check_starts(pair["current"], tcfg, ctx.seed, bundle)):
        raise RuntimeError("the two weeks split differently")
    t = phase(ctx, f"datasets (two weeks; {n_train} train windows, "
                   f"{num_steps} steps an epoch)", t)

    # 2. one trainer, one state; the seeded weights in its own weights' place
    trainer = Trainer(Config(model=mcfg, train=tcfg), bundle.feature_dim,
                      bundle.metric_names)
    _trainer_surface(trainer)
    state = seeded_state(ctx, trainer, bundle, key, dims)
    t = phase(ctx, "trainer, init_state and the seeded weights", t)

    # 3. one step on the prior week, the restage, two on the current week
    state, staged, program, compiled_early = checked_steps(
        ctx, trainer, state, bundles, starts, key, dims)
    del bundles
    t = phase(ctx, "three steps through the window's superstep, across "
                   f"the restage ({compiled_early} compilations after the "
                   "first dispatch)", t)

    # 4. warm-up: one whole epoch on the current week
    epoch_rng = np.random.default_rng(ctx.seed + 2)

    def epoch(st):
        with jax.profiler.TraceAnnotation("bench.train_epoch"):
            st, _ = trainer.train_epoch(st, bundle, epoch_rng, staged=staged)
        return st, trainer._last_epoch_losses

    state, losses = epoch(state)
    rows = gauge("deeprest_train_optimizer_rows")
    columns = gauge("deeprest_train_projection_columns")
    t = phase(ctx, f"warm-up epoch (loss {float(np.mean(losses)):.5f}; "
                   f"optimizer rows {rows})", t)

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
    ctx.log(f"window: {attempted} steps in {elapsed:.3f} s")
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
            "compiled_early": compiled_early, "rows": rows,
            "columns": columns,
            "executables": trainer._superstep._cache_size(),
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

    # 1. the two corpora
    pair = ctx.generator().generate(ctx.mix["params"], ctx.seed, model)
    phase(ctx, f"corpora (two weeks of {len(pair['current']['traffic'])} "
               "buckets)", t)

    out = _program(ctx, pair, mcfg, tcfg, key, dims)         # 2 to 5
    gc.collect()

    # 6. the reference's three steps, after the program's state is freed:
    # the prior week's rows, then the current week's twice
    t = phase(ctx, "program freed", time.perf_counter())
    starts = out["starts"]
    batches = (check_batches(pair["prior"], tcfg, starts[:1])
               + check_batches(pair["current"], tcfg, starts[1:]))
    reference = ref.train_three_steps(
        ref.init_params(key, *dims), batches, ctx.key_seed(),
        mcfg.quantiles, mcfg.dropout_rate, "f32")
    program = out.pop("program")
    ctx.log("losses program", program["losses"], "reference",
            reference["losses"], "steps counted", program["steps_counted"])
    correct = judge(ctx, compare(program, reference))
    phase(ctx, "reference and comparison (after the window; in neither "
               "setup_s nor the window)", t)

    # `stale` is None on a program older than the kind (the parent commit
    # laid over with this benchmark): it can still show that it ran Adam
    # over all F rows, which is what the cell is for
    rows, columns = out.pop("rows"), out.pop("columns")
    stale = rows.get("stale")
    faults = [
        (program["steps_counted"] != STEPS_CHECKED,
         f"the program counted {program['steps_counted']} steps for "
         f"{STEPS_CHECKED}"),
        (out["compiled_early"],
         f"{out['compiled_early']} compilations between the superstep's "
         "first dispatch and the warm-up epoch (the restage included)"),
        (out["executables"] != 1,
         f"{out['executables']} executables of the superstep for 1"),
        (out["compiled"], f"{out['compiled']} compilations inside the window"),
        (stale == 0,
         f"no stale row after the warm-up epoch ({rows}): the state carries "
         "no moment off the current week's table"),
        (rows.get("updated") != columns.get("total"),
         f"optimizer rows {rows} of {columns}: while a row is stale the "
         "program's rule is Adam over all of them"),
        (out["failed"],
         f"{out['failed']} of {out['attempted']} steps with a non-finite "
         "loss"),
    ]
    for bad, what in faults:
        if bad:
            ctx.log("NOT CORRECT: " + what)
            correct = False
    # the accepted metrics that apply by runner name read this run as the
    # `train` run it is (see the docstring; goes with ROADMAP D19)
    ctx.mix["runner"] = "train"
    return {"correct": correct, **out}
