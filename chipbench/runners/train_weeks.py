"""Runner ``train_weeks``: the weekly retrain of one estimator several
releases into its life: one ``Trainer``, ``stage_dataset`` once a week on
the same trainer and state, training on each week, whole epochs through
``train_epoch`` on the last.  It is chipbench/runners/train_warm.py for N
weeks (read its docstring first, and ``train.py``'s before it); this file
says only what differs.

- **The corpus** is a list of weeks (chipbench/generators/corpus_weeks.py),
  the last one current, a bundle each.
- **The check crosses every restage.**  The plan of the epoch's shape is
  ``train``'s: chunk 0 holds one real step, chunk 1 two.  For each prior
  week in order: ``stage_dataset(week)``, chunk 0 on 32 of ITS rows; then
  ``stage_dataset(current week)`` and chunk 1 on 64 of its rows; all
  through the one compiled ``trainer._superstep``.  With N weeks that is
  N + 1 checked steps across N - 1 restages.  From the second week on the
  state carries Adam moments on rows that left the table, more of them at
  every release, so every later step runs the superstep's off-table pass
  over a longer list: a program that skipped, froze, reset or
  double-stepped a retired row at ANY restage fails ``delta_norm_gap`` at
  the w_ih leaves (chipbench/tests/control_on_chip_weeks.py shows it).
- **The reference** is chipbench/reference/qrnn_ref.py as it stands:
  N + 1 plain dense Adam steps in float32 (``train_three_steps`` takes any
  number of batches), each batch normalised from its own raw week.
- **What else fails a run**, beside ``train_warm``'s list: ANY compilation
  after the superstep's first dispatch (N stagings, one executable); the
  gauge's ``stale`` after the warm-up epoch under ``STALE_FLOOR`` of what
  the releases retired (the cell would not be running what it is for) or
  over ``bound`` (it would be running the all-rows loop); ``trips`` and
  ``visited`` not what ``stale`` makes them by the program's rule.  (A
  program older than the kinds ``bound`` and ``trips``, the parent commit
  laid over with this benchmark, is held to ``stale`` and ``updated``.)
- **The accepted per-layer metrics** that apply by runner name are read as
  in ``train_warm.py``: ``run`` ends by naming the mix's runner ``train``.

``datasets``, ``reference_batches`` and ``checked_steps`` are what
chipbench/tests/control_on_chip_weeks.py drives over many seeds.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench.common import harness_only, judge, phase, traced
from chipbench.runners.train import (
    _trainer_surface, check_batches, check_starts, compare,
)
from chipbench.runners.train_warm import dataset, gauge, seeded_state

# The least share of the columns the releases retired that has to be stale
# after the warm-up epoch.  One checked step a week leaves a moment on the
# rows whose path was in that step's 32 windows: 5,111 to 5,120 of the cell's
# 5,120 retired rows on every seed read (chipbench/limits/
# tenk-retrain-live4k.json, `stale_after_the_checked_steps`), so nine tenths
# (4,608 there) is 500 rows under the smallest seed's count and 500 over
# what a program that lost one release's 1,024 rows would leave.
STALE_FLOOR = 0.9
_CHUNK = 64                     # rows a trip of the program's off-table pass


def retired_columns(params) -> int:
    """The columns the mix's releases retired before its last week."""
    return ((int(params["weeks"]) - 1)
            * (int(params["hot_paths"]) - int(params["carried_paths"])))


def datasets(weeks, tcfg, f, seed):
    """(a bundle a week, the check's rows): three batches of window starts
    that all differ, the same in every week (every week splits alike);
    each prior week's step takes the first, the current week's two steps
    the second and third."""
    bundles = [dataset(raw, tcfg, f) for raw in weeks]
    starts = check_starts(weeks[-1], tcfg, seed, bundles[-1])
    for raw, bundle in zip(weeks[:-1], bundles):
        if not np.array_equal(starts, check_starts(raw, tcfg, seed, bundle)):
            raise RuntimeError("the weeks split differently")
    return bundles, starts


def reference_batches(weeks, tcfg, starts) -> list:
    """The reference's batches for the checked steps, in their order."""
    batches = []
    for raw in weeks[:-1]:
        batches += check_batches(raw, tcfg, starts[:1])
    return batches + check_batches(weeks[-1], tcfg, starts[1:])


def checked_steps(ctx, trainer, state, bundles, starts, key, dims):
    """Phase 3: for each prior week stage it and take one real step on it,
    stage the current week, two real steps on it, all through
    ``trainer._superstep``.  Returns (state, the current week staged, the
    numbers of the check, what was compiled after the first dispatch, the
    tags of each staging's span)."""
    import jax
    import jax.numpy as jnp

    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.obs import spans
    from deeprest_tpu.parallel.distributed import stage_plan

    b = trainer.config.train.batch_size
    num_steps = -(-bundles[-1].num_train_windows // b)
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

    def stage(bundle):
        """``stage_dataset`` with the recorder on for its span's tags."""
        was, spans.RECORDER.enabled = spans.RECORDER.enabled, True
        try:
            staged = trainer.stage_dataset(bundle)
        finally:
            spans.RECORDER.enabled = was
        found = [s for s in spans.RECORDER.snapshot()
                 if s.name == "train.stage"]
        return staged, dict(found[-1].tags) if found else {}

    superstep = trainer._superstep
    losses, stagings, grad_norm, compiles0 = [], [], None, None
    for week, bundle in enumerate(bundles):
        current = week == len(bundles) - 1
        staged, tags = stage(bundle)
        if staged is None:
            raise RuntimeError(f"week {week} was not staged on the device")
        stagings.append(tags)
        state, chunk_losses = superstep(state, *staged, *plan, int(current))
        jax.block_until_ready(state)
        losses.append(chunk_losses)
        if compiles0 is None:
            compiles0 = ctx.compiles.count
            with harness_only(ctx, "reading the first gradient's norms"):
                grad_norm = {k: float(v) for k, v in
                             first_grad_norms(state.opt_state).items()}
    compiled = ctx.compiles.count - compiles0
    # a step's loss of each prior week's dispatch, two of the current week's
    *prior, current = (np.asarray(chunk) for chunk in losses)
    losses = [float(c[0]) for c in prior] + [float(x) for x in current[:2]]
    for week, tags in enumerate(stagings):
        ctx.log(f"staging {week + 1} of {len(stagings)}", tags)
    ctx.log("last staging", gauge("deeprest_train_last_stage_seconds"),
            "projection columns", gauge("deeprest_train_projection_columns"))
    with harness_only(ctx, "reading the norms of the parameters' change"):
        delta = {k: float(v) for k, v in
                 delta_norms(state.params, key).items()}
    program = {"losses": losses, "grad_norm": grad_norm, "delta_norm": delta,
               "steps_counted": int(state.step)}
    return state, staged, program, compiled, stagings


def _program(ctx, weeks, mcfg, tcfg, key, dims):
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
    bundles, starts = datasets(weeks, tcfg, f, ctx.seed)
    bundle = bundles[-1]
    n_train = bundle.num_train_windows
    num_steps = -(-n_train // b)
    t = phase(ctx, f"datasets ({len(weeks)} weeks; {n_train} train windows, "
                   f"{num_steps} steps an epoch)", t)

    # 2. one trainer, one state; the seeded weights in its own weights' place
    trainer = Trainer(Config(model=mcfg, train=tcfg), bundle.feature_dim,
                      bundle.metric_names)
    _trainer_surface(trainer)
    state = seeded_state(ctx, trainer, bundle, key, dims)
    t = phase(ctx, "trainer, init_state and the seeded weights", t)

    # 3. a step on each prior week, two on the current, a restage between
    state, staged, program, compiled_early, stagings = checked_steps(
        ctx, trainer, state, bundles, starts, key, dims)
    del bundles
    t = phase(ctx, f"{len(weeks) + 1} steps through the window's superstep, "
                   f"across {len(weeks) - 1} restages ({compiled_early} "
                   "compilations after the first dispatch)", t)

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
            "columns": columns, "stagings": stagings,
            "executables": trainer._superstep._cache_size(),
            "attempted": attempted, "failed": failed, "values": values,
            "evidence": evidence, "memory_peak_bytes": peak_bytes}


def faults(ctx, out, program, steps) -> list:
    """What fails a run beside the comparison: (whether, what)."""
    rows, columns = out["rows"], out["columns"]
    retired = retired_columns(ctx.mix["params"])
    stale, bound = rows.get("stale"), rows.get("bound")
    trips, visited = rows.get("trips"), rows.get("visited")
    found = [
        (program["steps_counted"] != steps,
         f"the program counted {program['steps_counted']} steps for {steps}"),
        (out["compiled_early"],
         f"{out['compiled_early']} compilations between the superstep's "
         f"first dispatch and the warm-up epoch ({len(out['stagings'])} "
         "stagings, one executable)"),
        (out["executables"] != 1,
         f"{out['executables']} executables of the superstep for 1"),
        (out["compiled"], f"{out['compiled']} compilations inside the window"),
        (stale is None or not STALE_FLOOR * retired <= stale <= retired,
         f"{stale} stale rows after the warm-up epoch ({rows}): the releases "
         f"retired {retired} columns and one step a week leaves a moment on "
         f"at least {STALE_FLOOR:g} of them"),
        (rows.get("updated") != columns.get("total"),
         f"optimizer rows {rows} of {columns}: while a row is stale the "
         "program's rule is Adam over all of them"),
        (out["failed"],
         f"{out['failed']} of {out['attempted']} steps with a non-finite "
         "loss"),
    ]
    if bound is not None and stale is not None:
        # the program's rule, from what it published: row by row up to the
        # bound, a trip a chunk, the table's rows beside them
        width = columns.get("contracted", 0)
        found += [
            (stale > bound,
             f"{stale} stale rows over the bound of {bound}: the all-rows "
             "loop ran, not the pass this cell is for"),
            (trips != -(-stale // _CHUNK),
             f"{trips} trips a dispatch for {stale} stale rows"),
            (visited != width + _CHUNK * -(-stale // _CHUNK),
             f"{visited} rows visited for a table of {width} and {stale} "
             "stale rows"),
        ]
    return found


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

    # 1. the corpora
    weeks = ctx.generator().generate(ctx.mix["params"], ctx.seed, model)
    phase(ctx, f"corpora ({len(weeks)} weeks of {len(weeks[-1]['traffic'])} "
               "buckets)", t)

    out = _program(ctx, weeks, mcfg, tcfg, key, dims)        # 2 to 5
    gc.collect()

    # 6. the reference's steps, after the program's state is freed: a batch
    # of each prior week, then the current week's two
    t = phase(ctx, "program freed", time.perf_counter())
    reference = ref.train_three_steps(
        ref.init_params(key, *dims),
        reference_batches(weeks, tcfg, out["starts"]), ctx.key_seed(),
        mcfg.quantiles, mcfg.dropout_rate, "f32")
    program = out.pop("program")
    ctx.log("losses program", program["losses"], "reference",
            reference["losses"], "steps counted", program["steps_counted"])
    correct = judge(ctx, compare(program, reference))
    phase(ctx, "reference and comparison (after the window; in neither "
               "setup_s nor the window)", t)

    for bad, what in faults(ctx, out, program, len(weeks) + 1):
        if bad:
            ctx.log("NOT CORRECT: " + what)
            correct = False
    for k in ("rows", "columns", "stagings"):
        out.pop(k)
    # the accepted metrics that apply by runner name read this run as the
    # `train` run it is (train_warm.py's docstring; goes with ROADMAP D19)
    ctx.mix["runner"] = "train"
    return {"correct": correct, **out}
