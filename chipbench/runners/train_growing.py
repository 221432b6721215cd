"""Runner ``train_growing``: the weekly retrain of one estimator while its
estate's call paths are onboarded in waves, so that ONE trainer's life
dispatches several supersteps: a table, a wider table, the dense form.  It
is chipbench/runners/train_weeks.py (read its docstring first, and
``train_warm.py``'s and ``train.py``'s before it) with another rule for
compilations; this file says only what differs.

- **The corpus** is a list of weeks whose live sets are nested and grow
  (chipbench/generators/corpus_weeks_growing.py); nothing retires, so no
  row goes stale and the off-table pass counts 0 throughout.
- **The programs of the life** follow from the mix's schedule by the
  program's own rule (``ops/densify.compact_rule``: a week's live set
  padded to a power of two, the dense form past the bound): ``expected``.
  The life must dispatch exactly those, in that order; a run that stayed
  compact in the last week, or went dense early, is not running what the
  cell is for and fails.
- **The check crosses every restage and both handovers**: ``train_weeks``'s
  plan, a checked step on each prior week and two on the current, all
  through ``trainer._superstep`` on the one carried state, against
  chipbench/reference/qrnn_ref.py as it stands (dense float32 Adam that
  knows no table: the whole of the semantics).  Each first dispatch of a
  staged program the life had not dispatched is made inside the trainer's
  own ``_first_dispatch`` (it books the span, the set-up phase and, in a
  program that keeps its books once a program, the counter and the
  seconds).
- **Compilations.**  One after the superstep's first dispatch is a fault
  UNLESS it falls in the staging or the first dispatch of a week whose
  (form, width) the life had not dispatched.  None between the warm-up
  epoch's first dispatch boundary and its last, none in the window, and
  none of ``train_superstep`` booked by the program to its set-up phase
  ``epoch`` (a new program's belong to ``first_dispatch``).
- **Held to what an older program can show**: where the program has no
  ``deeprest_train_superstep_programs_total`` (the parent commit laid over
  with this benchmark) the count of programs is the superstep's distinct
  executables (``_cache_size``), as ``train_weeks`` holds an older program
  to ``stale`` and ``updated``.
- **Memory by week**: after each week's first dispatch the chip's
  ``bytes_in_use`` and ``bytes_reserved`` are logged (what the runtime
  holds for the programs the life has loaded so far).
- **The accepted per-layer metrics** that apply by runner name are read as
  in ``train_warm.py``: ``run`` ends by naming the mix's runner ``train``.

``datasets`` and ``reference_batches`` are ``train_weeks``'s;
``checked_steps`` is what chipbench/tests/control_on_chip_growing.py drives
over many seeds.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from chipbench.common import harness_only, judge, phase, traced
from chipbench.readers.setup import _series
from chipbench.runners.train import _trainer_surface, compare
from chipbench.runners.train_warm import gauge, seeded_state
from chipbench.runners.train_weeks import datasets, reference_batches

PROGRAMS = "deeprest_train_superstep_programs_total"


def expected_programs(params, f: int) -> list:
    """The (form, width) of each week's staging by the program's rule, in
    order."""
    from deeprest_tpu.ops.densify import compact_rule

    out = []
    for live in params["hot_paths_by_week"]:
        padded, bound = compact_rule(int(live), f)
        out.append(("compact", padded) if padded <= bound else ("dense", f))
    return out


def programs_counted() -> dict | None:
    """{(form, width): first dispatches} of the program's counter, None on
    a program without it."""
    series = _series(PROGRAMS)
    if series is None:
        return None
    return {(labels["form"], int(labels["width"])): int(n)
            for labels, n in series}


def checked_steps(ctx, trainer, state, bundles, starts, key, dims,
                  at_handover=None):
    """Phase 3: for each prior week stage it and take one real step on it,
    stage the current week, two real steps on it, all through
    ``trainer._superstep``; a week whose staged program is new to the life
    dispatches inside ``trainer._first_dispatch``.  ``at_handover(state,
    week, tags) -> state`` (the controls') runs after each staging.
    Returns (state, the current week staged, the numbers of the check, a
    record a week: the stage span's tags, what the staging and the
    dispatch compiled, the chip's memory after the dispatch)."""
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
    losses, weeks, grad_norm, seen = [], [], None, set()
    for week, bundle in enumerate(bundles):
        current = week == len(bundles) - 1
        compiles0 = ctx.compiles.count
        staged, tags = stage(bundle)
        if staged is None:
            raise RuntimeError(f"week {week} was not staged on the device")
        jax.block_until_ready(staged)
        if at_handover is not None:
            state = at_handover(state, week, tags)
        compiles1 = ctx.compiles.count
        program = (tags.get("form"), tags.get("width"))
        new = program not in seen
        seen.add(program)
        t0 = time.perf_counter()
        if new:
            # the trainer's own books of a first dispatch (a program that
            # keeps them once a LIFE opens them for the first week only)
            with trainer._first_dispatch(superstep):
                state, chunk_losses = superstep(state, *staged, *plan,
                                                int(current))
                jax.block_until_ready(state)
        else:
            state, chunk_losses = superstep(state, *staged, *plan,
                                            int(current))
            jax.block_until_ready(state)
        seconds = time.perf_counter() - t0
        losses.append(chunk_losses)
        stats = ctx.device.memory_stats() or {}
        weeks.append({
            "tags": tags, "program": program, "new": new,
            "compiled_staging": compiles1 - compiles0,
            "compiled_dispatch": ctx.compiles.count - compiles1,
            "dispatch_s": seconds,
            "bytes_in_use": int(stats.get("bytes_in_use", 0)),
            "bytes_reserved": int(stats.get("bytes_reserved", 0)),
            "executables": trainer._superstep._cache_size()})
        if grad_norm is None:
            with harness_only(ctx, "reading the first gradient's norms"):
                grad_norm = {k: float(v) for k, v in
                             first_grad_norms(state.opt_state).items()}
    # a step's loss of each prior week's dispatch, two of the current week's
    *prior, current = (np.asarray(chunk) for chunk in losses)
    losses = [float(c[0]) for c in prior] + [float(x) for x in current[:2]]
    for week, found in enumerate(weeks):
        ctx.log(f"staging {week + 1} of {len(weeks)}", found["tags"])
        ctx.log(f"week {week + 1}: program {found['program']} "
                f"{'NEW' if found['new'] else 'kept'}, compiled "
                f"{found['compiled_staging']} staging + "
                f"{found['compiled_dispatch']} dispatching, dispatch "
                f"{found['dispatch_s']:.3f} s, {found['executables']} "
                f"executables; memory after it: in use "
                f"{found['bytes_in_use']} bytes, reserved for loaded "
                f"programs {found['bytes_reserved']} bytes")
    with harness_only(ctx, "reading the norms of the parameters' change"):
        delta = {k: float(v) for k, v in
                 delta_norms(state.params, key).items()}
    numbers = {"losses": losses, "grad_norm": grad_norm, "delta_norm": delta,
               "steps_counted": int(state.step)}
    return state, staged, numbers, weeks


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
    state, staged, program, life = checked_steps(
        ctx, trainer, state, bundles, starts, key, dims)
    del bundles
    t = phase(ctx, f"{len(weeks) + 1} steps through the window's superstep, "
                   f"across {len(weeks) - 1} restages and "
                   f"{sum(found['new'] for found in life)} programs", t)

    # 4. warm-up: one whole epoch on the current week; the harness's count
    # of compilations at every dispatch boundary of it
    epoch_rng = np.random.default_rng(ctx.seed + 2)
    boundaries = []

    def epoch(st, on_step=None):
        with jax.profiler.TraceAnnotation("bench.train_epoch"):
            st, _ = trainer.train_epoch(st, bundle, epoch_rng, staged=staged,
                                        on_step=on_step)
        return st, trainer._last_epoch_losses

    state, losses = epoch(
        state, lambda _step: boundaries.append(ctx.compiles.count))
    rows = gauge("deeprest_train_optimizer_rows")
    columns = gauge("deeprest_train_projection_columns")
    t = phase(ctx, f"warm-up epoch (loss {float(np.mean(losses)):.5f}; "
                   f"optimizer rows {rows}; compilations at its "
                   f"{len(boundaries)} dispatch boundaries {boundaries})", t)

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
            "compiled_warm_up": (boundaries[-1] - boundaries[0]
                                 if boundaries else None),
            "life": life, "rows": rows, "columns": columns,
            "executables": trainer._superstep._cache_size(),
            "counted": programs_counted(),
            "attempted": attempted, "failed": failed, "values": values,
            "evidence": evidence, "memory_peak_bytes": peak_bytes}


def compilation_faults(life) -> list:
    """(whether, what) for each week of ``life`` (``checked_steps``'s
    records) that compiled where the rule allows nothing: the first week
    stands before the superstep's first dispatch; a later week may compile
    only if its staged program is new to the life."""
    found = []
    for week, record in enumerate(life[1:], start=2):
        compiled = record["compiled_staging"] + record["compiled_dispatch"]
        found.append((
            bool(compiled) and not record["new"],
            f"{compiled} compilations in week {week}, whose program "
            f"{record['program']} the life had dispatched"))
    return found


def faults(ctx, out, program, steps) -> list:
    """What fails a run beside the comparison: (whether, what)."""
    rows, columns = out["rows"], out["columns"]
    f = int(ctx.config["model"]["feature_dim"])
    expected = expected_programs(ctx.mix["params"], f)
    staged = [found["program"] for found in out["life"]]
    distinct = list(dict.fromkeys(expected))
    found = [
        (program["steps_counted"] != steps,
         f"the program counted {program['steps_counted']} steps for {steps}"),
        (staged != expected,
         f"the life staged the programs {staged}; the schedule "
         f"{ctx.mix['params']['hot_paths_by_week']} implies {expected}"),
        (out["executables"] != len(distinct),
         f"{out['executables']} executables of the superstep for the "
         f"{len(distinct)} programs {distinct}"),
        *compilation_faults(out["life"]),
        (out["compiled_warm_up"] is None or out["compiled_warm_up"],
         f"{out['compiled_warm_up']} compilations between the warm-up "
         "epoch's first and last dispatch boundary"),
        (out["compiled"], f"{out['compiled']} compilations inside the window"),
        (rows.get("stale", 0) != 0,
         f"{rows.get('stale')} stale rows after the warm-up epoch ({rows})"),
        (rows.get("updated") != columns.get("total")
         or columns.get("contracted") != columns.get("total"),
         f"optimizer rows {rows} of {columns}: the window runs the dense "
         "form, Adam over all F rows"),
        (out["failed"],
         f"{out['failed']} of {out['attempted']} steps with a non-finite "
         "loss"),
    ]
    booked = _series("deeprest_compilations_total") or ()
    in_epoch = sum(int(n) for labels, n in booked
                   if (labels["program"], labels["phase"])
                   == ("train_superstep", "epoch"))
    found.append((
        in_epoch,
        f"the program booked {in_epoch} compilations of train_superstep to "
        "the set-up phase `epoch`: a new program's belong to its first "
        "dispatch"))
    if out["counted"] is not None:
        # the program's own count, where it keeps one: a first dispatch a
        # program, no more
        found.append((
            out["counted"] != {p: 1 for p in distinct},
            f"the program counted the first dispatches {out['counted']} "
            f"for one each of {distinct}"))
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
    for k in ("rows", "columns", "life", "counted", "compiled_warm_up"):
        out.pop(k)
    # the accepted metrics that apply by runner name read this run as the
    # `train` run it is (train_warm.py's docstring; goes with ROADMAP D19)
    ctx.mix["runner"] = "train"
    return {"correct": correct, **out}
