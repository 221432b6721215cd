"""Runner ``train_mesh``: runner ``train`` under the mesh the configuration
names (``"mesh": {"data": 4, "expert": 1, "model": 1}``), as ``deeprest_tpu
train --mesh 4,1,1`` runs it: ``Trainer(Config(model, train, mesh=...))``,
``init_state``, ``stage_dataset``, whole epochs through ``train_epoch`` on
the staged corpus.  The phases, the window, the check and the evidence are
those of chipbench/runners/train.py (read its docstring first); this file
says only what differs.

- **A step** is one optimizer step of the GLOBAL batch (``batch_size`` rows,
  split over the mesh's ``data`` axis), so ``train_steps_per_s`` counts
  updates of the one replicated state, not chips x updates.
- **The check** drives the window's own compiled superstep as there, but an
  epoch here may be one dispatch (32 steps of 128 rows), so "one real step,
  then two" are two plans of the epoch's shape, each dispatched at chunk 0:
  the first holds one real step, the second two; padded steps are skipped
  by the program's own rule.
- **The reference** is chipbench/reference/qrnn_ref.py as it stands: one
  device, float32, three Adam steps on the global batch.  That is what
  synchronous data-parallel training has to equal: every update the mean
  over all rows of the batch, the same state on every chip.  It runs after
  the window on the first chip alone, the program's state freed from all.
- **The trace** of a ``--trace 1`` run is read twice before it is deleted:
  by chipbench/trace_reduce.py as in every cell, and by
  chipbench/readers/collectives.py for the collectives' time from start to
  end and the part of it in which the chip ran nothing else.
- **Evidence** has the keys the ``train`` runner hands (``trace``,
  ``runner``, ``steps``, ``kernel_work_per_step`` at the rows ONE chip's
  kernels see), plus ``collectives``.
- **The accepted per-layer metrics.**  ``run.py`` gives a metric that has no
  ``workloads`` list to every cell whose mix names a runner that the
  metric's file lists, and the seven accepted files list ``train``; none
  may be edited here.  This run is a train run and its evidence is that
  runner's, so once it is made ``run`` names the mix's runner ``train``
  and the seven are read in this cell too (a traced run whose line lacks
  an accepted metric that moves ``train_steps_per_s`` is refused).  The
  line goes when this file is folded into runners/train.py (ROADMAP D19).

The run needs as many devices as the mesh has, and stops at once without.
"""

from __future__ import annotations

import gc
import shutil
import tempfile
import time

import numpy as np

from chipbench.common import harness_only, judge, phase
from chipbench.runners.train import (
    PROGRAM_SURFACE, STEPS_CHECKED, check_batches, check_starts, compare,
)


def check_plans(trainer, starts, num_steps: int):
    """Two host plans of the epoch's shape for the three checked steps:
    ``starts`` [3, B] -> ((starts, weights) with step 0 real, (starts,
    weights) with steps 0 and 1 real)."""
    b = starts.shape[1]
    s_len = trainer._superstep_len(num_steps)
    chunks = -(-num_steps // s_len)
    if s_len < 2:
        raise RuntimeError("the epoch is too short for the check's plans")
    plans = []
    for rows in (starts[:1], starts[1:]):
        plan_starts = np.zeros((chunks, s_len, b), np.int32)
        plan_weights = np.zeros((chunks, s_len, b), np.float32)
        plan_starts[0, :len(rows)] = rows
        plan_weights[0, :len(rows)] = 1.0
        plans.append((plan_starts, plan_weights))
    return plans


def traced(run_slice):
    """``common.traced`` with the trace kept until both reductions have
    read it: (what run_slice returned, trace_reduce's reduction, the
    collectives' reduction)."""
    import jax

    from chipbench import trace_reduce
    from chipbench.readers import collectives

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with jax.profiler.trace(trace_dir, profiler_options=options):
            out = run_slice()
        planes = trace_reduce.read_planes(trace_reduce.find_xplane(trace_dir))
        return (out, trace_reduce.reduce_planes(planes),
                collectives.reduce_planes(planes))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _gauge(name: str) -> dict:
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get(name)
    return {} if gauge is None else {
        "/".join(map(str, k)): v for k, v in gauge.series().items()}


def _program(ctx, raw, config, key, dims):
    """Phases 2 to 5.  Everything the program holds on the devices lives in
    this function, so that it is freed when it returns."""
    import jax
    import jax.numpy as jnp

    from chipbench import flops
    from chipbench.reference import qrnn_ref as ref
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace, FeaturizedData
    from deeprest_tpu.parallel.distributed import stage_plan
    from deeprest_tpu.train.data import prepare_dataset
    from deeprest_tpu.train.trainer import Trainer

    mcfg, tcfg = config.model, config.train
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
    t = phase(ctx, f"dataset ({n_train} train windows, {num_steps} steps of "
                   f"{b} an epoch)", t)

    # 2. one trainer under the configuration's mesh, one state; the seeded
    # weights in its own weights' place, on every chip
    trainer = Trainer(config, bundle.feature_dim, bundle.metric_names)
    missing = [n for n in PROGRAM_SURFACE if not hasattr(trainer, n)]
    if missing:
        raise RuntimeError(f"this program's Trainer has no {missing}")
    ctx.log("mesh", dict(trainer.mesh.shape), "on",
            [d.id for d in trainer.mesh.devices.flat])
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
    ctx.log("projection columns", _gauge("deeprest_train_projection_columns"))

    # 3. the first three steps, through the window's own superstep
    plans = [stage_plan(trainer.mesh, *plan)
             for plan in check_plans(trainer, starts, num_steps)]

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
    state, losses0 = superstep(state, *staged, *plans[0], 0)
    jax.block_until_ready(state)
    with harness_only(ctx, "reading the first gradient's norms"):
        grad_norm = {k: float(v) for k, v in
                     first_grad_norms(state.opt_state).items()}
    state, losses1 = superstep(state, *staged, *plans[1], 0)
    jax.block_until_ready(state)
    with harness_only(ctx, "reading the norms of the parameters' change"):
        delta = {k: float(v) for k, v in
                 delta_norms(state.params, key).items()}
    program = {
        "losses": [float(losses0[0]), float(losses1[0]), float(losses1[1])],
        "grad_norm": grad_norm, "delta_norm": delta,
        "steps_counted": int(state.step),
    }
    del plans
    t = phase(ctx, "first three steps through the window's superstep", t)

    # 4. warm-up: one whole epoch
    epoch_rng = np.random.default_rng(ctx.seed + 2)

    def epoch(st):
        with jax.profiler.TraceAnnotation("bench.train_epoch"):
            st, _ = trainer.train_epoch(st, bundle, epoch_rng, staged=staged)
        return st, trainer._last_epoch_losses

    compiles0 = ctx.compiles.count
    state, losses = epoch(state)
    t = phase(ctx, f"warm-up epoch (loss {float(np.mean(losses)):.5f}, "
                   f"{ctx.compiles.count - compiles0} compilations)", t)
    collective_bytes = _gauge("deeprest_train_collective_bytes")
    ctx.log("collective bytes a step", collective_bytes)

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
        (state, losses), reduced, collectives = traced(lambda: epoch(state))
        elapsed = time.perf_counter() - t_start
        attempted, failed = len(losses), int(np.sum(~np.isfinite(losses)))
        work = flops.gru_kernel_work(
            b // int(trainer.mesh.shape["data"]), w, e, h, training=True,
            act_bytes=jnp.dtype(mcfg.compute_dtype).itemsize)
        evidence = {"trace": reduced, "runner": "train_mesh",
                    "steps": attempted, "kernel_work_per_step": work,
                    "collectives": collectives}
        if collectives["collective_s"] > 0:
            per_step = collectives["collective_s"] / attempted
            moved = sum(collective_bytes.values())
            ctx.log(f"collectives: {1e3 * per_step:.4f} ms a step from start "
                    f"to end, {1e3 * collectives['exposed_s'] / attempted:.4f}"
                    f" with nothing else on the chip; {moved / 1e6:.3f} MB a "
                    f"step handed to them, {moved / per_step / 1e9:.2f} GB/s")
    compiled = ctx.compiles.count - compiles0
    peak_bytes = ctx.memory_peak_bytes()

    rate = attempted / elapsed
    ctx.log(f"window: {attempted} steps of {b} rows in {elapsed:.3f} s")
    if len(epoch_ends) > 1:
        ctx.log("seconds of each epoch of the window: "
                + " ".join(f"{x:.3f}" for x in np.diff(epoch_ends)))
    ctx.log("optimizer rows", _gauge("deeprest_train_optimizer_rows"))
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
    from deeprest_tpu.config import Config, MeshConfig, ModelConfig, TrainConfig

    t = time.perf_counter()
    model = dict(ctx.config["model"])
    model["quantiles"] = tuple(model["quantiles"])
    config = Config(
        model=ModelConfig(**model),
        train=TrainConfig(seed=ctx.key_seed(), **ctx.config.get("train", {})),
        mesh=MeshConfig(**ctx.config["mesh"]))
    if len(jax.devices()) < config.mesh.size:
        raise SystemExit(f"{len(jax.devices())} devices, the mesh "
                         f"{ctx.config['mesh']} asks for {config.mesh.size}")
    mcfg, tcfg = config.model, config.train
    dims = (mcfg.num_metrics, mcfg.feature_dim, mcfg.hidden_size,
            len(mcfg.quantiles))
    key = jax.random.PRNGKey(ctx.key_seed())

    # 1. corpus
    raw = ctx.generator().generate(ctx.mix["params"], ctx.seed, model)
    phase(ctx, f"corpus ({len(raw['traffic'])} buckets)", t)

    out = _program(ctx, raw, config, key, dims)              # 2 to 5
    gc.collect()

    # 6. the reference's three steps on the global batch, on one device,
    # after the program's state is freed
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
    # the accepted metrics that apply by runner name read this run as the
    # `train` run it is (see the docstring; goes with ROADMAP D19)
    ctx.mix["runner"] = "train"
    return {"correct": correct, **out}
