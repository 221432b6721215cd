#!/usr/bin/env python3
"""The readings behind a ``train_accum`` cell's limits, on the chip at the
cell's own size:

    chiprun -- python3 chipbench/tests/control_on_chip_accum.py \\
        --workload tenk-train-accum8 --seeds 1 2 3 ... --control-seeds 1 2
    chiprun -- python3 chipbench/tests/control_on_chip_accum.py \\
        --workload tenk-train-accum8 --profile 7

It drives the runner's own ``check_starts``, ``checked_updates`` and
``check_groups`` (three updates through ``Trainer._superstep``, the third a
ragged group) with ONE trainer over all seeds, frees it, and prints the
numbers the cell's comparison reads, beside its limits, for

- ``SOUND``: the program as it is against the float32 reference
  (chipbench/reference/qrnn_accum_ref.py), every seed;
- ``CONTROL``, the seeds of ``--control-seeds``, each of which has to fail
  at least one limit: the reference put in the program's place
  - in the precision below the configuration's (fp8 operands for bfloat16)
    and, as a calibration that must NOT fail, at it;
  - ``lost_microbatch``: with one microbatch's gradient of the first update
    never added (the sum still divided by all the group's windows);
  - ``ignored_weights``: the mean of the microbatches' means in the place of
    the mean over the real windows (they differ in the ragged third update);
  - ``summed``: the sum of the microbatches' mean-loss gradients, what the
    program made until ISSUE 48 (the first gradient reads G times too long;
    Adam's step all but forgives it).

Every line also goes to ``chiprun_out/control_accum.jsonl``.

``--profile SEED`` instead builds the cell's trainer as the runner does
(the check's three updates, a warm-up epoch) and prints
``Trainer.profile_epoch``'s table of one epoch (the ``accumulate`` and
``optimizer`` rows by the MICROBATCH, as every row), the epoch span's tags
and the ``set-up:`` line; the persistent compile cache is off for that
process, because an executable cached by an older checkout comes back under
the scope names it was compiled with.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")


def say(kind, workload, seed, what, numbers, limits):
    fails = [k for k, lim in limits.items() if not numbers[k] <= lim]
    print(f"{kind} {workload} seed {seed} {what}: {json.dumps(numbers)} "
          f"limits {json.dumps(limits)} fails {fails}", flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "control_accum.jsonl"), "a") as fh:
        fh.write(json.dumps({"kind": kind, "workload": workload,
                             "seed": seed, "what": what, "fails": fails,
                             **numbers}) + "\n")


def three_updates(cell, seed, trainer=None):
    """The runner's phases 1 to 3 for one seed, on ``trainer`` or a new
    one."""
    import jax

    from chipbench.runners import train_accum
    from deeprest_tpu.config import Config
    from deeprest_tpu.train.trainer import Trainer

    ctx = cell.context(seed)
    tcfg, raw = cell.tcfg(ctx), cell.pair(seed)
    bundle = train_accum.dataset(raw, tcfg, cell.dims[1])
    starts, weights = train_accum.check_starts(raw, tcfg, seed, bundle)
    if trainer is None:
        trainer = Trainer(Config(model=cell.mcfg, train=tcfg),
                          bundle.feature_dim, bundle.metric_names)
    key = jax.random.PRNGKey(ctx.key_seed())
    state = train_accum.seeded_state(ctx, trainer, bundle, key, cell.dims,
                                     seed=ctx.key_seed())
    staged = trainer.stage_dataset(bundle)
    num_steps = -(-bundle.num_train_windows // tcfg.batch_size)
    state, numbers = train_accum.checked_updates(
        ctx, trainer, state, staged, starts, weights, num_steps, key,
        cell.dims)
    return {"trainer": trainer, "tcfg": tcfg, "raw": raw, "bundle": bundle,
            "starts": starts, "weights": weights, "state": state,
            "staged": staged, "numbers": numbers}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--profile", type=int, default=None, metavar="SEED")
    args = ap.parse_args()
    from deeprest_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.profile is not None:
        jax.config.update("jax_enable_compilation_cache", False)
    from chipbench.tests.control_on_chip_warm import Cell

    cell = Cell(args.workload)
    print("device", cell.device.platform, cell.device.device_kind, flush=True)
    if args.profile is not None:
        return profile(cell, args.profile)

    from chipbench.reference import qrnn_accum_ref as accum_ref
    from chipbench.reference import qrnn_ref as ref
    from chipbench.runners import train_accum
    from chipbench.tests.control_on_chip import AT, BELOW

    limits = cell.loaded["limits"]
    seeds = list(dict.fromkeys(args.seeds + args.control_seeds))
    sound, trainer = {}, None
    for seed in seeds:
        t0 = time.perf_counter()
        run = three_updates(cell, seed, trainer)
        trainer = run["trainer"]
        sound[seed] = {k: run[k] for k in ("numbers", "raw", "tcfg",
                                           "starts", "weights")}
        print(f"  seed {seed}: three updates in "
              f"{time.perf_counter() - t0:.1f} s, counted "
              f"{run['numbers']['steps_counted']} steps, "
              f"{run['numbers']['updates_counted']} updates", flush=True)
        del run
    del trainer
    gc.collect()

    dtype = cell.mcfg.compute_dtype
    for seed in seeds:
        ctx, kept = cell.context(seed), sound.pop(seed)
        key = jax.random.PRNGKey(ctx.key_seed())
        groups = train_accum.check_groups(kept["raw"], kept["tcfg"],
                                          kept["starts"])

        def reference(precision="f32", control=None):
            t0 = time.perf_counter()
            out = accum_ref.train_three_updates(
                ref.init_params(key, *cell.dims), groups, kept["weights"],
                ctx.key_seed(), cell.mcfg.quantiles, cell.mcfg.dropout_rate,
                precision, control)
            print(f"  seed {seed}: reference {precision} {control or ''} in "
                  f"{time.perf_counter() - t0:.1f} s", flush=True)
            return out

        f32 = reference()
        say("SOUND", args.workload, seed, "program",
            train_accum.compare(kept["numbers"], f32), limits)
        if seed in args.control_seeds:
            for precision in dict.fromkeys((AT[dtype], BELOW[dtype])):
                say("CONTROL", args.workload, seed,
                    f"reference in {precision}",
                    train_accum.compare(reference(precision), f32), limits)
            for control in accum_ref.CONTROLS[1:]:
                say("CONTROL", args.workload, seed, f"reference {control}",
                    train_accum.compare(reference(control=control), f32),
                    limits)
        del groups, kept
    return 0


def profile(cell, seed) -> int:
    import numpy as np

    from deeprest_tpu.obs import profiler, spans
    from deeprest_tpu.obs import setup as obs_setup

    run = three_updates(cell, seed)
    trainer, bundle = run["trainer"], run["bundle"]
    state, staged = run["state"], run["staged"]
    rng = np.random.default_rng(seed + 2)
    state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
    with tempfile.TemporaryDirectory(prefix="chipbench-profile-") as tmp:
        state, table = trainer.profile_epoch(state, bundle, rng, staged, tmp)
    print(profiler.format_table(table), flush=True)
    epochs = [s for s in spans.RECORDER.snapshot() if s.name == "train.epoch"]
    print("train.epoch tags", dict(epochs[-1].tags) if epochs else "no span",
          flush=True)
    print(obs_setup.format_setup(table["setup"]), flush=True)
    os.makedirs(OUT, exist_ok=True)
    table.pop("trace", None)
    with open(os.path.join(OUT, f"profile_{cell.loaded['cell']['name']}"
                                f"_{seed}.json"), "w") as fh:
        json.dump(table, fh, indent=1, default=str)
    with open(os.path.join(OUT, f"hlo_{cell.loaded['cell']['name']}.txt"),
              "w") as fh:
        fh.write(trainer._dispatched_program_text(state))
    return 0


if __name__ == "__main__":
    sys.exit(main())
