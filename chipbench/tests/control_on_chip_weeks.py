#!/usr/bin/env python3
"""The readings behind a ``train_weeks`` cell's limits, on the chip at the
cell's own size:

    chiprun -- python3 chipbench/tests/control_on_chip_weeks.py \\
        --workload tenk-retrain-live4k --seeds 1 2 3 ... --control-seeds 1 2 3
    chiprun -- python3 chipbench/tests/control_on_chip_weeks.py \\
        --workload tenk-retrain-live4k --profile 7

It is ``control_on_chip_warm.py`` for N weeks (read its docstring first):
the runner's own ``datasets`` and ``checked_steps`` on the mix's corpora (a
step on each prior week, two on the current, a restage between, through
``Trainer._superstep``) with ONE trainer over all seeds, and the numbers
the cell's comparison reads, beside its limits, for ``SOUND`` (the program
as it is), ``SKIPPED`` (the off-table pass left out by
``control_on_chip_warm.without_the_off_table_pass``: the rows every
release retired never move again; it has to fail ``delta_norm_gap`` at a
w_ih leaf) and ``CONTROL`` (the reference in the program's place at the
configuration's precision and in the one below it).  Each ``SOUND`` line
also says how many rows were stale after the checked steps, of how many
columns the releases retired.  The references run after both trainers are
freed; their batches wait on the host as their nonzeros.  Every line also
goes to ``chiprun_out/control_warm.jsonl`` (``control_on_chip_warm.say``).

``--profile SEED`` instead builds the cell's trainer as the runner does
(the checked steps, a warm-up epoch) and prints ``Trainer.profile_epoch``'s
table of one epoch with the ``off_table`` row and the gauge; the
persistent compile cache is off for that process.
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
OUT = os.path.join(ROOT, "chiprun_out")        # the profile's table


def crossing(cell, seed, trainer=None):
    """The runner's phases 1 to 3 for one seed, on ``trainer`` or a new
    one."""
    import jax

    from chipbench.runners import train_weeks
    from deeprest_tpu.config import Config
    from deeprest_tpu.train.trainer import Trainer

    ctx = cell.context(seed)
    tcfg = cell.tcfg(ctx)
    weeks = cell.generator.generate(cell.loaded["mix"]["params"], seed,
                                    cell.model)
    bundles, starts = train_weeks.datasets(weeks, tcfg, cell.dims[1], seed)
    bundle = bundles[-1]
    if trainer is None:
        trainer = Trainer(Config(model=cell.mcfg, train=tcfg),
                          bundle.feature_dim, bundle.metric_names)
    key = jax.random.PRNGKey(ctx.key_seed())
    state = train_weeks.seeded_state(ctx, trainer, bundle, key, cell.dims,
                                     seed=ctx.key_seed())
    state, staged, numbers, compiled, stagings = train_weeks.checked_steps(
        ctx, trainer, state, bundles, starts, key, cell.dims)
    return {"trainer": trainer, "tcfg": tcfg, "weeks": weeks,
            "bundle": bundle, "starts": starts, "state": state,
            "staged": staged, "numbers": numbers, "compiled": compiled,
            "stagings": stagings}


def _packed(batches):
    """The reference's batches as their nonzeros (a window of 10,240 call
    paths holds a few dozen)."""
    import numpy as np

    packed = []
    for x, y in batches:
        at = np.flatnonzero(x)
        packed.append(((x.shape, at, x.ravel()[at]), y))
    return packed


def _unpacked(packed):
    import numpy as np

    batches = []
    for (shape, at, values), y in packed:
        x = np.zeros(int(np.prod(shape)), np.float32)
        x[at] = values
        batches.append((x.reshape(shape), y))
    return batches


def checks(cell, seeds, keep_batches):
    """The check's numbers of one trainer over ``seeds``: {seed: (numbers,
    the reference's batches packed or None, the stale rows after the
    checked steps)}."""
    from chipbench.runners import train_weeks

    out, trainer = {}, None
    for seed in seeds:
        t0 = time.perf_counter()
        run = crossing(cell, seed, trainer)
        trainer = run["trainer"]
        stale = int(trainer._stale_rows(run["state"].opt_state,
                                        run["staged"][0].live))
        batches = None
        if keep_batches:
            batches = _packed(train_weeks.reference_batches(
                run["weeks"], run["tcfg"], run["starts"]))
        out[seed] = (run["numbers"], batches, stale)
        moved = [(t.get("left"), t.get("entered")) for t in run["stagings"]]
        print(f"  seed {seed}: {len(run['weeks']) + 1} steps across "
              f"{len(run['weeks']) - 1} restages in "
              f"{time.perf_counter() - t0:.1f} s, {run['compiled']} "
              f"compilations after the first dispatch, left/entered {moved}, "
              f"{stale} stale rows, {trainer._superstep._cache_size()} "
              "executables", flush=True)
        del run
    return out


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
    from chipbench.tests.control_on_chip_warm import (
        Cell, say, without_the_off_table_pass,
    )

    cell = Cell(args.workload)
    print("device", cell.device.platform, cell.device.device_kind, flush=True)
    if args.profile is not None:
        return profile(cell, args.profile)

    from chipbench.reference import qrnn_ref as ref
    from chipbench.runners import train, train_weeks

    limits = cell.loaded["limits"]
    retired = train_weeks.retired_columns(cell.loaded["mix"]["params"])
    seeds = list(dict.fromkeys(args.seeds + args.control_seeds))
    sound = checks(cell, seeds, keep_batches=True)
    gc.collect()
    without_the_off_table_pass()
    skipped = checks(cell, args.control_seeds, keep_batches=False)
    gc.collect()
    from chipbench.tests.control_on_chip import AT, BELOW

    dtype = cell.mcfg.compute_dtype
    for seed in seeds:
        ctx = cell.context(seed)
        numbers, packed, stale = sound[seed]
        batches = _unpacked(packed)
        key = jax.random.PRNGKey(ctx.key_seed())

        def reference(precision):
            return ref.train_three_steps(
                ref.init_params(key, *cell.dims), batches, ctx.key_seed(),
                cell.mcfg.quantiles, cell.mcfg.dropout_rate, precision)

        f32 = reference("f32")
        say("SOUND", args.workload, seed,
            f"program ({stale} stale rows of {retired} retired)",
            train.compare(numbers, f32), limits)
        if seed in skipped:
            say("SKIPPED", args.workload, seed,
                "program without the off-table pass",
                train.compare(skipped[seed][0], f32), limits)
            for precision in dict.fromkeys((AT[dtype], BELOW[dtype])):
                say("CONTROL", args.workload, seed,
                    f"reference in {precision}",
                    train.compare(reference(precision), f32), limits)
    return 0


def profile(cell, seed) -> int:
    import numpy as np

    from chipbench.runners import train_weeks
    from deeprest_tpu.obs import profiler
    from deeprest_tpu.ops import scopes

    run = crossing(cell, seed)
    trainer, bundle = run["trainer"], run["bundle"]
    state, staged = run["state"], run["staged"]
    rng = np.random.default_rng(seed + 2)
    state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
    with tempfile.TemporaryDirectory(prefix="chipbench-profile-") as tmp:
        state, table = trainer.profile_epoch(state, bundle, rng, staged, tmp)
    print(profiler.format_table(table), flush=True)
    rows = train_weeks.gauge("deeprest_train_optimizer_rows")
    print("optimizer rows", rows, flush=True)
    for row in table["rows"]:
        if row["scope"] == scopes.OFF_TABLE and row.get("ms_per_step"):
            steps = len(trainer._last_epoch_losses)
            dispatches = -(-steps // trainer._superstep_len(steps))
            per_trip = (row["ms_per_step"] * steps
                        / max(dispatches * rows.get("trips", 0), 1))
            print(f"off_table: {row['ms_per_step']:.4f} ms a step, "
                  f"{row['ms_per_step'] * steps / dispatches:.2f} ms a "
                  f"dispatch, {per_trip:.3f} ms a trip of "
                  f"{rows.get('trips')} a dispatch", flush=True)
    os.makedirs(OUT, exist_ok=True)
    table.pop("trace", None)
    with open(os.path.join(OUT, f"profile_{cell.loaded['cell']['name']}"
                                f"_{seed}.json"), "w") as fh:
        json.dump(table, fh, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
