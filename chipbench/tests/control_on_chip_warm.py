#!/usr/bin/env python3
"""The readings behind a ``train_warm`` cell's limits, on the chip at the
cell's own size:

    chiprun -- python3 chipbench/tests/control_on_chip_warm.py \\
        --workload tenk-retrain-drift --seeds 1 2 3 ... --control-seeds 1 2 3
    chiprun -- python3 chipbench/tests/control_on_chip_warm.py \\
        --workload tenk-retrain-drift --profile 7

``control_on_chip.py`` knows the ``train`` runner's one corpus; this cell's
check crosses a restage between two, so it has a script of its own.  It
drives the runner's own ``dataset``, ``seeded_state`` and ``checked_steps``
(one step on the prior week, the restage, two steps on the current one,
through ``Trainer._superstep``) with ONE trainer over all seeds, and prints
the numbers the cell's comparison reads, beside its limits, for

- ``SOUND``: the program as it is, every seed of ``--seeds``;
- ``SKIPPED``: the program with the off-table pass left out, the seeds of
  ``--control-seeds``.  The superstep's rule is made to read "no moment
  off the table" here, by this script and by no option of the program, so
  the ``fori_loop``'s trip count is 0 and the rows the restage retired
  never move again.  It has to fail ``delta_norm_gap``, at a w_ih leaf;
- ``CONTROL``: the reference put in the program's place in the precision
  below the configuration's (fp8 for bfloat16) and, as a calibration, at
  it: ``control_on_chip.py``'s control on this cell's crossing batches.

The references run after both trainers are freed.  Every line also goes
to ``chiprun_out/control_warm.jsonl``.

``--profile SEED`` instead builds the cell's trainer as the runner does
(the check's three steps, a warm-up epoch) and prints
``Trainer.profile_epoch``'s table of one epoch, with the ``off_table``
row's GB/s against chipbench/peaks.json; the persistent compile cache is
off for that process, because an executable cached by an older checkout
comes back under the scope names it was compiled with.
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
    with open(os.path.join(OUT, "control_warm.jsonl"), "a") as fh:
        fh.write(json.dumps({"kind": kind, "workload": workload,
                             "seed": seed, "what": what, "fails": fails,
                             **numbers}) + "\n")


def without_the_off_table_pass() -> None:
    """From here on a superstep traced by a new ``Trainer`` reads "no
    moment off the table" whatever the state holds."""
    import jax.numpy as jnp

    import deeprest_tpu.train.trainer as T

    T.moments_off_table_are_zero = lambda _opt_state, _live: jnp.bool_(True)


class Cell:
    """The cell's configuration as the runner reads it, and one context a
    seed."""

    def __init__(self, workload):
        import importlib

        import jax

        from chipbench import flops, run
        from deeprest_tpu.config import ModelConfig

        self.run, self.loaded = run, run.load_cell(workload)
        self.model = dict(self.loaded["config"]["model"])
        self.model["quantiles"] = tuple(self.model["quantiles"])
        self.mcfg = ModelConfig(**self.model)
        self.dims = (self.mcfg.num_metrics, self.mcfg.feature_dim,
                     self.mcfg.hidden_size, len(self.mcfg.quantiles))
        self.generator = importlib.import_module(
            f"chipbench.generators.{self.loaded['mix']['generator']}")
        self.device = jax.devices()[0]
        self.peaks = (flops.chip_peaks(self.device.device_kind)
                      if self.device.platform == "tpu" else None)
        self.compiles = run.Compiles()

    def context(self, seed):
        return self.run.Context(self.loaded, seed, 0.0, False, self.device,
                                self.peaks, self.compiles)

    def tcfg(self, ctx):
        from deeprest_tpu.config import TrainConfig

        return TrainConfig(seed=ctx.key_seed(),
                           **self.loaded["config"].get("train", {}))

    def pair(self, seed):
        return self.generator.generate(self.loaded["mix"]["params"], seed,
                                       self.model)


def crossing(cell, seed, trainer=None):
    """The runner's phases 1 to 3 for one seed, on ``trainer`` or a new
    one: the two weeks, the seeded state, one step on the prior week, the
    restage, two steps on the current one."""
    import jax

    from chipbench.runners import train, train_warm
    from deeprest_tpu.config import Config
    from deeprest_tpu.train.trainer import Trainer

    ctx = cell.context(seed)
    tcfg, pair = cell.tcfg(ctx), cell.pair(seed)
    bundles = [train_warm.dataset(pair[k], tcfg, cell.dims[1])
               for k in ("prior", "current")]
    starts = train.check_starts(pair["current"], tcfg, seed, bundles[1])
    if trainer is None:
        trainer = Trainer(Config(model=cell.mcfg, train=tcfg),
                          bundles[1].feature_dim, bundles[1].metric_names)
    key = jax.random.PRNGKey(ctx.key_seed())
    state = train_warm.seeded_state(ctx, trainer, bundles[1], key, cell.dims,
                                    seed=ctx.key_seed())
    state, staged, numbers, compiled = train_warm.checked_steps(
        ctx, trainer, state, bundles, starts, key, cell.dims)
    return {"trainer": trainer, "tcfg": tcfg, "pair": pair,
            "bundle": bundles[1], "starts": starts, "state": state,
            "staged": staged, "numbers": numbers, "compiled": compiled}


def checks(cell, seeds, keep_batches):
    """The check's numbers of one trainer over ``seeds``: {seed: (numbers,
    the reference's batches or None)}."""
    from chipbench.runners import train

    out, trainer = {}, None
    for seed in seeds:
        t0 = time.perf_counter()
        run = crossing(cell, seed, trainer)
        trainer = run["trainer"]
        batches = None
        if keep_batches:
            pair, tcfg, starts = run["pair"], run["tcfg"], run["starts"]
            batches = (train.check_batches(pair["prior"], tcfg, starts[:1])
                       + train.check_batches(pair["current"], tcfg,
                                             starts[1:]))
        out[seed] = (run["numbers"], batches)
        print(f"  seed {seed}: three steps across the restage in "
              f"{time.perf_counter() - t0:.1f} s, {run['compiled']} "
              "compilations after the first dispatch", flush=True)
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
    cell = Cell(args.workload)
    print("device", cell.device.platform, cell.device.device_kind, flush=True)
    if args.profile is not None:
        return profile(cell, args.profile)

    from chipbench.reference import qrnn_ref as ref
    from chipbench.runners import train

    limits = cell.loaded["limits"]
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
        numbers, batches = sound[seed]
        key = jax.random.PRNGKey(ctx.key_seed())

        def reference(precision):
            return ref.train_three_steps(
                ref.init_params(key, *cell.dims), batches, ctx.key_seed(),
                cell.mcfg.quantiles, cell.mcfg.dropout_rate, precision)

        f32 = reference("f32")
        say("SOUND", args.workload, seed, "program",
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

    from chipbench.runners import train_warm
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
    print("optimizer rows",
          train_warm.gauge("deeprest_train_optimizer_rows"), flush=True)
    e, f, h, _ = cell.dims
    moved = 12 * e * f * 3 * h * 4       # six float32 arrays read and written
    for row in table["rows"]:
        if row["scope"] == scopes.OFF_TABLE and row.get("ms_per_step"):
            rate = moved / (row["ms_per_step"] * 1e-3) / 1e9
            peak = cell.peaks["hbm_gb_per_s"] if cell.peaks else float("nan")
            print(f"off_table: {row['ms_per_step']:.4f} ms a step, "
                  f"{moved / 1e9:.3f} GB read and written, {rate:.1f} GB/s, "
                  f"{100 * rate / peak:.1f}% of the HBM peak of {peak} GB/s",
                  flush=True)
    os.makedirs(OUT, exist_ok=True)
    table.pop("trace", None)
    with open(os.path.join(OUT, f"profile_{cell.loaded['cell']['name']}"
                                f"_{seed}.json"), "w") as fh:
        json.dump(table, fh, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
