#!/usr/bin/env python3
"""The readings behind a ``train_growing`` cell's limits, on the chip at the
cell's own size:

    chiprun -- python3 chipbench/tests/control_on_chip_growing.py \\
        --workload tenk-retrain-growing --seeds 1 2 3 ... --control-seeds 1 2 3
    chiprun -- python3 chipbench/tests/control_on_chip_growing.py \\
        --workload tenk-retrain-growing --profile 7

It is ``control_on_chip_weeks.py`` for a life that crosses programs (read
its docstring first): the runner's own ``datasets`` and ``checked_steps``
on the mix's corpora with ONE trainer over all seeds (from the second seed
on every program is one the trainer has dispatched: nothing compiles), and
the numbers the cell's comparison reads, beside its limits, for ``SOUND``
(the program as it is), ``HANDOVER`` (A HANDOVER THAT LOSES THE COMPACT
LIFE: the Adam moments of the two w_ih leaves zeroed at the restage that
takes the dense form, what a "fresh optimizer for the new program"
shortcut would do; nothing of the program is replaced, the state is; it has
to fail ``delta_norm_gap`` at a w_ih leaf) and ``CONTROL`` (the reference
in the program's place at the configuration's precision and in the one
below it).  Each ``SOUND`` line also says what each week's first dispatch
compiled and what the chip held after it.  The references run after the
trainer is freed; their batches wait on the host as their nonzeros.  Every
line also goes to ``chiprun_out/control_warm.jsonl``
(``control_on_chip_warm.say``).

``--profile SEED`` instead builds the cell's trainer as the runner does
(the checked steps, a warm-up epoch) and prints ``Trainer.profile_epoch``'s
table of one epoch of the last week's program, the stage and first-dispatch
spans' tags and the ``set-up:`` line; the persistent compile cache is off
for that process.
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


def zeroed_w_ih_moments(state):
    """``state`` with Adam's ``mu`` and ``nu`` of the layer-0 input weights
    at zero, a leaf at a time (no second copy of a tree is resident)."""
    import jax.numpy as jnp

    adam = state.opt_state[0]
    mu, nu = dict(adam.mu), dict(adam.nu)
    for tree in (mu, nu):
        for name in [k for k in tree if k.endswith("w_ih")]:
            old = tree.pop(name)
            tree[name] = jnp.zeros(old.shape, old.dtype,
                                   device=old.sharding)
            del old
    return state.replace(
        opt_state=(adam._replace(mu=mu, nu=nu), *state.opt_state[1:]))


def losing_the_compact_life(state, _week, tags):
    """``checked_steps``'s ``at_handover``: at the restage that takes the
    dense form from a compact one, drop the w_ih leaves' moments."""
    if tags.get("form") == "dense" and tags.get("restage"):
        return zeroed_w_ih_moments(state)
    return state


def crossing(cell, seed, trainer=None, at_handover=None):
    """The runner's phases 1 to 3 for one seed, on ``trainer`` or a new
    one."""
    import jax

    from chipbench.runners import train_growing
    from deeprest_tpu.config import Config
    from deeprest_tpu.train.trainer import Trainer

    ctx = cell.context(seed)
    tcfg = cell.tcfg(ctx)
    weeks = cell.generator.generate(cell.loaded["mix"]["params"], seed,
                                    cell.model)
    bundles, starts = train_growing.datasets(weeks, tcfg, cell.dims[1], seed)
    bundle = bundles[-1]
    if trainer is None:
        trainer = Trainer(Config(model=cell.mcfg, train=tcfg),
                          bundle.feature_dim, bundle.metric_names)
    key = jax.random.PRNGKey(ctx.key_seed())
    state = train_growing.seeded_state(ctx, trainer, bundle, key, cell.dims,
                                       seed=ctx.key_seed())
    state, staged, numbers, life = train_growing.checked_steps(
        ctx, trainer, state, bundles, starts, key, cell.dims,
        at_handover=at_handover)
    return {"trainer": trainer, "tcfg": tcfg, "weeks": weeks,
            "bundle": bundle, "starts": starts, "state": state,
            "staged": staged, "numbers": numbers, "life": life}


def checks(cell, seeds, keep_batches, trainer=None, at_handover=None):
    """The check's numbers of one trainer over ``seeds``: ({seed: (numbers,
    the reference's batches packed or None)}, the trainer)."""
    from chipbench.runners import train_growing
    from chipbench.tests.control_on_chip_weeks import _packed

    out = {}
    for seed in seeds:
        t0 = time.perf_counter()
        run = crossing(cell, seed, trainer, at_handover)
        trainer = run["trainer"]
        batches = None
        if keep_batches:
            batches = _packed(train_growing.reference_batches(
                run["weeks"], run["tcfg"], run["starts"]))
        out[seed] = (run["numbers"], batches)
        life = [(w["program"], w["compiled_staging"], w["compiled_dispatch"],
                 round(w["bytes_in_use"] / 1e9, 3),
                 round(w["bytes_reserved"] / 1e9, 3)) for w in run["life"]]
        print(f"  seed {seed}: {len(run['weeks']) + 1} steps across "
              f"{len(run['weeks']) - 1} restages in "
              f"{time.perf_counter() - t0:.1f} s; a week: (program, compiled "
              f"staging, compiled dispatching, GB in use, GB reserved) "
              f"{life}; {trainer._superstep._cache_size()} executables, "
              f"counted {train_growing.programs_counted()}", flush=True)
        del run
    return out, trainer


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
    from chipbench.tests.control_on_chip_warm import Cell, say
    from chipbench.tests.control_on_chip_weeks import _unpacked

    cell = Cell(args.workload)
    print("device", cell.device.platform, cell.device.device_kind, flush=True)
    if args.profile is not None:
        return profile(cell, args.profile)

    from chipbench.reference import qrnn_ref as ref
    from chipbench.runners import train

    limits = cell.loaded["limits"]
    seeds = list(dict.fromkeys(args.seeds + args.control_seeds))
    sound, trainer = checks(cell, seeds, keep_batches=True)
    lost, trainer = checks(cell, args.control_seeds, keep_batches=False,
                           trainer=trainer,
                           at_handover=losing_the_compact_life)
    del trainer
    gc.collect()
    from chipbench.tests.control_on_chip import AT, BELOW

    dtype = cell.mcfg.compute_dtype
    for seed in seeds:
        ctx = cell.context(seed)
        numbers, packed = sound[seed]
        batches = _unpacked(packed)
        key = jax.random.PRNGKey(ctx.key_seed())

        def reference(precision):
            return ref.train_three_steps(
                ref.init_params(key, *cell.dims), batches, ctx.key_seed(),
                cell.mcfg.quantiles, cell.mcfg.dropout_rate, precision)

        f32 = reference("f32")
        say("SOUND", args.workload, seed, "program",
            train.compare(numbers, f32), limits)
        if seed in lost:
            say("HANDOVER", args.workload, seed,
                "program, the w_ih moments zeroed at the restage that takes "
                "the dense form", train.compare(lost[seed][0], f32), limits)
            for precision in dict.fromkeys((AT[dtype], BELOW[dtype])):
                say("CONTROL", args.workload, seed,
                    f"reference in {precision}",
                    train.compare(reference(precision), f32), limits)
    return 0


def profile(cell, seed) -> int:
    import numpy as np

    from deeprest_tpu.obs import profiler, setup, spans

    was, spans.RECORDER.enabled = spans.RECORDER.enabled, True
    try:
        run = crossing(cell, seed)
    finally:
        spans.RECORDER.enabled = was
    for span in spans.RECORDER.snapshot():
        if span.name in ("train.stage", "train.first_dispatch"):
            print(span.name, dict(span.tags), flush=True)
    trainer, bundle = run["trainer"], run["bundle"]
    state, staged = run["state"], run["staged"]
    rng = np.random.default_rng(seed + 2)
    state, _ = trainer.train_epoch(state, bundle, rng, staged=staged)
    with tempfile.TemporaryDirectory(prefix="chipbench-profile-") as tmp:
        state, table = trainer.profile_epoch(state, bundle, rng, staged, tmp)
    print(profiler.format_table(table), flush=True)
    print(setup.format_setup(setup.setup_table()), flush=True)
    os.makedirs(OUT, exist_ok=True)
    table.pop("trace", None)
    with open(os.path.join(OUT, f"profile_{cell.loaded['cell']['name']}"
                                f"_{seed}.json"), "w") as fh:
        json.dump(table, fh, indent=1, default=str)
    return 0


if __name__ == "__main__":
    sys.exit(main())
