#!/usr/bin/env python3
"""The readings behind the limits of a ``train`` cell whose configuration
guarantees that no call path is dropped, on the chip at the cell's own size:

    chiprun -- python3 chipbench/tests/control_on_chip_live4k.py \\
        --workload tenk-train-live4k --seeds 1 2 3 ... --control-seeds 1 2 3

One process runs the cell once a seed through ``chipbench.run.run_cell``
(what ``python3 chipbench/run.py`` calls: the corpus, a new ``Trainer``, the
three checked steps through the window's own superstep, a warm-up epoch, a
window of ``--seconds``, the reference after it) and prints the numbers the
cell's comparison read, beside its limits, for

- ``SOUND``: the program as it is, every seed of ``--seeds``;
- ``DROPPED``: the broken path the configuration's guarantee names, the
  seeds of ``--control-seeds``: the program made to stage a table of the
  most-hit half of the live call paths (2,048 of 4,096 in
  ``tenk-train-live4k``) and to leave the other half out.  Two names of
  ``deeprest_tpu.train.trainer`` are replaced, by this script and by no
  option of the program: ``live_columns`` answers with the most-hit half,
  so the rule of the compact form takes a table of it, and
  ``stage_sparse_base`` is handed the rows without the entries off that
  table (the program's own guard, ``ops.densify.compact_rows``, raises for
  a nonzero count off the table: the fault this control stands for is a
  feed that thresholds BEFORE that guard).  It has to fail at least one
  limit on every seed.

The fp8 control of the same cell is ``control_on_chip.py --workload
<cell>``.  Every line also goes to ``chiprun_out/control_live4k.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out")


def most_hit_half_only() -> None:
    """From here on a ``Trainer`` stages a sparse corpus as if only the
    most-hit half of its live call paths existed."""
    import numpy as np

    import deeprest_tpu.train.trainer as T

    live_columns, stage_sparse_base = T.live_columns, T.stage_sparse_base

    def most_hit(cols, vals, mn, rg, capacity):
        live = live_columns(cols, vals, mn, rg, capacity)
        hits = np.bincount(cols[vals != 0], minlength=capacity)[live]
        keep = np.argsort(-hits, kind="stable")[:len(live) // 2]
        return np.sort(live[keep]).astype(np.int32)

    def without_the_rest(mesh, cols, vals, mn, rg, capacity, live=None):
        if live is not None:
            kept = np.isin(cols, live) & (vals != 0)
            cols, vals = np.where(kept, cols, 0), np.where(kept, vals, 0)
        return stage_sparse_base(mesh, cols, vals.astype(np.float32), mn, rg,
                                 capacity, live=live)

    T.live_columns, T.stage_sparse_base = most_hit, without_the_rest


def readings(workload: str, seeds, seconds: float):
    """One ``run_cell`` a seed: yields (seed, the numbers the cell's
    comparison read, the result object)."""
    from chipbench import run
    from chipbench.runners import train

    compare = train.compare
    for seed in seeds:
        seen = []

        def recording(program, reference):
            seen.append(compare(program, reference))
            return seen[-1]

        train.compare = recording
        try:
            result = run.run_cell(workload, seed, seconds, False)
        finally:
            train.compare = compare
        yield seed, seen[-1], result


def say(kind, workload, seed, what, numbers, result, limits) -> list:
    fails = [k for k, lim in limits.items() if not numbers[k] <= lim]
    line = {"kind": kind, "workload": workload, "seed": seed, "what": what,
            "fails": fails, "correct": result["correct"],
            "failed": result["failed"], "attempted": result["attempted"],
            "device": result["device"]["kind"], **numbers}
    print(f"{kind} {workload} seed {seed} {what}: {json.dumps(numbers)} "
          f"limits {json.dumps(limits)} fails {fails} correct "
          f"{result['correct']}", flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "control_live4k.jsonl"), "a") as fh:
        fh.write(json.dumps(line) + "\n")
    return fails


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0,
                    help="the window of each run (0: one epoch)")
    args = ap.parse_args()
    from chipbench import run

    limits = run.load_cell(args.workload)["limits"]
    for seed, numbers, result in readings(args.workload, args.seeds,
                                          args.seconds):
        say("SOUND", args.workload, seed, "program", numbers, result, limits)
    most_hit_half_only()
    passed = []
    for seed, numbers, result in readings(args.workload, args.control_seeds,
                                          args.seconds):
        if not say("DROPPED", args.workload, seed,
                   "program with the less-hit half of the live call paths "
                   "left out of its table", numbers, result, limits):
            passed.append(seed)
    if passed:
        print(f"THE CONTROL PASSED on seeds {passed}: the limits do not hold "
              "the configuration's guarantee", flush=True)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
