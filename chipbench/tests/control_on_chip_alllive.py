#!/usr/bin/env python3
"""``control_on_chip_live4k.py`` for a ``train`` cell whose sparse base is
staged in its DENSE form (``tenk-train-alllive``: every hashed column is
live, so the live set pads over the bound of the compact form's rule and no
table is staged):

    chiprun -- python3 chipbench/tests/control_on_chip_alllive.py \\
        --workload tenk-train-alllive --seeds 1 2 3 ... --control-seeds 1 2 3

Arguments, ``SOUND``, the output and ``chiprun_out/control_live4k.jsonl``
are that script's (its ``main`` runs here).  ``DROPPED`` is the same broken
path, a feed that thresholds its call paths before the program's guard, as
it looks where there is no table: that script's control makes the program
stage a table of the most-hit half of the live call paths, and here half of
10,240 pads to 8,192, over the rule's bound, so the form stays dense,
``stage_sparse_base`` is handed ``live=None`` and its control would drop
nothing.  So this one replaces ONE name of ``deeprest_tpu.train.trainer``,
by this script and by no option of the program: ``stage_sparse_base`` is
handed the rows without the entries of the less-hit half of the columns that
carry traffic.  ``live_columns`` is left alone, so the rule weighs the whole
live set and stages the dense form, which this script prints after every
run from the program's gauge (``deeprest_train_projection_columns``:
``contracted`` = ``total``).  The dropped columns' rows of the two w_ih
leaves then never move, the reference's do: it has to fail at least one
limit on every seed.

The fp8 control of the same cell is ``control_on_chip.py --workload
<cell>``.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

DROPPED = ("program in the dense form with the counts of the less-hit half "
           "of its columns left out")


def most_hit_half_only_dense() -> None:
    """From here on a ``Trainer`` stages a sparse corpus in the form its
    whole live set asks for, without the counts of the less-hit half of the
    columns that carry traffic."""
    import numpy as np

    import deeprest_tpu.train.trainer as T

    stage_sparse_base = T.stage_sparse_base

    def without_the_rest(mesh, cols, vals, mn, rg, capacity, live=None):
        if live is not None:
            raise RuntimeError(
                f"a table of {len(live)} columns was staged: this control "
                "is for the dense form (control_on_chip_live4k.py has the "
                "compact form's)")
        hits = np.bincount(cols[vals != 0], minlength=capacity)
        lit = np.flatnonzero(hits)
        keep = lit[np.argsort(-hits[lit], kind="stable")[:len(lit) // 2]]
        kept = np.isin(cols, keep) & (vals != 0)
        print(f"[control] {len(lit)} columns carry traffic; the counts of "
              f"{len(lit) - len(keep)} of them are left out "
              f"({int((vals != 0).sum() - kept.sum())} of "
              f"{int((vals != 0).sum())} entries)", flush=True)
        cols, vals = np.where(kept, cols, 0), np.where(kept, vals, 0)
        return stage_sparse_base(mesh, cols, vals.astype(np.float32), mn, rg,
                                 capacity, live=None)

    T.stage_sparse_base = without_the_rest


def dense_cells_control() -> None:
    """From here on ``control_on_chip_live4k.main`` runs this file's control
    for its own, says so in every ``DROPPED`` line, and prints after every
    run what the program's gauge says was staged."""
    from chipbench.tests import control_on_chip_live4k as base

    say = base.say

    def say_with_the_form(kind, workload, seed, what, *rest):
        from deeprest_tpu.obs.metrics import REGISTRY

        fails = say(kind, workload, seed,
                    DROPPED if kind == "DROPPED" else what, *rest)
        gauge = REGISTRY.get("deeprest_train_projection_columns")
        print("  staged: projection columns", {} if gauge is None else {
            k[0]: int(v) for k, v in gauge.series().items()}, flush=True)
        return fails

    base.most_hit_half_only, base.say = (most_hit_half_only_dense,
                                         say_with_the_form)


if __name__ == "__main__":
    from chipbench.tests import control_on_chip_live4k

    dense_cells_control()
    sys.exit(control_on_chip_live4k.main())
