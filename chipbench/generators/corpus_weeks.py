"""N seeded corpora of one application, a week apart each, between which
some of its live call paths moved at every release: a list of corpora in
order, the last one the current week, each as
chipbench/generators/corpus.py makes one.

Parameters (the traffic mix's ``params``): ``corpus_pair.py``'s, and

- ``weeks``: how many weeks, two or more; ``weeks - 1`` releases lie
  between the first and the last.

It is ``corpus_pair.py``'s construction, release after release: each week
IS ``corpus.generate`` under the pair's own ``_Week`` generator (one
application's components, gains and popularity in every week, every other
draw that week's own stream); at each release ``hot_paths -
carried_paths`` paths, one of every run of ``hot_paths / (hot_paths -
carried_paths)`` consecutive popularity ranks, drawn anew by the seed,
move to columns that were hot in NO earlier week, so a column a release
retires never comes back and ``weeks - 1`` releases retire ``(weeks - 1)
* (hot_paths - carried_paths)`` distinct columns.  For ``weeks`` 2 the
draws are the pair's, in the pair's order, and the two corpora are
``corpus_pair.generate``'s to the bit.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators import corpus
from chipbench.generators.corpus_pair import _Week


def hot_columns(params: dict, seed: int, f: int) -> list:
    """Each week's hot columns, position by position the same path (a
    position is a popularity rank, as in ``corpus_pair.hot_columns``)."""
    hot, carried = int(params["hot_paths"]), int(params["carried_paths"])
    weeks = int(params["weeks"])
    moved_a_release = hot - carried
    if weeks < 2 or not 0 <= carried <= hot:
        raise ValueError(f"{weeks} weeks, carried_paths {carried} of "
                         f"hot_paths {hot}")
    if hot + (weeks - 1) * moved_a_release > f:
        raise ValueError(
            f"{weeks} weeks of {hot} hot paths that move {moved_a_release} "
            f"a release need {hot + (weeks - 1) * moved_a_release} columns "
            f"never hot before; F is {f}")
    rng = np.random.default_rng([seed, 2])
    drawn = rng.choice(f, size=hot + (weeks - 1) * moved_a_release,
                       replace=False).astype(np.int64)
    columns = [drawn[:hot]]
    for release in range(weeks - 1):
        current = columns[-1].copy()
        if moved_a_release:
            moved = [rng.choice(run) for run in
                     np.array_split(np.arange(hot), moved_a_release)]
            at = hot + release * moved_a_release
            current[moved] = drawn[at:at + moved_a_release]
        columns.append(current)
    return columns


def generate(params: dict, seed: int, model: dict) -> list:
    week = {k: v for k, v in params.items()
            if k not in ("carried_paths", "weeks")}
    columns = hot_columns(params, seed, int(model["feature_dim"]))
    return [corpus.generate(
                week, _Week(np.random.SeedSequence([seed, i]), cols,
                            [seed, 3]), model)
            for i, cols in enumerate(columns)]
