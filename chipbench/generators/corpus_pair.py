"""Two seeded corpora of one application a week apart, between which some
of its live call paths moved: ``{"prior": corpus, "current": corpus}``,
each as chipbench/generators/corpus.py makes one.

Parameters (the traffic mix's ``params``): ``corpus.py``'s, and

- ``carried_paths``: how many of the ``hot_paths`` call paths keep their
  column from the prior week to the current one.  The others are the same
  paths of the same components under columns that were never hot (a
  release that renamed endpoints: the hashed call path lands elsewhere),
  spread evenly over the popularity ranks (:func:`hot_columns`).

Each week IS ``corpus.generate``: nothing of it is written again here.  It
is handed a generator whose three application draws are the pair's (which
columns are hot: ``choice``; which component each path loads:
``integers``; each component's gains: ``uniform``) and whose every other
draw (the order of the row widths, the slots, the counts, the noise) is
that week's own stream.  So the components, their gains, each path's
popularity and the daily cycle are one application's in both weeks, and,
as in ``corpus.py``, the seed changes which paths are hot, carried and
retired and the noise, never the amount of work: both weeks have the same
multiset of row widths and exactly ``hot_paths`` hot columns for every seed.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators import corpus


class _Week(np.random.Generator):
    """``corpus.generate``'s generator for one week (``default_rng`` hands
    a Generator back as it is): the application's draws are replayed from
    ``app_seed``, the same in every week of a pair."""

    def __init__(self, stream, hot_cols, app_seed):
        super().__init__(np.random.PCG64(stream))
        self._hot_cols = hot_cols
        self._app = np.random.default_rng(app_seed)

    def choice(self, a, size=None, replace=True, **kw):
        if size != len(self._hot_cols) or replace:
            raise ValueError("corpus.generate no longer draws its hot "
                             "columns as this generator expects")
        return self._hot_cols

    def integers(self, *args, **kw):
        return self._app.integers(*args, **kw)

    def uniform(self, *args, **kw):
        return self._app.uniform(*args, **kw)


def hot_columns(params: dict, seed: int, f: int):
    """(the prior week's hot columns, the current week's), position by
    position the same path, and a position is a popularity rank
    (``corpus.generate``: the path at position i has popularity
    1 / (i + 1) ** 0.7).  ``carried_paths`` positions keep their column,
    the others move to columns that were never hot: one of every run of
    ``hot_paths / (hot_paths - carried_paths)`` consecutive ranks, drawn by
    the seed, so every seed moves busy and quiet paths alike and about the
    same share of the traffic."""
    hot, carried = int(params["hot_paths"]), int(params["carried_paths"])
    if not 0 <= carried <= hot or 2 * hot - carried > f:
        raise ValueError(f"carried_paths {carried} of hot_paths {hot} in {f}")
    rng = np.random.default_rng([seed, 2])
    drawn = rng.choice(f, size=2 * hot - carried, replace=False).astype(np.int64)
    prior, current = drawn[:hot], drawn[:hot].copy()
    if hot > carried:
        moved = [rng.choice(run) for run in
                 np.array_split(np.arange(hot), hot - carried)]
        current[moved] = drawn[hot:]
    return prior, current


def generate(params: dict, seed: int, model: dict) -> dict:
    week = {k: v for k, v in params.items() if k != "carried_paths"}
    columns = hot_columns(params, seed, int(model["feature_dim"]))
    return {name: corpus.generate(
                week, _Week(np.random.SeedSequence([seed, i]), cols,
                            [seed, 3]), model)
            for i, (name, cols) in enumerate(zip(("prior", "current"),
                                                 columns))}
