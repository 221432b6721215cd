"""N seeded corpora of one application, a week apart each, while its call
paths are onboarded to tracing in waves: a list of corpora in order, the
last one the current week, each as chipbench/generators/corpus.py makes
one.

Parameters (the traffic mix's ``params``): ``corpus.py``'s but for
``hot_paths``, and

- ``weeks``: how many weeks, two or more;
- ``hot_paths_by_week``: how many of the F call paths carry traffic in each
  week, one number a week, never falling.

It is ``corpus_weeks.py``'s construction with growth in drift's place: each
week IS ``corpus.generate`` under ``corpus_pair._Week`` (one application's
components and gains in every week, every other draw that week's own
stream).  Every path keeps its column for life and none retires: week i's
hot columns are week i - 1's, position by position (a position is a
popularity rank, so the paths onboarded first stay the busiest), followed
by columns that were hot in NO earlier week.  So the live sets are nested,
a row that carries an Adam moment is on every later table, and nothing of
an earlier week goes stale.
"""

from __future__ import annotations

import numpy as np

from chipbench.generators import corpus
from chipbench.generators.corpus_pair import _Week


class _GrowingWeek(_Week):
    """``_Week`` for an estate that grows: the component each path loads is
    drawn for the LAST week's paths in every week and cut to this week's,
    so a path loads one component for life and the application's later
    draws (the gains) are the same in every week."""

    def __init__(self, stream, hot_cols, app_seed, paths_at_last):
        super().__init__(stream, hot_cols, app_seed)
        self._paths_at_last = paths_at_last

    def integers(self, low, high=None, size=None, **kw):
        if size != len(self._hot_cols):
            raise ValueError("corpus.generate no longer draws a component "
                             "a path as this generator expects")
        return super().integers(low, high, size=self._paths_at_last,
                                **kw)[:size]


def hot_columns(params: dict, seed: int, f: int) -> list:
    """Each week's hot columns: a prefix of ONE draw without replacement
    (drawn by the seed as ``corpus_weeks.hot_columns`` draws), as long as
    the week's count."""
    weeks = int(params["weeks"])
    counts = [int(n) for n in params["hot_paths_by_week"]]
    if weeks < 2 or len(counts) != weeks:
        raise ValueError(f"{weeks} weeks, hot_paths_by_week {counts}")
    if counts[0] < 1 or any(b < a for a, b in zip(counts, counts[1:])):
        raise ValueError(
            f"hot_paths_by_week {counts}: an estate that is onboarded "
            "never loses a path (drift is corpus_weeks.py's)")
    if counts[-1] > f:
        raise ValueError(f"{counts[-1]} hot paths in the last week; F is {f}")
    rng = np.random.default_rng([seed, 2])
    drawn = rng.choice(f, size=counts[-1], replace=False).astype(np.int64)
    return [drawn[:n] for n in counts]


def generate(params: dict, seed: int, model: dict) -> list:
    week = {k: v for k, v in params.items()
            if k not in ("hot_paths_by_week", "weeks")}
    columns = hot_columns(params, seed, int(model["feature_dim"]))
    return [corpus.generate(
                {**week, "hot_paths": len(cols)},
                _GrowingWeek(np.random.SeedSequence([seed, i]), cols,
                             [seed, 3], len(columns[-1])),
                model)
            for i, cols in enumerate(columns)]
