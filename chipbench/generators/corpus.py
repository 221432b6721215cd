"""A seeded corpus of one-minute buckets: call-path counts and the
resource series they cause.

Parameters (the traffic mix's ``params``):

- ``buckets``: rows (one-minute buckets) of the corpus;
- ``hot_paths``: how many of the F call paths ever carry traffic;
- ``nnz_lo``, ``nnz_hi``: distinct call paths in one bucket, ``lo <= n <
  hi`` (as ``benchmarks/tenk_bench._synthetic_sparse_rows``: a handful of
  hot paths per bucket);
- ``day``: buckets in the daily cycle of the request rate;
- ``resources``: the resource names of one component, in order; a name
  listed by the program as a level ("usage") accumulates.

Every seed gets the same multiset of row widths in another order, the same
daily cycle and the same sizes: the seed changes which paths are hot and
the noise, never the amount of work.
"""

from __future__ import annotations

import numpy as np


def hot_rows(rng, widths, rate, f, hot_cols, popularity, k):
    """[T, F] count rows: row t carries ``widths[t]`` distinct hot paths (of
    at most ``k``), each a Poisson count at ``rate[t]`` times the path's
    popularity, at least 1.  Returns (traffic, the [T, k] hot-path slots,
    their counts with the unused slots at 0)."""
    t = len(widths)
    slots = np.argsort(rng.random((t, len(hot_cols)), dtype=np.float32),
                       axis=1)[:, :k]
    live = np.arange(k)[None, :] < widths[:, None]
    counts = 1 + rng.poisson(rate[:, None] * popularity[slots])
    counts = np.where(live, counts, 0).astype(np.float32)
    traffic = np.zeros((t, f), np.float32)
    np.put_along_axis(traffic, hot_cols[slots], counts, axis=1)
    return traffic, slots, counts


def generate(params: dict, seed: int, model: dict) -> dict:
    f, e = int(model["feature_dim"]), int(model["num_metrics"])
    t = int(params["buckets"])
    hot = int(params["hot_paths"])
    lo, hi = int(params["nnz_lo"]), int(params["nnz_hi"])
    day = int(params.get("day", 1440))
    names = list(params["resources"])
    comps = e // len(names)
    if comps * len(names) != e:
        raise ValueError(f"{e} metrics are not components x {names}")
    rng = np.random.default_rng(seed)

    hot_cols = rng.choice(f, size=hot, replace=False).astype(np.int64)
    popularity = 1.0 / np.arange(1, hot + 1) ** 0.7
    # the same multiset of row widths for every seed, permuted
    widths = rng.permutation(lo + np.arange(t) % (hi - lo))
    phase = 2 * np.pi * np.arange(t) / day
    rate = 60.0 * (1.0 + 0.6 * np.sin(phase) + 0.2 * np.sin(2 * phase + 1.0))
    traffic, slots, counts = hot_rows(rng, widths, rate, f, hot_cols,
                                      popularity, hi - 1)

    # each hot path loads one component; a component's activity drives its
    # resources (cpu follows it, memory its moving average, writes a share)
    owner = rng.integers(0, comps, size=hot)
    activity = np.zeros((t, comps), np.float32)
    np.add.at(activity, (np.arange(t)[:, None].repeat(hi - 1, 1), owner[slots]),
              counts)
    kernel = np.exp(-np.arange(30) / 10.0)
    kernel /= kernel.sum()
    resources = {}
    for c in range(comps):
        a = activity[:, c]
        ema = np.convolve(a, kernel)[:t]
        gain = rng.uniform(0.5, 2.0, size=len(names))
        for j, name in enumerate(names):
            noise = 1.0 + rng.normal(0.0, 0.03, size=t)
            if name == "cpu":
                y = 2.0 + gain[j] * 0.05 * a * noise
            elif name == "memory":
                y = 200.0 + gain[j] * 0.5 * ema * noise
            elif name == "usage":
                y = 50.0 + np.cumsum(gain[j] * 0.02 * a * noise) / 1024.0
            else:
                y = gain[j] * 0.1 * a * noise
            resources[f"c{c}_{name}"] = y.astype(np.float32)
    return {"traffic": traffic, "resources": resources}
