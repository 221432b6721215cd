"""On-device densification of padded-COO traffic rows.

The 10k-endpoint regime (ROADMAP item 4, PAPERS [1]) makes the per-window
call-path count vector >99% zeros: any one window touches a handful of
call paths out of F=10240 columns.  The sparse-first pipeline therefore
carries traffic as padded-COO rows — ``(cols[..., K], vals[..., K])`` with
``K = nnz_cap`` real entries padded by ``(0, 0.0)`` — from featurization
(``CallPathSpace.extract_sparse``) through the ring corpus
(``SparseSeriesRing``) and the host→device feed, and densifies to the
model's static ``[..., F]`` inside the existing jit boundaries via the
scatter-add here.  Host→device bytes drop ~F/(2K) (cols int32 + vals
float32 vs dense float32): ~80× at F=10240, K=64.

Numerics contract (pinned by tests/test_sparse.py):

- ``densify_coo`` is BIT-EXACT vs the dense reference
  (``np.bincount``-built vectors): real columns within a row are unique
  (``extract_sparse`` goes through ``np.unique``; ``sparsify_rows``
  through ``np.flatnonzero``), so every output element receives exactly
  one real contribution, and the ``(0, 0.0)`` padding contributes exact
  float zeros (x + 0.0 == x for the non-negative count values carried
  here).  Scatter order therefore cannot re-associate anything.
- ``normalize_minmax`` mirrors ``MinMaxStats.apply`` exactly (including
  the degenerate-range passthrough); stats must enter the jit as runtime
  ARGUMENTS, never baked constants — a constant range lets XLA
  strength-reduce the divide into a multiply-by-reciprocal, which breaks
  bit parity with the host path (the serve/fused.py lesson).

The compact form (live-column compaction, PR 25).  A hashed path space is
sized for the call paths that MAY come; a staged corpus holds the paths
that DID.  A column is *live* when its normalized value can be nonzero in
some staged row (:func:`live_columns`); every other column is exactly 0.0
in every window, and a product with it adds nothing to the layer-0
projection.  When the live set, padded to a power of two
(:func:`compact_table`), is at most half of F (:func:`compact_rule` and
the readings it stands on), the trainer stages the
base with that table (``SparseBase.live``): ``cols`` are then RANKS in the
table, the statistics are taken at the table, and
:func:`gather_densify_normalize` builds ``[..., W, U_pad]`` windows whose
column j is dense column ``live[j]`` (by :func:`densify_compare`, which
at such widths is cheaper than the scatter and gives the same bits); the
model contracts over those columns only (``QuantileGRU.__call__`` with
``live_cols``).  The dense ``[..., W, F]`` window is never built.
Dropping exact zeros from a sum loses nothing, but the sum's order
changes, so the compact form agrees with the dense form to float
tolerance, not bit for bit.  With ``live=None`` (a wide live set, or F
sharded over the mesh's ``model`` axis) everything above holds unchanged.
"""

from __future__ import annotations

import numpy as np

from deeprest_tpu.ops import scopes

try:  # host-only callers (lint) may lack an initialized backend
    import flax.struct
    import jax
    import jax.numpy as jnp

    _HAVE_JAX = True
except Exception:  # pragma: no cover - jax is a hard dep of the repo
    _HAVE_JAX = False


DEFAULT_NNZ_CAP = 64
MIN_COMPACT_WIDTH = 128          # one lane tile: no narrower table
COMPARE_MAX_WIDTH = 4096         # densify_compare beats the scatter up to here


if _HAVE_JAX:

    @flax.struct.dataclass
    class SparseBase:
        """Device-staged padded-COO base series plus its normalization.

        The sparse twin of the staged dense ``x_base``: ``cols``/``vals``
        are ``[T, K]`` RAW (un-normalized) traffic rows resident in HBM;
        the train/eval steps gather windows by start index, densify via
        :func:`densify_coo`, and normalize on device with the staged
        ``mn``/``rg`` runtime arguments.  ``capacity`` is the static
        dense width — a Python int excluded from the pytree so jit
        treats it as a compile-time constant.

        ``live`` is None (windows are ``capacity`` wide) or the sorted
        ``[U_pad]`` table of :func:`compact_table`: ``cols`` then hold
        ranks in it, ``mn``/``rg`` the statistics at it, and windows are
        ``U_pad`` wide (module docstring, the compact form).
        """

        cols: object                 # [T, K] int32 device array
        vals: object                 # [T, K] float32 device array
        mn: object                   # broadcastable x_stats.min
        rg: object                   # broadcastable x_stats.range
        live: object = None          # [U_pad] int32 dense columns, or None
        capacity: int = flax.struct.field(pytree_node=False, default=0)

        @property
        def width(self) -> int:
            """Columns of a gathered window: U_pad, or the dense F."""
            return self.capacity if self.live is None else self.live.shape[0]

    def densify_coo(cols, vals, capacity: int):
        """``(cols[..., K], vals[..., K])`` padded-COO → ``[..., capacity]``.

        One scatter-add per call, batched over every leading axis; see the
        module docstring for why this is bit-exact vs the dense reference.
        """
        k = cols.shape[-1]
        flat_c = cols.reshape(-1, k)
        flat_v = vals.reshape(-1, k)
        b = flat_c.shape[0]
        idx = (jnp.arange(b, dtype=jnp.int32)[:, None] * capacity
               + flat_c).reshape(-1)
        out = jnp.zeros((b * capacity,), flat_v.dtype)
        out = out.at[idx].add(flat_v.reshape(-1))
        return out.reshape(*cols.shape[:-1], capacity)

    def densify_compare(cols, vals, width: int):
        """:func:`densify_coo` without a scatter: every output column
        compares itself with the row's K entries and sums the values that
        hit.  The same bits (at most one real entry hits, the rest add
        exact zeros), K x ``width`` compares a row where the scatter takes
        one serial update an entry.  PR 25's readings, the only ones there
        are (1,920 rows of K=64 on a TPU v5e): 0.09 / 0.16 / 0.36 / 0.82 ms
        at a width of 256 / 1024 / 2048 / 4096 against the scatter's
        1.0-1.2 ms at any width, so it is taken up to
        :data:`COMPARE_MAX_WIDTH` columns, which since PR 39 is the widest
        table :func:`compact_rule` admits at F = 10,240: the cell
        ``tenk-train-live4k`` runs it there (0.81 ms in its windows, PR
        38's reading, against the scatter's 1.35 into 10,240 columns).
        The scatter is left to the dense form (a live set over the bound,
        F sharded over the mesh's ``model`` axis), to a table wider than
        4,096 at a larger F, and to serving; no benchmark cell runs it in
        training since PR 39 (PERF.md section 7)."""
        hit = cols[..., :, None] == jnp.arange(width, dtype=cols.dtype)
        return jnp.sum(jnp.where(hit, vals[..., :, None], 0.0), axis=-2)

    def normalize_minmax(x, mn, rg):
        """The exact device mirror of ``MinMaxStats.apply`` (degenerate
        ranges pass through raw)."""
        return jnp.where(rg == 0.0, x,
                         (x - mn) / jnp.where(rg == 0.0, 1.0, rg))

    @jax.named_scope(scopes.DENSIFY)
    def gather_densify_normalize(base: "SparseBase", idx):
        """Window gather + densify + normalize for a staged sparse base:
        ``idx [..., W]`` start-expanded row indices → normalized
        ``[..., W, base.width]`` windows (dense, or the live columns of
        the compact form), all inside the caller's jit."""
        compare = base.live is not None and base.width <= COMPARE_MAX_WIDTH
        x = (densify_compare if compare else densify_coo)(
            base.cols[idx], base.vals[idx], base.width)
        return normalize_minmax(x, base.mn, base.rg)


# -- host twins (numpy; shared by ETL, parity tests, and fallbacks) --------


def densify_rows(cols: np.ndarray, vals: np.ndarray, capacity: int,
                 out: np.ndarray | None = None) -> np.ndarray:
    """Host-side dense reconstruction of padded-COO rows — the parity
    reference for :func:`densify_coo` and the serve-side fallback when no
    sparse device path is available.  ``cols``/``vals`` are ``[..., K]``;
    returns float32 ``[..., capacity]``."""
    cols = np.asarray(cols)
    vals = np.asarray(vals, np.float32)
    shape = (*cols.shape[:-1], capacity)
    if out is None:
        # graftlint: disable=DN002 -- the sanctioned host densify: the ONE dense [..., F] window per sweep/parity call is built HERE so the hot zones never allocate it themselves
        out = np.zeros(shape, np.float32)
    else:
        if out.shape != shape:
            raise ValueError(f"out shape {out.shape} != {shape}")
        out[:] = 0.0
    if cols.size == 0:          # K=0 rows (e.g. an empty bucket): all zeros
        return out
    flat_o = out.reshape(-1, capacity)
    flat_c = cols.reshape(-1, cols.shape[-1])
    flat_v = vals.reshape(-1, vals.shape[-1])
    # np.add.at handles the (0, 0.0) padding exactly like the device
    # scatter: a zero add is a no-op on the non-negative counts here.
    rows = np.repeat(np.arange(flat_c.shape[0]), flat_c.shape[1])
    np.add.at(flat_o, (rows, flat_c.reshape(-1)), flat_v.reshape(-1))
    return out


def sparsify_rows(dense: np.ndarray, nnz_cap: int
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Dense ``[..., F]`` rows → padded-COO ``(cols, vals, nnz)``.

    The inverse of :func:`densify_rows` for rows whose nonzero count fits
    ``nnz_cap`` — rows that don't RAISE loudly (the documented K-cap
    policy; size ``--sparse-nnz-cap`` to the corpus, never silently drop
    traffic).  Round-trip is bit-exact: the nonzero values are copied,
    not recomputed.
    """
    dense = np.asarray(dense)
    flat = dense.reshape(-1, dense.shape[-1])
    n = flat.shape[0]
    cols = np.zeros((n, nnz_cap), np.int32)
    vals = np.zeros((n, nnz_cap), np.float32)
    nnz = np.zeros((n,), np.int32)
    for i in range(n):
        nz = np.flatnonzero(flat[i])
        if len(nz) > nnz_cap:
            raise ValueError(
                f"row {i} has {len(nz)} nonzero traffic columns, over the "
                f"sparse nnz cap {nnz_cap}; raise --sparse-nnz-cap (or "
                f"disable --sparse-feed) — silently dropping call paths "
                f"would corrupt the count vector")
        cols[i, :len(nz)] = nz
        vals[i, :len(nz)] = flat[i, nz]
        nnz[i] = len(nz)
    return (cols.reshape(*dense.shape[:-1], nnz_cap),
            vals.reshape(*dense.shape[:-1], nnz_cap),
            nnz.reshape(dense.shape[:-1]))


def sparse_minmax(cols: np.ndarray, vals: np.ndarray, nnz: np.ndarray,
                  span: int, capacity: int):
    """Per-column min/max over the first ``span`` padded-COO rows,
    BIT-IDENTICAL to ``minmax_fit`` over the equivalent dense rows.

    A column absent from any row in the span has a dense 0.0 there, so
    its min folds 0 in; a column present in EVERY row never sees an
    implicit zero.  Presence is decided by the ``nnz`` row lengths (never
    by ``val != 0`` heuristics), so padding at column 0 cannot pollute
    column 0's statistics.  Returns a ``MinMaxStats`` with the stream's
    per-feature ``[1, F]`` broadcast shape.
    """
    from deeprest_tpu.data.windows import MinMaxStats

    c = np.asarray(cols[:span])
    v = np.asarray(vals[:span], np.float32)
    n = np.asarray(nnz[:span])
    mask = np.arange(c.shape[1])[None, :] < n[:, None]
    cm = c[mask]
    vm = v[mask]
    mx = np.full((capacity,), -np.inf, np.float32)
    mn = np.full((capacity,), np.inf, np.float32)
    np.maximum.at(mx, cm, vm)
    np.minimum.at(mn, cm, vm)
    cnt = np.zeros((capacity,), np.int64)
    np.add.at(cnt, cm, 1)
    everywhere = cnt == span
    mx = np.where(everywhere, mx, np.maximum(mx, np.float32(0.0)))
    mn = np.where(everywhere, mn, np.minimum(mn, np.float32(0.0)))
    return MinMaxStats(min=mn[None, :].astype(np.float32),
                       max=mx[None, :].astype(np.float32))


def live_columns(cols: np.ndarray, vals: np.ndarray, mn: np.ndarray,
                 rg: np.ndarray, capacity: int) -> np.ndarray:
    """The sorted dense columns whose NORMALIZED value can be nonzero in
    some row of the padded-COO ``cols``/``vals``.

    A column is live when it carries a nonzero value somewhere, or when
    its statistics turn the raw 0 into a nonzero constant
    (``normalize_minmax(0, mn, rg) != 0``: ``rg != 0 and mn != 0``, which
    statistics carried over from another span can give a column this
    corpus never saw).  ``mn``/``rg`` are one scalar or ``[capacity]``.
    """
    cols = np.asarray(cols)
    if cols.size and (cols.min() < 0 or cols.max() >= capacity):
        raise ValueError(f"padded-COO columns outside [0, {capacity})")
    seen = np.zeros((capacity,), bool)
    seen[cols[np.asarray(vals) != 0]] = True
    shifted = np.broadcast_to(
        (np.asarray(rg).reshape(-1) != 0) & (np.asarray(mn).reshape(-1) != 0),
        (capacity,))
    return np.flatnonzero(seen | shifted).astype(np.int32)


def compact_rule(n_live: int, capacity: int) -> tuple[int, int]:
    """What the rule of the compact form weighs: ``(padded, bound)``.
    ``padded`` is the next power of two at or above ``max(n_live,
    MIN_COMPACT_WIDTH)`` (so a live set that grows from one staging to the
    next meets a handful of shapes, not one each); ``bound`` is the widest
    table the rule admits, ``capacity // 2``.  The form is compact when
    ``padded <= bound``.

    Why a half, from readings at F = 10,240, E = 40, H = 128 on a TPU v5e
    (steps/s of 32 windows; PERF.md section 6 has them by scope).  PR 25
    read a table of 2,048 8% faster than the dense form and one of 4,096
    14% slower, and put the bound at a quarter; PRs 27, 32 and 34 then
    shortened the compact side alone (Adam over the table's rows, the rows
    carried through the superstep's scan, the off-table pass by stale
    rows).  PR 38 read both sides again: dense 31.74, a table of 4,096
    62.66, one of 2,048 95.67.  PR 39 read 62.65-62.70 at 4,096 over four
    seeds and two processes, and a table of 8,192 at 35.52 against the
    dense form's 31.73: a step costs 14.9 / 26.4 / 30.9 ms (4,096 / 8,192 /
    dense) and a DISPATCH 42 / 69 / 23 ms on top (the takes and puts of the
    table's rows cost by its width; the dense form copies its six leaves
    into the dot's layout and back).  So at 50 steps a dispatch both tables
    win, but at 2 (what ``stream``'s refreshes run) 4,096 still wins, 27.73
    against 23.60, and 8,192 loses, 16.37: the bound admits 4,096 and not
    8,192, and a power-of-two table is at most half of F at any F.  The
    cell ``tenk-train-live4k`` runs the widest table it admits there."""
    padded = max(MIN_COMPACT_WIDTH, 1 << max(n_live - 1, 0).bit_length())
    return padded, capacity // 2


def compact_table(live: np.ndarray, capacity: int) -> np.ndarray | None:
    """The ``[U_pad]`` table of the compact form, or None where the dense
    form is kept (:func:`compact_rule`: the padded live set is over the
    bound).  The pad slots are the lowest dead columns, so the sorted
    table names ``U_pad`` distinct columns and every pad slot's input is
    exactly 0."""
    u_pad, bound = compact_rule(len(live), capacity)
    if u_pad > bound:
        return None
    dead = np.setdiff1d(np.arange(capacity, dtype=np.int32), live,
                        assume_unique=True)
    return np.sort(np.concatenate([live, dead[:u_pad - len(live)]])
                   ).astype(np.int32)


def compact_rows(cols: np.ndarray, vals: np.ndarray,
                 table: np.ndarray) -> np.ndarray:
    """``cols`` as ranks in ``table``.  Entries whose value is 0 (the
    ``(0, 0.0)`` padding, or an explicit zero count) may name a column
    outside the table; they add 0.0 wherever they land, so they go to
    rank 0."""
    ranks = np.minimum(np.searchsorted(table, cols), len(table) - 1)
    real = np.asarray(vals) != 0
    if not np.array_equal(table[ranks][real], np.asarray(cols)[real]):
        raise ValueError("a nonzero traffic column is not in the live table")
    return np.where(real, ranks, 0).astype(np.int32)


__all__ = [
    "COMPARE_MAX_WIDTH",
    "DEFAULT_NNZ_CAP",
    "MIN_COMPACT_WIDTH",
    "compact_rows",
    "compact_rule",
    "compact_table",
    "live_columns",
    "densify_rows",
    "sparsify_rows",
    "sparse_minmax",
]
if _HAVE_JAX:
    __all__ += ["SparseBase", "densify_coo", "densify_compare",
                "normalize_minmax", "gather_densify_normalize"]
