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
"""

from __future__ import annotations

import numpy as np

from deeprest_tpu.ops import scopes

try:  # host-only callers (benchmarks, lint) may lack an initialized backend
    import flax.struct
    import jax
    import jax.numpy as jnp

    _HAVE_JAX = True
except Exception:  # pragma: no cover - jax is a hard dep of the repo
    _HAVE_JAX = False


DEFAULT_NNZ_CAP = 64


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
        """

        cols: object                 # [T, K] int32 device array
        vals: object                 # [T, K] float32 device array
        mn: object                   # broadcastable x_stats.min
        rg: object                   # broadcastable x_stats.range
        capacity: int = flax.struct.field(pytree_node=False, default=0)

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

    def normalize_minmax(x, mn, rg):
        """The exact device mirror of ``MinMaxStats.apply`` (degenerate
        ranges pass through raw)."""
        return jnp.where(rg == 0.0, x,
                         (x - mn) / jnp.where(rg == 0.0, 1.0, rg))

    @jax.named_scope(scopes.DENSIFY)
    def gather_densify_normalize(base: "SparseBase", idx):
        """Window gather + densify + normalize for a staged sparse base:
        ``idx [..., W]`` start-expanded row indices → normalized dense
        ``[..., W, capacity]`` windows, all inside the caller's jit."""
        x = densify_coo(base.cols[idx], base.vals[idx], base.capacity)
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


__all__ = [
    "DEFAULT_NNZ_CAP",
    "densify_rows",
    "sparsify_rows",
    "sparse_minmax",
]
if _HAVE_JAX:
    __all__ += ["SparseBase", "densify_coo", "normalize_minmax",
                "gather_densify_normalize"]
