"""The estimator: a multi-task, attention-masked, quantile GRU.

Capability parity with the reference model (reference:
resource-estimation/qrnn.py:6-55): per metric (component×resource) one
*expert* consisting of (a) a learned soft feature mask over the shared
traffic features (the "attention-based API-call encoder"), (b) a
bidirectional GRU over the time window, and (c) a quantile head fed with
``concat(mean of all other experts' GRU outputs, own GRU output)`` — the
cross-metric knowledge-sharing path.

TPU-first re-design (not a translation):

- **Experts are an array axis, not a ModuleList.**  All per-expert weights
  carry a leading ``E`` axis, so the whole model is one set of batched
  einsums — MXU-friendly, and expert parallelism is a sharding annotation
  on axis 0 (SURVEY.md §2.5/§7.1).
- **The mask is folded into the GRU input weights.**  ``(x ⊙ mask_e) @ W``
  ≡ ``x @ (mask_e[:,None] ⊙ W)``, so the masked input is never materialized
  per expert: the hoisted input projection reads ``x`` once — O(B·T·F)
  HBM traffic instead of O(E·B·T·F).
- **Cross-expert mixing is O(E), not O(E²).**  ``mean_{j≠i}(out_j)``
  = ``(Σ_j out_j − out_i) / (E−1)`` — the all-pairs stack/mean the
  reference materializes is computed from one global sum (SURVEY.md §7.3).

Deviation (documented): for ``num_metrics == 1`` the reference's mean over
the empty "others" set is undefined (it would crash); here the mix input
falls back to the expert's own output.

Live-column compaction (PR 25): a staged sparse corpus whose live call
paths are few (``ops/densify.py``, the compact form) hands ``__call__``
windows of those ``U_pad`` columns only, with the table that names them
(``live_cols``).  Layer 0 then takes the same columns of the soft mask and
of ``w_ih`` (:func:`take_columns`), folds and projects ``[B, T, U_pad] x
[E, U_pad, 3H]``: the sum over F without its exact-zero terms.  The mask's
softmax still runs over all F and every parameter keeps its shape.  A
caller that differentiates with respect to ``w_ih`` gets the take's
transpose: a dense gradient that is zero at the columns left out (the
per-step programs).  One that hands in the
taken rows itself (``live_w_ih``; the compact superstep, which takes them
once a dispatch and carries them through its scan, PR 32) gets their
gradient as ``[E, U_pad, 3H]`` and no dense one, accumulates it so, and
runs Adam on those rows alone (``train/trainer.py``).  Without
``live_cols`` (every dense feed, serving) the call is what it was.
"""

from __future__ import annotations

import functools
from typing import Any, Mapping

import flax.linen as nn
import jax
import jax.numpy as jnp

from deeprest_tpu.config import ModelConfig
from deeprest_tpu.ops import scopes
from deeprest_tpu.ops.gru import GRUParams, bidirectional_gru, gru
from deeprest_tpu.parallel.sharding import (
    carried_rows_split, project_split_rows,
)

MASK_PARAM_NAMES = ("mask_w1", "mask_b1", "mask_w2", "mask_b2")
# Layer-0 input weights the soft mask folds into ((x ⊙ m) @ W ≡ x @ (m ⊙ W));
# only the keys present in the params tree apply (bwd exists iff bidirectional).
MASKED_PARAM_NAMES = ("gru_fwd_w_ih", "gru_bwd_w_ih")


def resolve_params(params):
    """Weights-adapter hook (round 22): dequantize-at-use for a
    quantized serving param tree (ops/quantize.quantize_params),
    identity for f32/bf16 trees.

    The jitted serving wrappers (serve/predictor.py) call this BEFORE
    ``model.apply`` sees the tree: flax validates supplied param leaf
    shapes against init, so int8+scale ``QuantTensor`` pairs must
    resolve back to plain ``[.., K, C]`` arrays first.  The dequant
    still runs ON DEVICE inside the calling executable (this is traced
    code), through the one sanctioned site — ops/quantize.dequantize —
    shared with the ops-level ``gru.resolve_weights`` hook."""
    from deeprest_tpu.ops.quantize import dequantize_params

    return dequantize_params(params)


@jax.named_scope(scopes.MASK)
def feature_mask(params) -> jax.Array:
    """The learned soft feature mask ``[E, F]`` from the mask parameters.

    Mirrors the reference encoder: Linear(1→H) on a constant 1.0 input is
    just (weight + bias), then ReLU → Linear(H→F) → softmax
    (reference: resource-estimation/qrnn.py:20-26,33-36).
    """
    hidden_act = nn.relu(params["mask_w1"] + params["mask_b1"])     # [E, H]
    logits = (jnp.einsum("eh,ehf->ef", hidden_act, params["mask_w2"])
              + params["mask_b2"])
    return jax.nn.softmax(logits, axis=-1)                          # [E, F]


@jax.named_scope(scopes.MASK)
def _fold(mask: jax.Array, w_ih: jax.Array) -> jax.Array:
    """``(x ⊙ m) @ W ≡ x @ (m ⊙ W)``: the one place the fold multiplies."""
    return mask[:, :, None] * w_ih


def _over_experts(fn, mesh):
    """``fn`` (leaves ``[E, ...]`` first, the table last) on each device's
    own experts where ``mesh`` shards them: the rows ``e*F + live_cols[u]``
    of a shard's experts lie in that shard's part of the ``[E*F, 3H]`` view,
    which the partitioner cannot see (left to it, it gathers every shard's
    rows everywhere and reduces)."""
    if mesh is None or mesh.shape["expert"] == 1:
        return fn
    from jax.sharding import PartitionSpec as P

    def sharded(*args):
        return jax.shard_map(
            fn, mesh=mesh, in_specs=(*[P("expert")] * (len(args) - 1), P()),
            out_specs=P("expert"))(*args)
    return sharded


def _flat_rows(a: jax.Array, live_cols: jax.Array):
    """A ``[E, F, C]`` leaf as its ``[E*F, C]`` rows (a bitcast of the
    leaf as it lies in memory) and the rows ``e*F + live_cols[u]``, in
    ``e``-major order: sorted, unique and in bounds as the table is."""
    e, f = a.shape[:2]
    rows = jnp.arange(e, dtype=live_cols.dtype)[:, None] * f + live_cols
    return a.reshape(e * f, *a.shape[2:]), rows.reshape(-1)


def take_columns(a: jax.Array, live_cols: jax.Array, mesh=None) -> jax.Array:
    """``a[:, live_cols]``: the live columns of the ``[E, F]`` mask or of a
    ``[E, F, 3H]`` input weight.  The table is sorted, without repeats and
    in range (``ops/densify.compact_table``), and the gather says so, so
    its transpose is a scatter that need not serialize or accumulate.  A
    weight is indexed by row ``e*F + live_cols[u]`` of its ``[E*F, 3H]``
    view: a gather over dimension 1 of the leaf itself makes the TPU
    compiler lay the whole leaf out with that dimension outermost, a copy
    of the leaf each way (PERF.md section 6, PR 32); a gather of rows asks
    for no layout.  ``mesh``: the one the weight is sharded over, if any."""
    if a.ndim == 2:
        return a.at[:, live_cols].get(unique_indices=True,
                                      indices_are_sorted=True,
                                      mode="promise_in_bounds")

    def take(a, live_cols):
        flat, rows = _flat_rows(a, live_cols)
        return flat.at[rows].get(
            unique_indices=True, indices_are_sorted=True,
            mode="promise_in_bounds").reshape(a.shape[0], -1, *a.shape[2:])

    return _over_experts(take, mesh)(a, live_cols)


def put_columns(a: jax.Array, live_cols: jax.Array, rows: jax.Array,
                mesh=None) -> jax.Array:
    """The ``[E, F, 3H]`` weight ``a`` with ``rows`` at ``a[:, live_cols]``:
    what :func:`take_columns` took, put back under the same promises and
    over the same ``[E*F, 3H]`` view, so that on a donated or loop-carried
    ``a`` the scatter writes in place and no copy of ``a`` is made."""
    def put(a, rows, live_cols):
        flat, at = _flat_rows(a, live_cols)
        return flat.at[at].set(
            rows.reshape(-1, *a.shape[2:]), unique_indices=True,
            indices_are_sorted=True, mode="promise_in_bounds"
        ).reshape(a.shape)

    return _over_experts(put, mesh)(a, rows, live_cols)


def put_rows(a: jax.Array, cols: jax.Array, rows: jax.Array) -> jax.Array:
    """``a`` with ``rows[:, i]`` at ``a[:, cols[i]]``, one ``[E, 1, 3H]``
    slab at a time in a loop: for a few rows of a donated or loop-carried
    ``a``.  On a TPU v5e :func:`put_columns`' scatter costs a pass over the
    whole leaf however few rows it writes (1.95 ms for the 629 MB of
    ``[40, 10240, 384]``, for 1,280 rows as for 10,240), a
    ``dynamic_update_slice`` the slab it writes (under 5 us; both:
    PERF.md section 6, PR 34), so below some 400 rows this is the cheaper
    put.  It promises nothing about ``cols`` (a later row wins) and, the
    slab being whole along the experts, needs no ``shard_map`` where they
    are sharded."""
    def put(i, a):
        return jax.lax.dynamic_update_slice_in_dim(
            a, jax.lax.dynamic_slice_in_dim(rows, i, 1, axis=1), cols[i],
            axis=1)

    return jax.lax.fori_loop(0, cols.shape[0], put, a)


class KeptMaskDropout(nn.Module):
    """``flax.linen.Dropout``'s arithmetic on ``flax.linen.Dropout``'s
    mask (``bernoulli(1 - rate)`` from ``make_rng("dropout")``, so under
    the name ``Dropout_0`` the key's path, and the mask, are those of the
    first Dropout of the parent), with the mask behind an
    ``optimization_barrier``: a ``pred`` array, a byte an element, that the
    forward select and its transpose both READ.  Left to itself XLA fuses
    the threefry rounds into every consumer of the mask and draws it again
    in each: twice a step at best, once in the forward pass and once in
    the ``heads`` cotangent's fusion (PERF.md section 6, PR 36).  Only a
    barrier on every use holds it to one draw: a ``custom_vjp`` that kept
    a barriered mask for the backward pass alone left the forward's uses
    fused with the draw, six in all."""

    rate: float

    @nn.compact
    def __call__(self, x: jax.Array, *, deterministic: bool) -> jax.Array:
        if self.rate == 0.0 or deterministic:
            return x
        if self.rate == 1.0:            # no 0 / 0 in the gradient
            return jnp.zeros_like(x)
        keep_prob = 1.0 - self.rate
        keep = jax.lax.optimization_barrier(jax.random.bernoulli(
            self.make_rng("dropout"), keep_prob, x.shape))
        return jax.lax.select(keep, x / keep_prob, jnp.zeros_like(x))


class QuantileGRU(nn.Module):
    """Multi-task quantile GRU.

    Input ``[B, T, F]`` traffic-feature windows → output ``[B, T, E, Q]``
    per-metric quantile predictions.
    """

    config: ModelConfig
    # The (data, expert, model) mesh the caller shards params and batch
    # over, if any: the pallas recurrence needs it (ops/gru.py wraps the
    # kernel in shard_map); every other op is partitioned by GSPMD.
    mesh: Any = None

    @nn.compact
    def __call__(self, x: jax.Array, *, deterministic: bool = True,
                 live_cols: jax.Array | None = None,
                 live_w_ih: Mapping[str, jax.Array] | None = None
                 ) -> jax.Array:
        """``live_cols`` (``[U_pad]`` int32, sorted dense column indices):
        ``x`` is ``[..., U_pad]`` and holds those columns only; every
        column left out is zero in the dense input (module docstring).
        ``live_w_ih`` (with ``live_cols``): ``take_columns(w_ih,
        live_cols)`` of every ``MASKED_PARAM_NAMES`` leaf, by the leaf's
        name, from a caller that differentiates with respect to the taken
        rows; the leaves in the params tree are then not read."""
        cfg = self.config
        e, f, h, q = cfg.num_metrics, cfg.feature_dim, cfg.hidden_size, len(cfg.quantiles)
        if x.ndim != 3:
            raise ValueError(f"expected [B, T, F] windows, got shape {x.shape}")
        if live_cols is None and x.shape[-1] != f:
            raise ValueError(f"input feature dim {x.shape[-1]} != config.feature_dim {f}")
        if live_cols is not None and x.shape[-1] != live_cols.shape[0]:
            raise ValueError(f"input feature dim {x.shape[-1]} != the "
                             f"{live_cols.shape[0]} live columns")
        if live_w_ih is not None and live_cols is None:
            raise ValueError("live_w_ih holds the rows live_cols names; "
                             "live_cols is missing")
        compute_dtype = jnp.dtype(cfg.compute_dtype)

        def uniform_pm(scale):
            def _init(key, shape, dtype=jnp.float32):
                return jax.random.uniform(key, shape, dtype, minval=-scale, maxval=scale)
            return _init

        # (a) learned soft feature mask — Linear(1→H) → ReLU → Linear(H→F)
        # → softmax, driven by a constant 1.0 (reference: qrnn.py:20-26,33-36).
        # Linear(1→H) on a constant input is just (weight + bias): one [E,H]
        # pre-activation per expert.
        k_in = 1.0  # fan_in of the constant input
        mask_params = {
            "mask_w1": self.param("mask_w1", uniform_pm(1.0 / k_in ** 0.5), (e, h)),
            "mask_b1": self.param("mask_b1", uniform_pm(1.0 / k_in ** 0.5), (e, h)),
        }
        k_h = 1.0 / h ** 0.5
        mask_params["mask_w2"] = self.param("mask_w2", uniform_pm(k_h), (e, h, f))
        mask_params["mask_b2"] = self.param("mask_b2", uniform_pm(k_h), (e, f))

        mask = feature_mask(mask_params)                              # [E, F]

        # (b) (stacked) bidirectional GRU over the window (reference:
        # qrnn.py:24,39-43; layer l>0 consumes layer l-1's output, matching
        # torch's stacked-GRU semantics with zero inter-layer dropout).
        k_g = 1.0 / h ** 0.5

        def gru_params(name, in_dim):
            return GRUParams(
                w_ih=self.param(f"{name}_w_ih", uniform_pm(k_g), (e, in_dim, 3 * h)),
                w_hh=self.param(f"{name}_w_hh", uniform_pm(k_g), (e, h, 3 * h)),
                b_ih=self.param(f"{name}_b_ih", uniform_pm(k_g), (e, 3 * h)),
                b_hh=self.param(f"{name}_b_hh", uniform_pm(k_g), (e, 3 * h)),
            )

        # Fold the mask into the input weights: (x ⊙ m) @ W == x @ (m ⊙ W).
        # On a compact input only the live columns of either are folded.
        if live_cols is not None:
            mask = take_columns(mask, live_cols)                      # [E, U]

        # The caller's carried rows, which a mesh with a `data` axis splits
        # over it (parallel/sharding.py): a chip folds and casts its own,
        # and the projection and its backward are project_split_rows'.
        split = (live_w_ih is not None and carried_rows_split(
            self.mesh, live_cols.shape[0]) > 1)

        def masked(p: GRUParams, name: str) -> GRUParams:
            if live_cols is None:
                w_ih = p.w_ih
            elif live_w_ih is None:
                w_ih = take_columns(p.w_ih, live_cols, self.mesh)
            else:
                w_ih = live_w_ih[name]
            return p._replace(w_ih=_fold(mask, w_ih))

        def cast(p: GRUParams) -> GRUParams:
            return jax.tree.map(lambda a: a.astype(compute_dtype), p)

        out = x.astype(compute_dtype)                                  # [B,T,F]
        for layer in range(cfg.num_layers):
            sfx = "" if layer == 0 else f"_l{layer}"
            in_dim = f if layer == 0 else cfg.rnn_out_dim
            project = (functools.partial(project_split_rows, self.mesh)
                       if split and layer == 0 else None)
            fwd = gru_params(f"gru_fwd{sfx}", in_dim)
            if layer == 0:
                fwd = masked(fwd, MASKED_PARAM_NAMES[0])
            if cfg.bidirectional:
                bwd = gru_params(f"gru_bwd{sfx}", in_dim)
                if layer == 0:
                    bwd = masked(bwd, MASKED_PARAM_NAMES[1])
                out = bidirectional_gru(cast(fwd), cast(bwd), out,
                                        backend=cfg.rnn_backend,
                                        mesh=self.mesh, project=project)
            else:
                out = gru(cast(fwd), out, backend=cfg.rnn_backend,
                          mesh=self.mesh, project=project)
            # layer 0 broadcasts [B,T,F] across experts; the output (and all
            # deeper layers) carry the expert axis: [E,B,T,D].
        # The post-RNN path stays in the model's compute dtype (bf16 for
        # the flagship): rnn_out/mix are the largest activations outside
        # the recurrence (~78 MB each at flagship scale in f32), and
        # mixing + both head einsums each stream them through HBM (the
        # dropped array itself is rebuilt from the kept mask wherever it
        # is read).  All reductions still ACCUMULATE in f32 (the
        # cross-expert sum explicitly, the head dots via
        # preferred_element_type); only storage between ops is narrow.
        # f32 models are unchanged.
        with jax.named_scope(scopes.DROPOUT):
            rnn_out = KeptMaskDropout(
                rate=cfg.dropout_rate, name="Dropout_0")(
                    out, deterministic=deterministic)

        # (c) cross-expert mixing + per-metric quantile heads
        # (reference: qrnn.py:46-55), via the O(E) sum-minus-own identity.
        if e > 1:
            with jax.named_scope(scopes.MIXING):
                total = jnp.sum(rnn_out.astype(jnp.float32), axis=0,
                                keepdims=True)                        # [1,B,T,D]
                mix = ((total - rnn_out.astype(jnp.float32)) / (e - 1)
                       ).astype(compute_dtype)                        # [E,B,T,D]
        else:
            mix = rnn_out

        # The head consumes concat(mix, own) along the feature axis
        # (reference: qrnn.py:50-53).  The weight KEEPS that [E, 2D, Q]
        # layout (checkpoint compatibility), but the einsum is split over
        # the two halves instead of materializing the [E,B,T,2D]
        # concatenation — at flagship scale that intermediate is ~157 MB
        # of pure HBM traffic for an op XLA cannot always fuse away.
        d = rnn_out.shape[-1]
        d_in = 2 * d
        k_d = 1.0 / d_in ** 0.5
        head_w = self.param("head_w", uniform_pm(k_d), (e, d_in, q))
        head_b = self.param("head_b", uniform_pm(k_d), (e, q))
        with jax.named_scope(scopes.HEADS):
            hw = head_w.astype(compute_dtype)
            preds = (jnp.einsum("ebtd,edq->ebtq", mix, hw[:, :d],
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("ebtd,edq->ebtq", rnn_out, hw[:, d:],
                                  preferred_element_type=jnp.float32))
            preds = preds + head_b[:, None, None, :]
            preds = jnp.transpose(preds, (1, 2, 0, 3))                # [B,T,E,Q]
        return preds

    # ------------------------------------------------------------------
    @property
    def quantiles(self) -> tuple[float, ...]:
        return self.config.quantiles

    def median_index(self) -> int:
        """Index of the .50 quantile in the output's last axis (the point
        estimate the reference plots/evaluates, estimate.py:103)."""
        diffs = [abs(qv - 0.5) for qv in self.config.quantiles]
        return diffs.index(min(diffs))
