"""The inference engine: checkpointed model → utilization predictions.

Bundles everything a consumer needs — params, model config, normalization
statistics, metric names — restored from one checkpoint directory, so
serving cannot drift from training state (the reference never serializes
its model at all; SURVEY.md §5.4).  Prediction over arbitrary-length
traffic series runs the window as a rolling jit-compiled batch.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeprest_tpu.config import Config, ModelConfig
from deeprest_tpu.data.windows import MinMaxStats
from deeprest_tpu.models.qrnn import QuantileGRU, resolve_params
from deeprest_tpu.ops import quantize as quant_ops
from deeprest_tpu.serve.batcher import BatchedBackendMixin
from deeprest_tpu.serve.fused import FusedInferenceMixin


def rolled_prediction_reference(
        apply_fn, x_stats: MinMaxStats, y_stats: MinMaxStats,
        window_size: int, traffic: np.ndarray,
        max_batch: int = 64,
        delta_mask: np.ndarray | None = None,
        median_index: int | None = None) -> np.ndarray:
    """[T, F] raw traffic → de-normalized [T, E, Q] predictions.

    The HOST-LOOP reference implementation: windows stacked and
    normalized in numpy, every batch read back, de-normalized on host,
    delta columns integrated with a sequential per-window carry.  The
    production path is the fused device program (serve/fused.py) — this
    loop is kept as the pinned numerical specification
    (tests/test_fused_infer.py: the fused path must match it bit-exactly
    on CPU for non-delta metrics, <= 1e-5 relative for the prefix-sum
    delta carry).

    The series is tiled into non-overlapping windows (last window
    right-aligned so every step is covered exactly once; the recurrent
    core supports any duration — reference claim at
    resource-estimation/README.md:83).  Windows go through ``apply_fn``
    in batches of at most ``max_batch``, so memory stays bounded for
    arbitrarily long series (a month of minutes is ~720 windows; only one
    batch of them is ever resident on device).  Shared by the in-process
    Predictor and the exported-artifact loader so both serve identical
    semantics by construction.

    A ragged last batch (series length not a multiple of
    ``max_batch * window_size``) is NOT a new device shape: both serving
    backends hand in a shape-laddered ``apply_fn``
    (serve/batcher.ShapeLadder) that pads every batch up a fixed rung
    ladder and strips the padding rows, so the jit cache holds one
    executable per rung instead of one per ragged shape.

    ``delta_mask`` marks metrics the model predicts as per-bucket
    increments (train/data.py delta formulation): those columns are
    integrated back to a LEVEL series — each window's cumulative sum,
    chained across windows on the median quantile so the rollout is
    continuous.  The absolute offset is a pure-prediction rollout from 0
    (no observations exist here); consumers with observations re-anchor
    (AnomalyDetector, the demo's results layer).  Quantile columns are
    offset from the shared median base, so the band reflects within-
    window uncertainty rather than compounding across the whole series.
    """
    w = window_size
    t = len(traffic)
    if t < w:
        raise ValueError(f"series length {t} < window_size {w}")
    if delta_mask is not None and delta_mask.any() and median_index is None:
        raise ValueError("delta_mask requires median_index for the "
                         "cross-window carry")
    starts = list(range(0, t - w + 1, w))
    if starts[-1] != t - w:
        starts.append(t - w)

    out = None
    for lo in range(0, len(starts), max_batch):
        chunk = starts[lo:lo + max_batch]
        x = np.stack([traffic[s:s + w] for s in chunk]).astype(np.float32)
        x = x_stats.apply(x).astype(np.float32)
        # graftlint: disable=JX003 -- designed sink: the pinned HOST-LOOP reference reads every batch back by definition; the production path is the fused engine
        preds = np.asarray(apply_fn(x))                   # [n, W, E, Q]
        preds = y_stats.invert(
            np.maximum(preds, 1e-6).transpose(0, 1, 3, 2)
        ).transpose(0, 1, 3, 2)
        if out is None:
            out = np.empty((t, preds.shape[2], preds.shape[3]), np.float32)
        for s, window in zip(chunk, preds):
            if delta_mask is not None and delta_mask.any():
                # graftlint: disable=JX003 -- host data: `window` is a numpy slice of the already-read-back batch
                window = np.array(window, copy=True)
                c = np.cumsum(window[:, delta_mask, :], axis=0)
                # carry: the already-written median level one step before
                # this window (0 for the very first step of the series)
                base = (out[s - 1, delta_mask, median_index][None, :, None]
                        if s > 0 else 0.0)
                window[:, delta_mask, :] = base + c
            out[s:s + w] = window      # later (right-aligned) window wins
    return out


# Historical name, kept for consumers pinned to the host loop.
rolled_prediction = rolled_prediction_reference


class Predictor(BatchedBackendMixin, FusedInferenceMixin):
    """Quantile predictions for traffic feature series."""

    def __init__(self, params, model_config: ModelConfig,
                 x_stats: MinMaxStats, y_stats: MinMaxStats,
                 metric_names: list[str], window_size: int,
                 space_dict: dict | None = None,
                 delta_mask: np.ndarray | None = None,
                 ladder: tuple[int, ...] | None = None,
                 fused: bool = True,
                 page_windows: int | None = None,
                 coalesce_pages: int | None = None,
                 coalesce_groups: int = 1,
                 sparse_feed: bool = False,
                 sparse_nnz_cap: int = 64,
                 quant: str = "off",
                 quant_budget: dict | None = None,
                 mesh=None):
        # Quantized serving (round 22, ops/quantize.py): weight leaves
        # stored int8 (+f32 scales) or bf16, dequantized at use INSIDE
        # the jitted wrappers below via models.qrnn.resolve_params — the
        # one sanctioned site, on device, fused into the executables.
        if quant not in quant_ops.QUANT_MODES:
            raise ValueError(
                f"quant mode {quant!r} not in {quant_ops.QUANT_MODES}")
        self.quant = quant
        ref_params = params
        if quant != "off":
            params = quant_ops.quantize_params(params, quant)
        self.params = params
        # ``mesh``: the serving mesh ``params`` are sharded over, if any
        # (from_checkpoint's mesh_config) — the pallas recurrence needs it.
        self.model = QuantileGRU(config=model_config, mesh=mesh)
        self.x_stats = x_stats
        self.y_stats = y_stats
        self.metric_names = list(metric_names)
        self.window_size = window_size
        # serialized CallPathSpace of the training corpus (if checkpointed):
        # lets consumers featurize raw traces column-exactly — see space()
        self.space_dict = space_dict
        # [E] bool: metrics the model predicts as per-bucket increments
        # (train/data.py delta formulation); predict_series integrates
        # them back to levels.  None (pre-delta checkpoints): no-op.
        self.delta_mask = (np.asarray(delta_mask, bool)
                           if delta_mask is not None else None)
        # resolve_params is the weights-adapter: identity trace for f32
        # trees, on-device dequant for quantized ones — ONE apply path,
        # so the executable count stays flat across quant modes.
        self._apply = jax.jit(
            lambda p, x: self.model.apply({"params": resolve_params(p)},
                                          x, deterministic=True)
        )
        # Sparse-first serving feed (InferConfig.sparse_feed): a second
        # jitted apply taking RAW padded-COO windows plus the staged
        # stats — densify (one scatter-add) + normalize + model, all on
        # device (ops/densify.py for the bit-parity contract; stats are
        # runtime ARGUMENTS, like the fused engine's, so XLA cannot
        # strength-reduce the divide).  Dense entries stay the default.
        self.sparse_feed = bool(sparse_feed)
        self.sparse_nnz_cap = int(sparse_nnz_cap)
        apply_sparse = None
        if self.sparse_feed:
            from deeprest_tpu.ops.densify import (
                densify_coo, normalize_minmax,
            )

            feat = model_config.feature_dim
            x_mn = jnp.asarray(
                np.asarray(x_stats.min, np.float32).reshape(-1))
            x_rg = jnp.asarray(
                np.asarray(x_stats.range, np.float32).reshape(-1))
            self._apply_sparse = jax.jit(
                lambda p, c, v, mn, rg: self.model.apply(
                    {"params": resolve_params(p)},
                    normalize_minmax(densify_coo(c, v, feat), mn, rg),
                    deterministic=True))
            apply_sparse = lambda c, v: self._apply_sparse(
                self.params, jnp.asarray(c), jnp.asarray(v), x_mn, x_rg)
        else:
            self._apply_sparse = None
        # All serving batches go through the shape ladder (and, when one
        # is attached, the cross-request MicroBatcher): the jit cache
        # holds one executable per rung, never one per ragged shape.
        self._init_batching(
            lambda x: self._apply(self.params, jnp.asarray(x)),
            ladder=ladder, coalesce_groups=coalesce_groups,
            apply_sparse_fn=apply_sparse)
        # The fused device-resident rolled-inference engine (serve/fused.py)
        # shares the ladder's rung set, so mixed series lengths compile at
        # most one fused executable per rung.  Params thread through the
        # fused jit as arguments (bit parity — see FusedRolledEngine).
        self._init_fused(
            lambda p, x: self._apply(p, x), params=self.params,
            enabled=fused, page_windows=page_windows,
            coalesce_pages=coalesce_pages,
            sparse_nnz_cap=(self.sparse_nnz_cap if self.sparse_feed
                            else None))
        # Parity is a product contract: measure the per-(metric,
        # quantile) envelope vs the f32 reference at quantize time, and
        # fail LOUDLY if a stored budget (the checkpoint's pinned
        # envelope) is exceeded — a quantized predictor never serves
        # outside the parity its checkpoint recorded.
        self.parity_envelope = None
        if quant != "off":
            self.parity_envelope = self._measure_parity(
                ref_params, quant_budget)

    def _measure_parity(self, ref_params, budget: dict | None) -> dict:
        """Quantize-time parity measurement on the deterministic probe
        batch (ops/quantize.probe_batch): quantized apply vs the f32
        reference, reduced to the per-(metric, quantile) envelope.

        Runs through a throwaway jitted apply, NOT ``self._apply``, so
        the probe never perturbs the serving executable count the
        zero-post-warmup-compiles probes pin.  With a ``budget`` (the
        envelope stored next to the checkpoint) any violated cell
        raises — the loud gate."""
        probe = quant_ops.probe_batch(self.window_size,
                                      self.model.config.feature_dim)
        x = jnp.asarray(probe)
        apply_once = jax.jit(
            lambda p, xx: self.model.apply(
                {"params": resolve_params(p)}, xx, deterministic=True))
        measured = quant_ops.parity_envelope(
            apply_once(ref_params, x), apply_once(self.params, x),
            self.metric_names, self.model.config.quantiles)
        envelope = {
            "mode": self.quant,
            "measured": measured,
            "budget": (dict(budget["budget"]) if budget is not None
                       else quant_ops.budget_from_measured(measured)),
        }
        if budget is not None:
            violations = quant_ops.check_envelope(measured,
                                                  envelope["budget"])
            if violations:
                raise quant_ops.QuantParityError(
                    f"quantized ({self.quant}) predictions exceed the "
                    "stored parity envelope: "
                    + "; ".join(violations[:8])
                    + (f" (+{len(violations) - 8} more)"
                       if len(violations) > 8 else ""))
        return envelope

    def share_executables_from(self, donor: "Predictor") -> None:
        """Adopt the donor's jitted serving programs (fleet tier,
        serve/fleet.py): params and normalization stats are runtime
        ARGUMENTS throughout — ``_apply`` threads the params tree,
        the fused engine threads params AND stats (serve/fused.py bit-
        parity contract) — so predictors of the same architecture and
        quant mode serve different tenants' weights through the SAME
        compiled executables, and ``jit_cache_size`` stays flat in the
        number of tenants.

        The architecture/quant/geometry compatibility this requires is
        checked loudly here and in ``FusedRolledEngine.
        adopt_executables``; a mismatch would silently re-trace a new
        executable per tenant, which is exactly the regression the fleet
        bench's frozen-ledger gate exists to catch."""
        if not isinstance(donor, Predictor):
            raise TypeError(
                f"can only share executables between Predictors, got "
                f"{type(donor).__name__}")
        if donor is self:
            return
        if self.model_config != donor.model_config:
            raise ValueError(
                "cannot share executables across architectures: "
                f"{self.model_config} != {donor.model_config}")
        if self.quant != donor.quant:
            raise ValueError(
                f"cannot share executables across quant modes "
                f"({self.quant!r} vs {donor.quant!r}): the params tree "
                "leaf dtypes differ, which re-traces per mode")
        if self.window_size != donor.window_size:
            raise ValueError(
                f"cannot share executables across window sizes "
                f"({self.window_size} vs {donor.window_size})")
        if self.ladder.ladder != donor.ladder.ladder:
            raise ValueError(
                f"cannot share executables across shape ladders "
                f"({self.ladder.ladder} vs {donor.ladder.ladder})")
        if (self.sparse_feed, self.sparse_nnz_cap) != (
                donor.sparse_feed, donor.sparse_nnz_cap):
            raise ValueError(
                "cannot share executables across sparse-feed settings")
        self._apply = donor._apply
        if self._apply_sparse is not None:
            # the per-tenant entry wrapper closes over THIS predictor's
            # stats/params and late-binds self._apply_sparse, so only
            # the jitted function (and its cache) is shared
            self._apply_sparse = donor._apply_sparse
        if self._fused is not None and donor._fused is not None:
            self._fused.adopt_executables(donor._fused)

    def params_digest(self) -> str:
        """Stable fingerprint of the served params — the ``params_hash``
        half of the capacity-surface cache key (serve/surface.py).
        Computed ONCE per predictor (each reload builds a new instance)
        and cached: the tree walk reads every leaf back to host exactly
        one time, never on a request path."""
        digest = getattr(self, "_params_digest", None)
        if digest is None:
            import hashlib

            h = hashlib.sha1()
            # Quant mode enters the digest: a surface built at int8 must
            # never be served by (or to) an f32 predictor — the quant
            # mode is part of the cache-key identity, explicitly, not
            # just via the (already different) quantized leaf bytes.
            if self.quant != "off":
                h.update(self.quant.encode())
            for leaf in jax.tree_util.tree_leaves(self.params):
                # graftlint: disable=JX003 -- host data: one-time per-checkpoint fingerprint, cached on the instance
                h.update(np.asarray(leaf).tobytes())
            digest = self._params_digest = h.hexdigest()[:16]
        return digest

    def jit_cache_size(self) -> int | None:
        """Total compiled-executable count across BOTH serving programs —
        the per-rung batched apply and the fused rolled-inference pipeline
        (None when the running jax version has no cache probe) — the test
        hook behind the 'mixed series lengths trigger zero new compiles'
        guarantee.  ``jit_cache_stats`` has the per-program breakdown."""
        sizes = []
        for fn in (self._apply, self._apply_sparse):
            probe = getattr(fn, "_cache_size", None) if fn is not None \
                else None
            if callable(probe):
                sizes.append(int(probe()))
        if self._fused is not None:
            fused = self._fused.cache_size()
            if fused is not None:
                sizes.append(fused)
        return sum(sizes) if sizes else None

    def jit_cache_stats(self) -> dict:
        """Per-program executable counts plus the rung sets bounding them."""
        probe = getattr(self._apply, "_cache_size", None)
        sprobe = getattr(self._apply_sparse, "_cache_size", None) \
            if self._apply_sparse is not None else None
        return {
            "apply": int(probe()) if callable(probe) else None,
            "apply_sparse": int(sprobe()) if callable(sprobe) else None,
            "fused": (self._fused.cache_size()
                      if self._fused is not None else None),
            "ladder_rungs": len(self.ladder.ladder),
            "fused_rungs": (len(self._fused.rungs)
                            if self._fused is not None else 0),
            # the quant mode these executables were built at — the
            # flat-executable probes compare counts ACROSS modes, so the
            # breakdown must name which mode it counted
            "quant": self.quant,
        }

    @property
    def model_config(self) -> ModelConfig:
        """The restored architecture, as public API (equivalent to
        ``self.model.config``, which is an implementation detail)."""
        return self.model.config

    # The serving protocol shared with serve.export.ExportedPredictor —
    # consumers (AnomalyDetector, WhatIfEstimator, the HTTP server) use
    # only these, so either backend can sit behind them.

    @property
    def quantiles(self) -> tuple[float, ...]:
        return self.model.config.quantiles

    @property
    def feature_dim(self) -> int:
        return self.model.config.feature_dim

    def median_index(self) -> int:
        return self.model.median_index()

    # ------------------------------------------------------------------

    @classmethod
    def from_checkpoint(cls, directory: str, config: Config | None = None,
                        step: int | None = None,
                        ladder: tuple[int, ...] | None = None,
                        fused: bool = True,
                        page_windows: int | None = None,
                        coalesce_pages: int | None = None,
                        coalesce_groups: int = 1,
                        sparse_feed: bool = False,
                        sparse_nnz_cap: int = 64,
                        mesh_config=None,
                        quant: str = "off") -> "Predictor":
        """Restore params + host stats written by Trainer.save().

        ``quant`` ({'off','int8','bf16'}, ops/quantize.py): quantize the
        restored weights for serving.  The per-(metric, quantile) parity
        envelope vs the f32 reference is measured at quantize time and
        stored NEXT TO the checkpoint (``quant_parity_<mode>.json``); on
        every later load at the same mode the re-measured parity is
        checked against that stored budget and a violation raises — the
        envelope is a product contract, not a hope.

        With ``config=None`` the architecture comes wholesale from the
        checkpoint sidecar (all checkpoints written by Trainer.save carry
        it), so the restored predictor cannot drift from training.  An
        explicitly passed config is trusted as-is — the caller owns both
        architecture and serving knobs (compute_dtype, rnn_backend).

        ``mesh_config`` (a MeshConfig or None) lays a serving device mesh
        under the restored params: shardings resolve from the SAME
        partition-rule table the trainer pins with
        (parallel/sharding.PARTITION_RULES), so e.g. ``model=N`` gives the
        serving ladder and fused engine feature-axis TP over the F that
        grows with the endpoint vocabulary — there is no serving-side
        spec list to drift from training's.  The checkpoint may have been
        saved under any mesh shape (restore assembles by global index).
        """
        from deeprest_tpu.obs import spans as obs_spans
        from deeprest_tpu.parallel.mesh import make_mesh
        from deeprest_tpu.train.checkpoint import (
            latest_step, load_sidecar, restore_checkpoint,
        )
        from deeprest_tpu.train.trainer import Trainer

        with obs_spans.RECORDER.span("predictor.load",
                                     component="deeprest-predictor") as sp:
            sp.tag(directory=directory, step=step, quant=quant)
            return cls._from_checkpoint_inner(
                directory, config, step, ladder, fused, page_windows,
                coalesce_pages, coalesce_groups, sparse_feed,
                sparse_nnz_cap, mesh_config,
                make_mesh, latest_step, load_sidecar, restore_checkpoint,
                Trainer, quant)

    @staticmethod
    def _quant_envelope_path(directory: str, quant: str) -> str:
        import os

        return os.path.join(directory, f"quant_parity_{quant}.json")

    @classmethod
    def _from_checkpoint_inner(cls, directory, config, step, ladder, fused,
                               page_windows, coalesce_pages,
                               coalesce_groups, sparse_feed,
                               sparse_nnz_cap, mesh_config, make_mesh,
                               latest_step, load_sidecar,
                               restore_checkpoint, Trainer,
                               quant: str = "off") -> "Predictor":
        if step is None:
            step = latest_step(directory)
            if step is None:
                raise FileNotFoundError(f"no checkpoints under {directory!r}")
        extra = load_sidecar(directory, step)

        if config is None:
            if "model_config" not in extra:
                raise ValueError(
                    f"checkpoint {directory!r} predates sidecar model configs; "
                    "pass the architecture explicitly via `config`"
                )
            mc = dict(extra["model_config"])
            mc["quantiles"] = tuple(mc.get("quantiles", ()))
            config = Config(model=ModelConfig(**mc))

        metric_names = extra["metric_names"]
        mesh = make_mesh(mesh_config) if mesh_config is not None else None
        trainer = Trainer(config, extra["feature_dim"], metric_names,
                          mesh=mesh)
        target = trainer.init_state(
            np.zeros((1, extra["window_size"], extra["feature_dim"]), np.float32)
        )
        state, _ = restore_checkpoint(directory, target, step=step)
        # The stored parity envelope rides next to the checkpoint: first
        # quantized load at a mode measures and pins it; every later
        # load re-measures and the budget gate raises on violation
        # (Predictor._measure_parity).
        quant_budget = None
        if quant != "off":
            import json
            import os

            env_path = cls._quant_envelope_path(directory, quant)
            if os.path.exists(env_path):
                with open(env_path, encoding="utf-8") as fh:
                    quant_budget = json.load(fh)
        predictor = cls(
            params=state.params,
            model_config=trainer.model_config,
            x_stats=MinMaxStats.from_dict(extra["x_stats"]),
            y_stats=MinMaxStats.from_dict(extra["y_stats"]),
            metric_names=metric_names,
            window_size=extra["window_size"],
            space_dict=extra.get("space"),
            delta_mask=extra.get("delta_mask"),
            ladder=ladder,
            fused=fused,
            page_windows=page_windows,
            coalesce_pages=coalesce_pages,
            coalesce_groups=coalesce_groups,
            sparse_feed=sparse_feed,
            sparse_nnz_cap=sparse_nnz_cap,
            quant=quant,
            quant_budget=quant_budget,
            mesh=mesh,
        )
        if quant != "off" and quant_budget is None:
            import json

            env_path = cls._quant_envelope_path(directory, quant)
            with open(env_path, "w", encoding="utf-8") as fh:
                json.dump({"step": step, **predictor.parity_envelope},
                          fh, indent=2, sort_keys=True)
        return predictor

    def space(self):
        """The training corpus's CallPathSpace (column-exact featurization
        for raw serve-time traces); None for pre-sidecar checkpoints."""
        if self.space_dict is None:
            return None
        from deeprest_tpu.data.featurize import CallPathSpace

        return CallPathSpace.from_dict(self.space_dict)

    # ------------------------------------------------------------------
    # predict_series / predict_series_many come from FusedInferenceMixin:
    # the fused one-dispatch-per-page device pipeline by default, falling
    # back to rolled_prediction_reference through apply_windows (the
    # shape-laddered, MicroBatcher-coalesced host path) — see
    # serve/fused.py for the routing rule and numerics contract.
