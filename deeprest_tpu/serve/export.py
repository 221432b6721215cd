"""Portable inference artifact: checkpoint → serialized StableHLO on disk.

The north star names this path explicitly ("predictor/ exports via jax2tf
for the Go gRPC server" — BASELINE.json north_star; SURVEY.md §7.1 step 6):
an inference artifact a non-JAX consumer can load.  TensorFlow is not in
this image, so the artifact is ``jax.export``'s portable serialization —
versioned StableHLO with the trained parameters baked in as constants and a
*symbolic* batch dimension, executable by any PJRT-capable runtime (and by
``jax.export.deserialize`` here).  Everything else a consumer needs —
normalization statistics, metric names, quantiles, the call-path feature
space — rides next to it in a plain-JSON manifest, so serving state cannot
drift from training state (the same property Predictor gets from the
checkpoint sidecar).

Layout of an artifact directory::

    model.stablehlo   serialized jax.export artifact  (binary)
    manifest.json     stats + names + dims + model config  (JSON)
"""

from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import export as jexport

from deeprest_tpu.data.windows import MinMaxStats
from deeprest_tpu.models.qrnn import QuantileGRU, resolve_params
from deeprest_tpu.serve.batcher import BatchedBackendMixin
from deeprest_tpu.serve.fused import FusedInferenceMixin
from deeprest_tpu.serve.predictor import Predictor

ARTIFACT_BLOB = "model.stablehlo"
ARTIFACT_MANIFEST = "manifest.json"
_FORMAT = "jax.export/stablehlo"
_PLATFORMS = ("cpu", "tpu")


def export_predictor(pred: Predictor, directory: str) -> str:
    """Serialize ``pred`` into ``directory`` (created if needed).

    The exported computation is the deterministic forward pass on
    *normalized* windows ``[b, W, F] -> [b, W, E, Q]`` with ``b``
    symbolic, lowered for both cpu and tpu so one artifact serves on
    either; normalization/de-normalization are host-side (manifest).

    The recurrence in the artifact is always the ``lax.scan``: the Mosaic
    kernel is a TPU-only custom call that sizes its blocks from a static
    row count, so it can be lowered neither for the cpu platform nor under
    the symbolic ``b`` (on a TPU host ``rnn_backend="auto"`` would pick it
    and the export would fail).  A CPU host has always exported the scan.
    """
    os.makedirs(directory, exist_ok=True)
    portable = QuantileGRU(config=dataclasses.replace(
        pred.model_config, rnn_backend="scan"))
    (b,) = jexport.symbolic_shape("b")
    spec = jax.ShapeDtypeStruct(
        (b, pred.window_size, pred.feature_dim), jnp.float32)
    # resolve_params: a quantized predictor's tree dequantizes at trace
    # time, so the artifact bakes the quantized-then-dequantized values —
    # the exported module reproduces the quantized numerics (and the
    # manifest carries the mode + its measured parity envelope below).
    fn = jax.jit(lambda x: portable.apply(
        # graftlint: disable=JX001 -- deliberate: the artifact's whole point is baking the trained params into the serialized module as constants; bit parity vs the in-process path is pinned by tests/test_export_serve.py
        {"params": resolve_params(pred.params)}, x, deterministic=True))
    exported = jexport.export(fn, platforms=_PLATFORMS)(spec)
    with open(os.path.join(directory, ARTIFACT_BLOB), "wb") as f:
        f.write(exported.serialize())
    manifest = {
        "format": _FORMAT,
        "platforms": list(_PLATFORMS),
        "metric_names": pred.metric_names,
        "window_size": pred.window_size,
        "feature_dim": pred.feature_dim,
        "quantiles": list(pred.quantiles),
        "x_stats": pred.x_stats.to_dict(),
        "y_stats": pred.y_stats.to_dict(),
        "model_config": dataclasses.asdict(pred.model_config),
        "space": pred.space_dict,
        "delta_mask": (np.asarray(pred.delta_mask, bool).tolist()
                       if pred.delta_mask is not None else None),
        # quantized-serving provenance (round 22): the mode the baked
        # weights were quantized at, plus the measured-at-quantize-time
        # parity envelope — restoring at a DIFFERENT mode raises
        # (ExportedPredictor.load), never silently serves other numerics
        "quant": getattr(pred, "quant", "off"),
        "quant_parity": getattr(pred, "parity_envelope", None),
    }
    with open(os.path.join(directory, ARTIFACT_MANIFEST), "w",
              encoding="utf-8") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    return directory


def export_aot_sidecar(pred: Predictor, checkpoint_dir: str,
                       rungs=None) -> dict:
    """Compile + serialize the fused serving executables NEXT TO THE
    CHECKPOINT (``<ckpt>/aot/`` — serve/aot.py), the export-time half of
    fleet admission-by-deserialize: a pool admitting this checkpoint
    loads the artifacts instead of compiling the ladder.  Unlike the
    StableHLO artifact above, AOT sidecars are params-AGNOSTIC (params
    are runtime arguments) but platform-exact — the manifest fingerprint
    gates the load.  Returns a summary of what was written."""
    from deeprest_tpu.serve.aot import export_aot

    manifest = export_aot(pred, checkpoint_dir, rungs=rungs)
    entries = manifest["entries"]
    return {
        "dir": os.path.join(checkpoint_dir, "aot"),
        "executables": len(entries),
        "bytes": sum(e["bytes"] for e in entries),
        "rungs": sorted({e["rung"] for e in entries}),
        "platform": manifest["fingerprint"]["platform"],
    }


class ExportedPredictor(BatchedBackendMixin, FusedInferenceMixin):
    """Drop-in serving backend loaded from an artifact directory.

    Exposes the same serving protocol as :class:`Predictor`
    (``predict_series``, ``metric_names``, ``window_size``, ``quantiles``,
    ``feature_dim``, ``median_index``, ``space``, and the batched
    ``apply_windows`` entry point incl. MicroBatcher attachment), so
    AnomalyDetector, WhatIfEstimator, and the HTTP server work unchanged
    on either backend.  The artifact's symbolic batch dimension still
    compiles one executable per concrete shape it sees — the shape ladder
    bounds that set to the rungs, exactly as on the in-process path.
    """

    def __init__(self, exported: jexport.Exported, manifest: dict,
                 ladder: tuple[int, ...] | None = None,
                 fused: bool = True,
                 page_windows: int | None = None,
                 coalesce_pages: int | None = None,
                 coalesce_groups: int = 1,
                 quant: str = "off"):
        if manifest.get("format") != _FORMAT:
            raise ValueError(f"unknown artifact format {manifest.get('format')!r}")
        baked = str(manifest.get("quant", "off"))
        if str(quant) != baked:
            # A quantized artifact's weights are baked at export time; the
            # caller cannot change the numerics here, only acknowledge
            # them.  Refusing beats silently serving numerics the operator
            # did not opt into (the parity envelope belongs to ``baked``).
            raise ValueError(
                f"artifact was exported at quant={baked!r} but load was "
                f"asked for quant={quant!r}; pass --quant {baked} "
                f"(ExportedPredictor.load(..., quant={baked!r})) to serve "
                "it, or re-export at the mode you want")
        self.quant = baked
        self.parity_envelope = manifest.get("quant_parity")
        self._exported = exported
        self.manifest = manifest
        self.metric_names: list[str] = list(manifest["metric_names"])
        self.window_size: int = int(manifest["window_size"])
        self.feature_dim: int = int(manifest["feature_dim"])
        self.quantiles: tuple[float, ...] = tuple(manifest["quantiles"])
        self.x_stats = MinMaxStats.from_dict(manifest["x_stats"])
        self.y_stats = MinMaxStats.from_dict(manifest["y_stats"])
        self.space_dict = manifest.get("space")
        dm = manifest.get("delta_mask")
        self.delta_mask = np.asarray(dm, bool) if dm is not None else None
        self._init_batching(self._exported.call, ladder=ladder,
                            coalesce_groups=coalesce_groups)
        # Exported.call is traceable under jit, so the deserialized
        # StableHLO module composes into the same fused one-dispatch
        # pipeline the in-process Predictor uses (serve/fused.py).  The
        # artifact's weights are baked into the module; params stay ().
        self._init_fused(lambda _, x: self._exported.call(x),
                         enabled=fused, page_windows=page_windows,
                         coalesce_pages=coalesce_pages)

    @classmethod
    def load(cls, directory: str,
             ladder: tuple[int, ...] | None = None,
             fused: bool = True,
             page_windows: int | None = None,
             coalesce_pages: int | None = None,
             coalesce_groups: int = 1,
             quant: str = "off") -> "ExportedPredictor":
        with open(os.path.join(directory, ARTIFACT_MANIFEST),
                  encoding="utf-8") as f:
            manifest = json.load(f)
        with open(os.path.join(directory, ARTIFACT_BLOB), "rb") as f:
            exported = jexport.deserialize(f.read())
        return cls(exported, manifest, ladder=ladder, fused=fused,
                   page_windows=page_windows, coalesce_pages=coalesce_pages,
                   coalesce_groups=coalesce_groups, quant=quant)

    def jit_cache_size(self) -> int | None:
        """Fused-pipeline executable count (the artifact's own symbolic-
        batch apply has no probe); None when the engine is disabled."""
        return (self._fused.cache_size()
                if self._fused is not None else None)

    def median_index(self) -> int:
        diffs = [abs(q - 0.5) for q in self.quantiles]
        return diffs.index(min(diffs))

    def space(self):
        """The training corpus's CallPathSpace (see Predictor.space)."""
        if self.space_dict is None:
            return None
        from deeprest_tpu.data.featurize import CallPathSpace

        return CallPathSpace.from_dict(self.space_dict)

    # predict_series / predict_series_many come from FusedInferenceMixin —
    # identical tiling/integration/routing semantics to the in-process
    # Predictor (fused device pipeline by default, shape-laddered
    # rolled_prediction_reference through ``apply_windows`` otherwise).
