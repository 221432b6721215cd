"""Weight quantization for the serving path: int8 / bf16 storage,
f32-reference parity measured and pinned (ROADMAP item 2, round 22).

The serving profile is weight-bandwidth-bound at the flagship shapes
(per-step ``[32,128]x[128,384]`` dots touch every GRU weight byte each
window step at ~12% MXU row occupancy), so shrinking the weight plane is
the raw-speed lever that needs no new kernel: int8 storage moves 4x
fewer bytes through HBM per step, bf16 2x.  This module owns the whole
discipline:

- ``quantize_params(params, mode)`` — per-output-channel symmetric int8
  (a ``QuantTensor`` of int8 data + f32 scales) or bf16 storage for
  every matmul weight leaf (``w_ih``/``w_hh``/``head_w``/``mask_w2``);
  biases, the mask MLP's first layer, and all norm/stat leaves stay f32.
- ``dequantize`` — THE sanctioned dequant site.  int8 values may reach
  float math only through this helper; graftlint's QT001 rule
  (analysis/rules_jax.py) fires on any other int8→float promotion along
  any call chain into ops/ or serve/.  Dequant runs ON DEVICE inside
  the existing jitted executables (the resolve hooks below are called
  from the jitted wrappers), so XLA fuses scale-multiply into the
  consumer and the fused engine's executables stay one-per-rung.
- ``resolve hooks`` — ``ops.gru.resolve_weights`` and
  ``models.qrnn.resolve_params`` both route here, so the scan and
  pallas recurrence paths (and the bidirectional variant)
  share this one dequant site.
- parity as a product contract — ``parity_envelope`` measures the
  per-(metric, quantile) max deviation vs the f32 reference on a
  deterministic probe batch at quantize time; ``budget_from_measured``
  pins the stored budget; ``check_envelope`` is the loud gate
  (serve/predictor.py raises on violation at every (re)load).

Quantization itself runs once per (re)load on the host path; only
``dequantize`` is jit-reachable, so everything here uses jnp with
explicit dtypes (the JX006 discipline).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# The serving quant modes (config.InferConfig.quant / cli --quant).
QUANT_MODES = ("off", "int8", "bf16")

# Matmul weight leaves, by the param-name fragments the model fixes
# (models/qrnn.py): the GRU input/recurrent kernels, the quantile head,
# and the feature-mask MLP's second (einsum) layer.  ``mask_w1`` is an
# elementwise gate input, biases are adds — both stay f32.
WEIGHT_FRAGMENTS = ("w_ih", "w_hh", "head_w", "mask_w2")

# Symmetric int8: scales map the per-channel max magnitude to the full
# signed range (127, not 128 — symmetric, no zero-point).
_INT8_MAX = 127.0


class QuantParityError(ValueError):
    """A quantized prediction exceeded its stored parity budget — the
    envelope gate (serve/predictor.py) fails loudly, by contract; the
    checkpoint reloader must never mistake this for a benign mid-write
    checkpoint race."""


class QuantTensor(NamedTuple):
    """One int8-quantized weight matrix: ``data`` int8 ``[..., K, C]``
    with f32 per-output-channel ``scale`` ``[..., 1, C]`` (the reduction
    ran over the contraction axis K, so each output channel dequantizes
    with its own scale).  A NamedTuple, hence a pytree: it threads
    through jit/checkpoint treedefs as two leaves."""

    data: Any
    scale: Any


def is_weight_leaf(name: str) -> bool:
    """Is this param leaf one of the matmul weight matrices the
    quantized path stores narrow?"""
    return any(frag in name for frag in WEIGHT_FRAGMENTS)


def _leaf_name(path) -> str:
    """Last path component's name: DictKey for flax param dicts,
    GetAttrKey for NamedTuple params (ops.gru.GRUParams)."""
    key = path[-1]
    name = getattr(key, "key", None)
    if name is None:
        name = getattr(key, "name", None)
    return name if isinstance(name, str) else ""


def quantize_leaf_int8(w) -> QuantTensor:
    """Per-output-channel symmetric int8 quantization of one weight
    matrix ``[..., K, C]`` (contraction axis second-to-last, matching
    every einsum in models/qrnn.py and ops/gru.py)."""
    w = jnp.asarray(w, jnp.float32)
    if w.ndim < 2:
        raise ValueError(
            f"int8 quantization needs a [.., K, C] matrix, got {w.shape}")
    amax = jnp.max(jnp.abs(w), axis=-2, keepdims=True)
    scale = jnp.maximum(amax, jnp.float32(1e-12)) / jnp.float32(_INT8_MAX)
    q = jnp.clip(jnp.round(w / scale), -_INT8_MAX, _INT8_MAX)
    return QuantTensor(data=q.astype(jnp.int8),
                       scale=scale.astype(jnp.float32))


def dequantize(leaf, dtype=None):
    """THE sanctioned dequant site (QT001): int8 weights re-enter float
    math here and nowhere else.  Runs on device inside the calling
    executable — XLA fuses the widen+scale into the consumer dot.
    Identity on anything that is not a ``QuantTensor`` (f32 leaves and
    the bf16-storage mode, whose leaves are plain bf16 arrays cast at
    use by the model's own compute-dtype cast)."""
    if isinstance(leaf, QuantTensor):
        w = leaf.data.astype(jnp.float32) * leaf.scale
        return w if dtype is None else w.astype(dtype)
    return leaf


def _is_quant_leaf(x) -> bool:
    return isinstance(x, QuantTensor)


def quantize_params(params, mode: str):
    """Quantize every matmul weight leaf of ``params`` (a flax param
    dict or an ops.gru.GRUParams) for serving.

    - ``"off"``  — identity.
    - ``"int8"`` — weight leaves become ``QuantTensor`` (int8 + f32
      per-output-channel scales); everything else unchanged.
    - ``"bf16"`` — weight leaves stored bf16 (plain arrays; the model's
      compute-dtype cast handles them at use); everything else
      unchanged.
    """
    if mode == "off":
        return params
    if mode not in QUANT_MODES:
        raise ValueError(f"quant mode {mode!r} not in {QUANT_MODES}")

    def convert(path, leaf):
        if not is_weight_leaf(_leaf_name(path)):
            return leaf
        if mode == "int8":
            return quantize_leaf_int8(leaf)
        return jnp.asarray(leaf).astype(jnp.bfloat16)

    return jax.tree_util.tree_map_with_path(convert, params)


def dequantize_params(params):
    """Tree-wide dequant-at-use: every ``QuantTensor`` leaf through the
    sanctioned helper, every other leaf untouched.  This IS the
    weights-adapter the jitted serving wrappers call (identity trace
    for unquantized trees), so quantized and f32 predictors share one
    apply path and the executable count stays flat across quant modes."""
    return jax.tree_util.tree_map(dequantize, params,
                                  is_leaf=_is_quant_leaf)


# -- accounting (the bench's bytes gate) ------------------------------------


def weight_bytes(params) -> int:
    """Bytes held by the matmul weight leaves (scales included for
    QuantTensors — the honest number: the scale plane ships with the
    weights on every tenant swap)."""
    total = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=_is_quant_leaf)
    for path, leaf in flat:
        if isinstance(leaf, QuantTensor):
            total += leaf.data.size * leaf.data.dtype.itemsize
            total += leaf.scale.size * leaf.scale.dtype.itemsize
        elif is_weight_leaf(_leaf_name(path)):
            total += (int(np.prod(leaf.shape))
                      * np.dtype(leaf.dtype).itemsize)
    return total


# -- the parity envelope (measured, stored, enforced) -----------------------

# Probe geometry: deterministic, seeded, and small — one batch is enough
# because the envelope is a BUDGET (measured x margin), not a proof; the
# margin absorbs input-distribution slack and the reload-time re-measure
# keeps the stored budget honest across code changes.
PROBE_BATCH = 4
PROBE_SEED = 0
ENVELOPE_MARGIN = 2.0
ENVELOPE_FLOOR = 1e-6


def probe_batch(window_size: int, feature_dim: int,
                batch: int = PROBE_BATCH) -> np.ndarray:
    """The deterministic parity probe: uniform [0,1) windows (the
    normalized-feature range the model serves)."""
    rng = np.random.default_rng(PROBE_SEED)
    return rng.random((batch, window_size, feature_dim)).astype(np.float32)


def parity_envelope(ref_out, quant_out, metric_names,
                    quantiles) -> dict[str, float]:
    """Per-(metric, quantile) max |quantized - f32| over the probe,
    keyed ``"<metric>|q<quantile>"`` — model outputs are ``[B,T,E,Q]``
    (models/qrnn.py), reduced over batch and time."""
    ref = np.asarray(ref_out, np.float32)
    got = np.asarray(quant_out, np.float32)
    per = np.abs(got - ref).max(axis=(0, 1))              # [E, Q]
    return {
        f"{m}|q{q:g}": float(per[i, j])  # graftlint: disable=JX003 -- per is already a HOST np array (the one device→host readback happened at the np.asarray above); this loop indexes host memory once per (metric, quantile) cell at quantize time, not per serving request
        for i, m in enumerate(metric_names)
        for j, q in enumerate(quantiles)
    }


def budget_from_measured(measured: dict[str, float],
                         margin: float = ENVELOPE_MARGIN,
                         floor: float = ENVELOPE_FLOOR) -> dict[str, float]:
    """The stored budget: measured x margin with an absolute floor (a
    dead-zero measured cell must not pin an unmeetable 0.0 budget)."""
    return {k: max(v * margin, floor) for k, v in measured.items()}


def check_envelope(measured: dict[str, float],
                   budget: dict[str, float]) -> list[str]:
    """Violations of the stored budget — the loud-gate input.  A cell
    missing from the budget is a violation too (a quant mode must never
    silently serve metrics its envelope never covered)."""
    out = []
    for key, val in measured.items():
        cap = budget.get(key)
        if cap is None:
            out.append(f"{key}: no stored budget for this cell")
        elif val > cap:
            out.append(f"{key}: measured {val:.3e} > budget {cap:.3e}")
    return out
