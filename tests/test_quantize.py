"""Quantized serving path (round 22, ops/quantize.py): leaf roundtrip
bounds, weight-tree selection, the dequant-at-use hooks in ops/gru.py
and models/qrnn.py, the parity envelope as a product contract (stored
next to the checkpoint, re-measured and ENFORCED on every later load),
and the export/restore mode guard.

The deliberately-violated-envelope test is the pinned failure mode: a
tampered (impossibly tight) stored budget must make from_checkpoint
raise QuantParityError — a violated envelope is never benign, never a
silent fallback to f32."""

import dataclasses
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeprest_tpu.config import (
    Config, FeaturizeConfig, InferConfig, ModelConfig, TrainConfig,
)
from deeprest_tpu.data.featurize import featurize_buckets
from deeprest_tpu.ops import quantize as quant_ops
from deeprest_tpu.ops.quantize import (
    QuantParityError, QuantTensor, check_envelope, dequantize,
    dequantize_params, quantize_leaf_int8, quantize_params, weight_bytes,
)
from deeprest_tpu.serve.predictor import Predictor
from deeprest_tpu.train import Trainer, prepare_dataset

from conftest import make_series_buckets

SMALL = Config(
    model=ModelConfig(hidden_size=8, dropout_rate=0.1),
    train=TrainConfig(num_epochs=1, batch_size=16, window_size=12,
                      eval_stride=12, eval_max_cycles=3, seed=0),
)


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    """Tiny 1-epoch trained checkpoint (the test_coalesce recipe)."""
    buckets = make_series_buckets(120, seed=5)
    data = featurize_buckets(buckets, FeaturizeConfig(round_to=8))
    bundle = prepare_dataset(data, SMALL.train)
    tr = Trainer(SMALL, bundle.feature_dim, bundle.metric_names)
    state, _ = tr.fit(bundle, num_epochs=1)
    directory = str(tmp_path_factory.mktemp("quant_ckpt"))
    tr.save(directory, state, bundle)
    return dict(dir=directory, bundle=bundle)


# ---------------------------------------------------------------------------
# leaf-level quantization


def test_quantize_leaf_roundtrip_bound():
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((64, 48)) * 0.2).astype(np.float32)
    qt = quantize_leaf_int8(jnp.asarray(w))
    assert isinstance(qt, QuantTensor)
    assert qt.data.dtype == jnp.int8 and qt.data.shape == w.shape
    assert qt.scale.dtype == jnp.float32 and qt.scale.shape == (1, 48)
    back = np.asarray(dequantize(qt))
    # symmetric rounding: error per element <= scale/2 for that channel
    half_scale = np.asarray(qt.scale)[0] / 2.0
    assert (np.abs(back - w) <= half_scale + 1e-7).all()
    # per-OUTPUT-channel: each column's scale tracks ITS max magnitude
    expect = np.abs(w).max(axis=0) / 127.0
    np.testing.assert_allclose(np.asarray(qt.scale)[0], expect, rtol=1e-6)


def test_dequantize_is_identity_on_plain_arrays():
    x = jnp.ones((3, 4), jnp.float32)
    assert dequantize(x) is x


def test_check_envelope_missing_cell_is_violation():
    viol = check_envelope({"cpu|q0.5": 1e-4}, {})
    assert viol and "cpu|q0.5" in viol[0]
    assert not check_envelope({"cpu|q0.5": 1e-4}, {"cpu|q0.5": 2e-4})
    assert check_envelope({"cpu|q0.5": 3e-4}, {"cpu|q0.5": 2e-4})


# ---------------------------------------------------------------------------
# tree-level: selection, bytes, mode plumbing


def _model_params(f=32, h=16, e=2, w=12):
    from deeprest_tpu.models.qrnn import QuantileGRU

    mc = ModelConfig(feature_dim=f, num_metrics=e, hidden_size=h,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, w, f), np.float32),
                        deterministic=True)["params"]
    return model, mc, params


def test_quantize_params_selects_weight_matrices_only():
    _, _, params = _model_params()
    qp = quantize_params(params, "int8")
    leaves = jax.tree_util.tree_leaves_with_path(
        qp, is_leaf=lambda x: isinstance(x, QuantTensor))
    kinds = {"quant": 0, "plain": 0}
    for path, leaf in leaves:
        name = str(path[-1])
        if isinstance(leaf, QuantTensor):
            kinds["quant"] += 1
        else:
            kinds["plain"] += 1
            # biases / norm / stat leaves must stay full precision
            assert leaf.dtype == jnp.float32, (name, leaf.dtype)
    assert kinds["quant"] >= 4          # w_ih + w_hh per GRU, head, mask
    assert kinds["plain"] >= 1

    # bf16 mode: weight matrices cast, everything else untouched
    bp = quantize_params(params, "bf16")
    dtypes = {str(leaf.dtype)
              for leaf in jax.tree_util.tree_leaves(bp)}
    assert "bfloat16" in dtypes and "float32" in dtypes


def test_weight_bytes_ratio_meets_gate():
    _, _, params = _model_params(f=256, h=64)
    full = weight_bytes(params)
    int8 = weight_bytes(quantize_params(params, "int8"))
    bf16 = weight_bytes(quantize_params(params, "bf16"))
    assert full / int8 >= 3.5
    assert full / bf16 >= 1.9


def test_dequantize_params_roundtrip_close():
    _, _, params = _model_params()
    qp = quantize_params(params, "int8")
    back = dequantize_params(qp)
    ref = jax.tree_util.tree_leaves(params)
    got = jax.tree_util.tree_leaves(back)
    assert len(ref) == len(got)
    for r, g in zip(ref, got):
        assert r.shape == g.shape
        assert float(jnp.max(jnp.abs(r - g.astype(r.dtype)))) < 0.05


# ---------------------------------------------------------------------------
# dequant-at-use hooks: ops/gru.py + models/qrnn.py share one site


def test_gru_resolves_quantized_weights():
    from deeprest_tpu.ops.gru import GRUParams, gru, init_gru_params

    params = init_gru_params(jax.random.PRNGKey(1), 2, 16, 8)
    x = np.random.default_rng(2).standard_normal(
        (3, 10, 16)).astype(np.float32)
    ref = gru(params, x)
    qparams = GRUParams(
        w_ih=quantize_leaf_int8(params.w_ih),
        w_hh=quantize_leaf_int8(params.w_hh),
        b_ih=params.b_ih, b_hh=params.b_hh)
    got = gru(qparams, x)
    assert got.shape == ref.shape
    assert float(jnp.max(jnp.abs(got - ref))) < 0.05
    # and EXACT parity with dequantizing by hand first — one dequant
    # site means no second rounding anywhere
    manual = gru(params._replace(w_ih=dequantize(qparams.w_ih),
                                 w_hh=dequantize(qparams.w_hh)), x)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(manual))


# ---------------------------------------------------------------------------
# Predictor integration: envelope measured, stored, ENFORCED


def test_from_checkpoint_quant_modes(ckpt):
    pred_off = Predictor.from_checkpoint(ckpt["dir"])
    pred_q = Predictor.from_checkpoint(ckpt["dir"], quant="int8")
    assert pred_off.quant == "off" and pred_off.parity_envelope is None
    assert pred_q.quant == "int8"
    env = pred_q.parity_envelope
    assert env["mode"] == "int8"
    assert set(env["measured"]) == set(env["budget"])
    assert all(env["measured"][k] <= env["budget"][k] for k in env["budget"])

    # digests must differ (surface cache keys, reload dedup)
    assert pred_off.params_digest() != pred_q.params_digest()
    # executable ladder stays flat: same count either mode
    t = np.random.default_rng(3).random(
        (30, pred_off.feature_dim)).astype(np.float32)
    out_off = pred_off.predict_series(t)
    out_q = pred_q.predict_series(t)
    assert pred_off.jit_cache_size() == pred_q.jit_cache_size()
    # The ENVELOPE contract is per-window model output (normalized
    # space, asserted above); the serving wire amplifies it through
    # de-normalization (y range) and delta integration (prefix-sum
    # accumulates per-window drift over the series), so here the check
    # is a loose sanity bound, not the envelope itself, which
    # test_serving_drift_inside_the_stored_envelope holds at unit stats.
    assert float(np.max(np.abs(out_q - out_off))) < 0.5
    # stats name the mode
    assert pred_q.jit_cache_stats()["quant"] == "int8"


def test_envelope_file_written_and_reused(ckpt):
    path = os.path.join(ckpt["dir"], "quant_parity_int8.json")
    Predictor.from_checkpoint(ckpt["dir"], quant="int8")
    assert os.path.isfile(path)
    with open(path, encoding="utf-8") as fh:
        stored = json.load(fh)
    assert stored["mode"] == "int8"
    assert stored["measured"] and stored["budget"]
    # second load consumes the STORED budget (the pinned contract),
    # and passes against it
    pred2 = Predictor.from_checkpoint(ckpt["dir"], quant="int8")
    assert pred2.parity_envelope["budget"] == pytest.approx(
        stored["budget"])


def test_violated_envelope_raises(ckpt):
    """THE pinned failure mode: an impossibly tight stored budget must
    fail the load loudly — never silently serve out-of-envelope."""
    Predictor.from_checkpoint(ckpt["dir"], quant="int8")   # write file
    path = os.path.join(ckpt["dir"], "quant_parity_int8.json")
    with open(path, encoding="utf-8") as fh:
        stored = json.load(fh)
    tampered = dict(stored)
    tampered["budget"] = {k: 1e-12 for k in stored["budget"]}
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(tampered, fh)
    try:
        with pytest.raises(QuantParityError, match="parity envelope"):
            Predictor.from_checkpoint(ckpt["dir"], quant="int8")
        # and QuantParityError must be a ValueError so generic config
        # handling catches it, while the reloader still logs it loudly
        assert issubclass(QuantParityError, ValueError)
    finally:
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(stored, fh)


def test_bf16_mode_parity(ckpt):
    pred = Predictor.from_checkpoint(ckpt["dir"], quant="bf16")
    env = pred.parity_envelope
    assert env["mode"] == "bf16"
    assert all(env["measured"][k] <= env["budget"][k] for k in env["budget"])


# ---------------------------------------------------------------------------
# the serving path at unit stats: one tree, one Predictor a mode


_MODES = ("off", "int8", "bf16")
_SERIES = 96


@pytest.fixture(scope="module")
def unit_stats_predictors():
    """The same float32 tree served at every mode with unit min/max stats,
    so de-normalization amplifies nothing and the serving wire can be held
    to the envelope itself; each warmed by the same one request."""
    from router_test_support import build_tiny

    f = 96
    preds = {mode: build_tiny(f=f, e=3, h=48, w=12, quant=mode)
             for mode in _MODES}
    traffic = np.random.default_rng(7).random(
        (_SERIES, f)).astype(np.float32)
    answers = {m: np.asarray(p.predict_series(traffic), np.float64)
               for m, p in preds.items()}
    warm = {m: p.jit_cache_size() for m, p in preds.items()}
    return dict(preds=preds, traffic=traffic, answers=answers, warm=warm)


@pytest.mark.parametrize("mode", ["int8", "bf16"])
def test_serving_drift_inside_the_stored_envelope(unit_stats_predictors,
                                                  mode):
    """A held-out series (not the quantize-time probe) through the fused
    path stays inside the mode's budget, which holds one cell a metric and
    quantile."""
    u = unit_stats_predictors
    env = u["preds"][mode].parity_envelope
    assert len(env["budget"]) == len(env["measured"]) == 9   # 3 x 3
    drift = float(np.max(np.abs(u["answers"][mode] - u["answers"]["off"])))
    assert 0.0 < drift <= max(env["budget"].values())


def test_executable_count_is_the_same_at_every_mode(unit_stats_predictors):
    warm = unit_stats_predictors["warm"]
    assert warm["off"] is not None and warm["off"] >= 1
    assert len(set(warm.values())) == 1, warm


@pytest.mark.parametrize("mode", _MODES)
def test_no_executable_added_after_the_warm_up(unit_stats_predictors, mode):
    """The same series again and its first half (a second use of the
    rung) compile nothing at any mode."""
    u = unit_stats_predictors
    pred, traffic = u["preds"][mode], u["traffic"]
    np.testing.assert_array_equal(
        np.asarray(pred.predict_series(traffic), np.float64),
        u["answers"][mode])
    pred.predict_series(traffic[: _SERIES // 2])
    assert pred.jit_cache_size() == u["warm"][mode]


def test_invalid_quant_mode_rejected(ckpt):
    with pytest.raises(ValueError, match="quant"):
        Predictor.from_checkpoint(ckpt["dir"], quant="int4")


# ---------------------------------------------------------------------------
# serving surfaces: healthz + verdict + surface cache key + config


def test_healthz_reports_quant_mode(ckpt):
    from deeprest_tpu.serve import PredictionService

    pred = Predictor.from_checkpoint(ckpt["dir"], quant="int8")
    out = PredictionService(pred).healthz()
    assert out["quant"]["mode"] == "int8"
    assert out["quant"]["parity_max"] == max(
        pred.parity_envelope["measured"].values())
    assert out["quant"]["parity_cells"] == len(
        pred.parity_envelope["measured"])
    # off-mode still reports the (additive) key so dashboards need no
    # conditional
    off = PredictionService(
        Predictor.from_checkpoint(ckpt["dir"])).healthz()
    assert off["quant"] == {"mode": "off"}


def test_surface_cache_key_records_quant_mode(ckpt):
    from deeprest_tpu.config import SurfaceConfig
    from deeprest_tpu.serve.surface import CapacitySurfaceManager

    mgr = CapacitySurfaceManager(SurfaceConfig(enabled=True))
    pred_off = Predictor.from_checkpoint(ckpt["dir"])
    pred_q = Predictor.from_checkpoint(ckpt["dir"], quant="int8")
    k_off, k_q = mgr.params_hash_of(pred_off), mgr.params_hash_of(pred_q)
    assert k_off != k_q
    assert k_q.endswith(":int8")


def test_infer_config_quant_validation():
    assert InferConfig(quant="int8").quant == "int8"
    with pytest.raises(ValueError, match="InferConfig.quant"):
        InferConfig(quant="int4")


def test_exported_restore_mode_mismatch_raises():
    from deeprest_tpu.serve.export import _FORMAT, ExportedPredictor

    manifest = {"format": _FORMAT, "quant": "int8"}
    with pytest.raises(ValueError, match="--quant int8"):
        ExportedPredictor(None, manifest)            # default quant="off"
    with pytest.raises(ValueError, match="exported at quant='off'"):
        ExportedPredictor(None, {"format": _FORMAT}, quant="int8")
