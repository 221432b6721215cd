"""Semantic tests for the QuantileGRU: the fused/batched implementation must
equal an explicit per-expert loop (masks applied to inputs, O(E²) mixing)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeprest_tpu.config import ModelConfig
from deeprest_tpu.models import QuantileGRU
from deeprest_tpu.ops.gru import GRUParams, bidirectional_gru

CFG = ModelConfig(feature_dim=6, num_metrics=3, hidden_size=4)


def init_model(cfg=CFG, seed=0, batch=2, t=5):
    model = QuantileGRU(config=cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 100), (batch, t, cfg.feature_dim))
    variables = model.init(jax.random.PRNGKey(seed), x)
    return model, variables, x


def reference_forward(params, x, cfg):
    """Straightforward per-expert loop with masks applied to the *inputs*
    and the mixing mean computed over an explicit stack of others."""
    E = cfg.num_metrics
    rnn_outs = []
    for e in range(E):
        hidden = np.maximum(params["mask_w1"][e] + params["mask_b1"][e], 0.0)
        logits = hidden @ params["mask_w2"][e] + params["mask_b2"][e]
        mask = np.asarray(jax.nn.softmax(jnp.asarray(logits)))
        xm = jnp.asarray((np.asarray(x) * mask)[None])  # [1,B,T,F]
        fwd = GRUParams(*[jnp.asarray(params[f"gru_fwd_{k}"][e][None])
                          for k in ("w_ih", "w_hh", "b_ih", "b_hh")])
        bwd = GRUParams(*[jnp.asarray(params[f"gru_bwd_{k}"][e][None])
                          for k in ("w_ih", "w_hh", "b_ih", "b_hh")])
        rnn_outs.append(np.asarray(bidirectional_gru(fwd, bwd, xm))[0])  # [B,T,2H]

    preds = []
    for i in range(E):
        others = [rnn_outs[j] for j in range(E) if j != i]
        mix = np.mean(np.stack(others), axis=0) if others else rnn_outs[i]
        head_in = np.concatenate([mix, rnn_outs[i]], axis=-1)
        preds.append(head_in @ params["head_w"][i] + params["head_b"][i])
    return np.stack(preds, axis=2)  # [B,T,E,Q]


def test_forward_matches_explicit_loop():
    model, variables, x = init_model()
    got = np.asarray(model.apply(variables, x))
    params = {k: np.asarray(v) for k, v in variables["params"].items()}
    want = reference_forward(params, x, CFG)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_output_shape_and_dtype():
    model, variables, x = init_model()
    out = model.apply(variables, x)
    assert out.shape == (2, 5, CFG.num_metrics, len(CFG.quantiles))
    assert out.dtype == jnp.float32


def test_single_metric_mix_fallback():
    cfg = ModelConfig(feature_dim=4, num_metrics=1, hidden_size=3)
    model, variables, x = init_model(cfg)
    got = np.asarray(model.apply(variables, x))
    params = {k: np.asarray(v) for k, v in variables["params"].items()}
    want = reference_forward(params, x, cfg)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_dropout_train_vs_eval():
    model, variables, x = init_model()
    eval_a = model.apply(variables, x, deterministic=True)
    eval_b = model.apply(variables, x, deterministic=True)
    np.testing.assert_array_equal(np.asarray(eval_a), np.asarray(eval_b))

    train_a = model.apply(variables, x, deterministic=False,
                          rngs={"dropout": jax.random.PRNGKey(1)})
    train_b = model.apply(variables, x, deterministic=False,
                          rngs={"dropout": jax.random.PRNGKey(2)})
    assert not np.allclose(np.asarray(train_a), np.asarray(train_b))


def test_mask_is_a_distribution():
    """Each expert's feature mask must be a softmax over features: the model
    output must be invariant to scaling any *single* masked-out... instead,
    check directly that folded weights imply sum-to-one masks."""
    model, variables, x = init_model()
    p = variables["params"]
    hidden = jax.nn.relu(p["mask_w1"] + p["mask_b1"])
    mask = jax.nn.softmax(jnp.einsum("eh,ehf->ef", hidden, p["mask_w2"]) + p["mask_b2"])
    np.testing.assert_allclose(np.asarray(mask.sum(-1)), 1.0, rtol=1e-5)
    assert (np.asarray(mask) >= 0).all()


def test_jit_and_grad():
    model, variables, x = init_model()

    @jax.jit
    def loss_fn(params, x):
        out = QuantileGRU(config=CFG).apply({"params": params}, x)
        return jnp.mean(out ** 2)

    g = jax.grad(loss_fn)(variables["params"], x)
    leaves = jax.tree.leaves(g)
    assert leaves and all(np.isfinite(np.asarray(l)).all() for l in leaves)
    # Every parameter must receive gradient (no dead branches).
    for k, v in g.items():
        assert np.abs(np.asarray(v)).max() > 0, f"zero grad for {k}"


def test_median_index():
    assert QuantileGRU(config=CFG).median_index() == 1


def test_feature_dim_mismatch_raises():
    model, variables, _ = init_model()
    bad = jnp.zeros((2, 5, CFG.feature_dim + 1))
    try:
        model.apply(variables, bad)
        assert False, "expected ValueError"
    except ValueError as e:
        assert "feature_dim" in str(e)
    # a [G, B, T, F] stack is no calling convention (PR 28): it would
    # otherwise reach the recurrence as per-expert input
    with pytest.raises(ValueError, match=r"\[B, T, F\]"):
        model.apply(variables, jnp.zeros((2, 2, 5, CFG.feature_dim)))


def test_stacked_layers():
    cfg = ModelConfig(feature_dim=6, num_metrics=2, hidden_size=4, num_layers=2)
    model, variables, x = init_model(cfg)
    out = model.apply(variables, x)
    assert out.shape == (2, 5, 2, 3)
    p = variables["params"]
    assert "gru_fwd_l1_w_ih" in p and "gru_bwd_l1_w_ih" in p
    # deep-layer input dim is the previous layer's output (2H bidirectional)
    assert p["gru_fwd_l1_w_ih"].shape == (2, 8, 12)
    # all stacked params have sharding rules
    from deeprest_tpu.parallel import param_specs
    specs = param_specs(p)
    assert set(specs) == set(p)

    @jax.jit
    def loss_fn(params):
        return jnp.mean(model.apply({"params": params}, x) ** 2)

    g = jax.grad(loss_fn)(variables["params"])
    assert np.abs(np.asarray(g["gru_fwd_l1_w_ih"])).max() > 0


def test_bfloat16_compute_path():
    cfg = ModelConfig(feature_dim=6, num_metrics=2, hidden_size=4,
                      compute_dtype="bfloat16")
    model, variables, x = init_model(cfg)
    out = model.apply(variables, x)
    assert out.dtype == jnp.float32  # params/heads stay f32
    f32_cfg = ModelConfig(feature_dim=6, num_metrics=2, hidden_size=4)
    out32 = QuantileGRU(config=f32_cfg).apply(variables, x)
    # bf16 matmuls drift but stay in the same ballpark
    np.testing.assert_allclose(np.asarray(out), np.asarray(out32), atol=0.1)


def test_full_model_torch_weight_transplant_parity():
    """Pin the whole architecture to the reference: transplant every weight
    of the reference-equivalent torch model (mask MLP + bidirectional GRU +
    mixing + quantile heads — resource-estimation/qrnn.py:28-67) into
    QuantileGRU and require equal forward outputs AND equal pinball loss.
    Op-level GRU parity lives in test_ops.py; this is the end-to-end pin."""
    import pytest

    torch = pytest.importorskip("torch")
    from benchmarks.baseline_torch import TorchQuantileRNN

    from deeprest_tpu.ops import pinball_loss

    B, T, F, E, H = 2, 9, 6, 3, 4
    torch.manual_seed(3)
    tmodel = TorchQuantileRNN(F, E, hidden=H).eval()

    cfg = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                      dropout_rate=0.0)
    model, variables, _ = init_model(cfg)
    params = dict(variables["params"])

    def t(arr):
        return jnp.asarray(arr.detach().numpy())

    def stack(fn):
        return jnp.stack([fn(e) for e in tmodel.experts])

    params["mask_w1"] = stack(lambda e: t(e.mask_in.weight)[:, 0])
    params["mask_b1"] = stack(lambda e: t(e.mask_in.bias))
    params["mask_w2"] = stack(lambda e: t(e.mask_out.weight).T)
    params["mask_b2"] = stack(lambda e: t(e.mask_out.bias))
    for jax_name, torch_sfx in (("gru_fwd", ""), ("gru_bwd", "_reverse")):
        params[f"{jax_name}_w_ih"] = stack(
            lambda e: t(getattr(e.rnn, f"weight_ih_l0{torch_sfx}")).T)
        params[f"{jax_name}_w_hh"] = stack(
            lambda e: t(getattr(e.rnn, f"weight_hh_l0{torch_sfx}")).T)
        params[f"{jax_name}_b_ih"] = stack(
            lambda e: t(getattr(e.rnn, f"bias_ih_l0{torch_sfx}")))
        params[f"{jax_name}_b_hh"] = stack(
            lambda e: t(getattr(e.rnn, f"bias_hh_l0{torch_sfx}")))
    params["head_w"] = stack(lambda e: t(e.head.weight).T)
    params["head_b"] = stack(lambda e: t(e.head.bias))

    rng = np.random.default_rng(7)
    x = rng.normal(size=(B, T, F)).astype(np.float32)
    y = rng.normal(size=(B, T, E)).astype(np.float32)

    ours = np.asarray(model.apply({"params": params}, jnp.asarray(x),
                                  deterministic=True))
    with torch.no_grad():
        theirs = tmodel(torch.from_numpy(x)).numpy()
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)

    # Loss-formula equivalence, pinned on the SAME prediction tensor so the
    # tolerance is independent of the forward-parity budget above.
    our_loss = float(pinball_loss(jnp.asarray(theirs), jnp.asarray(y),
                                  cfg.quantiles))
    their_loss = float(tmodel.loss(torch.from_numpy(theirs),
                                   torch.from_numpy(y)))
    np.testing.assert_allclose(our_loss, their_loss, rtol=1e-5)
