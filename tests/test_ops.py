"""Numerical parity of the GRU scan against torch.nn.GRU (public API) and
golden tests for the pinball loss."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from deeprest_tpu.ops import GRUParams, bidirectional_gru, gru, init_gru_params, pinball_loss

torch = pytest.importorskip("torch")


def torch_gru_params(tgru, reverse=False):
    sfx = "_reverse" if reverse else ""
    return GRUParams(
        w_ih=jnp.asarray(getattr(tgru, f"weight_ih_l0{sfx}").detach().numpy().T)[None],
        w_hh=jnp.asarray(getattr(tgru, f"weight_hh_l0{sfx}").detach().numpy().T)[None],
        b_ih=jnp.asarray(getattr(tgru, f"bias_ih_l0{sfx}").detach().numpy())[None],
        b_hh=jnp.asarray(getattr(tgru, f"bias_hh_l0{sfx}").detach().numpy())[None],
    )


@pytest.mark.parametrize("reverse", [False, True])
def test_gru_matches_torch_single_direction(reverse):
    B, T, F, H = 3, 11, 5, 7
    torch.manual_seed(0)
    tgru = torch.nn.GRU(F, H, num_layers=1, bidirectional=False)
    x = np.random.default_rng(0).normal(size=(B, T, F)).astype(np.float32)

    xt = torch.from_numpy(x[:, ::-1].copy() if reverse else x).permute(1, 0, 2)
    with torch.no_grad():
        tout, _ = tgru(xt, torch.zeros(1, B, H))
    tout = tout.permute(1, 0, 2).numpy()
    if reverse:
        tout = tout[:, ::-1]  # re-align reversed-run outputs with input time

    params = torch_gru_params(tgru)
    out = np.asarray(gru(params, jnp.asarray(x)[None], reverse=reverse))[0]
    np.testing.assert_allclose(out, tout, rtol=1e-5, atol=1e-5)


def test_bidirectional_matches_torch():
    B, T, F, H = 2, 9, 4, 6
    torch.manual_seed(1)
    tgru = torch.nn.GRU(F, H, num_layers=1, bidirectional=True)
    x = np.random.default_rng(1).normal(size=(B, T, F)).astype(np.float32)

    with torch.no_grad():
        tout, _ = tgru(torch.from_numpy(x).permute(1, 0, 2), torch.zeros(2, B, H))
    tout = tout.permute(1, 0, 2).numpy()  # [B, T, 2H], (fwd, bwd) halves

    out = np.asarray(
        bidirectional_gru(torch_gru_params(tgru), torch_gru_params(tgru, reverse=True),
                          jnp.asarray(x)[None])
    )[0]
    np.testing.assert_allclose(out, tout, rtol=1e-5, atol=1e-5)


def test_expert_axis_is_independent():
    """Each expert's output must equal running it alone (no cross-talk)."""
    key = jax.random.PRNGKey(0)
    E, B, T, F, H = 4, 2, 8, 5, 6
    params = init_gru_params(key, E, F, H)
    x = jax.random.normal(jax.random.PRNGKey(1), (E, B, T, F))
    full = bidirectional_gru(params, params, x)
    for e in range(E):
        solo_params = GRUParams(*[p[e][None] for p in params])
        solo = bidirectional_gru(solo_params, solo_params, x[e][None])
        np.testing.assert_allclose(np.asarray(full[e]), np.asarray(solo[0]),
                                   rtol=1e-5, atol=1e-6)


def test_gru_jit_and_grad():
    params = init_gru_params(jax.random.PRNGKey(0), 2, 4, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 10, 4))

    @jax.jit
    def loss_fn(p, x):
        return jnp.sum(gru(p, x) ** 2)

    g = jax.grad(loss_fn)(params, x)
    assert all(np.isfinite(np.asarray(t)).all() for t in jax.tree.leaves(g))
    assert g.w_ih.shape == params.w_ih.shape


def test_pinball_loss_golden():
    # Single element: target 1.0, preds [0.0, 1.0, 2.0], q = (.05, .5, .95)
    preds = jnp.asarray([0.0, 1.0, 2.0]).reshape(1, 1, 1, 3)
    targets = jnp.ones((1, 1, 1))
    # errors: 1, 0, -1 → losses: .05*1, 0, (1-.95)*1 = .05 + 0 + .05
    loss = pinball_loss(preds, targets, (0.05, 0.50, 0.95))
    np.testing.assert_allclose(float(loss), 0.10, rtol=1e-6)


def test_pinball_loss_matches_loop_reference():
    """Vectorized loss == the documented per-metric/per-quantile loop
    (reference formula, resource-estimation/qrnn.py:58-67)."""
    rng = np.random.default_rng(0)
    B, T, E, Q = 4, 6, 3, 3
    quantiles = (0.05, 0.50, 0.95)
    preds = rng.normal(size=(B, T, E, Q)).astype(np.float32)
    targets = rng.normal(size=(B, T, E)).astype(np.float32)

    per_metric = []
    for m in range(E):
        per_q = []
        for i, q in enumerate(quantiles):
            err = targets[:, :, m] - preds[:, :, m, i]
            per_q.append(np.maximum((q - 1) * err, q * err))
        per_metric.append(np.mean(np.sum(np.stack(per_q, axis=-1), axis=-1)))
    expected = float(np.mean(per_metric))

    got = float(pinball_loss(jnp.asarray(preds), jnp.asarray(targets), quantiles))
    np.testing.assert_allclose(got, expected, rtol=1e-5)


def test_pinball_loss_asymmetry():
    """A low quantile estimate should rarely exceed the target, so the
    5th-percentile loss punishes over-prediction far more than under-."""
    q = (0.05,)
    over = pinball_loss(jnp.full((1, 1, 1, 1), 2.0), jnp.ones((1, 1, 1)), q)
    under = pinball_loss(jnp.full((1, 1, 1, 1), 0.0), jnp.ones((1, 1, 1)), q)
    np.testing.assert_allclose(float(over), 0.95, rtol=1e-6)
    np.testing.assert_allclose(float(under), 0.05, rtol=1e-6)
