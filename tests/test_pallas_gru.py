"""Numerics of the fused pallas GRU recurrence vs the `lax.scan` reference:
one direction at a time, over the time-block boundaries, values and
gradients, and the block chooser under a VMEM budget.

Runs the kernels in interpret mode so the comparison works on the CPU test
mesh; on TPU the same code path runs compiled (ops/gru.py 'auto' dispatch).
The join of the two directions is tests/test_pallas_gru_bidirectional.py,
the reverse walk tests/test_pallas_gru_reverse.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprest_tpu.ops.gru import (
    GRUParams,
    bidirectional_gru,
    gru,
)
from pallas_gru_support import B, E, F, H, T, _setup


@pytest.mark.parametrize("reverse", [False, True])
def test_forward_matches_scan(reverse):
    params, x, _ = _setup()
    ref = gru(params, x, reverse=reverse, backend="scan")
    out = gru(params, x, reverse=reverse, backend="pallas_interpret")
    assert out.shape == ref.shape == (E, B, T, H)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_forward_aligned_shapes():
    # E and B multiples of 8: the no-padding fast path.
    params, x, _ = _setup(e=8, b=16)
    ref = gru(params, x, backend="scan")
    out = gru(params, x, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("t", [1, 2, 6, 12])
def test_time_blocking_boundaries(t):
    # T below / equal to / a multiple of the time block (6): padding and the
    # in-program time loop must agree with scan in both directions, values
    # and grads.
    params, x, _ = _setup(t=t)

    def loss(backend, x):
        fwd = gru(params, x, backend=backend)
        rev = gru(params, x, reverse=True, backend=backend)
        return jnp.sum(fwd ** 2) + jnp.sum(jnp.sin(rev))

    np.testing.assert_allclose(
        float(loss("pallas_interpret", x)), float(loss("scan", x)),
        rtol=1e-5)
    g_ref = jax.grad(lambda x: loss("scan", x))(x)
    g_pl = jax.grad(lambda x: loss("pallas_interpret", x))(x)
    np.testing.assert_allclose(np.asarray(g_pl), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-4)


def test_gradients_match_scan():
    params, x, _ = _setup()

    def loss(backend, params, x):
        out = bidirectional_gru(params, params, x, backend=backend)
        return jnp.sum(out * jnp.cos(jnp.arange(out.size).reshape(out.shape)))

    g_ref = jax.grad(lambda p: loss("scan", p, x))(params)
    g_pl = jax.grad(lambda p: loss("pallas_interpret", p, x))(params)
    for name in GRUParams._fields:
        np.testing.assert_allclose(
            np.asarray(getattr(g_pl, name)), np.asarray(getattr(g_ref, name)),
            rtol=2e-4, atol=2e-4, err_msg=f"grad mismatch: {name}",
        )


def test_gradient_wrt_input_matches_scan():
    params, x, _ = _setup()

    def loss(backend, x):
        return jnp.sum(gru(params, x, backend=backend) ** 2)

    g_ref = jax.grad(lambda x: loss("scan", x))(x)
    g_pl = jax.grad(lambda x: loss("pallas_interpret", x))(x)
    np.testing.assert_allclose(np.asarray(g_pl), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-4)


def test_model_parity_across_backends():
    """The full QuantileGRU forward agrees between backends."""
    import dataclasses

    from deeprest_tpu.config import ModelConfig
    from deeprest_tpu.models.qrnn import QuantileGRU

    cfg = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                      rnn_backend="scan")
    model = QuantileGRU(config=cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, T, F), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x)
    ref = model.apply(variables, x, deterministic=True)

    cfg_pl = dataclasses.replace(cfg, rnn_backend="pallas_interpret")
    out = QuantileGRU(config=cfg_pl).apply(variables, x, deterministic=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_unsupported_hidden_falls_back_to_scan():
    # H not lane-aligned → dispatch silently uses the scan path.
    params, x, _ = _setup(h=32)
    ref = gru(params, x, backend="scan")
    out = gru(params, x, backend="pallas_interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


def test_no_fitting_plan_raises_naming_the_shape(monkeypatch):
    """A shape with no plan inside the scoped-VMEM budget is an error that
    names it, not a warning followed by a compile the chip refuses."""
    from deeprest_tpu.ops import pallas_gru

    monkeypatch.setattr(pallas_gru, "_VMEM_BUDGET", 1)
    with pytest.raises(ValueError, match=r"E=8 T=12 B=8 H=128.*float32"):
        params, x, _ = _setup(t=12)
        gru(params, x, backend="pallas_interpret")


def test_vmem_budget_shrinks_time_block(monkeypatch):
    """When the block footprint would exceed the scoped-VMEM budget, the
    chooser shrinks the TIME block (the expert block is sublane-pinned to
    multiples of 8) — numerics must be unchanged.  A tiny budget forces
    the smallest blocking; this is the regression test for the f32
    backward kernel OOM observed on v5e (see PERF.md, round 4)."""
    from deeprest_tpu.ops import pallas_gru

    params, x, _ = _setup(t=12)

    def loss(backend, x):
        fwd = gru(params, x, backend=backend)
        rev = gru(params, x, reverse=True, backend=backend)
        return jnp.sum(fwd ** 2) + jnp.sum(jnp.sin(rev))

    ref_l = float(loss("scan", x))
    g_ref = jax.grad(lambda x: loss("scan", x))(x)

    # E=3 pads to 8 experts, B=5 to 8 rows: admit the backward kernel (the
    # larger of the two) at t_blk=1 and nothing wider.
    per_expert = pallas_gru._bwd_per_expert_bytes(
        8, 3 * H, H, jnp.float32, hp_io=4, do_io=4, w_itemsize=4)
    monkeypatch.setattr(pallas_gru, "_VMEM_BUDGET", 8 * per_expert(1))
    e_blk, t_blk = pallas_gru._choose_blocks(8, 12, per_expert)
    assert t_blk == 1 and e_blk == 8      # shrank time, kept sublane-legal E

    np.testing.assert_allclose(float(loss("pallas_interpret", x)), ref_l,
                               rtol=1e-5)
    g_pl = jax.grad(lambda x: loss("pallas_interpret", x))(x)
    np.testing.assert_allclose(np.asarray(g_pl), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-4)
