"""The join of the two directions on the interpreted kernels
(``bidirectional_gru``): beside two ``gru`` calls joined afterwards to the
bit, and against the scan backend in float32 and bfloat16.  Split from
tests/test_pallas_gru.py, whose helpers it shares, so that its module
fixture traces on a worker of its own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprest_tpu.ops.gru import (
    GRUParams,
    bidirectional_gru,
    gru,
    init_gru_params,
)
from pallas_gru_support import H, _setup


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def joined_and_two_calls(request):
    """``bidirectional_gru`` beside two ``gru`` calls joined afterwards:
    values and the gradients of both parameter sets and of the input, on
    the interpreted kernels and on the scan, with DISTINCT fwd/bwd weights
    at a shape that hits every padding branch (odd E, B under the sublane,
    T off the time block).  Computed once a dtype (an interpreted backward
    pass is half a minute of CPU) and read by the three tests below."""
    dtype = jnp.dtype(request.param)
    e, b, t, f, h = 5, 3, 13, 7, 128
    kf, kb, kx, kw = jax.random.split(jax.random.PRNGKey(7), 4)
    fwd = init_gru_params(kf, e, f, h, dtype)
    bwd = init_gru_params(kb, e, f, h, dtype)
    x = jax.random.normal(kx, (b, t, f), dtype)
    weight = jax.random.normal(kw, (e, b, t, 2 * h), jnp.float32)

    def joined(ps, x, backend):
        return bidirectional_gru(ps[0], ps[1], x, backend=backend)

    def two_calls(ps, x, backend):
        return jnp.concatenate(
            [gru(ps[0], x, backend=backend),
             gru(ps[1], x, reverse=True, backend=backend)], axis=-1)

    def run(layer, backend):
        def loss(ps, x):
            out = layer(ps, x, backend)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)((fwd, bwd), x)
        assert out.shape == (e, b, t, 2 * h) and out.dtype == dtype
        return [np.asarray(a, np.float32)
                for a in (out, *jax.tree.leaves(grads))]

    return {"dtype": request.param,
            "joined": run(joined, "pallas_interpret"),
            "two_calls": run(two_calls, "pallas_interpret"),
            "scan": run(joined, "scan")}


def test_bidirectional_values_are_two_gru_calls_joined(joined_and_two_calls):
    """The pallas path joins the two directions in the kernels' own order
    before its one transpose (ops/gru._layer_pallas): layout work only, so
    the layer's output is bit for bit that of joining afterwards."""
    r = joined_and_two_calls
    np.testing.assert_array_equal(r["joined"][0], r["two_calls"][0])


def test_bidirectional_gradients_are_two_gru_calls_joined(
        joined_and_two_calls):
    """... and so is every gradient: eight parameter leaves and the input."""
    r = joined_and_two_calls
    assert len(r["joined"]) == 1 + 2 * len(GRUParams._fields) + 1
    for got, want in zip(r["joined"][1:], r["two_calls"][1:]):
        np.testing.assert_array_equal(got, want)


def test_bidirectional_matches_scan(joined_and_two_calls):
    """Against the scan backend: 1e-5 on values and 2e-4 on gradients in
    float32, bf16 quantization noise in bfloat16 (the bounds of
    test_bf16_proj_io_matches_bf16_scan)."""
    r = joined_and_two_calls
    (out, *grads), (ref, *g_ref) = r["joined"], r["scan"]
    if r["dtype"] == "float32":
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        for got, want in zip(grads, g_ref):
            np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    else:
        assert np.max(np.abs(out - ref)) < 0.05
        for got, want in zip(grads, g_ref):
            assert np.max(np.abs(got - want)) < 0.15 * (
                1e-3 + np.max(np.abs(want)))


def test_bf16_proj_io_matches_bf16_scan():
    """With bf16 params/inputs the kernel keeps bf16 proj I/O (the einsum
    already quantized the values — storing f32 would just double the
    dominant HBM stream).  Outputs and grads must match the bf16 scan
    within bf16 quantization noise; the f32 path stays exact."""
    e, b, t, f, h = 3, 5, 9, 7, 128
    kf, kb, kx = jax.random.split(jax.random.PRNGKey(3), 3)
    fwd = init_gru_params(kf, e, f, h)
    bwd = init_gru_params(kb, e, f, h)
    x = jax.random.normal(kx, (b, t, f))
    fwd16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), fwd)
    bwd16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), bwd)
    x16 = x.astype(jnp.bfloat16)

    ref = np.asarray(
        bidirectional_gru(fwd16, bwd16, x16, backend="scan"), np.float32)
    pl = np.asarray(
        bidirectional_gru(fwd16, bwd16, x16, backend="pallas_interpret"),
        np.float32)
    assert np.max(np.abs(ref - pl)) < 0.05

    def loss(ps, backend):
        out = bidirectional_gru(ps[0], ps[1], x16, backend=backend)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    g_ref = jax.grad(lambda ps: loss(ps, "scan"))((fwd16, bwd16))
    g_pl = jax.grad(lambda ps: loss(ps, "pallas_interpret"))((fwd16, bwd16))
    for a, b_ in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_pl)):
        a = np.asarray(a, np.float32)
        b_ = np.asarray(b_, np.float32)
        assert np.max(np.abs(a - b_)) < 0.15 * (1e-3 + np.max(np.abs(a)))


@pytest.mark.parametrize("dtype", ["f32", "bf16"])
def test_bidirectional_values_and_input_grads_match_scan(dtype):
    """The kernels agree with the scan backend in values and grads in
    BOTH dtypes, over a window off the time-block grid (f32 gate stash is
    a lossless round-trip; bf16 rounds it to the kernel's I/O dtype)."""
    params, x, _ = _setup(t=9)
    if dtype == "bf16":
        params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
        x = x.astype(jnp.bfloat16)

    def loss(backend, x):
        out = bidirectional_gru(params, params, x, backend=backend)
        return jnp.sum(out.astype(jnp.float32) ** 2)

    tol = dict(rtol=1e-5) if dtype == "f32" else dict(rtol=2e-2)
    np.testing.assert_allclose(
        float(loss("pallas_interpret", x)), float(loss("scan", x)), **tol)
    g_ref = np.asarray(jax.grad(lambda x: loss("scan", x))(x), np.float32)
    g_pl = np.asarray(jax.grad(lambda x: loss("pallas_interpret", x))(x),
                      np.float32)
    if dtype == "f32":
        np.testing.assert_allclose(g_pl, g_ref, rtol=2e-4, atol=2e-4)
    else:
        assert np.max(np.abs(g_pl - g_ref)) < 0.15 * (
            1e-3 + np.max(np.abs(g_ref)))
