"""Numerics of the fused pallas GRU recurrence vs the `lax.scan` reference.

Runs the kernels in interpret mode so the comparison works on the CPU test
mesh; on TPU the same code path runs compiled (ops/gru.py 'auto' dispatch).
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

E, B, T, F, H = 3, 5, 7, 11, 128  # E and B not multiples of 8 (the blocks)


def _setup(seed=0, e=E, b=B, t=T, f=F, h=H):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    params = init_gru_params(k1, e, f, h)
    x = jax.random.normal(k2, (b, t, f), jnp.float32)
    return params, x, k3


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


# -- the reverse direction: the kernels walk time back to front ------------

_REVERSE_SHAPES = {            # (E, T, B)
    "padded-T-odd-B": (5, 13, 3),      # the time pad at the array's FRONT
    "ten-time-blocks-three-expert-blocks": (24, 60, 8),
}


@pytest.fixture(scope="module",
                params=[(s, d) for s in _REVERSE_SHAPES
                        for d in ("float32", "bfloat16")],
                ids=lambda p: f"{p[0]}-{p[1]}")
def walked_and_flipped(request):
    """The reverse direction as the kernels run it (``reverse=True``: the
    time blocks walked back to front by their ``index_map``) beside the
    independent form of the same kernels: flip the projection in time,
    scan forward, flip the states back.  ``h_all`` and the gradients to
    ``proj``, ``w_hh``, ``b_hh``, ``h0`` of each, through
    ``ops.gru._recur_local`` (``pallas_gru.gru_recurrence`` of one
    direction, which pads what the blocks do not divide; the input bias it
    adds is zero here)."""
    from deeprest_tpu.ops.gru import _recur_local

    shape, dtype = request.param
    e, t, b = _REVERSE_SHAPES[shape]
    kp, kw, kb, kh, kc = jax.random.split(jax.random.PRNGKey(41), 5)
    k = 1.0 / np.sqrt(H)
    proj = jax.random.normal(kp, (e, t, b, 3 * H), jnp.dtype(dtype))
    w_hh = jax.random.uniform(kw, (e, H, 3 * H), minval=-k, maxval=k)
    b_hh = jax.random.uniform(kb, (e, 3 * H), minval=-k, maxval=k)
    h0 = jax.random.normal(kh, (e, b, H))
    weight = jax.random.normal(kc, (e, t, b, H))

    def one(proj, w_hh, b_hh, h0, reverse):
        direction = (proj, jnp.zeros_like(b_hh), w_hh, b_hh, h0)
        return _recur_local((direction,), True, (reverse,))

    def walked(proj, w_hh, b_hh, h0):
        return one(proj, w_hh, b_hh, h0, True)

    def flipped(proj, w_hh, b_hh, h0):
        return jnp.flip(one(jnp.flip(proj, 1), w_hh, b_hh, h0, False), 1)

    def run(layer):
        def loss(*args):
            h_all = layer(*args)
            return jnp.sum(h_all.astype(jnp.float32) * weight), h_all
        (_, h_all), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True))(proj, w_hh, b_hh, h0)
        assert h_all.shape == (e, t, b, H) and h_all.dtype == proj.dtype
        return dict(zip(("h_all", "proj", "w_hh", "b_hh", "h0"),
                        (np.asarray(a, np.float32)
                         for a in (h_all, *grads))))

    return {"dtype": dtype, "walked": run(walked), "flipped": run(flipped)}


def test_reverse_walk_values_are_flip_kernel_flip(walked_and_flipped):
    """Only addresses differ: every step does the same arithmetic on the
    same operands in the same scan order, so the states are bit for bit
    those of flipping round a forward scan."""
    r = walked_and_flipped
    assert np.any(r["walked"]["h_all"])
    np.testing.assert_array_equal(r["walked"]["h_all"],
                                  r["flipped"]["h_all"])


def test_reverse_walk_gradients_are_flip_kernel_flip(walked_and_flipped):
    """... and so are the gradients that accumulate step by step: to the
    projection (time-aligned with it), to ``b_hh`` and to ``h0``."""
    r = walked_and_flipped
    for name in ("proj", "b_hh", "h0"):
        assert np.any(r["walked"][name]), name
        np.testing.assert_array_equal(r["walked"][name], r["flipped"][name],
                                      err_msg=name)


def test_reverse_walk_w_hh_gradient_is_the_same_sum_reassociated(
        walked_and_flipped):
    """``dW_hh`` is ONE dot a time block over the block's ``t_blk x B``
    rows, which now lie in array order, the reverse of scan order: the
    same float32 sum in another association, not a lower precision.  1e-6
    of the leaf's largest magnitude in float32; where the kernel ships the
    leaf as bfloat16, two of its spacings there."""
    r = walked_and_flipped
    got, want = r["walked"]["w_hh"], r["flipped"]["w_hh"]
    top = np.max(np.abs(want))
    atol = (1e-6 * top if r["dtype"] == "float32"
            else 2 * 2.0 ** (np.floor(np.log2(top)) - 7))
    assert top > 0
    np.testing.assert_allclose(got, want, rtol=0, atol=atol)


def test_bidirectional_matches_scan_over_three_time_blocks():
    """Both directions over several time blocks and two expert blocks
    against the scan backend, values and every gradient, within the
    file's float32 tolerances."""
    e, b, t, f = 16, 8, 18, 7
    kf, kb, kx, kw = jax.random.split(jax.random.PRNGKey(5), 4)
    fwd = init_gru_params(kf, e, f, H)
    bwd = init_gru_params(kb, e, f, H)
    x = jax.random.normal(kx, (b, t, f))
    weight = jax.random.normal(kw, (e, b, t, 2 * H))

    def run(backend):
        def loss(ps, x):
            out = bidirectional_gru(ps[0], ps[1], x, backend=backend)
            return jnp.sum(out * weight), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)((fwd, bwd), x)
        return [np.asarray(a) for a in (out, *jax.tree.leaves(grads))]

    (out, *grads), (ref, *g_ref) = run("pallas_interpret"), run("scan")
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    for got, want in zip(grads, g_ref):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)

