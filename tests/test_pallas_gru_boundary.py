"""The boundary of the recurrence's VJP (ISSUE 42): the add of the input
bias in front of the kernels and the join of the directions behind them are
inside ``pallas_gru.gru_recurrence``, so the backward kernels read the
joined cotangent in place and return the input bias's gradient.

The layer as the public entry points run it is held here to the parent's
arrangement of the same kernels, rebuilt below (:func:`_parent_form`):
every value and every gradient but ``db_ih`` to the bit.  Interpret mode,
on the CPU; a file of its own beside tests/test_pallas_gru.py so that the
two files' interpreted kernels trace on two workers.
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

H = 128


_BOUNDARY_SHAPES = {           # (E, B, T)
    "aligned": (8, 8, 12),
    "padded-E-B-T": (5, 3, 13),
    "ten-time-blocks-three-expert-blocks": (24, 8, 60),
}
_LEAVES = ("out", "x", "h0", *(f"{d}.{f}" for d in ("fwd", "bwd")
                               for f in GRUParams._fields))


def _parent_form(directions, x, probes):
    """The layer as the parent of ISSUE 42 arranged it, on the same
    kernels: the input bias added OUTSIDE the recurrence's VJP (so autodiff
    sums ``dproj`` for its gradient), one VJP a direction, and the
    directions joined outside (so autodiff splits the joined cotangent).
    ``probes``: zeros added to each projection, whose gradient is that
    direction's ``dproj`` as the kernel wrote it."""
    from deeprest_tpu.ops.gru import _project, _recur_local

    outs = []
    for (p, h0, reverse), probe in zip(directions, probes):
        proj = _project(p, x)
        proj = (proj + p.b_ih[:, None, None, :]).astype(proj.dtype) + probe
        alone = (proj, jnp.zeros_like(p.b_ih), p.w_hh, p.b_hh, h0)
        outs.append(_recur_local((alone,), True, (reverse,)))
    return jnp.moveaxis(jnp.concatenate(outs, axis=-1), 1, 2).astype(x.dtype)


@pytest.fixture(scope="module",
                params=[(s, d, k) for s in _BOUNDARY_SHAPES
                        for d in ("float32", "bfloat16")
                        for k in ("both", "one")
                        if k == "both" or s == "padded-E-B-T"],
                ids=lambda p: "-".join(p))
def layer_and_parent_form(request):
    """The layer through the public entry points (``bidirectional_gru``,
    or one reverse ``gru`` from a nonzero ``h0``) beside
    :func:`_parent_form`, interpreted kernels: the output and the gradient
    of every leaf, of the input and of ``h0``, and the parent form's
    ``dproj`` a direction.  Both forms under time blocks of two steps: an
    interpreted kernel costs its unrolled block to trace, a third of it
    this way, and the width of a block is the business of
    tests/test_pallas_gru.py, which runs the widest."""
    from deeprest_tpu.ops import pallas_gru

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(pallas_gru, "_T_BLK", 2)
        return _layer_and_parent_form(*request.param)


def _layer_and_parent_form(shape, dtype, kind):
    e, b, t = _BOUNDARY_SHAPES[shape]
    dtype = jnp.dtype(dtype)
    f, n = 7, 2 if kind == "both" else 1
    kf, kb, kx, kw, kh = jax.random.split(jax.random.PRNGKey(42), 5)
    ps = (init_gru_params(kf, e, f, H, dtype),
          init_gru_params(kb, e, f, H, dtype))
    x = jax.random.normal(kx, (b, t, f), dtype)
    weight = jax.random.normal(kw, (e, b, t, n * H), jnp.float32)
    # `bidirectional_gru` starts both directions from zeros
    h0 = (jnp.zeros((e, b, H), jnp.float32) if kind == "both"
          else jax.random.normal(kh, (e, b, H), jnp.float32))
    probes = tuple(jnp.zeros((e, t, b, 3 * H), dtype) for _ in range(n))

    def directions(ps, h0):
        if kind == "both":
            return ((ps[0], h0, False), (ps[1], h0, True))
        return ((ps[0], h0, True),)

    def layer(ps, x, h0, probes):
        if kind == "both":
            return bidirectional_gru(*ps, x, backend="pallas_interpret")
        return gru(ps[0], x, h0, reverse=True, backend="pallas_interpret")

    def parent(ps, x, h0, probes):
        return _parent_form(directions(ps, h0), x, probes)

    def run(form):
        def loss(ps, x, h0, probes):
            out = form(ps, x, h0, probes)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), (g_ps, g_x, g_h0, g_probes) = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3), has_aux=True))(ps, x, h0, probes)
        assert out.shape == (e, b, t, n * H) and out.dtype == dtype
        found = dict(zip(_LEAVES, (out, g_x, g_h0, *g_ps[0], *g_ps[1])))
        found = {k: np.asarray(v, np.float32) for k, v in found.items()}
        return found, [np.asarray(p, np.float32) for p in g_probes]

    mine, _ = run(layer)
    parents, dprojs = run(parent)
    live = [k for k in _LEAVES
            if kind == "both" and k != "h0"
            or kind == "one" and not k.startswith("bwd.")]
    return {"dtype": str(dtype), "live": live, "mine": mine,
            "parent": parents, "dproj": dict(zip(("fwd", "bwd"), dprojs))}


def test_vjp_boundary_moves_no_value(layer_and_parent_form):
    """The bias add and the join compute inside the VJP what they computed
    outside: the layer's output is the parent form's, bit for bit."""
    r = layer_and_parent_form
    assert np.any(r["mine"]["out"])
    np.testing.assert_array_equal(r["mine"]["out"], r["parent"]["out"])


def test_vjp_boundary_moves_no_gradient_but_the_input_bias(
        layer_and_parent_form):
    """Reading its H lanes of the joined cotangent in place hands a
    backward kernel the numbers the split handed it: every gradient but
    ``db_ih`` is the parent form's, bit for bit (padded experts, rows and
    steps, a nonzero ``h0`` and ten time blocks among the cases)."""
    r = layer_and_parent_form
    for name in r["live"]:
        assert np.any(r["mine"][name]), name
        if not name.endswith(".b_ih"):
            np.testing.assert_array_equal(r["mine"][name], r["parent"][name],
                                          err_msg=name)


def test_input_bias_gradient_shares_two_gates_with_the_hidden_bias(
        layer_and_parent_form):
    """``db_ih`` is (sum da_r, sum da_z, sum dtanh) and ``db_hh`` (sum da_r,
    sum da_z, sum dhn): the kernel keeps the first two sums once."""
    r = layer_and_parent_form
    for d in {k.split(".")[0] for k in r["live"] if "." in k}:
        np.testing.assert_array_equal(r["mine"][f"{d}.b_ih"][:, :2 * H],
                                      r["mine"][f"{d}.b_hh"][:, :2 * H])
        assert np.any(r["mine"][f"{d}.b_ih"][:, 2 * H:]
                      != r["mine"][f"{d}.b_hh"][:, 2 * H:])


def test_input_bias_gradient_is_the_float32_row_sum(layer_and_parent_form):
    """The kernel sums the float32 gate gradients in float32, where the
    parent form sums ``dproj`` after its rounding to the kernels' I/O
    dtype, in that dtype.  Against the float32 sum of the ``dproj`` the
    kernel wrote: within 1e-6 of the largest entry in float32 (the same
    numbers in another order); in bfloat16 not further from it than the
    parent form's own sum."""
    r = layer_and_parent_form
    for d in {k.split(".")[0] for k in r["live"] if "." in k}:
        want = r["dproj"][d].sum(axis=(1, 2), dtype=np.float32)
        top = np.max(np.abs(want))
        mine = np.max(np.abs(r["mine"][f"{d}.b_ih"] - want)) / top
        parent = np.max(np.abs(r["parent"][f"{d}.b_ih"] - want)) / top
        print(f"db_ih ({d}, {r['dtype']}) from the float32 sum of dproj: "
              f"{mine:.3e}, the parent form's {parent:.3e}")
        assert mine <= (1e-6 if r["dtype"] == "float32" else parent)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_projection_that_brings_the_bias_moves_no_value_and_no_gradient(
        dtype, monkeypatch):
    """ISSUE 49: a caller's ``project`` makes ``x @ W_ih + b_ih`` itself (in
    pieces; here whole), the layer hands it the bias under
    ``stop_gradient`` and tells the recurrence the add is done
    (``gru_recurrence(..., biased=True)``), whose backward kernels still
    return the bias's gradient: the output and every gradient, ``db_ih``
    among them, are those of the layer without ``project``, bit for bit."""
    from deeprest_tpu.ops import pallas_gru

    monkeypatch.setattr(pallas_gru, "_T_BLK", 2)
    e, b, t = 8, 8, 4                          # two time blocks
    dtype, f = jnp.dtype(dtype), 7
    kf, kb, kx, kw = jax.random.split(jax.random.PRNGKey(49), 4)
    ps = (init_gru_params(kf, e, f, H, dtype),
          init_gru_params(kb, e, f, H, dtype))
    x = jax.random.normal(kx, (b, t, f), dtype)
    weight = jax.random.normal(kw, (e, b, t, 2 * H), jnp.float32)
    seen = []

    def project(x, w_ih, b_ih):
        seen.append(b_ih)
        return jnp.einsum("btf,efg->etbg", x, w_ih) + b_ih[:, None, None, :]

    def run(project):
        def loss(ps, x):
            out = bidirectional_gru(*ps, x, backend="pallas_interpret",
                                    project=project)
            return jnp.sum(out.astype(jnp.float32) * weight), out
        (_, out), (g_ps, g_x) = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(ps, x)
        return [np.asarray(v, np.float32)
                for v in (out, g_x, *g_ps[0], *g_ps[1])]

    plain, brought = run(None), run(project)
    assert len(seen) == 2                      # a call a direction
    for name, was, now in zip(_LEAVES[:2] + _LEAVES[3:], plain, brought):
        assert np.any(was), name
        np.testing.assert_array_equal(now, was, err_msg=name)
