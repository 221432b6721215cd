"""The reverse direction on the interpreted kernels: the time blocks walked
back to front by their ``index_map`` beside flip, forward scan, flip, and
both directions over several time blocks against the scan.  Split from
tests/test_pallas_gru.py so that its module fixture traces on a worker of
its own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeprest_tpu.ops.gru import bidirectional_gru, init_gru_params
from pallas_gru_support import H


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
