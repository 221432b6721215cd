"""The accumulated update written plainly: `endpoints-10k-accum8`'s copy of
the yardstick (chipbench/reference/qrnn_ref.py, which see; nothing of it is
changed and nothing of ``deeprest_tpu`` is imported here either).

The configuration's update is the pod's: plain Adam on the gradient of the
weighted mean pinball loss over ALL real windows of a group of G
microbatches of B windows, what ONE batch of G x B windows gives and what
the source's eight chips all-reduce.  It is written here from that one
batch, not from the program: the loss of the group is

    sum over its real windows of the window's loss  /  N,     N = real windows

and it is computed a microbatch at a time only so that it fits the chip
(the float32 forward and backward of 256 windows at F = 10,240 does not):
microbatch g with ``n_g`` real windows contributes ``pinball`` over its
real windows times ``n_g / N``, which is the same sum.  Padding trails the
real windows of a microbatch and the real microbatches of a group, as the
trainer's plans lay them out; a padded window is in no sum.

The dropout stream is the published rule: the keep mask of microbatch g of
the update that starts at step s (s counts the real microbatches before it)
is drawn from ``fold_in(fold_in(root, s), g)`` at the shape of a whole
microbatch, ``root`` as ``qrnn_ref.train_rng`` gives it
(tests/test_dropout_mask.py pins it on the program's side).

``control`` makes the three references that must FAIL the cell's limits, for
chipbench/tests/control_on_chip_accum.py (``precision="fp8"`` is the fourth,
as in every cell):

- ``"lost_microbatch"``: the gradient of one whole microbatch of the first
  update never reaches the sum, which is still divided by all N windows
  (an add lost on the way: the accumulated gradient is some 7/8 as long).
  The other way to lose one, its windows out of the sum AND out of N, the
  comparison cannot see on the chip (the gradient of 224 windows is as long
  as that of 256 to 3 parts in 10,000: my chip runs, PR 48); a program that
  loses one so counts 21 steps for 22, which `correct` also reads;
- ``"ignored_weights"``: every real microbatch counts ``1 / (real
  microbatches)`` whatever its real windows: the mean of the microbatches'
  means in the place of the mean over the windows, which differ where a
  group is ragged;
- ``"summed"``: every microbatch counts 1: the sum of the microbatches'
  mean-loss gradients, G times the mean, which is what the program did
  until ISSUE 48.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.reference.qrnn_ref import (
    ADAM, dropout_keep, forward, leaf_norms, pinball, train_rng,
)

CONTROLS = (None, "lost_microbatch", "ignored_weights", "summed")


def shares(real: np.ndarray, control: str | None = None) -> np.ndarray:
    """What each microbatch of one group counts in the group's loss, from
    its real windows ``real`` [G]: ``n_g / N``, or what ``control`` says."""
    real = np.asarray(real, np.float64)
    if control == "summed":
        return (real > 0).astype(np.float64)
    if control == "ignored_weights":
        return (real > 0) / np.count_nonzero(real)
    return real / real.sum()


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("n", "quantiles", "rate", "precision"))
def _add_microbatch(params, acc, x, y, key, share, *, n, quantiles, rate,
                    precision):
    """``acc`` plus ``share`` times the gradient of the mean pinball loss
    over the microbatch's first ``n`` (real) windows; that loss."""
    e, h = params["mask_w1"].shape
    keep = (dropout_keep(key, (e, *x.shape[:2], 2 * h), rate)
            if rate > 0 else None)

    def loss_fn(p):
        preds = forward(p, x, precision, keep, rate)
        loss = pinball(preds[:n], y[:n], quantiles)
        return loss * share, loss

    (_, loss), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
    return {k: acc[k] + grads[k] for k in acc}, loss


@functools.partial(jax.jit, donate_argnums=(0, 1, 2))
def _adam(params, mu, nu, count, grads):
    count = count + 1
    mu = {k: ADAM["b1"] * mu[k] + (1 - ADAM["b1"]) * grads[k] for k in grads}
    nu = {k: ADAM["b2"] * nu[k] + (1 - ADAM["b2"]) * grads[k] ** 2
          for k in grads}
    c1 = 1 - ADAM["b1"] ** count
    c2 = 1 - ADAM["b2"] ** count
    params = {k: params[k] - ADAM["lr"] * (mu[k] / c1)
              / (jnp.sqrt(nu[k] / c2) + ADAM["eps"]) for k in params}
    return params, mu, nu, count


def train_three_updates(params, groups, weights, seed: int, quantiles,
                        rate: float, precision: str = "f32",
                        control: str | None = None) -> dict:
    """Adam updates from ``params`` (consumed), one a group.  ``groups``: a
    list (three in the cell) of lists of microbatches ``(x [B,W,F], y
    [B,W,E])``, normalised; ``weights``: for each group its ``[G, B]`` 0/1
    weights, real windows first in a microbatch and real microbatches first
    in a group.  Returns the numbers the comparison reads: every real
    microbatch's loss in order, the first update's accumulated gradient's
    norm per leaf, the norm of each leaf's change after all updates, and
    how many microbatches (``steps``) and updates were made."""
    if control not in CONTROLS:
        raise ValueError(f"control {control!r}: one of {CONTROLS}")
    start = jax.tree.map(jnp.copy, params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    root = train_rng(seed)
    losses, first, step = [], None, 0
    for u, (group, w) in enumerate(zip(groups, weights)):
        real = np.asarray(w).sum(axis=1).astype(int)              # [G]
        if not (np.diff((real > 0).astype(int)) <= 0).all():
            raise ValueError("padded microbatches must trail the real ones")
        share = shares(real, control)
        if control == "lost_microbatch" and u == 0:
            share[len(group) // 2] = 0.0
        update_key = jax.random.fold_in(root, step)
        acc = jax.tree.map(jnp.zeros_like, params)
        for g, (x, y) in enumerate(group):
            if not real[g]:
                continue
            acc, loss = _add_microbatch(
                params, acc, jnp.asarray(x, jnp.float32),
                jnp.asarray(y, jnp.float32), jax.random.fold_in(update_key, g),
                jnp.float32(share[g]), n=int(real[g]),
                quantiles=tuple(quantiles), rate=rate, precision=precision)
            losses.append(float(loss))
        step += int(np.count_nonzero(real))
        if first is None:
            first = {k: float(v) for k, v in leaf_norms(acc).items()}
        params, mu, nu, count = _adam(params, mu, nu, count, acc)
    delta = leaf_norms({k: params[k] - start[k] for k in params})
    return {"losses": losses, "grad_norm": first,
            "delta_norm": {k: float(v) for k, v in delta.items()},
            "steps": step, "updates": len(groups)}
