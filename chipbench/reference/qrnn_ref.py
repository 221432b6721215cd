"""The estimator written plainly: the yardstick `correct` is decided against.

Float32 throughout, every matmul under ``precision="highest"``, no kernels,
no batching tricks, no cache.  It follows IBM/DeepRest
``resource-estimation/qrnn.py``: per metric (expert) a learned soft feature
mask, a bidirectional GRU over the window, a quantile head fed with
``concat(mean of the other experts' GRU outputs, own GRU output)``, the
pinball loss and Adam, on min-max normalised windows.

It imports nothing of ``deeprest_tpu`` and takes nothing the program made:
weights come from :func:`init_params` (the benchmark installs the same
values into the program), inputs come from the traffic generator.  The only
things shared with the program are the NAMES of the parameter leaves, the
published rule for the dropout stream (``fold_in(split(PRNGKey(seed))[1],
step)`` through ``flax.linen.Dropout``: flax is a library, not the program)
and the optimizer's published constants (Adam, lr 1e-3).

``precision`` selects the operand precision of every matmul, for the
control of `correct` ("the reference, put in the program's place and
computed in the nearest precision below"): ``"f32"`` is the reference,
``"bf16"`` what the configurations state, ``"fp8"`` the step below
(per-tensor scaled float8_e4m3fn operands, float32 accumulation).
"""

from __future__ import annotations

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

GRU_LEAVES = ("w_ih", "w_hh", "b_ih", "b_hh")
ADAM = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


# -- weights -----------------------------------------------------------------


def param_shapes(e: int, f: int, h: int, q: int) -> dict:
    """Leaf name -> (shape, uniform half-width), the published init."""
    k_h = 1.0 / h ** 0.5
    k_d = 1.0 / (4 * h) ** 0.5
    shapes = {
        "mask_w1": ((e, h), 1.0), "mask_b1": ((e, h), 1.0),
        "mask_w2": ((e, h, f), k_h), "mask_b2": ((e, f), k_h),
        "head_w": ((e, 4 * h, q), k_d), "head_b": ((e, q), k_d),
    }
    for d in ("fwd", "bwd"):
        shapes[f"gru_{d}_w_ih"] = ((e, f, 3 * h), k_h)
        shapes[f"gru_{d}_w_hh"] = ((e, h, 3 * h), k_h)
        shapes[f"gru_{d}_b_ih"] = ((e, 3 * h), k_h)
        shapes[f"gru_{d}_b_hh"] = ((e, 3 * h), k_h)
    return shapes


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def init_params(key, e: int, f: int, h: int, q: int) -> dict:
    """All weights on the device in one jitted call from the seed's key."""
    shapes = param_shapes(e, f, h, q)
    keys = jax.random.split(key, len(shapes))
    return {name: jax.random.uniform(k, shape, jnp.float32, -half, half)
            for k, (name, (shape, half)) in zip(keys, sorted(shapes.items()))}


# -- matmul at a stated operand precision -------------------------------------


def _round(a, precision: str):
    if precision == "f32":
        return a
    if precision == "bf16":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
        return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    raise ValueError(f"precision {precision!r}: f32, bf16 or fp8")


def _mm(eq: str, a, b, precision: str):
    return jnp.einsum(eq, _round(a, precision), _round(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


# -- the model ----------------------------------------------------------------


def feature_mask(params) -> jax.Array:
    """[E, F] soft mask: Linear(1->H) on a constant 1, ReLU, Linear(H->F),
    softmax."""
    hidden = jax.nn.relu(params["mask_w1"] + params["mask_b1"])
    logits = jnp.einsum("eh,ehf->ef", hidden, params["mask_w2"],
                        precision=jax.lax.Precision.HIGHEST)
    return jax.nn.softmax(logits + params["mask_b2"], axis=-1)


def _gru_direction(w_ih, w_hh, b_ih, b_hh, x, reverse: bool, precision: str):
    """One expert, one direction.  x [B, T, F] (already masked through
    w_ih) -> [B, T, H], time-aligned with x."""
    proj = _mm("btf,fg->tbg", x, w_ih, precision) + b_ih      # [T, B, 3H]
    w_hh_r = _round(w_hh, precision)

    def step(h, xp):
        gh = jnp.einsum("bh,hg->bg", _round(h, precision), w_hh_r,
                        precision=jax.lax.Precision.HIGHEST) + b_hh
        xr, xz, xn = jnp.split(xp, 3, axis=-1)
        hr, hz, hn = jnp.split(gh, 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn)
        h = (1.0 - z) * n + z * h
        return h, h

    h0 = jnp.zeros((x.shape[0], w_hh.shape[0]), jnp.float32)
    _, out = jax.lax.scan(step, h0, proj, reverse=reverse)     # [T, B, H]
    return jnp.swapaxes(out, 0, 1)


def forward(params, x, precision: str = "f32", keep=None, rate: float = 0.0):
    """x [B, T, F] normalised traffic -> [B, T, E, Q] quantile predictions.

    ``keep`` is the dropout keep-mask [E, B, T, 2H] (training) or None."""
    mask = feature_mask(params)                                # [E, F]
    e = mask.shape[0]

    def expert(i):
        outs = []
        for d, reverse in (("fwd", False), ("bwd", True)):
            w_ih = mask[i][:, None] * params[f"gru_{d}_w_ih"][i]
            outs.append(_gru_direction(
                w_ih, params[f"gru_{d}_w_hh"][i], params[f"gru_{d}_b_ih"][i],
                params[f"gru_{d}_b_hh"][i], x, reverse, precision))
        return jnp.concatenate(outs, axis=-1)                  # [B, T, 2H]

    rnn = jax.lax.map(expert, jnp.arange(e))                   # [E, B, T, 2H]
    if keep is not None:
        rnn = jnp.where(keep, rnn / (1.0 - rate), 0.0)
    others = ((jnp.sum(rnn, axis=0, keepdims=True) - rnn) / (e - 1)
              if e > 1 else rnn)
    cat = jnp.concatenate([others, rnn], axis=-1)              # [E, B, T, 4H]
    preds = _mm("ebtd,edq->ebtq", cat, params["head_w"], precision)
    preds = preds + params["head_b"][:, None, None, :]
    return jnp.transpose(preds, (1, 2, 0, 3))


def pinball(preds, targets, quantiles) -> jax.Array:
    """mean over metrics of mean over rows and time of the summed quantile
    losses."""
    q = jnp.asarray(quantiles, jnp.float32)
    err = targets[..., None] - preds
    per = jnp.sum(jnp.maximum((q - 1.0) * err, q * err), axis=-1)
    return jnp.mean(jnp.mean(per, axis=(0, 1)))


# -- training: three Adam steps ------------------------------------------------


class _DropProbe(nn.Module):
    """The first Dropout of a compact module: the same rng path as the
    model's one dropout layer, so the same key gives the same mask."""
    rate: float

    @nn.compact
    def __call__(self, x):
        return nn.Dropout(rate=self.rate)(x, deterministic=False)


def dropout_keep(key, shape, rate: float) -> jax.Array:
    ones = jnp.ones(shape, jnp.float32)
    return _DropProbe(rate).apply({}, ones, rngs={"dropout": key}) > 0


def train_rng(seed: int) -> jax.Array:
    """The dropout stream's root as published: the second half of
    ``split(PRNGKey(seed))``; step i draws from ``fold_in(root, i)``."""
    return jax.random.split(jax.random.PRNGKey(seed))[1]


@functools.partial(jax.jit, static_argnames=("quantiles", "rate", "precision"),
                   donate_argnums=(0, 1, 2))
def _adam_step(params, mu, nu, count, x, y, key, *, quantiles, rate,
               precision):
    e = params["mask_w1"].shape[0]
    h = params["mask_w1"].shape[1]
    keep = (dropout_keep(key, (e, *x.shape[:2], 2 * h), rate)
            if rate > 0 else None)

    def loss_fn(p):
        return pinball(forward(p, x, precision, keep, rate), y, quantiles)

    loss, grads = jax.value_and_grad(loss_fn)(params)
    gnorm = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
    count = count + 1
    mu = {k: ADAM["b1"] * mu[k] + (1 - ADAM["b1"]) * grads[k] for k in grads}
    nu = {k: ADAM["b2"] * nu[k] + (1 - ADAM["b2"]) * grads[k] ** 2
          for k in grads}
    c1 = 1 - ADAM["b1"] ** count
    c2 = 1 - ADAM["b2"] ** count
    params = {k: params[k] - ADAM["lr"] * (mu[k] / c1)
              / (jnp.sqrt(nu[k] / c2) + ADAM["eps"]) for k in params}
    return params, mu, nu, count, loss, gnorm


def leaf_norms(tree) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def train_three_steps(params, batches, seed: int, quantiles, rate: float,
                      precision: str = "f32") -> dict:
    """Three Adam steps from ``params`` (consumed) on ``batches`` (three
    ``(x [B,W,F], y [B,W,E])`` pairs, normalised).  Returns the numbers the
    comparison reads: each step's loss, the first gradient's norm per leaf
    and the norm of each leaf's change after the three steps."""
    start = jax.tree.map(jnp.copy, params)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    count = jnp.zeros((), jnp.float32)
    root = train_rng(seed)
    losses, first = [], None
    for i, (x, y) in enumerate(batches):
        params, mu, nu, count, loss, gnorm = _adam_step(
            params, mu, nu, count, jnp.asarray(x, jnp.float32),
            jnp.asarray(y, jnp.float32), jax.random.fold_in(root, i),
            quantiles=tuple(quantiles), rate=rate, precision=precision)
        losses.append(float(loss))
        if first is None:
            first = {k: float(v) for k, v in gnorm.items()}
    delta = leaf_norms({k: params[k] - start[k] for k in params})
    return {"losses": losses, "grad_norm": first,
            "delta_norm": {k: float(v) for k, v in delta.items()}}


# -- normalisation ---------------------------------------------------------------


def minmax(x, lo, hi):
    rng = hi - lo
    return np.where(rng == 0.0, x, (x - lo) / np.where(rng == 0.0, 1.0, rng))
