"""Bisect the 10k-endpoint full-step blowup: which ingredient of the
jitted train step (dropout, weighting, value_and_grad, adam, donation)
causes step time far beyond the sum of its parts."""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sync(out):
    """Host readback of the result: the clock stops after the device."""
    import jax
    import numpy as np

    leaf = jax.tree.leaves(out)[0]
    np.asarray(jax.numpy.ravel(leaf)[:1])


def bench(fn, args, warmup=2, iters=5, donate_state=False):
    state = args[0]
    for _ in range(warmup):
        out = fn(*((state,) + args[1:]))
        if donate_state:
            state = out[0]
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*((state,) + args[1:]))
        if donate_state:
            state = out[0]
    _sync(out)
    return (time.perf_counter() - t0) / iters * 1000


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.ops.quantile import pinball_loss
    from deeprest_tpu.train import Trainer
    from deeprest_tpu.train.trainer import TrainState

    B, T, F, E, H = 32, 60, 10240, 40, 128
    cfg = Config(
        model=ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                          compute_dtype="bfloat16"),
        train=TrainConfig(batch_size=B, window_size=T),
    )
    trainer = Trainer(cfg, F, [f"c{i}" for i in range(E)])
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((B, T, F), np.float32))
    y = jnp.asarray(rng.random((B, T, E), np.float32))
    w = jnp.ones((B,), jnp.float32)
    state = trainer.init_state(np.asarray(x))
    q = cfg.model.quantiles
    model = trainer.model
    tx = trainer.tx

    out = {}

    # A: value_and_grad, deterministic, no weights, no adam
    def a(st, xb, yb):
        def lf(p):
            preds = model.apply({"params": p}, xb, deterministic=True)
            return pinball_loss(preds, yb, q)
        return jax.value_and_grad(lf)(st.params)
    out["A_vag_det"] = bench(jax.jit(a), (state, x, y)); print(out, flush=True)

    # B: + dropout
    def b(st, xb, yb):
        dr = jax.random.fold_in(st.rng, st.step)
        def lf(p):
            preds = model.apply({"params": p}, xb, deterministic=False,
                                rngs={"dropout": dr})
            return pinball_loss(preds, yb, q)
        return jax.value_and_grad(lf)(st.params)
    out["B_vag_dropout"] = bench(jax.jit(b), (state, x, y)); print(out, flush=True)

    # C: + sample weights
    def c(st, xb, yb, wb):
        dr = jax.random.fold_in(st.rng, st.step)
        def lf(p):
            preds = model.apply({"params": p}, xb, deterministic=False,
                                rngs={"dropout": dr})
            return pinball_loss(preds, yb, q, sample_weight=wb)
        return jax.value_and_grad(lf)(st.params)
    out["C_vag_dropout_w"] = bench(jax.jit(c), (state, x, y, w)); print(out, flush=True)

    # D: + adam, no donation
    def d(st, xb, yb, wb):
        dr = jax.random.fold_in(st.rng, st.step)
        def lf(p):
            preds = model.apply({"params": p}, xb, deterministic=False,
                                rngs={"dropout": dr})
            return pinball_loss(preds, yb, q, sample_weight=wb)
        loss, grads = jax.value_and_grad(lf)(st.params)
        updates, opt_state = tx.update(grads, st.opt_state)
        params = optax.apply_updates(st.params, updates)
        return TrainState(step=st.step + 1, params=params,
                          opt_state=opt_state, rng=st.rng), loss
    out["D_full_nodonate"] = bench(jax.jit(d), (state, x, y, w),
                                   donate_state=True); print(out, flush=True)

    # E: + donation (== trainer._train_step shape)
    out["E_full_donate"] = bench(jax.jit(d, donate_argnums=0),
                                 (state, x, y, w), donate_state=True)

    # F: the trainer's own compiled step
    state2 = trainer.init_state(np.asarray(x))
    out["F_trainer_step"] = bench(trainer._train_step, (state2, x, y, w),
                                  donate_state=True)

    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
