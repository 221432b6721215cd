"""Component-level timing of the train step at the 10k-endpoint width.

Times each stage of the flagship step at F=10240 in isolation (proj einsum,
model fwd, fwd+bwd, full step with Adam, the mask-fold materialization) to
locate where the 10k config's step time actually goes.  Diagnostic tool, not
part of the bench contract.
"""

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


def timeit(fn, *args, warmup=2, iters=5):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters * 1000  # ms


def main():
    import jax
    import jax.numpy as jnp

    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.train import Trainer

    B, T, F, E, H = 32, 60, int(sys.argv[1]) if len(sys.argv) > 1 else 10240, 40, 128
    cfg = Config(
        model=ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                          compute_dtype="bfloat16"),
        train=TrainConfig(batch_size=B, window_size=T),
    )
    names = [f"c{i}_r" for i in range(E)]
    trainer = Trainer(cfg, F, names)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.random((B, T, F), np.float32))
    y = jnp.asarray(rng.random((B, T, E), np.float32))
    w = jnp.ones((B,), jnp.float32)
    state = trainer.init_state(np.asarray(x))

    out = {"shape": {"B": B, "T": T, "F": F, "E": E, "H": H}}

    # full step (donated state: rebuild each call is wrong; run via scan of 1)
    st = state
    def full_step(st, x, y, w):
        st2, loss = trainer._train_step(st, x, y, w)
        return st2, loss
    # warmup/compile
    st, loss = full_step(st, x, y, w)
    _sync(loss)
    t0 = time.perf_counter()
    iters = 5
    for _ in range(iters):
        st, loss = full_step(st, x, y, w)
    _sync(loss)
    out["full_step_ms"] = (time.perf_counter() - t0) / iters * 1000

    params = st.params

    # fwd only
    fwd = jax.jit(lambda p, xb: trainer.model.apply({"params": p}, xb,
                                                    deterministic=True))
    out["fwd_ms"] = timeit(fwd, params, x)

    # fwd+bwd (no optimizer)
    from deeprest_tpu.ops.quantile import pinball_loss
    q = cfg.model.quantiles

    def loss_fn(p, xb, yb):
        preds = trainer.model.apply({"params": p}, xb, deterministic=True)
        return pinball_loss(preds, yb, q)
    grad = jax.jit(jax.grad(loss_fn))
    out["fwd_bwd_ms"] = timeit(grad, params, x, y)

    # adam update alone
    g = grad(params, x, y)
    upd = jax.jit(lambda g, o, p: trainer.tx.update(g, o, p))
    out["adam_ms"] = timeit(upd, g, st.opt_state, params)

    # proj einsum alone (per direction): x @ w_ih
    w_ih = params["gru_fwd_w_ih"].astype(jnp.bfloat16)
    xb16 = x.astype(jnp.bfloat16)
    proj = jax.jit(lambda xv, wv: jnp.einsum("btf,efg->etbg", xv, wv))
    out["proj_einsum_ms"] = timeit(proj, xb16, w_ih)

    # mask-fold materialization alone: mask[:, :, None] * w_ih
    mask = jax.nn.softmax(jnp.asarray(rng.random((E, F), np.float32)), -1)
    fold = jax.jit(lambda m, wv: m[:, :, None] * wv)
    out["mask_fold_ms"] = timeit(fold, mask, params["gru_fwd_w_ih"])

    # masked proj (what the model actually computes per direction)
    mproj = jax.jit(lambda xv, m, wv: jnp.einsum(
        "btf,efg->etbg", xv, (m[:, :, None] * wv).astype(jnp.bfloat16)))
    out["masked_proj_ms"] = timeit(mproj, x, mask, params["gru_fwd_w_ih"])

    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
