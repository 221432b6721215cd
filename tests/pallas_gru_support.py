"""What the three files of interpret-mode recurrence tests share
(tests/test_pallas_gru.py, test_pallas_gru_bidirectional.py,
test_pallas_gru_reverse.py): the odd shape and the parameters drawn at it.
One file a module fixture, so that ``--dist loadfile`` gives each a worker.
"""

import jax
import jax.numpy as jnp

from deeprest_tpu.ops.gru import init_gru_params

E, B, T, F, H = 3, 5, 7, 11, 128  # E and B not multiples of 8 (the blocks)


def _setup(seed=0, e=E, b=B, t=T, f=F, h=H):
    key = jax.random.PRNGKey(seed)
    k1, k2, k3 = jax.random.split(key, 3)
    params = init_gru_params(k1, e, f, h)
    x = jax.random.normal(k2, (b, t, f), jnp.float32)
    return params, x, k3
