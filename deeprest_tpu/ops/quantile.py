"""Quantile (pinball) regression loss.

Vectorized over (batch, time, metric, quantile) in one shot instead of the
reference's per-metric/per-quantile Python loops (reference:
resource-estimation/qrnn.py:58-67); reductions are arranged to be
algebraically identical: sum over quantiles, mean over batch×time, mean over
metrics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from deeprest_tpu.ops import scopes


@jax.named_scope(scopes.LOSS)
def pinball_loss(
    preds: jax.Array,
    targets: jax.Array,
    quantiles: tuple[float, ...] | jax.Array,
    sample_weight: jax.Array | None = None,
    allow_empty: bool = False,
) -> jax.Array:
    """Mean pinball loss.

    Args:
      preds: ``[B, T, E, Q]`` quantile predictions.
      targets: ``[B, T, E]`` observed values.
      quantiles: the Q quantile levels in prediction order.
      sample_weight: optional ``[B]`` weights; the batch mean becomes a
        weighted mean.  Used to pad ragged trailing batches up to a static
        shape with zero-weight duplicates while keeping the loss exactly
        the mean over real samples.
      allow_empty: guard the weighted mean's denominator at 1 so an
        all-zero-weight batch yields loss 0 (and exactly-zero gradients)
        instead of 0/0 NaN.  Real batches have ``sum(weight) >= 1``, where
        ``max(sum, 1)`` returns the identical float — bit-equal to the
        unguarded loss (gradient accumulation relies on this:
        zero-weight pad microbatches inside a partially-real group must
        contribute nothing without a per-microbatch cond branch).

    Returns: scalar loss,
      ``mean_E( mean_{B,T}( sum_Q max((q-1)·err, q·err) ) )``
      with ``err = target - pred``.
    """
    q = jnp.asarray(quantiles, dtype=preds.dtype)  # [Q]
    err = targets[..., None] - preds               # [B, T, E, Q]
    per_q = jnp.maximum((q - 1.0) * err, q * err)  # [B, T, E, Q]
    per_sample = jnp.sum(per_q, axis=-1)           # [B, T, E]
    if sample_weight is None:
        per_metric = jnp.mean(per_sample, axis=(0, 1))
    else:
        w = sample_weight.astype(per_sample.dtype)[:, None, None]
        den = jnp.sum(sample_weight)
        if allow_empty:
            den = jnp.maximum(den, jnp.ones((), den.dtype))
        per_metric = jnp.sum(per_sample * w, axis=(0, 1)) / (
            den * per_sample.shape[1]
        )
    return jnp.mean(per_metric)
