"""TPU compute primitives: scan-based GRU, quantile (pinball) loss."""

from deeprest_tpu.ops.gru import (
    GRUParams,
    bidirectional_gru,
    gru,
    init_gru_params,
)
from deeprest_tpu.ops.quantile import pinball_loss

__all__ = [
    "GRUParams",
    "gru",
    "bidirectional_gru",
    "init_gru_params",
    "pinball_loss",
]
