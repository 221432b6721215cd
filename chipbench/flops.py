"""Operations and bytes the algorithm needs, computed from shapes, and the
table of peaks.  The yardstick: later PRs change the program, not this.

``train_step_tflops`` is copied from ``bench.train_step_tflops`` (the
original is listed in PERF.md's open questions for deletion with bench.py).
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def chip_peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; raises for a kind the table
    does not hold."""
    with open(os.path.join(_HERE, "peaks.json")) as fh:
        table = json.load(fh)["peaks"]
    if device_kind in table:
        return table[device_kind]
    raise KeyError(f"no published peak for device kind {device_kind!r} in "
                   "chipbench/peaks.json")


def forward_flops(rows, window, features, experts, hidden, quantiles=3,
                  directions=2) -> dict:
    """Matmul FLOPs (2*M*N*K) of one forward pass over ``rows`` windows, by
    family: the hoisted input projection, the recurrence, the heads."""
    proj = 2 * rows * window * experts * features * 3 * hidden * directions
    recur = 2 * rows * window * experts * hidden * 3 * hidden * directions
    heads = 2 * rows * window * experts * (2 * directions * hidden) * quantiles
    return {"proj": proj, "recur": recur, "heads": heads}


def train_step_tflops(batch, window, features, experts, hidden, quantiles=3,
                      directions=2) -> float:
    """TFLOPs of one training step: forward plus backward, about three
    times the forward matmuls.  Recomputation does not count."""
    fwd = forward_flops(batch, window, features, experts, hidden, quantiles,
                        directions)
    return 3 * sum(fwd.values()) / 1e12


def gru_kernel_work(rows, window, experts, hidden, directions=2,
                    training=True, act_bytes=2) -> dict:
    """FLOPs and HBM bytes the recurrence kernels need for one step
    (training: forward and backward) or one forward dispatch of ``rows``
    windows.

    FLOPs: the h @ W_hh dot of every time step (forward), and in the
    backward pass the two dots of the same size (dh @ W_hh^T and the
    weight gradient h^T @ dgates): three times the forward, as counted by
    ``train_step_tflops``.  Gate arithmetic is not counted.

    Bytes, the least the kernel must move: it reads the projected inputs
    [T, E, rows, 3H] and writes the hidden states [T, E, rows, H], in the
    activation type, and reads W_hh once; the backward reads the output
    cotangent and the stored states and gates and writes the projection's
    cotangent (weights and their gradients once each, in float32)."""
    per_dir = rows * window * experts * hidden
    flops_fwd = 2 * per_dir * 3 * hidden * directions
    w_bytes = experts * hidden * 3 * hidden * directions
    bytes_fwd = (per_dir * 3 + per_dir) * directions * act_bytes \
        + w_bytes * act_bytes
    if not training:
        return {"flops": flops_fwd, "bytes": bytes_fwd}
    bytes_bwd = (per_dir            # output cotangent
                 + per_dir          # stored states
                 + per_dir * 3      # stored gates
                 + per_dir * 3      # projection cotangent written
                 ) * directions * act_bytes + 2 * w_bytes * 4
    return {"flops": 3 * flops_fwd, "bytes": bytes_fwd + bytes_bwd}


def roofline_share_pct(work: dict, seconds: float, peaks: dict) -> tuple:
    """(share in %, which bound) of the least time the chip could take over
    the measured time."""
    t_flops = work["flops"] / (peaks["bf16_tflops"] * 1e12)
    t_bytes = work["bytes"] / (peaks["hbm_gb_per_s"] * 1e9)
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
