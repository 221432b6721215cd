"""The program's own analytic count of a training step, and the chip's peak.

What is left of the retired CPU harness: the two functions that
``chipbench/tests/test_yardstick.py`` cross-checks ``chipbench/flops.py``
against and ``tests/test_bench_analytics.py`` pins by hand.  The benchmark
is ``chipbench/`` (``BENCHMARK.json``); this file measures nothing, imports
nothing and goes when the yardstick drops its cross-check (ROADMAP D1).
"""

from __future__ import annotations

# Peak bf16 TFLOP/s of one chip, keyed by device_kind substring.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s.  A kind that is not in the table is an error,
# not a default: an MFU against a guessed peak is worse than none.
_CHIP_PEAK_TFLOPS = (
    ("v5 lite", 197.0),     # v5e, as jax.devices()[0].device_kind names it
    ("v5litepod", 197.0),
    ("v5e", 197.0),
)


def chip_peak_tflops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, peak in _CHIP_PEAK_TFLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no published peak for device kind {device_kind!r}; add it to "
        "bench._CHIP_PEAK_TFLOPS with its source")


def train_step_tflops(batch, window, features, experts, hidden,
                      quantiles=3, directions=2) -> float:
    """Analytic TFLOPs per training step (fwd+bwd ~= 3x fwd matmul FLOPs).

    Counts the three matmul families (a 2*M*N*K each): the hoisted input
    projections x @ W_ih, the T sequential h @ W_hh recurrence steps, and
    the quantile heads; mask/mixing/elementwise are negligible.
    """
    proj = 2 * batch * window * experts * features * 3 * hidden
    recur = 2 * batch * window * experts * hidden * 3 * hidden
    heads = 2 * batch * window * experts * (2 * directions * hidden) * quantiles
    fwd = directions * (proj + recur) + heads
    return 3 * fwd / 1e12
