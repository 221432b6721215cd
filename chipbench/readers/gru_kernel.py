"""The recurrence kernels (the Pallas/Mosaic custom calls of
``ops/pallas_gru.py``) in the traced slice: their time per unit of work, and
their share of the roofline."""

from chipbench import flops


def ms_per_step(evidence):
    trace, steps = evidence.get("trace"), evidence.get("steps")
    if not trace or not steps or trace["kernel_s"] <= 0:
        return None
    return 1e3 * trace["kernel_s"] / steps


def ms_per_kwindow(evidence):
    trace, windows = evidence.get("trace"), evidence.get("windows")
    if not trace or not windows or trace["kernel_s"] <= 0:
        return None
    return 1e3 * trace["kernel_s"] / (windows / 1e3)


def roofline(evidence):
    trace, steps = evidence.get("trace"), evidence.get("steps")
    work, peaks = evidence.get("kernel_work_per_step"), evidence.get("peaks")
    if not trace or not steps or not work or not peaks \
            or trace["kernel_s"] <= 0:
        return None
    share, bound = flops.roofline_share_pct(
        work, trace["kernel_s"] / steps, peaks)
    evidence["log"](f"gru kernels: {work['flops'] / 1e9:.2f} GFLOP and "
                    f"{work['bytes'] / 1e6:.1f} MB a step in "
                    f"{1e3 * trace['kernel_s'] / steps:.4f} ms: "
                    f"{bound}-bound, {share:.3f}% of the roofline")
    return share
