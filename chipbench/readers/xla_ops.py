"""Device time outside the recurrence kernels (projection, mask fold,
mixing, heads, loss, optimizer, gather/densify), per step."""


def ms_per_step(evidence):
    trace, steps = evidence.get("trace"), evidence.get("steps")
    if not trace or not steps:
        return None
    return 1e3 * (trace["busy_s"] - trace["kernel_s"]) / steps
