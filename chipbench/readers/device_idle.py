"""Device idle share of the traced slice: 1 - busy/window, in %."""


def read(evidence):
    trace = evidence.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
