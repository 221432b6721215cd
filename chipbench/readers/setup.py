"""What set-up cost and what the compiler decided, as the program itself
recorded them (``deeprest_tpu/obs/setup.py`` names the gauges,
``Trainer`` and the process's one compile listener set them), read in the
run's own process after its last epoch.  A program without a gauge (an
older commit), or a backend that reports no device memory, reads as
nothing, not as an error."""

_OWN = ("other", "other")   # (program, phase) of what is not the program's


def _series(name):
    """[({label: value}, number)] of the metric's series, or None."""
    from deeprest_tpu.obs.metrics import REGISTRY

    metric = REGISTRY.get(name)
    if metric is None or not metric.series():
        return None
    return [(dict(zip(metric.labelnames, key)), value)
            for key, value in metric.series().items()]


def _sum(name, keep=lambda labels: True):
    series = _series(name)
    if series is None:
        return None
    return sum(value for labels, value in series if keep(labels))


def _programs_own(labels) -> bool:
    """Every compilation but those of a program that is none of the
    trainer's outside every set-up phase: the caller's own, its
    reference's."""
    return (labels["program"], labels["phase"]) != _OWN


def init_state_s(_evidence):
    """``Trainer.init_state``: the sum of its phases' host seconds, the
    last of which ends with the wait on the state."""
    return _sum("deeprest_train_init_state_seconds")


def compile_s(_evidence):
    """Seconds in XLA's backend for the program's own compilations and
    cache loads."""
    return _sum("deeprest_compile_seconds_total", _programs_own)


def compilations(_evidence):
    """How many they were."""
    return _sum("deeprest_compilations_total", _programs_own)


def _device_gb(at, kind):
    series = _series("deeprest_train_device_bytes")
    for labels, value in series or ():
        if labels == {"at": at, "kind": kind}:
            return value / 1e9
    return None


def init_state_peak_gb(_evidence):
    """The peak of device memory when ``init_state`` returned."""
    return _device_gb("init_state", "peak")


def steady_hbm_gb(_evidence):
    """Device memory in use after the first epoch: the state and the staged
    corpus, the step's temporaries freed."""
    return _device_gb("first_epoch", "in_use")


def gru_kernel_vmem_pct(_evidence):
    """The share of the bytes the compiled superstep hands its recurrence
    kernels a step (operands and results) that the compiler's memory-space
    assignment put in VMEM."""
    total = _sum("deeprest_train_kernel_operand_bytes")
    if not total:
        return None
    return 100.0 * _sum("deeprest_train_kernel_operand_bytes",
                        lambda labels: labels["space"] == "vmem") / total
