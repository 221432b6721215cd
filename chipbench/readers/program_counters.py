"""The program's own always-live counters (``deeprest_tpu.obs.metrics``),
read in the run's own process after its last epoch: what the trainer's
host did at the epoch's boundary, and how often it read back or
dispatched.  A name the program does not have (an older commit) reads as
nothing, not as an error."""


def _metric(name):
    from deeprest_tpu.obs.metrics import REGISTRY

    return REGISTRY.get(name)


def _per_epoch(name):
    metric, epochs = _metric(name), _metric("deeprest_train_epochs_total")
    if metric is None or epochs is None or not epochs.value():
        return None
    return sum(metric.series().values()) / epochs.value()


def epoch_host_ms(_evidence):
    """The last finished epoch's host work at its boundary, during which
    the device has nothing queued: plan build, plan upload, loss readback.
    (Dispatch, log readbacks and the closing wait are waits on the device
    and are left out.)  The run's last epoch is the traced one."""
    last = _metric("deeprest_train_last_epoch_phase_seconds")
    if last is None or not last.series():
        return None
    return 1e3 * sum(last.value(phase=p)
                     for p in ("plan_build", "plan_h2d", "loss_readback"))


def readbacks_per_epoch(_evidence):
    return _per_epoch("deeprest_train_readbacks_total")


def dispatches_per_epoch(_evidence):
    return _per_epoch("deeprest_train_superstep_dispatches_total")
