"""Optimizer updates (Adam steps) an epoch: the program's counter
``deeprest_train_optimizer_updates_total`` over
``deeprest_train_epochs_total``, read in the run's own process after its
last epoch.  One a step where a step is an update (126 or 32 in the cells of
one microbatch an update), one a group of ``grad_accum_windows`` microbatches
under accumulation (16 in `tenk-train-accum8`).  A program without the
counter (an older commit) reads as nothing, not as an error."""


def per_epoch(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    updates = REGISTRY.get("deeprest_train_optimizer_updates_total")
    epochs = REGISTRY.get("deeprest_train_epochs_total")
    if updates is None or epochs is None or not epochs.value():
        return None
    return updates.value() / epochs.value()
