"""The host time of the trainer's last ``stage_dataset`` call: the
program's gauge ``deeprest_train_last_stage_seconds``.  In a cell of the
``train_warm`` runner the last call is the restage of the current week on
the trainer that had staged the prior one.  A program without the gauge
(an older commit) reads as nothing, not as an error."""


def last_stage_ms(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_last_stage_seconds")
    if gauge is None or not gauge.series():
        return None
    return 1e3 * gauge.value()
