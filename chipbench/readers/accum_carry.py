"""The gradient accumulator an update carries across its microbatches, in
MB: the program's gauge ``deeprest_train_accumulation{kind="carry_bytes"}``,
set in the first epoch from the shapes the dispatched superstep's scan
carries.  Some 260 MB where the accumulator is the compact table's rows and
the other leaves; 1,486 MB if an ``[E, F, 3H]`` gradient of each w_ih leaf
were accumulated.  A program without the gauge (an older commit), or one
that makes one microbatch an update, reads as nothing, not as an error."""


def carry_mb(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_accumulation")
    if gauge is None or gauge.value(kind="microbatches") <= 1:
        return None
    return gauge.value(kind="carry_bytes") / 1e6
