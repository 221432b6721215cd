"""How much of the call-path axis the layer-0 projection sums over: the
program's gauge ``deeprest_train_projection_columns``, set when it stages
a sparse corpus (``live``: columns that can be nonzero; ``contracted``:
columns the projection contracts over; ``total``: F).  A program without
the gauge (an older commit), or one that staged no sparse corpus, reads as
nothing, not as an error."""


def contracted_pct(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_projection_columns")
    if gauge is None or not gauge.value(kind="total"):
        return None
    return 100.0 * gauge.value(kind="contracted") / gauge.value(kind="total")
