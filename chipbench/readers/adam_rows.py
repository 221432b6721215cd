"""How many rows of each layer-0 input weight the optimizer wrote in the
last epoch: the program's gauge ``deeprest_train_optimizer_rows``, set at
the end of an epoch on a staged sparse corpus (``updated``: the table's
width where the compact superstep ran Adam on the table's rows, F where a
step ran over all of them; ``total``: F).  A program without the gauge (an
older commit), or one that trained on no sparse corpus, reads as nothing,
not as an error."""


def updated_pct(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_optimizer_rows")
    if gauge is None or not gauge.value(kind="total"):
        return None
    return 100.0 * gauge.value(kind="updated") / gauge.value(kind="total")
