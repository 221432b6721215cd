"""How many rows of each layer-0 input weight the last epoch's dispatches
WROTE: the program's gauge ``deeprest_train_optimizer_rows``, kinds
``visited`` (the table's width and the stale rows to the chunk of the
off-table pass; F past the program's bound and on the paths that consult no
table; set since PR 34) and ``total`` (F).  Beside it ``adam_rows_pct.train``
reads ``updated``, the rows over which the step IS Adam, which is F while a
single row is stale.  A program without the kind (an older commit), or one
that trained on no sparse corpus, reads as nothing, not as an error."""


def visited_pct(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_optimizer_rows")
    if gauge is None or not gauge.value(kind="total") or not any(
            "visited" in key for key in gauge.series()):
        return None
    return 100.0 * gauge.value(kind="visited") / gauge.value(kind="total")
