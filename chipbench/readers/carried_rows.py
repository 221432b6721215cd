"""How much of the compact table's width ONE chip steps: the program's
gauge ``deeprest_train_optimizer_rows``, kind ``per_chip`` (the rows of each
layer-0 input weight whose Adam one chip ran a step in the last epoch: under
a mesh whose ``data`` axis splits the rows that ride the compact superstep's
scan, the table's width over that axis; set since PR 45), over the table's
width (``deeprest_train_projection_columns``, kind ``contracted``).  25 where
four chips each step a quarter of the rows, 100 where every chip steps all of
them.  A program without the kind (an older commit), or one that trained on
no sparse corpus, reads as nothing, not as an error."""


def per_chip_pct(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    rows = REGISTRY.get("deeprest_train_optimizer_rows")
    cols = REGISTRY.get("deeprest_train_projection_columns")
    if rows is None or cols is None or not cols.value(kind="contracted") or (
            not any("per_chip" in key for key in rows.series())):
        return None
    return 100.0 * rows.value(kind="per_chip") / cols.value(kind="contracted")
