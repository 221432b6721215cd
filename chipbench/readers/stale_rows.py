"""How many rows of each layer-0 input weight were stale when the last
epoch began: off the staged corpus's table and yet carrying an Adam moment
that an earlier corpus left.  The program's gauge
``deeprest_train_optimizer_rows``, kinds ``stale`` and ``total`` (F), set
at the end of an epoch through the compact superstep; while it is above 0
every step of the epoch ran Adam over all F rows (``adam_rows_pct.train``
100).  A program without the kind (an older commit), or one that trained
on no compact base, reads as nothing, not as an error."""


def stale_pct(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_optimizer_rows")
    if gauge is None or not gauge.value(kind="total") or not any(
            "stale" in key for key in gauge.series()):
        return None
    return 100.0 * gauge.value(kind="stale") / gauge.value(kind="total")
