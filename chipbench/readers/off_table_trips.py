"""How many chunk trips the off-table pass of one dispatch made in the last
epoch: the program's gauge ``deeprest_train_optimizer_rows``, kind
``trips`` (a trip a chunk of 64 stale rows, taken from the six whole
leaves, stepped and put back; 0 with no stale row; every chunk of F where
the state is past the program's bound and the pass runs over the whole
leaves).  A program without the kind (an older commit), or one that
consulted no table, reads as nothing, not as an error."""


def trips(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_optimizer_rows")
    if gauge is None or not any("trips" in key for key in gauge.series()):
        return None
    return gauge.value(kind="trips")
