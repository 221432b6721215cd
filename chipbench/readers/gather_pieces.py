"""In how many pieces a direction's folded layer-0 input weight reaches the
projection each step under a mesh whose ``data`` axis splits the carried
rows: the program's gauge ``deeprest_train_projection_gather_pieces`` (set
since PR 49 beside ``deeprest_train_optimizer_rows``, kind ``per_chip``:
the groups of experts whose rows are gathered while the group before them
is projected, ``parallel/sharding.gather_pieces``; 1 where the table is
narrow and the partitioner gathers the weight whole).  What the pieces buy
is read by ``collective_exposed_ms.train``.  A program without the gauge (an
older commit), or one whose rows are not split (one chip), reads as nothing,
not as an error."""


def per_step(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    pieces = REGISTRY.get("deeprest_train_projection_gather_pieces")
    if pieces is None or not pieces.series():
        return None
    return pieces.value()
