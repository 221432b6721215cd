"""How much of the call-path axis the layer-0 projection sums over though it
is exactly zero in every staged row: 100 x (``contracted`` - ``live``) /
``total`` of the program's gauge ``deeprest_train_projection_columns``, set
when it stages a sparse corpus (readers/proj_columns.py says what the three
kinds count; a program has had them since PR 25, and this reader asks for no
other).  0 where the table is the live set, the pad slots' share in the
compact form, the whole dead share of F in the dense form.  A program
without the gauge (an older commit), or one that staged no sparse corpus,
reads as nothing, not as an error."""


def dead_pct(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_projection_columns")
    if gauge is None or not gauge.value(kind="total"):
        return None
    return 100.0 * (gauge.value(kind="contracted")
                    - gauge.value(kind="live")) / gauge.value(kind="total")
