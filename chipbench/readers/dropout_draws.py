"""In how many places the compiled superstep generates the dropout mask's
random bits a step: the program's gauge ``deeprest_train_dropout_draws``,
set in the first epoch from the text of the executable it dispatched
(``deeprest_tpu/obs/profiler.threefry_draws``: the fusions that hold a
threefry round on an array under the ``dropout`` scope).  The program
draws one mask a forward pass; 1 says that the compiled step kept it for
the backward pass, 2 that it drew the bits again there.  A program without the gauge (an older commit), or a step that
draws no mask, reads as nothing, not as an error."""


def draws_per_step(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_dropout_draws")
    if gauge is None or not gauge.series():
        return None
    return gauge.value()
