"""How many arrays the compiled superstep reverses in time a step round
the recurrence kernels: the program's gauge
``deeprest_train_time_reversals``, set in the first epoch from the text
of the executable it dispatched
(``deeprest_tpu/obs/profiler.time_reversals``: the ``reverse``
instructions, fused or not, under the ``recurrence`` scope whose result
is an array).  Each is a pass over a ``[E, T, B, 3H]`` or ``[E, T, B, H]``
array that computes nothing; 0 says that the kernels walk the reverse
direction's time blocks back to front themselves, 5 is what flipping the
projection and the hidden states round a forward-only kernel compiles to
with its backward pass.  A program without the gauge (an older commit)
reads as nothing, not as an error."""


def reversals_per_step(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_time_reversals")
    if gauge is None or not gauge.series():
        return None
    return gauge.value()
