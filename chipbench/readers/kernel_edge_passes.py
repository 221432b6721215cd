"""How many passes a step of the compiled superstep makes over an operand
or a result of a recurrence kernel call only to cut it apart or to sum it:
the program's gauge ``deeprest_train_kernel_edge_passes``, set in the first
epoch from the text of the executable it dispatched
(``deeprest_tpu/obs/profiler.kernel_edge_passes``: a ``reduce``, fused or
not, with no dot beside it, over a result of a ``gru_kernel_bwd`` call,
and what a ``split`` under the ``recurrence`` scope lowered to).  Each
reads a ``[E, T, B, 3H]`` or ``[E, T, B, 2H]`` array again to compute
nothing the backward kernels do not hold; 0 says that the kernels read the
joined cotangent where it lies and return the input bias's gradient
themselves, 3 is what autodiff puts round a VJP of one direction's kernel
call (the split of the joined cotangent, a sum over ``dproj`` a
direction).  A program without the gauge (an older commit) reads as
nothing, not as an error."""


def passes_per_step(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_kernel_edge_passes")
    if gauge is None or not gauge.series():
        return None
    return gauge.value()
