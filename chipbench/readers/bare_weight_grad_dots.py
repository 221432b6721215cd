"""How many layer-0 weight-gradient dots a step of the compiled superstep
runs only to hand the gradient over: the program's gauge
``deeprest_train_bare_weight_grad_dots``, set in the first epoch from the
text of the executable it dispatched
(``deeprest_tpu/obs/profiler.bare_weight_grad_dots``: the convolution
fusions of the ``in_proj`` backward that are as large as a w_ih leaf and
return no float32 array of its shape).  Such a dot runs on the MXU with HBM
idle and writes a bf16 gradient that the optimizer's fusion reads again; 0
says that each direction's dot carries its own leaf's fold backward and
Adam, 1 is what the compiler makes where the two leaves' Adam loops share
one fusion (which takes one dot).  Under accumulation the count is of the
gradients that the ACCUMULATOR consumes, not Adam: `tenk-train-accum8`
reads 14 with the update ordered or plain (eight microbatches' dots a
direction, of which the compiler gives two to the fusion that holds the sum
and Adam), and there the number says nothing of the update's order.  A
program without the gauge (an older commit) reads as nothing, not as an
error."""


def dots_per_step(_evidence):
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_bare_weight_grad_dots")
    if gauge is None or not gauge.series():
        return None
    return gauge.value()
