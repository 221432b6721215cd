"""The names of the work inside the compiled train step.

Every ``jax.named_scope`` of ops/, models/qrnn.py and train/trainer.py,
and the ``name=`` of the two ``pallas_call`` s of ops/pallas_gru.py, is one
of these constants, so a call site cannot name a scope this list lacks.
``Trainer.profile_epoch`` hands :data:`STEP_SCOPES` + :data:`KERNELS` to
obs/profiler.py, which knows no layer's name of its own and puts whatever
carries none of the names it is given under ``other``.
tests/test_obs_layers.py holds every name to the compiled superstep.
"""

GATHER = "gather"           # starts → idx → base[idx] (train/trainer.py)
DENSIFY = "densify"         # ops/densify.py
MASK = "mask"               # the feature mask and its fold (models/qrnn.py)
IN_PROJ = "in_proj"         # the hoisted input projection (ops/gru.py)
RECURRENCE = "recurrence"   # the scan, or the layout work round the kernels
                            # (pads, the join, the one transpose; no reversal
                            # in time since ISSUE 41: the kernels walk it)
DROPOUT = "dropout"         # the step's key (trainer); the mask's ONE draw, a
                            # fusion of its own since ISSUE 36 (models/qrnn.py)
MIXING = "mixing"           # cross-expert mixing (models/qrnn.py)
HEADS = "heads"             # the quantile heads (models/qrnn.py)
LOSS = "loss"               # ops/quantile.py
ACCUMULATE = "accumulate"   # a microbatch's gradient added into the update's
                            # accumulator (grad_accum_windows > 1; trainer)
OPTIMIZER = "optimizer"     # tx.update + apply_updates, once an UPDATE
                            # (train/trainer.py)
OFF_TABLE = "off_table"     # the compact superstep's zero-gradient Adam pass
                            # over the stale rows of the w_ih leaves, by
                            # chunks or, past a bound, whole (train/trainer.py)
STEP_SCOPES = (GATHER, DENSIFY, MASK, IN_PROJ, RECURRENCE, DROPOUT, MIXING,
               HEADS, LOSS, ACCUMULATE, OPTIMIZER, OFF_TABLE)

# Not gru_fwd/gru_bwd: those are the two DIRECTIONS' parameter leaves.
GRU_KERNEL_FWD = "gru_kernel_fwd"   # the forward pass's kernel
GRU_KERNEL_BWD = "gru_kernel_bwd"   # the backward pass's kernel
KERNELS = (GRU_KERNEL_FWD, GRU_KERNEL_BWD)
