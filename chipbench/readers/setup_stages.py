"""Where set-up's host seconds go inside jax's pipeline, as the program's
one ``jax.monitoring`` listener recorded them (``deeprest_tpu/obs/setup.py``)
beside the compilations ``setup.py`` reads: the seconds a thread spent
tracing a jitted program to a jaxpr
(``deeprest_trace_seconds_total{program,phase}``) and lowering the jaxpr to
an MLIR module (``deeprest_lower_seconds_total{program,phase}``).  Both are
SELF time: only the outermost trace or lower event open on a thread is
counted, less the compilations that ended inside it, so trace + lower +
compile seconds of a series is the wall time its thread spent in the
pipeline and the sums below count no second twice.  Read in the run's own
process after its last epoch.  A program without the counters (an older
commit) reads as nothing, not as an error."""

from chipbench.readers.setup import _programs_own, _sum

_TRACE = "deeprest_trace_seconds_total"
_LOWER = "deeprest_lower_seconds_total"
_COMPILE = "deeprest_compile_seconds_total"


def trace_s(_evidence):
    """Seconds tracing the program's own jitted functions (the trainer's
    by name; the primitives an un-jitted ``model.init`` runs one by one as
    ``other`` in phase ``init_state``); the harness's and its reference's
    (``other`` outside every phase) are left out, as ``compile_s`` does."""
    return _sum(_TRACE, _programs_own)


def lower_s(_evidence):
    """The same of lowering."""
    return _sum(_LOWER, _programs_own)


def superstep_build_s(_evidence):
    """Trace + lower + compile seconds of ``train_superstep`` in every
    phase: what stands between a process and its first step that an
    executable kept from the last process would skip."""
    parts = [_sum(name, lambda labels: labels["program"] == "train_superstep")
             for name in (_TRACE, _LOWER, _COMPILE)]
    return None if None in parts else sum(parts)
