"""How many supersteps the trainer's life dispatched for the first time,
and what the later ones cost: the program's counter
``deeprest_train_superstep_programs_total{form,width}`` (one increment a
staged program's first dispatch) and its gauge
``deeprest_train_superstep_first_dispatch_seconds{nth,form,width}`` (the
host seconds of the nth one: trace, lower, compile or load, enqueue, the
wait).  A life on one table reads 1 and 0; one whose live set outgrows a
table, or the compact form, meets a new program at every such restage.
Read in the run's own process after its last epoch.  A program without
the series (an older commit) reads as nothing, not as an error."""

from chipbench.readers.setup import _series, _sum


def superstep_programs(_evidence):
    """The programs first dispatched, all forms and widths together."""
    return _sum("deeprest_train_superstep_programs_total")


def program_switch_s(_evidence):
    """The seconds of the first dispatches after the life's first: what
    the estate's growth cost set-up beyond a life on one program."""
    series = _series("deeprest_train_superstep_first_dispatch_seconds")
    if series is None:
        return None
    return sum(value for labels, value in series if int(labels["nth"]) > 1)
