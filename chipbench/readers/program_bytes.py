"""What the compiler counted for the dispatched superstep, from its
``memory_analysis``: the program's gauge
``deeprest_train_program_bytes{kind}``, set in the first epoch (since PR
35), read in the run's own process after its last epoch.  ``temporaries``
is what a dispatch needs beside its arguments; the runtime reserves 81 to
99% of it from the program's first run (``memory_samples.py`` reads that
side from the device; PERF.md section 6, PR 53): where steady memory and
that reservation pass what ``init_state`` pins and frees, the step sets the
peak of device memory.  A program without the
gauge (an older commit), or a backend whose executable gives no analysis,
reads as nothing, not as an error."""

from chipbench.readers.setup import _series


def temporaries_gb(_evidence):
    for labels, value in _series("deeprest_train_program_bytes") or ():
        if labels["kind"] == "temporaries":
            return value / 1e9
    return None
