"""Whether the run's process loaded the superstep's executable from the
store the last process left beside the compilation cache
(``deeprest_tpu/train/kept.py``) and so traced, lowered and compiled nothing
of it: ``deeprest_train_kept_executables_total{program,result}`` counts each
first call of a signature once, by what the store did.  Read in the run's
own process after its last epoch.  A program without the counter (an older
commit) reads as nothing, not as an error."""

from chipbench.readers.setup import _sum


def superstep_loaded(_evidence):
    """The ``loaded`` count of ``train_superstep``: 1 where the process ran
    the kept executable, 0 where it traced (no file, a stale one, one it
    could not read, nothing to keep it in)."""
    return _sum("deeprest_train_kept_executables_total",
                lambda labels: (labels["program"], labels["result"])
                == ("train_superstep", "loaded"))
