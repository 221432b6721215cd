"""What the runtime held reserved for loaded programs while the window's
superstep was loaded, from the harness's own samples of ``memory_stats()``
(``run.MemoryWatch``: one at every phase line and at every read of the
peak): the largest ``bytes_reserved`` level of any chip from the phase line
that closes the checked steps (every runner names it "... through the
window's superstep": the superstep has been dispatched, ``init_state``'s and
the seeded weights' programs are behind) to the runner's read of the peak
right after the window.  The samples after that read are taken while the
reference's programs are loaded and are left out; the backend's own mark,
``peak_bytes_reserved``, is of the whole process and is not read.  A
backend that reserves nothing, or reports no memory (the CPU), reads as
nothing."""

PEAK_READ = "memory_peak_bytes"      # the label of `Context.memory_peak_bytes`
CHECKED = "through the window's superstep"


def program_reserved_gb(evidence):
    samples = evidence.get("memory_samples") or []
    first = next((i for i, s in enumerate(samples) if CHECKED in s["at"]),
                 None)
    reads = [i for i, s in enumerate(samples) if s["at"] == PEAK_READ]
    if first is None or not reads or reads[-1] < first:
        return None
    largest = max((chip.get("bytes_reserved", 0)
                   for s in samples[first:reads[-1] + 1]
                   for chip in s["devices"].values()), default=0)
    return largest / 1e9 if largest else None
