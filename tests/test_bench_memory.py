"""The yardstick's memory rule in tier-1 (ISSUE 54; PERF.md section 7 (2)):
`chipbench/tests/test_bench_memory.py`'s eighteen scripted cases of
``run.MemoryWatch``, ``common.harness_only``, ``common.phase`` and the
reader of the samples, run by the driver.  That file is the benchmark's
(only a `benchmark` PR may edit or remove it); this one imports its cases
as they stand, so the two cannot drift, and adds what ISSUE 54's cell can
read that no accepted cell does: a peak made of LOADED PROGRAMS.
"""

from chipbench.tests.test_bench_memory import *  # noqa: F401,F403 - its cases
import pytest

from chipbench.tests.test_bench_memory import high_after, stats


def test_a_life_that_loads_three_programs_peaks_at_the_sum_held_at_once():
    """`tenk-retrain-growing`'s two possible readings on one scripted chip:
    a runtime that sizes its one reserved region to the largest loaded
    program (PERF.md section 6, PR 53) leaves the pin as the peak; one that
    kept every loaded program's reservation would make the step the peak,
    and the watch would say so."""
    pin = [stats(0.0, 0.0, 0.0), stats(4.5, 8.9, 0.0)]
    largest = [stats(4.5, 8.9, r) for r in (1.1, 1.9, 3.8)]
    summed = [stats(4.5, 8.9, r) for r in (1.1, 3.0, 6.8)]
    assert high_after([*pin, *largest])[0] == pytest.approx(8.9)
    assert high_after([*pin, *summed])[0] == pytest.approx(4.5 + 6.8)
