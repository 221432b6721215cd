"""``run.MemoryWatch``: the most bytes a chip held at one time, on scripted
``memory_stats`` sequences of stub devices (no backend is asked anything);
``common.harness_only`` and ``common.phase`` on top of it; the reader of the
samples.  GB here are just round numbers."""

import types

import pytest

from chipbench import common
from chipbench.readers import memory_samples
from chipbench.run import MemoryWatch

GB = 10 ** 9


class Chip:
    """A device whose ``memory_stats()`` plays a script: one dict a call,
    the last one for ever after."""

    def __init__(self, id, script):
        self.id, self.script, self.calls = id, list(script), 0

    def memory_stats(self):
        stats = self.script[min(self.calls, len(self.script) - 1)]
        self.calls += 1
        return stats


def stats(in_use, peak, reserved=None, peak_reserved=None):
    out = {"bytes_in_use": int(in_use * GB), "peak_bytes_in_use": int(peak * GB)}
    if reserved is not None:
        out["bytes_reserved"] = int(reserved * GB)
    if peak_reserved is not None:
        out["peak_bytes_reserved"] = int(peak_reserved * GB)
    return out


def high_after(*scripts):
    chips = [Chip(i, s) for i, s in enumerate(scripts)]
    watch = MemoryWatch(lambda: chips)
    for n in range(max(len(s) for s in scripts)):
        high = watch.sample(f"sample {n}")
    return high / GB, watch


CASES = {
    # init_state pins 8.9 and frees to 4.5; the superstep is loaded AFTER:
    # 8.9, not 8.9 + 0.6 (the two peaks were never held together)
    "a reservation after the allocator's peak is not added to it": (
        [[stats(0.0, 0.0, 0.0), stats(4.5, 8.9, 0.0), stats(4.5, 8.9, 0.6),
          stats(4.5, 8.9, 0.6)]], 8.9),
    # the program is loaded first and the allocator peaks beside it
    "a reservation that stands while the allocator peaks is added": (
        [[stats(0.3, 0.3, 2.4), stats(0.3, 0.7, 2.4)]], 0.7 + 2.4),
    # the step is the peak: steady in use + the loaded program
    "in use and reserved at one sample are held together": (
        [[stats(0.3, 0.65, 0.0), stats(0.3, 0.65, 2.4),
          stats(0.31, 0.65, 2.4)]], 0.31 + 2.4),
    # the peak rose inside an interval whose ends reserve 0.6 and 4.5
    "a peak that rose inside an interval takes the larger reservation": (
        [[stats(1.0, 1.0, 0.6), stats(1.0, 5.0, 4.5)]], 5.0 + 4.5),
    "a reservation dropped at the interval's end still counts for it": (
        [[stats(1.0, 1.0, 4.5), stats(1.0, 5.0, 0.0)]], 5.0 + 4.5),
    # no rise: the larger of the two ends' in use, never the old peak again
    "an old peak is not counted a second time with a new reservation": (
        [[stats(2.0, 9.0, 0.0), stats(3.0, 9.0, 0.0), stats(2.5, 9.0, 4.0)]],
        9.0),
    # the backend's mark of the reservation may lie apart in time from the
    # allocator's: only the two levels of an interval's ends are counted
    "a reservation made and dropped inside an interval is not seen": (
        [[stats(0.3, 0.65, 0.0, 0.1), stats(0.31, 0.65, 0.0, 2.4)]], 0.65),
    "the backend's mark of the reservation is never added": (
        [[stats(0.3, 0.65, 0.0, 2.4), stats(0.5, 0.65, 0.0, 2.4),
          stats(0.4, 0.65, 0.1, 2.4)]], 0.65),
    "of two chips the fullest wins": (
        [[stats(1.0, 1.0, 0.0), stats(1.0, 1.0, 0.5)],
         [stats(1.0, 1.0, 0.0), stats(1.2, 3.0, 0.5)]], 3.5),
    "a key that is absent reads 0 for its part": (
        [[stats(1.0, 2.0), stats(1.0, 2.0)]], 2.0),
    "a backend without stats reads 0": ([[None, None]], 0.0),
    "an empty dict reads 0": ([[{}, {}]], 0.0),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_high_water_rule(name):
    scripts, expected = CASES[name]
    high, _ = high_after(*scripts)
    assert high == pytest.approx(expected, abs=1e-9), name


def test_never_the_sum_of_the_two_peaks():
    """ROADMAP B18 as PR 52 wrote it (peak_bytes_in_use +
    peak_bytes_reserved) reads 13.4 here; at no time were more than 9.0
    held."""
    script = [stats(4.5, 8.9, 0.0) | {"peak_bytes_reserved": 0},
              stats(4.5, 8.9, 4.5) | {"peak_bytes_reserved": int(4.5 * GB)}]
    high, _ = high_after(script)
    assert high == pytest.approx(9.0)


def _ctx(chips):
    logged = []
    ctx = types.SimpleNamespace(
        memory=MemoryWatch(lambda: chips), device=chips[0],
        log=lambda *parts: logged.append(" ".join(map(str, parts))))
    ctx.memory_peak_bytes = lambda: ctx.memory.sample("memory_peak_bytes")
    return ctx, logged


def test_harness_only_raises_when_the_harness_sets_the_peak():
    # before: 0.3 + 2.4 held; inside, the harness's copy takes the
    # allocator to 0.7 beside the loaded program: 3.1 > 2.7
    chip = Chip(0, [stats(0.3, 0.3, 2.4), stats(0.3, 0.7, 2.4)])
    ctx, _ = _ctx([chip])
    with pytest.raises(RuntimeError, match="raised the peak of device memory"):
        with common.harness_only(ctx, "a second copy"):
            pass


def test_harness_only_passes_under_an_older_peak():
    # init_state's 8.9 stands; the harness's copy beside the loaded program
    # reaches 4.5 + 1.5 + 0.6 and sets no peak
    chip = Chip(0, [stats(4.5, 8.9, 0.0), stats(4.5, 8.9, 0.6),
                    stats(6.0, 8.9, 0.6), stats(4.5, 8.9, 0.6)])
    ctx, _ = _ctx([chip])
    ctx.memory.sample("init_state")
    with common.harness_only(ctx, "the seeded weights"):
        ctx.memory.sample("inside")
    assert ctx.memory.high() == int(8.9 * GB)


def test_phase_samples_and_logs_the_reservation():
    chip = Chip(0, [stats(0.3, 0.65, 2.4)])
    ctx, logged = _ctx([chip])
    common.phase(ctx, "warm-up epoch", 0.0)
    assert [s["at"] for s in ctx.memory.samples] == ["warm-up epoch"]
    assert ctx.memory.high() == int(0.65 * GB) + int(2.4 * GB)
    assert "reserved for loaded programs 2.400 GB" in logged[0]


def test_phase_takes_a_context_that_watches_no_memory():
    """The stub contexts of tests/test_accum8.py, test_warm_retrain.py and
    test_weeks_retrain.py have no ``memory``."""
    logged = []
    ctx = types.SimpleNamespace(device=Chip(0, [None]),
                                log=lambda *p: logged.append(p))
    common.phase(ctx, "dataset", 0.0)
    assert "reserved for loaded programs 0.000 GB" in logged[0][0]


def test_reader_reads_the_reservation_while_the_superstep_was_loaded():
    # init_state's programs reserve 3.0 before the superstep is loaded, the
    # reference's 8.6 GB program comes after the runner's last read, and the
    # backend's own mark stands at 8.6 from a process's earlier life
    chips = [Chip(0, [stats(4.5, 8.9, 3.0, 8.6), stats(0.3, 8.9, 2.4, 8.6),
                      stats(0.3, 8.9, 2.39, 8.6), stats(0.3, 8.9, 2.39, 8.6),
                      stats(0.0, 8.9, 8.6, 8.6)])]
    watch = MemoryWatch(lambda: chips)
    for at in ("trainer and init_state",
               "first three steps through the window's superstep",
               "warm-up epoch (loss 0.1)", "memory_peak_bytes",
               "reference and comparison"):
        watch.sample(at)
    evidence = {"memory_samples": watch.samples}
    assert memory_samples.program_reserved_gb(evidence) == pytest.approx(2.4)
    assert memory_samples.program_reserved_gb({}) is None
    # no line closes the checked steps: nothing to read
    assert memory_samples.program_reserved_gb(
        {"memory_samples": watch.samples[:1] + watch.samples[3:]}) is None
    # a backend that reserves nothing reads as nothing, not as 0
    cpu = MemoryWatch(lambda: [Chip(0, [None])])
    cpu.sample("three steps through the window's superstep, across weeks")
    cpu.sample("memory_peak_bytes")
    assert memory_samples.program_reserved_gb(
        {"memory_samples": cpu.samples}) is None
