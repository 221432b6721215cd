"""The phases of a repeated unit of host work, as spans AND as seconds.

The trainer's epoch is the user: plan build, plan upload, dispatch, log
readbacks, the closing wait, the loss readback.  From outside such a unit
can only be timed whole (or read as the device's idle share); from inside
each phase is one ``with`` line that

- opens a span ``<component>/<phase>`` on the process recorder, a child of
  the unit's own span (so, recorder on, it lies on the profiler's
  timeline and obs/profiler.py can put a device idle gap down to it), and
- adds its seconds to the unit's tally, which is published when the unit
  finishes: a gauge holding the LAST finished unit's seconds per phase (a
  mean over the process would fold the first unit's one-off compiles in)
  and a count of units (none for a unit that runs once a process, as
  ``Trainer.init_state`` does).

A phase entered inside another (a log readback inside the dispatch loop)
is timed exclusively: its seconds are taken off the enclosing phase's, so
a unit's phases never sum to more than its wall time.  The metrics are
always live and are touched once per unit, never per step; with the
recorder off a phase costs one clock pair.
"""

from __future__ import annotations

import contextlib

from deeprest_tpu.obs.metrics import Counter, Gauge, Stopwatch
from deeprest_tpu.obs.spans import RECORDER, current_context


class PhaseClock:
    def __init__(self, name: str, component: str, phases: tuple[str, ...],
                 last_seconds: Gauge, units_total: Counter | None = None):
        self.name = name
        self.component = component
        self.phases = tuple(phases)
        self.last_seconds = last_seconds
        self.units_total = units_total

    @contextlib.contextmanager
    def unit(self, tags: dict | None = None):
        """One unit of work, its span carrying ``tags``.  Yields
        ``phase(name)``, the context manager for its phases.  A unit that
        raises publishes nothing."""
        seconds = dict.fromkeys(self.phases, 0.0)
        nested = [0.0]      # seconds of the phases inside the open one

        @contextlib.contextmanager
        def phase(name: str):
            if name not in seconds:
                raise ValueError(f"{self.name} has no phase {name!r} "
                                 f"(has: {self.phases})")
            outer, nested[0] = nested[0], 0.0
            sw = Stopwatch()
            try:
                with RECORDER.span(name, self.component, parent=parent):
                    yield
            finally:
                elapsed = sw.elapsed()
                seconds[name] += elapsed - nested[0]
                nested[0] = outer + elapsed

        with RECORDER.span(self.name, self.component, tags):
            parent = current_context()
            yield phase
        for name, value in seconds.items():
            self.last_seconds.set(value, phase=name)
        if self.units_total is not None:
            self.units_total.inc()


__all__ = ["PhaseClock"]
