"""The collectives of a traced slice (the all-reduces the partitioner puts
into a program that runs on a mesh of several chips): their time, the part
of it in which the chip did nothing else, and the bytes a step hands them.

:func:`reduce_planes` is the reduction, on what
``trace_reduce.read_planes`` gives; the runner calls it while the trace is
still there and puts the result in ``evidence["collectives"]``.  Per chip
(line ``XLA Ops`` of each ``/device:TPU:n`` plane), averaged over chips:

- a collective is an event whose instruction name (the text before `` = ``)
  is ``all-reduce``, ``reduce-scatter``, ``all-gather``,
  ``collective-permute`` or ``all-to-all``, with or without ``-start`` /
  ``-done`` and XLA's ``.N``;
- its time runs from its start to its end: a synchronous one is its own
  event; an asynchronous one runs from the start of its ``-start`` event to
  the end of the next ``-done`` event of its kind.  Overlapping ones are
  counted once (the union);
- exposed: the part of that time in which no other operation ran on that
  chip.  What else ran: every event that is no collective and holds no
  other event (a ``while`` or ``conditional`` holds its body's events on
  the same line and is itself no work).

A reader returns None where there is nothing to read: no ``collectives``
in the evidence (another runner's run), a slice without a collective, a
program without the gauge (an older commit).
"""

from __future__ import annotations

import collections
import re

from chipbench import trace_reduce

KINDS = ("all-reduce", "reduce-scatter", "all-gather", "collective-permute",
         "all-to-all")
_NAME = re.compile(rf"^%?({'|'.join(KINDS)})(-start|-done)?(?:\.\d+)?$")


def kind_of(text: str):
    """("all-reduce", "-start") of an event's text, None for what is no
    collective."""
    m = _NAME.match(trace_reduce.short_name(text))
    return (m[1], m[2] or "") if m else None


def _merged(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _overlap(a, b) -> int:
    total = i = j = 0
    while i < len(a) and j < len(b):
        total += max(0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def line_collectives(events):
    """(ns from start to end, ns of it exposed) of one chip's [(text,
    start ns, duration ns)]."""
    spans, opened, others, stack = [], collections.defaultdict(list), [], []
    for text, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            lo, hi, holds = stack.pop()
            if not holds:
                others.append((lo, hi))
        kind = kind_of(text)
        if kind is None:
            if stack:
                stack[-1][2] = True
            stack.append([start, start + dur, False])
        elif kind[1] == "-start":
            opened[kind[0]].append(start)
        elif kind[1] == "-done" and opened[kind[0]]:
            spans.append((opened[kind[0]].pop(0), start + dur))
        else:
            spans.append((start, start + dur))
    others += [(lo, hi) for lo, hi, holds in stack if not holds]
    spans = _merged(spans)
    total = sum(hi - lo for lo, hi in spans)
    return total, total - _overlap(spans, _merged(others))


def reduce_planes(planes) -> dict:
    per_chip = [line_collectives(events)
                for plane, lines in planes
                if plane.startswith(trace_reduce.DEVICE_PLANE)
                for line, events in lines
                if line == trace_reduce.OPS_LINE and events]
    n = max(len(per_chip), 1)
    return {"chips": len(per_chip),
            "collective_s": sum(t for t, _ in per_chip) / n / 1e9,
            "exposed_s": sum(x for _, x in per_chip) / n / 1e9}


def _per_step_ms(evidence, key):
    found, steps = evidence.get("collectives"), evidence.get("steps")
    if not found or not steps or found["collective_s"] <= 0:
        return None
    return 1e3 * found[key] / steps


def ms_per_step(evidence):
    return _per_step_ms(evidence, "collective_s")


def exposed_ms_per_step(evidence):
    return _per_step_ms(evidence, "exposed_s")


def mb_per_step(_evidence):
    """The program's gauge ``deeprest_train_collective_bytes``: bytes a
    step of the compiled superstep hands to its collectives, all kinds."""
    from deeprest_tpu.obs.metrics import REGISTRY

    gauge = REGISTRY.get("deeprest_train_collective_bytes")
    if gauge is None or not gauge.series():
        return None
    return sum(gauge.series().values()) / 1e6
