"""From a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer metrics read, with nothing but ``jax.profiler.ProfileData``.

- window: from the first to the last device operation of the traced slice;
- busy: the union of the intervals in which an operation ran on the device
  (line ``XLA Ops`` of each ``/device:TPU:n`` plane), averaged over chips;
- an operation's time is its self time: its duration less what the
  operations nested in it cover (``while`` and ``conditional`` hold their
  bodies' operations on the same line);
- kernel time: the summed self time of the Pallas/Mosaic custom calls (the
  event's HLO text holds ``KERNEL_MARK``);
- device_ops: operations by summed self time, under the instruction names
  the trace gives (the text before `` = ``);
- idle_gaps: the gaps between device operations, each named for the
  benchmark's own host span (``jax.profiler.TraceAnnotation`` whose name
  starts with ``bench.``) that covers its middle, else ``unattributed``,
  summed by that name.
"""

from __future__ import annotations

import collections
import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def is_kernel(text: str) -> bool:
    return KERNEL_MARK in text


def short_name(text: str) -> str:
    return text.split(" = ", 1)[0]


def self_times(events):
    """[(text, self ns)] of events [(text, start, duration)] on one line,
    where an event may hold later, shorter events inside it."""
    out, stack = [], []                 # stack of [text, end, self]
    for text, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([text, start + dur, dur])
    out += [(text, own) for text, _, own in stack]
    return out


def union_seconds(intervals) -> float:
    """Length of the union of (start, end) intervals, in the intervals'
    unit."""
    total, cur_lo, cur_hi = 0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        elif hi > cur_hi:
            cur_hi = hi
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def gaps(intervals):
    """The idle (start, end) stretches between sorted, merged intervals."""
    out, cur_hi = [], None
    for lo, hi in sorted(intervals):
        if cur_hi is not None and lo > cur_hi:
            out.append((cur_hi, lo))
        cur_hi = hi if cur_hi is None else max(cur_hi, hi)
    return out


def attribute(gap, spans) -> str:
    mid = (gap[0] + gap[1]) / 2
    best = None
    for name, lo, hi in spans:
        if lo <= mid <= hi and (best is None or hi - lo < best[1]):
            best = (name, hi - lo)          # the innermost covering span
    return best[0] if best else "unattributed"


def reduce_planes(planes) -> dict:
    """``planes``: an iterable of (plane name, [(line name, [(event name,
    start ns, duration ns)])]): what ProfileData gives, as plain data."""
    device, spans = {}, []
    for plane_name, lines in planes:
        for line_name, events in lines:
            if plane_name.startswith(DEVICE_PLANE):
                if line_name == OPS_LINE:
                    device[plane_name] = events
            else:
                spans += [(n, s, s + d) for n, s, d in events
                          if n.startswith(SPAN_PREFIX)]
    if not any(device.values()):
        raise RuntimeError("the trace holds no device operation")
    per_chip, ops, kernel_ns, gap_ns = [], collections.Counter(), 0, \
        collections.Counter()
    for events in device.values():
        if not events:
            continue
        ivals = [(s, s + d) for _, s, d in events]
        lo = min(i[0] for i in ivals)
        hi = max(i[1] for i in ivals)
        per_chip.append((union_seconds(ivals), hi - lo))
        for text, own in self_times(events):
            ops[short_name(text)] += own
            if is_kernel(text):
                kernel_ns += own
        for gap in gaps(ivals):
            gap_ns[attribute(gap, spans)] += gap[1] - gap[0]
    n = len(per_chip)
    return {
        "chips": n,
        "busy_s": sum(b for b, _ in per_chip) / n / 1e9,
        "window_s": sum(w for _, w in per_chip) / n / 1e9,
        "kernel_s": kernel_ns / n / 1e9,
        "device_ops": [[k, v / n / 1e9] for k, v in ops.most_common(10)],
        "idle_gaps": [[k, v / n / 1e9] for k, v in gap_ns.most_common(10)],
    }


def read_planes(path: str):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [(plane.name,
             [(line.name, [(ev.name, ev.start_ns, ev.duration_ns)
                           for ev in line.events])
              for line in plane.lines])
            for plane in data.planes
            if plane.name.startswith(DEVICE_PLANE)
            or plane.name.startswith("/host:")]


def reduce_file(path: str) -> dict:
    return reduce_planes(read_planes(path))


def reduce_dir(trace_dir: str) -> dict:
    return reduce_file(find_xplane(trace_dir))
