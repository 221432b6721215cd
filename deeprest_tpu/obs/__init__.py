"""deeprest_tpu/obs — spans, metrics, and profiling for the whole plane.

One package, four surfaces (ISSUE 9), two helpers (ISSUEs 24, 35):

- :mod:`.spans` — ring-buffer span recorder with request-scoped trace ids
  propagated router → admission → replica → batcher → fused dispatch
  (process replicas forward span batches over their duplex pipe);
  near-zero cost when disabled.
- :mod:`.metrics` — counters/gauges/histograms registry rendered as
  Prometheus text at ``GET /metrics`` on the serving plane; the trainer /
  stream side emits step time, superstep dispatch counts, what set-up
  cost, ETL stall/lag, and readback counts into the same registry.
- :mod:`.profiler` — ``jax.profiler`` windows that are read back
  (``POST /v1/profile`` + ``deeprest profile``, ``train --profile-dir``):
  the device's time by the named scopes of the compiled step, its idle
  gaps by the program span that covers them.
- :mod:`.phases` — ``PhaseClock``: the phases of a repeated unit of host
  work (the trainer's epoch) as spans and as a gauge of the last unit's
  seconds per phase.
- :mod:`.setup` — what comes before the first steady step: the set-up
  phase open on a thread, the process's one ``jax.monitoring`` listener
  (every compilation by jitted program and by that phase, as counters and
  as spans), and the set-up gauges as a table and as a line.
- :mod:`.export` — spans as Jaeger-style JSON + span-derived busy-seconds
  as Prometheus range JSON, both consumed by the STANDARD ingest pipeline
  (data/ingest.py), so the plane's own traffic becomes a DeepRest corpus
  and the estimator can estimate itself.

Nothing here imports jax at module scope (the profiler imports it inside
its functions) — obs is safe to wire through every layer, including the
CLI's lazy-import cold path.
"""

from __future__ import annotations

from deeprest_tpu.obs.metrics import (
    PROMETHEUS_CONTENT_TYPE, REGISTRY, Counter, Gauge, Histogram,
    MetricsRegistry, Stopwatch,
)
from deeprest_tpu.obs.spans import (
    NULL_SPAN, RECORDER, SpanRecord, SpanRecorder, current_context,
    set_context, span,
)
from deeprest_tpu.obs.phases import PhaseClock


def configure(enabled: bool | None = None,
              span_capacity: int | None = None) -> None:
    """Flip the process-default span recorder (the serve CLI's ``--obs``
    knob).  Metrics counters are always live — they are the cheap half —
    so only span recording is gated.  The recorder is reconfigured IN
    PLACE: every module already holding the reference keeps recording
    into the same object."""
    if span_capacity is not None and span_capacity != RECORDER.capacity:
        RECORDER.set_capacity(span_capacity)
    if enabled is not None:
        RECORDER.enabled = bool(enabled)


__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Stopwatch",
    "PhaseClock",
    "REGISTRY", "PROMETHEUS_CONTENT_TYPE",
    "SpanRecord", "SpanRecorder", "RECORDER", "NULL_SPAN",
    "span", "current_context", "set_context", "configure",
]
