"""What every runner shares: the phase log, the judgement of the numbers
compared against the cell's limits, and the traced slice."""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time

import numpy as np


def phase(ctx, name: str, since: float) -> float:
    """Log a phase's seconds with the device memory in use, its peak so far
    and what the runtime reserves for the loaded programs; a context that
    watches memory (``run.Context``) takes a sample here; returns now."""
    now = time.perf_counter()
    watch = getattr(ctx, "memory", None)
    if watch is not None:
        watch.sample(name)
    stats = ctx.device.memory_stats() or {}
    ctx.log(f"phase {name}: {now - since:.3f} s (memory in use "
            f"{stats.get('bytes_in_use', 0) / 1e9:.3f} GB, peak so far "
            f"{stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB, reserved for "
            f"loaded programs {stats.get('bytes_reserved', 0) / 1e9:.3f} GB)")
    return now


@contextlib.contextmanager
def harness_only(ctx, what: str):
    """Around what the harness itself puts on the device beside the
    program's state.  The caller waits for the program's work before it
    enters and for its own results before it leaves; if the most bytes a
    chip held at one time (``ctx.memory_peak_bytes()``) rose in between, the
    peak is no longer the program's and the run fails.  (A backend that
    reports no memory is not judged.)"""
    before = ctx.memory_peak_bytes()
    yield
    after = ctx.memory_peak_bytes()
    if after > before:
        raise RuntimeError(
            f"{what} raised the peak of device memory from {before} to "
            f"{after} bytes: the harness's own copies, not the program, "
            "would set hbm_peak_gb")


def judge(ctx, numbers: dict) -> bool:
    """Print each number compared beside its limit; True when all hold.  A
    limit whose number was not read does not hold."""
    ok = True
    for name, limit in ctx.limits.items():
        value = numbers.get(name, float("nan"))
        good = bool(np.isfinite(value)) and value <= limit
        extra = numbers.get(name + "_leaf")
        ctx.log(f"compare {name} = {value:.6g} (limit {limit:g})"
                + (f" at {extra}" if extra else "")
                + ("" if good else "  <-- OUT"))
        ok &= good
    return ok


def traced(run_slice):
    """Run ``run_slice()`` under ``jax.profiler`` (Python tracer off, trace
    in a temporary directory that is deleted) and reduce the trace.
    Returns (what run_slice returned, the reduction)."""
    import jax

    from chipbench import trace_reduce

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        with jax.profiler.trace(trace_dir, profiler_options=options):
            out = run_slice()
        return out, trace_reduce.reduce_dir(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
