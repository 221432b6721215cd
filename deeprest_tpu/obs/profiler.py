"""On-demand jax.profiler capture windows + the step-time breakdown.

Two tools:

- :func:`capture` — a bounded ``jax.profiler`` trace window, one at a
  time (a second concurrent request gets :class:`ProfilerBusy`).  The
  serving plane mounts it at ``POST /v1/profile`` and ``deeprest
  profile`` drives it over the wire: the handler keeps serving traffic on
  the other threads while the window is open, so the trace captures the
  plane under its real load.  Inspect with TensorBoard/XProf.
- :func:`measure_step_breakdown` — where does a train step's wall time
  go?  Built on bench.py measure_main's trial ledger: the only timed
  edges are host readbacks of the updated params, and the ledger asserts
  every trial closed with one (chip_smoke.py times the same steps closed
  by ``jax.block_until_ready`` and prints both).  The breakdown splits per-step cost into host
  feed (fresh window tensors staged to device), dispatch (the Python/jax
  call returning), and device wait (dispatch edge → updated-params
  readback completing).
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

_capture_lock = threading.Lock()


class ProfilerBusy(RuntimeError):
    """A capture window is already open (one at a time, by design)."""


def capture(out_dir: str, seconds: float,
            max_seconds: float = 120.0) -> dict:
    """Open a ``jax.profiler`` trace window for ``seconds`` and block
    until it closes.  Returns ``{"trace_dir", "seconds"}``.

    Bounded (``max_seconds``) because the handler thread blocks for the
    window; concurrent captures fail fast with :class:`ProfilerBusy`
    instead of interleaving two traces into one unreadable dump.
    """
    seconds = float(seconds)
    if not (0 < seconds <= max_seconds):
        raise ValueError(
            f"capture seconds {seconds} must be in (0, {max_seconds}]")
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusy("a profiler capture window is already open")
    try:
        import jax

        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
        try:
            time.sleep(seconds)
        finally:
            jax.profiler.stop_trace()
    finally:
        _capture_lock.release()
    return {"trace_dir": os.path.abspath(out_dir), "seconds": seconds}


def measure_step_breakdown(trainer, x, y, w, steps: int = 10,
                           warmup: int = 2) -> dict:
    """Per-step wall-time breakdown of ``trainer._train_step`` on the
    host-feed path (the upper-bound feed cost; the staged path's feed
    term is a [B] index ship and measures ~0).

    Phases, each closed by the honest-sync readback discipline:

    - ``host_feed``: staging the numpy batch onto the device
      (``jax.device_put`` + readiness of the staged buffers).
    - ``dispatch``: the jitted step call returning to Python (async
      dispatch cost — what the host pays per step even when the device
      is the bottleneck).
    - ``device_wait``: from the last dispatch returning to the
      updated-params element readback completing (device execution not
      hidden behind dispatch).

    The trial ledger asserts every timed phase ended in a host readback —
    the same guard bench.py's ``timed_trial`` carries.
    """
    import jax
    import jax.numpy as jnp

    ledger = {"started": 0, "synced": 0}

    def sync_params(state) -> None:
        v = float(jnp.ravel(jax.tree.leaves(state.params)[0])[0])
        if not np.isfinite(v):
            raise RuntimeError(f"non-finite params in breakdown trial ({v})")
        ledger["synced"] += 1

    state = trainer.init_state(x)
    for _ in range(max(1, warmup)):
        state, loss = trainer._train_step(
            state, jnp.asarray(x), jnp.asarray(y), jnp.asarray(w))
    sync_params(state)
    ledger["started"] += 1          # warmup closes with a readback too

    # host_feed: stage fresh batches and force their readiness with an
    # element readback of the staged buffer (same primitive discipline).
    ledger["started"] += 1
    t0 = time.perf_counter()
    staged = []
    for _ in range(steps):
        xb = jax.device_put(x)
        yb = jax.device_put(y)
        wb = jax.device_put(w)
        staged.append((xb, yb, wb))
    probe = float(jnp.ravel(staged[-1][0])[0])
    if not np.isfinite(probe):
        raise RuntimeError("non-finite staged feed probe")
    ledger["synced"] += 1
    host_feed_s = time.perf_counter() - t0

    # dispatch + device wait over the pre-staged batches.
    ledger["started"] += 1
    t1 = time.perf_counter()
    for xb, yb, wb in staged:
        state, loss = trainer._train_step(state, xb, yb, wb)
    t2 = time.perf_counter()        # all steps dispatched
    sync_params(state)              # the trial's closing readback
    t3 = time.perf_counter()

    assert ledger["started"] == ledger["synced"] == 3, ledger
    per = 1e3 / steps
    return {
        "steps": steps,
        "host_feed_ms_per_step": round(host_feed_s * per, 4),
        "dispatch_ms_per_step": round((t2 - t1) * per, 4),
        "device_wait_ms_per_step": round((t3 - t2) * per, 4),
        "total_ms_per_step": round((host_feed_s + (t3 - t1)) * per, 4),
        "ledger": dict(ledger),
    }


__all__ = ["capture", "measure_step_breakdown", "ProfilerBusy"]
