#!/usr/bin/env python3
"""ISSUE 53 step 1, run ON THE CHIP by PR 53 (readings: PERF.md section 6):

    chiprun -- python3 chipbench/tests/phase_stats_on_chip.py <cell> <seed> <seconds> <trace 0|1>

One run of the cell through ``run.run_cell`` that prints the whole ``memory_stats()`` of the fullest
chip at every ``common.phase()`` line and every sample ``run.MemoryWatch`` takes (WHEN the runtime's
reservation appears, whether it stands between dispatches, what is left once the program is freed), and
at the end the program's gauges ``deeprest_train_program_bytes`` and ``deeprest_train_device_bytes`` to
hold the reservation against.  ``PHASE_STATS_SAMPLER=1`` adds a 10 ms sampler thread that prints every
change over 8 MB of in use, its peak, reserved and its peak.  The result line comes last."""
import importlib
import json
import os
import pkgutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
import chipbench.run as R
import chipbench.common as C
import chipbench.runners as RUNNERS

KEYS4 = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved", "peak_bytes_reserved")

def fullest():
    import jax
    best = {}
    for d in jax.devices():
        s = d.memory_stats() or {}
        if s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0) >= best.get("bytes_in_use", 0) + best.get("bytes_reserved", 0):
            best = s
    return best

def at():
    return f"[stats] at {time.perf_counter() - R.T0:8.3f} s"

_orig = C.phase
_th = []
def phase(ctx, name, since):
    if os.environ.get("PHASE_STATS_SAMPLER") and not _th:
        _th.append(threading.Thread(target=sampler, daemon=True)); _th[0].start()
    now = _orig(ctx, name, since)
    print(f"{at()} PHASE {name[:48]!r}: " + json.dumps(fullest()), flush=True)
    return now

stop = threading.Event()
def sampler():
    import jax
    dev = jax.devices()[0]
    last, hi_sum = None, 0
    while not stop.is_set():
        s = dev.memory_stats() or {}
        cur = tuple(s.get(k, 0) for k in KEYS4)
        hi_sum = max(hi_sum, cur[0] + cur[2])
        if last is None or any(abs(a - b) > (8 << 20) for a, b in zip(cur, last)):
            print(f"{at()} sample in_use {cur[0]/1e9:.3f} peak_in_use {cur[1]/1e9:.3f} "
                  f"reserved {cur[2]/1e9:.3f} peak_reserved {cur[3]/1e9:.3f}  in_use+reserved {(cur[0]+cur[2])/1e9:.3f}", flush=True)
            last = cur
        time.sleep(0.01)
    print(f"[stats] sampler: highest in_use+reserved seen {hi_sum/1e9:.4f} GB", flush=True)

def main():
    workload, seed, seconds, trace = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), bool(int(sys.argv[4]))
    C.phase = phase
    for m in pkgutil.iter_modules(RUNNERS.__path__):
        mod = importlib.import_module(f"chipbench.runners.{m.name}")
        if hasattr(mod, "phase"):
            mod.phase = phase
    if hasattr(R, "MemoryWatch"):
        plain = R.MemoryWatch.sample
        def sample(self, label):
            high = plain(self, label)
            print(f"{at()} WATCH {label[:40]!r} high {high} " + json.dumps(self.samples[-1]["devices"]), flush=True)
            return high
        R.MemoryWatch.sample = sample
    try:
        result = R.run_cell(workload, seed, seconds, trace)
    finally:
        stop.set(); [t.join() for t in _th]
    print("[stats] end " + json.dumps(fullest()), flush=True)
    from chipbench.readers.setup import _series
    for g in ("deeprest_train_program_bytes", "deeprest_train_device_bytes"):
        print("[stats] gauge", g, _series(g), flush=True)
    print(json.dumps(result), flush=True)

main()
