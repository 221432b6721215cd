#!/usr/bin/env python3
"""One process, one cell, one run of the benchmark.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by name: the cell in
``BENCHMARK.json``, its configuration file, its traffic mix under
``chipbench/traffic/``, the mix's generator under ``chipbench/generators/``,
its runner under ``chipbench/runners/``, the limits of its correctness
check under ``chipbench/limits/``, and each per-layer metric's reader
(``chipbench/layer_metrics/<metric>.json`` names it).  This file holds no
cell, configuration, mix or metric name.  See chipbench/README.md.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()        # process start, as near as Python allows

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def log(*parts) -> None:
    print("[chipbench]", *parts, flush=True)


def load_cell(workload: str) -> dict:
    """The cell, its configuration, its traffic mix and its limits, each
    from its file."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, entry["file"])) as fh:
        config = json.load(fh)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as fh:
        mix = json.load(fh)
    with open(os.path.join(HERE, "limits", workload + ".json")) as fh:
        limits = json.load(fh)["limits"]
    return {"bench": bench, "cell": cell, "config": config, "mix": mix,
            "limits": limits}


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


class Compiles:
    """Counts XLA compilations in this process (``jax.monitoring``): every
    backend compile, served from the persistent cache or not."""

    def __init__(self):
        import jax.monitoring

        self.count = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


class MemoryWatch:
    """The most bytes a chip held AT ONE TIME, the allocator's and the loaded
    programs' together, to the resolution of its samples.

    On this backend ``peak_bytes_in_use`` does not count what the runtime
    reserves for a loaded program's temporaries (``bytes_reserved``, real HBM:
    PERF.md section 6, PR 53), and the two peaks the backend keeps may lie
    apart in time, so their sum is bytes that were never held together.
    Between two samples of one chip the allocator's part is
    ``peak_bytes_in_use`` if it rose in the interval, else the larger of the
    two ``bytes_in_use``; the programs' part is the larger of the two
    ``bytes_reserved``.  The interval holds the sum of the two parts, the
    chip the largest interval's, the cell the fullest chip's.  What it
    cannot see: a peak of the allocator inside an interval that does not
    pass its own high-water mark, and a reservation made and dropped inside
    one interval.  A backend that reports no memory reads 0, an absent key 0
    for its part."""

    KEYS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved")

    def __init__(self, devices):
        self._devices = devices         # () -> the devices to watch
        self._last = {}                 # device id -> its last sample
        self._high = {}                 # device id -> most bytes at one time
        self.samples = []               # [{"at", "devices": {id: sample}}]

    def sample(self, at: str) -> int:
        """Read every chip, close the interval since its last sample;
        returns the fullest chip's high-water mark."""
        now = {}
        for d in self._devices():
            stats = d.memory_stats() or {}
            cur = now[d.id] = {k: int(stats.get(k, 0)) for k in self.KEYS}
            old = self._last.get(d.id, dict.fromkeys(self.KEYS, 0))
            allocator = (cur["peak_bytes_in_use"]
                         if cur["peak_bytes_in_use"] > old["peak_bytes_in_use"]
                         else max(cur["bytes_in_use"], old["bytes_in_use"]))
            programs = max(cur["bytes_reserved"], old["bytes_reserved"])
            self._high[d.id] = max(self._high.get(d.id, 0),
                                   allocator + programs)
        self._last = now
        self.samples.append({"at": at, "devices": now})
        return self.high()

    def high(self) -> int:
        return max(self._high.values(), default=0)


class Context:
    """What a runner gets: the cell's data, the seed, the clock's origin and
    the device."""

    def __init__(self, loaded: dict, seed: int, seconds: float, trace: bool,
                 device, peaks: dict | None, compiles: Compiles):
        import jax

        self.cell = loaded["cell"]
        self.config = loaded["config"]
        self.mix = loaded["mix"]
        self.limits = loaded["limits"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t0 = T0
        self.device = device
        self.peaks = peaks
        self.compiles = compiles
        self.log = log
        self.memory = MemoryWatch(jax.devices)

    def generator(self):
        return importlib.import_module(
            f"chipbench.generators.{self.mix['generator']}")

    def memory_peak_bytes(self) -> int:
        """The most bytes the fullest chip has held at one time so far
        (``MemoryWatch``: the one place the rule lives); takes a sample."""
        return self.memory.sample("memory_peak_bytes")

    def key_seed(self) -> int:
        """The seed folded into what a 32-bit PRNG key takes."""
        return self.seed % (2 ** 31 - 1)


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True) -> dict:
    """Run one cell and return the result object.  ``require_chip=False`` is
    for the tests under chipbench/tests only: the command line cannot reach
    it, and a result made without a chip names the platform it ran on."""
    loaded = load_cell(workload)
    from deeprest_tpu.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    import jax

    t_import = time.perf_counter()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compiles = Compiles()

    from chipbench import flops

    devices = jax.devices()
    dev = devices[0]
    record = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    log("device", json.dumps(record), "compile cache", cache_dir)
    log(f"start-up: {t_import - T0:.3f} s to import jax, "
        f"{time.perf_counter() - t_import:.3f} s to reach the device")
    peaks = None
    if require_chip:
        if dev.platform != "tpu":
            raise SystemExit(f"no accelerator: platform {dev.platform!r}")
        if len(devices) < int(loaded["cell"]["chips"]):
            raise SystemExit(f"{len(devices)} chips, the cell asks for "
                             f"{loaded['cell']['chips']}")
    if dev.platform == "tpu":
        peaks = flops.chip_peaks(dev.device_kind)   # raises for an unknown kind

    ctx = Context(loaded, seed, seconds, trace, dev, peaks, compiles)
    runner = importlib.import_module(
        f"chipbench.runners.{loaded['mix']['runner']}")
    out = runner.run(ctx)

    record["memory_peak_bytes"] = int(out["memory_peak_bytes"])
    log("memory", json.dumps({k: v for k, v in
                              (dev.memory_stats() or {}).items()
                              if k in ("peak_bytes_in_use", "bytes_limit",
                                       "peak_bytes_reserved")}))

    bench, name = loaded["bench"], loaded["cell"]["name"]
    metrics, result = {}, {}
    if trace:
        evidence = out["evidence"]
        evidence.update(config=loaded["config"], peaks=peaks, log=log,
                        memory_samples=ctx.memory.samples)
        for m in bench["per_layer"]:
            with open(os.path.join(HERE, "layer_metrics",
                                   m["name"] + ".json")) as fh:
                spec = json.load(fh)
            # a metric names its cells, or else is read in every cell of
            # the kinds of run its file lists
            if (name not in m["workloads"] if "workloads" in m
                    else loaded["mix"]["runner"] not in spec["runners"]):
                continue
            module, func = spec["reader"].split(":")
            value = getattr(importlib.import_module(
                f"chipbench.readers.{module}"), func)(evidence)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        reduced = evidence["trace"]
        record["busy_s"] = reduced["busy_s"]
        record["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    else:
        for m in bench["end_to_end"]:
            if _applies(m, name) and m["name"] in out["values"]:
                metrics[m["name"]] = {"value": out["values"][m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]), "failed": int(out["failed"]),
              "metrics": metrics, "device": record, **result}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
