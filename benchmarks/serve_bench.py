#!/usr/bin/env python
"""Closed-loop load benchmark for the HTTP prediction service.

Two measurement planes:

1. **Single-engine** (schema v1 cells, unchanged): N concurrent clients
   hammer `/v1/predict` with MIXED series lengths against ONE
   Predictor/MicroBatcher stack, batched vs per-request — what
   cross-request micro-batching buys at the request level.
2. **Replica sweep** (schema v2, new keys only): the same workload
   against a ReplicaRouter of R in-process engine replicas (each pinned
   to its own virtual device) at concurrencies up to 1024, with bounded
   admission — what the routing plane buys, and the proof that admission
   control sheds overload as fast 429s instead of queueing p99 into
   collapse.  Cells report goodput (rps of 200s), latency percentiles of
   SERVED requests, and 429 counts.

The model is a random-init Predictor at a serving-realistic small shape —
load benching needs the compute graph, not trained weights.  Closed loop:
each client issues its next request as soon as the previous one returns
(a 429 sleeps the advertised Retry-After first), so offered load scales
with measured capacity rather than overrunning it.

Emits ONE schema-versioned JSON document (benchmarks/serve_bench.json).
Schema note (learned from bench.py's round-5 key repurposing): fields are
never silently redefined — meaning changes bump schema_version; v2 adds
keys (replica cells carry ``replicas``/``rejected_429``; the doc gains
``replica_sweep``, ``admission_at_max``, ``honest_cpu``) and changes none.

A NOTE ON THE CPU CEILING: this container exposes one physical core;
R replicas on R virtual devices still share it, so aggregate rps cannot
scale with R here — the sweep proves the PLUMBING (balanced per-replica
served counts, zero post-warmup compiles per stack, bounded p99 under
admission); the hardware curve is not measured on the chip.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SCHEMA_VERSION = 2

# Serving-realistic small shape: big enough that the device batch is real
# work, small enough that the bench is CPU-friendly.
F, E, H, W, Q = 32, 8, 128, 24, 3
# Mixed series lengths -> 1..3 windows per request incl. ragged tails
# (right-aligned last window): the online capacity-estimation request is
# "predict for the most recent window(s)".
SERIES_LENGTHS = (24, 24, 24, 31, 36, 47)
LADDER = (8, 16, 32, 64)


def build_predictor():
    import numpy as np

    import jax

    from deeprest_tpu.config import ModelConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.serve import Predictor

    mc = ModelConfig(feature_dim=F, num_metrics=E, hidden_size=H,
                     dropout_rate=0.0)
    model = QuantileGRU(config=mc)
    params = model.init(jax.random.PRNGKey(0),
                        np.zeros((1, W, F), np.float32),
                        deterministic=True)["params"]
    x_stats = MinMaxStats(min=np.float32(0.0), max=np.float32(1.0))
    y_stats = MinMaxStats(min=np.zeros((E,), np.float32),
                          max=np.ones((E,), np.float32))
    names = [f"comp{i // 2}_res{i % 2}" for i in range(E)]
    return Predictor(params, mc, x_stats, y_stats, names, W, ladder=LADDER)


def warm_ladder(pred) -> None:
    """Compile every rung up front: the measurement must see zero new
    compiles (the acceptance bar for the shape-bucketed jit cache)."""
    import numpy as np

    for rung in pred.ladder.ladder:
        pred.ladder(np.zeros((rung, W, F), np.float32))


def warm_router(router) -> None:
    """Warm every DISTINCT replica stack's ladder rungs."""
    seen = set()
    for rep in router.replicas:
        backend = rep.backend()
        if id(backend) in seen:
            continue
        seen.add(id(backend))
        warm_ladder(backend)


def router_rung_compiles(router) -> int:
    seen, total = set(), 0
    for rep in router.replicas:
        backend = rep.backend()
        if id(backend) in seen:
            continue
        seen.add(id(backend))
        total += backend.ladder.stats()["rung_compiles"]
    return total


class _Client(threading.Thread):
    """One closed-loop client: request, wait, repeat until the deadline.
    Admission 429s are counted separately (not errors, not latencies) and
    honor the server's Retry-After hint before the next attempt."""

    def __init__(self, addr, payloads, deadline, barrier):
        super().__init__(daemon=True)
        self.addr = addr
        self.payloads = payloads
        self.deadline = deadline
        self.barrier = barrier
        self.latencies: list[float] = []
        self.errors = 0
        self.rejected = 0

    def run(self):
        conn = http.client.HTTPConnection(*self.addr, timeout=120)
        i = 0
        self.barrier.wait()
        while time.perf_counter() < self.deadline:
            body = self.payloads[i % len(self.payloads)]
            i += 1
            t0 = time.perf_counter()
            try:
                conn.request("POST", "/v1/predict", body=body,
                             headers={"Content-Type": "application/json"})
                resp = conn.getresponse()
                resp.read()
                if resp.status == 429:
                    self.rejected += 1
                    retry = resp.getheader("Retry-After")
                    try:
                        time.sleep(min(float(retry or 0.05), 0.25))
                    except ValueError:
                        time.sleep(0.05)
                    continue
                if resp.status != 200:
                    self.errors += 1
                    continue
            except Exception:
                self.errors += 1
                conn.close()
                conn = http.client.HTTPConnection(*self.addr, timeout=120)
                continue
            self.latencies.append(time.perf_counter() - t0)
        conn.close()


def _percentile(sorted_vals, p):
    if not sorted_vals:
        return None
    k = min(len(sorted_vals) - 1, int(round(p / 100 * (len(sorted_vals) - 1))))
    return sorted_vals[k]


def run_cell(addr, payloads, concurrency, duration_s, warmup_s) -> dict:
    """One (mode, concurrency) measurement cell against a live server."""
    start = time.perf_counter()
    deadline = start + warmup_s + duration_s
    barrier = threading.Barrier(concurrency)
    clients = [_Client(addr, payloads[i::len(payloads)] or payloads,
                       deadline, barrier)
               for i in range(concurrency)]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    cut = warmup_s  # drop each client's warmup-phase latencies by time share
    lats: list[float] = []
    for c in clients:
        # keep only steady-state samples: requests completed after warmup
        acc = 0.0
        for lat in c.latencies:
            acc += lat
            if acc >= cut:
                lats.append(lat)
    lats.sort()
    measured = len(lats)
    errors = sum(c.errors for c in clients)
    rejected = sum(c.rejected for c in clients)
    return {
        "concurrency": concurrency,
        "requests": measured,
        "errors": errors,
        "rejected_429": rejected,
        "rps": round(measured / duration_s, 2),
        "p50_ms": round(1e3 * _percentile(lats, 50), 3) if lats else None,
        "p95_ms": round(1e3 * _percentile(lats, 95), 3) if lats else None,
        "p99_ms": round(1e3 * _percentile(lats, 99), 3) if lats else None,
    }


def _git_sha():
    try:
        out = subprocess.run(["git", "describe", "--always", "--dirty"],
                             capture_output=True, text=True, cwd=REPO,
                             timeout=10)
        return out.stdout.strip() or None
    except Exception:
        return None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--duration", type=float, default=4.0,
                    help="steady-state seconds per (mode, concurrency) cell")
    ap.add_argument("--warmup", type=float, default=1.0,
                    help="per-cell warmup seconds (excluded from stats)")
    ap.add_argument("--concurrency", default="1,4,16,64",
                    help="single-engine closed-loop client counts")
    ap.add_argument("--replicas", default="1,2,4",
                    help="replica counts for the routing-plane sweep")
    ap.add_argument("--replica-concurrency", default="16,64,256,1024",
                    help="closed-loop client counts for the replica sweep")
    ap.add_argument("--admission-depth", type=int, default=64,
                    help="router admission bound (in-flight requests) for "
                         "the replica sweep — sized to the at-capacity "
                         "concurrency so overload is shed, not queued")
    ap.add_argument("--linger-ms", type=float, default=2.0)
    ap.add_argument("--max-batch", type=int, default=64)
    ap.add_argument("--quick", action="store_true",
                    help="tier-1 smoke shape: tiny durations and counts "
                         "(tests/test_serve_bench.py)")
    ap.add_argument("--out", default=os.path.join(REPO, "benchmarks",
                                                  "serve_bench.json"))
    args = ap.parse_args()
    if args.quick:
        args.duration = min(args.duration, 0.6)
        args.warmup = min(args.warmup, 0.3)
        args.concurrency = "2,4"
        args.replicas = "1,2"
        args.replica_concurrency = "4,8"
        args.admission_depth = 8
    concurrencies = [int(c) for c in args.concurrency.split(",")]
    replica_counts = [int(r) for r in args.replicas.split(",")]
    replica_conc = [int(c) for c in args.replica_concurrency.split(",")]

    # Virtual devices so replicas pin to distinct (if contended) devices;
    # must land before the first jax import.
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            f"{max(replica_counts)}").strip()

    import numpy as np

    import jax

    # Serving load tests target the CPU tier (the acceptance harness)
    # unless JAX_PLATFORMS says otherwise; the record's "platform" key
    # names what ran.
    if os.environ.get("JAX_PLATFORMS", "cpu") == "cpu":
        jax.config.update("jax_platforms", "cpu")

    from deeprest_tpu.serve import (
        BatcherConfig, PredictionServer, PredictionService, ReplicaRouter,
        RouterConfig,
    )

    pred = build_predictor()
    warm_ladder(pred)
    rng = np.random.default_rng(0)
    payloads = [json.dumps(
        {"traffic": rng.random((t, F)).astype(np.float32).tolist()}
    ).encode() for t in SERIES_LENGTHS]

    compiles_after_warmup = pred.ladder.stats()["rung_compiles"]
    jit_before = pred.jit_cache_size()

    # -- plane 1: single engine, batched vs per-request (v1 cells) -------
    modes = {
        "batched": BatcherConfig(max_batch=args.max_batch,
                                 max_linger_s=args.linger_ms / 1e3),
        "per_request": None,
    }
    results = []
    for mode, batching in modes.items():
        service = PredictionService(pred, None, backend=f"bench:{mode}",
                                    batching=batching)
        server = PredictionServer(service, port=0).start()
        try:
            for conc in concurrencies:
                cell = run_cell(server.address, payloads, conc,
                                args.duration, args.warmup)
                cell["mode"] = mode
                cell["replicas"] = 1
                if service.batcher is not None:
                    s = service.batcher.stats()
                    cell["batcher"] = {
                        k: s[k] for k in
                        ("batches", "windows", "coalesced_batches",
                         "flush_full", "flush_linger", "flush_pipeline",
                         "max_batch_windows")
                    }
                results.append(cell)
                print(json.dumps(cell), file=sys.stderr)
        finally:
            server.stop()

    new_compiles = pred.ladder.stats()["rung_compiles"] - compiles_after_warmup
    jit_after = pred.jit_cache_size()

    # -- plane 2: replica sweep behind the routing front (v2 cells) ------
    replica_results = []
    replica_new_compiles = 0
    batching = BatcherConfig(max_batch=args.max_batch,
                             max_linger_s=args.linger_ms / 1e3)
    for nrep in replica_counts:
        router = ReplicaRouter.build(
            pred, nrep,
            config=RouterConfig(admission_depth=args.admission_depth,
                                max_wait_s=0.1, retry_after_s=0.25),
            batching=batching,
            devices=list(jax.devices())[:nrep])
        warm_router(router)
        warm_compiles = router_rung_compiles(router)
        service = PredictionService(router, None,
                                    backend=f"bench:replicas={nrep}")
        server = PredictionServer(service, port=0).start()
        try:
            for conc in replica_conc:
                router.admission.reset_window()
                cell = run_cell(server.address, payloads, conc,
                                args.duration, args.warmup)
                cell["mode"] = "replicated"
                cell["replicas"] = nrep
                stats = router.router_stats()
                cell["per_replica_served"] = [
                    r["served_requests"] for r in stats["replicas"]]
                cell["admission"] = {
                    k: stats["admission"][k]
                    for k in ("depth", "admitted", "rejected", "queued")}
                # the latency component the admission bound actually
                # controls (grant -> response); client-observed latency
                # additionally carries the HTTP layer's thread scheduling
                cell["in_plane_p50_ms"] = stats["admission"].get(
                    "in_plane_p50_ms")
                cell["in_plane_p99_ms"] = stats["admission"].get(
                    "in_plane_p99_ms")
                replica_results.append(cell)
                print(json.dumps(cell), file=sys.stderr)
        finally:
            server.stop()           # closes the router's replicas too
        replica_new_compiles += (router_rung_compiles(router)
                                 - warm_compiles)

    def _rcell(nrep, conc):
        for r in replica_results:
            if r["replicas"] == nrep and r["concurrency"] == conc:
                return r
        return None

    def _cell(mode, conc):
        for r in results:
            if r["mode"] == mode and r["concurrency"] == conc:
                return r
        return None

    headline_conc = 16 if 16 in concurrencies else concurrencies[-1]
    b, p = _cell("batched", headline_conc), _cell("per_request", headline_conc)
    headline = None
    if b and p and p["rps"]:
        headline = {
            "concurrency": headline_conc,
            "batched_rps": b["rps"],
            "per_request_rps": p["rps"],
            "throughput_speedup": round(b["rps"] / p["rps"], 2),
            "batched_p99_ms": b["p99_ms"],
            "per_request_p50_ms": p["p50_ms"],
            # acceptance: batched p99 <= 2x per-request p50 at same load
            "latency_ok": (b["p99_ms"] is not None and p["p50_ms"] is not None
                           and b["p99_ms"] <= 2 * p["p50_ms"]),
        }

    sweep_conc = 64 if 64 in replica_conc else replica_conc[-1]
    replica_sweep = {
        "concurrency": sweep_conc,
        "rps_by_replicas": {str(n): (_rcell(n, sweep_conc) or {}).get("rps")
                            for n in replica_counts},
        "p99_ms_by_replicas": {
            str(n): (_rcell(n, sweep_conc) or {}).get("p99_ms")
            for n in replica_counts},
    }
    r1, r2 = _rcell(1, sweep_conc), _rcell(2, sweep_conc)
    if r1 and r2 and r1["rps"]:
        replica_sweep["speedup_2_vs_1"] = round(r2["rps"] / r1["rps"], 3)
        replica_sweep["p99_no_worse_2_vs_1"] = (
            r2["p99_ms"] is not None and r1["p99_ms"] is not None
            and r2["p99_ms"] <= 1.1 * r1["p99_ms"])

    max_conc = max(replica_conc)
    admission_at_max = None
    ref = _rcell(max(replica_counts), sweep_conc)
    cell = _rcell(max(replica_counts), max_conc)
    if cell and ref and ref["p99_ms"] and cell["p99_ms"]:
        in_plane_ref = ref.get("in_plane_p99_ms")
        in_plane_max = cell.get("in_plane_p99_ms")
        admission_at_max = {
            "concurrency": max_conc,
            "replicas": max(replica_counts),
            "rps": cell["rps"],
            "p99_ms": cell["p99_ms"],
            "in_plane_p99_ms": in_plane_max,
            "rejected_429": cell["rejected_429"],
            "errors": cell["errors"],
            "reference_concurrency": sweep_conc,
            "reference_p99_ms": ref["p99_ms"],
            "reference_in_plane_p99_ms": in_plane_ref,
            # the overload gate: the IN-PLANE p99 (admission grant ->
            # response, the part the bounded depth controls) at max
            # concurrency stays within 3x of the at-capacity value —
            # excess load is shed as fast 429s instead of queueing the
            # engine plane into collapse.  Client-observed p99_ms also
            # carries the HTTP layer's thread scheduling (see honest_cpu).
            "p99_bounded": (in_plane_ref is not None
                            and in_plane_max is not None
                            and in_plane_max <= 3.0 * in_plane_ref),
        }

    ncores = os.cpu_count() or 1
    honest_cpu = None
    if jax.devices()[0].platform == "cpu":
        honest_cpu = {
            "physical_cores": ncores,
            "virtual_devices": len(jax.devices()),
            "note": (
                f"replica scaling is device-contention-capped here: "
                f"{len(jax.devices())} virtual CPU devices share "
                f"{ncores} physical core(s), so R replicas add scheduling "
                "slots, not FLOPs — aggregate rps cannot scale with R on "
                "this box.  Client-observed p99 at high concurrency is "
                "additionally dominated by the stdlib thread-per-"
                "connection HTTP layer time-sharing the core across "
                "~concurrency runnable threads BEFORE admission; the "
                "in_plane_p99_ms columns isolate the part the admission "
                "bound controls.  The sweep is the PLUMBING proof "
                "(balanced per_replica_served, zero post-warmup compiles, "
                "bounded in-plane p99 under admission); the hardware "
                "scaling curve is not measured on the chip."),
        }

    doc = {
        "schema_version": SCHEMA_VERSION,
        "metric": "serve_predict_rps",
        "platform": jax.devices()[0].platform,
        "model": {"F": F, "E": E, "H": H, "W": W, "Q": Q,
                  "weights": "random-init (load bench measures the serving "
                             "path, not accuracy)"},
        "workload": {
            "closed_loop": True,
            "series_lengths": list(SERIES_LENGTHS),
            "windows_per_request": [
                len(range(0, t - W + 1, W)) + (0 if (t - W) % W == 0 else 1)
                for t in SERIES_LENGTHS],
            "duration_s": args.duration,
            "warmup_s": args.warmup,
        },
        "batcher": {"max_batch": args.max_batch,
                    "max_linger_ms": args.linger_ms,
                    "ladder": list(LADDER)},
        "router": {"admission_depth": args.admission_depth,
                   "replica_counts": replica_counts,
                   "dispatch": "least-outstanding-windows"},
        "results": results,
        "replica_results": replica_results,
        "headline": headline,
        "replica_sweep": replica_sweep,
        "admission_at_max": admission_at_max,
        "honest_cpu": honest_cpu,
        # Mixed ragged series lengths, two modes, all concurrencies: the
        # shape ladder must have absorbed every shape it saw post-warmup.
        "new_compiles_after_warmup": new_compiles,
        "replica_new_compiles_after_warmup": replica_new_compiles,
        "jit_cache_size": {"before": jit_before, "after": jit_after},
        "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "git_sha": _git_sha(),
    }
    with open(args.out, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(json.dumps({"out": args.out, "headline": headline,
                      "replica_sweep": replica_sweep,
                      "admission_at_max": admission_at_max,
                      "new_compiles_after_warmup": new_compiles,
                      "replica_new_compiles_after_warmup":
                          replica_new_compiles}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
