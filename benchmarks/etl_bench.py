#!/usr/bin/env python
"""Host-ETL benchmark: the featurization firehose, old vs new.

The paper's signal path starts host-side — span trees walked into
per-window call-path count vectors — and the streaming capacity loop
re-featurizes live telemetry forever.  PRs 1-2 removed dispatch overhead
from serving and training; this bench pins the third leg: does host ETL
keep up with the device?  Three measurements, all CPU (the ETL never
touches the chip, so a CPU run is the real measurement):

1. ``featurize``  — buckets/sec through ``CallPathSpace``: the historical
   per-span accumulation loop (``extract_reference``) vs the vectorized
   memo+bincount path (``extract``), hash mode at F∈{512, 10240} and
   dictionary mode, plus the forked-pool corpus featurization
   (``featurize_buckets(workers=N)``) vs serial.
2. ``refresh_assembly`` — milliseconds to assemble the retained-corpus
   traffic matrix + target matrix at refresh time: the deque-era
   ``np.stack`` + per-dict rebuild vs the SeriesRing contiguous views.
3. ``overlap`` — StreamingTrainer refresh cadence against a pre-written
   backlog with the background ETL thread off vs on: per-refresh
   train-thread ETL stall (RefreshResult.etl_stall_s) and refresh-to-
   refresh wall time.  Uses a deliberately small model (the point is the
   host pipeline, not the chip).

``--quick`` runs measurement 1 at F=512 plus measurement 2 at reduced
sizes in a couple of seconds — the tier-1 smoke that keeps the vectorized
path and this harness exercised on every run.  ``quick_buckets_per_sec``
is imported by bench.py for the headline ``etl_buckets_per_sec`` key; it
must stay importable without initializing a JAX backend.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F_FLAGSHIP, F_10K = 512, 10240


def _corpus(buckets: int, seed: int = 0):
    from deeprest_tpu.workload import normal_scenario, simulate_corpus

    scn = normal_scenario(seed)
    scn.calls_per_user = 0.4
    return simulate_corpus(scn, buckets)


def _spans(buckets) -> int:
    return sum(1 for b in buckets for t in b.traces for _ in t.walk())


def _time(fn, min_s: float = 0.2) -> float:
    """Best-of-trials wall time for fn(), re-running until min_s elapsed."""
    best = float("inf")
    spent = 0.0
    while spent < min_s or best == float("inf"):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = min(best, dt)
        spent += dt
    return best


def measure_featurize(buckets, capacity: int, hash_mode: bool = True) -> dict:
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace

    if hash_mode:
        cfg = FeaturizeConfig(hash_features=True, capacity=capacity)
    else:
        cfg = FeaturizeConfig(round_to=128)
    loop_space = CallPathSpace(config=cfg)
    vec_space = CallPathSpace(config=cfg)
    if not hash_mode:
        loop_space.observe(buckets)
        vec_space.observe(buckets)

    def run_loop():
        for b in buckets:
            loop_space.extract_reference(b.traces)

    def run_vec():
        for b in buckets:
            vec_space.extract(b.traces)

    run_vec()                              # warm the path→column memo
    t_loop = _time(run_loop)
    t_vec = _time(run_vec)
    n = len(buckets)
    return {
        "mode": "hash" if hash_mode else "dict",
        "capacity": int(loop_space.capacity),
        "buckets": n,
        "spans": _spans(buckets),
        "loop_buckets_per_sec": round(n / t_loop, 2),
        "vectorized_buckets_per_sec": round(n / t_vec, 2),
        "speedup": round(t_loop / t_vec, 2),
    }


def measure_parallel(buckets) -> dict:
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import featurize_buckets, resolve_workers

    cfg = FeaturizeConfig(round_to=128)
    workers = resolve_workers(0)
    t_serial = _time(lambda: featurize_buckets(buckets, cfg), min_s=0.0)
    t_par = _time(lambda: featurize_buckets(buckets, cfg, workers=workers),
                  min_s=0.0)
    return {
        "workers": workers,
        "buckets": len(buckets),
        "serial_buckets_per_sec": round(len(buckets) / t_serial, 2),
        "parallel_buckets_per_sec": round(len(buckets) / t_par, 2),
        "speedup": round(t_serial / t_par, 2),
    }


def measure_native(tmp_dir: str, buckets, capacity: int) -> dict:
    """The native C++ featurizer (native/featurizer.cpp) vs the 27-31×
    vectorized Python path, hash mode at a given capacity.

    Banked here for the first time: the .so has BUILT since round 9 but
    was never benchmarked against the vectorized path it was written to
    beat.  Returns a skip-with-reason record when the library cannot be
    built on this host (the round-8 gcc-10 class of failure) — a missing
    number stated loudly beats a silently absent arm.
    """
    import subprocess

    build = subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                           capture_output=True, text=True, timeout=300)
    from deeprest_tpu.data.native import native_available

    if build.returncode != 0 or not native_available():
        reason = (build.stderr.strip().splitlines() or ["library absent"])[-1]
        return {"mode": "native", "capacity": capacity,
                "skipped": f"native ETL library unavailable: {reason[:200]}"}

    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace
    from deeprest_tpu.data.native import featurize_jsonl
    from deeprest_tpu.data.schema import save_raw_data_jsonl

    path = os.path.join(tmp_dir, f"native_bench_{capacity}.jsonl")
    save_raw_data_jsonl(buckets, path)
    cfg = FeaturizeConfig(hash_features=True, capacity=capacity)

    vec_space = CallPathSpace(config=cfg)

    def run_vec():
        for b in buckets:
            vec_space.extract(b.traces)

    run_vec()                               # warm the path→column memo
    t_vec = _time(run_vec)
    t_native = _time(lambda: featurize_jsonl(path, cfg,
                                             require_native=True))
    # Parity, not just speed: the native traffic matrix must match the
    # Python pipeline's bit-for-bit (shared FNV-1a golden vectors).
    got = featurize_jsonl(path, cfg, require_native=True).traffic
    ref = np.stack([CallPathSpace(config=cfg).extract(b.traces)
                    for b in buckets])
    np.testing.assert_array_equal(got, ref)
    n = len(buckets)
    return {
        "mode": "native",
        "capacity": capacity,
        "buckets": n,
        "spans": _spans(buckets),
        "vectorized_python_buckets_per_sec": round(n / t_vec, 2),
        "native_buckets_per_sec": round(n / t_native, 2),
        # >1: C++ wins.  The native path re-PARSES the JSONL inside the
        # timed region (it is a file-to-features pipeline) while the
        # Python arm walks pre-parsed span trees, so this is the honest
        # end-to-end comparison for cold corpora, stated as such.
        "speedup_vs_vectorized": round(t_vec / t_native, 2),
        "note": ("native arm times file→features (JSON parse included); "
                 "python arm times pre-parsed tree walks — the native "
                 "win is understated for cold JSONL corpora"),
    }


def measure_refresh_assembly(history: int, capacity: int,
                             num_metrics: int = 8) -> dict:
    """Retained-corpus assembly cost at refresh time, deque-era vs ring."""
    from collections import deque

    from deeprest_tpu.train.data import SeriesRing

    rng = np.random.default_rng(0)
    rows = rng.random((history, capacity)).astype(np.float32)
    names = [f"c{i}_cpu" for i in range(num_metrics)]
    dicts = [{n: float(rng.random()) for n in names} for _ in range(history)]

    old_traffic = deque(rows, maxlen=history)
    old_metrics = deque(dicts, maxlen=history)

    def assemble_old():
        traffic = np.stack(list(old_traffic))
        out = np.zeros((len(old_metrics), num_metrics), np.float32)
        pos = {n: i for i, n in enumerate(names)}
        for t, row in enumerate(old_metrics):
            for k, v in row.items():
                out[t, pos[k]] = v
        return traffic, out

    ring = SeriesRing(history, capacity)
    tring = SeriesRing(history, num_metrics)
    for r, d in zip(rows, dicts):
        ring.append_slot()[:] = r
        slot = tring.append_slot()
        for i, n in enumerate(names):
            slot[i] = d[n]

    def assemble_new():
        return ring.view(), tring.view()

    t_old = _time(assemble_old, min_s=0.1)
    t_new = _time(assemble_new, min_s=0.02)
    ref_t, ref_y = assemble_old()
    new_t, new_y = assemble_new()
    np.testing.assert_array_equal(ref_t, new_t)   # parity, not just speed
    np.testing.assert_array_equal(ref_y, new_y)
    return {
        "history": history,
        "capacity": capacity,
        "old_ms": round(t_old * 1e3, 3),
        "new_ms": round(t_new * 1e3, 6),
        "speedup": round(t_old / t_new, 1),
    }


def measure_overlap(tmp_dir: str, capacity: int = 512,
                    refreshes: int = 3) -> dict:
    """Train-thread ETL stall + refresh cadence, overlap off vs on."""
    import dataclasses

    # etl_bench is CPU-only by design; its record says "platform": "cpu".
    import jax

    jax.config.update("jax_platforms", "cpu")

    from deeprest_tpu.config import Config, EtlConfig, FeaturizeConfig, \
        ModelConfig, TrainConfig
    from deeprest_tpu.data.schema import save_raw_data_jsonl
    from deeprest_tpu.train.stream import (
        BucketTailer, StreamConfig, StreamingTrainer,
    )

    per_refresh = 40
    corpus = _corpus(per_refresh * (refreshes + 1), seed=3)
    path = os.path.join(tmp_dir, "etl_bench_stream.jsonl")
    save_raw_data_jsonl(corpus, path)

    def run_mode(overlap: bool) -> dict:
        cfg = Config(
            model=ModelConfig(feature_dim=capacity, hidden_size=8),
            train=TrainConfig(batch_size=8, window_size=6, seed=0,
                              eval_stride=1, eval_max_cycles=2,
                              log_every_steps=0),
            etl=EtlConfig(overlap=overlap),
        )
        st = StreamingTrainer(
            cfg, StreamConfig(refresh_buckets=per_refresh,
                              finetune_epochs=1, eval_holdout=2,
                              poll_interval_s=0.01),
            feature_config=FeaturizeConfig(hash_features=True,
                                           capacity=capacity))
        # Cap the poll size so the backlog arrives as a stream of batches
        # (one giant poll would leave nothing to overlap).
        tailer = BucketTailer(path, max_poll_bytes=1 << 18)
        gaps, stalls, lags = [], [], []
        t_prev = time.perf_counter()
        for r in st.run(tailer, max_refreshes=refreshes, deadline_s=600):
            now = time.perf_counter()
            gaps.append(now - t_prev)
            t_prev = now
            stalls.append(r.etl_stall_s)
            lags.append(r.etl_lag_buckets)
        tailer.close()
        return {
            "refresh_gap_s": [round(g, 3) for g in gaps],
            "etl_stall_s": [round(s, 4) for s in stalls],
            "etl_lag_buckets": lags,
            # First gap includes jit compile of the fine-tune step; the
            # steady-state comparison is the tail.
            "steady_stall_s": round(float(np.mean(stalls[1:]) if
                                          len(stalls) > 1 else stalls[0]), 4),
        }

    off = run_mode(False)
    on = run_mode(True)
    return {
        "capacity": capacity,
        "refresh_buckets": per_refresh,
        "overlap_off": off,
        "overlap_on": on,
        "stall_reduction": round(
            off["steady_stall_s"] / max(on["steady_stall_s"], 1e-9), 1),
    }


def quick_buckets_per_sec(buckets: int = 30) -> float:
    """Vectorized hash-mode featurization throughput at the flagship
    F=512 — bench.py's ``etl_buckets_per_sec`` headline key.  Numpy-only:
    never initializes a JAX backend (bench.py's parent process contract).
    """
    corpus = _corpus(buckets)
    return measure_featurize(corpus, F_FLAGSHIP)["vectorized_buckets_per_sec"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke: F=512 featurize + small "
                         "assembly; skips F=10240, the pool, and the "
                         "stream-overlap run")
    ap.add_argument("--out", default=None,
                    help="write the JSON here (default: stdout only; the "
                         "committed artifact is benchmarks/etl_bench.json)")
    args = ap.parse_args()

    result: dict = {
        "schema_version": 1,
        "metric": "host_etl",
        "platform": "cpu",
        "quick": bool(args.quick),
        "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if args.quick:
        corpus = _corpus(30)
        result["featurize"] = [measure_featurize(corpus, F_FLAGSHIP)]
        result["refresh_assembly"] = measure_refresh_assembly(
            history=512, capacity=F_FLAGSHIP)
    else:
        corpus = _corpus(150)
        result["featurize"] = [
            measure_featurize(corpus, F_FLAGSHIP),
            measure_featurize(corpus, F_10K),
            measure_featurize(corpus, 0, hash_mode=False),
        ]
        result["parallel"] = measure_parallel(corpus)
        result["refresh_assembly"] = measure_refresh_assembly(
            history=4096, capacity=F_FLAGSHIP)
        import tempfile

        with tempfile.TemporaryDirectory() as td:
            result["native"] = [measure_native(td, corpus, F_FLAGSHIP),
                                measure_native(td, corpus, F_10K)]
            result["overlap"] = measure_overlap(td)

    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
