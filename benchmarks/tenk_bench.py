#!/usr/bin/env python
"""10k-endpoint vertical benchmark: the sparse-first pipeline end to end.

ROADMAP item 4 asks for the F=10240 stress tier to be real everywhere,
with memory ceilings documented.  This bench runs the full vertical at
F=10240 — featurization throughput, ring ingest, host→device feed bytes,
train steps, serve rps, peak RSS — with the dense reference and the
sparse-first path (round 15: extract_sparse → SparseSeriesRing →
on-device densify, ops/densify.py) side by side.

Honest-measurement notes, in the repo's established style:

- BYTES and RSS are deterministic on this 1-core CPU container even
  where timing is contended; the byte table is the headline, the CPU
  steps/s and rps are plumbing proofs (not measured on the chip).
- The month-scale RSS is measured on the SPARSE retained corpus
  (43 200 rows = 30 days of minutes actually allocated and touched); the
  dense ring's bytes at that scale (~3.4 GiB) are reported
  arithmetically — deliberately NOT allocated by default so the bench
  runs inside CI memory budgets (``--dense-rss`` opts in).
- ``quick_tenk_stats`` is imported by bench.py for the schema-v9
  headline keys and must stay numpy-only (never initializes a JAX
  backend in the parent process).

``--quick`` runs the featurize + ring + bytes measurements at reduced
sizes in a few seconds — the tier-1 smoke (tests/test_tenk_bench.py).
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F_10K = 10240
NNZ_CAP = 64
WINDOW = 60
MONTH_ROWS = 30 * 24 * 60            # 30 days of minute buckets


def _peak_rss_mb() -> float:
    """Process high-water RSS in MiB (ru_maxrss is KiB on Linux)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _time(fn, min_s: float = 0.2) -> float:
    best = float("inf")
    spent = 0.0
    while spent < min_s or best == float("inf"):
        t0 = time.perf_counter()
        fn()
        dt = time.perf_counter() - t0
        best = min(best, dt)
        spent += dt
    return best


def _corpus(buckets: int, seed: int = 0):
    from deeprest_tpu.workload import normal_scenario, simulate_corpus

    scn = normal_scenario(seed)
    scn.calls_per_user = 0.4
    return simulate_corpus(scn, buckets)


def _synthetic_sparse_rows(rows: int, capacity: int, k: int, seed: int = 0):
    """Pre-generated (cols, vals) pairs shaped like real 10k-wide traffic
    (a handful of hot call paths per bucket) — used where walking real
    traces for every row would time the workload simulator, not the
    pipeline under test."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(rows):
        n = int(rng.integers(4, k // 2))
        cols = np.sort(rng.choice(capacity, size=n,
                                  replace=False)).astype(np.int32)
        vals = rng.integers(1, 200, size=n).astype(np.float32)
        out.append((cols, vals))
    return out


# -- measurements -----------------------------------------------------------


def measure_featurize(buckets, capacity: int = F_10K) -> dict:
    """Dense extract vs extract_sparse rows/sec at the 10k width, plus
    the bit-identity check the sparse path is contracted to."""
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace
    from deeprest_tpu.ops.densify import densify_rows

    cfg = FeaturizeConfig(hash_features=True, capacity=capacity)
    dense_space = CallPathSpace(config=cfg)
    sparse_space = CallPathSpace(config=cfg)

    def run_dense():
        for b in buckets:
            dense_space.extract(b.traces)

    def run_sparse():
        for b in buckets:
            sparse_space.extract_sparse(b.traces)

    run_sparse()                          # warm the shared path→col memo
    run_dense()
    t_dense = _time(run_dense)
    t_sparse = _time(run_sparse)
    cols, vals = sparse_space.extract_sparse(buckets[0].traces)
    np.testing.assert_array_equal(
        densify_rows(cols[None], vals[None], capacity)[0],
        dense_space.extract(buckets[0].traces))
    n = len(buckets)
    nnz = [len(sparse_space.extract_sparse(b.traces)[0]) for b in buckets]
    return {
        "capacity": capacity,
        "buckets": n,
        "dense_rows_per_sec": round(n / t_dense, 2),
        "sparse_rows_per_sec": round(n / t_sparse, 2),
        "speedup": round(t_dense / t_sparse, 2),
        "max_row_nnz": int(max(nnz)),
        "mean_row_nnz": round(float(np.mean(nnz)), 1),
    }


def measure_ring_ingest(rows: int, capacity: int = F_10K,
                        k: int = NNZ_CAP) -> dict:
    """Appends/sec and resident bytes: SparseSeriesRing vs SeriesRing at
    the 10k width (pre-featurized rows, so this times the rings)."""
    from deeprest_tpu.ops.densify import densify_rows
    from deeprest_tpu.train.data import SeriesRing, SparseSeriesRing

    sparse_rows = _synthetic_sparse_rows(rows, capacity, k)
    dense_rows = [densify_rows(c[None], v[None], capacity)[0]
                  for c, v in sparse_rows]
    sring = SparseSeriesRing(rows, capacity, k)
    dring = SeriesRing(rows, capacity)

    def ingest_sparse():
        for c, v in sparse_rows:
            sring.append_sparse(c, v)

    def ingest_dense():
        for r in dense_rows:
            dring.append_slot()[:] = r

    t_sparse = _time(ingest_sparse, min_s=0.05)
    t_dense = _time(ingest_dense, min_s=0.05)
    np.testing.assert_array_equal(sring.densify(), dring.view())
    dense_bytes = dring._buf.nbytes
    return {
        "rows": rows,
        "capacity": capacity,
        "nnz_cap": k,
        "sparse_appends_per_sec": round(rows / t_sparse, 1),
        "dense_appends_per_sec": round(rows / t_dense, 1),
        "sparse_ring_bytes": int(sring.nbytes),
        "dense_ring_bytes": int(dense_bytes),
        "ring_bytes_ratio": round(dense_bytes / sring.nbytes, 1),
    }


def feed_bytes_table(window: int = WINDOW, capacity: int = F_10K,
                     k: int = NNZ_CAP, month_rows: int = MONTH_ROWS) -> dict:
    """The headline host→device byte accounting (deterministic on any
    host): per-window page bytes and the one-time staged-base bytes, at
    the month scale."""
    dense_pw = window * capacity * 4                 # float32 window
    sparse_pw = window * k * (4 + 4)                 # int32 cols + f32 vals
    dense_base = month_rows * capacity * 4
    sparse_base = month_rows * k * 8 + month_rows * 4
    return {
        "window_size": window,
        "capacity": capacity,
        "nnz_cap": k,
        "month_rows": month_rows,
        "dense_bytes_per_window": dense_pw,
        "sparse_feed_bytes_per_window": sparse_pw,
        "bytes_per_window_ratio": round(dense_pw / sparse_pw, 1),
        "dense_staged_base_bytes": dense_base,
        "sparse_staged_base_bytes": sparse_base,
        "staged_base_ratio": round(dense_base / sparse_base, 1),
    }


def measure_month_rss(k: int = NNZ_CAP, capacity: int = F_10K,
                      rows: int = MONTH_ROWS,
                      dense_rss: bool = False) -> dict:
    """Peak RSS with a month-scale F=10240 SPARSE retained corpus
    actually resident (allocated AND touched); the dense ring at the same
    scale is reported arithmetically unless --dense-rss."""
    from deeprest_tpu.train.data import SeriesRing, SparseSeriesRing

    before_mb = _peak_rss_mb()
    ring = SparseSeriesRing(rows, capacity, k)
    for c, v in _synthetic_sparse_rows(min(rows, 2048), capacity, k):
        ring.append_sparse(c, v)
    # touch the full buffers so the RSS number is real, not lazily mapped
    cols_v, vals_v, _ = ring._cols._buf, ring._vals._buf, ring._nnz._buf
    cols_v[:] = cols_v
    vals_v[:] = vals_v
    out = {
        "rows": rows,
        "capacity": capacity,
        "nnz_cap": k,
        "sparse_ring_bytes": int(ring.nbytes),
        "peak_rss_mb_before": round(before_mb, 1),
        "peak_rss_mb_with_sparse_corpus": round(_peak_rss_mb(), 1),
        "dense_ring_bytes_computed": 2 * rows * capacity * 4,
        "dense_rss_measured": None,
    }
    if dense_rss:
        dring = SeriesRing(rows, capacity)
        dring._buf[:] = 1.0
        out["dense_rss_measured"] = round(_peak_rss_mb(), 1)
        del dring
    del ring
    return out


def measure_train(rows: int = 200, capacity: int = F_10K,
                  k: int = NNZ_CAP, steps_cap: int | None = None) -> dict:
    """Fine-tune steps/s at F=10240, sparse vs dense staged feed, loss
    parity asserted.  Honest CPU: 1 core, contended — the number proves
    the plumbing; not measured on the chip."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.data.featurize import FeaturizedData, CallPathSpace
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.ops.densify import densify_rows
    from deeprest_tpu.train.data import prepare_dataset
    from deeprest_tpu.train.trainer import Trainer

    sparse_rows = _synthetic_sparse_rows(rows, capacity, k, seed=1)
    traffic = np.zeros((rows, capacity), np.float32)
    for t, (c, v) in enumerate(sparse_rows):
        densify_rows(c[None], v[None], capacity, out=traffic[t:t + 1])
    rng = np.random.default_rng(2)
    space = CallPathSpace(config=FeaturizeConfig(hash_features=True,
                                                 capacity=capacity)).freeze()
    data = FeaturizedData(
        traffic=traffic,
        resources={"svc_cpu": rng.random(rows).astype(np.float32) * 50,
                   "svc_mem": rng.random(rows).astype(np.float32) * 8},
        invocations={"general": np.ones(rows, np.float32)},
        space=space)

    def run(sparse: bool):
        tc = TrainConfig(num_epochs=1, batch_size=8, window_size=12,
                         eval_stride=6, eval_max_cycles=2, seed=0,
                         log_every_steps=0, device_data="always",
                         sparse_feed=sparse, sparse_nnz_cap=k)
        cfg = Config(model=ModelConfig(hidden_size=16, dropout_rate=0.1),
                     train=tc)
        bundle = prepare_dataset(data, cfg.train)
        tr = Trainer(cfg, bundle.feature_dim, bundle.metric_names)
        st = tr.init_state(np.zeros((1, 12, capacity), np.float32))
        staged = tr.stage_dataset(bundle)
        erng = np.random.default_rng(0)
        st, _ = tr.train_epoch(st, bundle, erng, staged=staged)  # warm
        t0 = time.perf_counter()
        st, _ = tr.train_epoch(st, bundle, erng, staged=staged)
        # honest sync: the loss curve readback in train_epoch already
        # forced params; bank an updated-params element read explicitly
        float(np.asarray(jax.tree.leaves(st.params)[0]).ravel()[0])
        dt = time.perf_counter() - t0
        steps = len(tr._last_epoch_losses)
        return steps / dt, tr._last_epoch_losses.copy()

    sparse_sps, sparse_losses = run(True)
    dense_sps, dense_losses = run(False)
    np.testing.assert_array_equal(sparse_losses, dense_losses)
    return {
        "rows": rows,
        "capacity": capacity,
        "dense_steps_per_sec": round(dense_sps, 2),
        "sparse_steps_per_sec": round(sparse_sps, 2),
        "loss_parity": "bit-identical",
        "honest_cpu": ("1-core CPU: the scatter-densify competes with the "
                       "matmul for the same core, so sparse steps/s here "
                       "measures plumbing, not the chip; the feed-byte "
                       "table is the transferable number"),
    }


def measure_serve(capacity: int = F_10K, k: int = NNZ_CAP,
                  series_len: int = 120, n_series: int = 4) -> dict:
    """predict_series rps at F=10240, dense vs sparse entry, parity
    asserted (same honest-CPU caveat as measure_train)."""
    import jax

    jax.config.update("jax_platforms", "cpu")

    from deeprest_tpu.config import ModelConfig
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.models.qrnn import QuantileGRU
    from deeprest_tpu.ops.densify import densify_rows
    from deeprest_tpu.serve.predictor import Predictor

    w = 12
    mc = ModelConfig(feature_dim=capacity, num_metrics=3, hidden_size=16)
    params = dict(QuantileGRU(config=mc).init(
        jax.random.PRNGKey(0), np.zeros((1, w, capacity), np.float32))
        ["params"])
    sparse_rows = _synthetic_sparse_rows(series_len, capacity, k, seed=3)
    cols = np.zeros((series_len, k), np.int32)
    vals = np.zeros((series_len, k), np.float32)
    for t, (c, v) in enumerate(sparse_rows):
        cols[t, :len(c)] = c
        vals[t, :len(v)] = v
    dense = densify_rows(cols, vals, capacity)
    x_stats = MinMaxStats(min=np.zeros((1, capacity), np.float32),
                          max=np.maximum(dense.max(0, keepdims=True), 1.0)
                          .astype(np.float32))
    y_stats = MinMaxStats(min=np.zeros((1, 3), np.float32),
                          max=np.full((1, 3), 10.0, np.float32))
    names = ["a_cpu", "b_cpu", "c_usage"]
    dm = np.array([False, False, True])

    def build(sparse):
        return Predictor(params, mc, x_stats, y_stats, names, w,
                         delta_mask=dm, sparse_feed=sparse,
                         sparse_nnz_cap=k)

    pd, ps = build(False), build(True)
    ref = pd.predict_series(dense)
    got = ps.predict_series_sparse(cols, vals)
    np.testing.assert_array_equal(got, ref)

    t_dense = _time(lambda: [pd.predict_series(dense)
                             for _ in range(n_series)], min_s=0.3)
    t_sparse = _time(lambda: [ps.predict_series_sparse(cols, vals)
                              for _ in range(n_series)], min_s=0.3)
    return {
        "capacity": capacity,
        "series_len": series_len,
        "dense_series_per_sec": round(n_series / t_dense, 2),
        "sparse_series_per_sec": round(n_series / t_sparse, 2),
        "parity": "bit-identical (integrate + non-integrate)",
        "honest_cpu": "1-core CPU; see measure_train.honest_cpu",
    }


# -- bench.py quick hooks (numpy-only; parent-process contract) -------------


def quick_tenk_stats(buckets: int = 20) -> dict:
    """The schema-v9 headline keys for bench.py: 10k-width featurize
    throughput (rows/sec through extract_sparse) and the deterministic
    sparse-feed byte table.  Numpy-only — never initializes a JAX
    backend."""
    feat = measure_featurize(_corpus(buckets), F_10K)
    bytes_tbl = feed_bytes_table()
    return {
        "tenk_featurize_rows_per_sec": feat["sparse_rows_per_sec"],
        "sparse_feed_bytes_per_window":
            bytes_tbl["sparse_feed_bytes_per_window"],
        "dense_bytes_per_window": bytes_tbl["dense_bytes_per_window"],
        "bytes_per_window_ratio": bytes_tbl["bytes_per_window_ratio"],
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--quick", action="store_true",
                    help="seconds-scale smoke: featurize + ring + bytes "
                         "at reduced sizes; skips train/serve/month-RSS")
    ap.add_argument("--dense-rss", action="store_true",
                    help="ALSO allocate the month-scale dense ring "
                         "(~3.4 GiB) to measure its RSS directly")
    ap.add_argument("--out", default=None,
                    help="write the JSON here (committed artifact: "
                         "benchmarks/tenk_bench.json)")
    args = ap.parse_args()

    result: dict = {
        "schema_version": 1,
        "metric": "tenk_vertical",
        "platform": "cpu",
        "quick": bool(args.quick),
        "recorded_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    # month_rss runs FIRST: ru_maxrss is a process high-water mark, so
    # measuring the sparse corpus's residency after the dense-reference
    # arms (which deliberately allocate F-wide rings) would report their
    # peak, not the sparse corpus's.
    if args.quick:
        result["month_rss"] = measure_month_rss(rows=4096)
        corpus = _corpus(20)
        result["featurize"] = measure_featurize(corpus)
        result["ring_ingest"] = measure_ring_ingest(rows=256)
        result["feed_bytes"] = feed_bytes_table()
    else:
        result["month_rss"] = measure_month_rss(dense_rss=args.dense_rss)
        corpus = _corpus(100)
        result["featurize"] = measure_featurize(corpus)
        result["ring_ingest"] = measure_ring_ingest(rows=2048)
        result["feed_bytes"] = feed_bytes_table()
        result["train"] = measure_train()
        result["serve"] = measure_serve()
    result["tenk_peak_rss_mb"] = result["month_rss"][
        "peak_rss_mb_with_sparse_corpus"]
    # the whole-run high water (dense reference arms included), for scale
    result["process_peak_rss_mb"] = round(_peak_rss_mb(), 1)

    line = json.dumps(result)
    print(line)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
