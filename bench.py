#!/usr/bin/env python
"""Headline benchmark: trainer steps/sec on the flagship configuration.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

Configuration: the DeathStarBench-social-network scale from BASELINE.json
config 2 — 40 metric experts (8 components x 5 resources), 512 call-path
features, window 60, batch 32, hidden 128, bfloat16 matmuls.

One process for each chip: the orchestrating process never imports JAX.
All device work runs in child processes (`bench.py --measure`), one after
the other, each on the device JAX finds there; the record names that
device.  A phase that fails ends the run with a non-zero exit code: nothing
here changes backend or platform after a failure.  ``--cpu`` is the one
way to a CPU run, and it has to be asked for.

``vs_baseline`` is measured against the reference-equivalent PyTorch model
(benchmarks/baseline_torch.py) on this host's CPU — the reference publishes
no throughput numbers and no GPU is attached here (BASELINE.md).  That
anchor is honest but weak (CPU torch vs TPU jax is not the A100 ratio the
north star names), so the output labels it explicitly in ``anchor``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

B, T, F, E, H = 32, 60, 512, 40, 128
Q = 3                       # quantiles (.05, .50, .95)
F_10K = 10240               # the 10k-endpoint width (BASELINE.json configs[3])
BASELINE_CACHE = os.path.join(REPO, "bench_baseline.json")

# Peak bf16 TFLOP/s of one chip, keyed by device_kind substring.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB HBM at 819 GB/s.  A kind that is not in the table is an error,
# not a default: an MFU against a guessed peak is worse than none.
_CHIP_PEAK_TFLOPS = (
    ("v5 lite", 197.0),     # v5e, as jax.devices()[0].device_kind names it
    ("v5litepod", 197.0),
    ("v5e", 197.0),
)


def chip_peak_tflops(device_kind: str) -> float:
    kind = device_kind.lower()
    for key, peak in _CHIP_PEAK_TFLOPS:
        if key in kind:
            return peak
    raise ValueError(
        f"no published peak for device kind {device_kind!r}; add it to "
        "bench._CHIP_PEAK_TFLOPS with its source")


def train_step_tflops(batch, window, features, experts, hidden,
                      quantiles=Q, directions=2) -> float:
    """Analytic TFLOPs per training step (fwd+bwd ~= 3x fwd matmul FLOPs).

    Counts the three matmul families (a 2*M*N*K each): the hoisted input
    projections x @ W_ih, the T sequential h @ W_hh recurrence steps, and
    the quantile heads; mask/mixing/elementwise are negligible.
    """
    proj = 2 * batch * window * experts * features * 3 * hidden
    recur = 2 * batch * window * experts * hidden * 3 * hidden
    heads = 2 * batch * window * experts * (2 * directions * hidden) * quantiles
    fwd = directions * (proj + recur) + heads
    return 3 * fwd / 1e12

MEASURE_TIMEOUT_S = 2400     # flagship f32 CPU steps (--cpu) are ~7s each

# Measurement sizes.  A --cpu run uses fewer steps and f32 (bf16 is
# software-emulated on CPU, ~60s/step): it is a sanity anchor, not the
# headline, and its JSON labels the dtype honestly.
#
# Step counts are sized so the end-of-trial host readback (see
# measure_main) is amortized to <2% of the trial.
#
# grad_accum_G: the accumulation factor for the schema-v6
# coalesced_steps_per_sec measurement (G plan steps an update —
# TrainConfig.grad_accum_windows); a --cpu run uses 2 to bound its
# ~7 s/step trials.
FULL = {"warmup": 5, "steps": 100, "trials": 3, "dtype": "bfloat16",
        "superstep_S": 8, "grad_accum_G": 4}
LIGHT = {"warmup": 1, "steps": 3, "trials": 1, "dtype": "float32",
         "superstep_S": 2, "grad_accum_G": 2}
TENK = {"warmup": 2, "steps": 20, "trials": 2, "dtype": "bfloat16",
        "superstep_S": 8, "grad_accum_G": 4}

TORCH_STEPS, TORCH_WARMUP = 10, 2


# ---------------------------------------------------------------------------
# child: actually measure (runs with whatever backend the env selects)
# ---------------------------------------------------------------------------


def measure_main(light: bool, tenk: bool = False) -> None:
    import numpy as np

    import jax

    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.train import Trainer

    sizes = LIGHT if light else FULL
    if tenk:
        sizes = TENK
    feat = F_10K if tenk else F
    cfg = Config(
        model=ModelConfig(feature_dim=feat, num_metrics=E, hidden_size=H,
                          compute_dtype=sizes["dtype"]),
        train=TrainConfig(batch_size=B, window_size=T),
    )
    metric_names = [f"comp{i // 5}_res{i % 5}" for i in range(E)]
    trainer = Trainer(cfg, feat, metric_names)

    rng = np.random.default_rng(0)
    x = rng.random((B, T, feat), np.float32)
    y = rng.random((B, T, E), np.float32)
    w = np.ones((B,), np.float32)

    state = trainer.init_state(x)

    # Every trial ends with a host readback of an element of the UPDATED
    # params (sync_leaf below), which forces the whole step including the
    # optimizer update; the loss would not, being computed before the
    # update.  chip_smoke.py times the same steps closed by
    # jax.block_until_ready and by this readback and prints both; until S0
    # rebuilds this file around that finding, the readback stays.  Inputs
    # are staged on device ONCE: the headline is compute throughput with
    # data resident in HBM (what an input pipeline sustains in steady
    # state); the per-step host-feed cost is measured separately below and
    # reported as `host_feed_steps_per_sec`.
    import jax.numpy as jnp

    x_d, y_d, w_d = jnp.asarray(x), jnp.asarray(y), jnp.asarray(w)
    for _ in range(sizes["warmup"]):
        state, loss = trainer._train_step(state, x_d, y_d, w_d)
    lv = float(loss)                           # readback = real sync
    if not np.isfinite(lv):
        raise RuntimeError(f"non-finite bench loss {lv}")

    # Trial sync reads back an element of the UPDATED params, not the loss:
    # the loss is computed before the optimizer update inside the step, so a
    # loss readback would leave the final step's parameter update outside
    # the timed region (~1% flattering at 100 steps/trial).
    sync_leaf = lambda s: float(jnp.ravel(jax.tree.leaves(s.params)[0])[0])

    # SYNC GUARD (schema v6): timed_trial is the ONLY way a trial gets
    # timed, and it structurally ends in the updated-params readback
    # before the clock stops; the ledger is asserted against at the end
    # of the measurement so a timing loop that stops its clock at the
    # dispatch cannot come back silently.
    trial_ledger = {"started": 0, "synced": 0}

    def timed_trial(run, state):
        trial_ledger["started"] += 1
        t0 = time.perf_counter()
        state = run(state)
        v = sync_leaf(state)                   # updated-params readback
        elapsed = time.perf_counter() - t0
        if not np.isfinite(v):
            raise RuntimeError(f"non-finite params after timed trial ({v})")
        trial_ledger["synced"] += 1
        return elapsed, state

    loss_box = {}
    best = 0.0
    for _ in range(sizes["trials"]):
        def run_steps(st):
            for _ in range(sizes["steps"]):
                st, loss_box["loss"] = trainer._train_step(st, x_d, y_d, w_d)
            return st

        elapsed, state = timed_trial(run_steps, state)
        best = max(best, sizes["steps"] / elapsed)
    lv = float(loss_box["loss"])
    if not np.isfinite(lv):
        raise RuntimeError(f"non-finite bench loss {lv}")

    # PRODUCTION feed path (train_epoch's device-resident pipeline): the
    # normalized base series staged in HBM once, each step shipping only
    # [B] int32 start indices + weights.  Windows overlap W−1 of W rows,
    # so the old materialized-window shipping re-sent every row W times
    # (at F=10240 the one July 2026 builder run read host_feed 0.087 vs
    # 17.7 staged steps/s; not reproduced on this toolchain).
    # Reported as indexed_feed_steps_per_sec — a NEW key, so that
    # host_feed_steps_per_sec keeps its historical meaning (fresh window
    # tensors shipped host→device every step, the upper-bound cost when
    # data CANNOT stage) and cross-round comparisons stay apples-to-apples
    # (round-5 ADVICE low #1: the round-5 output silently repurposed the
    # old key; schema_version 2 marks the fix).
    base_len = 512 + T
    xb_host = rng.random((base_len, feat), np.float32)
    if sizes["dtype"] == "bfloat16":
        import ml_dtypes

        xb_host = xb_host.astype(ml_dtypes.bfloat16)
    x_base = jnp.asarray(xb_host)
    y_base = jnp.asarray(rng.random((base_len, E), np.float32))
    host_steps = max(3, sizes["steps"] // 10)
    starts_pool = rng.integers(0, base_len - T,
                               size=(host_steps + 2, B)).astype(np.int32)
    for i in range(2):                                  # compile + warm
        state, loss = trainer._train_step_indexed(
            state, x_base, y_base, starts_pool[i], w)
    _ = sync_leaf(state)

    def run_indexed(st):
        for i in range(host_steps):
            st, _l = trainer._train_step_indexed(
                st, x_base, y_base, starts_pool[2 + i], w)
        return st

    elapsed, state = timed_trial(run_indexed, state)
    indexed_sps = host_steps / elapsed

    # Fused superstep path (train_epoch's dispatch-amortized driver,
    # schema v3 key): the SAME staged base series, but S steps scanned
    # inside one donated jit call over a device-resident [C, S, B] plan —
    # isolates what removing per-step Python dispatch, per-step index
    # shipping, and per-step readback opportunities buys over the indexed
    # per-step loop measured above.
    S = sizes["superstep_S"]
    ss_chunks = 2
    plan_shape = (ss_chunks + 1, S, B)
    sp_d = jnp.asarray(rng.integers(0, base_len - T,
                                    size=plan_shape).astype(np.int32))
    wp_d = jnp.asarray(np.ones(plan_shape, np.float32))
    state, _ss = trainer._superstep(state, x_base, y_base,
                                    sp_d, wp_d, 0)       # compile + warm
    _ = sync_leaf(state)

    def run_superstep(st):
        for c in range(1, ss_chunks + 1):
            st, _l = trainer._superstep(st, x_base, y_base, sp_d, wp_d, c)
        return st

    elapsed, state = timed_trial(run_superstep, state)
    superstep_sps = ss_chunks * S / elapsed

    # Accumulation superstep (schema v6): G consecutive plan steps run
    # forward and backward each and feed ONE optimizer update
    # (TrainConfig.grad_accum_windows).  A second Trainer is needed
    # because G is a plan-shape static.
    accum_g = sizes["grad_accum_G"]
    import dataclasses as _dc

    cfg_c = cfg.replace(
        train=_dc.replace(cfg.train, grad_accum_windows=accum_g))
    trainer_c = Trainer(cfg_c, feat, metric_names)
    state_c = trainer_c.init_state(x)
    s_c = max(accum_g, (S // accum_g) * accum_g)
    plan_c = (ss_chunks + 1, s_c, B)
    sp_c = jnp.asarray(rng.integers(0, base_len - T,
                                    size=plan_c).astype(np.int32))
    wp_c = jnp.asarray(np.ones(plan_c, np.float32))
    state_c, _ = trainer_c._accum_superstep(state_c, x_base, y_base,
                                            sp_c, wp_c, 0)   # compile
    _ = sync_leaf(state_c)

    def run_coalesced(st):
        for c in range(1, ss_chunks + 1):
            st, _l = trainer_c._accum_superstep(st, x_base, y_base,
                                                sp_c, wp_c, c)
        return st

    elapsed, state_c = timed_trial(run_coalesced, state_c)
    coalesced_sps = ss_chunks * s_c / elapsed     # microbatch steps/s

    # Historical host-feed path: fresh numpy window tensors shipped
    # host->device every step (what a corpus too big to stage pays).
    def run_host_feed(st):
        for _ in range(host_steps):
            st, _l = trainer._train_step(st, x, y, w)
        return st

    elapsed, state = timed_trial(run_host_feed, state)
    host_sps = host_steps / elapsed
    # Every timed trial closed with its updated-params readback — the
    # sync assertion the v6 schema promises.
    expected_trials = sizes["trials"] + 4
    assert (trial_ledger["started"] == trial_ledger["synced"]
            == expected_trials), (trial_ledger, expected_trials)
    dev = jax.devices()[0]
    out = {
        "steps_per_sec": best,
        "indexed_feed_steps_per_sec": indexed_sps,
        "superstep_steps_per_sec": superstep_sps,
        "superstep_S": S,
        "coalesced_steps_per_sec": coalesced_sps,
        "grad_accum_G": accum_g,
        "recurrence_rows": B,
        "host_feed_steps_per_sec": host_sps,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "n_devices": len(jax.devices()),
        "dtype": sizes["dtype"],
        "shape": {"B": B, "T": T, "F": feat, "E": E, "H": H},
    }
    # Exact device-state footprint (params + Adam moments + step/rng),
    # from array metadata, beside the live counters where the backend
    # reports them (the CPU's memory_stats() is None).
    out["model_state_bytes"] = int(sum(
        leaf.nbytes for leaf in jax.tree.leaves((state.params, state.opt_state))
    ))
    stats = dev.memory_stats()
    if stats and stats.get("bytes_in_use"):
        out["hbm_bytes_in_use"] = int(stats["bytes_in_use"])
        out["hbm_peak_bytes"] = int(
            stats.get("peak_bytes_in_use", stats["bytes_in_use"]))
    print(json.dumps(out))


# ---------------------------------------------------------------------------
# parent: orchestrate child processes, never touch a backend
# ---------------------------------------------------------------------------


def _last_json_line(stdout: str) -> dict | None:
    for line in reversed(stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def _run_script(argv: list[str], env_overrides: dict[str, str],
                timeout_s: float) -> dict:
    """Run one child to its end and return the last JSON line it printed.
    A non-zero exit or no record raises: a failed phase fails the bench."""
    proc = subprocess.run(
        [sys.executable, *argv], capture_output=True, text=True,
        timeout=timeout_s, env={**os.environ, **env_overrides}, cwd=REPO,
    )
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout or "").strip().splitlines()[-8:]
        raise RuntimeError(f"{' '.join(argv)}: rc={proc.returncode}: "
                           + " | ".join(tail))
    record = _last_json_line(proc.stdout)
    if record is None:
        raise RuntimeError(f"{' '.join(argv)}: child produced no JSON line")
    return record


def _measure(extra_args: list[str], cpu: bool) -> dict:
    """One ``bench.py --measure`` child on the device JAX finds there, or on
    the CPU when ``--cpu`` asked for it."""
    return _run_script(
        [os.path.abspath(__file__), "--measure", *extra_args],
        {"JAX_PLATFORMS": "cpu"} if cpu else {}, MEASURE_TIMEOUT_S)


def _cpu_headline(script: str, keys: tuple[str, ...]) -> dict:
    """The ``--quick --headline`` record of one benchmarks/ script.  These
    are CPU-only by design (host-path counts and parity gates) and their
    records say so; ``keys`` must all be present."""
    record = _run_script(
        [os.path.join(REPO, "benchmarks", script), "--quick", "--headline"],
        {"JAX_PLATFORMS": "cpu"}, 900)
    missing = [k for k in keys if k not in record]
    if missing:
        raise RuntimeError(f"{script}: headline record lacks {missing}")
    return record


def _committed(name: str) -> dict:
    with open(os.path.join(REPO, "benchmarks", name), encoding="utf-8") as f:
        return json.load(f)


def torch_baseline_steps_per_sec() -> float:
    cache_key = [B, T, F, E, H, TORCH_STEPS]
    if os.path.exists(BASELINE_CACHE):
        with open(BASELINE_CACHE, encoding="utf-8") as f:
            cached = json.load(f)
        if cached.get("config") == cache_key:
            return float(cached["torch_cpu_steps_per_sec"])

    from benchmarks.baseline_torch import measure_steps_per_sec

    sps = measure_steps_per_sec(batch=B, window=T, num_features=F,
                                num_metrics=E, hidden=H,
                                steps=TORCH_STEPS, warmup=TORCH_WARMUP)
    try:
        with open(BASELINE_CACHE, "w", encoding="utf-8") as f:
            json.dump({"config": cache_key,
                       "torch_cpu_steps_per_sec": sps,
                       "note": "reference-equivalent torch model, this host's"
                               f" CPU, {TORCH_STEPS} measured steps"},
                      f, indent=2)
    except OSError:
        pass
    return sps


def _pallas_proof() -> dict:
    """On an accelerator, record pallas-vs-scan numerics + speedup
    (benchmarks/pallas_tpu_check.py; it exits non-zero on a numerics
    failure, and that fails the bench)."""
    out_path = os.path.join(REPO, "benchmarks", "pallas_tpu_result.json")
    return _run_script(
        [os.path.join(REPO, "benchmarks", "pallas_tpu_check.py"),
         "--out", out_path], {}, 600)


def _mfu_block(measured: dict, features: int) -> dict:
    """Absolute perf anchor: analytic TFLOPs/step × measured steps/s vs the
    chip's public peak.  Raises for a device kind with no published peak
    (chip_peak_tflops), a CPU among them."""
    sps = float(measured["steps_per_sec"])
    step_tflops = train_step_tflops(B, T, features, E, H)
    sustained = step_tflops * sps
    peak = chip_peak_tflops(measured["device_kind"])
    block = {
        "analytic_tflops_per_step": round(step_tflops, 4),
        "sustained_tflops": round(sustained, 2),
        "chip": measured["device_kind"],
        "chip_peak_bf16_tflops": peak,
        "mfu_pct": round(100 * sustained / peak, 2),
    }
    for k in ("model_state_bytes", "hbm_bytes_in_use", "hbm_peak_bytes"):
        if k in measured:
            block[k] = measured[k]
    if "indexed_feed_steps_per_sec" in measured:
        # The production pipeline: base series staged in HBM, per-step
        # host traffic = [B] start indices (train_epoch's device-resident
        # path).  host_feed keeps its historical meaning: the no-staging
        # upper bound (fresh window tensors shipped every step).
        block["indexed_feed_steps_per_sec"] = round(
            float(measured["indexed_feed_steps_per_sec"]), 3)
    if "superstep_steps_per_sec" in measured:
        # Fused multi-step dispatch (schema v3, NEW key): S train steps
        # lax.scan-ned inside one donated jit call over the device-
        # resident epoch plan — the production epoch driver when data is
        # staged (benchmarks/superstep_sweep.py has the full S sweep).
        block["superstep_steps_per_sec"] = round(
            float(measured["superstep_steps_per_sec"]), 3)
        block["superstep_S"] = measured.get("superstep_S")
    if measured.get("coalesced_steps_per_sec") is not None:
        # Accumulation superstep (schema v6 keys): G plan steps per
        # optimizer update (TrainConfig.grad_accum_windows).  Rate is in
        # MICROBATCH steps/s — directly comparable to
        # superstep_steps_per_sec at the same shape.
        block["coalesced_steps_per_sec"] = round(
            float(measured["coalesced_steps_per_sec"]), 3)
        block["grad_accum_G"] = measured.get("grad_accum_G")
        block["recurrence_rows"] = measured.get("recurrence_rows")
    if "host_feed_steps_per_sec" in measured:
        block["host_feed_steps_per_sec"] = round(
            float(measured["host_feed_steps_per_sec"]), 3)
    return block


def _git_sha() -> str | None:
    try:
        # --dirty: a snapshot measured from an uncommitted tree must not be
        # attributed to the clean HEAD commit (it would send a bisecting
        # maintainer to code that did not produce the number).
        out = subprocess.run(["git", "describe", "--always", "--dirty"],
                             capture_output=True, text=True, cwd=REPO,
                             timeout=10)
        return out.stdout.strip() or None
    except Exception:
        return None


def main() -> None:
    from deeprest_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()      # never imports JAX; the children inherit
    cpu = "--cpu" in sys.argv
    measured = _measure(["--light"] if cpu else [], cpu)
    jax_sps = float(measured["steps_per_sec"])
    platform = measured["platform"]
    torch_sps = torch_baseline_steps_per_sec()

    # Host-ETL headline (schema v4): vectorized hash-mode featurization
    # throughput at the flagship F=512 on this host's CPU — numpy-only, so
    # the parent's never-touch-a-backend contract holds.
    from benchmarks.etl_bench import quick_buckets_per_sec

    etl_bps = quick_buckets_per_sec()

    # 10k-endpoint sparse-first headline (schema v9): F=10240 featurize
    # throughput through extract_sparse plus the deterministic host→device
    # feed-byte table, numpy-only in the parent.  tenk_peak_rss_mb comes
    # from the committed full-vertical dossier (benchmarks/tenk_bench.json).
    # Not measured on the chip.
    from benchmarks.tenk_bench import quick_tenk_stats

    tenk_stats = quick_tenk_stats()
    tenk_rss = _committed("tenk_bench.json").get("tenk_peak_rss_mb")

    # The serving-side headlines (schema v5, v8, v10, v12, v13) are each a
    # CPU child's --quick --headline record: counts, parity envelopes and
    # overhead ratios of the host path, labelled as CPU by their scripts.
    rolled_wps = float(_cpu_headline(
        "infer_bench.py", ("rolled_windows_per_sec",)
    )["rolled_windows_per_sec"])
    obs_overhead = float(_cpu_headline(
        "obs_bench.py", ("obs_overhead_pct",))["obs_overhead_pct"])
    drift = _cpu_headline("drift_bench.py", ("drift_detection_sweeps",
                                             "drift_overhead_pct"))
    drift_detection = float(drift["drift_detection_sweeps"])
    drift_overhead = float(drift["drift_overhead_pct"])
    whatif_rps = float(_cpu_headline(
        "whatif_bench.py", ("whatif_surface_rps",))["whatif_surface_rps"])
    quant = _cpu_headline("quant_bench.py", ("quant_weight_bytes",
                                             "quant_parity_max"))
    quant_bytes = int(quant["quant_weight_bytes"])
    quant_parity = float(quant["quant_parity_max"])

    # Fleet (v14), wire (v15) and elastic-remesh (v11) headlines are read
    # from the committed full-run dossiers: each storm owns its own
    # wall-time budget and asserts its own gates.
    _fleet = _committed("fleet_bench.json")
    fleet_apps = int(_fleet["ledger"]["apps"])
    fleet_cold = float(_fleet["aot"]["aot_cold_start_ms"])
    fleet_restore = float(_fleet["churn"]["restore_ms_median"])
    _wire = _committed("wire_bench.json")["throughput"]
    wire_sps = float(_wire["wire_spans_per_sec"])
    wire_p99 = float(_wire["p99_ingest_ms"])
    remesh_recovery = (_committed("chaos_bench.json")["arms"]["elastic"]
                       ["max_recovery_s"])

    # A --cpu run has no published peak to be held against: no MFU block.
    perf = None if cpu else _mfu_block(measured, F)
    result = {
        # v15: the wire-ingestion tier adds wire_spans_per_sec (sustained
        # socket->ring spans/sec through the warm memoized push path at
        # F=10240 sparse, from the committed benchmarks/wire_bench.json
        # full run, whose own gates assert the >=10x wire-vs-tailer bar,
        # the overload drop/backpressure accounting identity, and
        # wire-vs-tailer training bit-parity with zero post-warmup
        # compiles) and wire_p99_ingest_ms (drain-side p99 frame
        # featurized -> drained-into-ring latency from the receiver's
        # own histogram) — NEW keys only; every v14 key keeps its
        # meaning.
        # v14: the fleet tier adds fleet_apps (synthetic apps served
        # through ONE fused-executable plane in the committed
        # benchmarks/fleet_bench.json full run), fleet_cold_start_ms
        # (AOT deserialize + first dispatch on a fresh engine, vs
        # compile-from-scratch in the dossier), and
        # fleet_spill_restore_ms (median host->device restore of an
        # LRU-evicted tenant's weight tree during the churn storm) —
        # NEW keys only; every v13 key keeps its meaning.
        # v13: the quantized serving tier adds quant_weight_bytes (the
        # int8 serving weight-tree bytes on the quick world —
        # benchmarks/quant_bench.py; the committed quant_bench.json
        # asserts the >=3.5x f32/int8 byte ratio) and quant_parity_max
        # (the worst measured parity-envelope cell vs the f32 reference,
        # enforced at every load) — NEW keys only; every v12 key keeps
        # its meaning.
        # v12: whatif_surface_rps is the what-if capacity-surface
        # headline (cached interpolated /v1/whatif reads per second at
        # concurrency 16 on the quick real-pipeline world —
        # benchmarks/whatif_bench.py; the committed whatif_bench.json
        # asserts the >=50x cached-vs-direct ratio, the interpolation
        # parity envelope, and zero post-warmup compiles) — a NEW key
        # only; every v11 key keeps its meaning.
        # v11: remesh_recovery_s is the elastic-remeshing recovery
        # headline (worst detect->rebuild->restore wall seconds from the
        # committed chaos_bench.json elastic arm, whose own gates pin
        # bit-identical-to-restart-resume params, executables flat
        # across remeshes, and a zero-leak census incl. live device
        # buffers) — a NEW key only; every v10 key keeps its meaning.
        # v10: the model-quality observability tier adds
        # drift_detection_sweeps (windows-to-flag on the quick
        # topology-shift corpus — benchmarks/drift_bench.py detection
        # arm) and drift_overhead_pct (the quality monitors' serve/train
        # overhead, budgeted with obs_overhead_pct under the same <=3%)
        # — NEW keys only; every v9 key keeps its meaning.
        # v9: the sparse-first 10k-endpoint tier adds
        # sparse_feed_bytes_per_window (padded-COO [W,K] page bytes; the
        # dense [W,F] float32 twin rides in tenk_feed for the ratio),
        # tenk_featurize_rows_per_sec (extract_sparse throughput at
        # F=10240), and tenk_peak_rss_mb (month-scale sparse-corpus
        # residency from the committed benchmarks/tenk_bench.json) — NEW
        # keys only; every v8 key keeps its meaning.
        # v8: obs_overhead_pct is the observability-enabled overhead on
        # the serve+train hot paths (deeprest_tpu/obs; the committed
        # benchmarks/obs_bench.json asserts the 3% budget in full mode)
        # — a NEW key, nothing repurposed; every v7 key keeps its
        # meaning.
        # v7: the measured multi-chip tier (bench.py --mesh /
        # benchmarks/multichip_sweep.py, dossier MULTICHIP_r06.json) adds
        # mesh_shape, multichip_steps_per_sec, scaling_efficiency, and
        # flagship_mfu — NEW keys, emitted by the mesh mode's record;
        # every v6 key of this headline record keeps its meaning, and the
        # mesh sweep's timed trials carry the same asserted
        # updated-params-readback ledger.
        # v6: coalesced_steps_per_sec (+ grad_accum_G, recurrence_rows) is
        # the accumulation superstep — G plan steps an optimizer update,
        # B recurrence rows per matmul since PR 28 — and every
        # timed trial is now ASSERTED to end in an updated-params readback
        # (the honest-sync ledger in measure_main), so the round-2
        # dispatch-rate bug class cannot regress silently.  NEW keys only;
        # every v5 key keeps its meaning.
        # v5: rolled_windows_per_sec is the fused rolled-inference serving
        # headline — a NEW key, nothing repurposed; every v4 key keeps its
        # meaning.
        # v4: etl_buckets_per_sec is the host-ETL featurization headline —
        # a NEW key, nothing repurposed; every v3 key keeps its meaning.
        # v3: superstep_steps_per_sec (+ superstep_S) is the fused
        # multi-step dispatch driver — a NEW key, nothing repurposed
        # (per round-5 ADVICE); every v2 key keeps its meaning.
        # v2: indexed_feed_steps_per_sec is the staged index-gather feed
        # (new key); host_feed_steps_per_sec regained its pre-round-5
        # meaning (fresh windows shipped every step); vs_baseline moved
        # under footnotes (round-5 ADVICE low #1 / VERDICT weak #5).
        "schema_version": 15,
        "metric": "train_steps_per_sec",
        "value": round(jax_sps, 3),
        "unit": f"steps/s ({platform}; B={B} T={T} F={F} E={E} H={H}, "
                f"{measured.get('dtype', 'bfloat16')})",
        # The absolute anchor is `perf` (sustained TFLOP/s + MFU vs the
        # chip's public bf16 peak).  The A100 ratio the north star names is
        # explicitly unmeasurable here — no GPU is attached to this host —
        # and saying so beats publishing a number that invites misreading.
        "perf": perf,
        "a100_ratio": "unmeasurable on this host (no GPU attached; "
                      "use perf.mfu_pct as the absolute anchor)",
        # The torch-CPU ratio measures nothing the north star cares about:
        # a footnote, not a headline field.
        "footnotes": {
            "vs_baseline": (round(jax_sps / torch_sps, 3)
                            if torch_sps > 0 else None),
            "torch_cpu_anchor": (
                f"vs_baseline is torch-CPU ({torch_sps:.4f} steps/s over "
                f"{TORCH_STEPS} steps, reference-equivalent model) — the "
                "reference publishes no throughput and no GPU exists on "
                "this host; use perf.mfu_pct as the absolute anchor"),
        },
        "measurement_note": (
            "Every trial ends with a host readback of an updated-params "
            "element and inputs are staged in HBM once; the "
            "separately-reported "
            "indexed_feed_steps_per_sec covers the production feed path "
            "(device-resident base series, per-step index shipping) and "
            "host_feed_steps_per_sec the no-staging upper bound (fresh "
            "window tensors shipped every step — the key's historical "
            "meaning)."),
    }
    result.update({
        "device": {"platform": platform,
                   "kind": measured["device_kind"],
                   "count": measured["n_devices"]},
        "git_sha": _git_sha(),
        "etl_buckets_per_sec": round(float(etl_bps), 2),
        "sparse_feed_bytes_per_window": int(
            tenk_stats["sparse_feed_bytes_per_window"]),
        "tenk_featurize_rows_per_sec": round(
            float(tenk_stats["tenk_featurize_rows_per_sec"]), 2),
        "tenk_feed": {
            "dense_bytes_per_window": int(
                tenk_stats["dense_bytes_per_window"]),
            "bytes_per_window_ratio": float(
                tenk_stats["bytes_per_window_ratio"]),
        },
        "rolled_windows_per_sec": round(rolled_wps, 1),
        "obs_overhead_pct": round(obs_overhead, 3),
        "drift_detection_sweeps": round(drift_detection, 2),
        "drift_overhead_pct": round(drift_overhead, 3),
        "remesh_recovery_s": round(float(remesh_recovery), 4),
        "whatif_surface_rps": round(whatif_rps, 1),
        "quant_weight_bytes": quant_bytes,
        "quant_parity_max": quant_parity,
        "fleet_apps": fleet_apps,
        "fleet_cold_start_ms": fleet_cold,
        "fleet_spill_restore_ms": fleet_restore,
        "wire_spans_per_sec": round(wire_sps, 1),
        "wire_p99_ingest_ms": round(wire_p99, 3),
    })
    if tenk_rss is not None:
        result["tenk_peak_rss_mb"] = float(tenk_rss)

    # 10k-endpoint config (BASELINE.json configs[3]): single-chip step time
    # + HBM at F=10240, and the kernel proof.  Only on the accelerator:
    # both children run after the headline child has released the chip.
    if not cpu:
        tenk = _measure(["--tenk"], cpu=False)
        result["tenk_endpoint"] = {
            "steps_per_sec": round(float(tenk["steps_per_sec"]), 3),
            "shape": tenk.get("shape"),
            "dtype": tenk.get("dtype"),
            **_mfu_block(tenk, F_10K),
        }
        result["pallas_tpu"] = _pallas_proof()
    print(json.dumps(result))


def mesh_main() -> None:
    """``bench.py --mesh``: the multi-chip tier (schema v7).

    Orchestration only — the parent never imports JAX, and
    ``benchmarks/multichip_sweep.py`` is its one child, so the chips have
    one owner.  The sweep runs on the devices JAX finds there;
    ``--virtual`` (8 virtual CPU devices, what MULTICHIP_r06.json
    commits: it proves plumbing, not speed) has to be asked for.
    """
    from deeprest_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    out_path = os.path.join(REPO, "MULTICHIP_r06.json")
    if "--out" in sys.argv:
        out_path = sys.argv[sys.argv.index("--out") + 1]
    child = [os.path.join(REPO, "benchmarks", "multichip_sweep.py"),
             "--out", out_path,
             *(f for f in ("--virtual", "--quick") if f in sys.argv)]
    print(json.dumps(_run_script(child, {}, 3600)))


if __name__ == "__main__":
    if "--measure" in sys.argv:
        measure_main(light="--light" in sys.argv, tenk="--tenk" in sys.argv)
    elif "--mesh" in sys.argv:
        mesh_main()
    else:
        main()
