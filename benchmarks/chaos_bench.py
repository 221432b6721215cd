"""Chaos storm gate: kill replicas under live HTTP load and prove the
plane degrades honestly (fast 429/503, never a hang, never a wrong
answer) and heals itself (ejected workers reboot and rejoin).

Two storm arms, one per replica kind:

- **process** — N worker-subprocess replicas behind the routing front;
  a killer thread SIGKILLs a random live worker on a schedule while
  closed-loop HTTP clients hammer ``/v1/predict``.  The per-request
  deadline + typed ``ReplicaDeadError`` turn each kill into (at most)
  one retried request; the background probe reboots the corpse and
  rejoins it.
- **thread** — N in-process replicas; the chaos schedule calls
  ``router.eject()`` (in-process stacks cannot die separately from the
  plane, so ejection IS their failure mode) and the probe rejoins them.

Gates (asserted, and recorded in the committed
``benchmarks/chaos_bench.json`` — ``make chaos-bench``):

- **zero wrong answers**: every 200 body is byte-identical to the
  healthy plane's answer (predictions are pure; a retried request must
  reproduce them exactly).
- **bounded error budget**: every non-200 is a fast 429/503 — no other
  status, and no request's wall time past the stated deadline envelope.
- **self-healing**: ejections AND rejoins both observed; full recovery
  (every replica live) within the recovery envelope after the storm.
- **zero leaks**: post-storm thread/child-process/fd census returns to
  the pre-plane baseline (the plane starts lint-clean — RS001/RS002
  prove the code SHAPE; this proves the runtime).

Honest-CPU note: every replica shares one host core here, so
throughput/latency numbers are plumbing proofs; worker reboot time is
dominated by the child's jax import (~5-15 s cold).  Not measured on the
chip.
"""

from __future__ import annotations

import argparse
import json
import multiprocessing
import os
import random
import signal
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

F, E, H, W = 6, 3, 8, 8


def build_tiny(scale: float = 1.0, ladder=(8,), delay_s: float = 0.0):
    """Factory for both the parent reference stack and the worker
    subprocesses (spec ``factory: chaos_bench:build_tiny``).  A fixed
    ``delay_s`` per predict gives the killer a window to land SIGKILLs
    MID-request."""
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
    if scale != 1.0:
        params = jax.tree.map(lambda a: a * scale, params)
    pred = Predictor(
        params, mc,
        x_stats=MinMaxStats(min=np.float32(0.0), max=np.float32(1.0)),
        y_stats=MinMaxStats(min=np.zeros((E,), np.float32),
                            max=np.ones((E,), np.float32)),
        metric_names=[f"c{i}_cpu" for i in range(E)],
        window_size=W, ladder=tuple(ladder))
    if delay_s:
        class _Slow:
            def __init__(self, inner):
                self._inner = inner

            def __getattr__(self, name):
                return getattr(self._inner, name)

            def predict_series(self, traffic, integrate=True):
                time.sleep(delay_s)
                return self._inner.predict_series(traffic,
                                                  integrate=integrate)

            def predict_series_many(self, series_list, integrate=True):
                time.sleep(delay_s)
                return self._inner.predict_series_many(
                    series_list, integrate=integrate)

        return _Slow(pred)
    return pred


def _noop():
    pass


def _warm_multiprocessing() -> None:
    """Start+reap one throwaway spawn process BEFORE any baseline
    census: the first spawn in a process initializes one-time singletons
    (the resource-tracker daemon and its pipe fd) that would otherwise
    read as a storm 'leak' when they are process-lifetime machinery."""
    ctx = multiprocessing.get_context("spawn")
    p = ctx.Process(target=_noop)
    p.start()
    p.join(timeout=60)
    try:
        p.close()
    except ValueError:
        pass


def _census() -> dict:
    import gc

    gc.collect()     # drop cycles so the device-buffer count is honest
    for _ in multiprocessing.active_children():   # reaps exited workers
        pass
    # Live DEVICE buffers join the census (round 20): a remesh that
    # strands old-mesh arrays — or a closed plane whose predictor stacks
    # stay referenced — leaks HBM the thread/fd census cannot see (the
    # round-17 fd audit caught a real Popen-sentinel leak; device memory
    # gets the same treatment).
    try:
        import jax

        buffers = len(jax.live_arrays())
    except Exception:
        buffers = 0
    return {
        "threads": threading.active_count(),
        "children": len(multiprocessing.active_children()),
        "fds": len(os.listdir("/proc/self/fd")),
        "device_buffers": buffers,
    }


def _settled_census(baseline: dict, timeout_s: float = 15.0) -> dict:
    """Post-storm census with a settle loop: batcher workers, HTTP
    handler threads, SIGCHLD reaping, and device-buffer frees all finish
    asynchronously after close() — poll until the counts return to
    baseline (or report the stuck values)."""
    deadline = time.monotonic() + timeout_s
    while True:
        now = _census()
        clean = (now["threads"] <= baseline["threads"]
                 and now["children"] <= baseline["children"]
                 and now["fds"] <= baseline["fds"]
                 and now["device_buffers"] <= baseline["device_buffers"])
        if clean or time.monotonic() > deadline:
            return {"before": baseline, "after": now, "clean": clean}
        time.sleep(0.2)


class _LoadStats:
    def __init__(self):
        self.lock = threading.Lock()
        self.ok = 0
        self.http_429 = 0
        self.http_503 = 0
        self.other_status = 0
        self.wrong_answers = 0
        self.walls: list[float] = []


def _client_loop(address, payload, reference, stop, stats: _LoadStats):
    import http.client

    while not stop.is_set():
        t0 = time.monotonic()
        try:
            conn = http.client.HTTPConnection(*address, timeout=120)
            conn.request("POST", "/v1/predict", body=payload,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = resp.read()
            status = resp.status
            conn.close()
        except OSError:
            # connection-level failure = the hang/drop class the gate
            # forbids (the server must always answer)
            status, body = -1, b""
        wall = time.monotonic() - t0
        with stats.lock:
            stats.walls.append(wall)
            if status == 200:
                preds = json.loads(body)["predictions"]
                if preds == reference:
                    stats.ok += 1
                else:
                    stats.wrong_answers += 1
            elif status == 429:
                stats.http_429 += 1
            elif status == 503:
                stats.http_503 += 1
            else:
                stats.other_status += 1


def _pct(sorted_vals, p):
    if not sorted_vals:
        return None
    k = min(len(sorted_vals) - 1,
            int(round(p / 100 * (len(sorted_vals) - 1))))
    return sorted_vals[k]


def _await_recovery(router, n, timeout_s: float) -> float:
    t0 = time.monotonic()
    deadline = t0 + timeout_s
    while True:
        stats = router.router_stats()
        if stats["live_replicas"] == n:
            return time.monotonic() - t0
        if time.monotonic() > deadline:
            return float("inf")
        time.sleep(0.25)


def _run_arm(kind: str, *, replicas: int, duration_s: float,
             clients: int, chaos_interval_s: float, delay_s: float,
             replica_timeout_s: float, recovery_envelope_s: float,
             seed: int) -> dict:
    from deeprest_tpu.serve import (
        PredictionServer, PredictionService, ReplicaRouter, RouterConfig,
    )
    from deeprest_tpu.serve.replica import ProcessReplica

    baseline = _census()
    reference = build_tiny().predict_series(
        np.random.default_rng(0).random((2 * W, F)).astype(np.float32))
    traffic = np.random.default_rng(0).random((2 * W, F)).astype(
        np.float32)
    payload = json.dumps({"traffic": traffic.tolist()}).encode()
    reference_json = json.loads(json.dumps(reference.tolist()))

    cfg = RouterConfig(admission_depth=64,
                       replica_timeout_s=replica_timeout_s,
                       eject_after_failures=1, retry_budget=1,
                       probe_interval_s=0.25)
    if kind == "process":
        spec = {"factory": "chaos_bench:build_tiny",
                "kwargs": {"delay_s": delay_s, "ladder": [8]},
                "sys_path": [os.path.dirname(os.path.abspath(__file__))]}
        router = ReplicaRouter(
            [ProcessReplica(spec, name=f"p{i}", boot_timeout_s=300.0,
                            request_timeout_s=replica_timeout_s)
             for i in range(replicas)], config=cfg)
    else:
        router = ReplicaRouter.build(build_tiny(delay_s=delay_s),
                                     replicas, config=cfg)
    service = PredictionService(router, None, backend=f"chaos-{kind}")
    server = PredictionServer(service, port=0).start()

    load_stop = threading.Event()
    chaos_stop = threading.Event()
    stats = _LoadStats()
    rng = random.Random(seed)
    threads = [threading.Thread(
        target=_client_loop,
        args=(server.address, payload, reference_json, load_stop, stats),
        name=f"chaos-client-{i}") for i in range(clients)]

    def chaos_loop():
        while not chaos_stop.wait(chaos_interval_s):
            victims = router.replicas
            if not victims:
                continue
            victim = rng.choice(victims)
            if kind == "process":
                pid = victim.stats().get("pid")
                if pid and victim.alive():
                    os.kill(pid, signal.SIGKILL)
            else:
                try:
                    router.eject(victim.name, reason="chaos schedule")
                except KeyError:
                    pass

    chaos = threading.Thread(target=chaos_loop, name="chaos-killer")
    for t in threads:
        t.start()
    # let the plane serve healthy traffic first (warmup + baseline 200s)
    time.sleep(max(1.0, 3 * delay_s))
    chaos.start()
    time.sleep(duration_s)
    # storm ends; load keeps flowing briefly through the RECOVERING
    # plane (the interesting window), then drains
    chaos_stop.set()
    chaos.join(timeout=10)
    time.sleep(1.0)
    load_stop.set()
    for t in threads:
        t.join(timeout=180)
    hung = [t.name for t in threads if t.is_alive()]

    recovery_s = _await_recovery(router, replicas, recovery_envelope_s * 2)
    health = router.router_stats()["health"]

    # the healed plane answers byte-identically
    final = service.predict({"traffic": traffic.tolist()})
    final_ok = final["predictions"] == reference_json

    server.stop()
    # Release the plane before the census: the router's replica stacks
    # (and their device-resident params) are exactly what the
    # device-buffer column must see freed.
    router = service = server = None  # noqa: F841
    leak = _settled_census(baseline)

    with stats.lock:
        walls = sorted(stats.walls)
        total = (stats.ok + stats.http_429 + stats.http_503
                 + stats.other_status + stats.wrong_answers)
        envelope = replica_timeout_s + delay_s + 10.0
        arm = {
            "replicas": replicas,
            "clients": clients,
            "duration_s": duration_s,
            "chaos_interval_s": chaos_interval_s,
            "requests": total,
            "ok": stats.ok,
            "http_429": stats.http_429,
            "http_503": stats.http_503,
            "other_status": stats.other_status + len(hung),
            "wrong_answers": stats.wrong_answers + (0 if final_ok else 1),
            "max_request_wall_s": round(max(walls), 3) if walls else None,
            "envelope_s": envelope,
            "p50_ms": round(1e3 * _pct(walls, 50), 3) if walls else None,
            "p99_ms": round(1e3 * _pct(walls, 99), 3) if walls else None,
            "ejections": health["ejections"],
            "retries": health["retries"],
            "rejoins": health["rejoins"],
            "recovery_s": (round(recovery_s, 3)
                           if np.isfinite(recovery_s) else None),
            "recovery_envelope_s": recovery_envelope_s,
            "leak": leak,
        }
    arm["pass"] = bool(
        arm["wrong_answers"] == 0
        and arm["other_status"] == 0
        and arm["ok"] >= 1
        and arm["max_request_wall_s"] is not None
        and arm["max_request_wall_s"] <= arm["envelope_s"]
        and arm["ejections"] >= 1
        and arm["rejoins"] >= 1
        and arm["recovery_s"] is not None
        and arm["recovery_s"] <= recovery_envelope_s
        and leak["clean"])
    return arm


# ---------------------------------------------------------------------------
# elastic arm: storm injected device losses mid-TRAINING (round 20)


def _series_corpus(n: int, seed: int):
    """A traffic-correlated synthetic corpus long enough for windowed
    training (the bench's self-contained twin of the test fixtures)."""
    from deeprest_tpu.data.schema import Bucket, MetricSample, Span

    rng = np.random.default_rng(seed)
    buckets = []
    for t in range(n):
        load = 2.0 + np.sin(2 * np.pi * t / 24.0) + rng.uniform(-0.2, 0.2)
        nc = max(0, int(rng.poisson(load)))
        nr = max(0, int(rng.poisson(2 * load)))
        traces = [Span(component="gateway", operation="/compose",
                       children=[Span(component="store-svc",
                                      operation="/store")])
                  for _ in range(nc)]
        traces += [Span(component="gateway", operation="/read")
                   for _ in range(nr)]
        metrics = [
            MetricSample("gateway", "cpu",
                         10.0 * nc + 3.0 * nr + rng.normal(0, 0.5)),
            MetricSample("store-db", "wiops",
                         25.0 * nc + rng.normal(0, 1.0)),
        ]
        buckets.append(Bucket(metrics=metrics, traces=traces))
    return buckets


def _elastic_train_cfg(ckpt_dir: str, superstep: int, accum: int,
                       elastic: bool):
    from deeprest_tpu.config import Config, ModelConfig, TrainConfig

    return Config(
        model=ModelConfig(hidden_size=8, dropout_rate=0.5),
        train=TrainConfig(
            num_epochs=2, batch_size=16, window_size=12,
            eval_stride=12, eval_max_cycles=2, seed=0,
            device_data="always", steps_per_superstep=superstep,
            grad_accum_windows=accum, log_every_steps=0,
            checkpoint_dir=str(ckpt_dir), snapshot_every_steps=2,
            elastic=elastic, remesh_backoff_ms=1.0,
            remesh_max_attempts=4))


def _state_leaves(state):
    import jax

    return [np.asarray(x) for x in jax.tree.leaves(state)]


def _run_elastic_scenario(name: str, corpus, workdir: str, *,
                          superstep: int, accum: int,
                          losses: dict[int, int]) -> dict:
    """One elastic storm cell: the same device-loss schedule through the
    round-17 restart-resume path (fresh process per loss — the
    reference) and through the in-process elastic barrier, then compare
    final params BIT-for-bit.

    The reference chain uses the same FaultInjector (raising BEFORE any
    cursor bookkeeping) as a crash stand-in, so both paths see the same
    newest durable snapshot at each loss — the parity the round-20
    contract pins.
    """
    import shutil
    import time

    from deeprest_tpu.config import MeshConfig
    from deeprest_tpu.parallel import DeviceLossError, FaultInjector
    from deeprest_tpu.parallel.mesh import make_mesh, shrink_mesh_config
    from deeprest_tpu.train import Trainer, prepare_dataset

    schedule = sorted(losses.items())
    ref_dir = os.path.join(workdir, f"{name}-ref")
    ela_dir = os.path.join(workdir, f"{name}-elastic")
    for d in (ref_dir, ela_dir):
        shutil.rmtree(d, ignore_errors=True)

    # -- reference: the round-17 path — every loss kills the "process",
    # a fresh Trainer on the survivor mesh resumes from the newest
    # cursor snapshot
    cfg_ref = _elastic_train_cfg(ref_dir, superstep, accum, elastic=False)
    bundle = prepare_dataset(corpus, cfg_ref.train)
    t0 = time.monotonic()
    data_axis = 8
    state_ref = hist_ref = tr_ref = None
    for i in range(len(schedule) + 1):
        tr_ref = Trainer(cfg_ref, bundle.feature_dim, bundle.metric_names,
                         mesh=make_mesh(MeshConfig(data=data_axis)))
        if i < len(schedule):
            tr_ref.install_fault_injector(
                FaultInjector(dict([schedule[i]])))
        try:
            if i == 0:
                state_ref, hist_ref = tr_ref.fit(bundle)
            else:
                state_ref, hist_ref = tr_ref.resume_training(bundle)
            break
        except DeviceLossError:
            data_axis = shrink_mesh_config(
                MeshConfig(data=data_axis),
                data_axis - schedule[i][1]).data
    wall_ref = time.monotonic() - t0
    ref_cache = tr_ref._jit_cache_size()
    ref_leaves = _state_leaves(state_ref)
    ref_final_loss = hist_ref[-1].test_loss
    del state_ref, hist_ref, tr_ref

    # -- elastic: ONE trainer, same schedule, in-process recovery
    cfg_ela = _elastic_train_cfg(ela_dir, superstep, accum, elastic=True)
    tr = Trainer(cfg_ela, bundle.feature_dim, bundle.metric_names,
                 mesh=make_mesh(MeshConfig(data=8)))
    tr.install_fault_injector(FaultInjector(dict(schedule)))
    t0 = time.monotonic()
    state, hist = tr.fit(bundle)
    wall_ela = time.monotonic() - t0
    ela_cache = tr._jit_cache_size()
    ela_leaves = _state_leaves(state)
    bit_identical = (len(ref_leaves) == len(ela_leaves)
                     and all(np.array_equal(a, b)
                             for a, b in zip(ref_leaves, ela_leaves)))
    cell = {
        "kill_steps": {str(k): v for k, v in schedule},
        "mesh_path": "8x1x1 -> " + " -> ".join(
            f"{r['mesh']['data']}x{r['mesh']['expert']}x{r['mesh']['model']}"
            for r in tr.remesh_history),
        "remeshes": tr.remesh_count,
        "expected_remeshes": len(schedule),
        "bit_identical": bool(bit_identical),
        "final_test_loss_equal": bool(hist[-1].test_loss
                                      == ref_final_loss),
        "recoveries_s": [round(r["recovery_s"], 4)
                         for r in tr.remesh_history],
        "restored_steps": [r["restored_step"]
                           for r in tr.remesh_history],
        # one program set per live mesh shape: the elastic trainer's jit
        # caches after the storm must not exceed what a FRESH trainer on
        # the final mesh compiled (the reference chain's last trainer) —
        # any excess would be per-remesh or per-step recompilation
        "jit_executables": {"elastic": ela_cache, "reference": ref_cache},
        "executables_flat": (ela_cache is None or ref_cache is None
                             or ela_cache <= ref_cache),
        "wall_elastic_s": round(wall_ela, 3),
        "wall_reference_s": round(wall_ref, 3),
    }
    del state, hist, tr, ref_leaves, ela_leaves, bundle
    return cell


def _run_elastic_arm(*, quick: bool, seed: int,
                     recovery_envelope_s: float) -> dict:
    """The elastic storm: injected device losses mid-training — per-step,
    mid-superstep, and mid-grad-accum — each cell gated on bit-identical
    final params vs the restart-resume reference, bounded recovery,
    executables flat across remeshes, and a zero-leak census (threads,
    fds, children, live device buffers: a remesh must not strand
    old-mesh arrays)."""
    import tempfile

    import jax

    if len(jax.devices()) < 8:
        # A single attached chip cannot lose half of itself; the storm
        # needs a multi-device slice (the CPU backend forces 8 virtual
        # devices for exactly this).
        return {"skipped": f"needs >= 8 devices, have "
                           f"{len(jax.devices())}",
                "pass": True}

    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import featurize_buckets

    baseline = _census()
    corpus = featurize_buckets(_series_corpus(140, seed=7),
                               FeaturizeConfig(round_to=8))
    scenarios = {
        # two losses through the fused superstep path: 8 -> 4 -> 2
        "superstep": dict(superstep=2, accum=1, losses={3: 4, 7: 2}),
        # mid-grad-accum: the coalesced group's dispatch is the failing
        # unit (G=2 microbatches per update)
        "grad_accum": dict(superstep=2, accum=2, losses={3: 4}),
    }
    if not quick:
        # the per-step dispatch path (no scan fusion)
        scenarios["per_step"] = dict(superstep=1, accum=1,
                                     losses={3: 4})
    cells = {}
    with tempfile.TemporaryDirectory(prefix="chaos-elastic-") as workdir:
        for cell_name, spec in scenarios.items():
            cells[cell_name] = _run_elastic_scenario(
                cell_name, corpus, workdir, **spec)
    del corpus
    leak = _settled_census(baseline)
    recoveries = [r for c in cells.values() for r in c["recoveries_s"]]
    arm = {
        "scenarios": cells,
        "remeshes": sum(c["remeshes"] for c in cells.values()),
        "bit_identical": all(c["bit_identical"] for c in cells.values()),
        "executables_flat": all(c["executables_flat"]
                                for c in cells.values()),
        "max_recovery_s": (round(max(recoveries), 4)
                           if recoveries else None),
        "recovery_envelope_s": recovery_envelope_s,
        "leak": leak,
    }
    arm["pass"] = bool(
        arm["bit_identical"]
        and arm["executables_flat"]
        and all(c["remeshes"] == c["expected_remeshes"]
                for c in cells.values())
        and all(c["final_test_loss_equal"] for c in cells.values())
        and arm["max_recovery_s"] is not None
        and arm["max_recovery_s"] <= recovery_envelope_s
        and leak["clean"])
    return arm


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="tier-1-sized storm (fewer replicas, kills, "
                         "seconds) — plumbing + gates, not endurance")
    ap.add_argument("--arms", default="thread,process,elastic",
                    help="comma list of storm arms to run")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    wanted = [a.strip() for a in args.arms.split(",") if a.strip()]
    if "elastic" in wanted and "xla_force_host_platform_device_count" \
            not in os.environ.get("XLA_FLAGS", ""):
        # The elastic storm needs a mesh that can LOSE devices; on the
        # CPU backend that means 8 virtual devices, set before the first
        # jax import (no effect on accelerator platforms).
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_"
                                     "device_count=8").strip()

    import jax

    if "process" in wanted and jax.default_backend() != "cpu":
        # a chip belongs to one process: this parent holds the host's
        # chips by now, and worker processes that need one cannot boot
        ap.error(f"the process arm spawns workers that need the "
                 f"{jax.default_backend()} this process already holds; run "
                 "it with JAX_PLATFORMS=cpu, or drop it: --arms "
                 "thread,elastic")
    _warm_multiprocessing()
    quick = bool(args.quick)
    # recovery on CPU is dominated by the worker reboot's jax import
    # (cold ~5-15 s; warm compile cache much less) — the envelope states
    # that honestly rather than pretending chip-grade failover
    recovery_envelope_s = 90.0
    arms = {}
    for kind in wanted:
        if kind == "elastic":
            # in-process device-loss storm on the TRAINING plane; the
            # envelope covers restore (detect->rebuild->restore legs);
            # the first post-restore dispatch additionally pays one
            # compile per new mesh shape (reported in wall_elastic_s)
            arms[kind] = _run_elastic_arm(
                quick=quick, seed=args.seed,
                recovery_envelope_s=30.0)
        elif kind == "thread":
            arms[kind] = _run_arm(
                "thread",
                replicas=2 if quick else 4,
                duration_s=4.0 if quick else 20.0,
                clients=3 if quick else 6,
                chaos_interval_s=1.0 if quick else 2.0,
                delay_s=0.05,
                replica_timeout_s=15.0,
                recovery_envelope_s=recovery_envelope_s,
                seed=args.seed)
        elif kind == "process":
            arms[kind] = _run_arm(
                "process",
                replicas=2 if quick else 3,
                duration_s=8.0 if quick else 30.0,
                clients=3 if quick else 6,
                chaos_interval_s=4.0 if quick else 6.0,
                delay_s=0.3,
                replica_timeout_s=20.0,
                recovery_envelope_s=recovery_envelope_s,
                seed=args.seed)
        else:
            ap.error(f"unknown arm {kind!r}")

    result = {
        # v2: the elastic arm joins (in-process device-loss storm on the
        # training plane: bit-identical-to-restart-resume, bounded
        # recovery, executables flat across remeshes) and every census
        # gains a live device-buffer column — NEW arm + NEW census key
        # only; every v1 key keeps its meaning.
        "schema_version": 2,
        "quick": quick,
        "platform": jax.default_backend(),
        "honest_cpu": (
            "all replicas share one host core; worker reboot time is "
            "dominated by the child's jax import — throughput/latency "
            "cells are plumbing proofs, the gates (zero wrong answers, "
            "bounded errors, rejoin, zero leaks) are the product.  The "
            "elastic arm's recovery seconds are CPU restore times "
            "(tiny model, local disk); on hardware the same legs add "
            "real HBM restore + per-shape XLA compiles"),
        "arms": arms,
        "pass": bool(arms) and all(a["pass"] for a in arms.values()),
    }
    blob = json.dumps(result, indent=2, sort_keys=True)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            f.write(blob + "\n")
    print(json.dumps(result, sort_keys=True))
    return 0 if result["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
