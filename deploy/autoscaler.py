#!/usr/bin/env python
"""Autoscaler: the prediction service sizes ITSELF with its own model.

DeepRest's headline capability is what-if capacity estimation ("how much
resource would the component need if traffic looked like X?" — PAPERS.md
[1]); this control loop dogfoods that capability on the serving plane:
the service's *own observed request traffic* becomes the what-if traffic
program, the model's predicted utilization becomes the capacity basis,
and the replica count follows.  The Clipper-style router
(deeprest_tpu/serve/router.py) is the actuator — ``scale_to`` grows or
drains replicas live — and every decision is emitted to ``/healthz``
(``router.autoscaler``) and, when asked, into the committed k8s
manifests (deploy/k8s/predictor.yaml ``spec.replicas``).

Two capacity bases, used in preference order:

1. **model** — a fitted :class:`WhatIfEstimator` whose corpus covers the
   serving plane: recent observed rps is projected into a traffic
   program, the estimator predicts the configured metric's series, and
   ``desired = ceil(peak_predicted / (unit_capacity * target))``.
2. **measured** — no estimator: ``desired = ceil(peak_rps /
   (capacity_rps_per_replica * target))`` with the per-replica rps as
   the deployment measured it on its own replicas.

Run it in-process (``deeprest_tpu serve --replicas N --autoscale ...``
starts the loop thread next to the server) or drive :meth:`step`
directly (tests, cron).
"""

from __future__ import annotations

import collections
import dataclasses
import math
import threading
import time


@dataclasses.dataclass(frozen=True)
class AutoscalerConfig:
    min_replicas: int = 1
    max_replicas: int = 8
    interval_s: float = 10.0
    # fraction of a replica's capacity the plane should run at — headroom
    # for bursts between control ticks
    target_utilization: float = 0.7
    # measured basis: requests/s one replica sustains, as the deployment
    # measured it (the benchmark has no serving cell yet)
    capacity_rps_per_replica: float | None = None
    # model basis: what the estimator predicts for the serving plane
    endpoint: str = "deeprest-predictor_/v1/predict"
    metric: str | None = None           # e.g. "deeprest-predictor_cpu"
    quantile: str = "q50"
    # utilization (in the metric's unit) one replica sustains
    unit_capacity: float | None = None
    history: int = 30                   # control-tick samples retained

    def __post_init__(self):
        if not (1 <= self.min_replicas <= self.max_replicas):
            raise ValueError(
                f"bad replica bounds [{self.min_replicas}, "
                f"{self.max_replicas}]")
        if not (0 < self.target_utilization <= 1):
            raise ValueError(
                f"target_utilization {self.target_utilization} must be in "
                "(0, 1]")


class Autoscaler:
    """Control loop over a :class:`~deeprest_tpu.serve.router.ReplicaRouter`.

    ``estimator`` (optional WhatIfEstimator) enables the model basis;
    ``manifest_path`` (optional deploy/k8s/predictor.yaml) mirrors every
    applied decision into the k8s Deployment's ``spec.replicas``.
    """

    def __init__(self, router, config: AutoscalerConfig | None = None,
                 estimator=None, manifest_path: str | None = None,
                 actuate: bool = True):
        self.router = router
        self.config = config or AutoscalerConfig()
        self.estimator = estimator
        self.manifest_path = manifest_path
        self.actuate = actuate
        # Guards the sample history and the latest decision: the control
        # loop thread writes them while /healthz handler threads (via
        # router.note_autoscaler) and tests read.
        self._lock = threading.Lock()
        self._samples: collections.deque = collections.deque(
            maxlen=self.config.history)
        self._last_decision: dict | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # -- observation -----------------------------------------------------

    def sample(self, now: float | None = None) -> float:
        """Record the plane's cumulative demand counters; returns the
        observed rps since the previous sample (0.0 on the first).

        Demand comes from the obs metrics registry's counters via
        ``router.demand_totals()`` — the SAME objects /healthz and
        ``GET /metrics`` read — not from a private re-derivation of the
        stats JSON (one source of truth; router_stats stays as the
        fallback for minimal router stand-ins in tests).
        """
        now = time.monotonic() if now is None else now
        demand = getattr(self.router, "demand_totals", None)
        if callable(demand):
            totals = demand()
            served, rejected = totals["served"], totals["shed"]
        else:
            stats = self.router.router_stats()
            served = sum(r["served_requests"] for r in stats["replicas"])
            rejected = stats["admission"]["rejected"]
        # admission rejections are demand too: a saturated plane must
        # scale UP even though served throughput has flat-lined
        with self._lock:
            prev = self._samples[-1] if self._samples else None
            self._samples.append((now, served, rejected))
        if prev is None or now <= prev[0]:
            return 0.0
        dt = now - prev[0]
        return max(0.0, (served - prev[1]) + (rejected - prev[2])) / dt

    def _rps_window(self) -> tuple[float, float]:
        """(mean, peak) demand rps over the retained control ticks."""
        with self._lock:
            samples = list(self._samples)
        if len(samples) < 2:
            return 0.0, 0.0
        rates = []
        for (t0, s0, r0), (t1, s1, r1) in zip(samples, samples[1:]):
            if t1 > t0:
                rates.append(max(0.0, (s1 - s0) + (r1 - r0)) / (t1 - t0))
        if not rates:
            return 0.0, 0.0
        return sum(rates) / len(rates), max(rates)

    # -- decision --------------------------------------------------------

    def desired_replicas(self, mean_rps: float, peak_rps: float) -> dict:
        cfg = self.config
        basis = None
        desired = None
        if (self.estimator is not None and cfg.metric is not None
                and cfg.unit_capacity):
            try:
                t = max(self.router.window_size, 12)
                program = [{cfg.endpoint: max(1, round(peak_rps))}] * t
                bands = self.estimator.estimate(program)
                series = bands[cfg.metric][cfg.quantile]
                peak_predicted = float(max(series))
                desired = math.ceil(
                    peak_predicted / (cfg.unit_capacity
                                      * cfg.target_utilization))
                basis = {"mode": "model", "endpoint": cfg.endpoint,
                         "metric": cfg.metric, "quantile": cfg.quantile,
                         "peak_predicted": round(peak_predicted, 4),
                         "unit_capacity": cfg.unit_capacity}
            except KeyError:
                # the fitted corpus does not know the serving plane's
                # endpoint/metric — fall through to the measured basis
                basis = None
        if desired is None and cfg.capacity_rps_per_replica:
            desired = math.ceil(
                peak_rps / (cfg.capacity_rps_per_replica
                            * cfg.target_utilization))
            basis = {"mode": "measured",
                     "capacity_rps_per_replica":
                         cfg.capacity_rps_per_replica}
        if desired is None:            # no basis configured: hold steady
            desired = len(self.router.replicas)
            basis = {"mode": "hold"}
        desired = max(cfg.min_replicas, min(cfg.max_replicas, desired, 1024))
        return {"desired": desired, "basis": basis,
                "mean_rps": round(mean_rps, 3),
                "peak_rps": round(peak_rps, 3)}

    def step(self, now: float | None = None) -> dict:
        """One control tick: sample → decide → (optionally) actuate →
        emit.  Returns the decision record."""
        rps = self.sample(now)
        mean_rps, peak_rps = self._rps_window()
        decision = self.desired_replicas(mean_rps, peak_rps)
        decision["instant_rps"] = round(rps, 3)
        current = len(self.router.replicas)
        decision["current"] = current
        applied = False
        if self.actuate and decision["desired"] != current:
            self.router.scale_to(decision["desired"])
            applied = True
        decision["applied"] = applied
        decision["recorded_monotonic"] = round(
            time.monotonic() if now is None else now, 3)
        with self._lock:
            self._last_decision = decision
        self.router.note_autoscaler(decision)        # -> /healthz
        if self.manifest_path:
            try:
                self.write_manifest(decision["desired"])
                decision["manifest"] = self.manifest_path
            except Exception as exc:   # manifest trouble must not kill the loop
                decision["manifest_error"] = str(exc)
        return decision

    @property
    def last_decision(self) -> dict | None:
        with self._lock:
            return self._last_decision

    # -- emission --------------------------------------------------------

    def write_manifest(self, replicas: int) -> None:
        """Mirror the decision into the committed serving manifest: the
        Deployment named ``deeprest-predictor`` gets ``spec.replicas``."""
        import yaml

        with open(self.manifest_path, encoding="utf-8") as f:
            docs = list(yaml.safe_load_all(f))
        changed = False
        for doc in docs:
            if (isinstance(doc, dict) and doc.get("kind") == "Deployment"
                    and doc.get("metadata", {}).get("name")
                    == "deeprest-predictor"):
                doc["spec"]["replicas"] = int(replicas)
                changed = True
        if not changed:
            raise ValueError(
                f"{self.manifest_path}: no deeprest-predictor Deployment")
        with open(self.manifest_path, "w", encoding="utf-8") as f:
            yaml.safe_dump_all(docs, f, sort_keys=False)

    # -- loop ------------------------------------------------------------

    def start(self) -> "Autoscaler":
        self._stop.clear()
        # graftlint: disable=TH001 -- lifecycle handle: start/stop run on the owning driver thread only
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="autoscaler")
        self._thread.start()
        return self

    def _run(self) -> None:
        while not self._stop.wait(self.config.interval_s):
            try:
                self.step()
            except Exception as exc:   # a bad tick must not end the loop
                import sys

                print(f"autoscaler tick failed: {exc!r}", file=sys.stderr)

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=self.config.interval_s + 5)
