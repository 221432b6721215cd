"""Typed configuration for the whole framework.

The reference scatters its knobs across module-level constant blocks
(reference: resource-estimation/estimate.py:13-18, featurize.py:6-7,
qrnn.py:7-8, locust/locustfile-*.py:14-23).  Here every knob is a field on a
frozen dataclass so configs are explicit, serializable, and hashable enough
to key jit caches.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Mapping


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Dimensions of the multi-task quantile GRU.

    Defaults mirror the reference model (reference:
    resource-estimation/qrnn.py:7-8 — hidden 128, 1 layer, bidirectional,
    quantiles (.05, .50, .95), dropout 0.5).
    """

    feature_dim: int = 8          # padded call-path feature capacity |M|
    num_metrics: int = 3          # number of component_resource targets (experts)
    hidden_size: int = 128
    num_layers: int = 1
    bidirectional: bool = True
    quantiles: tuple[float, ...] = (0.05, 0.50, 0.95)
    dropout_rate: float = 0.50
    # bfloat16 matmuls on the MXU; params and loss stay float32.
    compute_dtype: str = "float32"
    # GRU recurrence backend: 'auto' uses the fused pallas kernel on TPU
    # and `lax.scan` elsewhere (ops/gru.py, ops/pallas_gru.py).
    rnn_backend: str = "auto"

    @property
    def directions(self) -> int:
        return 2 if self.bidirectional else 1

    @property
    def rnn_out_dim(self) -> int:
        return self.hidden_size * self.directions


LEVEL_RESOURCES = ("usage",)
"""Resources modeled as per-bucket increments by default (the
``TrainConfig.delta_resources`` default).  Disk usage accumulates writes —
a level whose absolute value encodes history the traffic cannot see;
predicting its CHANGE and integrating from a window anchor is the modeling
counterpart of the re-anchoring the reference demo applies to exactly
these level-type series (reference: web-demo/dataloader.py:143-156)."""


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training-loop knobs (reference: resource-estimation/estimate.py:13-18)."""

    num_epochs: int = 50
    batch_size: int = 32
    learning_rate: float = 1e-3
    train_split: float = 0.40     # leading fraction of windows used for training
    window_size: int = 60         # sliding-window length (time steps)
    eval_stride: int = 60         # test windows sampled every `stride` steps
    eval_max_cycles: int = 9      # cap on evaluated test windows per epoch
    eval_batch_size: int = 64     # eval windows per device batch (pages the
                                  # eval like predict(); one giant batch
                                  # OOMs at wide F × many windows)
    seed: int = 0
    checkpoint_dir: str | None = None
    checkpoint_every_epochs: int = 10
    log_every_steps: int = 50
    # Batches kept in flight on-device ahead of the step consuming them:
    # device transfers are asynchronous, so depth>=1 overlaps the
    # host->device copy of batch t+1 with the compute of batch t (the
    # reference ships every batch synchronously, estimate.py:68-69).
    # 0 disables prefetch.
    prefetch_depth: int = 2
    # Resources trained as per-bucket INCREMENTS instead of absolute
    # levels.  Disk usage is an integrator — its absolute value encodes a
    # history API traffic cannot see, so a traffic→level regression
    # structurally trails a persistence baseline; its per-bucket CHANGE is
    # what traffic causes (the reference demo re-anchors exactly these
    # level-type series before comparing, web-demo/dataloader.py:143-156).
    # Predictions for these resources are integrated from the window
    # anchor at eval/serve time (train/data.py:integrate_level_columns).
    # Empty tuple disables the delta formulation entirely.
    delta_resources: tuple[str, ...] = LEVEL_RESOURCES
    # Device-resident input pipeline: "auto" stages the normalized BASE
    # series in HBM (bf16 for bf16 models) on ACCELERATOR backends when it
    # fits the byte budget, and each train step gathers its windows by
    # start index — per-step host→device traffic becomes [B] int32
    # instead of the [B,W,F] window tensor (windows overlap W−1 of W
    # rows; materialized shipping re-sends every row W times).  On the
    # CPU backend "auto" does NOT stage: the transfer it avoids is a
    # memcpy, and XLA's CPU gather lowers to scalar loops (~3× slower
    # than host streaming at month scale).  "always" forces staging
    # (tests, virtual meshes); "off" always streams from host.
    device_data: str = "auto"
    device_data_max_bytes: int = 4 << 30
    # Fused multi-step supersteps on the staged (device-resident) path:
    # the whole epoch's shuffled batch plan ([C, S, B] start indices +
    # weights, trailing chunk zero-weight padded) ships to device once per
    # epoch and ``jax.lax.scan`` runs S train steps inside ONE donated jit
    # dispatch — an epoch becomes ceil(K/S) dispatches instead of K, with
    # per-step losses accumulated on device and read back once per
    # superstep.  Bit-identical to the per-step loop (same fold_in(rng,
    # step) dropout, same step counter; padded steps pass the prior state
    # through a cond skip branch).  1 = per-step dispatch (the historical
    # loop); "epoch" = the
    # whole epoch in one dispatch; "auto" = min(epoch length,
    # log_every_steps or 32), capped so a plan chunk stays under ~1 MiB.
    # Ignored when the dataset is not staged (host-feed fallback keeps the
    # per-step loop).
    steps_per_superstep: int | str = "auto"
    # Gradient accumulation in THE superstep (train/trainer.py
    # train_update): G consecutive plan steps (microbatches) each run the
    # shared step's forward and backward under their own kept mask
    # (dropout key fold_in(fold_in(rng, step), g)), their gradients,
    # weighted by each microbatch's share of the group's real windows, are
    # added in microbatch order, and the optimizer updates ONCE on that
    # sum: plain Adam on the gradient of the mean loss over all real
    # windows of the group, what one batch of G x batch_size windows
    # gives (a team with one chip makes a pod's update so: 8 x 32 = the
    # 256 windows of the v5e-8 layout the brief names).  On a compact
    # sparse base the accumulator is the table's carried rows and the
    # other leaves, and Adam runs on the table's rows as at G = 1.
    # 1 = one update a step (default; the trace of before the knob).
    # Requires the staged (device-resident) feed; per-microbatch losses
    # keep their meaning, the step counter counts real microbatches and
    # Adam's own count counts updates.  The benchmark's cell
    # `tenk-train-accum8` (G = 8) runs it: what accumulation amortises is
    # the optimizer's pass over the large leaves (the [E, H, F] mask
    # weights' Adam, 40% of a 10k step at G = 1, runs once in G), not the
    # recurrence kernels, which are 17% of that step and see the same B
    # rows a call whatever G is (PERF.md sections 5 and 6, PR 48).
    grad_accum_windows: int = 1
    # Sparse-first traffic feed (the 10k-endpoint tier, ROADMAP item 4):
    # traffic rows travel host→device as padded-COO ``(cols[K], vals[K])``
    # pairs — >99% of a 10k-wide count vector is zeros — and densify to
    # the model's static [.., F] via ONE on-device scatter inside the
    # existing train/eval executables (ops/densify.py).  Staged feed
    # bytes drop ~F/(2K) (~80× at F=10240, K=64); losses stay
    # BIT-IDENTICAL to the dense reference (tests/test_sparse.py).  The
    # dense path remains the default and the parity spec.  Requires the
    # staged (device-resident) feed — incompatible with
    # device_data="off".
    sparse_feed: bool = False
    # Max nonzero traffic columns per bucket row under sparse_feed; a
    # fatter row RAISES (dropping call paths would corrupt the count
    # vector).  Also the padded-COO row width, so it sizes both ring
    # memory and feed bytes.
    sparse_nnz_cap: int = 64
    # Preemption-safe training (ROADMAP item 7, dynamic half): every this
    # many REAL train steps (superstep path: at the first chunk boundary
    # at or past the cadence) the trainer writes an atomic
    # deeprest-sharded-v1 checkpoint PLUS the epoch-plan cursor (epoch
    # index, steps done within the epoch, the shuffle rng's bit-generator
    # state at epoch start, global step) into the sidecar.  A killed run
    # restarts via ``Trainer.resume_training`` — onto whatever mesh
    # remains (cross-mesh restore) — replays the plan from the cursor,
    # and is bit-identical to the uninterrupted run at the same step
    # (tests/test_chaos.py).  0 = off (the historical behavior; epoch-
    # cadence checkpoints only).
    snapshot_every_steps: int = 0
    # Snapshot retention GC: keep only the newest this-many CURSOR
    # snapshots (the preemption-resume anchors) — snapshot_every_steps
    # used to accumulate checkpoints unboundedly.  Pruning happens only
    # AFTER a durable newer save and never touches the newest (restore-
    # target) snapshots or non-cursor checkpoints (epoch-cadence saves,
    # streaming refresh checkpoints — the stream's keep_checkpoints owns
    # those).  0 = unlimited (the historical behavior).
    snapshot_keep: int = 3
    # Elastic remeshing (ROADMAP item 7's last training gap): survive
    # device loss IN-PROCESS.  The fault barrier around the step/
    # superstep dispatch catches the device-loss family (real
    # XlaRuntimeError device errors on hardware; the deterministic
    # FaultInjector's DeviceLossError on CPU), re-enumerates healthy
    # devices, rebuilds the mesh (data axis shrinks by divisors,
    # expert/model preserved — parallel/mesh.shrink_mesh_config),
    # re-derives every sharding from the one rule table, restores the
    # newest fsync'd cursor snapshot through the cross-mesh assembly,
    # re-stages the epoch plan onto the new mesh, and continues — the
    # post-remesh trajectory is BIT-IDENTICAL to killing the process and
    # running resume_training on the survivor mesh (tests/test_chaos.py).
    # Requires cursor snapshots (snapshot_every_steps >= 1 and a
    # checkpoint_dir at fit time).
    elastic: bool = False
    # Bounded recovery: total remeshes one fit() may perform before the
    # barrier surfaces RemeshExhaustedError instead of respinning (the
    # RS004 discipline on the training plane), and the backoff slept
    # before each rebuild (scaled by the attempt number).
    remesh_max_attempts: int = 3
    remesh_backoff_ms: float = 100.0

    def __post_init__(self):
        v = self.steps_per_superstep
        ok = v in ("auto", "epoch") or (
            isinstance(v, int) and not isinstance(v, bool) and v >= 1)
        if not ok:
            raise ValueError(
                f"TrainConfig.steps_per_superstep={v!r}: must be 'auto', "
                f"'epoch', or an int >= 1")
        g = self.grad_accum_windows
        if not isinstance(g, int) or isinstance(g, bool) or g < 1:
            raise ValueError(
                f"TrainConfig.grad_accum_windows={g!r}: must be an int >= 1")
        if not isinstance(self.sparse_nnz_cap, int) \
                or isinstance(self.sparse_nnz_cap, bool) \
                or self.sparse_nnz_cap < 1:
            raise ValueError(
                f"TrainConfig.sparse_nnz_cap={self.sparse_nnz_cap!r}: "
                f"must be an int >= 1")
        s = self.snapshot_every_steps
        if not isinstance(s, int) or isinstance(s, bool) or s < 0:
            raise ValueError(
                f"TrainConfig.snapshot_every_steps={s!r}: must be an "
                f"int >= 0 (0 = snapshots off)")
        k = self.snapshot_keep
        if not isinstance(k, int) or isinstance(k, bool) or k < 0:
            raise ValueError(
                f"TrainConfig.snapshot_keep={k!r}: must be an int >= 0 "
                "(0 = unlimited retention)")
        a = self.remesh_max_attempts
        if not isinstance(a, int) or isinstance(a, bool) or a < 1:
            raise ValueError(
                f"TrainConfig.remesh_max_attempts={a!r}: must be an "
                "int >= 1 (the barrier must stay bounded)")
        if not isinstance(self.remesh_backoff_ms, (int, float)) \
                or isinstance(self.remesh_backoff_ms, bool) \
                or self.remesh_backoff_ms < 0:
            raise ValueError(
                f"TrainConfig.remesh_backoff_ms="
                f"{self.remesh_backoff_ms!r}: must be a number >= 0")
        if self.elastic and self.snapshot_every_steps < 1:
            raise ValueError(
                "TrainConfig.elastic=True requires snapshot_every_steps "
                ">= 1: the remesh barrier restores from cursor "
                "snapshots; without them a device loss would restart "
                "training from scratch silently")
        if self.sparse_feed and self.device_data == "off":
            raise ValueError(
                "TrainConfig.sparse_feed=True requires the staged "
                "(device-resident) feed — the on-device densify lives "
                "inside the staged executables; set device_data to "
                "'auto' or 'always'")


@dataclasses.dataclass(frozen=True)
class FeaturizeConfig:
    """Call-path feature-space construction.

    The raw feature space is unbounded (one dimension per observed
    root-to-node call path; reference: resource-estimation/featurize.py:11-24).
    XLA wants static shapes, so the vector is materialized at a fixed
    ``capacity``; ``hash_features=True`` switches from a growable dictionary
    to stable hash-bucketing so streaming corpora never force a recompile.
    """

    capacity: int = 0             # 0 = size to the observed space, rounded up
    round_to: int = 128           # pad capacity to a multiple (MXU lane width)
    hash_features: bool = False
    hash_seed: int = 0x5EED

    def __post_init__(self):
        if self.hash_features and self.capacity <= 0:
            raise ValueError(
                "hash_features=True requires an explicit capacity > 0 "
                "(there is no observed vocabulary to size the space from)"
            )


@dataclasses.dataclass(frozen=True)
class EtlConfig:
    """Host-ETL pipeline knobs (featurization + streaming ingest).

    The featurization firehose is host-side work (trace walking, hashing,
    counting) that must keep up with the device (PERF.md "Host ETL"):
    ``workers`` shards offline corpus featurization across a forked
    process pool, and ``overlap`` moves the streaming trainer's
    tail→parse→featurize onto a background thread double-buffered against
    device fine-tuning, with ``queue_depth`` bounding the featurized-but-
    not-yet-ingested backlog (backpressure blocks the ETL thread, which
    in turn stops draining the tailer).
    """

    workers: int = 1              # offline featurize pool: 1 = serial, 0 = per-CPU
    queue_depth: int = 512        # buckets buffered between ETL and train threads
    overlap: bool = True          # background ETL thread in StreamingTrainer.run

    def __post_init__(self):
        if self.workers < 0:
            raise ValueError(f"EtlConfig.workers={self.workers}: must be >= 0")
        if self.queue_depth < 1:
            raise ValueError(
                f"EtlConfig.queue_depth={self.queue_depth}: must be >= 1")


@dataclasses.dataclass(frozen=True)
class InferConfig:
    """Serving-side rolled-inference knobs (serve/fused.py).

    ``fused=True`` routes ``predict_series`` / ``predict_series_many``
    through the device-resident one-dispatch-per-page pipeline (on-device
    normalize → model → clamp, prefix-sum delta integration, carry
    threaded between pages on device); ``False`` pins the host-loop
    reference path.  ``page_windows`` sets the fused page size explicitly
    (an off-ladder value adds one per-rung executable).  ``None`` picks a
    backend-tuned default: small cache-resident pages on the CPU backend
    (measured ~2x per-window over rung-32/64 batches — PERF.md "rolled
    inference"), the ladder's top rung on accelerators (MXU occupancy).
    """

    fused: bool = True
    page_windows: int | None = None
    # Multi-series/multi-scenario page coalescing (serve/fused.py): fold up
    # to this many consecutive pages of the window plan into ONE dispatch,
    # so a rung-64 page becomes a 64·G-row batch that actually fills MXU
    # rows instead of paging thin.  The carry/segment machinery already
    # expresses any fold in one batch, so this only widens dispatches (new
    # super-rungs page·{2..G} join the jit ladder).  None = backend auto:
    # 1 on the CPU backend (the per-window cost there is cache-bound and
    # MINIMIZED at small pages — PERF.md "rolled inference"), 4 on
    # accelerators (256 recurrence rows at the default ladder).
    coalesce_pages: int | None = None
    # Sparse-first serving feed (the serve-side twin of
    # TrainConfig.sparse_feed): traffic series ship host→device as
    # padded-COO ``(cols[K], vals[K])`` window pages and densify inside
    # the fused executable (ops/densify.py) — ~F/(2K) fewer feed bytes
    # at 10k-endpoint width, bit-identical non-delta outputs, and the
    # executable count stays flat (one sparse program per rung).  Dense
    # entry paths remain the default and the parity spec.
    sparse_feed: bool = False
    sparse_nnz_cap: int = 64
    # Quantized serving (ops/quantize.py, round 22): "int8" stores every
    # GRU/dense weight matrix per-output-channel symmetric int8 and
    # dequantizes at use inside the fused executables (~3.9x fewer weight
    # bytes); "bf16" halves them.  Output drift vs the f32 reference is
    # measured at quantize time and pinned as a parity envelope next to
    # the checkpoint — a violating reload raises (QuantParityError).
    quant: str = "off"

    def __post_init__(self):
        if self.quant not in ("off", "int8", "bf16"):
            raise ValueError(
                f"InferConfig.quant={self.quant!r}: must be one of "
                "'off', 'int8', 'bf16'")
        if not isinstance(self.sparse_nnz_cap, int) \
                or isinstance(self.sparse_nnz_cap, bool) \
                or self.sparse_nnz_cap < 1:
            raise ValueError(
                f"InferConfig.sparse_nnz_cap={self.sparse_nnz_cap!r}: "
                f"must be an int >= 1")
        if self.page_windows is not None and self.page_windows < 1:
            raise ValueError(
                f"InferConfig.page_windows={self.page_windows}: must be "
                ">= 1 (or None for the ladder's top rung)")
        if self.coalesce_pages is not None and self.coalesce_pages < 1:
            raise ValueError(
                f"InferConfig.coalesce_pages={self.coalesce_pages}: must "
                "be >= 1 (or None for the backend default)")


@dataclasses.dataclass(frozen=True)
class ObsConfig:
    """Observability knobs (deeprest_tpu/obs).

    ``enabled`` gates the SPAN recorder only — metrics counters are
    always live (they are the cheap half, and /metrics must answer even
    on a spans-off plane).  ``span_capacity`` bounds the in-process span
    ring (newest win; a long-lived server must never grow unbounded).
    """

    enabled: bool = False
    span_capacity: int = 4096

    def __post_init__(self):
        if self.span_capacity < 1:
            raise ValueError(
                f"ObsConfig.span_capacity={self.span_capacity}: must be "
                ">= 1")


@dataclasses.dataclass(frozen=True)
class QualityConfig:
    """Model-quality monitors + the drift→retrain→reload loop
    (deeprest_tpu/obs/quality.py, train/stream.DriftController —
    ROADMAP item 6).

    The monitors watch the live bucket stream: feature-distribution
    drift (streaming per-call-path PSI/KS vs the training reference),
    rolling q-band coverage + pinball calibration, and the continuous
    not-justified-by-traffic anomaly check.  Every verdict stream runs
    through a hysteresis machine — separate enter/exit thresholds plus
    sustained-sweep counts — so one noisy window never flaps the
    surface.  ``auto_retrain`` is the act half: sustained drift triggers
    an out-of-cadence retrain on the retained rings, then a rolling
    reload into the serving plane (``retrain_cooldown_buckets`` bounds
    the loop's own thrash; ``auto_retrain=False`` is the manual
    override — verdicts only, a human pulls the trigger).
    """

    enabled: bool = False
    # sweep cadence (buckets between monitor passes) and the trailing
    # live window the drift score compares against the reference
    sweep_every_buckets: int = 30
    live_window: int = 120
    min_sweep_buckets: int = 8
    # Drift-reference anchor: the trailing this-many retained buckets at
    # (re)train time.  The verdict's question is "has the distribution
    # moved since the model last trained" — anchoring on the ring TAIL
    # (not the whole history) lets the verdict EXIT once a retrain has
    # adapted to the new regime, instead of forever comparing the live
    # stream against a pre/post mixture.
    reference_window: int = 240
    # hysteresis: enter/exit thresholds per stream + sustained counts
    drift_enter: float = 0.25          # traffic-mass-weighted PSI
    drift_exit: float = 0.10
    calibration_enter: float = 0.30    # undercoverage (nominal - observed)
    calibration_exit: float = 0.15
    anomaly_enter: float = 1.00        # mean normalized excess (≥ one
    anomaly_exit: float = 0.25         # full scale unit above the band)
    sustain_enter: int = 2
    sustain_exit: int = 2
    # calibration rolling window, in sweeps
    calibration_sweeps: int = 8
    # the continuous not-justified-by-traffic check's knobs (the same
    # meaning as the batch /v1/anomaly route's)
    anomaly_tolerance: float = 0.10
    anomaly_min_run: int = 5
    # Cold-start honesty: a stream's model in its first refreshes is
    # undertrained, and a bad band produces one-sided excess that is
    # indistinguishable from a real traffic-decoupled consumer (measured
    # — PERF.md round 18).  The model-CONDITIONED verdict streams
    # (calibration, anomaly) therefore arm only after this many
    # refreshes on the train plane; the serving plane arms immediately
    # (its checkpoint is trusted by definition of serving it).
    model_warmup_refreshes: int = 3
    # the act half (DriftController)
    auto_retrain: bool = True
    retrain_cooldown_buckets: int = 240
    # retraining ON anomalous data would teach the model the very
    # consumption the paper's sanity check exists to flag; default off
    retrain_during_anomaly: bool = False

    def __post_init__(self):
        for name in ("sweep_every_buckets", "live_window",
                     "min_sweep_buckets", "reference_window",
                     "sustain_enter", "sustain_exit",
                     "calibration_sweeps", "anomaly_min_run"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"QualityConfig.{name}={v!r}: must be an int >= 1")
        if self.retrain_cooldown_buckets < 0:
            raise ValueError(
                f"QualityConfig.retrain_cooldown_buckets="
                f"{self.retrain_cooldown_buckets}: must be >= 0")
        if not isinstance(self.model_warmup_refreshes, int) \
                or isinstance(self.model_warmup_refreshes, bool) \
                or self.model_warmup_refreshes < 0:
            raise ValueError(
                f"QualityConfig.model_warmup_refreshes="
                f"{self.model_warmup_refreshes!r}: must be an int >= 0")
        for enter, exit_ in (("drift_enter", "drift_exit"),
                             ("calibration_enter", "calibration_exit"),
                             ("anomaly_enter", "anomaly_exit")):
            if getattr(self, exit_) > getattr(self, enter):
                raise ValueError(
                    f"QualityConfig.{exit_} must be <= {enter} "
                    "(hysteresis needs exit at or below enter)")


@dataclasses.dataclass(frozen=True)
class SurfaceConfig:
    """Capacity-surface plane (deeprest_tpu/serve/surface.py — ROADMAP
    item 5): precomputed what-if surfaces answering ``/v1/whatif`` and
    ``/v1/whatif/surface`` by multilinear interpolation, invalidated on
    every backend reload.

    ``grid`` is the per-axis scale ladder a surface sweeps around its
    base program; ``max_axes`` caps the grid dimensionality (more active
    endpoints than this collapse to one shared scale axis — vertex count
    is ``len(grid) ** axes``); ``jitter`` is the Monte-Carlo probe count
    behind the measured parity envelope.  ``max_surfaces``/``max_bytes``
    bound the host-resident LRU; ``warm_async`` builds cache-miss
    surfaces on a background thread (the miss answers from the frontier
    meanwhile) instead of inline.
    """

    enabled: bool = False
    grid: tuple[float, ...] = (0.5, 1.0, 2.0, 4.0)
    max_axes: int = 3
    jitter: int = 8
    max_surfaces: int = 8
    max_bytes: int = 64 * 1024 * 1024
    warm_async: bool = True

    def __post_init__(self):
        for name in ("max_axes", "max_surfaces", "max_bytes"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"SurfaceConfig.{name}={v!r}: must be an int >= 1")
        if not isinstance(self.jitter, int) or isinstance(self.jitter, bool) \
                or self.jitter < 0:
            raise ValueError(
                f"SurfaceConfig.jitter={self.jitter!r}: must be an int >= 0")
        grid = tuple(float(g) for g in self.grid)
        if len(grid) < 2 or list(grid) != sorted(set(grid)) or grid[0] <= 0:
            raise ValueError(
                f"SurfaceConfig.grid={self.grid!r}: must be >= 2 strictly-"
                "increasing positive scales")


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Fleet tier (deeprest_tpu/serve/fleet.py — ROADMAP item 3): M
    tenant applications on one serving plane through a checkpoint-keyed
    predictor pool.

    ``hbm_budget`` bounds how many tenants' params stay device-resident
    (the LRU working set — evicted tenants spill to host memory and
    restore with one ``device_put``); ``aot`` loads serialized
    executables at admission (serve/aot.py) so a tenant's cold start is
    a deserialize, not a compile; ``top_k_tenants`` bounds per-tenant
    observability cardinality (/metrics labels, /healthz maps — the
    rest rolls up under ``__other__``); ``quality`` attaches one
    QualityMonitor per pool entry (per-tenant /v1/verdict).
    """

    enabled: bool = False
    hbm_budget: int = 4
    aot: bool = True
    top_k_tenants: int = 8
    quality: bool = True

    def __post_init__(self):
        for name in ("hbm_budget", "top_k_tenants"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"FleetConfig.{name}={v!r}: must be an int >= 1")


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical device-mesh shape for pjit/GSPMD execution.

    Axes: ``data`` shards the batch (DP over ICI), ``expert`` shards the
    stacked per-metric experts (EP), ``model`` shards the feature/hidden
    dimensions of the mask and GRU projections (TP) for huge call-path
    spaces.  Pipeline/sequence parallelism are deliberately N/A for this
    model family (window length 60, recurrent core; SURVEY.md §2.5/§5.7).
    """

    data: int = 1
    expert: int = 1
    model: int = 1

    @property
    def size(self) -> int:
        return self.data * self.expert * self.model

    @classmethod
    def parse(cls, spec: str) -> "MeshConfig":
        """``"D,E,M"`` → MeshConfig (the shared ``--mesh`` CLI contract
        for train, serve, predict, and bench)."""
        try:
            d, e, m = (int(x) for x in spec.split(","))
        except ValueError:
            raise ValueError(
                f"mesh spec {spec!r} is not data,expert,model") from None
        if min(d, e, m) < 1:
            raise ValueError(f"mesh spec {spec!r}: axis sizes must be >= 1")
        return cls(data=d, expert=e, model=m)


@dataclasses.dataclass(frozen=True)
class Config:
    model: ModelConfig = dataclasses.field(default_factory=ModelConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    featurize: FeaturizeConfig = dataclasses.field(default_factory=FeaturizeConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    etl: EtlConfig = dataclasses.field(default_factory=EtlConfig)
    infer: InferConfig = dataclasses.field(default_factory=InferConfig)
    obs: ObsConfig = dataclasses.field(default_factory=ObsConfig)
    quality: QualityConfig = dataclasses.field(default_factory=QualityConfig)
    surface: SurfaceConfig = dataclasses.field(default_factory=SurfaceConfig)
    fleet: FleetConfig = dataclasses.field(default_factory=FleetConfig)

    def replace(self, **sections: Any) -> "Config":
        return dataclasses.replace(self, **sections)

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "Config":
        def build(tp, section):
            known = {f.name for f in dataclasses.fields(tp)}
            kwargs = dict(section)
            unknown = set(kwargs) - known
            if unknown:
                raise ValueError(
                    f"unknown {tp.__name__} keys: {sorted(unknown)} "
                    f"(known: {sorted(known)})"
                )
            for k, v in kwargs.items():
                if isinstance(v, list):
                    kwargs[k] = tuple(v)
            return tp(**kwargs)

        return cls(
            model=build(ModelConfig, d.get("model", {})),
            train=build(TrainConfig, d.get("train", {})),
            featurize=build(FeaturizeConfig, d.get("featurize", {})),
            mesh=build(MeshConfig, d.get("mesh", {})),
            etl=build(EtlConfig, d.get("etl", {})),
            infer=build(InferConfig, d.get("infer", {})),
            obs=build(ObsConfig, d.get("obs", {})),
            quality=build(QualityConfig, d.get("quality", {})),
            surface=build(SurfaceConfig, d.get("surface", {})),
            fleet=build(FleetConfig, d.get("fleet", {})),
        )

    @classmethod
    def from_json(cls, s: str) -> "Config":
        return cls.from_dict(json.loads(s))
