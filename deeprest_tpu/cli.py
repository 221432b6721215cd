"""Command-line drivers for the whole pipeline.

The reference drives everything through bare scripts with constants edited
in place (reference: resource-estimation/featurize.py:60, estimate.py:21,
module constants at estimate.py:13-18; SURVEY.md §5.6).  Here each stage is
a subcommand over the typed config:

    python -m deeprest_tpu simulate   --scenario=normal --ticks=480 --out=raw.jsonl
    python -m deeprest_tpu featurize  --raw=raw.jsonl --out=input.npz
    python -m deeprest_tpu train      --features=input.npz --ckpt-dir=ckpt --plots-dir=plots
    python -m deeprest_tpu synthesize --raw=raw.jsonl --mix='{"gateway /compose": 40}' --ticks=120
    python -m deeprest_tpu predict    --ckpt-dir=ckpt --features=input.npz --out=preds.npz
    python -m deeprest_tpu anomaly    --ckpt-dir=ckpt --features=input.npz

``--raw`` accepts the reference pickle format (raw_data.pkl) or the
framework's JSONL stream; ``simulate`` needs no cluster (it uses the
in-process workload simulator — use ``python -m deeprest_tpu.loadgen`` to
capture a corpus from the real native app instead).
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np


# -- shared loaders ---------------------------------------------------------


def _load_buckets(path: str):
    from deeprest_tpu.data.schema import iter_raw_data_jsonl, load_raw_data

    if path.endswith((".jsonl", ".jsl")):
        return list(iter_raw_data_jsonl(path))
    return load_raw_data(path)


def _load_features(args):
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import FeaturizedData, featurize_buckets

    if getattr(args, "features", None):
        return FeaturizedData.load(args.features)
    cfg = FeaturizeConfig(
        capacity=args.capacity, round_to=args.round_to,
        hash_features=args.hash_features,
    )
    return featurize_buckets(_load_buckets(args.raw), cfg,
                             workers=getattr(args, "workers", 1))


def _add_input_args(p: argparse.ArgumentParser, features_ok: bool = True):
    if features_ok:
        p.add_argument("--features", default=None,
                       help="featurized .npz (from the featurize subcommand)")
    p.add_argument("--raw", default=None,
                   help="raw corpus: reference pickle or JSONL stream")
    p.add_argument("--capacity", type=int, default=0,
                   help="feature capacity (0 = size to observed, rounded)")
    p.add_argument("--round-to", type=int, default=128)
    p.add_argument("--hash-features", action="store_true",
                   help="stable hash-bucketing instead of a grown vocabulary")


def _require_input(args, features_ok: bool = True):
    if getattr(args, "features", None) is None and args.raw is None:
        sys.exit("error: provide --raw" + (" or --features" if features_ok else ""))


def _add_fused_infer_args(p: argparse.ArgumentParser):
    p.add_argument("--no-fused-infer", action="store_true",
                   help="serve predictions through the host-loop reference "
                        "path instead of the fused one-dispatch-per-page "
                        "device pipeline (serve/fused.py)")
    p.add_argument("--infer-page-windows", type=int, default=None,
                   metavar="N",
                   help="fused-inference page size in windows (adds a rung "
                        "when off-ladder; default auto: cache-sized small "
                        "pages on CPU, the ladder's top rung on "
                        "accelerators)")
    p.add_argument("--infer-coalesce-pages", type=int, default=None,
                   metavar="G",
                   help="fold up to G consecutive fused-inference pages "
                        "into one dispatch so multi-series/what-if work "
                        "fills page*G recurrence rows (adds super-rungs; "
                        "default auto: 1 on CPU — small pages are "
                        "cache-bound faster there — 4 on accelerators)")
    p.add_argument("--quant", choices=("off", "int8", "bf16"),
                   default="off",
                   help="quantized serving weights (ops/quantize.py): int8 "
                        "stores GRU/dense matrices per-output-channel "
                        "symmetric int8 (~3.9x fewer weight bytes), bf16 "
                        "halves them; dequant happens at use inside the "
                        "same fused executables, drift vs f32 is pinned "
                        "by a parity envelope stored next to the "
                        "checkpoint (violations raise; default off)")


def _add_sparse_args(p: argparse.ArgumentParser, serving: bool = False):
    where = ("the fused engine / shape ladder densifies on device"
             if serving else
             "the staged train feed densifies on device inside the "
             "existing executables")
    p.add_argument("--sparse-feed", action="store_true",
                   help="sparse-first traffic pipeline (the 10k-endpoint "
                        "tier): ship per-window call-path counts as "
                        f"padded-COO (cols, vals) pairs — {where} "
                        "(ops/densify.py) — cutting host->device bytes "
                        "~F/(2K) at 10k width; bit-identical to the "
                        "dense default (tests/test_sparse.py)")
    p.add_argument("--sparse-nnz-cap", type=int, default=64, metavar="K",
                   help="max nonzero traffic columns per bucket under "
                        "--sparse-feed (the padded-COO row width); a "
                        "fatter row raises rather than dropping call "
                        "paths (default 64)")


def _add_elastic_args(p: argparse.ArgumentParser, streaming: bool = False):
    what = ("the interrupted refresh defers through the remesh and "
            "completes (never dropped)" if streaming else
            "the continuation is bit-identical to killing the process "
            "and resuming on the survivor mesh")
    p.add_argument("--elastic", action="store_true",
                   help="survive device loss IN-PROCESS (elastic "
                        "remeshing): catch device-loss failures at the "
                        "step dispatch, shrink the mesh's data axis over "
                        "the surviving devices (expert/model preserved), "
                        "restore the newest cursor snapshot through the "
                        "cross-mesh assembly, and continue — "
                        f"{what}; requires --snapshot-every-steps >= 1")
    p.add_argument("--remesh-max-attempts", type=int, default=3,
                   metavar="N",
                   help="device losses one run may recover from before "
                        "the barrier surfaces the failure instead of "
                        "respinning (default 3)")
    p.add_argument("--remesh-backoff-ms", type=float, default=100.0,
                   metavar="MS",
                   help="backoff slept before each remesh rebuild, "
                        "scaled by the attempt number (default 100)")
    p.add_argument("--snapshot-keep", type=int, default=3, metavar="K",
                   help="newest cursor snapshots retained (snapshot "
                        "retention GC; pruning runs only after a durable "
                        "newer save and never touches the restore "
                        "target or non-cursor checkpoints; 0 = keep "
                        "everything, the historical behavior)")


def _add_mesh_arg(p: argparse.ArgumentParser, serving: bool = False):
    extra = (" (serving: shardings resolve from the same partition-rule "
             "table training pins with — parallel/sharding.py — so "
             "model=N gives the ladder + fused engine feature-axis TP)"
             if serving else
             " (multi-host joins via JAX_COORDINATOR_ADDRESS / pod "
             "metadata first)")
    p.add_argument("--mesh", default=None, metavar="D,E,M",
                   help="device mesh data,expert,model (default 1,1,1)"
                        + extra)


def _parse_mesh(args):
    """``args.mesh`` → MeshConfig | None (exits with a message on a bad
    spec — the shared contract of every --mesh flag)."""
    from deeprest_tpu.config import MeshConfig

    if not getattr(args, "mesh", None):
        return None
    try:
        return MeshConfig.parse(args.mesh)
    except ValueError as exc:
        sys.exit(f"error: {exc}")


def _superstep_arg(v: str):
    """``--steps-per-superstep`` parser: int >= 1, 'auto', or 'epoch'."""
    if v in ("auto", "epoch"):
        return v
    try:
        n = int(v)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"{v!r} is not an int, 'auto', or 'epoch'")
    if n < 1:
        raise argparse.ArgumentTypeError(f"{v} must be >= 1")
    return n


# -- subcommands ------------------------------------------------------------


def cmd_simulate(args) -> int:
    from deeprest_tpu.data.schema import (
        save_raw_data_jsonl, save_raw_data_pickle,
    )
    from deeprest_tpu.workload.scenarios import SCENARIOS
    from deeprest_tpu.workload.simulator import (
        build_shifted_app, build_synthetic_app, simulate_corpus,
        simulate_drift_corpus_iter, write_corpus_jsonl,
    )

    scenario = SCENARIOS[args.scenario](args.seed)
    if args.shift_at:
        # mid-corpus topology change (services added/removed — the drift
        # scenario library; workload/simulator.py owns the generator)
        if args.app != "synthetic":
            sys.exit("error: --shift-at needs --app synthetic (the "
                     "social topology is fixed)")
        after_n = (args.services_after if args.services_after is not None
                   else args.services + max(args.services // 2, 1))
        before, after, endpoints = build_shifted_app(
            scenario, args.services, after_n, args.endpoints, args.seed)
        it = simulate_drift_corpus_iter(scenario, args.ticks,
                                        args.shift_at, before, after,
                                        endpoints)
        if args.out.endswith((".jsonl", ".jsl")):
            n = 0

            def counted():
                nonlocal n
                for b in it:
                    n += 1
                    yield b

            save_raw_data_jsonl(counted(), args.out)
        else:
            buckets = list(it)
            save_raw_data_pickle(buckets, args.out)
            n = len(buckets)
        print(json.dumps({"scenario": args.scenario, "buckets": n,
                          "app": args.app, "shift_at": args.shift_at,
                          "services": [args.services, after_n],
                          "out": args.out}))
        return 0
    app = endpoints = None
    if args.app == "synthetic":
        app, endpoints = build_synthetic_app(scenario, args.services,
                                             args.endpoints, args.seed)
    if args.out.endswith((".jsonl", ".jsl")):
        # streaming write: month-scale corpora never accumulate in memory
        stats = write_corpus_jsonl(scenario, args.ticks, args.out,
                                   app=app, endpoints=endpoints)
        n = stats["buckets"]
    else:
        buckets = simulate_corpus(scenario, args.ticks, app=app,
                                  endpoints=endpoints)
        save_raw_data_pickle(buckets, args.out)
        n = len(buckets)
    print(json.dumps({"scenario": args.scenario, "buckets": n,
                      "app": args.app, "out": args.out}))
    return 0


def _ensure_npz(path: str) -> str:
    """np.savez appends '.npz' when missing — report the real filename."""
    return path if path.endswith(".npz") else path + ".npz"


def cmd_featurize(args) -> int:
    _require_input(args, features_ok=False)
    data = _load_features(args)
    written = data.save(args.out)
    print(json.dumps({
        "out": written,
        "buckets": int(data.traffic.shape[0]),
        "capacity": int(data.traffic.shape[1]),
        "observed_paths": data.space.num_observed,
        "metrics": data.metric_names,
    }))
    return 0


def _parse_metric_map(specs, metric_rule_cls):
    """``PROM_METRIC:RESOURCE[:MODE]`` specs → {metric: MetricRule}.

    None → None (use the cadvisor-style defaults).  An explicitly-empty
    list is honored (traces only, suppress all metrics) rather than
    silently falling back to the default.  Raises ValueError on bad
    entries — a typo'd mode must not silently average a cumulative
    counter into monotonically exploding values.
    """
    if specs is None:
        return None
    resource_map = {}
    for spec in specs:
        parts = spec.split(":")
        if len(parts) not in (2, 3) or not all(parts):
            raise ValueError(f"bad --metric-map entry {spec!r} "
                             "(want prom_metric:resource[:gauge|counter])")
        mode = parts[2] if len(parts) == 3 else "gauge"
        if mode not in ("gauge", "counter"):
            raise ValueError(f"bad --metric-map mode {mode!r} in {spec!r} "
                             "(must be 'gauge' or 'counter')")
        resource_map[parts[0]] = metric_rule_cls(parts[1], mode)
    return resource_map


def cmd_ingest(args) -> int:
    """Jaeger/OTLP trace dumps + Prometheus range dumps → raw JSONL.

    The adapter for pointing the estimator at an EXISTING instrumented
    cluster (reference input contract: resource-estimation/README.md:29-63)
    instead of this framework's own collector.  Sources: trace/metric dump
    FILES (--traces/--prom) or LIVE endpoints (--jaeger-url/--prom-url
    with a time range) — the reference deploys live Jaeger + Prometheus
    services (k8s-yaml/tracing/run.yaml; monitor-openebs-pg.yaml)."""
    from deeprest_tpu.data.ingest import MetricRule, ingest_files, ingest_live
    from deeprest_tpu.data.schema import save_raw_data_jsonl

    try:
        resource_map = _parse_metric_map(args.metric_map, MetricRule)
    except ValueError as exc:
        print(exc)
        return 2
    live = bool(args.jaeger_url or args.prom_url)
    if live and (args.traces or args.prom):
        print("ingest: --traces/--prom dumps and --jaeger-url/--prom-url "
              "are mutually exclusive sources")
        return 2
    if not live and not args.traces:
        print("ingest: need either --traces dump files or a live "
              "--jaeger-url/--prom-url")
        return 2
    if live:
        import time as _time

        end_s = args.end if args.end is not None else _time.time()
        start_s = (args.start if args.start is not None
                   else end_s - args.last_seconds)
        if start_s >= end_s:
            print(f"ingest: empty time range [{start_s}, {end_s})")
            return 2
        buckets = ingest_live(
            args.jaeger_url, args.prom_url, start_s, end_s,
            args.bucket_seconds, step_s=args.step_seconds,
            resource_map=resource_map,
            services=args.jaeger_services or None)
    else:
        buckets = ingest_files(args.traces, args.prom or [],
                               args.bucket_seconds,
                               resource_map=resource_map)
    if not buckets:
        print("ingest: no buckets produced (empty dumps or disjoint ranges)")
        return 1
    save_raw_data_jsonl(buckets, args.out)
    keys = sorted({(m.component, m.resource) for m in buckets[0].metrics})
    print(json.dumps({
        "out": args.out,
        "buckets": len(buckets),
        "traces": sum(len(b.traces) for b in buckets),
        "metric_keys": len(keys),
        "components": sorted({c for c, _ in keys}),
    }))
    return 0


def cmd_train(args) -> int:
    from deeprest_tpu.config import Config, MeshConfig, ModelConfig, TrainConfig
    from deeprest_tpu.models.baselines import baseline_predictions
    from deeprest_tpu.parallel import initialize_distributed
    from deeprest_tpu.train import Trainer, format_report, prepare_dataset

    # Multi-host: join the job when one is configured (env/pod metadata);
    # after this jax.devices() is the global set and --mesh lays the
    # (data, expert, model) axes over it. No-op on a single host.
    if initialize_distributed():
        import jax

        print(f"distributed: process {jax.process_index()} of "
              f"{jax.process_count()}, {len(jax.devices())} global devices",
              flush=True)

    mesh_cfg = _parse_mesh(args) or MeshConfig()

    _require_input(args)
    data = _load_features(args)
    cfg = Config(
        model=ModelConfig(hidden_size=args.hidden_size,
                          dropout_rate=args.dropout,
                          compute_dtype=args.compute_dtype),
        train=TrainConfig(num_epochs=args.epochs, batch_size=args.batch_size,
                          window_size=args.window, learning_rate=args.lr,
                          train_split=args.split, seed=args.seed,
                          eval_stride=args.window,
                          checkpoint_dir=args.ckpt_dir or "",
                          device_data=args.device_data,
                          steps_per_superstep=args.steps_per_superstep,
                          grad_accum_windows=args.grad_accum_windows,
                          sparse_feed=args.sparse_feed,
                          sparse_nnz_cap=args.sparse_nnz_cap,
                          snapshot_every_steps=args.snapshot_every_steps,
                          snapshot_keep=args.snapshot_keep,
                          elastic=args.elastic,
                          remesh_max_attempts=args.remesh_max_attempts,
                          remesh_backoff_ms=args.remesh_backoff_ms),
        mesh=mesh_cfg,
    )
    bundle = prepare_dataset(data, cfg.train)
    baselines = None
    if not args.no_baselines:
        baselines = baseline_predictions(data, bundle)

    trainer = Trainer(cfg, bundle.feature_dim, bundle.metric_names)

    # ML-plane profiling (SURVEY.md §5.1: the reference has nothing beyond
    # epoch prints).  --profile-dir traces the SECOND epoch (the first
    # steady one; the first compiles) through Trainer.profile_epoch, which
    # reads the trace back: the device's time by the named scopes of the
    # train step, its idle gaps by the epoch's host phases.  The table is
    # printed and written as layers.json beside the trace.
    def report_profile():
        table, trainer.last_profile = trainer.last_profile, None
        if table is None:
            return
        import os

        from deeprest_tpu.obs.profiler import format_table

        path = os.path.join(args.profile_dir, "layers.json")
        with open(path, "w") as fh:
            json.dump(table, fh, indent=1)
        print(format_table(table), flush=True)
        print(f"profiler trace and {path} written to {args.profile_dir}",
              flush=True)

    # What came before the first steady step, once, when the first epoch
    # of this process is done (obs/setup.py): seconds of init_state by
    # phase, of staging, of each first dispatch, the compilations by
    # program and phase, device memory, the superstep executable's bytes.
    setup_reported = False

    def on_epoch(result, state):
        nonlocal setup_reported
        report_profile()
        if not setup_reported:
            from deeprest_tpu.obs.setup import format_setup, setup_table

            setup_reported = True
            print(format_setup(setup_table()), flush=True)
        line = (f"epoch {result.epoch}: train {result.train_loss:.4f}"
                + (f" test {result.test_loss:.4f}" if result.test_loss else ""))
        print(line, flush=True)
        if args.report_every and (result.epoch + 1) % args.report_every == 0:
            print(format_report(result.report), flush=True)

    # Preemption-safe restarts: with --snapshot-every-steps and a cursor
    # snapshot already on disk, re-running the SAME command resumes the
    # killed run (plan replay, bit-identical to uninterrupted) instead of
    # restarting from scratch — the operator's contract is simply "run it
    # again".
    resume = False
    if args.snapshot_every_steps and args.ckpt_dir:
        from deeprest_tpu.train.checkpoint import latest_cursor_step

        resume = latest_cursor_step(args.ckpt_dir) is not None
        if resume:
            print(f"resuming preempted run from {args.ckpt_dir} "
                  "(newest cursor snapshot)", flush=True)
    if resume:
        state, history = trainer.resume_training(
            bundle, baseline_preds=baselines, on_epoch=on_epoch,
            profile_dir=args.profile_dir)
    else:
        state, history = trainer.fit(bundle, baseline_preds=baselines,
                                     on_epoch=on_epoch,
                                     profile_dir=args.profile_dir)
    if history:
        print(format_report(history[-1].report))
    else:
        print("resume point is already past the final epoch; nothing to do")
    print(f"steady-state throughput: {trainer.throughput.steps_per_sec:.2f} steps/s")

    if args.plots_dir:
        import os

        from deeprest_tpu.train.data import eval_window_indices
        from deeprest_tpu.train.plots import learning_curves, prediction_plots

        learning_curves(history,
                        os.path.join(args.plots_dir, "learning_curve.png"))
        idx = eval_window_indices(len(bundle.x_test), cfg.train.eval_stride,
                                  cfg.train.eval_max_cycles)
        preds = trainer.predict(state, bundle.x_test[idx])   # [N, W, E, Q]
        med = trainer.model.median_index()
        # Delta-trained columns plot in LEVEL space via the bundle's shared
        # reconstruction (the same contract trainer.evaluate reports).
        labels = bundle.level_labels(idx)
        denorm = lambda q: bundle.integrate_test_preds(
            bundle.denorm_targets(np.maximum(preds[..., q], 1e-6)), idx)
        prediction_plots(
            denorm(med), labels,
            bundle.metric_names, args.plots_dir,
            quantile_band=(denorm(0), denorm(preds.shape[-1] - 1)),
        )
        print(f"plots written to {args.plots_dir}")
    return 0


def cmd_synthesize(args) -> int:
    from deeprest_tpu.data.synthesize import TraceSynthesizer

    _require_input(args, features_ok=False)
    buckets = _load_buckets(args.raw)
    from deeprest_tpu.config import FeaturizeConfig
    from deeprest_tpu.data.featurize import CallPathSpace

    if args.ckpt_dir:
        # Use the checkpoint's training-time space so the synthesized
        # columns are exact for that model by construction.
        from deeprest_tpu.serve.predictor import Predictor

        space = Predictor.from_checkpoint(args.ckpt_dir).space()
        if space is None:
            sys.exit("error: checkpoint has no feature space")
    else:
        space = CallPathSpace(config=FeaturizeConfig(
            capacity=args.capacity, round_to=args.round_to,
            hash_features=args.hash_features))
    synth = TraceSynthesizer(space).fit(buckets)
    mix = json.loads(args.mix)
    series = synth.synthesize_series([mix] * args.ticks, seed=args.seed)
    out = _ensure_npz(args.out)
    # Embed the space so `predict --features` can verify column identity
    # against the serving checkpoint (same contract as FeaturizedData.save);
    # a bare traffic array would silently bypass that guard.
    np.savez_compressed(
        out, traffic=series.astype(np.float32),
        space_json=np.frombuffer(
            json.dumps(space.to_dict()).encode(), dtype=np.uint8),
    )
    print(json.dumps({"out": out, "ticks": args.ticks,
                      "endpoints": synth.endpoints,
                      "capacity": int(space.capacity)}))
    return 0


def cmd_stream(args) -> int:
    """Continuous retrain: tail a growing raw-data JSONL — or poll live
    Jaeger/Prometheus endpoints — fine-tune, and re-checkpoint
    (BASELINE.json config 5; train/stream.py docstring has the
    drift-handling design)."""
    from deeprest_tpu.config import (
        Config, EtlConfig, FeaturizeConfig, ModelConfig, TrainConfig,
    )
    from deeprest_tpu.train.stream import (
        BucketTailer, StreamConfig, StreamingTrainer,
    )

    live = bool(args.jaeger_url or args.prom_url)
    wire = bool(args.wire_listen)
    if sum((bool(args.raw), live, wire)) != 1:
        print("stream: need exactly one source — --raw JSONL, live "
              "--jaeger-url/--prom-url endpoints, or a --wire-listen "
              "push receiver")
        return 2
    if wire and not args.sparse_feed:
        print("stream: --wire-listen requires the sparse feed "
              "(the firehose is sparse-first by design; drop "
              "--no-sparse-feed)")
        return 2
    if args.metric_map is not None and not live:
        # Silently ignoring it would hide a typo'd pipeline config.
        print("stream: --metric-map only applies to the live "
              "Jaeger/Prometheus source, not --raw JSONL")
        return 2

    from deeprest_tpu.config import QualityConfig

    quality = None
    if args.drift_detect:
        quality = QualityConfig(
            enabled=True,
            sweep_every_buckets=args.drift_sweep_every,
            live_window=args.drift_live_window,
            reference_window=args.drift_reference_window,
            drift_enter=args.drift_enter, drift_exit=args.drift_exit,
            auto_retrain=not args.no_drift_auto_retrain,
            retrain_cooldown_buckets=args.drift_cooldown_buckets)
    cfg = Config(
        model=ModelConfig(feature_dim=args.capacity,
                          hidden_size=args.hidden_size,
                          compute_dtype=args.compute_dtype),
        train=TrainConfig(batch_size=args.batch_size, window_size=args.window,
                          learning_rate=args.lr, seed=args.seed,
                          eval_stride=1, eval_max_cycles=args.eval_holdout,
                          log_every_steps=0,
                          steps_per_superstep=args.steps_per_superstep,
                          grad_accum_windows=args.grad_accum_windows,
                          sparse_feed=args.sparse_feed,
                          sparse_nnz_cap=args.sparse_nnz_cap,
                          snapshot_every_steps=args.snapshot_every_steps,
                          snapshot_keep=args.snapshot_keep,
                          elastic=args.elastic,
                          remesh_max_attempts=args.remesh_max_attempts,
                          remesh_backoff_ms=args.remesh_backoff_ms),
        etl=EtlConfig(overlap=not args.no_etl_overlap,
                      queue_depth=args.etl_queue_depth),
        quality=quality or QualityConfig(),
    )
    st = StreamingTrainer(
        cfg,
        StreamConfig(refresh_buckets=args.refresh_buckets,
                     finetune_epochs=args.finetune_epochs,
                     history_max=args.history_max,
                     eval_holdout=args.eval_holdout,
                     poll_interval_s=args.poll_interval,
                     keep_checkpoints=args.keep_checkpoints),
        ckpt_dir=args.ckpt_dir,
        feature_config=FeaturizeConfig(hash_features=True,
                                       capacity=args.capacity,
                                       hash_seed=args.hash_seed),
    )
    receiver = None
    if live:
        from deeprest_tpu.data.ingest import LiveEndpointTailer, MetricRule

        try:
            rmap = _parse_metric_map(args.metric_map, MetricRule)
        except ValueError as exc:
            print(exc)
            return 2
        tailer = LiveEndpointTailer(
            jaeger_url=args.jaeger_url, prom_url=args.prom_url,
            bucket_s=args.bucket_seconds, resource_map=rmap)
    elif wire:
        from deeprest_tpu.data.wire import (
            SpanFirehoseReceiver, parse_hostport,
        )

        host, port = parse_hostport(args.wire_listen)
        # The receiver featurizes in its handler threads against the
        # trainer's own CallPathSpace, so wire rows land in the ring
        # bit-identical to the tailer path (tests/test_wire.py pins it).
        receiver = SpanFirehoseReceiver(
            host, port, space=st.space, sparse=True,
            queue_depth=args.wire_queue_depth).start()
        print(json.dumps({"wire_listen": "%s:%d" % receiver.address}),
              flush=True)
        tailer = receiver
    else:
        tailer = BucketTailer(args.raw)
    controller = None
    if quality is not None:
        from deeprest_tpu.train.stream import DriftController

        controller = DriftController(st, quality)
    try:
        for r in st.run(tailer,
                        max_refreshes=args.max_refreshes or None,
                        deadline_s=args.deadline or None):
            rec = {
                "refresh": r.refresh, "buckets": r.num_buckets,
                "train_loss": round(r.train_loss, 6),
                "eval_loss": round(r.eval_loss, 6),
                "checkpoint": r.checkpoint_path,
                "trigger": r.trigger,
                "etl": {"stall_s": round(r.etl_stall_s, 4),
                        "lag_buckets": r.etl_lag_buckets,
                        "dropped": r.etl_dropped},
            }
            if receiver is not None:
                rec["wire"] = receiver.stats()
            if controller is not None and controller.monitor is not None:
                v = controller.monitor.verdicts()
                rec["quality"] = {"states": v.get("states"),
                                  "feature_drift":
                                      v["feature_drift"].get("state"),
                                  "psi": v["feature_drift"].get("psi"),
                                  **{k: controller.stats[k]
                                     for k in ("sweeps",
                                               "retrains_triggered")}}
            print(json.dumps(rec), flush=True)
    finally:
        if receiver is not None:
            receiver.close()
    return 0


def cmd_whatif(args) -> int:
    """What-if capacity estimation from the command line: a hypothetical
    traffic mix (optionally swept over a scale grid) → per-metric peak
    utilization, batched through the fused multi-scenario prediction
    pipeline (serve/whatif.py estimate_many / sweep)."""
    from deeprest_tpu.data.synthesize import TraceSynthesizer
    from deeprest_tpu.serve.predictor import Predictor
    from deeprest_tpu.serve.whatif import WhatIfEstimator

    pred = Predictor.from_checkpoint(
        args.ckpt_dir, fused=not args.no_fused_infer,
        page_windows=args.infer_page_windows,
        coalesce_pages=args.infer_coalesce_pages,
        mesh_config=_parse_mesh(args),
        quant=getattr(args, "quant", "off"))
    space = pred.space()
    if space is None:
        sys.exit("error: checkpoint has no feature space; cannot fit the "
                 "what-if synthesizer from --raw")
    synth = TraceSynthesizer(space).fit(_load_buckets(args.raw))
    est = WhatIfEstimator(pred, synth)
    try:
        mix = {str(k): int(v) for k, v in json.loads(args.mix).items()}
    except (ValueError, AttributeError) as exc:
        sys.exit(f"error: --mix is not a JSON endpoint→count object: {exc}")
    unknown = sorted(set(mix) - set(est.endpoints))
    if unknown:
        sys.exit(f"error: unknown API endpoints {unknown} "
                 f"(known: {est.endpoints})")
    program = [mix] * args.ticks
    if args.sweep:
        try:
            factors = [float(f) for f in args.sweep.split(",")]
        except ValueError:
            sys.exit(f"error: --sweep {args.sweep!r} is not a "
                     "comma-separated list of scale factors")
        records = est.sweep(program, factors, seed=args.seed)
        result = {"ticks": args.ticks, "mix": mix, "sweep": records}
    else:
        bands = est.estimate(program, seed=args.seed)
        dm = pred.delta_mask
        peaks = {
            metric: {q: (max(float(np.max(s) - s[0]), 0.0)
                         if dm is not None and dm[e]
                         else float(np.max(s)))
                     for q, s in bands[metric].items()}
            for e, metric in enumerate(pred.metric_names)
        }
        result = {"ticks": args.ticks, "mix": mix, "peaks": peaks}
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(result, f, indent=2, sort_keys=True)
        result["out"] = args.out
    print(json.dumps(result))
    return 0


def cmd_export(args) -> int:
    """Checkpoint → portable inference artifact (serve/export.py), plus
    optional AOT executable sidecars next to the checkpoint (--aot)."""
    from deeprest_tpu.serve.export import export_aot_sidecar, export_predictor
    from deeprest_tpu.serve.predictor import Predictor

    pred = Predictor.from_checkpoint(args.ckpt_dir)
    out = export_predictor(pred, args.out)
    result = {
        "out": out,
        "metrics": len(pred.metric_names),
        "feature_dim": pred.feature_dim,
        "window_size": pred.window_size,
    }
    if args.aot:
        # fleet cold-start artifacts (serve/aot.py): pool admission of
        # this checkpoint becomes a deserialize, not a compile
        result["aot"] = export_aot_sidecar(pred, args.ckpt_dir)
    print(json.dumps(result))
    return 0


def _parse_tenant_weights(spec: str | None) -> dict[str, float] | None:
    """``a=3,b=1`` → {"a": 3.0, "b": 1.0} (None/empty → None)."""
    if not spec:
        return None
    out = {}
    for part in spec.split(","):
        name, _, w = part.partition("=")
        try:
            out[name.strip()] = float(w)
        except ValueError:
            raise ValueError(f"bad --tenant-weights entry {part!r} "
                             "(want name=weight)") from None
        if not name.strip() or out[name.strip()] <= 0:
            raise ValueError(f"bad --tenant-weights entry {part!r} "
                             "(weight must be > 0)")
    return out


def _load_autoscaler_module():
    """deploy/autoscaler.py is deployment-plane code living next to the
    manifests it rewrites; load it by path from the repo layout."""
    import importlib.util
    import os

    import deeprest_tpu

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(
            deeprest_tpu.__file__))), "deploy", "autoscaler.py")
    if not os.path.isfile(path):
        sys.exit(f"error: autoscaler module not found at {path}")
    spec = importlib.util.spec_from_file_location("deeprest_autoscaler", path)
    mod = importlib.util.module_from_spec(spec)
    # register BEFORE exec: the module's @dataclass decorators resolve
    # sys.modules[cls.__module__] at class-creation time (py3.10)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cmd_serve(args) -> int:
    """Serve predict / what-if / anomaly over HTTP from a checkpoint or an
    exported artifact (serve/server.py), with cross-request micro-batching
    on by default (serve/batcher.py; disable with --no-batcher).  With
    --replicas N the backend becomes a routing front over N engine
    replicas (serve/router.py): least-outstanding-work dispatch, bounded
    admission (--admission-depth → fast 429 + Retry-After), per-tenant
    weighted round-robin on the X-Tenant header, zero-downtime rolling
    reload under --watch, and an optional self-sizing control loop
    (--autoscale, deploy/autoscaler.py)."""
    from deeprest_tpu.serve.batcher import BatcherConfig
    from deeprest_tpu.serve.server import (
        CheckpointReloader, PredictionServer, PredictionService,
    )

    if bool(args.ckpt_dir) == bool(args.artifact):
        sys.exit("error: provide exactly one of --ckpt-dir or --artifact")
    try:
        ladder = tuple(int(r) for r in args.batch_ladder.split(","))
    except ValueError:
        sys.exit(f"error: --batch-ladder {args.batch_ladder!r} is not a "
                 "comma-separated list of window counts")
    if not ladder or min(ladder) < 1:
        sys.exit(f"error: --batch-ladder {args.batch_ladder!r}: rungs must "
                 "be >= 1")
    if args.batch_coalesce_groups < 1:
        sys.exit(f"error: --batch-coalesce-groups "
                 f"{args.batch_coalesce_groups} must be >= 1")
    batching = None
    if not args.no_batcher:
        top = max(ladder) * args.batch_coalesce_groups
        if args.batch_max_windows > top:
            sys.exit(f"error: --batch-max-windows {args.batch_max_windows} "
                     f"exceeds the top (coalesced) ladder rung {top}")
        batching = BatcherConfig(max_batch=args.batch_max_windows,
                                 max_linger_s=args.batch_linger_ms / 1e3)
    if args.watch and not args.ckpt_dir:
        sys.exit("error: --watch requires --ckpt-dir (artifacts are "
                 "immutable; re-export and restart instead)")
    if args.watch < 0:
        sys.exit(f"error: --watch {args.watch} must be >= 0")
    # Observability (deeprest_tpu/obs): span recording is ON by default
    # for the serving plane (it is the subsystem's reason to exist here);
    # /metrics answers either way — metrics counters are always live.
    from deeprest_tpu import obs

    if args.obs_span_capacity < 1:
        sys.exit(f"error: --obs-span-capacity {args.obs_span_capacity} "
                 "must be >= 1")
    obs.configure(enabled=not args.no_obs,
                  span_capacity=args.obs_span_capacity)
    mesh_cfg = _parse_mesh(args)
    if mesh_cfg is not None and args.artifact:
        sys.exit("error: --mesh requires --ckpt-dir (exported artifacts "
                 "bake single-device params; re-serve from the checkpoint "
                 "to shard them)")
    reloader = None
    if args.ckpt_dir:
        from deeprest_tpu.serve.predictor import Predictor

        if args.watch:
            # Built BEFORE the initial load: a checkpoint the live trainer
            # writes while we load would otherwise be recorded as already
            # served and never reloaded. Worst case of this ordering is one
            # redundant reload of the step we are about to serve anyway.
            reloader = CheckpointReloader(
                args.ckpt_dir, min_interval_s=args.watch, ladder=ladder,
                fused=not args.no_fused_infer,
                page_windows=args.infer_page_windows,
                coalesce_pages=args.infer_coalesce_pages,
                coalesce_groups=args.batch_coalesce_groups,
                sparse_feed=args.sparse_feed,
                sparse_nnz_cap=args.sparse_nnz_cap,
                mesh_config=mesh_cfg,
                quant=args.quant)
        pred = Predictor.from_checkpoint(
            args.ckpt_dir, ladder=ladder, fused=not args.no_fused_infer,
            page_windows=args.infer_page_windows,
            coalesce_pages=args.infer_coalesce_pages,
            coalesce_groups=args.batch_coalesce_groups,
            sparse_feed=args.sparse_feed,
            sparse_nnz_cap=args.sparse_nnz_cap,
            mesh_config=mesh_cfg,
            quant=args.quant)
        backend = f"checkpoint:{args.ckpt_dir}"
        if reloader is not None:
            backend += " (watching)"
    else:
        from deeprest_tpu.serve.export import ExportedPredictor

        pred = ExportedPredictor.load(
            args.artifact, ladder=ladder, fused=not args.no_fused_infer,
            page_windows=args.infer_page_windows,
            coalesce_pages=args.infer_coalesce_pages,
            coalesce_groups=args.batch_coalesce_groups,
            quant=args.quant)
        backend = f"artifact:{args.artifact}"

    # -- multi-replica routing front (serve/router.py) -------------------
    base_pred = pred           # pre-router reference: the fleet template
    autoscaler = None
    if args.replicas > 1 or args.admission_depth or args.tenant_weights:
        from deeprest_tpu.serve.router import ReplicaRouter, RouterConfig

        try:
            weights = _parse_tenant_weights(args.tenant_weights)
        except ValueError as exc:
            sys.exit(f"error: {exc}")
        if args.replica_timeout_ms < 0:
            sys.exit(f"error: --replica-timeout-ms "
                     f"{args.replica_timeout_ms} must be >= 0 (0 = none)")
        router_cfg = RouterConfig(
            admission_depth=args.admission_depth or 64,
            max_wait_s=args.admission_wait_ms / 1e3,
            retry_after_s=args.admission_retry_after_ms / 1e3,
            tenant_weights=weights,
            replica_timeout_s=(args.replica_timeout_ms / 1e3
                               if args.replica_timeout_ms else None),
            eject_after_failures=args.eject_after_failures,
            retry_budget=args.retry_budget)
        if args.replica_mode == "process":
            if not (args.ckpt_dir or args.artifact):
                sys.exit("error: --replica-mode=process needs --ckpt-dir "
                         "or --artifact (workers rebuild their own stacks)")
            spec = {"ckpt_dir": args.ckpt_dir, "artifact": args.artifact,
                    "kwargs": {"ladder": ladder,
                               "fused": not args.no_fused_infer,
                               "page_windows": args.infer_page_windows,
                               "coalesce_pages": args.infer_coalesce_pages,
                               "coalesce_groups":
                                   args.batch_coalesce_groups,
                               "quant": args.quant}}
            pred = ReplicaRouter.build_process(
                spec, args.replicas, config=router_cfg, batching=batching)
        else:
            pred = ReplicaRouter.build(
                pred, args.replicas, config=router_cfg, batching=batching)
        batching = None          # the router owns per-replica batchers
        backend = f"{backend} x{args.replicas} ({args.replica_mode})"

        if args.autoscale:
            mod = _load_autoscaler_module()
            autoscaler = mod.Autoscaler(
                pred,
                mod.AutoscalerConfig(
                    min_replicas=args.autoscale_min,
                    max_replicas=args.autoscale_max,
                    interval_s=args.autoscale_interval,
                    capacity_rps_per_replica=args.autoscale_rps_per_replica),
                manifest_path=args.autoscale_manifest or None).start()
    elif args.autoscale:
        sys.exit("error: --autoscale needs --replicas > 1 (the router is "
                 "the autoscaler's actuator)")

    # -- fleet tier (serve/fleet.py): M tenants on this plane ------------
    fleet_pool = None
    if args.fleet:
        from deeprest_tpu.config import FleetConfig, QualityConfig
        from deeprest_tpu.serve.fleet import PredictorPool
        from deeprest_tpu.serve.predictor import Predictor

        if not args.ckpt_dir:
            sys.exit("error: --fleet needs --ckpt-dir (tenant pools hold "
                     "Predictor params; artifacts bake theirs in)")
        if args.replica_mode == "process" and args.replicas > 1:
            sys.exit("error: --fleet needs --replica-mode=thread (the "
                     "per-request backend override would re-ship tenant "
                     "params over the worker pipe)")
        try:
            fleet_cfg = FleetConfig(
                enabled=True, hbm_budget=args.fleet_hbm_budget,
                aot=not args.no_fleet_aot,
                top_k_tenants=args.fleet_top_k,
                quality=not args.no_fleet_quality)
        except ValueError as e:
            sys.exit(f"error: {e}")
        fleet_pool = PredictorPool(
            hbm_budget=fleet_cfg.hbm_budget, aot=fleet_cfg.aot,
            quality_config=(QualityConfig(enabled=True)
                            if fleet_cfg.quality else None),
            top_k_tenants=fleet_cfg.top_k_tenants)
        # the serving backend is the default tenant AND the executable
        # template; its AOT sidecar (deeprest export --aot) warms the
        # whole plane — later tenants adopt, never compile
        fleet_pool.admit("default", base_pred,
                         checkpoint_path=args.ckpt_dir)
        for spec_item in args.fleet:
            name, _, ckpt = spec_item.partition("=")
            if not name.strip() or not ckpt.strip():
                sys.exit(f"error: bad --fleet entry {spec_item!r} "
                         "(want tenant=checkpoint_dir)")
            tenant_pred = Predictor.from_checkpoint(
                ckpt.strip(), ladder=ladder,
                fused=not args.no_fused_infer,
                page_windows=args.infer_page_windows,
                coalesce_pages=args.infer_coalesce_pages,
                coalesce_groups=args.batch_coalesce_groups,
                sparse_feed=args.sparse_feed,
                sparse_nnz_cap=args.sparse_nnz_cap,
                quant=args.quant)
            try:
                fleet_pool.admit(name.strip(), tenant_pred,
                                 checkpoint_path=ckpt.strip())
            except ValueError as e:
                sys.exit(f"error: {e}")

    synthesizer = None
    if args.raw:
        from deeprest_tpu.data.synthesize import TraceSynthesizer

        space = pred.space()
        if space is None:
            sys.exit("error: model has no feature space; cannot fit the "
                     "what-if synthesizer from --raw")
        synthesizer = TraceSynthesizer(space).fit(_load_buckets(args.raw))

    surface_cfg = None
    if args.surface:
        from deeprest_tpu.config import SurfaceConfig

        if synthesizer is None:
            sys.exit("error: --surface needs --raw (capacity surfaces are "
                     "built through the what-if synthesizer)")
        try:
            surface_cfg = SurfaceConfig(
                enabled=True,
                grid=tuple(float(x)
                           for x in args.surface_grid.split(",") if x),
                max_axes=args.surface_max_axes,
                jitter=args.surface_jitter,
                max_surfaces=args.surface_max_surfaces,
                max_bytes=int(args.surface_max_bytes_mb * 1024 * 1024),
                warm_async=not args.surface_sync)
        except ValueError as e:
            sys.exit(f"error: {e}")

    service = PredictionService(pred, synthesizer, backend=backend,
                                reloader=reloader, batching=batching,
                                surface=surface_cfg)
    if fleet_pool is not None:
        service.attach_fleet(fleet_pool)
    verdict_wire = getattr(args, "verdict_wire_listen", None)
    if args.verdict_raw and verdict_wire:
        sys.exit("error: --verdict-raw and --verdict-wire-listen are "
                 "alternative verdict-corpus sources; pick one")
    if args.verdict_raw or verdict_wire:
        from deeprest_tpu.config import QualityConfig
        from deeprest_tpu.obs.quality import QualityMonitor
        from deeprest_tpu.serve.server import VerdictIngestor
        from deeprest_tpu.train.stream import BucketTailer

        space = pred.space()
        if space is None:
            sys.exit("error: model has no feature space; the verdict "
                     "surface needs the training-time call-path space to "
                     "featurize the tailed corpus")
        monitor = QualityMonitor(
            list(pred.metric_names),
            QualityConfig(enabled=True,
                          sweep_every_buckets=args.verdict_sweep_every,
                          live_window=args.verdict_live_window))
        if verdict_wire:
            from deeprest_tpu.data.wire import (
                SpanFirehoseReceiver, parse_hostport,
            )

            # Bucket-mode receiver: the VerdictIngestor featurizes the
            # buckets itself, so the wire stays a transport here (the
            # featurized fast path belongs to the stream plane).
            whost, wport = parse_hostport(verdict_wire)
            vtailer = SpanFirehoseReceiver(whost, wport).start()
            service.attach_wire(vtailer)
        else:
            vtailer = BucketTailer(args.verdict_raw)
        ingestor = VerdictIngestor(service, vtailer,
                                   space, monitor).start()
        service.attach_quality(monitor, ingestor)
    server = PredictionServer(service, host=args.host, port=args.port)
    host, port = server.address
    print(json.dumps({"listening": f"http://{host}:{port}",
                      "backend": backend,
                      "whatif": synthesizer is not None,
                      "surface": ({"grid": list(surface_cfg.grid),
                                   "max_axes": surface_cfg.max_axes,
                                   "jitter": surface_cfg.jitter,
                                   "max_surfaces": surface_cfg.max_surfaces,
                                   "max_bytes": surface_cfg.max_bytes}
                                  if surface_cfg is not None else None),
                      "replicas": args.replicas,
                      "fleet": ({"tenants": fleet_pool.tenants(),
                                 "hbm_budget": fleet_pool.hbm_budget,
                                 "aot": fleet_pool.stats()["aot"]}
                                if fleet_pool is not None else None),
                      "autoscale": autoscaler is not None,
                      "verdict": ({"raw": args.verdict_raw,
                                   "wire": ("%s:%d" % vtailer.address
                                            if verdict_wire else None),
                                   "sweep_every": args.verdict_sweep_every}
                                  if (args.verdict_raw or verdict_wire)
                                  else None),
                      "obs": {"spans": not args.no_obs,
                              "span_capacity": args.obs_span_capacity,
                              "metrics": "/metrics"},
                      "batching": (None if args.no_batcher else {
                          "max_batch": args.batch_max_windows,
                          "max_linger_ms": args.batch_linger_ms,
                          "ladder": list(ladder),
                      })}), flush=True)
    try:
        if args.deadline:
            server.start()
            import time as _time

            _time.sleep(args.deadline)
            server.stop()
        else:
            try:
                server.serve_forever()
            except KeyboardInterrupt:    # SIGINT is the operator's stop
                pass
            server.stop()
    finally:
        if autoscaler is not None:
            autoscaler.stop()
    return 0


def _predictor(args):
    from deeprest_tpu.serve.predictor import Predictor

    # model architecture comes from the checkpoint sidecar
    return Predictor.from_checkpoint(
        args.ckpt_dir,
        fused=not getattr(args, "no_fused_infer", False),
        page_windows=getattr(args, "infer_page_windows", None),
        coalesce_pages=getattr(args, "infer_coalesce_pages", None),
        mesh_config=_parse_mesh(args),
        quant=getattr(args, "quant", "off"))


def _serving_traffic(args, pred) -> np.ndarray:
    """Traffic features for serving, column-exact with the checkpoint.

    ``--features`` artifacts embed the space they were extracted with,
    which must equal the checkpoint's (matching width alone would let a
    permuted vocabulary through); ``--raw`` corpora are featurized against
    the *checkpoint's* space (the training vocabulary) directly.
    """
    if args.features and not args.raw:
        with np.load(_ensure_npz(args.features)) as z:
            traffic = np.asarray(z["traffic"])
            space_json = (bytes(z["space_json"]).decode()
                          if "space_json" in z else None)
        if space_json is not None and pred.space_dict is not None:
            embedded = json.loads(space_json)
            if embedded["vocabulary"] != pred.space_dict["vocabulary"]:
                sys.exit("error: the features file was extracted with a "
                         "different call-path vocabulary than the checkpoint "
                         "was trained on; re-featurize the raw corpus with "
                         "--raw (uses the checkpoint's space)")
    else:
        space = pred.space()
        if space is None:
            sys.exit("error: checkpoint has no feature space; featurize the "
                     "raw corpus with the training-time space and pass "
                     "--features instead of --raw")
        from deeprest_tpu.data.featurize import featurize_buckets

        traffic = featurize_buckets(_load_buckets(args.raw),
                                    space=space).traffic
    if traffic.shape[1] != pred.feature_dim:
        sys.exit(f"error: feature dim {traffic.shape[1]} != model "
                 f"{pred.feature_dim}")
    return traffic


def cmd_predict(args) -> int:
    _require_input(args)
    pred = _predictor(args)
    traffic = _serving_traffic(args, pred)
    out_path = _ensure_npz(args.out)
    out = pred.predict_series(traffic)                    # [T, E, Q]
    np.savez_compressed(out_path, predictions=out,
                        metric_names=np.array(pred.metric_names))
    print(json.dumps({"out": out_path, "steps": int(out.shape[0]),
                      "metrics": pred.metric_names}))
    return 0


def cmd_anomaly(args) -> int:
    from deeprest_tpu.serve.anomaly import AnomalyDetector

    _require_input(args)
    pred = _predictor(args)
    if args.features and not args.raw:
        from deeprest_tpu.data.featurize import FeaturizedData

        data = FeaturizedData.load(args.features)
        # Same vocabulary-identity guard as `predict --features`: equal
        # width with a permuted vocabulary would silently produce bogus
        # anomaly reports.
        if (pred.space_dict is not None
                and data.space.to_dict()["vocabulary"]
                != pred.space_dict["vocabulary"]):
            sys.exit("error: the features file was extracted with a "
                     "different call-path vocabulary than the checkpoint "
                     "was trained on; re-featurize the raw corpus with "
                     "--raw (uses the checkpoint's space)")
    else:
        # featurize against the checkpoint's space for column exactness
        space = pred.space()
        if space is None:
            sys.exit("error: checkpoint has no feature space; pass --features")
        from deeprest_tpu.data.featurize import featurize_buckets

        data = featurize_buckets(_load_buckets(args.raw), space=space)
    if list(data.metric_names) != list(pred.metric_names):
        sys.exit("error: corpus metrics do not match the checkpoint's")
    if data.traffic.shape[1] != pred.feature_dim:
        sys.exit(f"error: feature dim {data.traffic.shape[1]} != model "
                 f"{pred.feature_dim}")
    detector = AnomalyDetector(pred, tolerance=args.tolerance,
                               min_run=args.min_run)
    reports = detector.check(data.traffic, data.targets())
    for r in reports:
        print(r)
    flagged = [r.metric for r in reports if r.flagged]
    print(json.dumps({"flagged": flagged}))
    return 1 if flagged and args.fail_on_anomaly else 0


def cmd_profile(args) -> int:
    """Open a jax.profiler capture window on a RUNNING serving plane
    (POST /v1/profile — obs/profiler.py): the server keeps answering
    traffic on its other handler threads while the window is open, so
    the trace shows the plane under its live load.  The answer holds the
    trace's directory and ``layers``: device busy and idle, the kernels'
    time by name, the idle gaps under the serving spans that cover them."""
    import urllib.error
    import urllib.request

    if args.seconds <= 0:
        sys.exit(f"error: --seconds {args.seconds} must be > 0")
    payload = {"seconds": args.seconds}
    if args.out_dir:
        payload["out_dir"] = args.out_dir
    req = urllib.request.Request(
        args.url.rstrip("/") + "/v1/profile",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req,
                                    timeout=args.seconds + 60.0) as resp:
            body = json.loads(resp.read().decode())
    except urllib.error.HTTPError as exc:
        detail = exc.read().decode(errors="replace")[:300]
        sys.exit(f"error: server answered {exc.code}: {detail}")
    except (urllib.error.URLError, OSError) as exc:
        sys.exit(f"error: cannot reach {args.url}: {exc}")
    print(json.dumps(body))
    return 0


def _git_changed_python_files(anchor_dir: str) -> list[str] | None:
    """Repo-relative .py paths changed vs HEAD (staged + unstaged) plus
    untracked ones, or None when ``anchor_dir`` is not in a git work
    tree — the ``deeprest lint --changed`` file selector."""
    import subprocess

    def git(*argv):
        return subprocess.run(["git", "-C", anchor_dir, *argv],
                              capture_output=True, text=True)

    if git("rev-parse", "--show-toplevel").returncode != 0:
        return None
    changed: set[str] = set()
    for argv in (("diff", "--name-only", "HEAD"),
                 ("ls-files", "--others", "--exclude-standard")):
        out = git(*argv)
        if out.returncode != 0:
            continue
        changed.update(line.strip() for line in out.stdout.splitlines()
                       if line.strip().endswith(".py"))
    return sorted(changed)


def _component_suffix_match(a: str, b: str) -> bool:
    """Lint-root-relative and repo-relative spellings of the same file
    agree on their trailing path components."""
    pa = a.replace("\\", "/").split("/")
    pb = b.replace("\\", "/").split("/")
    k = min(len(pa), len(pb))
    return k > 0 and pa[-k:] == pb[-k:]


def cmd_lint(args) -> int:
    """graftlint: the repo's JAX- and concurrency-aware static analyzer
    (deeprest_tpu/analysis; rule catalog in ANALYSIS.md).  Exit status:
    0 clean, 1 non-baselined findings, 2 usage error."""
    from deeprest_tpu.analysis import (
        LintResult, all_rules, default_baseline_path, lint_paths,
        load_baseline, load_project, render_json, render_rules,
        render_sarif, render_suppressions_json,
        render_suppressions_markdown, render_suppressions_text,
        render_text, render_timings, save_baseline,
        suppression_inventory,
    )

    if args.list_rules:
        print(render_rules())
        return 0
    rules = None
    if args.rules:
        registry = all_rules()
        wanted = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = sorted(set(wanted) - set(registry))
        if unknown:
            print(f"lint: unknown rules {unknown} "
                  f"(known: {sorted(registry)})")
            return 2
        rules = [registry[r] for r in wanted]
    import os

    jobs = args.jobs if args.jobs is not None else (os.cpu_count() or 1)
    if jobs < 1:
        print(f"lint: --jobs {jobs} must be >= 1")
        return 2
    paths = args.paths
    if not paths:
        import deeprest_tpu

        paths = [os.path.dirname(os.path.abspath(deeprest_tpu.__file__))]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"lint: no such path {missing}")
        return 2

    if args.fix:
        from deeprest_tpu.analysis.autofix import fix_paths

        report = fix_paths(paths)
        print(report.summary())
        return 0
    if args.list_suppressions:
        entries = suppression_inventory(load_project(paths, jobs=jobs))
        if args.format == "json":
            print(render_suppressions_json(entries))
        elif args.format == "markdown":
            print(render_suppressions_markdown(entries))
        elif args.format == "text":
            print(render_suppressions_text(entries))
        else:
            print(f"lint: --list-suppressions has no {args.format!r} "
                  "rendering (text/json/markdown)")
            return 2
        return 0
    if args.format == "markdown":
        print("lint: --format markdown is the --list-suppressions "
              "rendering; findings come as text/json/sarif")
        return 2

    baseline_path = args.baseline or default_baseline_path()
    try:
        baseline_keys = load_baseline(baseline_path)
    except ValueError as exc:
        print(f"lint: {exc}")
        return 2
    timings: dict | None = None
    if getattr(args, "timings", False):
        # a cache hit stores no per-pack times, so --timings always
        # runs the analysis fresh (that is the number being asked for)
        import time as _time

        from deeprest_tpu.analysis import (
            analyze_project, apply_baseline,
        )

        timings = {}
        t0 = _time.perf_counter()
        project = load_project(paths, jobs=jobs)
        timings["parse"] = _time.perf_counter() - t0
        kept, suppressed = analyze_project(project, rules=rules,
                                           timings=timings)
        result = apply_baseline(kept, suppressed, len(project.files),
                                baseline_keys)
    elif args.no_cache:
        result = lint_paths(paths, rules=rules,
                            baseline_keys=baseline_keys, jobs=jobs)
    else:
        from deeprest_tpu.analysis.cache import lint_paths_cached

        result, _cache = lint_paths_cached(
            paths, rules=rules, baseline_keys=baseline_keys, jobs=jobs,
            cache_dir=args.cache_dir)
    if args.write_baseline:
        save_baseline(baseline_path, result.findings + result.baselined)
        print(f"lint: baselined {len(result.findings + result.baselined)} "
              f"findings to {baseline_path}")
        return 0
    scope_note = ""
    if args.changed:
        anchor = paths[0] if os.path.isdir(paths[0]) else os.path.dirname(
            os.path.abspath(paths[0]))
        changed = _git_changed_python_files(anchor)
        if changed is None:
            print(f"lint: --changed needs a git work tree around "
                  f"{anchor!r}")
            return 2
        # the WHOLE project is still parsed (cross-module rules need the
        # full symbol table / call graph); only the REPORT is scoped
        result = LintResult(
            findings=[f for f in result.findings
                      if any(_component_suffix_match(f.path, c)
                             for c in changed)],
            baselined=[f for f in result.baselined
                       if any(_component_suffix_match(f.path, c)
                              for c in changed)],
            suppressed_count=result.suppressed_count,
            files=result.files)
        scope_note = (f" [--changed: findings scoped to {len(changed)} "
                      "changed file(s); whole project parsed]")
    if args.format == "sarif":
        print(render_sarif(result))
    elif args.format == "json":
        print(render_json(result, timings=timings))
    else:
        print(render_text(result) + scope_note)
        if timings is not None:
            print(render_timings(timings))
    return 1 if result.findings else 0


# -- parser -----------------------------------------------------------------


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="deeprest_tpu",
        description="TPU-native API-aware resource estimation",
    )
    sub = ap.add_subparsers(dest="command", required=True)

    p = sub.add_parser("simulate", help="generate a raw corpus (no cluster)")
    from deeprest_tpu.workload.scenarios import SCENARIOS

    p.add_argument("--scenario", choices=sorted(SCENARIOS), default="normal")
    p.add_argument("--ticks", type=int, default=480)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", default="raw_data.jsonl")
    p.add_argument("--app", choices=("social", "synthetic"), default="social",
                   help="topology: the 12-service social network or a seeded "
                        "synthetic service DAG (TrainTicket scale)")
    p.add_argument("--services", type=int, default=40,
                   help="synthetic app: number of services")
    p.add_argument("--endpoints", type=int, default=12,
                   help="synthetic app: number of API endpoints")
    p.add_argument("--shift-at", type=int, default=0,
                   help="mid-corpus topology change: buckets at/after "
                        "this index generate from a re-drawn synthetic "
                        "topology with --services-after services (0 = no "
                        "shift; the drift-scenario library)")
    p.add_argument("--services-after", type=int, default=None,
                   help="post-shift service count (default: --services "
                        "+ 50%%)")
    p.set_defaults(fn=cmd_simulate)

    p = sub.add_parser("featurize", help="raw corpus → model-ready features")
    _add_input_args(p, features_ok=False)
    p.add_argument("--workers", type=int, default=1,
                   help="shard trace walking across a forked process pool "
                        "(0 = one per CPU, 1 = serial); bit-identical "
                        "output in both featurization modes")
    p.add_argument("--out", default="input.npz")
    p.set_defaults(fn=cmd_featurize)

    p = sub.add_parser(
        "ingest",
        help="Jaeger/OTLP + Prometheus (dumps or live endpoints) → raw "
             "corpus JSONL")
    p.add_argument("--traces", nargs="*", default=[],
                   help="Jaeger query-API or OTLP/JSON trace dump files")
    p.add_argument("--prom", nargs="*", default=[],
                   help="Prometheus query_range JSON dump files")
    p.add_argument("--jaeger-url", default=None,
                   help="live Jaeger query API base URL (e.g. "
                        "http://jaeger-query:16686)")
    p.add_argument("--prom-url", default=None,
                   help="live Prometheus base URL (e.g. "
                        "http://prometheus:9090)")
    p.add_argument("--start", type=float, default=None,
                   help="live pull range start (epoch seconds; default "
                        "end - --last-seconds)")
    p.add_argument("--end", type=float, default=None,
                   help="live pull range end (epoch seconds; default now)")
    p.add_argument("--last-seconds", type=float, default=3600.0,
                   help="live pull lookback when --start is omitted")
    p.add_argument("--step-seconds", type=float, default=None,
                   help="Prometheus query_range step (default: the bucket "
                        "width — scrape interval = bucket contract)")
    p.add_argument("--jaeger-services", nargs="*", default=None,
                   help="restrict the live Jaeger pull to these services "
                        "(default: discover via /api/services)")
    p.add_argument("--bucket-seconds", type=float, default=5.0,
                   help="discretization window (= the cluster's scrape "
                        "interval; the reference scrapes at 5s)")
    p.add_argument("--metric-map", nargs="*", default=None,
                   metavar="PROM_METRIC:RESOURCE[:MODE]",
                   help="override the cadvisor-style default metric map "
                        "(mode: gauge|counter)")
    p.add_argument("--out", default="raw_data.jsonl")
    p.set_defaults(fn=cmd_ingest)

    p = sub.add_parser("train", help="train + eval vs both baselines")
    _add_input_args(p)
    p.add_argument("--epochs", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--window", type=int, default=60)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--split", type=float, default=0.40)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--hidden-size", type=int, default=128)
    p.add_argument("--dropout", type=float, default=0.5)
    p.add_argument("--compute-dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--device-data", default="auto",
                   choices=["auto", "always", "off"],
                   help="stage the normalized base series in device memory "
                        "and feed steps by start index (auto skips CPU "
                        "backends and over-budget corpora)")
    p.add_argument("--steps-per-superstep", type=_superstep_arg,
                   default="auto", metavar="N|auto|epoch",
                   help="train steps fused into one compiled dispatch via "
                        "lax.scan on the staged path (1 = per-step loop; "
                        "'epoch' = whole epoch per dispatch; 'auto' sizes "
                        "from the logging cadence)")
    p.add_argument("--grad-accum-windows", type=int, default=1, metavar="G",
                   help="gradient accumulation on the staged superstep "
                        "path: G consecutive microbatches each run "
                        "forward and backward, one optimizer update per "
                        "G on the gradient of their mean loss over the "
                        "group's real windows; requires the "
                        "device-resident feed (--device-data always on "
                        "CPU); 1 = per-step updates (default)")
    p.add_argument("--snapshot-every-steps", type=int, default=0,
                   metavar="N",
                   help="preemption-safe training: atomically checkpoint "
                        "the full state PLUS the epoch-plan cursor "
                        "(epoch, step offset, shuffle-rng state) into "
                        "--ckpt-dir every N real steps; re-running the "
                        "same command after a kill resumes the run — "
                        "onto whatever mesh remains — bit-identical to "
                        "an uninterrupted run at the same step (0 = off)")
    _add_elastic_args(p)
    _add_sparse_args(p)
    _add_mesh_arg(p)
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--plots-dir", default=None)
    p.add_argument("--profile-dir", default=None,
                   help="trace the second epoch with jax.profiler, print "
                        "its device time by layer and idle gaps by host "
                        "phase, write layers.json beside the trace")
    p.add_argument("--report-every", type=int, default=0,
                   help="print the full MAE table every N epochs (0 = end only)")
    p.add_argument("--no-baselines", action="store_true")
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("synthesize", help="what-if traffic feature synthesis")
    _add_input_args(p, features_ok=False)
    p.add_argument("--mix", required=True,
                   help='JSON {endpoint: count} per time step')
    p.add_argument("--ticks", type=int, default=60)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--ckpt-dir", default=None,
                   help="use this checkpoint's feature space (column-exact "
                        "for that model)")
    p.add_argument("--out", default="synthetic.npz")
    p.set_defaults(fn=cmd_synthesize)

    p = sub.add_parser("stream",
                       help="tail a growing raw corpus (or poll live "
                            "Jaeger/Prometheus); fine-tune + re-checkpoint "
                            "continuously")
    p.add_argument("--raw", default=None,
                   help="raw-data JSONL being appended to (collector --out)")
    p.add_argument("--jaeger-url", default=None,
                   help="live Jaeger query API base URL (alternative "
                        "source to --raw)")
    p.add_argument("--prom-url", default=None,
                   help="live Prometheus base URL (alternative source "
                        "to --raw)")
    p.add_argument("--wire-listen", default=None, metavar="HOST:PORT",
                   help="push-based span firehose: listen for framed "
                        "span batches (data/wire.py protocol) and "
                        "featurize them straight into the sparse ring "
                        "— requires --sparse-feed")
    p.add_argument("--wire-queue-depth", type=int, default=256,
                   help="per-connection inflight frame budget before "
                        "the receiver sends SLOWDOWN (2x = fast-drop "
                        "with accounting, 4x drop streak = eviction)")
    p.add_argument("--bucket-seconds", type=float, default=5.0,
                   help="live-source discretization window (= scrape "
                        "interval)")
    p.add_argument("--metric-map", nargs="*", default=None,
                   metavar="PROM_METRIC:RESOURCE[:MODE]",
                   help="live-source metric map override "
                        "(default: cadvisor names; mode: gauge|counter)")
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--capacity", type=int, default=512,
                   help="hash-feature width (static model input dim)")
    p.add_argument("--hash-seed", type=int, default=0x5EED)
    p.add_argument("--window", type=int, default=60)
    p.add_argument("--batch-size", type=int, default=32)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--hidden-size", type=int, default=128)
    p.add_argument("--compute-dtype", default="float32",
                   choices=["float32", "bfloat16"])
    p.add_argument("--steps-per-superstep", type=_superstep_arg,
                   default="auto", metavar="N|auto|epoch",
                   help="fused steps per compiled dispatch for the staged "
                        "fine-tune epochs (1 = per-step loop)")
    p.add_argument("--grad-accum-windows", type=int, default=1, metavar="G",
                   help="gradient accumulation on the staged superstep "
                        "path: G consecutive microbatches each run "
                        "forward and backward, one optimizer update per "
                        "G on the gradient of their mean loss over the "
                        "group's real windows; requires the "
                        "device-resident feed (--device-data always on "
                        "CPU); 1 = per-step updates (default)")
    p.add_argument("--snapshot-every-steps", type=int, default=0,
                   metavar="N",
                   help="preemption-safe fine-tuning: checkpoint the full "
                        "state + stream sidecar (frozen metric set, "
                        "stats, refresh counter, retained-ring "
                        "watermarks) every N fine-tune steps, so a "
                        "stream killed MID-refresh resumes at most N "
                        "steps stale instead of losing the refresh "
                        "(0 = off; refresh-end checkpoints always "
                        "happen)")
    _add_elastic_args(p, streaming=True)
    _add_sparse_args(p)
    p.add_argument("--refresh-buckets", type=int, default=60,
                   help="fine-tune after this many new buckets")
    p.add_argument("--finetune-epochs", type=int, default=2)
    p.add_argument("--history-max", type=int, default=4096)
    def positive_int(v: str) -> int:
        n = int(v)
        if n < 1:
            raise argparse.ArgumentTypeError(f"{v} must be >= 1")
        return n

    p.add_argument("--keep-checkpoints", type=positive_int, default=3,
                   help="newest checkpoint steps retained (disk bound, "
                        ">= 1)")
    p.add_argument("--eval-holdout", type=int, default=8,
                   help="newest windows held out for eval each refresh")
    p.add_argument("--poll-interval", type=float, default=0.5)
    p.add_argument("--no-etl-overlap", action="store_true",
                   help="run tail→parse→featurize inline on the train "
                        "thread instead of the background ETL thread "
                        "(same refresh results; only the overlap differs)")
    p.add_argument("--etl-queue-depth", type=int, default=512,
                   help="buckets buffered between the ETL thread and the "
                        "train loop (backpressure bound)")
    p.add_argument("--max-refreshes", type=int, default=0,
                   help="stop after N refreshes (0 = run forever)")
    p.add_argument("--deadline", type=float, default=0,
                   help="stop after this many seconds (0 = no deadline)")
    p.add_argument("--drift-detect", action="store_true",
                   help="arm the online quality monitors + the "
                        "drift→retrain loop (obs/quality.py, "
                        "DriftController): streaming per-call-path "
                        "PSI/KS vs the training reference, rolling band "
                        "coverage/pinball, the continuous "
                        "not-justified-by-traffic check, and "
                        "auto-retrain on sustained drift")
    p.add_argument("--drift-sweep-every", type=int, default=30,
                   metavar="N", help="buckets between monitor sweeps")
    p.add_argument("--drift-live-window", type=int, default=120,
                   metavar="N",
                   help="trailing buckets the drift score compares "
                        "against the training reference")
    p.add_argument("--drift-reference-window", type=int, default=240,
                   metavar="N",
                   help="retained-ring tail re-anchored as the drift "
                        "reference after each (re)train")
    p.add_argument("--drift-enter", type=float, default=0.25,
                   help="weighted-PSI threshold entering the drift "
                        "verdict (sustained sweeps required — "
                        "hysteresis)")
    p.add_argument("--drift-exit", type=float, default=0.10,
                   help="weighted-PSI threshold exiting the drift "
                        "verdict")
    p.add_argument("--drift-cooldown-buckets", type=int, default=240,
                   metavar="N",
                   help="minimum buckets between drift-triggered "
                        "retrains")
    p.add_argument("--no-drift-auto-retrain", action="store_true",
                   help="manual override: verdicts only — sustained "
                        "drift never fires a retrain by itself")
    p.set_defaults(fn=cmd_stream)

    p = sub.add_parser("whatif",
                       help="hypothetical traffic mix → per-metric peak "
                            "utilization; --sweep runs a batched capacity-"
                            "sweep grid through the fused pipeline")
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--raw", required=True,
                   help="raw corpus to fit the what-if trace synthesizer")
    p.add_argument("--mix", required=True,
                   help='JSON {endpoint: count} per time step')
    p.add_argument("--ticks", type=int, default=60)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--sweep", default=None, metavar="F1,F2,...",
                   help="scale the mix by each factor and estimate ALL "
                        "scenarios in one batched prediction train "
                        "(e.g. 0.5,1,2,4)")
    p.add_argument("--out", default=None,
                   help="also write the full result JSON here")
    _add_fused_infer_args(p)
    _add_mesh_arg(p, serving=True)
    p.set_defaults(fn=cmd_whatif)

    p = sub.add_parser("export",
                       help="checkpoint → portable inference artifact "
                            "(jax.export StableHLO + JSON manifest)")
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--out", required=True, help="artifact directory")
    p.add_argument("--aot", action="store_true",
                   help="also compile + serialize the fused serving "
                        "executables next to the checkpoint "
                        "(<ckpt>/aot/, serve/aot.py) so fleet pool "
                        "admission deserializes instead of compiling — "
                        "platform-exact: export on the platform that "
                        "will serve")
    p.set_defaults(fn=cmd_export)

    p = sub.add_parser("serve",
                       help="HTTP prediction service: predict / what-if / "
                            "anomaly")
    p.add_argument("--ckpt-dir", default=None,
                   help="serve the in-process predictor from this checkpoint")
    p.add_argument("--watch", type=float, default=0, metavar="SECONDS",
                   help="with --ckpt-dir: hot-reload newer checkpoints, "
                        "polling at most every SECONDS (0 = off)")
    p.add_argument("--artifact", default=None,
                   help="serve the exported artifact from this directory")
    p.add_argument("--raw", default=None,
                   help="raw corpus to fit the what-if trace synthesizer")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=2021)
    p.add_argument("--deadline", type=float, default=0,
                   help="stop after this many seconds (0 = run forever)")
    p.add_argument("--no-batcher", action="store_true",
                   help="disable cross-request micro-batching (each request "
                        "dispatches its own device batches; the shape "
                        "ladder still bounds jit compiles)")
    p.add_argument("--batch-max-windows", type=int, default=64,
                   help="flush a coalesced batch at this many windows "
                        "(should equal the top ladder rung)")
    p.add_argument("--batch-linger-ms", type=float, default=2.0,
                   help="max time the first request in a batch waits for "
                        "co-arrivals before flushing")
    p.add_argument("--batch-ladder", default="8,16,32,64",
                   help="comma-separated window-count rungs every device "
                        "batch is padded up to (bounds the jit cache to "
                        "one executable per rung)")
    p.add_argument("--batch-coalesce-groups", type=int, default=1,
                   metavar="G",
                   help="extend the ladder with top-rung*{2..G} "
                        "super-rungs so a deep cross-request backlog "
                        "dispatches one batch of top*G windows (G*64 "
                        "recurrence rows at the default ladder) instead "
                        "of G sequential top-rung dispatches; raise "
                        "--batch-max-windows to match")
    p.add_argument("--replicas", type=int, default=1, metavar="N",
                   help="engine replicas behind the routing front "
                        "(serve/router.py): each a full Predictor/"
                        "MicroBatcher/fused-engine stack pinned to its own "
                        "device (replicas sharing a device share one "
                        "stack), dispatched least-outstanding-work; 1 = "
                        "today's single-engine path")
    p.add_argument("--replica-mode", choices=("thread", "process"),
                   default="thread",
                   help="replica isolation: in-process threads (default; "
                        "one process drives every chip of the host) or "
                        "worker subprocesses that each rebuild the full "
                        "stack from --ckpt-dir/--artifact.  A chip "
                        "belongs to one process: on an accelerator this "
                        "process has taken the chips by the time it "
                        "starts workers, so 'process' is refused there; "
                        "it is for the CPU tier")
    p.add_argument("--admission-depth", type=int, default=0, metavar="N",
                   help="max concurrently admitted requests across the "
                        "plane; beyond it (plus a same-size bounded wait "
                        "queue) requests fail fast with 429 + Retry-After "
                        "instead of queueing into collapse (0 = default "
                        "64 when the router is on)")
    p.add_argument("--admission-wait-ms", type=float, default=250.0,
                   help="max time a request may wait in the fairness "
                        "queue for a slot before the 429")
    p.add_argument("--admission-retry-after-ms", type=float, default=50.0,
                   help="Retry-After hint sent with admission 429s")
    p.add_argument("--tenant-weights", default=None, metavar="a=3,b=1",
                   help="weighted round-robin shares per X-Tenant header "
                        "value (unknown tenants weigh 1)")
    p.add_argument("--replica-timeout-ms", type=float, default=30000.0,
                   metavar="MS",
                   help="per-request deadline on process replicas: a "
                        "worker dead between heartbeats becomes a typed "
                        "ReplicaDeadError instead of an indefinite pipe "
                        "recv (0 = no deadline — the historical hang)")
    p.add_argument("--eject-after-failures", type=int, default=3,
                   metavar="N",
                   help="consecutive dead-replica failures that eject a "
                        "replica from dispatch (a confirmed-dead worker "
                        "ejects immediately); the background probe "
                        "reboots process replicas and rejoins them")
    p.add_argument("--retry-budget", type=int, default=1, metavar="N",
                   help="max re-dispatches of ONE request onto survivor "
                        "replicas — only for failures proving the "
                        "request never produced a response (worker dead "
                        "/ send failed); deadline expiries on a live "
                        "worker are never retried (no double-execution)")
    p.add_argument("--autoscale", action="store_true",
                   help="run the self-sizing control loop "
                        "(deploy/autoscaler.py): observed traffic -> "
                        "what-if capacity estimate -> router.scale_to; "
                        "decisions surface on /healthz under "
                        "router.autoscaler")
    p.add_argument("--autoscale-min", type=int, default=1)
    p.add_argument("--autoscale-max", type=int, default=8)
    p.add_argument("--autoscale-interval", type=float, default=10.0,
                   help="control-tick seconds")
    p.add_argument("--autoscale-rps-per-replica", type=float, default=None,
                   help="measured per-replica capacity basis (rps; the "
                        "committed serve_bench headline is the honest "
                        "source)")
    p.add_argument("--autoscale-manifest", default=None, metavar="PATH",
                   help="mirror decisions into this k8s manifest's "
                        "deeprest-predictor Deployment spec.replicas "
                        "(deploy/k8s/predictor.yaml)")
    p.add_argument("--no-obs", action="store_true",
                   help="disable span recording (deeprest_tpu/obs); "
                        "/metrics and its counters stay live — only the "
                        "trace ring is gated (near-zero cost either way)")
    p.add_argument("--obs-span-capacity", type=int, default=4096,
                   metavar="N",
                   help="bound on retained spans (newest win; GET "
                        "/v1/spans exports them as Jaeger JSON for the "
                        "self-ingestion loop)")
    p.add_argument("--verdict-raw", default=None, metavar="PATH",
                   help="arm the streaming verdict surface (GET "
                        "/v1/verdict): tail this growing collector JSONL, "
                        "featurize against the served model's call-path "
                        "space, and run the online quality monitors "
                        "(drift PSI/KS, band coverage/pinball, the "
                        "continuous not-justified-by-traffic check) — "
                        "the streaming replacement for the batch anomaly "
                        "CLI")
    p.add_argument("--verdict-wire-listen", default=None,
                   metavar="HOST:PORT",
                   help="arm the verdict surface from a push firehose "
                        "instead of a tailed JSONL: listen for framed "
                        "span batches (data/wire.py) and feed them to "
                        "the VerdictIngestor — alternative to "
                        "--verdict-raw")
    p.add_argument("--verdict-sweep-every", type=int, default=30,
                   metavar="N",
                   help="buckets between verdict-surface monitor sweeps")
    p.add_argument("--verdict-live-window", type=int, default=120,
                   metavar="N",
                   help="trailing buckets in the drift live window (also "
                        "the auto-arm reference size)")
    p.add_argument("--surface", action="store_true",
                   help="arm the capacity-surface plane (serve/surface.py; "
                        "needs --raw): in-space /v1/whatif reads answer by "
                        "multilinear interpolation over precomputed "
                        "surfaces, POST /v1/whatif/surface serves sweep-"
                        "style peak queries, and every reload invalidates "
                        "the cache eagerly (reason-labeled)")
    p.add_argument("--surface-grid", default="0.5,1,2,4", metavar="S,S,...",
                   help="per-axis scale ladder a surface sweeps around its "
                        "base traffic program")
    p.add_argument("--surface-max-axes", type=int, default=3, metavar="K",
                   help="max independent per-endpoint scale axes (more "
                        "active endpoints collapse to one shared axis; "
                        "vertex count is len(grid)**K)")
    p.add_argument("--surface-jitter", type=int, default=8, metavar="N",
                   help="Monte-Carlo probe mixes per build — held out of "
                        "the grid, they measure the surface-vs-direct "
                        "parity envelope reported on /healthz")
    p.add_argument("--surface-max-surfaces", type=int, default=8,
                   metavar="N",
                   help="LRU bound on resident surfaces")
    p.add_argument("--surface-max-bytes-mb", type=float, default=64.0,
                   metavar="MB",
                   help="host-byte budget across resident surfaces "
                        "(oversized mix spaces refuse to build and answer "
                        "from the frontier instead)")
    p.add_argument("--surface-sync", action="store_true",
                   help="build cache-miss surfaces inline instead of on a "
                        "background warm thread (deterministic tests/"
                        "benches; first query pays the build)")
    p.add_argument("--fleet", action="append", default=None,
                   metavar="TENANT=CKPT_DIR",
                   help="admit another tenant application to this plane "
                        "(repeatable; serve/fleet.py): X-Tenant then "
                        "selects the MODEL, all tenants share one "
                        "compiled executable set, and --ckpt-dir serves "
                        "as the 'default' tenant and executable template")
    p.add_argument("--fleet-hbm-budget", type=int, default=4, metavar="N",
                   help="max tenants with device-resident params (LRU; "
                        "evicted tenants spill to host memory and "
                        "restore with one device_put — never a disk "
                        "read or a compile)")
    p.add_argument("--no-fleet-aot", action="store_true",
                   help="skip loading AOT executable sidecars "
                        "(<ckpt>/aot/, written by deeprest export "
                        "--aot) at pool admission; rungs then compile "
                        "lazily on first dispatch")
    p.add_argument("--fleet-top-k", type=int, default=8, metavar="K",
                   help="per-tenant observability cardinality bound: "
                        "top-K tenants by serve count get their own "
                        "/metrics labels and /healthz rows, the rest "
                        "roll up under __other__")
    p.add_argument("--no-fleet-quality", action="store_true",
                   help="skip the per-tenant QualityMonitor (GET "
                        "/v1/verdict then 503s for fleet tenants)")
    _add_fused_infer_args(p)
    _add_sparse_args(p, serving=True)
    _add_mesh_arg(p, serving=True)
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("profile",
                       help="open a jax.profiler capture window on a "
                            "running serving plane (POST /v1/profile); "
                            "inspect with TensorBoard/XProf")
    p.add_argument("--url", default="http://127.0.0.1:2021",
                   help="base URL of the running `deeprest serve` plane")
    p.add_argument("--seconds", type=float, default=2.0,
                   help="capture window length (server bounds it)")
    p.add_argument("--out-dir", default=None,
                   help="trace directory on the SERVER host (default: a "
                        "server-side temp dir, echoed back)")
    p.set_defaults(fn=cmd_profile)

    p = sub.add_parser("lint",
                       help="graftlint: JAX- and concurrency-aware static "
                            "analysis over the package (rule catalog: "
                            "ANALYSIS.md); nonzero exit on non-baselined "
                            "findings")
    p.add_argument("paths", nargs="*",
                   help="files/directories to lint (default: the installed "
                        "deeprest_tpu package)")
    p.add_argument("--format", choices=("text", "json", "sarif",
                                        "markdown"), default="text",
                   help="findings as text/json/sarif (SARIF 2.1.0 for "
                        "CI inline annotation); markdown renders the "
                        "--list-suppressions table")
    p.add_argument("--rules", default=None, metavar="JX001,TH001,...",
                   help="run only these rule ids (default: all)")
    p.add_argument("--changed", action="store_true",
                   help="report only findings in files changed vs git "
                        "HEAD (plus untracked); the whole project is "
                        "still parsed so cross-module rules keep their "
                        "call graph (make lint-changed)")
    p.add_argument("--jobs", type=int, default=None, metavar="N",
                   help="parse the project across N worker processes "
                        "(default: os.cpu_count(); small trees parse "
                        "serially regardless)")
    p.add_argument("--list-suppressions", action="store_true",
                   help="emit the live suppression inventory (rule, "
                        "file:line, reason) instead of linting; "
                        "--format markdown renders the generated "
                        "ANALYSIS.md table")
    p.add_argument("--baseline", default=None,
                   help="baseline JSON path (default: the checked-in "
                        "deeprest_tpu/analysis/baseline.json, which is "
                        "EMPTY and pinned so by tests/test_lint_clean.py)")
    p.add_argument("--write-baseline", action="store_true",
                   help="record every current finding into the baseline "
                        "instead of reporting (for adopting graftlint on "
                        "a dirty tree; this repo keeps the baseline empty)")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog with the historical "
                        "incident each rule guards against")
    p.add_argument("--fix", action="store_true",
                   help="apply the safe mechanical fixes (HY001 unused "
                        "imports, HY002 unreachable code) instead of "
                        "reporting; loops until stable, refuses "
                        "suppressed findings, second run is a "
                        "byte-identical no-op (make lint-fix)")
    p.add_argument("--no-cache", action="store_true",
                   help="bypass the incremental lint cache (parse "
                        "pickles + whole-tree findings payloads under "
                        ".graftlint_cache/)")
    p.add_argument("--timings", action="store_true",
                   help="print the per-pack wall-time breakdown (text "
                        "trailer or JSON 'timings' key); implies a "
                        "fresh uncached run — a cache hit has no "
                        "per-pack cost to report")
    p.add_argument("--cache-dir", default=".graftlint_cache",
                   metavar="DIR",
                   help="incremental cache root (default: "
                        ".graftlint_cache under the working directory; "
                        "entries key on content hashes and the rule-"
                        "pack version, so stale hits are impossible)")
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("predict", help="checkpoint + traffic → utilization")
    _add_input_args(p)
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--out", default="predictions.npz")
    _add_fused_infer_args(p)
    _add_mesh_arg(p, serving=True)
    p.set_defaults(fn=cmd_predict)

    p = sub.add_parser("anomaly", help="traffic-justified utilization check")
    _add_input_args(p)
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--tolerance", type=float, default=0.10)
    p.add_argument("--min-run", type=int, default=5)
    p.add_argument("--fail-on-anomaly", action="store_true")
    p.set_defaults(fn=cmd_anomaly)

    return ap


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    from deeprest_tpu.compile_cache import configure_compile_cache

    configure_compile_cache()
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
