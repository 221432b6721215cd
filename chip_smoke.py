#!/usr/bin/env python3
"""Does the system still start on the chip?  simulate -> train -> serve on one
TPU v5e, through the entry points a user calls, at the flagship width.

    python3 chip_smoke.py               one chip; what the driver runs
    python3 chip_smoke.py --chips 4     the data-parallel path only, four chips
    python3 chip_smoke.py --rehearse    tiny sizes on the CPU: control flow only

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
and the exit code is 0, only when every phase passed ON A TPU.  Without an
accelerator the first phase says so and the run ends there with a non-zero
exit code (``--rehearse`` goes on at tiny sizes, and still reports failure:
this script never passes without a chip).  Every earlier line is one JSON
object per phase; its times are smoke timings, not benchmark numbers.

One process for each chip.  This parent never imports JAX.  Every phase
that needs the chip is a child process, one after the other: the
``deeprest_tpu`` CLI itself (simulate, featurize, train, export, serve), or
``checks``/``data_parallel`` below, which drive the Python API where the
CLI prints too little to assert on.  Nothing is read from an earlier run:
the corpus comes from ``--seed``, weights from training or a seeded init,
and the compile cache (deeprest_tpu/compile_cache.py) starts wherever
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# The flagship geometry (BASELINE.json configs[1]): 40 experts (component x
# resource metrics), 512 hashed call-path features, H=128, W=60, B=32, bf16.
# `simulate --app synthetic --services 8` draws its topology from the seed,
# and the metric count with it, so the corpus seed is the first one from
# --seed on whose topology has exactly E metrics (_corpus_seed).
FULL = dict(services=8, endpoints=12, ticks=400, serve_ticks=80, split=0.5,
            E=40, F=512, H=128, W=60, B=32, dtype="bfloat16", epochs=4,
            F_wide=10240, nnz_cap=64, wide_windows=128, timed_steps=20,
            dp_steps=4, cli_hidden=128)
# --rehearse: same phases, same code paths, toy sizes.  The CLI children run
# what `rnn_backend="auto"` resolves to on the CPU (the scan) at H=8 on
# however many metrics the smallest synthetic app has (E=None: not held);
# the in-process checks run the kernels in interpret mode at E=2, H=128.
REHEARSE = dict(services=4, endpoints=3, ticks=70, serve_ticks=30, split=0.5,
                E=None, F=32, H=128, W=6, B=8, dtype="float32", epochs=2,
                F_wide=128, nnz_cap=8, wide_windows=20, timed_steps=2,
                dp_steps=2, cli_hidden=8)

# tests/test_pallas_gru.py holds the bf16 kernel to the bf16 scan at
# rtol=2e-2 on the loss and 0.15 * max|g| on gradients; f32 at 1e-5 / 2e-4.
# The f32 bound is an interpret-mode bound (both sides exact f32 there); on
# the chip XLA's f32 dots run at default MXU precision, so only the
# production dtype is held to its bound and the f32 difference is printed.
LOSS_RTOL = {"bfloat16": 2e-2, "float32": 1e-5}
# tests/test_parallel.py: sharded vs single-device training losses.
DP_RTOL, DP_ATOL = 2e-3, 1e-5


def emit(**record) -> None:
    print(json.dumps(record), flush=True)


class PhaseFailed(Exception):
    pass


def _child_env(extra: dict | None = None) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = HERE + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


# ---------------------------------------------------------------------------
# children that use the Python API (run as `python -c`, own the chip)
# ---------------------------------------------------------------------------


def _api_child(fn: str, opts: dict, env: dict | None = None,
               timeout: float = 900) -> tuple[int, list[dict]]:
    """Run ``chip_smoke.<fn>(opts)`` in a fresh interpreter; echo its JSON
    lines as they come and return (exit code, parsed lines)."""
    code = (f"import json, sys, chip_smoke; "
            f"sys.exit(chip_smoke.{fn}(json.loads(sys.argv[1])))")
    proc = subprocess.Popen(
        [sys.executable, "-c", code, json.dumps(opts)], cwd=HERE,
        env=_child_env(env), stdout=subprocess.PIPE, text=True)
    records: list[dict] = []
    timer = threading.Timer(timeout, proc.kill)
    timer.start()
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            try:
                rec = json.loads(line)
            except ValueError:
                print(line, file=sys.stderr, flush=True)
                continue
            if isinstance(rec, dict):
                records.append(rec)
                emit(**rec)
        return proc.wait(), records
    finally:
        timer.cancel()
        proc.stdout.close()


def _device_record():
    import importlib.metadata as md

    import jax

    def version(pkg):
        try:
            return md.version(pkg)
        except md.PackageNotFoundError:
            return None

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    versions = {p: version(p) for p in ("jax", "jaxlib", "libtpu", "flax")}
    return dev, device, versions


class _CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses in this
    process (jax.monitoring): the proof that a second process found what
    the first one compiled."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _flagship(opts: dict, backend: str, feature_dim: int, mesh=None,
              **train_kw):
    """A Trainer at the smoke's geometry, as `deeprest_tpu train` builds it."""
    from deeprest_tpu.config import Config, ModelConfig, TrainConfig
    from deeprest_tpu.train import Trainer

    z = opts["sizes"]
    e = z["E"] or 2
    cfg = Config(
        model=ModelConfig(feature_dim=feature_dim, num_metrics=e,
                          hidden_size=z["H"], compute_dtype=z["dtype"],
                          rnn_backend=backend),
        train=TrainConfig(batch_size=z["B"], window_size=z["W"],
                          seed=opts["seed"], **train_kw))
    return Trainer(cfg, feature_dim, [f"c{i // 5}_r{i % 5}" for i in range(e)],
                   mesh=mesh)


def checks(opts: dict) -> int:
    """Phase `checks`: what the CLI cannot show.  The device; the flagship
    train step's compile time and whether the cache served it; that the
    lowered programs hold the kernel; kernel-vs-scan parity; whether
    block_until_ready waits; and the F=10240 step and fused predict through
    the sparse feed.  With ``opts["compile_only"]`` it stops after the
    compile: the second process of the cache proof."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeprest_tpu.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    # every compile is kept, also one under JAX's default floor of a second
    # (the rehearsal's): the second process must find this one's
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    events = _CacheEvents()
    rehearse = opts["rehearse"]
    z = opts["sizes"]
    dev, device, versions = _device_record()
    emit(phase="device", device=device, versions=versions,
         compile_cache_dir=cache_dir,
         ok=device["platform"] == "tpu")
    if device["platform"] != "tpu" and not rehearse:
        return 3
    failed = [] if device["platform"] == "tpu" else ["device"]

    def check(name: str, ok: bool, **facts) -> None:
        emit(phase=name, ok=bool(ok), **facts)
        if not ok:
            failed.append(name)

    # Every trainer below runs what a user gets by default ("auto": the
    # compiled kernel on a TPU, the scan elsewhere).  In the rehearsal the
    # kernel's own code still has to run once, so the parity check there
    # builds its kernel side in interpret mode.
    backend = "auto"
    kernel_backend = "pallas_interpret" if rehearse else "auto"
    on_chip = device["platform"] == "tpu"
    b, w, f, e = z["B"], z["W"], z["F"], z["E"] or 2
    rng = np.random.default_rng(opts["seed"])
    x = rng.random((b, w, f), np.float32)
    y = rng.random((b, w, e), np.float32)
    wt = np.ones((b,), np.float32)

    trainer = _flagship(opts, backend, f)
    state = trainer.init_state(x)
    x_d, y_d, w_d = jnp.asarray(x), jnp.asarray(y), jnp.asarray(wt)

    before = (events.hits, events.misses)
    t0 = time.perf_counter()
    compiled = trainer._train_step.lower(state, x_d, y_d, w_d).compile()
    compile_s = time.perf_counter() - t0
    hit = events.hits > before[0]
    emit(phase="compile", program="train_step", seconds=round(compile_s, 3),
         cache_hit=hit, cache_events={"hits": events.hits - before[0],
                                      "misses": events.misses - before[1]},
         note="smoke timing, not a benchmark number")
    if opts.get("compile_only"):
        return 0

    from deeprest_tpu.ops.gru import _resolve_backend

    # on the chip: the compiled kernel, in the default program, not in
    # interpret mode; in the rehearsal the default is the scan, and the
    # kernel's code runs interpreted in the parity check below
    resolved = _resolve_backend(backend)
    has_call = "tpu_custom_call" in compiled.as_text()
    check("kernel_in_train_step",
          (has_call and resolved == "pallas") if on_chip
          else _resolve_backend(kernel_backend) == "pallas_interpret",
          rnn_backend=resolved, tpu_custom_call=has_call,
          parity_check_backend=_resolve_backend(kernel_backend))

    # -- does block_until_ready wait on this backend? ----------------------
    # The same N steps, closed two ways.  If block_until_ready returned at
    # the dispatch, the readback that follows it would carry the device
    # time; if it waits, that readback is only a transfer.
    def read_param(st) -> float:
        return float(jnp.ravel(jax.tree.leaves(st.params)[0])[0])

    for _ in range(2):
        state, loss = trainer._train_step(state, x_d, y_d, w_d)
    read_param(state)
    n = z["timed_steps"]
    t0 = time.perf_counter()
    for _ in range(n):
        state, loss = trainer._train_step(state, x_d, y_d, w_d)
    t_dispatched = time.perf_counter()
    jax.block_until_ready(state)
    t_ready = time.perf_counter()
    read_param(state)
    t_read = time.perf_counter()
    bur = {"dispatch_s": t_dispatched - t0, "wait_s": t_ready - t_dispatched,
           "readback_after_s": t_read - t_ready, "total_s": t_ready - t0}
    t0 = time.perf_counter()
    for _ in range(n):
        state, loss = trainer._train_step(state, x_d, y_d, w_d)
    t_dispatched = time.perf_counter()
    read_param(state)
    t_read = time.perf_counter()
    readback = {"dispatch_s": t_dispatched - t0,
                "wait_s": t_read - t_dispatched, "total_s": t_read - t0}
    waits = bur["readback_after_s"] < 0.1 * bur["total_s"]
    check("block_until_ready", np.isfinite(float(loss)), steps=n,
          closed_by_block_until_ready=bur, closed_by_param_readback=readback,
          block_until_ready_waits=bool(waits),
          note="smoke timings, not benchmark numbers")

    # -- kernel vs scan, same params, same batch ---------------------------
    scan = _flagship(opts, "scan", f)
    kern = trainer if on_chip else _flagship(opts, kernel_backend, f)
    k_preds, k_loss = kern._eval_step(state.params, x_d, y_d)
    s_preds, s_loss = scan._eval_step(state.params, x_d, y_d)
    k_loss, s_loss = float(k_loss), float(s_loss)
    rel = abs(k_loss - s_loss) / abs(s_loss)

    def grads(t):
        def loss_fn(p):
            return t._eval_step.__wrapped__(p, x_d, y_d)[1]
        return jax.jit(jax.grad(loss_fn))(state.params)

    g_k, g_s = grads(kern), grads(scan)
    worst = {}
    for name in g_s:
        a = np.asarray(g_s[name], np.float32)
        d = float(np.max(np.abs(a - np.asarray(g_k[name], np.float32))))
        worst[name] = d / (1e-3 + float(np.max(np.abs(a))))
    grad_bound = 0.15 if z["dtype"] == "bfloat16" else 2e-4
    check("kernel_vs_scan",
          rel <= LOSS_RTOL[z["dtype"]] and max(worst.values()) <= grad_bound
          and np.isfinite(k_loss),
          dtype=z["dtype"], kernel_loss=k_loss, scan_loss=s_loss,
          loss_rel_diff=rel, loss_rtol=LOSS_RTOL[z["dtype"]],
          worst_grad_rel_diff=max(worst.values()),
          worst_grad_leaf=max(worst, key=worst.get), grad_bound=grad_bound,
          preds_max_abs_diff=float(jnp.max(jnp.abs(
              k_preds.astype(jnp.float32) - s_preds.astype(jnp.float32)))))

    # -- four batches' rows in one recurrence vs one call a batch ----------
    from deeprest_tpu.ops.gru import gru, init_gru_params

    fold = {}
    for dtype in ("float32", "bfloat16"):
        p = init_gru_params(jax.random.PRNGKey(opts["seed"]), e, f, z["H"],
                            jnp.dtype(dtype))
        x4 = jnp.asarray(rng.random((4, b, w, f), np.float32), dtype)
        run = jax.jit(lambda p, x: gru(p, x, backend=backend))
        full = run(p, x4.reshape(4 * b, w, f)).astype(jnp.float32)
        fold[dtype] = max(
            float(jnp.max(jnp.abs(full[:, g * b:(g + 1) * b]
                                  - run(p, x4[g]).astype(jnp.float32))))
            for g in range(4))
    check("row_fold_vs_per_group",
          all(np.isfinite(v) for v in fold.values()),
          max_abs_diff=fold, rows=4 * b,
          note="0.0 means the row fold is bit-identical on this backend")

    del scan, kern, g_k, g_s, k_preds, s_preds

    # -- the 10k-endpoint width through the sparse feed --------------------
    from deeprest_tpu.data.windows import MinMaxStats
    from deeprest_tpu.parallel.distributed import stage_sparse_base
    from deeprest_tpu.serve import aot
    from deeprest_tpu.serve.predictor import Predictor

    fw, k = z["F_wide"], z["nnz_cap"]
    wide = _flagship(opts, backend, fw, sparse_feed=True, sparse_nnz_cap=k)
    t_len = 4 * w
    # K distinct columns a row (the padded-COO contract, ops/densify.py)
    cols = ((rng.integers(0, fw, (t_len, 1)) + np.arange(k) * (fw // k))
            % fw).astype(np.int32)
    vals = rng.random((t_len, k)).astype(np.float32)
    base = stage_sparse_base(wide.mesh, cols, vals, np.zeros((fw,)),
                             np.ones((fw,)), fw)
    y_base = jnp.asarray(rng.random((t_len, e), np.float32))
    starts = jnp.asarray(rng.integers(0, t_len - w, (b,)).astype(np.int32))
    wstate = wide.init_state(np.zeros((1, w, fw), np.float32))
    lowered = wide._train_step_indexed.lower(wstate, base, y_base, starts, w_d)
    t0 = time.perf_counter()
    wstate, wloss = wide._train_step_indexed(wstate, base, y_base, starts, w_d)
    wloss = float(wloss)
    wide_step_s = time.perf_counter() - t0
    check("wide_train_step", np.isfinite(wloss) and (
              not on_chip or "tpu_custom_call" in lowered.compile().as_text()),
          feature_dim=fw, nnz_cap=k, loss=wloss,
          first_step_seconds=round(wide_step_s, 3),
          state_bytes=int(sum(a.nbytes for a in jax.tree.leaves(
              (wstate.params, wstate.opt_state)))),
          note="smoke timing (compile included), not a benchmark number")

    pred = Predictor(
        params=wstate.params, model_config=wide.model_config,
        x_stats=MinMaxStats(min=np.zeros((fw,), np.float32),
                            max=np.ones((fw,), np.float32)),
        y_stats=MinMaxStats(min=np.zeros((e,), np.float32),
                            max=np.ones((e,), np.float32)),
        metric_names=wide.metric_names, window_size=w,
        sparse_feed=True, sparse_nnz_cap=k)
    from deeprest_tpu.ops.densify import densify_rows

    series = densify_rows(cols[:2 * w], vals[:2 * w], fw)
    t0 = time.perf_counter()
    out = pred.predict_series(series)
    wide_pred_s = time.perf_counter() - t0
    fused = pred.fused.stats()
    rung = fused["dispatched_rungs"][0]
    text = pred.fused._jit_sparse.lower(
        *aot._example_args(pred, rung, True)).compile().as_text()
    check("wide_fused_predict",
          out.shape == (2 * w, e, 3) and bool(np.isfinite(out).all())
          and fused["sparse_pages"] >= 1
          and (not on_chip or "tpu_custom_call" in text),
          shape=list(out.shape), fused=fused,
          first_call_seconds=round(wide_pred_s, 3),
          note="smoke timing (compile included), not a benchmark number")

    stats = dev.memory_stats()
    emit(phase="memory", backend_reports_memory_stats=stats is not None,
         peak_bytes_in_use=(stats or {}).get("peak_bytes_in_use"),
         bytes_limit=(stats or {}).get("bytes_limit"))
    emit(phase="checks", ok=not failed, failed=failed)
    return 1 if failed else 0


def data_parallel(opts: dict) -> int:
    """Phase `data_parallel` (--chips 4): K train steps at the flagship width
    on a 4x1x1 mesh, as `train --mesh 4,1,1` builds its Trainer, against the
    same K steps on one device of the same process."""
    import jax
    import numpy as np

    from deeprest_tpu.compile_cache import configure_compile_cache
    from deeprest_tpu.config import MeshConfig
    from deeprest_tpu.parallel.distributed import feed_global_batch
    from deeprest_tpu.parallel.mesh import make_mesh

    configure_compile_cache()
    rehearse = opts["rehearse"]
    z = opts["sizes"]
    _dev, device, versions = _device_record()
    emit(phase="device", device=device, versions=versions,
         ok=device["platform"] == "tpu" and device["count"] == 4)
    if device["count"] != 4 or (device["platform"] != "tpu" and not rehearse):
        return 3
    backend = "pallas_interpret" if rehearse else "auto"
    b, w, f, e = z["B"], z["W"], z["F"], z["E"] or 2
    rng = np.random.default_rng(opts["seed"])
    batches = [(rng.random((b, w, f), np.float32),
                rng.random((b, w, e), np.float32), np.ones((b,), np.float32))
               for _ in range(z["dp_steps"])]

    def run(mesh):
        trainer = _flagship(opts, backend, f, mesh=mesh)
        state = trainer.init_state(batches[0][0])
        losses, fed = [], None
        for batch in batches:
            fed = tuple(feed_global_batch(trainer.mesh, a) for a in batch)
            state, loss = trainer._train_step(state, *fed)
            losses.append(float(loss))
        text = trainer._train_step.lower(state, *fed).compile().as_text()
        return state, losses, fed, text

    one_state, one_losses, _, _ = run(
        make_mesh(MeshConfig(), devices=jax.devices()[:1]))
    dp_state, dp_losses, fed, text = run(make_mesh(MeshConfig(data=4)))

    rows = [int(s.data.shape[0]) for s in fed[0].addressable_shards]
    spread = [len(leaf.sharding.device_set)
              for leaf in jax.tree.leaves(dp_state.params)]
    parity = bool(np.allclose(dp_losses, one_losses, rtol=DP_RTOL,
                              atol=DP_ATOL))
    param_diff = max(
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(c, np.float32))))
        for a, c in zip(jax.tree.leaves(dp_state.params),
                        jax.tree.leaves(one_state.params)))
    ok = (parity and rows == [b // 4] * 4 and set(spread) == {4}
          and all(np.isfinite(dp_losses))
          and (rehearse or "tpu_custom_call" in text))
    emit(phase="data_parallel", ok=ok, mesh="4x1x1", steps=z["dp_steps"],
         dp_losses=dp_losses, single_device_losses=one_losses,
         rtol=DP_RTOL, atol=DP_ATOL, loss_parity=parity,
         batch_rows_per_device=rows,
         devices_holding_each_param_leaf=sorted(set(spread)),
         params_max_abs_diff=param_diff,
         kernel_in_step="tpu_custom_call" in text,
         all_reduces=text.count(" all-reduce("))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# the CLI pipeline (parent side)
# ---------------------------------------------------------------------------


def _cli(argv: list[str], work: str, timeout: float = 900) -> str:
    """One `python -m deeprest_tpu ...` child to its end; returns stdout."""
    proc = subprocess.run(
        [sys.executable, "-m", "deeprest_tpu", *argv], cwd=work,
        env=_child_env(), stdout=subprocess.PIPE, text=True, timeout=timeout)
    if proc.returncode != 0:
        tail = proc.stdout.strip().splitlines()[-5:]
        raise PhaseFailed(f"deeprest_tpu {argv[0]} exited "
                          f"{proc.returncode}: {' | '.join(tail)}")
    return proc.stdout


def _last_json(stdout: str) -> dict:
    for line in reversed(stdout.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict):
            return rec
    raise PhaseFailed(f"no JSON record in: {stdout[-300:]!r}")


def _train(name: str, argv: list[str], work: str) -> None:
    t0 = time.perf_counter()
    out = _cli(["train", *argv], work)
    losses = [float(line.split("train ")[1].split()[0])
              for line in out.splitlines() if line.startswith("epoch ")]
    ok = (len(losses) >= 2 and all(x == x and abs(x) != float("inf")
                                   for x in losses)
          and losses[-1] < losses[0])
    emit(phase=name, ok=ok, epoch_train_losses=losses,
         wall_seconds=round(time.perf_counter() - t0, 1), argv=argv,
         note="smoke timing (start-up and compile included)")
    if not ok:
        raise PhaseFailed(f"{name}: loss not finite and falling: {losses}")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class _Server:
    """`deeprest_tpu serve` as a child that owns the chip while it lives."""

    def __init__(self, argv: list[str], work: str, boot_timeout: float = 600):
        self.port = _free_port()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "deeprest_tpu", "serve", *argv,
             "--host", "127.0.0.1", "--port", str(self.port)],
            cwd=work, env=_child_env(), stdout=subprocess.PIPE, text=True)
        self.startup: dict | None = None
        t0 = time.perf_counter()
        killer = threading.Timer(boot_timeout, self.proc.kill)
        killer.start()
        try:
            for line in self.proc.stdout:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict) and "listening" in rec:
                    self.startup = rec
                    break
        finally:
            killer.cancel()
        if self.startup is None:
            self.proc.wait()
            raise PhaseFailed(f"serve {argv} never listened "
                              f"(exit {self.proc.returncode})")
        self.boot_seconds = time.perf_counter() - t0
        # keep the pipe drained so the server never blocks on a full one
        threading.Thread(target=lambda: [None for _ in self.proc.stdout],
                         daemon=True).start()

    def request(self, path: str, body: dict | None = None,
                timeout: float = 600):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{self.port}{path}", data=data,
            headers={"Content-Type": "application/json"})
        t0 = time.perf_counter()
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                status, payload = resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            status, payload = e.code, {"error": e.read().decode()[:500]}
        return status, payload, time.perf_counter() - t0

    def stop(self) -> int:
        """SIGINT is the operator's stop; a clean one exits 0."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGINT)
            try:
                self.proc.wait(timeout=60)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode


def _finite(obj) -> bool:
    if isinstance(obj, (list, tuple)):
        return all(_finite(v) for v in obj)
    if isinstance(obj, dict):
        return all(_finite(v) for v in obj.values())
    if isinstance(obj, float):
        return obj == obj and abs(obj) != float("inf")
    return True


def _serve_main(work: str, z: dict, features: dict, on_tpu: bool) -> None:
    """The serving plane on the bf16 checkpoint: every route once, one
    what-if sweep wide enough for a super-rung, a burst the batcher can
    coalesce, then /healthz for who did the work, then a clean stop."""
    w = z["W"]
    traffic = features["traffic"][:2 * w]
    observed = features["observed"][:2 * w]
    srv = _Server(["--ckpt-dir", "ckpt", "--raw", "raw_serve.jsonl"], work)
    try:
        calls = {}
        status, health, _ = srv.request("/healthz")
        ok = status == 200 and health.get("ok") is True
        status, meta, _ = srv.request("/v1/meta")
        endpoints = meta.get("whatif_endpoints") or []
        ok = ok and status == 200 and len(endpoints) > 0
        mix = {ep: 3 + i for i, ep in enumerate(endpoints[:3])}

        def call(name, path, body, want):
            status, payload, seconds = srv.request(path, body)
            good = status == 200 and want in payload and _finite(payload)
            calls[name] = {"status": status, "seconds": round(seconds, 3),
                           "ok": good}
            if not good:
                calls[name]["error"] = str(payload)[:300]
            return payload

        p = call("predict", "/v1/predict", {"traffic": traffic},
                 "predictions")
        ok = ok and len(p.get("predictions", ())) == 2 * w
        call("whatif", "/v1/whatif", {"expected_traffic": [mix] * (2 * w)},
             "estimates")
        call("anomaly", "/v1/anomaly",
             {"traffic": traffic, "observed": observed}, "reports")
        call("whatif_wide", "/v1/whatif",
             {"expected_traffic": [mix] * (w * z["wide_windows"])},
             "estimates")
        # four callers at once: what the cross-request batcher coalesces
        burst = [threading.Thread(
            target=call, args=(f"burst{i}", "/v1/predict",
                               {"traffic": traffic}, "predictions"))
            for i in range(4)]
        for t in burst:
            t.start()
        for t in burst:
            t.join()
        _, health, _ = srv.request("/healthz")
        fused = health.get("fused_infer") or {}
        batcher = health.get("batcher") or {}
        rows = fused.get("max_dispatch_rows", 0)
        ok = (ok and all(c["ok"] for c in calls.values())
              and fused.get("pages", 0) >= 1
              and batcher.get("batches", 0) >= 1
              # on an accelerator the wide sweep is one super-rung dispatch
              and (not on_tpu or rows >= z["wide_windows"]))
    finally:
        rc = srv.stop()
    ok = ok and rc == 0
    emit(phase="serve", ok=ok, boot_seconds=round(srv.boot_seconds, 1),
         calls=calls, fused_infer=fused,
         batcher={k: batcher.get(k) for k in (
             "submitted", "batches", "windows", "coalesced_batches",
             "max_batch_windows")},
         shutdown_exit_code=rc,
         note="smoke timings (first calls compile), not benchmark numbers")
    if not ok:
        raise PhaseFailed("serve: see the record above")


def _serve_once(name: str, argv: list[str], work: str, traffic, probe) -> None:
    """One more start of the server with other flags, one request, a look
    at the startup record and /healthz through ``probe``, a clean stop."""
    srv = _Server(["--ckpt-dir", "ckpt", *argv], work)
    try:
        status, payload, seconds = srv.request("/v1/predict",
                                               {"traffic": traffic})
        _, health, _ = srv.request("/healthz")
    finally:
        rc = srv.stop()
    facts = probe(srv.startup, health)
    ok = (status == 200 and _finite(payload) and "predictions" in payload
          and rc == 0 and facts.pop("ok"))
    emit(phase=name, ok=ok, status=status, boot_seconds=round(
        srv.boot_seconds, 1), request_seconds=round(seconds, 3),
        shutdown_exit_code=rc, **facts)
    if not ok:
        raise PhaseFailed(f"{name}: see the record above")


def _corpus_seed(z: dict, seed: int) -> int:
    """The first seed >= ``seed`` whose synthetic topology reports exactly
    ``z["E"]`` metrics (2 per component, 5 per stateful one — what
    workload/telemetry.py emits).  numpy only; any seed when E is free."""
    if z["E"] is None:
        return seed
    sys.path.insert(0, HERE)
    from deeprest_tpu.workload.microtopo import (
        SyntheticMicroserviceApp, TopologyParams,
    )
    from deeprest_tpu.workload.telemetry import is_stateful

    for candidate in range(seed, seed + 1000):
        app = SyntheticMicroserviceApp(TopologyParams(
            num_services=z["services"], num_endpoints=z["endpoints"],
            seed=candidate))
        if sum(5 if is_stateful(c) else 2
               for c in app.components) == z["E"]:
            return candidate
    raise PhaseFailed(f"no seed from {seed} on gives {z['E']} metrics")


def _features(work: str, path: str) -> dict:
    """Rows of a featurized corpus, as JSON-ready lists (numpy only)."""
    import numpy as np

    with np.load(os.path.join(work, path)) as zf:
        return {"traffic": zf["traffic"].astype(float).tolist(),
                "observed": zf["resource_values"].astype(float).tolist()}


def pipeline(opts: dict, work: str) -> None:
    z = opts["sizes"]
    seed = str(_corpus_seed(z, opts["seed"]))
    on_tpu = not opts["rehearse"]
    app = ["--app", "synthetic", "--services", str(z["services"]),
           "--endpoints", str(z["endpoints"])]
    t0 = time.perf_counter()
    rec = _last_json(_cli(["simulate", *app, "--ticks", str(z["ticks"]),
                           "--seed", seed, "--out", "raw.jsonl"], work))
    _cli(["simulate", *app, "--ticks", str(z["serve_ticks"]), "--seed", seed,
          "--out", "raw_serve.jsonl"], work)
    emit(phase="simulate", ok=rec["buckets"] == z["ticks"], **rec,
         corpus_seed=int(seed),
         wall_seconds=round(time.perf_counter() - t0, 1))

    t0 = time.perf_counter()
    rec = _last_json(_cli(
        ["featurize", "--raw", "raw.jsonl", "--hash-features", "--capacity",
         str(z["F"]), "--out", "input.npz"], work))
    ok = rec["capacity"] == z["F"] and len(rec["metrics"]) == (
        z["E"] or len(rec["metrics"]))
    emit(phase="featurize", ok=ok, buckets=rec["buckets"],
         capacity=rec["capacity"], metrics=len(rec["metrics"]),
         # the CLI walks traces in Python; native/libdeeprest_etl.so is only
         # behind data/native.featurize_jsonl, which nothing here calls
         featurizer="python",
         wall_seconds=round(time.perf_counter() - t0, 1))
    if not ok:
        raise PhaseFailed(f"featurize: {rec['capacity']} features, "
                          f"{len(rec['metrics'])} metrics")

    common = ["--features", "input.npz", "--batch-size", str(z["B"]),
              "--window", str(z["W"]), "--hidden-size", str(z["cli_hidden"]),
              "--compute-dtype", z["dtype"], "--split", str(z["split"]),
              "--seed", seed, "--no-baselines"]
    _train("train", [*common, "--epochs", str(z["epochs"]),
                     "--ckpt-dir", "ckpt"], work)
    if not os.path.isdir(os.path.join(work, "ckpt")):
        raise PhaseFailed("train: no checkpoint written")
    _train("train_superstep_g4",
           [*common, "--epochs", str(max(2, z["epochs"] // 2)),
            "--device-data", "always", "--steps-per-superstep", "4",
            "--grad-accum-windows", "4", "--ckpt-dir", "ckpt_g4"], work)

    features = _features(work, "input.npz")
    _serve_main(work, z, features, on_tpu)
    traffic = features["traffic"][:2 * z["W"]]

    _serve_once(
        "serve_int8", ["--quant", "int8"], work, traffic,
        lambda startup, health: {
            "ok": health.get("quant", {}).get("mode") == "int8",
            "quant": health.get("quant")})

    t0 = time.perf_counter()
    rec = _last_json(_cli(["export", "--ckpt-dir", "ckpt", "--out", "artifact",
                           "--aot"], work))
    emit(phase="export_aot", ok=bool(rec.get("aot")), aot=rec.get("aot"),
         wall_seconds=round(time.perf_counter() - t0, 1))
    # a second start that LOADS: fleet admission deserializes the sidecar;
    # without a batcher even a short series rides the fused engine
    _serve_once(
        "serve_aot_load", ["--fleet", "twin=ckpt", "--no-batcher"], work,
        traffic,
        lambda startup, health: {
            "ok": ((startup.get("fleet") or {}).get("aot", {}).get("loaded", 0)
                   >= 1
                   and (startup["fleet"]["aot"]["compile_fallbacks"] == 0)
                   and health.get("fused_infer", {}).get("aot_pages", 0) >= 1),
            "aot": (startup.get("fleet") or {}).get("aot"),
            "aot_pages": health.get("fused_infer", {}).get("aot_pages")})


# ---------------------------------------------------------------------------


def _first(records: list[dict], phase: str) -> dict | None:
    return next((r for r in records if r.get("phase") == phase), None)


def _four_chips(opts: dict, env: dict) -> tuple[dict | None, list[str]]:
    rc, records = _api_child("data_parallel", opts, env)
    device = (_first(records, "device") or {}).get("device")
    return device, ["data_parallel"] if rc != 0 else []


def _one_chip(opts: dict, env: dict) -> tuple[dict | None, list[str]]:
    rc, records = _api_child("checks", opts, env)
    device = (_first(records, "device") or {}).get("device")
    if rc == 3:                      # no accelerator, and not rehearsing
        return device, ["device"]
    failed = ["checks"] if rc != 0 else []
    # the same compile in a second process: the cache proof
    cold = _first(records, "compile")
    rc2, again = _api_child("checks", {**opts, "compile_only": True}, env)
    warm = _first(again, "compile")
    ok = bool(rc2 == 0 and cold and warm and warm["cache_hit"]
              and (cold["cache_hit"] or warm["seconds"] < cold["seconds"]))
    emit(phase="compile_cache", ok=ok,
         cold_seconds=cold and cold["seconds"],
         cold_was_a_hit=cold and cold["cache_hit"],
         warm_seconds=warm and warm["seconds"],
         second_process_hit=warm and warm["cache_hit"],
         note="smoke timings, not benchmark numbers")
    if not ok:
        failed.append("compile_cache")
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        try:
            pipeline(opts, work)
        except (PhaseFailed, subprocess.TimeoutExpired, KeyError,
                ImportError) as e:
            emit(phase="pipeline", ok=False, error=str(e)[:600])
            failed.append("pipeline")
    return device, failed


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel path on four chips "
                         "and its single-device comparison")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the corpus, the weights and the batches")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU, kernels in interpret mode: "
                         "control flow only; reports failure like any run "
                         "without a chip")
    args = ap.parse_args()
    opts = {"seed": args.seed, "rehearse": args.rehearse,
            "sizes": REHEARSE if args.rehearse else FULL}
    env = {}
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        os.environ["JAX_PLATFORMS"] = "cpu"      # the CLI children too
        if args.chips == 4:
            env["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                + " --xla_force_host_platform_device_count=4")

    t_start = time.perf_counter()
    try:
        device, failed = (_four_chips if args.chips == 4 else _one_chip)(
            opts, env)
    finally:
        emit(phase="total", wall_seconds=round(time.perf_counter() - t_start, 1))

    want = {"platform": "tpu", "count": args.chips}
    on_chip = device is not None and all(device.get(k) == v
                                         for k, v in want.items())
    if not on_chip and "device" not in failed:
        failed.append("device")
    if failed:
        print(json.dumps({"ok": False, "device": device, "failed": failed}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
