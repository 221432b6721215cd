"""Engine replicas: N independent serving stacks behind one routing front.

One fused engine saturates around a single device's dispatch pipeline —
PERF.md's serving table stops at concurrency 16 on one
Predictor/MicroBatcher/FusedRolledEngine stack inside one process.  The
Clipper shape (PAPERS.md [2]) scales past that by replicating the model
container and putting batching/admission in a routing layer.  This module
is the replica half of that split; serve/router.py is the front.

Two replica kinds behind ONE interface (``predict_series``,
``predict_series_many``, ``outstanding``, ``drain``/``resume``/
``wait_idle``, ``reload_backend``, ``close``):

``EngineReplica``
    In-process: a full serving stack (Predictor or ExportedPredictor +
    shape ladder + fused rolled engine + optional per-stack MicroBatcher)
    pinned to one device via ``jax.default_device``.  Replicas that
    resolve to the SAME device (the virtual-CPU dev box, or more replicas
    than chips) SHARE one stack: executables are per-device, so a second
    replica on a device compiles nothing new — the scheduling state
    (outstanding-work counter, drain flag) stays per replica.

``ProcessReplica``
    A worker subprocess (``multiprocessing`` spawn context — fork after
    JAX initialization is unsafe) building its own stack from a spec
    (checkpoint dir, artifact dir, or a ``module:function`` factory) and
    serving requests over a duplex pipe.  The parent side multiplexes
    concurrent requests by id (send lock + one reader thread resolving
    futures); the child handles them on a small thread pool so its
    MicroBatcher still coalesces.  Process replicas sidestep the GIL and
    give each engine its own runtime — the deployment shape for one
    replica per host/chip.

The router never sees the difference: both kinds expose the same
outstanding-work signal its least-outstanding-work dispatch reads.
"""

from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from deeprest_tpu.obs import metrics as obs_metrics
from deeprest_tpu.obs import spans as obs_spans


class ReplicaDeadError(RuntimeError):
    """A replica cannot answer this request: its worker died, its pipe
    broke, or it blew through the per-request deadline.

    ``retriable`` encodes the router's no-double-execution safety rule
    (serve/router.py): True only when the failure PROVES no response was
    or ever will be produced — the send never reached the worker, or the
    worker process is dead (its device state died with it, so the work
    cannot complete elsewhere-visibly).  A deadline expiry on a LIVE
    worker is retriable=False: the request may still be executing on the
    device, and re-dispatching it would double-execute — the router
    ejects the replica and answers a fast 503 instead.
    """

    def __init__(self, message: str, replica: str = "",
                 retriable: bool = False):
        super().__init__(message)
        self.replica = replica
        self.retriable = retriable


def _release_proc(proc) -> None:
    """Free a reaped worker's parent-side resources NOW (the Popen
    sentinel pipe fd otherwise lives until garbage collection — the
    chaos harness's post-storm fd census counts exactly such strays).
    No-op while the process is still running."""
    if proc is None or proc.is_alive():
        return
    try:
        proc.close()
    except ValueError:
        pass        # already closed / never started


def _num_windows(t: int, w: int) -> int:
    """Window count of a [T, F] series under the serving tiling (regular
    stride-W tiling + right-aligned ragged tail) — the router's
    outstanding-work unit."""
    if t < w:
        return 1
    n = (t - w) // w + 1
    return n + (1 if (t - w) % w != 0 else 0)


def clone_backend(backend, device=None, **overrides):
    """A fresh serving stack sharing ``backend``'s restored state.

    Params/stats/metadata are shared (device_put onto ``device`` when one
    is given); ladders, fused engines, and jit wrappers are NEW — each
    clone compiles for (and dispatches on) its own device.  Works for
    both in-process backends: Predictor (has ``params``) and
    ExportedPredictor (has the serialized module).
    """
    import jax

    if hasattr(backend, "params"):           # in-process Predictor
        from deeprest_tpu.serve.predictor import Predictor

        params = backend.params
        if device is not None:
            params = jax.device_put(params, device)
        kwargs = dict(
            ladder=backend.ladder.base_ladder,
            coalesce_groups=backend.ladder.coalesce_groups,
            fused=backend.fused is not None,
            page_windows=(backend.fused.page
                          if backend.fused is not None else None),
            coalesce_pages=(backend.fused.coalesce_pages
                            if backend.fused is not None else None),
        )
        kwargs.update(overrides)
        return Predictor(
            params=params,
            model_config=backend.model_config,
            x_stats=backend.x_stats,
            y_stats=backend.y_stats,
            metric_names=backend.metric_names,
            window_size=backend.window_size,
            space_dict=backend.space_dict,
            delta_mask=backend.delta_mask,
            **kwargs,
        )
    if hasattr(backend, "_exported"):        # exported artifact
        from deeprest_tpu.serve.export import ExportedPredictor

        kwargs = dict(
            ladder=backend.ladder.base_ladder,
            coalesce_groups=backend.ladder.coalesce_groups,
            fused=backend.fused is not None,
            page_windows=(backend.fused.page
                          if backend.fused is not None else None),
            coalesce_pages=(backend.fused.coalesce_pages
                            if backend.fused is not None else None),
        )
        kwargs.update(overrides)
        return ExportedPredictor(backend._exported, backend.manifest,
                                 **kwargs)
    raise TypeError(f"cannot clone serving backend {type(backend).__name__}")


class EngineReplica:
    """One in-process serving stack + the per-replica scheduling state the
    router reads (outstanding windows, drain flag).

    ``backend`` may be SHARED with other replicas pinned to the same
    device (executables are per-device; see module docstring) — the
    router's rolling reload groups such replicas and swaps their shared
    stack once, after draining all of them.
    """

    kind = "thread"

    def __init__(self, backend, name: str = "r0", device=None,
                 batching=None):
        from deeprest_tpu.serve.batcher import MicroBatcher

        self.name = name
        self.device = device
        # Guards every mutable field below: the ThreadingHTTPServer front
        # calls replicas from concurrent handler threads while the router
        # reads outstanding counters and the reload path flips the drain
        # flag (graftlint TH001 discipline).
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._backend = backend
        self._outstanding = 0          # windows currently dispatched here
        # Served totals are obs Counters (per-instance objects): the
        # stats() JSON, the router's /metrics collector, and the
        # autoscaler's demand read all consume the SAME objects.
        self._m_served_requests = obs_metrics.Counter(
            "deeprest_replica_served_requests_total",
            labelnames=("replica",))
        self._m_served_windows = obs_metrics.Counter(
            "deeprest_replica_served_windows_total",
            labelnames=("replica",))
        self._draining = False
        self._closed = False
        self._batching = batching
        if batching is not None and backend.batcher is None:
            backend.attach_batcher(MicroBatcher(backend.ladder, batching))

    # -- scheduling signal (read by the router's dispatch loop) ----------

    def outstanding(self) -> int:
        with self._lock:
            return self._outstanding

    def available(self) -> bool:
        with self._lock:
            return not (self._draining or self._closed)

    @property
    def window_size(self) -> int:
        with self._lock:
            return self._backend.window_size

    def backend(self):
        with self._lock:
            return self._backend

    # -- serving ---------------------------------------------------------

    def _begin(self, windows: int):
        with self._lock:
            if self._closed:
                raise RuntimeError(f"replica {self.name} is closed")
            self._outstanding += windows
        return windows

    def _end(self, windows: int, requests: int = 1) -> None:
        with self._cv:
            self._outstanding -= windows
            self._cv.notify_all()      # wake wait_idle() drains
        self._m_served_requests.inc(requests, replica=self.name)
        self._m_served_windows.inc(windows, replica=self.name)

    def served_requests(self) -> int:
        return int(self._m_served_requests.value(replica=self.name))

    def served_windows(self) -> int:
        return int(self._m_served_windows.value(replica=self.name))

    def predict_series(self, traffic: np.ndarray,
                       integrate: bool = True, backend=None) -> np.ndarray:
        # ``backend`` override: the fleet tier (serve/fleet.py) resolves
        # tenant → pool-entry predictor BEFORE dispatch and serves this
        # one request through it — the replica still owns the scheduling
        # state (outstanding windows, drain flag), the pool owns the
        # per-tenant weights.  None keeps the replica's own stack.
        if backend is None:
            with self._lock:
                backend = self._backend
        n = self._begin(_num_windows(len(traffic), backend.window_size))
        try:
            with _device_ctx(self.device), \
                    obs_spans.RECORDER.span(
                        "replica.predict",
                        component="deeprest-replica") as sp:
                sp.tag(replica=self.name, windows=n)
                return backend.predict_series(traffic, integrate=integrate)
        finally:
            self._end(n)

    def predict_series_many(self, series_list, integrate: bool = True,
                            backend=None):
        if backend is None:
            with self._lock:
                backend = self._backend
        series_list = list(series_list)
        n = self._begin(sum(_num_windows(len(s), backend.window_size)
                            for s in series_list))
        try:
            with _device_ctx(self.device), \
                    obs_spans.RECORDER.span(
                        "replica.predict",
                        component="deeprest-replica") as sp:
                sp.tag(replica=self.name, windows=n,
                       series=len(series_list))
                return backend.predict_series_many(series_list,
                                                   integrate=integrate)
        finally:
            self._end(n, requests=len(series_list))

    # -- lifecycle (the router's rolling-reload path) --------------------

    def drain(self) -> None:
        """Stop receiving router dispatches (in-flight work finishes)."""
        with self._lock:
            self._draining = True

    def resume(self) -> None:
        with self._lock:
            self._draining = False

    def wait_idle(self, timeout_s: float = 30.0) -> bool:
        """Block until every dispatched window has completed."""
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._outstanding > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return True

    def reload_backend(self, fresh) -> None:
        """Swap in a drained-and-rebuilt stack.  The caller (router) has
        already drained this replica, so no request straddles the swap —
        the no-mixed-params guarantee is structural."""
        from deeprest_tpu.serve.batcher import MicroBatcher

        with self._lock:
            # ONE critical section from the batching/backend read to the
            # publish (graftrace RC003): two concurrent reloads — or a
            # reload racing set_batching — would otherwise both read the
            # same `old`, and the loser's published stack (batcher and
            # all) retires silently, never detached or closed.  The
            # MicroBatcher built here touches only the unpublished
            # `fresh`, so holding the lock across it cannot invert
            # lock order.
            batching = self._batching
            old = self._backend
            if batching is not None and fresh.batcher is None:
                fresh.attach_batcher(MicroBatcher(fresh.ladder, batching))
            self._backend = fresh
        old_b = old.batcher
        if old_b is not None and old_b is not fresh.batcher:
            old.attach_batcher(None)
            old_b.close()

    def set_batching(self, config) -> None:
        """(Re)attach a per-stack MicroBatcher (None detaches)."""
        from deeprest_tpu.serve.batcher import MicroBatcher

        with self._lock:
            backend = self._backend
            self._batching = config
        old = backend.batcher
        fresh = (MicroBatcher(backend.ladder, config)
                 if config is not None else None)
        backend.attach_batcher(fresh)
        if old is not None:
            old.close()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._draining = True
            backend = self._backend
        b = backend.batcher
        if b is not None:
            backend.attach_batcher(None)
            b.close()

    def stats(self) -> dict:
        with self._lock:
            out = {
                "name": self.name,
                "kind": self.kind,
                "device": str(self.device) if self.device is not None else None,
                "outstanding_windows": self._outstanding,
                "served_requests": self.served_requests(),
                "served_windows": self.served_windows(),
                "state": ("closed" if self._closed
                          else "draining" if self._draining else "live"),
            }
            backend = self._backend
        b = backend.batcher
        if b is not None:
            out["batcher"] = b.stats()
        cache = getattr(backend, "jit_cache_size", None)
        if callable(cache):
            out["jit_cache_size"] = cache()
        return out


def _device_ctx(device):
    """``jax.default_device`` ONLY when the replica's device differs from
    the process default: the default-device setting is part of the jit
    cache key, so entering the context for the device that is already the
    default would mint a second, bit-identical executable per program —
    exactly the waste the shared-stack plane avoids.  Committed params
    (clone_backend's device_put) pin Predictor dispatches regardless; the
    context covers uncommitted-input backends (exported artifacts)."""
    import jax

    if device is None:
        return contextlib.nullcontext()
    default = getattr(jax.config, "jax_default_device", None)
    if default is None:
        default = jax.devices()[0]
    if device == default:
        return contextlib.nullcontext()
    return jax.default_device(device)


# ---------------------------------------------------------------------------
# Worker-subprocess replicas


def _resolve_factory(path: str):
    import importlib

    mod, _, fn = path.partition(":")
    if not fn:
        raise ValueError(f"bad factory spec {path!r} (want 'module:function')")
    return getattr(importlib.import_module(mod), fn)


def build_backend_from_spec(spec: dict):
    """Child-side stack construction: checkpoint dir, artifact dir, or a
    ``module:function`` factory, with optional serving kwargs."""
    import sys

    for p in spec.get("sys_path", ()):     # test factories live off-package
        if p not in sys.path:
            sys.path.insert(0, p)
    kwargs = dict(spec.get("kwargs") or {})
    if spec.get("ckpt_dir"):
        from deeprest_tpu.serve.predictor import Predictor

        return Predictor.from_checkpoint(spec["ckpt_dir"], **kwargs)
    if spec.get("artifact"):
        from deeprest_tpu.serve.export import ExportedPredictor

        return ExportedPredictor.load(spec["artifact"], **kwargs)
    if spec.get("factory"):
        return _resolve_factory(spec["factory"])(**kwargs)
    raise ValueError(f"replica spec needs ckpt_dir, artifact, or factory: "
                     f"{sorted(spec)}")


def _worker_main(spec: dict, conn) -> None:
    """Subprocess entry: build the stack, then serve pipe requests on a
    small thread pool (so the in-child MicroBatcher still coalesces).

    Observability: with ``spec["obs"]`` the child enables its own span
    recorder, adopts the parent's propagated ``(trace_id, span_id)``
    context per request, and forwards its committed spans back over the
    SAME duplex pipe as ``"__spans__"``-tagged messages — the parent's
    reader ingests them into the process-default recorder, so a request's
    trace crosses the process boundary intact.
    """
    import os
    from concurrent.futures import ThreadPoolExecutor

    # The worker's platform is the spec's, or else the parent's (the
    # environment it inherits) — never a quiet CPU.  With a platform named,
    # JAX fails the backend's start-up, and with it the boot, when it is
    # not there: a worker that was to hold a chip does not serve without it.
    if spec.get("jax_platform"):
        os.environ["JAX_PLATFORMS"] = spec["jax_platform"]
    obs_on = bool(spec.get("obs"))
    if obs_on:
        from deeprest_tpu import obs

        obs.configure(enabled=True)
    try:
        backend = build_backend_from_spec(spec)
        if spec.get("batching"):
            from deeprest_tpu.serve.batcher import BatcherConfig, MicroBatcher

            cfg = BatcherConfig(**spec["batching"])
            backend.attach_batcher(MicroBatcher(backend.ladder, cfg))
    except Exception as exc:   # surface the constructor error to the parent
        conn.send(("__boot__", False, f"{type(exc).__name__}: {exc}"))
        conn.close()
        return
    conn.send(("__boot__", True, {
        "window_size": backend.window_size,
        "metric_names": list(backend.metric_names),
        "feature_dim": backend.feature_dim,
        "quantiles": list(backend.quantiles),
        "median_index": backend.median_index(),
        "delta_mask": (np.asarray(backend.delta_mask, bool).tolist()
                       if backend.delta_mask is not None else None),
        # y_stats ride the handshake so the router can serve the full
        # AnomalyDetector protocol (scale floors for re-anchored/delta
        # metrics) — the streaming verdict surface sweeps THROUGH the
        # router, same as /v1/anomaly.
        "y_stats": (backend.y_stats.to_dict()
                    if getattr(backend, "y_stats", None) is not None
                    else None),
        # Per-tenant serving identity under a ``fleet`` key (ADDITIVE —
        # every existing handshake field keeps its shape).  A worker
        # subprocess serves exactly one stack, so its map has one entry,
        # but the SHAPE matches the pool's /healthz view: consumers read
        # fleet.tenants[t].{quant, params_digest} whether the plane is
        # one process worker or a hundred-tenant pool.
        "fleet": {"tenants": {"default": {
            "quant": getattr(backend, "quant", "off"),
            "params_digest": (backend.params_digest()
                              if callable(getattr(backend, "params_digest",
                                                  None)) else None),
        }}},
    }))
    send_lock = threading.Lock()

    def handle(req_id, method, args, ctx=None):
        token = obs_spans.set_context(ctx) if ctx is not None else None
        try:
            with obs_spans.RECORDER.span("replica.worker",
                                         component="deeprest-replica") as sp:
                if method == "predict_series":
                    traffic, integrate = args
                    sp.tag(method=method, windows=_num_windows(
                        len(traffic), backend.window_size))
                    out = backend.predict_series(traffic,
                                                 integrate=integrate)
                elif method == "predict_series_many":
                    series_list, integrate = args
                    sp.tag(method=method, series=len(series_list))
                    out = backend.predict_series_many(series_list,
                                                      integrate=integrate)
                else:
                    raise ValueError(f"unknown method {method!r}")
            with send_lock:
                conn.send((req_id, True, out))
        except Exception as exc:
            with send_lock:
                conn.send((req_id, False, f"{type(exc).__name__}: {exc}"))
        finally:
            if token is not None:
                obs_spans.set_context(None)
            if obs_on:
                batch = [r.to_dict() for r in obs_spans.RECORDER.drain()]
                if batch:
                    with send_lock:
                        conn.send(("__spans__", True, batch))

    try:
        with ThreadPoolExecutor(
                max_workers=int(spec.get("worker_threads", 4))) as pool:
            while True:
                try:
                    msg = conn.recv()
                except (EOFError, OSError):
                    break              # parent went away: drain and exit
                if msg is None:        # shutdown sentinel
                    break
                pool.submit(handle, *msg)
    finally:
        # close the child's pipe end on EVERY exit path (a handler bug
        # escaping the pool must not strand the parent's reader thread
        # on a half-open pipe)
        conn.close()


class ProcessReplica:
    """Worker-subprocess replica behind the EngineReplica interface."""

    kind = "process"

    def __init__(self, spec: dict, name: str = "p0",
                 boot_timeout_s: float = 120.0,
                 request_timeout_s: float | None = None):
        from concurrent.futures import Future

        self.name = name
        self.device = None             # the child owns its device binding
        self.spec = dict(spec)
        # Per-request deadline (None = the historical indefinite wait).
        # Without it a worker that dies mid-request BETWEEN heartbeats
        # wedges its caller forever on the response future — the bug the
        # router's ejection path consumes as a typed ReplicaDeadError.
        self.request_timeout_s = request_timeout_s
        # The child mirrors the parent's span-recording state at boot
        # (an explicit spec["obs"] wins — tests pin both modes).
        self.spec.setdefault("obs", obs_spans.RECORDER.enabled)
        self.boot_timeout_s = boot_timeout_s
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._outstanding = 0
        self._m_served_requests = obs_metrics.Counter(
            "deeprest_replica_served_requests_total",
            labelnames=("replica",))
        self._m_served_windows = obs_metrics.Counter(
            "deeprest_replica_served_windows_total",
            labelnames=("replica",))
        self._draining = False
        self._closed = False
        self._next_id = 0
        self._futures: dict[int, Future] = {}
        # Dedicated send lock: a pipe send can block when the OS buffer
        # fills, and blocking while holding the bookkeeping lock would
        # stall the reader thread (which needs it per response) — the
        # classic duplex-pipe deadlock.
        self._send_lock = threading.Lock()
        self._conn = None
        self._proc = None
        self._meta = None
        self._boot()

    def _boot(self) -> None:
        """Spawn a worker and wait for its stack to come up.  Called from
        __init__ and from reload (restart-with-newest-checkpoint); the
        caller guarantees no requests are in flight."""
        import multiprocessing as mp

        ctx = mp.get_context("spawn")  # fork after jax init is unsafe
        conn, child = ctx.Pipe(duplex=True)
        proc = None
        try:
            proc = ctx.Process(target=_worker_main,
                               args=(self.spec, child), daemon=True)
            proc.start()
            child.close()
            if not conn.poll(self.boot_timeout_s):
                raise RuntimeError(
                    f"replica {self.name}: worker boot timed out")
            # recv itself can raise (EOFError when the worker dies after
            # start but before the handshake lands) — the except below
            # owns cleanup for EVERY failed-boot path, so no path leaks
            # a pipe end or a live subprocess (graftlint RS001)
            tag, ok, meta = conn.recv()
            if tag != "__boot__" or not ok:
                raise RuntimeError(f"replica {self.name}: worker failed "
                                   f"to boot: {meta}")
        except Exception:
            conn.close()
            child.close()
            if proc is not None and proc.pid is not None:
                if proc.is_alive():
                    proc.terminate()
                proc.join(timeout=5)
                _release_proc(proc)
            raise
        with self._lock:
            self._conn = conn
            self._proc = proc
            self._meta = meta
            self._next_id = 0
        reader = threading.Thread(target=self._read_loop, args=(conn,),
                                  daemon=True,
                                  name=f"replica-{self.name}-reader")
        reader.start()

    # -- parent-side metadata -------------------------------------------

    @property
    def window_size(self) -> int:
        with self._lock:       # a reload swaps self._meta
            return self._meta["window_size"]

    def fleet_meta(self) -> dict | None:
        """The worker's per-tenant serving identity from the boot
        handshake (``{"tenants": {name: {quant, params_digest}}}``) —
        the process-replica half of the /healthz ``fleet`` view."""
        with self._lock:
            meta = self._meta
        return meta.get("fleet") if meta is not None else None

    def outstanding(self) -> int:
        with self._lock:
            return self._outstanding

    def available(self) -> bool:
        with self._lock:
            return not (self._draining or self._closed)

    def alive(self) -> bool:
        """Is the worker subprocess running?  The router's health layer
        reads this to pick between retry (dead ⇒ the request provably
        has no surviving execution) and eject-without-retry (alive but
        wedged ⇒ possible double-execution)."""
        with self._lock:
            proc = self._proc
        if proc is None:
            return False
        try:
            return proc.is_alive()
        except ValueError:
            return False       # reaped and released (close()/restart())

    # -- request multiplexing -------------------------------------------

    def _read_loop(self, conn) -> None:
        """Resolve response futures from ONE pipe generation; a reload
        swaps the pipe, and this loop exits on its EOF.  ``"__spans__"``
        messages are the worker's forwarded span batches — ingested into
        the parent's recorder, never a request response."""
        while True:
            try:
                req_id, ok, payload = conn.recv()
            except (EOFError, OSError):
                with self._lock:
                    stale = self._conn is not conn
                    pending = ([] if stale
                               else list(self._futures.values()))
                    if not stale:
                        self._futures.clear()
                for f in pending:
                    # Worker death proves no response will ever come and
                    # its device state died with it — retriable: the
                    # router may re-dispatch these to a survivor.
                    f.set_exception(ReplicaDeadError(
                        f"replica {self.name}: worker exited "
                        "mid-request", replica=self.name, retriable=True))
                return
            if req_id == "__spans__":
                if ok:
                    obs_spans.RECORDER.ingest(payload)
                continue
            with self._lock:
                fut = self._futures.pop(req_id, None)
            if fut is None:
                continue
            if ok:
                fut.set_result(payload)
            else:
                fut.set_exception(RuntimeError(payload))

    def _call(self, method: str, args, windows: int, requests: int = 1):
        from concurrent.futures import Future
        from concurrent.futures import TimeoutError as FutureTimeout

        fut = Future()
        with self._lock:
            if self._closed:
                raise RuntimeError(f"replica {self.name} is closed")
            req_id = self._next_id
            self._next_id += 1
            self._futures[req_id] = fut
            self._outstanding += windows
            conn = self._conn
        try:
            # the propagated trace context rides in the request tuple, so
            # the child's spans join this request's trace
            ctx = obs_spans.current_context()
            try:
                with self._send_lock:
                    conn.send((req_id, method, args, ctx))
            except (OSError, BrokenPipeError, ValueError) as exc:
                # the request never reached the worker: provably safe to
                # re-dispatch on a survivor
                with self._lock:
                    self._futures.pop(req_id, None)
                raise ReplicaDeadError(
                    f"replica {self.name}: request send failed ({exc})",
                    replica=self.name, retriable=True) from exc
            try:
                out = fut.result(timeout=self.request_timeout_s)
            except FutureTimeout:
                # Deadline blown.  Withdraw the future so a late answer
                # is dropped (the reader treats unknown ids as stale).
                # Retriability hinges on worker liveness: a DEAD worker
                # cannot be mid-execution — safe to retry; a live one may
                # still be running the request on its device, so a retry
                # would double-execute (the router ejects + 503s).
                with self._lock:
                    self._futures.pop(req_id, None)
                dead = not self.alive()
                why = ("worker dead" if dead else
                       "worker alive — not retried, the request may "
                       "still be executing")
                raise ReplicaDeadError(
                    f"replica {self.name}: no response within "
                    f"{self.request_timeout_s:.3f}s ({why})",
                    replica=self.name, retriable=dead) from None
        finally:
            with self._cv:
                self._outstanding -= windows
                self._cv.notify_all()
            self._m_served_requests.inc(requests, replica=self.name)
            self._m_served_windows.inc(windows, replica=self.name)
        return out

    def served_requests(self) -> int:
        return int(self._m_served_requests.value(replica=self.name))

    def served_windows(self) -> int:
        return int(self._m_served_windows.value(replica=self.name))

    def predict_series(self, traffic: np.ndarray,
                       integrate: bool = True, backend=None) -> np.ndarray:
        if backend is not None:
            # The override would need the tenant's params INSIDE the
            # worker subprocess; shipping a params tree per request over
            # the pipe is exactly the weight traffic the pool's
            # device-resident LRU exists to avoid.
            raise ValueError(
                "fleet backend override is not supported on process "
                "replicas — serve the fleet tier over in-process "
                "(thread) replicas")
        traffic = np.ascontiguousarray(traffic, np.float32)
        n = _num_windows(len(traffic), self.window_size)
        return self._call("predict_series", (traffic, integrate), n)

    def predict_series_many(self, series_list, integrate: bool = True,
                            backend=None):
        if backend is not None:
            raise ValueError(
                "fleet backend override is not supported on process "
                "replicas — serve the fleet tier over in-process "
                "(thread) replicas")
        series_list = [np.ascontiguousarray(s, np.float32)
                       for s in series_list]
        n = sum(_num_windows(len(s), self.window_size)
                for s in series_list)
        return self._call("predict_series_many", (series_list, integrate), n,
                          requests=len(series_list))

    # -- lifecycle -------------------------------------------------------

    def drain(self) -> None:
        with self._lock:
            self._draining = True

    def resume(self) -> None:
        with self._lock:
            self._draining = False

    def wait_idle(self, timeout_s: float = 30.0) -> bool:
        deadline = time.monotonic() + timeout_s
        with self._cv:
            while self._outstanding > 0:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(left)
            return True

    def reload_backend(self, fresh) -> None:
        """Process replicas reload by restart: the worker rebuilds its
        stack from the spec — ``fresh`` is only the reload trigger, since
        the child loads the newest checkpoint step itself.  The caller
        (router) has drained this replica, so no request is in flight."""
        self.restart()

    def restart(self) -> None:
        """Reboot the worker: new process/pipe/reader generation from the
        same spec.  Works on a HEALTHY drained worker (rolling reload)
        and on a dead or wedged one (the router's probe-and-rejoin path
        after an ejection — a SIGKILLed worker reboots here).  Any
        requests still pending against the old generation fail with a
        retriable ReplicaDeadError first, so no caller is left holding a
        future the new worker will never answer."""
        with self._lock:
            if self._closed:
                raise RuntimeError(f"replica {self.name} is closed")
            old_conn, old_proc = self._conn, self._proc
            orphans = list(self._futures.values())
            self._futures.clear()
        for f in orphans:
            f.set_exception(ReplicaDeadError(
                f"replica {self.name}: worker restarted mid-request",
                replica=self.name, retriable=True))
        try:
            self._boot()               # new pipe/process/reader generation
        finally:
            # reap the old generation even when the fresh boot fails (the
            # router's probe will retry the restart; the dead worker and
            # its pipe end must not outlive this attempt)
            if old_conn is not None:
                try:
                    old_conn.send(None)
                except (OSError, BrokenPipeError):
                    pass
                old_conn.close()       # old reader exits on EOF
            if old_proc is not None:
                old_proc.join(timeout=10)
                if old_proc.is_alive():
                    old_proc.terminate()
                    old_proc.join(timeout=5)
                _release_proc(old_proc)

    def set_batching(self, config) -> None:
        """Batching lives inside the worker's own stack: record the knob
        in the spec — it applies at the next boot (reload), where
        ``_worker_main`` attaches the MicroBatcher."""
        with self._lock:
            if config is None:
                self.spec.pop("batching", None)
            else:
                self.spec["batching"] = {
                    "max_batch": config.max_batch,
                    "max_linger_s": config.max_linger_s,
                    "max_queue": config.max_queue,
                }

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._draining = True
            conn, proc = getattr(self, "_conn", None), getattr(
                self, "_proc", None)
        if conn is not None:
            try:
                conn.send(None)
            except (OSError, BrokenPipeError):
                pass
            conn.close()
        if proc is not None:
            proc.join(timeout=10)
            if proc.is_alive():
                # a handler may still be mid-predict (the shutdown
                # sentinel only stops the recv loop); reap the SIGTERM
                # so close() returns with the worker actually gone
                proc.terminate()
                proc.join(timeout=5)
            _release_proc(proc)

    def stats(self) -> dict:
        with self._lock:
            try:
                pid = self._proc.pid if self._proc is not None else None
            except ValueError:
                pid = None     # reaped and released (close())
            return {
                "name": self.name,
                "kind": self.kind,
                "pid": pid,
                "outstanding_windows": self._outstanding,
                "served_requests": self.served_requests(),
                "served_windows": self.served_windows(),
                "state": ("closed" if self._closed
                          else "draining" if self._draining else "live"),
            }
