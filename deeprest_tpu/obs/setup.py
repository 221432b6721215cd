"""What happens before the first steady step, recorded by the program.

A retrain's set-up (state, staging, the first dispatches, every program
XLA compiles or loads from its cache on the way) costs more than its
epochs once the step is fast, and from outside it can only be timed whole.
This module holds the three pieces that are not one trainer's:

- :func:`phase` — the set-up phase open on this thread (``init_state``,
  ``stage``, ``first_dispatch``, ``epoch``; :data:`OTHER` outside all of
  them).  The trainer opens them; the innermost one names where a
  compilation happened.
- :func:`install` — ONE listener a process on ``jax.monitoring``.  Every
  backend compilation (served from the persistent cache or not) is added
  to ``deeprest_compilations_total{program,phase,cache}`` and its seconds
  to ``deeprest_compile_seconds_total{program,phase}``: ``program`` is the
  jitted function's name where it is one of the names :func:`install` was
  given and :data:`OTHER` for everything else (the primitives an un-jitted
  ``model.init`` runs one by one, a caller's own programs), so the label
  has a dozen values; ``cache`` is ``hit`` or ``miss`` by the cache's own
  event inside that compilation, ``uncached`` where it sent neither (the
  cache is off, or the entry was under its thresholds and not written),
  ``kept`` for an executable that ``train/kept.py`` loaded from its store
  before anything was traced (:func:`count_kept_load`: no event of jax's).
  With the span recorder on, a compilation under an open span is also a
  span ``deeprest-jax/compile`` tagged ``program`` and ``cache``, a child
  of that span, entered when the compilation begins: it lies on the
  profiler's clock like every other (outside every span it is counted and
  no span: it would be a trace of its own in the plane's exports).  A compilation in phase ``epoch``
  after the first epoch is a recompile, with its name: a program compiled
  twice.  (The superstep an epoch meets after a restage onto a NEW staged
  program is compiled in ``first_dispatch``, like the trainer's first.)
  The two stages before a compilation are heard the same way and kept
  beside it: ``deeprest_trace_seconds_total{program,phase}`` (Python
  tracing the function to a jaxpr) and
  ``deeprest_lower_seconds_total{program,phase}`` (the jaxpr to an MLIR
  module), as SELF time.  Tracing nests (every jitted ``jnp`` helper
  sends its own event inside the superstep's, thousands a process),
  lowering can trace, and tracing can compile and run a constant: only
  the OUTERMOST trace or lower event open on a thread is counted, under
  its own program and stage, less the compilations that ended inside it.
  So the three counters of a ``(program, phase)`` add up to the wall time
  the thread spent in jax's pipeline for it.  Under an open span the
  outermost event is also a span ``deeprest-jax/trace`` or
  ``deeprest-jax/lower`` tagged ``program``; a nested event is no span.
- :func:`setup_table` / :func:`format_setup` — the set-up gauges of the
  process registry (the form a staged sparse corpus took among them) as
  one table (``Trainer.profile_epoch``'s ``setup``,
  ``layers.json``) and as the one line ``deeprest_tpu train`` prints when
  its first epoch is done.  The names are this module's constants; the
  trainer sets them.
"""

from __future__ import annotations

import contextlib
import contextvars
import re
import threading

from deeprest_tpu.obs.metrics import REGISTRY
from deeprest_tpu.obs.spans import RECORDER, current_context

OTHER = "other"
UNCACHED = "uncached"

COMPILATIONS = "deeprest_compilations_total"
COMPILE_SECONDS = "deeprest_compile_seconds_total"
TRACE_SECONDS = "deeprest_trace_seconds_total"
LOWER_SECONDS = "deeprest_lower_seconds_total"
INIT_STATE_SECONDS = "deeprest_train_init_state_seconds"
STAGE_SECONDS = "deeprest_train_last_stage_seconds"
STAGINGS = "deeprest_train_stagings_total"
OPTIMIZER_ROWS = "deeprest_train_optimizer_rows"
ACCUMULATION = "deeprest_train_accumulation"
GATHER_PIECES = "deeprest_train_projection_gather_pieces"
PROJECTION_COLUMNS = "deeprest_train_projection_columns"
FIRST_DISPATCH_SECONDS = "deeprest_train_first_dispatch_seconds"
SUPERSTEP_PROGRAMS = "deeprest_train_superstep_programs_total"
SUPERSTEP_FIRST_DISPATCH_SECONDS = (
    "deeprest_train_superstep_first_dispatch_seconds")
DEVICE_BYTES = "deeprest_train_device_bytes"
PROGRAM_BYTES = "deeprest_train_program_bytes"
KERNEL_OPERAND_BYTES = "deeprest_train_kernel_operand_bytes"
TIME_REVERSALS = "deeprest_train_time_reversals"
KERNEL_EDGE_PASSES = "deeprest_train_kernel_edge_passes"
BARE_WEIGHT_GRAD_DOTS = "deeprest_train_bare_weight_grad_dots"
KEPT_EXECUTABLES = "deeprest_train_kept_executables_total"
KEPT = "kept"           # the `cache` of an executable train/kept.py loaded

_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                 "/jax/compilation_cache/cache_misses": "miss"}

# the stages of jax's pipeline the listener hears, by the event that
# brackets each (a scalar at its start, a duration at its end)
_COMPILE, _TRACE, _LOWER = "compile", "trace", "lower"
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": _TRACE,
           "/jax/core/compile/jaxpr_to_mlir_module_duration": _LOWER,
           "/jax/core/compile/backend_compile_duration": _COMPILE}

# `jit(train_superstep)` (a compilation, a lowering) and
# `jit_train_superstep` (a module's name); tracing sends the bare name
_WRAPPED = re.compile(r"\w+\((.*)\)|jit_(.*)")

_PHASE: contextvars.ContextVar[str] = contextvars.ContextVar(
    "deeprest_obs_setup_phase", default=OTHER)


@contextlib.contextmanager
def phase(name: str):
    """``name`` is the set-up phase of this thread until the block ends (a
    phase inside another wins while it is open)."""
    token = _PHASE.set(name)
    try:
        yield
    finally:
        _PHASE.reset(token)


def current_phase() -> str:
    return _PHASE.get()


def _compilations():
    return REGISTRY.counter(
        COMPILATIONS,
        "XLA backend compilations by jitted program (other: not one of the "
        "trainer's), by the set-up phase open on the compiling thread, and "
        "by what the persistent cache did (hit, miss, uncached)",
        labelnames=("program", "phase", "cache"))


_SECONDS = {
    _COMPILE: (COMPILE_SECONDS,
               "seconds of those compilations (a cache hit's are its load)"),
    _TRACE: (TRACE_SECONDS,
             "seconds a thread spent tracing jitted programs to jaxprs, by "
             "the outermost program and the set-up phase open on it: self "
             "time (what nests inside is counted once, a compilation inside "
             "it only as a compilation)"),
    _LOWER: (LOWER_SECONDS,
             "seconds a thread spent lowering jaxprs to MLIR modules, self "
             "time as tracing's"),
}


def _seconds(stage: str):
    name, text = _SECONDS[stage]
    return REGISTRY.counter(name, text, labelnames=("program", "phase"))


class _Open(threading.local):
    """What is open on this thread."""

    cache = UNCACHED    # the cache's verdict on the open compilation
    span = None         # its span
    depth = 0           # the open trace and lower events
    compiled = 0.0      # seconds of the compilations inside the outermost
    stage_span = None   # the outermost's span


class _Listener:
    """``jax.monitoring`` hands a stage's start as a scalar event and its
    seconds as a duration event at its end, the cache's verdict on a
    compilation as a plain event inside it, all on the thread that does
    the work."""

    def __init__(self):
        self.programs: set[str] = set()
        self._open = _Open()

    def _program(self, fun_name) -> str:
        """``train_superstep``, ``jit(train_superstep)`` and
        ``jit_train_superstep`` -> ``train_superstep`` if it is one of
        ours."""
        name = fun_name or ""
        if name not in self.programs:
            m = _WRAPPED.fullmatch(name)
            name = (m[1] or m[2]) if m else name
        return name if name in self.programs else OTHER

    def _span(self, stage: str, fun_name):
        """An entered span for the stage that begins, only under an open
        one: outside every span it would be a trace of its own in the
        plane's exports."""
        if current_context() is None:
            return None
        span = RECORDER.span(stage, "deeprest-jax",
                             {"program": self._program(fun_name)})
        span.__enter__()
        return span

    def began(self, event: str, _value, fun_name=None, **_kw) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        at = self._open
        if stage == _COMPILE:
            at.cache = UNCACHED
            at.span = self._span(stage, fun_name)
            return
        at.depth += 1
        if at.depth == 1:
            at.compiled = 0.0
            at.stage_span = self._span(stage, fun_name)

    def cache(self, event: str, **_kw) -> None:
        verdict = _CACHE_EVENTS.get(event)
        if verdict:
            self._open.cache = verdict

    def ended(self, event: str, seconds: float, fun_name=None,
              **_kw) -> None:
        stage = _STAGES.get(event)
        if stage is None:
            return
        at = self._open
        if stage != _COMPILE:
            if not at.depth:    # it began before the listener was there
                return
            at.depth -= 1
            if at.depth:        # inside another: the outermost holds it
                return
        seconds = max(float(seconds), 0.0)
        if stage == _COMPILE:
            cache, span = at.cache, at.span
            at.cache, at.span = UNCACHED, None
            if span is not None:
                span.tag(cache=cache).__exit__(None, None, None)
            if at.depth:
                at.compiled += seconds
            program, where = self._program(fun_name), current_phase()
            # looked up by name each time: a registry reset between two
            # compilations (tests) must not leave the counts in dropped
            # objects
            _compilations().inc(program=program, phase=where, cache=cache)
            _seconds(stage).inc(seconds, program=program, phase=where)
            return
        span, at.stage_span = at.stage_span, None
        if span is not None:
            span.__exit__(None, None, None)
        _seconds(stage).inc(max(seconds - at.compiled, 0.0),
                            program=self._program(fun_name),
                            phase=current_phase())


def compilations_of(program: str) -> dict[str, int]:
    """The process's compilations of ``program`` so far, by ``cache``."""
    found: dict[str, int] = {}
    for labels, n in _series(COMPILATIONS):
        if labels["program"] == program:
            found[labels["cache"]] = found.get(labels["cache"], 0) + int(n)
    return found


def count_kept_load(program: str, seconds: float) -> None:
    """An executable of ``program`` came from train/kept.py's store in
    ``seconds``: counted where the compilation cache's load would have
    been, in the phase open on this thread, with ``cache="kept"``."""
    where = current_phase()
    _compilations().inc(program=program, phase=where, cache=KEPT)
    _seconds(_COMPILE).inc(seconds, program=program, phase=where)


_listener: _Listener | None = None
_install_lock = threading.Lock()


def install(programs=()) -> None:
    """Listen to this process's compilations (once, however often it is
    called) and count those of the jitted functions named ``programs``
    under their own names from now on."""
    global _listener
    with _install_lock:
        if _listener is None:
            import jax.monitoring as monitoring

            _listener = _Listener()
            monitoring.register_scalar_listener(_listener.began)
            monitoring.register_event_listener(_listener.cache)
            monitoring.register_event_duration_secs_listener(_listener.ended)
        _listener.programs.update(programs)


# -- the set-up gauges as a table and as a line ------------------------------


def _series(name: str) -> list[tuple[dict, float]]:
    metric = REGISTRY.get(name)
    if metric is None:
        return []
    return [(dict(zip(metric.labelnames, key)), value)
            for key, value in metric.series().items()]


def _by(name: str, *labels: str) -> dict:
    """The metric's series as nested dicts keyed by ``labels`` in turn."""
    out: dict = {}
    for found, value in _series(name):
        at = out
        for label in labels[:-1]:
            at = at.setdefault(found[label], {})
        at[found[labels[-1]]] = value
    return out


def setup_table() -> dict:
    """The set-up gauges as they stand: seconds of ``init_state`` by phase,
    of the last ``stage_dataset`` and, for a sparse corpus, the form its
    rule chose (``sparse_feed``: ``form``, and the columns ``live``,
    ``padded``, ``bound``, ``contracted``, ``total``), which staging of the
    process that was (``nth``), of each first dispatch; the supersteps a
    life dispatched for the first time, in order, each with its staged
    program and the seconds of that first dispatch (``programs``: more
    than one where a restage changed the table's width or the form); what the last
    epoch's off-table pass found and did (``off_table``: the ``stale``
    rows, the ``bound`` up to which they are visited row by row, the
    ``trips`` of a dispatch); under gradient accumulation the
    ``microbatches`` of an optimizer update and the ``carry_bytes`` of its
    gradient accumulator (``accumulation``; left out with one microbatch an
    update); jax's pipeline by program and phase (``compilations``: count,
    seconds and misses of the compilations, how many of them were
    executables train/kept.py loaded (``kept``), ``trace_seconds`` and
    ``lower_seconds`` of the two stages before them, a row also where a
    program traced and nothing compiled; the costliest first); device
    memory at the
    three moments; the superstep executable's bytes, where its kernels'
    operands live, how many arrays a step reverses in time round them
    (``time_reversals``) and how many passes it makes over a kernel's
    operand or result only to cut or to sum it (``kernel_edge_passes``) and
    how many layer-0 weight-gradient dots it runs only to hand the gradient
    over (``bare_weight_grad_dots``).
    What was never set is left out."""
    compilations: dict = {}

    def row(found: dict) -> dict:
        return compilations.setdefault(
            (found["program"], found["phase"]),
            {"program": found["program"], "phase": found["phase"],
             "count": 0, "misses": 0, "kept": 0, "seconds": 0.0,
             "trace_seconds": 0.0, "lower_seconds": 0.0})

    for s, n in _series(COMPILATIONS):
        row(s)["count"] += int(n)
        if s["cache"] == KEPT:
            row(s)["kept"] += int(n)
        elif s["cache"] != "hit":
            row(s)["misses"] += int(n)
    for key, name in (("seconds", COMPILE_SECONDS),
                      ("trace_seconds", TRACE_SECONDS),
                      ("lower_seconds", LOWER_SECONDS)):
        for s, v in _series(name):
            row(s)[key] = v
    stage = _series(STAGE_SECONDS)
    stagings = _series(STAGINGS)
    reversals = _series(TIME_REVERSALS)
    edge_passes = _series(KERNEL_EDGE_PASSES)
    bare_dots = _series(BARE_WEIGHT_GRAD_DOTS)
    rows = _by(OPTIMIZER_ROWS, "kind")
    accumulation = {k: int(v) for k, v in _by(ACCUMULATION, "kind").items()}
    columns = {k: int(v) for k, v in _by(PROJECTION_COLUMNS, "kind").items()}
    feed = None
    if columns.get("total"):            # a sparse corpus was staged
        compact = columns["contracted"] < columns["total"]
        feed = {"form": "compact" if compact else "dense", **columns}
    programs = sorted(
        ({"nth": int(s["nth"]), "form": s["form"], "width": int(s["width"]),
          "seconds": v}
         for s, v in _series(SUPERSTEP_FIRST_DISPATCH_SECONDS)),
        key=lambda found: found["nth"])
    table = {
        "init_state_seconds": _by(INIT_STATE_SECONDS, "phase"),
        "stage_seconds": stage[0][1] if stage else None,
        "sparse_feed": feed,
        "nth": int(stagings[0][1]) if stagings else None,
        "off_table": {k: int(rows[k]) for k in ("stale", "bound", "trips")
                      if k in rows},
        "accumulation": (accumulation
                         if accumulation.get("microbatches", 1) > 1 else None),
        "first_dispatch_seconds": _by(FIRST_DISPATCH_SECONDS, "program"),
        "programs": programs,
        "compilations": sorted(
            compilations.values(),
            key=lambda r: -(r["seconds"] + r["trace_seconds"]
                            + r["lower_seconds"])),
        "device_bytes": _by(DEVICE_BYTES, "at", "kind"),
        "program_bytes": _by(PROGRAM_BYTES, "kind"),
        "kernel_operand_bytes": _by(KERNEL_OPERAND_BYTES, "kernel", "space"),
        "time_reversals": int(reversals[0][1]) if reversals else None,
        "kernel_edge_passes": (int(edge_passes[0][1]) if edge_passes
                               else None),
        "bare_weight_grad_dots": (int(bare_dots[0][1]) if bare_dots
                                  else None),
    }
    return {k: v for k, v in table.items() if v not in (None, {}, [])}


def format_setup(table: dict) -> str:
    """:func:`setup_table` as one line."""
    def seconds(found: dict) -> str:
        return ", ".join(f"{k} {v:.3f}" for k, v in found.items())

    def size(n: float) -> str:
        return f"{n / 1e9:.3f} GB" if n >= 1e8 else f"{n / 1e6:.1f} MB"

    parts = []
    if "init_state_seconds" in table:
        found = table["init_state_seconds"]
        parts.append(f"init_state {sum(found.values()):.3f} s "
                     f"({seconds(found)})")
    if "stage_seconds" in table:
        parts.append(f"stage {table['stage_seconds']:.3f} s"
                     + (f" (nth {table['nth']})" if "nth" in table else ""))
    if "sparse_feed" in table:
        feed = table["sparse_feed"]
        parts.append(
            f"sparse feed {feed['form']} ({feed['live']} live call paths "
            f"of {feed['total']}, padded to {feed['padded']}, bound "
            f"{feed['bound'] or 'the model axis'}, {feed['contracted']} "
            "contracted)")
    if "off_table" in table:
        parts.append("off the table " + ", ".join(
            f"{k} {v}" for k, v in table["off_table"].items()))
    if "accumulation" in table:
        found = table["accumulation"]
        parts.append(f"accumulation {found['microbatches']} microbatches an "
                     f"update, carry {found.get('carry_bytes', 0) / 1e6:.1f} MB")
    if "first_dispatch_seconds" in table:
        parts.append("first dispatch "
                     + seconds(table["first_dispatch_seconds"]) + " s")
    found = table.get("programs", ())
    if len(found) > 1:      # one says nothing the line does not say already
        parts.append(
            f"programs {len(found)} ("
            + ", ".join(f"{p['form']} {p['width']}" if p["form"] == "compact"
                        else p["form"] for p in found)
            + "), first dispatched in "
            + ", ".join(f"{p['seconds']:.3f}" for p in found) + " s")
    rows = table.get("compilations", ())
    if rows:
        def total(key: str):
            return sum(r[key] for r in rows)

        parts.append(
            f"{total('count')} compilations in {total('seconds'):.3f} s, "
            f"{total('misses')} not from the cache"
            + (f", {total('kept')} kept" if total("kept") else "")
            + ", traced "
            f"{total('trace_seconds'):.3f} s, lowered "
            f"{total('lower_seconds'):.3f} s ("
            + ", ".join(f"{r['program']} in {r['phase']} {r['count']} in "
                        f"{r['seconds']:.3f} s"
                        + (f", {r['misses']} missed" if r["misses"] else "")
                        + (f", {r['kept']} kept" if r["kept"] else "")
                        + f", traced {r['trace_seconds']:.3f} s, lowered "
                        f"{r['lower_seconds']:.3f} s"
                        for r in rows) + ")")
    for at, found in table.get("device_bytes", {}).items():
        parts.append(f"device memory at {at} {size(found.get('in_use', 0))} "
                     f"in use, peak {size(found.get('peak', 0))}")
    if "program_bytes" in table:
        parts.append("superstep " + ", ".join(
            f"{k} {size(v)}" for k, v in table["program_bytes"].items()))
    for kernel, found in table.get("kernel_operand_bytes", {}).items():
        parts.append(f"{kernel} operands " + ", ".join(
            f"{space} {size(v)}" for space, v in found.items()))
    if "time_reversals" in table:
        parts.append(f"{table['time_reversals']} reversals in time a step")
    if "kernel_edge_passes" in table:
        parts.append(f"{table['kernel_edge_passes']} passes at the kernels' "
                     "edge a step")
    if "bare_weight_grad_dots" in table:
        parts.append(f"{table['bare_weight_grad_dots']} bare weight-gradient "
                     "dots a step")
    return "set-up: " + "; ".join(parts)


__all__ = ["OTHER", "UNCACHED", "phase", "current_phase", "install",
           "setup_table", "format_setup", "COMPILATIONS", "COMPILE_SECONDS",
           "TRACE_SECONDS", "LOWER_SECONDS",
           "INIT_STATE_SECONDS", "STAGE_SECONDS", "STAGINGS",
           "OPTIMIZER_ROWS", "ACCUMULATION", "GATHER_PIECES",
           "PROJECTION_COLUMNS",
           "FIRST_DISPATCH_SECONDS", "SUPERSTEP_PROGRAMS",
           "SUPERSTEP_FIRST_DISPATCH_SECONDS", "DEVICE_BYTES", "PROGRAM_BYTES",
           "KERNEL_OPERAND_BYTES", "TIME_REVERSALS", "KERNEL_EDGE_PASSES",
           "BARE_WEIGHT_GRAD_DOTS",
           "KEPT_EXECUTABLES", "KEPT", "compilations_of", "count_kept_load"]
