"""``jax.profiler`` windows that are read, not only opened.

A trace alone names the device's time ``fusion.17``; the numbers after
``fusion.`` change whenever the graph does.  This module joins three
things the program owns into one table of layers:

- **Scopes.**  The program names its own work: ``jax.named_scope`` s
  inside a jitted function, ``name=`` on a ``pallas_call``
  (ops/scopes.py holds the train step's).  This libtpu writes no scope
  into the trace, but the compiled executable's text carries
  ``op_name="jit(..)/transpose(jvp(..))/in_proj/dot_general"`` on every
  instruction: :func:`scope_table` turns that text and the names the
  caller gives it into ``{instruction: (scope, pass)}``.  No layer's name
  is written here.
- **The device's operations**, with their self time, from the trace
  (``jax.profiler.ProfileData``, nothing else): :func:`layer_table` sums
  them by ``(scope, pass)``.  A fusion is counted under its own label,
  which XLA takes from one of the operations it fused; what else it holds
  (:func:`fused_scopes`) is listed beside the row, because XLA does fuse
  Adam's update of a weight into the matmul that makes its gradient.
- **Collectives.**  The partitioner puts the collectives of a mesh in and
  gives them no scope of their own (they carry the ``op_name`` of whatever
  they reduce or gather), so they are found by instruction kind: one row
  :data:`COLLECTIVE`.  XLA:TPU wraps some of them in a ``fusion`` of
  ``kind=kCustom`` named for no collective (a reduce-scatter is a
  ``fusion.N`` that calls ``%all-reduce-scatter``; an asynchronous one is
  a chain of fusions, ``async-collective-start``, ``async-collective-done``
  and between them the fusions of the work it hides behind, each holding
  a piece with one ``chain_id``): such a wrapper is found by what its
  computation holds.  Like every row it holds self time, and the device
  runs one operation at a time, so it is the part of the collectives'
  time in which the chip ran nothing else (what an asynchronous pair, or
  chain, hides between its start and its done lies in the rows of what
  ran then).  :func:`collective_bytes` reads what they move from the
  program's text.
- **The program's spans.**  An enabled span (obs/spans.py) enters a
  ``TraceAnnotation`` ``<component>/<name>``, so it lies on the trace's
  clock; each idle gap of the device goes to the innermost program span
  that covers its middle.

Entries: :func:`capture` (``POST /v1/profile``, ``deeprest profile``) opens
a window on a running plane and returns the table without a scope map
(kernels by their names in the trace, idle gaps by the serving spans);
``Trainer.profile_epoch`` (``train --profile-dir``) traces one steady epoch
with the scope map of the program it dispatches.

The arithmetic of busy, window and self time is the same as
``chipbench/trace_reduce.py``'s, written again: the program may not import
the yardstick.  tests/test_obs_layers.py holds the two to one answer on a
recorded trace.
"""

from __future__ import annotations

import bisect
import collections
import contextlib
import glob
import os
import re
import threading
import time

OTHER = "other"
COLLECTIVE = "collective"
# the instruction kinds that move data between chips; an asynchronous one
# is a `-start` and a `-done` instruction of the kind
COLLECTIVE_KINDS = ("all-reduce", "reduce-scatter", "all-gather",
                    "collective-permute", "all-to-all")
# every component the program records spans under starts with this
PROGRAM_SPAN_PREFIXES = ("deeprest",)
UNATTRIBUTED = "unattributed"

_KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"

_capture_lock = threading.Lock()


class ProfilerBusy(RuntimeError):
    """A capture window is already open (one at a time, by design)."""


@contextlib.contextmanager
def trace_window(out_dir: str):
    """A ``jax.profiler`` window into ``out_dir`` with the Python tracer
    off (the program's own spans name the host's time; Python frames
    would only slow it)."""
    import jax

    os.makedirs(out_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(out_dir, profiler_options=options):
        yield


def capture(out_dir: str, seconds: float,
            max_seconds: float = 120.0) -> dict:
    """Open a trace window for ``seconds``, block until it closes, read
    it.  Returns ``{"trace_dir", "seconds", "layers"}``, ``layers`` being
    :func:`layer_table` without a scope map.  A trace that cannot be read
    (none written, a file ``ProfileData`` refuses) still answers with its
    directory, ``layers`` null and the reason as ``layers_error``: the
    window was opened, and the operator can open the trace elsewhere.

    Bounded (``max_seconds``) because the handler thread blocks for the
    window; concurrent captures fail fast with :class:`ProfilerBusy`
    instead of interleaving two traces into one unreadable dump.
    """
    seconds = float(seconds)
    if not (0 < seconds <= max_seconds):
        raise ValueError(
            f"capture seconds {seconds} must be in (0, {max_seconds}]")
    if not _capture_lock.acquire(blocking=False):
        raise ProfilerBusy("a profiler capture window is already open")
    try:
        with trace_window(out_dir):
            time.sleep(seconds)
        out = {"trace_dir": os.path.abspath(out_dir), "seconds": seconds}
        try:
            out["layers"] = layer_table(out_dir)
        except (OSError, ValueError, RuntimeError) as exc:
            out["layers"] = None
            out["layers_error"] = f"{type(exc).__name__}: {exc}"
    finally:
        _capture_lock.release()
    return out


# -- the compiled program's text → scopes ----------------------------------

_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?(?P<name>[\w.\-]+) = .*? (?P<opcode>[a-z][a-z0-9\-]*)\(")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?(?P<name>[\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")
# never a device event of their own
_NO_EVENT = frozenset({"parameter", "constant", "get-tuple-element", "tuple",
                       "bitcast"})
_COLLECTIVE = re.compile(
    rf"^({'|'.join(COLLECTIVE_KINDS)})(-start|-done)?(?:\.\d+)?$")
# XLA:TPU's wrappers round a collective, by the name of the computation a
# `kind=kCustom` fusion calls or of the fusion itself
_WRAPPED = re.compile(
    r"^(all-reduce-scatter|async-collective)(-start|-done)?(?:\.\d+)?$")
ASYNC_COLLECTIVE = "async-collective"
_KCUSTOM = "kind=kCustom"
_CHAIN = re.compile(r'\bchannel_id=(\d+).*\bchain_id="(\d+)"')
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")
_ARRAY = re.compile(
    r"\b(?:pred|[a-z]+?(\d+)\w*)\[([\d,]*)\](?:\{([^}]*)\})?")
_F32_ARRAY = re.compile(r"\bf32\[([\d,]*)\]")
_SPACE = re.compile(r"S\((\d+)\)")
_OPERAND = re.compile(r"%?([\w.\-]+)\s*(?:,|$)")
# the memory spaces a TPU layout names; an array with no `S(n)` is in HBM
HBM = "hbm"
_SPACES = {"1": "vmem"}


def collective_kind(name: str) -> tuple[str, str] | None:
    """``all-reduce-start.3`` (an opcode, an instruction's name as the
    trace gives it, or the name of the computation a ``kCustom`` fusion
    calls) -> ``("all-reduce", "-start")``; XLA:TPU's
    ``all-reduce-scatter.1`` -> ``("reduce-scatter", "")`` and its
    ``async-collective-done`` -> ``(ASYNC_COLLECTIVE, "-done")``, whose
    kind proper only the text says; None for what is no collective."""
    name = name.lstrip("%")
    m = _COLLECTIVE.match(name)
    if m:
        return m[1], m[2] or ""
    m = _WRAPPED.match(name)
    if not m:
        return None
    return ("reduce-scatter" if m[1] == "all-reduce-scatter"
            else ASYNC_COLLECTIVE), m[2] or ""


def _scope_of(op_name: str, names) -> tuple[str, str]:
    """``jit(step)/transpose(jvp(Model))/in_proj/dot_general`` →
    ``("in_proj", "bwd")``: the innermost of ``names`` among the path's
    components (transform wrappers opened up, the primitive at the end
    left out), ``bwd`` under a ``transpose(``; ``(OTHER, "-")`` when the
    path holds none of them."""
    parts = [p for p in re.split(r"[/()]", op_name) if p][:-1]
    known = [p for p in parts if p in names]
    if not known:
        return OTHER, "-"
    return known[-1], "bwd" if "transpose(" in op_name else "fwd"


def instruction_lines(hlo_text):
    """({computation: [(the match of its instruction, the line)]}, the
    ENTRY computation's name) of an HLO module's text.  Every reader of a
    module's text below takes this in the text's place too, so a caller
    with several questions (``Trainer._publish_program``) parses once."""
    if not isinstance(hlo_text, str):
        return hlo_text
    computations, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            current = computations.setdefault(m["name"], []) if m else None
            if m and line.startswith("ENTRY"):
                entry = m["name"]
            continue
        m = _INSTRUCTION.match(line)
        if current is not None and m:
            current.append((m, line))
    return computations, entry


def _parse_hlo(hlo_text: str):
    """{computation: [(instruction, opcode, op_name, called computation)]},
    the set of computations that run inside another instruction (a
    fusion's body, a reduce's combiner), and the ``kind=kCustom`` fusion
    instructions whose computation holds a collective (XLA:TPU's wrappers:
    a reduce-scatter, the start and the done of an asynchronous chain)."""
    computations, inner, custom = {}, set(), {}
    for comp, lines in instruction_lines(hlo_text)[0].items():
        current = computations[comp] = []
        for m, line in lines:
            op_name = _OP_NAME.search(line)
            calls = _CALLS.search(line) if m["opcode"] == "fusion" else None
            inner.update(_TO_APPLY.findall(line))
            if calls:
                inner.add(calls[1])
                if _KCUSTOM in line:
                    custom[m["name"]] = calls[1]
            current.append((m["name"], m["opcode"],
                            op_name[1] if op_name else "",
                            calls[1] if calls else None))
    wrappers = {name for name, comp in custom.items()
                if any(collective_kind(opcode)
                       for _, opcode, _, _ in computations.get(comp, ()))}
    return computations, inner, wrappers


def module_name(hlo_text: str) -> str | None:
    """The name the trace's ``XLA Modules`` line gives this program's
    executions (less the run's fingerprint in brackets)."""
    m = re.match(r"HloModule ([\w.\-]+)", hlo_text)
    return m[1] if m else None


def scope_table(hlo_text: str, names) -> dict[str, tuple[str, str]]:
    """``{instruction: (scope, pass)}`` for every instruction of an
    optimized HLO module's text (``jit(f).lower(..).compile().as_text()``)
    that can be an event on the device: a fusion under its own label, a
    collective (by its kind, whatever it reduces, and XLA:TPU's wrapper
    fusions of one by what they hold) under ``(COLLECTIVE, "-")``,
    instructions under none of the scope and kernel ``names`` under
    ``(OTHER, "-")``.  A fusion that holds a piece of an asynchronous
    chain beside the work that hides it keeps that work's label."""
    computations, inner, wrappers = _parse_hlo(hlo_text)
    names = frozenset(names)
    return {name: ((COLLECTIVE, "-")
                   if collective_kind(opcode) or name in wrappers
                   else _scope_of(op_name, names))
            for comp, instructions in computations.items() if comp not in inner
            for name, opcode, op_name, _ in instructions
            if opcode not in _NO_EVENT}


def fused_scopes(hlo_text: str, names) -> dict[str, tuple[str, ...]]:
    """``{fusion instruction: the other ones of ``names`` inside it}``, for
    the fusions that hold operations of more scopes than their label
    says."""
    computations, inner, _ = _parse_hlo(hlo_text)
    names = frozenset(names)

    def inside(comp: str) -> set[str]:
        found = set()
        for _, opcode, op_name, calls in computations.get(comp, ()):
            if opcode in _NO_EVENT:     # a shared constant carries the
                continue                # label of whoever made it first
            found.add(_scope_of(op_name, names)[0])
            if calls:
                found |= inside(calls)
        return found

    out = {}
    for comp, instructions in computations.items():
        if comp in inner:
            continue
        for name, _, op_name, calls in instructions:
            if calls:
                extra = inside(calls) - {_scope_of(op_name, names)[0], OTHER}
                if extra:
                    out[name] = tuple(sorted(extra))
    return out


def _arrays(type_text: str):
    """``(bytes, memory space)`` of every array a result type names:
    ``(f32[40,3]{0,1}, bf16[40,512,3]{2,1,0:T(8,128)(2,1)S(1)})`` ->
    ``(480, "hbm"), (122880, "vmem")``.  The space is what the layout
    says: ``S(1)`` is VMEM, no ``S(n)`` HBM, another ``S(n)`` itself."""
    for bits, dims, layout in _ARRAY.findall(type_text):
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        space = _SPACE.search(layout)
        yield (n * max(int(bits or 8) // 8, 1),
               HBM if not space else _SPACES.get(space[1], space[0]))


def _array_bytes(type_text: str) -> int:
    """Bytes of every array a result type names."""
    return sum(n for n, _ in _arrays(type_text))


def _result_type(m, line: str) -> str:
    """The result type of an instruction: between `` = `` and its opcode."""
    head = line.split(" = ", 1)[1]
    return head[:head.index(f" {m['opcode']}(")]


def collective_bytes(hlo_text: str, steps: int = 1) -> dict[str, int]:
    """``{kind: bytes}`` that one step of an optimized HLO module gets
    from its collectives: the result of every instruction of a
    :data:`COLLECTIVE_KINDS` kind (of an asynchronous pair the ``-done``,
    whose result is the collective's; its ``-start`` not) that the entry
    computation reaches.  A collective inside a ``fusion`` is its
    fusion's: XLA:TPU's ``%all-reduce-scatter`` (an all-reduce and a
    chip's slice of it) counts as the reduce-scatter it is, by the slice,
    and the pieces of an asynchronous chain (one ``chain_id`` on one
    channel, in ``async-collective-start``, the fusions between and
    ``async-collective-done``) count once.  The body of a loop is taken
    once (for a superstep: one train step) and of a conditional's branches
    the one that moves most; what stands outside every loop runs once a
    dispatch, and is divided by ``steps``, the loop's trips.  Kinds the
    program does not use are left out; a program for one device gives
    ``{}``.  On the links an all-reduce of a result of S bytes over n
    chips moves 2 S (n - 1) / n a chip, an all-gather of a result of S
    moves S (n - 1) / n, a reduce-scatter of a result of S moves
    S (n - 1)."""
    bodies, entry = instruction_lines(hlo_text)
    chains = set()

    def held(comp: str) -> collections.Counter:
        """What the computation of a fusion holds."""
        total = collections.Counter()
        for m, line in bodies.get(comp, ()):
            kind = collective_kind(m["opcode"])
            chain = _CHAIN.search(line)
            if (not kind or kind[1] == "-start"
                    or chain and chain.groups() in chains):
                continue
            if chain:
                chains.add(chain.groups())
            total[kind[0]] += _array_bytes(_result_type(m, line))
        return total

    def cost(comp: str):
        """(once a dispatch, once a trip of a loop) of a computation."""
        once, looped = collections.Counter(), collections.Counter()
        for m, line in bodies.get(comp, ()):
            opcode = m["opcode"]
            kind = collective_kind(opcode)
            if kind and kind[1] != "-start":
                once[kind[0]] += _array_bytes(_result_type(m, line))
            called = [c.lstrip("%")
                      for one, many in _CALLED.findall(line)
                      for c in ([one] if one else re.split(r",\s*", many))]
            if opcode == "fusion":
                for c in called:
                    if collective_kind(c) == ("reduce-scatter", ""):
                        once["reduce-scatter"] += _array_bytes(
                            _result_type(m, line))
                    else:
                        once.update(held(c))
                continue
            costs = [cost(c) for c in called]
            if opcode == "conditional" and costs:
                costs = [max(costs, key=lambda c: sum((c[0] + c[1]).values()))]
            for a, b in costs:
                (looped if opcode == "while" else once).update(a)
                looped.update(b)
        return once, looped

    if not entry:
        return {}
    once, looped = cost(entry)
    return {kind: looped[kind] + round(once[kind] / steps)
            for kind in {*once, *looped} if once[kind] or looped[kind]}


def kernel_operand_spaces(hlo_text: str, names) -> dict[str, dict[str, int]]:
    """``{kernel: {memory space: bytes}}`` of the arrays that every
    ``tpu_custom_call`` of an optimized HLO module hands its kernel, the
    call's operands and its results alike (the kernel's body takes both as
    its operands), for the calls whose name (the instruction's, less XLA's
    ``.N``: what its ``pallas_call`` was given) is one of ``names``, summed
    over a kernel's calls.  The text names an operand and not its type, so
    each is looked up where its own computation defines it; the space is
    the one its layout there carries (:func:`_arrays`), which the
    compiler's memory-space assignment chose: the same kernel on the same
    shapes finds an array in VMEM in one program and in HBM in the next.
    A kernel the text does not hold is left out."""
    names = frozenset(names)
    out: dict[str, collections.Counter] = {}
    for lines in instruction_lines(hlo_text)[0].values():
        types = None
        for m, line in lines:
            base, _, number = m["name"].rpartition(".")
            kernel = base if number.isdigit() else m["name"]
            if _KERNEL_MARK not in line or kernel not in names:
                continue
            if types is None:
                types = {d["name"]: _result_type(d, text)
                         for d, text in lines}
            found = out.setdefault(kernel, collections.Counter())
            for handed in [m["name"], *_operands(m, line)]:
                for n, space in _arrays(types[handed]):
                    found[space] += n
    return {kernel: dict(found) for kernel, found in out.items()}


def _array_op_under(m, line: str, opcode: str, scope: str) -> bool:
    """An ``opcode`` instruction whose ``op_name`` has ``scope`` among its
    path's components and whose result has more than one element."""
    op_name = _OP_NAME.search(line)
    if (m["opcode"] != opcode or not op_name
            or scope not in re.split(r"[/()]", op_name[1])):
        return False
    dims = _ARRAY.search(_result_type(m, line))[2]
    return any(int(d) > 1 for d in dims.split(",") if d)


def threefry_draws(hlo_text: str, scope: str) -> list[str]:
    """The places of an optimized HLO module's text that generate random
    bits under ``scope``, by name: every fusion instruction whose fused
    computation holds a round of the threefry block cipher
    (``jax.random``'s default generator, lowered to plain adds, rotations
    and xors) on an array (identical fusions share one computation, so the
    calls are what is counted), and a computation that holds such a round
    unfused.  A round is found by an ``xor`` whose ``op_name`` has
    ``scope`` among its path's components and whose result has more than
    one element: nothing else a ``bernoulli`` lowers to xors, and deriving
    a key (``fold_in``, ``split``) runs the rounds on scalars, which are
    not counted.  XLA fuses a draw into each consumer it can rather than
    keep its bits, so a mask that the program draws once and uses twice is
    two such places unless the program holds the compiler to one
    (models/qrnn.py); a loop's body is counted once however often it runs,
    and XLA:CPU rolls the rounds into a loop of their own, so there the
    count says little."""
    computations = instruction_lines(hlo_text)[0]
    holders = {comp for comp, lines in computations.items()
               if any(_array_op_under(m, line, "xor", scope)
                      for m, line in lines)}
    fusions = [(m["name"], _CALLS.search(line))
               for lines in computations.values() for m, line in lines
               if m["opcode"] == "fusion"]
    fused = {calls[1] for _, calls in fusions if calls}
    return ([name for name, calls in fusions if calls and calls[1] in holders]
            + sorted(holders - fused))


def time_reversals(hlo_text: str, scope: str) -> list[str]:
    """The ``reverse`` instructions of an optimized HLO module's text under
    ``scope``, by name: fused into a neighbour or an operation of their
    own, each is an array read back to front to compute nothing (what a
    ``jnp.flip`` round a kernel that only scans forward lowers to, and what
    autodiff mirrors in the backward pass).  A loop's body is counted once
    however often it runs, so for a superstep the count is a train step's;
    a reversal of a single element is none.  The recurrence kernels walk
    the reverse direction's time blocks back to front themselves
    (ops/pallas_gru.py), so a compiled step that holds one under
    ``recurrence`` has grown a flip again."""
    return [m["name"]
            for lines in instruction_lines(hlo_text)[0].values()
            for m, line in lines
            if _array_op_under(m, line, "reverse", scope)]


def kernel_edge_passes(hlo_text: str, scope: str, kernel: str) -> list[str]:
    """The instructions of an optimized HLO module's text that only read
    again, or write again, a whole operand or result of a pallas kernel
    call, by name; each is a pass over the array that computes nothing the
    kernel does not hold.  Two kinds are found, what autodiff put round the
    recurrence kernels while the VJP's boundary lay at the kernel call
    (ops/pallas_gru.py):

    - a ``reduce`` with a result of a ``kernel`` call (a
      ``tpu_custom_call`` of that name, less XLA's ``.N``) among its
      operands, alone or in a fusion that holds no dot: the input bias's
      gradient summed from ``dproj``, which the backward kernel now sums
      from the gate gradients in its registers;
    - an instruction under ``scope`` that a ``split`` lowered to (a
      fusion labelled with it, a ``slice`` left alone): the joined
      cotangent of a bidirectional layer cut into one array a direction,
      which the backward kernels now read in place.

    Instructions inside a fusion are its fusion's; a loop's body is counted
    once however often it runs, so for a superstep the count is a train
    step's."""
    computations = instruction_lines(hlo_text)[0]
    fused = {}                  # a fusion's computation -> the opcodes in it
    for lines in computations.values():
        for m, line in lines:
            calls = _CALLS.search(line) if m["opcode"] == "fusion" else None
            if calls:
                fused[calls[1]] = {i["opcode"] for i, _
                                   in computations.get(calls[1], ())}
    found = []
    for comp, lines in computations.items():
        if comp in fused:
            continue
        made = {m["name"]: (m, line) for m, line in lines}

        def of_kernel(name: str) -> bool:
            m, line = made.get(name, (None, ""))
            if m is not None and m["opcode"] == "get-tuple-element":
                return of_kernel(_operands(m, line)[0])
            base, _, number = name.rpartition(".")
            return (_KERNEL_MARK in line
                    and (base if number.isdigit() else name) == kernel)

        for m, line in lines:
            opcode, op_name = m["opcode"], _OP_NAME.search(line)
            inside = ({opcode} if opcode != "fusion"
                      else fused.get(_CALLS.search(line)[1], set()))
            parts = re.split(r"[/()]", op_name[1]) if op_name else []
            if ("reduce" in inside and not inside & {"dot", "convolution"}
                    and any(of_kernel(o) for o in _operands(m, line))):
                found.append(m["name"])
            elif (opcode in ("fusion", "slice") and scope in parts
                    and parts[-1] == "split"):
                found.append(m["name"])
    return found


def _dims(text: str) -> tuple[int, ...]:
    return tuple(int(d) for d in text.split(",") if d)


def bare_weight_grad_dots(hlo_text: str, scope: str, leaves) -> list[str]:
    """The fusions of an optimized HLO module's text that compute a weight
    gradient under ``scope`` only to hand it over, by name: a fusion whose
    computation holds a ``convolution`` (what XLA:TPU makes of a dot) with
    ``scope`` among the components of its ``op_name`` under a
    ``transpose(`` (the backward pass), whose result is as large as one of
    ``leaves`` (the shapes of the weights differentiated there: its
    dimensions are theirs in the dot's own order), and which returns no
    float32 array of that leaf's shape.  A fusion that holds such a dot and
    returns the leaf, ``mu`` and ``nu`` has consumed the gradient where it
    was made: the dot's MXU time hides under the optimizer's HBM traffic
    and no gradient is written.  One that returns the gradient itself (in
    the compute dtype) runs the MXU with HBM idle, and the gradient is
    written once and read once by whoever holds the optimizer
    (train/trainer.py's ``apply_gradients`` orders the update so that none
    does).  A loop's body is counted once however often it runs, so for a
    superstep the count is a train step's."""
    leaves = {tuple(dims) for dims in leaves}
    sizes = {tuple(sorted(d for d in dims if d > 1)) for dims in leaves}
    computations = instruction_lines(hlo_text)[0]

    def weight_gradient(m, line: str) -> bool:
        op_name = m["opcode"] == "convolution" and _OP_NAME.search(line)
        if not op_name or _scope_of(op_name[1], {scope}) != (scope, "bwd"):
            return False
        made = _dims(_ARRAY.search(_result_type(m, line))[2])
        return tuple(sorted(d for d in made if d > 1)) in sizes

    holders = {comp for comp, lines in computations.items()
               if any(weight_gradient(m, line) for m, line in lines)}
    if not holders:
        return []
    return [m["name"]
            for lines in computations.values() for m, line in lines
            if m["opcode"] == "fusion"
            and _CALLS.search(line)[1] in holders
            and not leaves & {_dims(dims) for dims in _F32_ARRAY.findall(
                _result_type(m, line))}]


def _operands(m, line: str) -> list[str]:
    """The names of an instruction's operands."""
    head = line[line.index(f" {m['opcode']}(") + len(m["opcode"]) + 2:]
    return _OPERAND.findall(head[:head.index(")")])


# -- the trace → the table -------------------------------------------------


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_planes(path: str):
    """The trace as plain data: ``[(plane, [(line, [(event, start ns,
    duration ns)])])]`` of the device and host planes."""
    from jax.profiler import ProfileData

    return [(plane.name,
             [(line.name, [(ev.name, ev.start_ns, ev.duration_ns)
                           for ev in line.events])
              for line in plane.lines])
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith(("/device:", "/host:"))]


def _self_times(events):
    """[(text, start, self ns)] of events [(text, start, duration)] on one
    line, where an event (a ``while``, a ``conditional``) may hold later,
    shorter events inside it."""
    out, stack = [], []                 # stack of [text, start, end, self]
    for text, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][2]:
            top = stack.pop()
            out.append((top[0], top[1], top[3]))
        if stack:
            stack[-1][3] -= min(dur, stack[-1][2] - start)
        stack.append([text, start, start + dur, dur])
    out += [(text, start, own) for text, start, _, own in stack]
    return out


def _merged(intervals):
    """Sorted (start, end) intervals with the overlapping ones joined."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _covering_spans(gaps, spans):
    """For each ``(lo, hi)`` gap, in time order, the name of the innermost
    span ``(name, lo, hi)`` that covers its middle, else
    :data:`UNATTRIBUTED`.  One sweep: a busy plane's window holds
    thousands of spans and many more gaps."""
    pending = sorted(spans, key=lambda s: -s[1])    # earliest start last
    active = []
    for lo, hi in gaps:
        mid = (lo + hi) / 2
        while pending and pending[-1][1] <= mid:
            active.append(pending.pop())
        active = [s for s in active if s[2] >= mid]
        best = min(active, key=lambda s: s[2] - s[1], default=None)
        yield best[0] if best else UNATTRIBUTED


def _row_key(name: str, text: str, scopes) -> tuple[str, str]:
    """The row of the operation ``name`` (its event's ``text``): through
    the map when it is in it; a collective the map does not hold by its
    kind; a kernel the map does not hold under the name its
    ``pallas_call`` gave it (the instruction's, less XLA's ``.N``); else
    ``(OTHER, "-")``."""
    if scopes and name in scopes:
        return tuple(scopes[name])
    if collective_kind(name):
        return COLLECTIVE, "-"
    if _KERNEL_MARK in text:
        base, _, number = name.rpartition(".")
        return (base if number.isdigit() else name), "-"
    return OTHER, "-"


def layer_table_of(planes, scopes=None, fused=None, module=None,
                   span_prefixes=PROGRAM_SPAN_PREFIXES, steps=None) -> dict:
    """:func:`layer_table` on what :func:`read_planes` gives.  Seconds are
    means over the chips of the trace; a trace with no device operation (a
    CPU run) gives ``chips: 0``, no seconds and no gaps, and still one row
    per ``(scope, pass)`` of ``scopes``."""
    device, spans = [], []
    for plane, lines in planes:
        by_line = dict(lines)
        if plane.startswith("/device:"):
            if by_line.get(_OPS_LINE):
                device.append((by_line[_OPS_LINE],
                               by_line.get(_MODULES_LINE, ())))
        else:
            spans += [(n, s, s + d) for events in by_line.values()
                      for n, s, d in events
                      if n.startswith(tuple(span_prefixes))]
    fused = fused or {}
    rows = collections.Counter(
        {tuple(key): 0 for key in (scopes or {}).values()})
    rows.setdefault((OTHER, "-"), 0)        # a row, never dropped
    held = collections.defaultdict(collections.Counter)
    gaps = collections.defaultdict(list)
    busy_ns = window_ns = kernel_ns = 0
    for events, modules in device:
        # instruction names repeat across programs: the map holds for the
        # operations that ran inside its own module's executions only
        runs = _merged((s, s + d) for n, s, d in modules
                       if n.split("(")[0] == module)
        starts = [lo for lo, _ in runs]
        busy = _merged((s, s + d) for _, s, d in events)
        busy_ns += sum(hi - lo for lo, hi in busy)
        window_ns += busy[-1][1] - busy[0][0]
        for text, start, own in _self_times(events):
            name = text.split(" = ", 1)[0].lstrip("%")
            i = bisect.bisect_right(starts, start) - 1
            mapped = module is None or (i >= 0 and start < runs[i][1])
            key = _row_key(name, text, scopes if mapped else None)
            rows[key] += own
            if _KERNEL_MARK in text:
                kernel_ns += own
            for other in fused.get(name, ()) if mapped else ():
                held[key][other] += own
        idle = [(lo, hi) for (_, lo), (hi, _) in zip(busy, busy[1:])]
        for (lo, hi), name in zip(idle, _covering_spans(idle, spans)):
            gaps[name].append(hi - lo)
    n = len(device)
    per = 1e9 * max(n, 1)

    def row(key, ns):
        out = {"scope": key[0], "pass": key[1], "seconds": ns / per,
               "share_of_busy": ns / busy_ns if busy_ns else 0.0}
        if steps:
            out["ms_per_step"] = 1e3 * ns / per / steps
        if held.get(key):
            out["in_fusions_that_also_hold"] = {
                other: v / per for other, v in held[key].most_common()}
        return out

    return {
        "chips": n,
        "window_s": window_ns / per,
        "busy_s": busy_ns / per,
        "idle_pct": (100.0 * (1.0 - busy_ns / window_ns) if window_ns
                     else None),
        "kernel_s": kernel_ns / per,
        "steps": steps,
        "rows": [row(k, v) for k, v in sorted(
            rows.items(), key=lambda kv: (-kv[1], kv[0]))],
        "idle_gaps": [
            {"span": name, "seconds": sum(g) / per, "gaps": len(g),
             "longest_s": max(g) / 1e9}
            for name, g in sorted(gaps.items(), key=lambda kv: -sum(kv[1]))],
    }


def layer_table(trace_dir: str, scopes=None, fused=None, module=None,
                span_prefixes=PROGRAM_SPAN_PREFIXES, steps=None) -> dict:
    """The newest trace under ``trace_dir`` as a table of layers.

    - ``window_s`` from the first to the last device operation, ``busy_s``
      the union of the operations' intervals, ``idle_pct`` the rest;
    - ``rows``: device self time (an operation's duration less what the
      operations nested in it cover) summed by ``(scope, pass)`` through
      ``scopes`` (:func:`scope_table`; it holds for the operations inside
      the executions of ``module``, :func:`module_name`, when that is
      given), a kernel the map does not hold by its own name; per row
      seconds, share of busy, ms per step when ``steps`` is given, and the
      seconds spent in fusions that also hold another scope's operations
      (``fused``: :func:`fused_scopes`).  :data:`COLLECTIVE` (pass ``-``)
      holds the collectives, found by kind: the part of their time in
      which the chip ran nothing else.  :data:`OTHER` (pass ``-``) is
      ONE row, never dropped: the operations under none of the map's
      names and those the map does not hold, so the rows sum to
      ``busy_s``;
    - ``kernel_s``: the ``tpu_custom_call`` events' self time, the
      cross-check of the kernel rows;
    - ``idle_gaps``: the gaps between device operations, summed under the
      innermost program span (a ``TraceAnnotation`` whose name starts
      with one of ``span_prefixes``) that covers their middle, else
      :data:`UNATTRIBUTED`, with the count and the longest.
    """
    path = find_xplane(trace_dir)
    table = layer_table_of(read_planes(path), scopes, fused, module,
                           span_prefixes, steps)
    table["trace"] = path
    return table


def format_table(table: dict) -> str:
    """The table as the lines ``train --profile-dir`` prints."""
    head = (f"device: {table['chips']} chip(s), window "
            f"{table['window_s']:.4f} s, busy {table['busy_s']:.4f} s"
            + ("" if table["idle_pct"] is None
               else f", idle {table['idle_pct']:.2f}%"))
    lines = [head, f"{'scope':<16}{'pass':<5}{'seconds':>10}{'ms/step':>10}"
                   f"{'of busy':>9}  in fusions that also hold"]
    for r in table["rows"]:
        also = ", ".join(f"{k} {v:.4f} s" for k, v in
                         r.get("in_fusions_that_also_hold", {}).items())
        ms = f"{r['ms_per_step']:10.4f}" if "ms_per_step" in r else " " * 10
        lines.append(f"{r['scope']:<16}{r['pass']:<5}{r['seconds']:10.4f}{ms}"
                     f"{100 * r['share_of_busy']:8.2f}%  {also}")
    for g in table["idle_gaps"]:
        lines.append(f"idle under {g['span']}: {g['seconds']:.6f} s in "
                     f"{g['gaps']} gap(s), longest {g['longest_s']:.6f} s")
    for name, value in table.get("phases", {}).items():
        lines.append(f"host phase {name}: {value:.6f} s")
    return "\n".join(lines)


__all__ = ["OTHER", "UNATTRIBUTED", "COLLECTIVE", "COLLECTIVE_KINDS",
           "ASYNC_COLLECTIVE", "collective_kind", "collective_bytes",
           "kernel_operand_spaces", "threefry_draws", "time_reversals",
           "kernel_edge_passes", "bare_weight_grad_dots", "instruction_lines",
           "PROGRAM_SPAN_PREFIXES", "ProfilerBusy", "capture",
           "trace_window", "scope_table", "fused_scopes", "module_name",
           "layer_table", "layer_table_of", "read_planes", "find_xplane",
           "format_table"]
