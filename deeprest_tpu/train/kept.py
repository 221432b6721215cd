"""A jitted program whose executable a later process loads instead of
tracing it again.

jax's persistent compilation cache is keyed by the LOWERED module, so a
process has to trace the function to a jaxpr and lower that to a module
before the cache can tell it that the executable was on disk all along:
for ``train_superstep`` 9-10 s of Python on one chip, 29 s with eight
microbatches an update, in every process (PERF.md section 6, PR 51).
:class:`KeptJit` keeps what that work led to.  It is called as the
``jax.jit`` it wraps; on the first call for a signature it computes a KEY
from what decides the program from OUTSIDE (so before anything is traced),
looks for ``<jax_compilation_cache_dir>/deeprest-kept/<program>-<digest of
the configuration, the mesh and the arguments>.bin``, and if the file's
stored key equals the live one loads the executable onto the mesh's
devices (``jax.experimental.serialize_executable``) and dispatches it.
Otherwise it calls the jit as before and, once that call has returned,
writes the executable the jit compiled under the same name (a temporary
file, then ``os.replace``: several processes may share the directory).

The key, all of which has to match (a stale file costs a trace, never a
wrong answer): a digest of every ``*.py`` of this package; the versions of
jax, jaxlib, flax, optax and of the backend's runtime (libtpu); what the
caller says decides the program besides its arguments (``identity``: the
trainer passes its whole ``Config``); the mesh's axes and its devices'
kind, ids and coordinates in order; the pytree structure, shape, dtype,
weak type and sharding of every argument; ``XLA_FLAGS``,
``LIBTPU_INIT_ARGS`` and the jax options that change a lowering.  What no
key made of files can see is a function replaced at run time.  Where the
replacement's code is in a file outside the package the store stands aside
(below); what is left (a constant set by hand, code from a string) is why
every test has a store of its own (tests/conftest.py).

It engages by what it can observe and has no switch: a compilation cache
directory is configured, the mesh is one process's, no function of the
package has been replaced from outside it (:func:`replaced_at_run_time`),
the executable serialises.  Every first call of a signature is counted once in
``deeprest_train_kept_executables_total{program,result}``: ``loaded``,
``stored``, ``miss`` (no file), ``stale`` (a file of another key),
``unreadable`` (cut short, damaged, not loadable), ``unsupported`` (no
cache directory, a mesh across processes, a replaced function, an executable
that does not serialise or is not known to be whole, a directory that cannot
be written).
A load is also one ``deeprest_compilations_total{cache="kept"}`` with its
seconds in ``deeprest_compile_seconds_total``, where the cache's load was,
and a span ``deeprest-trainer/train.load_kept``.

Only an executable that is WHOLE may be written.  XLA:CPU serialises an
executable that jax's cache loaded as a thin reference to symbols of the
process that compiled it: a second process loads it, and fails at the first
readback, after the donation (``Function ... not found``;
tests/test_kept_executable.py shows it).  So on the CPU only a process
whose backend compiled the program writes; XLA:TPU's executables round-trip
whichever way they came (PERF.md section 7, read on the chip in PR 51), so
a TPU process writes what it has.  :func:`whole` is that rule.

Deleting the directory, or any file in it, is always safe.  The files are
pickles: like jax's own cache they are read back only from the directory
this program writes.
"""

from __future__ import annotations

import collections
import functools
import hashlib
import json
import os
import pickle
import sys
import sysconfig
import tempfile
import zlib

import jax

from deeprest_tpu.obs import metrics as obs_metrics
from deeprest_tpu.obs import setup as obs_setup
from deeprest_tpu.obs import spans as obs_spans

SUBDIR = "deeprest-kept"
_MAGIC = b"deeprest-kept 1\n"
_PACKAGE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCALARS = (bool, int, float, complex)
# where code that is the program's, or a library's the key names by
# version, lives
_OWN_CODE = tuple({_PACKAGE + os.sep} | {
    os.path.realpath(root) + os.sep for root in sysconfig.get_paths().values()})


def store_dir() -> str | None:
    """Where kept executables live: beside jax's compilation cache, so
    whoever places the one places the other.  None without a cache."""
    cache = jax.config.jax_compilation_cache_dir
    return os.path.join(cache, SUBDIR) if cache else None


@functools.cache
def source_digest() -> str:
    """Every ``*.py`` of the package by path and content: the program as
    far as its files say it (31.7k lines, milliseconds, once a process)."""
    h = hashlib.sha256()
    for folder, folders, files in os.walk(_PACKAGE):
        folders.sort()
        for name in sorted(files):
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                h.update(os.path.relpath(path, _PACKAGE).encode())
                with open(path, "rb") as fh:
                    h.update(fh.read())
    return h.hexdigest()


def versions(client) -> dict:
    """What compiles and what is compiled against."""
    import flax
    import jaxlib
    import optax

    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "flax": flax.__version__, "optax": optax.__version__,
            "backend": f"{client.platform} {client.platform_version}"}


def replaced_at_run_time() -> list[str]:
    """The functions in the namespaces of the package's loaded modules and
    of their classes whose code is in a file that is neither the package's
    nor an installed library's: what a caller put there after the import (a
    test's monkeypatch, a control that breaks the program on purpose:
    ``chipbench/tests/control_on_chip_warm.without_the_off_table_pass``).
    :func:`source_digest` reads files and cannot see them, so a process
    that has any neither loads an executable nor leaves one.  This module's
    own names are not looked at (a test places the store by replacing
    :func:`store_dir`), nor is code without a file (``<string>``: what
    ``dataclasses`` generates, a ``python -c`` script)."""
    package = __name__.split(".")[0]
    found = []
    for name, module in sorted(sys.modules.items()):
        if (module is None or name == __name__
                or not (name == package or name.startswith(package + "."))):
            continue
        spaces = [(name, dict(vars(module)))]
        spaces += [(f"{name}.{k}", dict(vars(v)))
                   for k, v in spaces[0][1].items()
                   if isinstance(v, type) and v.__module__ == name]
        for owner, space in spaces:
            for attr, obj in space.items():
                code = getattr(getattr(obj, "__func__", obj), "__code__", None)
                if code is not None and os.path.isabs(code.co_filename) \
                        and not code.co_filename.startswith(_OWN_CODE):
                    found.append(f"{owner}.{attr}")
    return found


def lowering_options() -> dict:
    """The environment and the jax options that change a lowering."""
    options = {name: os.environ.get(name, "")
               for name in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")}
    for name in ("jax_enable_x64", "jax_default_matmul_precision",
                 "jax_default_prng_impl", "jax_threefry_partitionable"):
        options[name] = str(getattr(jax.config, name))
    return options


def whole(platform: str, fresh: bool) -> bool:
    """Whether the executable this process holds serialises whole:
    anything the backend compiled here (``fresh``), and on the TPU also
    what jax's cache loaded."""
    return fresh or platform == "tpu"


def _leaf(a):
    kind = type(a)
    if kind in _SCALARS:
        return kind                     # a Python scalar is weakly typed
    return a.shape, a.dtype, getattr(a, "sharding", None)


def _signature(args):
    """What tells two calls' executables apart, hashable.  It is made on
    every dispatch, so from what an array answers at once.  A leaf's weak
    type and commitment are in the KEY beside these (:func:`_described`);
    to an executable, which is typed, they make no difference."""
    leaves, tree = jax.tree_util.tree_flatten(args)
    return tree, tuple(map(_leaf, leaves))


def _described(a):
    """A leaf as the key holds it: all that tells two lowerings apart."""
    if isinstance(a, _SCALARS):
        return type(a).__name__
    return (tuple(a.shape), str(a.dtype), bool(getattr(a, "weak_type", False)),
            repr(getattr(a, "sharding", None)),
            bool(getattr(a, "_committed", True)))


def _digest(part) -> str:
    return hashlib.sha256(json.dumps(part, sort_keys=True).encode()).hexdigest()


class _Loaded:
    """What ``lower`` answers for a signature whose executable was loaded:
    ``compile()`` is that executable; anything else of a lowering (its
    StableHLO text) is asked of the jit, which then traces."""

    def __init__(self, compiled, lower):
        self._compiled, self._lower = compiled, lower

    def compile(self):
        return self._compiled

    def __getattr__(self, name):
        return getattr(self._lower(), name)


class KeptJit:
    """``jax.jit(fun, **jit_kwargs)`` for the programs of ``mesh``, its
    executables kept in :func:`store_dir`.  ``identity`` is whatever
    decides the program besides the source, the mesh and the arguments (its
    ``repr`` enters the key)."""

    def __init__(self, fun, mesh, identity, **jit_kwargs):
        functools.update_wrapper(self, fun)
        self._jit = jax.jit(fun, **jit_kwargs)
        self._mesh = mesh
        self._identity = repr(identity)
        # signature -> what runs it: a loaded executable, or the jit
        self._run: dict = {}

    def __call__(self, *args):
        signature = _signature(args)
        run = self._run.get(signature)
        if run is None:
            return self._first_call(signature, args)
        return run(*args)

    def lower(self, *args):
        """The jit's ``lower``; for a signature whose executable was loaded
        something whose ``compile()`` is that executable, traced only if
        asked for more."""
        run = self._run.get(_signature(args), self._jit)
        if run is self._jit:
            return self._jit.lower(*args)
        return _Loaded(run, lambda: self._jit.lower(*args))

    def _cache_size(self) -> int:
        """The distinct executables dispatched: the loaded ones and the
        jit's."""
        return (sum(run is not self._jit for run in self._run.values())
                + self._jit._cache_size())

    # -- the first call of a signature ------------------------------------

    def _count(self, result: str) -> None:
        # looked up by name each time, as the compile listener does: a
        # registry reset between two trainers must not lose the count
        obs_metrics.REGISTRY.counter(
            obs_setup.KEPT_EXECUTABLES,
            "first calls of a signature of a program whose executable is "
            "kept beside the compilation cache, by what the store did: "
            "loaded, stored, miss (no file), stale (a file of another key), "
            "unreadable, unsupported",
            labelnames=("program", "result")).inc(
                program=self.__name__, result=result)

    def _first_call(self, signature, args):
        if any(isinstance(a, jax.core.Tracer) for a in jax.tree.leaves(args)):
            return self._jit(*args)     # a caller is tracing us
        where = self._where(args)
        if where is None:
            self._count("unsupported")
            self._run[signature] = self._jit
            return self._jit(*args)
        path, key = where
        loaded = self._load(path, key, args)
        if loaded is not None:
            self._run[signature] = loaded
            return loaded(*args)
        self._run[signature] = self._jit
        name = self.__name__
        # On the TPU the file is written BESIDE the first dispatch (the call
        # left the trace, the lowering and the executable in the jit's
        # caches).  Anywhere else the executable is compiled and written
        # AHEAD of its first run: XLA:CPU cannot serialise an executable
        # whose sort has run once (``UNIMPLEMENTED: `LessThan` is not
        # serializable``; the superstep sorts its stale rows), and beside
        # the dispatch that was a race a loaded machine lost.  Not ahead on
        # the TPU too: there the call after an ahead-of-time compile traces
        # the program a second time (2.0 s of 15 in `tenk-retrain-drift`;
        # PERF.md section 6, PRs 55 and 56).
        ahead = self._mesh.devices.flat[0].platform != "tpu"
        before = obs_setup.compilations_of(name)
        if ahead:
            compiled = self._jit.lower(*args).compile()
        else:
            compiled, out = None, self._jit(*args)
        made = collections.Counter(obs_setup.compilations_of(name))
        made.subtract(before)
        compiled_here = made["miss"] + made[obs_setup.UNCACHED] > 0
        self._keep(path, key, args, compiled_here and not made["hit"],
                   compiled)
        return self._jit(*args) if ahead else out

    def _where(self, args):
        """(the file, the live key) for these arguments, or None where
        nothing can be kept."""
        directory = store_dir()
        if (directory is None or self._mesh.is_multi_process
                or replaced_at_run_time()):
            return None
        devices = list(self._mesh.devices.flat)
        leaves, tree = jax.tree_util.tree_flatten(args)
        shape_part = {
            "identity": self._identity,
            "mesh": {"axes": list(self._mesh.shape.items()),
                     "devices": [(d.id, d.device_kind,
                                  getattr(d, "coords", None),
                                  getattr(d, "core_on_chip", None))
                                 for d in devices]},
            "arguments": {"tree": str(tree),
                          "leaves": list(map(_described, leaves))},
        }
        key = {"program": self.__name__, "source": source_digest(),
               "versions": versions(devices[0].client),
               "options": lowering_options(),
               **{name: _digest(part) for name, part in shape_part.items()}}
        name = f"{self.__name__}-{_digest(shape_part)[:24]}.bin"
        return os.path.join(directory, name), key

    def _load(self, path: str, key: dict, args):
        """The file's executable if it is whole and of this key, loaded on
        the mesh's devices; else None.  Counted either way."""
        from jax.experimental.serialize_executable import deserialize_and_load

        clock = obs_metrics.Stopwatch()
        loaded = None
        with obs_spans.RECORDER.span(
                "train.load_kept", "deeprest-trainer",
                {"program": self.__name__}) as span:
            result, found = _read(path)
            if found is not None:
                stored, out_tree, payload = found
                differs = sorted(k for k in set(key) | set(stored)
                                 if key.get(k) != stored.get(k))
                if differs:
                    result = "stale"
                    span.tag(differs=",".join(differs))
                else:
                    try:
                        loaded = deserialize_and_load(
                            payload, jax.tree_util.tree_structure((args, {})),
                            out_tree,
                            execution_devices=list(self._mesh.devices.flat))
                        result = "loaded"
                    except Exception as e:  # noqa: BLE001 - whatever the
                        # unpickler or the backend's deserialiser raises,
                        # this process cannot use the file: it traces
                        result = "unreadable"
                        span.tag(error=f"{type(e).__name__}: {e}"[:200])
            span.tag(result=result)
        self._count(result)
        if loaded is not None:
            obs_setup.count_kept_load(self.__name__, clock.elapsed())
        return loaded

    def _keep(self, path: str, key: dict, args, fresh: bool,
              compiled=None) -> None:
        """Write the executable the jit dispatches for ``args``
        (``compiled``, where the caller compiled it ahead), if it is
        whole."""
        from jax.experimental.serialize_executable import serialize

        platform = self._mesh.devices.flat[0].platform
        if not whole(platform, fresh):
            self._count("unsupported")
            return
        try:
            # a call left the trace, the lowering and the executable in
            # the jit's caches (the donated arguments keep their types)
            if compiled is None:
                compiled = self._jit.lower(*args).compile()
            payload, _, out_tree = serialize(compiled)
            _write(path, (key, out_tree, payload))
        except (NotImplementedError, ValueError, OSError):
            # constants closed over, a backend that does not serialise, a
            # directory that cannot be written
            self._count("unsupported")
            return
        self._count("stored")


# -- the file ------------------------------------------------------------------


def _write(path: str, entry: tuple) -> None:
    """``entry`` under ``path``, whole or not at all: a temporary name in
    the same directory, then ``os.replace``."""
    body = pickle.dumps(entry, protocol=pickle.HIGHEST_PROTOCOL)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(_MAGIC + b"%08x\n" % zlib.crc32(body))
            fh.write(body)
        os.replace(tmp, path)
    except BaseException:
        os.unlink(tmp)
        raise


def _read(path: str):
    """``("miss", None)`` without a file, ``("unreadable", None)`` for one
    that is cut short, damaged or not ours, else ``("found", entry)``."""
    try:
        with open(path, "rb") as fh:
            data = fh.read()
    except (FileNotFoundError, NotADirectoryError):
        return "miss", None
    except OSError:
        return "unreadable", None
    head = len(_MAGIC) + 9
    body = memoryview(data)[head:]
    if (not data.startswith(_MAGIC)
            or data[len(_MAGIC):head] != b"%08x\n" % zlib.crc32(body)):
        return "unreadable", None
    try:
        key, out_tree, payload = pickle.loads(body)
    except Exception:   # noqa: BLE001 - a body of another version of us
        return "unreadable", None
    return "found", (key, out_tree, payload)
