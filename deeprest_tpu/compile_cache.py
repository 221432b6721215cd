"""Where JAX's persistent compilation cache lives, decided in one place.

A chip run starts with no compiled code, and the flagship step takes tens
of seconds to compile, so every entry point (``deeprest_tpu`` CLI,
``chip_smoke.py``, ``chipbench``, the tests) shares one cache.  Its path is
part of the cache key's environment, so it has to be stable: no temporary
name, pid or time in it.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code, so whoever runs the program can place the cache.
- unset: ``<checkout>/.jax_cache`` (listed in ``.gitignore``), exported
  under that name so that JAX and every child process find it.

The directory holds jax's entries (one a lowered module, found only after
a process has traced and lowered the program again) and, in
``deeprest-kept/``, the training superstep's executable as
``deeprest_tpu/train/kept.py`` keeps it: one file a program, configuration,
mesh and argument shapes, found by a key made before anything is traced
(the package's sources, the versions, the whole ``Config`` but its seed, the
mesh, the arguments' types, the compiler's flags).  A file of another key
is stale: the process traces as before and overwrites it, so the directory
does not grow with edits.  Deleting ``deeprest-kept/``, any file in it, or
the whole cache is always safe.  The tests share the cache on purpose and
give each test a ``deeprest-kept/`` of its own (tests/conftest.py): the
key cannot see a function a test replaced at run time.
"""

from __future__ import annotations

import os
import sys

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def configure_compile_cache() -> str:
    """Settle the cache directory for this process and its children;
    returns it.  Never imports JAX itself, so a parent that must stay off
    the chip can call it before it starts its children."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    os.environ[ENV_VAR] = path      # JAX reads it at import; children inherit
    jax = sys.modules.get("jax")
    if jax is not None:             # imported already: it read the variable then
        jax.config.update("jax_compilation_cache_dir", path)
    return path
