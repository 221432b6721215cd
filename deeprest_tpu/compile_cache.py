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
