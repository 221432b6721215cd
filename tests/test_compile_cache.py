"""The one compile-cache helper (deeprest_tpu/compile_cache.py): a directory
given from outside is used as it is, and otherwise the cache is
``<checkout>/.jax_cache`` — whether JAX was imported before the call or
after.  Each case is a fresh interpreter: the helper and JAX both read the
environment once."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = """
import json, os, sys
{first}
from deeprest_tpu.compile_cache import configure_compile_cache
path = configure_compile_cache()
import jax
print(json.dumps({{"returned": path,
                   "jax": jax.config.jax_compilation_cache_dir,
                   "env": os.environ.get("JAX_COMPILATION_CACHE_DIR")}}))
"""


@pytest.mark.parametrize("jax_first", [False, True])
@pytest.mark.parametrize("given", [None, "/some/dir"])
def test_cache_dir(given, jax_first, tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    if given is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = given
    proc = subprocess.run(
        [sys.executable, "-c",
         PROBE.format(first="import jax" if jax_first else "")],
        capture_output=True, text=True, env=env, cwd=str(tmp_path),
        timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    want = given or os.path.join(REPO, ".jax_cache")
    assert got == {"returned": want, "jax": want, "env": want}
