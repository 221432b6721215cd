"""AOT executable serialization: tenant cold-start is a deserialize.

The fleet tier (serve/fleet.py) shares ONE fused-engine executable set
across every tenant, so the plane compiles each (program, rung) pair at
most once — but that once is still an XLA compile on the serving path,
and a cold plane admitting its first tenant pays the whole ladder.  This
module moves the compile to EXPORT time: ``export_aot`` lowers the fused
serving programs at every rung (``jax.jit(...).lower().compile()``, the
AOT lineage), serializes each compiled executable
(``jax.experimental.serialize_executable``), and writes the artifacts
next to the checkpoint (``<ckpt>/aot/``).  ``load_aot`` — called at pool
admission — deserializes every artifact whose manifest fingerprint
matches the live engine and installs it into the engine's AOT dispatch
table, so the first request compiles nothing; rungs with no loadable
artifact fall back to the normal lazy jit compile and are counted
loudly (the pool's compile-fallback counter).  An artifact that matches
the fingerprint and still does not load is an error: a plane that was told
it cold-starts from artifacts must not turn into one that compiles.

Three contracts keep this honest:

- **Params-agnostic artifacts.**  The fused program threads params and
  normalization stats as runtime ARGUMENTS (serve/fused.py bit-parity
  contract), so one artifact set serves every tenant of the same
  architecture + quant mode; only avals (shapes/dtypes/tree structure)
  are baked, and the manifest fingerprints exactly those.
- **Identical lowering.**  The serialized executable is compiled from
  the SAME traced program the lazy jit path would compile, with default
  options on the same backend — outputs are bit-identical either way
  (asserted by tests/test_fleet.py's AOT cases).
- **Loud staleness.**  A manifest whose fingerprint (jax version, XLA
  platform, geometry, params tree signature) mismatches the live engine
  is never partially loaded: the whole load falls back to compile, with
  the mismatch named in the result — a stale artifact must cost a
  compile, never a wrong answer.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np

AOT_SUBDIR = "aot"
MANIFEST_NAME = "manifest.json"


def aot_dir(checkpoint_dir: str) -> str:
    """Where a checkpoint's AOT artifacts live (next to the checkpoint —
    the artifacts are as checkpoint-adjacent as the quant parity
    envelope, and ride the same directory copy)."""
    return os.path.join(checkpoint_dir, AOT_SUBDIR)


def _tree_signature(params) -> str:
    """Stable hash of the params AVAL pytree — structure plus per-leaf
    shape/dtype, never values: the executable is params-agnostic but
    aval-exact, so this is the exact compatibility surface."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(params)
    h = hashlib.sha1(str(treedef).encode())
    for leaf in leaves:
        # np.result_type reads dtype METADATA (no array materialization,
        # no device->host copy for jax leaves)
        dtype = getattr(leaf, "dtype", None) or np.result_type(leaf)
        h.update(str((tuple(np.shape(leaf)), str(dtype))).encode())
    return h.hexdigest()[:16]


def engine_fingerprint(predictor) -> dict:
    """Everything that must match between the exporting and the loading
    engine for a serialized executable to be callable and correct."""
    import jax

    eng = predictor.fused
    if eng is None:
        raise ValueError("AOT artifacts cover the fused serving engine; "
                         "construct the predictor with fused=True")
    return {
        "jax": jax.__version__,
        "platform": jax.default_backend(),
        "window_size": int(predictor.window_size),
        "feature_dim": int(predictor.feature_dim),
        "num_metrics": len(predictor.metric_names),
        "num_quantiles": len(predictor.quantiles),
        "quant": predictor.quant,
        "rungs": list(int(r) for r in eng.rungs),
        "delta": bool(eng._has_delta),
        "sparse_nnz_cap": eng._sparse_nnz_cap,
        "params_tree": _tree_signature(eng._params),
    }


def _example_args(predictor, rung: int, sparse: bool):
    """The exact argument tuple the fused dispatch site passes at this
    rung — same shapes, dtypes, and tree structure (serve/fused.py
    ``_predict_many_inner``); zeros everywhere because only avals
    matter for lowering and tree reconstruction."""
    import jax.numpy as jnp

    eng = predictor.fused
    w = eng.window_size
    g = jnp.asarray(np.full((rung,), w - 1, np.int32))
    seg = jnp.asarray(np.zeros((rung,), np.bool_))
    tail = (eng._x_mn, eng._x_rg, eng._y_mn, eng._y_rg, eng._carry0,
            g, seg, np.int32(rung), np.bool_(True))
    if sparse:
        k = eng._sparse_nnz_cap
        xc = jnp.asarray(np.zeros((rung, w, k), np.int32))
        xv = jnp.asarray(np.zeros((rung, w, k), np.float32))
        return (eng._params, xc, xv) + tail
    feat = int(predictor.feature_dim)
    x = jnp.asarray(np.zeros((rung, w, feat), np.float32))
    return (eng._params,) + (x,) + tail


_PROGRAM_OF = {"dense": "_program", "sparse": "_program_sparse"}


def _kinds(eng) -> list[str]:
    """The fused programs the engine serves: dense, and sparse when on."""
    return ["dense", "sparse"] if eng.sparse_enabled else ["dense"]


def _fresh_jit(program):
    """``program`` under a jit of its own.  JAX keeps lowerings and
    executables in memory per traced function, so the engine's own jit
    would hand ``export_aot`` the executable its warm-up loaded from the
    persistent cache; a new wrapper traces the same Python to the same HLO
    and compiles it anew."""
    import jax

    return jax.jit(lambda *args: program(*args))


def export_aot(predictor, checkpoint_dir: str,
               rungs=None) -> dict:
    """Compile and serialize the fused serving executables next to the
    checkpoint.  Returns the manifest (also written to
    ``<ckpt>/aot/manifest.json``).

    Lowering + AOT compile does NOT enter the jit call cache (verified
    by tests/test_fleet.py), so exporting from a live predictor never
    perturbs the zero-post-warmup-compiles ledger.
    """
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.experimental.serialize_executable import serialize

    eng = predictor.fused
    fp = engine_fingerprint(predictor)
    out_dir = aot_dir(checkpoint_dir)
    os.makedirs(out_dir, exist_ok=True)
    entries = []
    programs = {kind: _fresh_jit(getattr(eng, _PROGRAM_OF[kind]))
                for kind in _kinds(eng)}
    # Compile OUTSIDE the persistent compilation cache: a cache-hit
    # executable serializes as a thin reference to jit-compiled symbols
    # ("Function ... not found" when the loaded artifact first runs)
    # instead of embedding its object code, and the artifact must be
    # self-contained on any host.  Two layers have to be passed: the
    # cache memoizes whether it is in use, so the flag is switched with a
    # reset on either side; and each program is traced under a jit of its
    # own (_fresh_jit).
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        for rung in (tuple(rungs) if rungs is not None else eng.rungs):
            for kind, jitted in programs.items():
                args = _example_args(predictor, int(rung), kind == "sparse")
                compiled = jitted.lower(*args).compile()
                payload, _, _ = serialize(compiled)
                fname = f"{kind}_r{int(rung)}.bin"
                with open(os.path.join(out_dir, fname), "wb") as f:
                    f.write(payload)
                entries.append({"kind": kind, "rung": int(rung),
                                "file": fname, "bytes": len(payload)})
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        compilation_cache.reset_cache()
    manifest = {"fingerprint": fp, "entries": entries}
    with open(os.path.join(out_dir, MANIFEST_NAME), "w",
              encoding="utf-8") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    return manifest


def load_aot(predictor, checkpoint_dir: str) -> dict:
    """Load-or-compile at pool admission: deserialize every artifact
    whose fingerprint matches the live engine into the engine's AOT
    dispatch table.  A missing or stale artifact means that rung compiles
    lazily through the normal jit path, and the result names every such
    fallback:

    ``{"loaded": n, "fallback_rungs": [(kind, rung), ...],
       "reason": None | str, "bytes": total_payload_bytes}``

    An artifact the manifest lists under a matching fingerprint that then
    fails to deserialize raises ``RuntimeError`` naming the file.  Each
    executable is bound to the engine's own device (``execution_devices``),
    not to every device JAX sees: a replica owns one.
    """
    from jax.experimental.serialize_executable import deserialize_and_load
    import jax.tree_util as jtu

    eng = predictor.fused
    result = {"loaded": 0, "fallback_rungs": [], "reason": None, "bytes": 0}
    if eng is None:
        result["reason"] = "fused engine disabled"
        return result
    want = [(kind, int(r)) for r in eng.rungs for kind in _kinds(eng)]
    man_path = os.path.join(aot_dir(checkpoint_dir), MANIFEST_NAME)
    if not os.path.exists(man_path):
        result["reason"] = "no artifacts"
        result["fallback_rungs"] = want
        return result
    try:
        with open(man_path, encoding="utf-8") as f:
            manifest = json.load(f)
    except (OSError, ValueError) as e:
        result["reason"] = f"unreadable manifest: {e}"
        result["fallback_rungs"] = want
        return result
    fp = engine_fingerprint(predictor)
    stored = manifest.get("fingerprint", {})
    if stored != fp:
        diff = sorted(k for k in set(fp) | set(stored)
                      if fp.get(k) != stored.get(k))
        result["reason"] = f"fingerprint mismatch: {diff}"
        result["fallback_rungs"] = want
        return result
    by_key = {(e["kind"], int(e["rung"])): e
              for e in manifest.get("entries", ())}
    device = next(iter(eng._carry0.devices()))
    for kind, rung in want:
        entry = by_key.get((kind, rung))
        path = (os.path.join(aot_dir(checkpoint_dir), entry["file"])
                if entry is not None else None)
        if path is None or not os.path.exists(path):
            result["fallback_rungs"].append((kind, rung))
            continue
        with open(path, "rb") as f:
            payload = f.read()
        args = _example_args(predictor, rung, kind == "sparse")
        _, in_tree = jtu.tree_flatten((args, {}))
        # the program returns (out, carry): a 2-tuple of arrays
        _, out_tree = jtu.tree_flatten((0.0, 0.0))
        try:
            loaded = deserialize_and_load(payload, in_tree, out_tree,
                                          execution_devices=[device])
        except Exception as e:
            raise RuntimeError(
                f"AOT artifact {path} matches the engine fingerprint but "
                f"does not load: {type(e).__name__}: {e}") from e
        eng._aot[(kind, rung)] = loaded
        result["loaded"] += 1
        result["bytes"] += len(payload)
    if result["fallback_rungs"]:
        result["reason"] = "artifacts missing for " + ", ".join(
            f"{kind}_r{rung}" for kind, rung in result["fallback_rungs"][:4])
    return result
