"""A throw-away benchmark root for the CPU rehearsals: a copy of chipbench/
plus a configuration, traffic mixes, limits, cells and a per-layer metric
that are added AS FILES, with no edit to any file that is there."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
REPO = os.path.dirname(CHIPBENCH)

TINY_MODEL = {"feature_dim": 24, "num_metrics": 10, "hidden_size": 8,
              "num_layers": 1, "bidirectional": True,
              "quantiles": [0.05, 0.5, 0.95], "dropout_rate": 0.5,
              "compute_dtype": "float32", "rnn_backend": "auto"}
RESOURCES = ["cpu", "memory", "write-iops", "write-tp", "usage"]


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


def make_root(tmp: str) -> str:
    """Copy chipbench/ into ``tmp`` and add tiny cells as new files."""
    root = os.path.join(tmp, "root")
    shutil.copytree(CHIPBENCH, os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    cb = os.path.join(root, "chipbench")
    _write(os.path.join(cb, "configs", "tiny.json"), {
        "name": "tiny", "source": "test", "runners": ["train"],
        "model": TINY_MODEL,
        "train": {"batch_size": 4, "window_size": 6, "device_data": "always",
                  "steps_per_superstep": 8, "log_every_steps": 0},
        "reduced": [], "assumed": {}})
    _write(os.path.join(cb, "configs", "tiny-sparse.json"), {
        "name": "tiny-sparse", "source": "test", "runners": ["train"],
        "model": TINY_MODEL,
        "train": {"batch_size": 4, "window_size": 6, "sparse_feed": True,
                  "sparse_nnz_cap": 8, "steps_per_superstep": 8,
                  "log_every_steps": 0},
        "reduced": [], "assumed": {}})
    _write(os.path.join(cb, "traffic", "tiny-corpus.json"), {
        "name": "tiny-corpus", "runner": "train", "generator": "corpus",
        "params": {"buckets": 400, "hot_paths": 16, "nnz_lo": 2, "nnz_hi": 6,
                   "day": 100, "resources": RESOURCES}})
    train_limits = {"limits": {"loss_rel_gap": 1e-4, "grad_norm_gap": 1e-3,
                               "delta_norm_gap": 1e-2}}
    _write(os.path.join(cb, "limits", "tiny-train.json"), train_limits)
    _write(os.path.join(cb, "limits", "tiny-train-sparse.json"), train_limits)
    # a per-layer metric of the test's own: two new files
    _write(os.path.join(cb, "layer_metrics", "steps_in_slice.json"), {
        "name": "steps_in_slice", "unit": "steps", "layer": "test",
        "moves": "train_steps_per_s", "source": "program_counter",
        "reader": "steps_in_slice:read", "runners": ["train"]})
    with open(os.path.join(cb, "readers", "steps_in_slice.py"), "w") as fh:
        fh.write("def read(evidence):\n    return evidence.get('steps')\n")
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"] += [
        {"name": "tiny", "source": "test", "file": "chipbench/configs/tiny.json",
         "reduced": [], "why": "test"},
        {"name": "tiny-sparse", "source": "test",
         "file": "chipbench/configs/tiny-sparse.json", "reduced": [],
         "why": "test"}]
    bench["workloads"] += [
        {"name": "tiny-train", "config": "tiny", "traffic": "tiny-corpus",
         "chips": 1, "why": "test"},
        {"name": "tiny-train-sparse", "config": "tiny-sparse",
         "traffic": "tiny-corpus", "chips": 1, "why": "test"}]
    bench["per_layer"].append(
        {"name": "steps_in_slice", "unit": "steps", "better": "higher",
         "source": "program_counter", "layer": "test",
         "moves": "train_steps_per_s",
         "workloads": ["tiny-train", "tiny-train-sparse"]})
    for m in bench["end_to_end"]:            # every metric here is a train one
        if "workloads" in m:
            m["workloads"] += ["tiny-train", "tiny-train-sparse"]
    _write(os.path.join(root, "BENCHMARK.json"), bench)
    return root


DRIVER = """
import json, sys
sys.path.insert(0, {root!r})
{prelude}
from chipbench import run
result = run.run_cell({cell!r}, {seed}, {seconds}, {trace}, require_chip=False)
print("RESULT " + json.dumps(result))
"""


def run_cell(root: str, cell: str, seed: int = 5, seconds: float = 0.5,
             trace: bool = False, prelude: str = "", timeout: int = 600):
    """One rehearsal run in a process of its own, on the CPU; returns
    (result object, all output)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""),
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    code = DRIVER.format(root=root, cell=cell, seed=seed, seconds=seconds,
                         trace=trace, prelude=prelude)
    proc = subprocess.run([sys.executable, "-c", code], env=env, cwd=root,
                          capture_output=True, text=True, timeout=timeout)
    out = proc.stdout + proc.stderr
    if proc.returncode != 0:
        raise RuntimeError(f"rehearsal of {cell} failed:\n{out[-4000:]}")
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):]), out
