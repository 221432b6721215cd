"""chip_smoke.py away from the chip: the rehearsal runs every phase at toy
sizes on the CPU (kernels in interpret mode where the kernel is the point),
and the script never passes without a TPU — not rehearsing, not run plainly,
not alone in a directory."""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")

PHASES = (
    "device", "compile", "kernel_in_train_step", "block_until_ready",
    "kernel_vs_scan", "row_fold_vs_per_group", "wide_train_step",
    "wide_fused_predict", "memory", "checks", "compile_cache", "simulate",
    "featurize", "train", "train_superstep_g4", "serve", "serve_int8",
    "export_aot", "serve_aot_load", "total",
)


def _run(argv, cwd=REPO, script=SMOKE, timeout=900):
    # not conftest's eight virtual devices, and no path to the repo but the
    # script's own
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, script, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    return proc, lines


def test_rehearsal_runs_every_phase_and_still_reports_failure():
    proc, lines = _run(["--rehearse"])
    by_phase = {}
    for rec in lines[:-1]:
        by_phase.setdefault(rec["phase"], []).append(rec)
    assert set(PHASES) <= set(by_phase), (
        sorted(set(PHASES) - set(by_phase)), proc.stderr[-3000:])
    # no phase failed but the ones that say "this is not a TPU"
    bad = [r["phase"] for recs in by_phase.values() for r in recs
           if r.get("ok") is False]
    assert sorted(set(bad)) == ["checks", "device"], (bad, lines)
    assert by_phase["checks"][0]["failed"] == ["device"]
    # the cache proof ran in two processes, and the second one hit
    assert len(by_phase["compile"]) == 2
    assert by_phase["compile_cache"][0]["second_process_hit"] is True
    # the kernel's own code ran (interpreted) against the scan
    assert (by_phase["kernel_in_train_step"][0]["parity_check_backend"]
            == "pallas_interpret")
    assert by_phase["kernel_vs_scan"][0]["loss_rel_diff"] <= 1e-5
    # the served plane did its work through both dispatchers
    serve = by_phase["serve"][0]
    assert serve["fused_infer"]["pages"] >= 1
    assert serve["batcher"]["batches"] >= 1
    assert serve["shutdown_exit_code"] == 0
    assert by_phase["serve_aot_load"][0]["aot_pages"] >= 1
    # the last line has the contract's shape and says no
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
        "failed": ["checks", "device"]}
    assert proc.returncode != 0


def test_without_a_chip_it_fails_at_the_first_phase():
    proc, lines = _run([], timeout=300)
    assert proc.returncode != 0
    assert [r.get("phase") for r in lines[:-1]] == ["device", "total"]
    assert lines[-1]["ok"] is False and lines[-1]["failed"] == ["device"]
    assert lines[-1]["device"]["platform"] == "cpu"


def test_alone_in_a_directory_it_fails(tmp_path):
    script = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc, lines = _run([], cwd=str(tmp_path), script=str(script),
                       timeout=300)
    assert proc.returncode != 0
    assert lines[-1]["ok"] is False
    assert not any(r.get("ok") is True for r in lines)
