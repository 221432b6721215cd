"""fleet_bench plumbing gate (tier-1): the --quick arms run end-to-end
(12 apps through one PredictorPool), their gates hold, and the committed
full-mode artifact keeps asserting the 100-apps-one-plane claim.

Quick mode keeps tier-1 honest about PLUMBING (admission sharing, the
frozen jit-cache ledger, LRU spill->restore bit-exactness, threaded
tenant isolation, the AOT round-trip) with generous timing gates — CPU
wall-clock noise must not flake tier-1; the committed
benchmarks/fleet_bench.json is the full-mode record whose gates this
file re-checks without re-running the bench.  The quick bench runs ONCE
per module — its record and headline line feed every test below.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED = os.path.join(REPO, "benchmarks", "fleet_bench.json")


@pytest.fixture(scope="module")
def quick_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("fleet_bench") / "fleet_bench.json"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "fleet_bench.py"),
         "--quick", "--headline", "--out", str(out)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    return json.loads(out.read_text()), proc.stdout


def test_fleet_bench_quick_ledger_flat(quick_run):
    rec, _ = quick_run
    assert rec["mode"] == "quick"
    led = rec["ledger"]
    assert led["ok"]
    assert led["per_app_compiles"] == 0
    assert led["jit_cache_after_all_apps"] == led["jit_cache_after_warmup"]
    assert led["apps"] > led["hbm_budget"]   # the storm is real


def test_fleet_bench_quick_churn_honest_and_bit_exact(quick_run):
    rec, _ = quick_run
    ch = rec["churn"]
    assert ch["ok"]
    assert ch["spills"] > 0 and ch["restores"] > 0
    assert ch["post_storm_bit_exact"]
    assert ch["p99_over_median"] <= rec["p99_factor"]
    # the host tier is an LRU, not a leak: residency stays at budget
    assert ch["resident"] == rec["shapes"]["hbm_budget"]


def test_fleet_bench_quick_isolation_and_aot(quick_run):
    rec, _ = quick_run
    iso = rec["isolation"]
    assert iso["ok"]
    assert iso["solo_bit_identical"] and iso["concurrent_bit_identical"]
    assert iso["b_reload_took_effect"]
    assert iso["b_invalidations"] == {"storm-reload": 1}
    aot = rec["aot"]
    assert aot["ok"]
    assert aot["aot_loaded"] > 0 and not aot["aot_fallback_rungs"]
    assert aot["bit_identical_vs_compiled"]
    assert aot["lazy_jit_untouched"]
    assert aot["pool_admission"]["compile_fallbacks"] == 0


def test_headline_emits_schema_v14_keys(quick_run):
    """bench.py (schema v14) consumes exactly these keys."""
    _, stdout = quick_run
    line = stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    assert rec["fleet_apps"] > 0
    assert rec["fleet_cold_start_ms"] > 0
    assert rec["fleet_spill_restore_ms"] > 0


def test_committed_record_keeps_the_claim():
    """The committed full-mode dossier: 100 apps through one executable
    plane with ZERO per-app compiles, honest spill/restore counters,
    byte-checked isolation, and AOT cold start beating
    compile-from-scratch."""
    with open(COMMITTED, encoding="utf-8") as f:
        rec = json.load(f)
    assert rec["mode"] == "full"
    assert rec["ledger"]["apps"] == 100
    assert rec["ledger"]["per_app_compiles"] == 0
    assert rec["churn"]["spills"] > 0 and rec["churn"]["restores"] > 0
    assert rec["churn"]["post_storm_bit_exact"]
    assert rec["isolation"]["concurrent_bit_identical"]
    assert rec["isolation"]["b_reload_took_effect"]
    assert rec["aot"]["speedup"] >= 1.5
    assert rec["aot"]["bit_identical_vs_compiled"]
    assert rec["aot"]["pool_admission"]["compile_fallbacks"] == 0
    # the cold-start gap is not measured on the chip, and not claimed from
    # this CPU artifact — the footnote must say so
    assert "CPU" in rec["aot"]["footnote"]
