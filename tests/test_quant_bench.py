"""quant_bench plumbing gate (tier-1): the --quick arms run end-to-end
(three predictors through the fused engine, one per quant mode), their
gates hold, and the committed full-mode artifact keeps asserting the
≥3.5x weight-byte claim with parity inside the pinned envelope.

Quick mode keeps tier-1 honest about PLUMBING (quantized Predictor
construction at every mode, the envelope measurement, the flatness of
the executable ladder, the serving-path parity check) with collapse-only
timing gates — CPU wall-clock noise must not flake tier-1; the committed
benchmarks/quant_bench.json is the full-mode record whose gates this
file re-checks without re-running the bench.  The quick bench runs ONCE
per module — its record and headline line feed every test below.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMMITTED = os.path.join(REPO, "benchmarks", "quant_bench.json")


@pytest.fixture(scope="module")
def quick_run(tmp_path_factory):
    out = tmp_path_factory.mktemp("quant_bench") / "quant_bench.json"
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "quant_bench.py"),
         "--quick", "--headline", "--out", str(out)],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-2000:])
    return json.loads(out.read_text()), proc.stdout


def test_quant_bench_quick_gates(quick_run):
    rec, _ = quick_run
    assert rec["mode"] == "quick"
    assert rec["bytes"]["ok"]
    assert rec["bytes"]["ratio_int8"] >= 3.5
    assert rec["bytes"]["ratio_bf16"] >= 1.9
    assert rec["parity"]["ok"]
    for mode in ("int8", "bf16"):
        cell = rec["parity"]["modes"][mode]
        assert cell["within_envelope"]
        assert cell["serving_max_abs_diff"] <= cell["envelope_budget_max"]
        assert cell["cells"] == 9            # 3 metrics x 3 quantiles


def test_quant_bench_quick_executables_flat_and_frozen(quick_run):
    rec, _ = quick_run
    c = rec["compiles"]
    assert c["flat_across_modes"], c
    assert c["zero_post_warmup"], c
    # quantization must not grow the ladder: all three modes compile
    # the SAME number of executables from the same warmup
    assert len(set(c["after_warmup"].values())) == 1


def test_headline_emits_schema_v13_keys(quick_run):
    """bench.py (schema v13) consumes exactly these keys."""
    _, stdout = quick_run
    line = stdout.strip().splitlines()[-1]
    rec = json.loads(line)
    assert "quant_weight_bytes" in rec
    assert "quant_parity_max" in rec
    assert rec["quant_weight_bytes"] > 0
    assert rec["quant_parity_max"] >= 0


def test_committed_record_keeps_the_claim():
    """The committed full-mode dossier: int8 weight tree ≥3.5x smaller
    than f32 at flagship-ish shapes, serving-path drift inside the
    stored envelope for both modes, executable count identical across
    off/int8/bf16 and frozen post-warmup."""
    with open(COMMITTED, encoding="utf-8") as f:
        rec = json.load(f)
    assert rec["mode"] == "full"
    assert rec["bytes"]["ratio_int8"] >= 3.5
    assert rec["bytes"]["ratio_bf16"] >= 1.9
    assert rec["parity"]["ok"]
    for mode in ("int8", "bf16"):
        assert rec["parity"]["modes"][mode]["within_envelope"]
    assert rec["compiles"]["flat_across_modes"]
    assert rec["compiles"]["zero_post_warmup"]
    # a speedup is not measured on the chip, and not claimed from this
    # CPU artifact — the footnotes must say so
    assert "CPU" in rec["throughput"]["footnote"]
    assert "CPU" in rec["coldstart"]["footnote"]
