"""Tier-1 self-check: the whole package lints clean against an EMPTY
baseline.

This is the enforcement half of graftlint: tests/test_analysis.py proves
each rule fires and stays silent correctly; this test pins deeprest_tpu
itself at zero non-baselined findings forever.  A PR that introduces a
jit closure capture (JX001/PR 4 bug class), a recompile hazard, an
off-lock shared attribute (TH001), a leaked worker pipe (RS001), a
drained-and-stranded replica (RS002/EX002), or a lock cycle fails
tier-1 here — the same way a racy native featurizer change fails the
tsan selftest.

Also pinned here: ANALYSIS.md's generated suppression table matches the
live in-code inventory exactly (doc-vs-code drift is a failure).
"""

import os

import deeprest_tpu
from deeprest_tpu.analysis import (
    default_baseline_path, lint_paths, load_baseline, load_project,
    render_suppressions_markdown, render_text, suppression_inventory,
)

PACKAGE_DIR = os.path.dirname(os.path.abspath(deeprest_tpu.__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_package_lints_clean_with_empty_baseline():
    baseline = load_baseline(default_baseline_path())
    assert baseline == [], (
        "the checked-in baseline must stay EMPTY: fix findings (or "
        "suppress them in-code with a reason), do not baseline them; "
        f"found {baseline}")
    result = lint_paths([PACKAGE_DIR], baseline_keys=baseline)
    assert result.files >= 50, "package walk looks truncated"
    assert not result.findings, "\n" + render_text(result)


def test_suppressions_all_carry_reasons():
    # Redundant with GL001 (which the clean run above enforces), but
    # explicit: every in-code deviation must say WHY.
    result = lint_paths([PACKAGE_DIR], rules=[])
    assert not [f for f in result.findings if f.rule == "GL001"]


def test_analysis_md_suppression_table_matches_live_inventory():
    """ANALYSIS.md's suppression table is GENERATED (`deeprest lint
    --list-suppressions --format markdown`); this pin makes doc-vs-code
    drift a tier-1 failure.  Regenerate the block between the markers
    after adding/removing a suppression."""
    md_path = os.path.join(REPO_ROOT, "ANALYSIS.md")
    if not os.path.exists(md_path):
        import pytest

        pytest.skip("ANALYSIS.md not present in this checkout")
    content = open(md_path, encoding="utf-8").read()
    begin, end = "<!-- suppressions:begin -->", "<!-- suppressions:end -->"
    assert begin in content and end in content, \
        "ANALYSIS.md lost its generated-suppressions markers"
    committed = content.split(begin, 1)[1].split(end, 1)[0].strip()
    live = render_suppressions_markdown(
        suppression_inventory(load_project([PACKAGE_DIR]))).strip()
    assert committed == live, (
        "ANALYSIS.md's suppression table drifted from the code; "
        "regenerate it:\n  python -m deeprest_tpu lint "
        "--list-suppressions --format markdown")
