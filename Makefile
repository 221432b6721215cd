# Repo-level convenience targets.
#
#   make lint             graftlint over the package, JSON output (the
#                         same gate tests/test_lint_clean.py enforces in
#                         tier-1; see ANALYSIS.md for the rule catalog)
#   make lint-changed     graftlint scoped to files changed vs git HEAD
#                         (whole project still parsed for the call
#                         graph), SARIF output for CI inline annotation
#   make lint-fix         apply the safe mechanical fixes (HY001 unused
#                         imports, HY002 unreachable code); loops until
#                         stable, refuses suppressed findings, second
#                         run is a byte-identical no-op
#   make lint-sarif       full-repo SARIF 2.1.0 artifact (lint.sarif) —
#                         the artifact deploy/ci/lint-gate.sh uploads
#   make lint-gate        the committed pre-merge gate: lint --changed
#                         (SARIF) + the tier-1 test command
#                         (deploy/ci/lint-gate.sh)
#   make chip-smoke       simulate -> train -> serve on one TPU v5e
#                         (chip_smoke.py; fails without a chip)
#   make native           build the C++ featurizer (native/Makefile)
#   make tsan             build the thread-sanitized featurizer selftest
#                         — the native-side twin of the TH rule pack
#   make chaos-bench      the kill-under-load chaos storm gate (SIGKILL
#                         worker replicas + scheduled thread-replica
#                         ejections under live HTTP load, plus the
#                         elastic arm's injected device losses
#                         mid-training: zero wrong answers, bounded
#                         429/503, auto-rejoin, remesh bit-identical to
#                         restart-resume, zero leaked threads/processes/
#                         fds/device buffers) — refreshes
#                         benchmarks/chaos_bench.json; not measured on
#                         the chip

PYTHON ?= python

lint:
	$(PYTHON) -m deeprest_tpu lint --format json

lint-changed:
	$(PYTHON) -m deeprest_tpu lint --changed --format sarif

lint-fix:
	$(PYTHON) -m deeprest_tpu lint --fix

lint-sarif:
	$(PYTHON) -m deeprest_tpu lint --format sarif > lint.sarif; \
	status=$$?; echo "wrote lint.sarif"; exit $$status

lint-gate:
	bash deploy/ci/lint-gate.sh

chip-smoke:
	$(PYTHON) chip_smoke.py

native:
	$(MAKE) -C native

tsan:
	$(MAKE) -C native tsan

chaos-bench:
	$(PYTHON) benchmarks/chaos_bench.py --out benchmarks/chaos_bench.json

.PHONY: lint lint-changed lint-fix lint-sarif lint-gate chip-smoke native tsan \
	chaos-bench
