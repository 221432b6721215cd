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
#   make bench-multichip  the mesh-shape scaling sweep on the 8-device
#                         virtual CPU mesh, quick tier (locally
#                         reproducible in a few minutes; refreshes
#                         MULTICHIP_r06.json — plumbing only; the real
#                         curve is not measured on the chip)
#   make serve-bench-replicas
#                         the serving-plane replica sweep (routing front,
#                         admission, concurrency up to 1024) — refreshes
#                         benchmarks/serve_bench.json; not measured on
#                         the chip
#   make obs-bench        the observability overhead gate (serve + train
#                         hot paths, obs off/on A/B, asserted <=3%
#                         budget) — refreshes benchmarks/obs_bench.json;
#                         not measured on the chip
#   make tenk-bench       the 10k-endpoint sparse-first vertical (F=10240
#                         featurize → ring → feed bytes → train → serve →
#                         peak RSS, dense vs padded-COO) — refreshes
#                         benchmarks/tenk_bench.json; not measured on
#                         the chip
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
#   make drift-bench      the model-quality observability gate (topology
#                         shift detection latency, ransomware-mid-drift,
#                         clean-corpus zero verdicts, <=3% monitor
#                         overhead) — refreshes benchmarks/
#                         drift_bench.json; not measured on the chip
#   make whatif-bench     the what-if capacity-surface gate (cached
#                         interpolated reads >=50x the direct
#                         synthesize->predict path at concurrency 16,
#                         parity envelope, batched build fold, zero
#                         post-warmup compiles) — refreshes benchmarks/
#                         whatif_bench.json; not measured on the chip
#   make quant-bench      the quantized-serving gate (int8 weight tree
#                         >=3.5x smaller than f32, serving drift inside
#                         the pinned parity envelope, executable count
#                         flat across off/int8/bf16 and frozen
#                         post-warmup) — refreshes benchmarks/
#                         quant_bench.json; a bandwidth win is not
#                         measured on the chip
#   make fleet-bench      the multi-tenant serving gate (100 apps, one
#                         executable plane: zero post-warmup compiles,
#                         bit-exact LRU spill/restore, byte-checked
#                         tenant isolation, AOT cold start beating
#                         compile-from-scratch) — refreshes benchmarks/
#                         fleet_bench.json; cold start and restore are
#                         not measured on the chip
#   make wire-bench       the span-firehose ingestion gate (push wire vs
#                         tailer-poll spans/sec at F=10240 sparse, >=10x
#                         asserted; overload storm with the drop/
#                         backpressure accounting identity; wire-vs-
#                         tailer training bit-parity + zero post-warmup
#                         compiles) — refreshes benchmarks/
#                         wire_bench.json; the wire tier runs on the
#                         host CPU, so this is its real measurement

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

bench-multichip:
	$(PYTHON) bench.py --mesh --virtual --quick --out MULTICHIP_r06.json

serve-bench-replicas:
	$(PYTHON) benchmarks/serve_bench.py --out benchmarks/serve_bench.json

obs-bench:
	$(PYTHON) benchmarks/obs_bench.py --out benchmarks/obs_bench.json

tenk-bench:
	$(PYTHON) benchmarks/tenk_bench.py --out benchmarks/tenk_bench.json

chaos-bench:
	$(PYTHON) benchmarks/chaos_bench.py --out benchmarks/chaos_bench.json

drift-bench:
	$(PYTHON) benchmarks/drift_bench.py --out benchmarks/drift_bench.json

whatif-bench:
	$(PYTHON) benchmarks/whatif_bench.py --out benchmarks/whatif_bench.json

quant-bench:
	$(PYTHON) benchmarks/quant_bench.py --out benchmarks/quant_bench.json

fleet-bench:
	$(PYTHON) benchmarks/fleet_bench.py --out benchmarks/fleet_bench.json

wire-bench:
	$(PYTHON) benchmarks/wire_bench.py --out benchmarks/wire_bench.json

.PHONY: lint lint-changed lint-fix lint-sarif lint-gate chip-smoke native tsan \
	bench-multichip serve-bench-replicas obs-bench tenk-bench \
	chaos-bench drift-bench whatif-bench quant-bench fleet-bench \
	wire-bench
