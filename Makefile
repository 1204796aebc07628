# repo root on the path too: benchmarks/ imports `benchmarks.common`
PY := PYTHONPATH=src:. python

.PHONY: verify test quick bench bench-smoke analysis obs-smoke

# tier-1 gate: the full suite + the round-executor benchmark in smoke mode,
# checked against the committed BENCH_cola.json trajectory (>20% slowdown
# fails; tune with BENCH_TOLERANCE)
verify: test bench-smoke

# --durations surfaces the slowest tests in CI logs so wall-time
# regressions (e.g. an unmarked multi-device subprocess test) are visible
test:
	$(PY) -m pytest -x -q --durations=15

# quick path: skip the slow subprocess equivalence tests
quick:
	$(PY) -m pytest -x -q -m "not slow"

# full round-executor benchmark; writes BENCH_cola.json at the repo root.
# Its plan-gossip rows run on a mesh of this process's devices: eight
# virtual ones on the CPU
BENCH_MESH := XLA_FLAGS=--xla_force_host_platform_device_count=8
bench:
	$(BENCH_MESH) $(PY) benchmarks/round_bench.py

bench-smoke:
	$(BENCH_MESH) $(PY) benchmarks/round_bench.py --smoke --check

# static contract verification: AST lints over src/, every registered
# driver config checked against its declared comm contract, and the
# seeded-violation smoke proving each pass still fires
analysis:
	$(PY) -m repro.analysis --all --selftest

# observability smoke: two telemetry runs (clean fp32 + int8/trim under a
# seeded Byzantine pair) land in a throwaway registry, then every
# repro.obs subcommand runs over them — list, show, diff (which must come
# back telemetry-only against the clean twin's config delta), timeline
obs-smoke:
	$(PY) -m repro.obs --dir $$(mktemp -d) smoke
