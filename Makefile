# Tier-1 verification and benchmark entry points.
#
#   make test        — fast tier-1 suite (slow-marked tests excluded)
#   make test-all    — everything, including AOT dry-run compiles
#   make lint        — ruff check + format check (no-op if ruff missing)
#   make bench-smoke — small-size pass over the benchmark drivers
#   make bench-sparse— dense-vs-sparse scaling acceptance run
#   make bench-serve — batched serving throughput (writes BENCH_serve.json)
#   make bench-plan  — planner-vs-empirical crossover smoke (CI gate;
#                      exits 1 on disagreement at the extremes)
#   make bench-incremental — streaming-update maintenance acceptance
#                      (CI gate; exits 1 below the ≥10× update-to-answer
#                      speedup, on answer divergence, or when the planner
#                      fails to pick delta_restart; BENCH_incremental.json)
#   make test-dist   — the sharded suite on 8 simulated host devices
#                      (DESIGN.md §6; CI job test-distributed)
#   make bench-sharded — graph-axis sharded crossover acceptance on 8
#                      simulated devices (CI gate; exits 1 on
#                      sharded/single-device divergence, when D=8 loses
#                      to one device at the largest size, when exchanged
#                      bytes drop < 5× under the dense all-gather, or
#                      when the planner's pick disagrees with the
#                      measured winner on either side of the crossover;
#                      BENCH_sharded.json)
#   make bench-check — regression gate: fresh BENCH_*.json vs the
#                      committed baselines (exits 1 on >25% regression;
#                      the unitless sharded speedup gets a tighter 20%
#                      gate so the crossover claim cannot quietly rot)
#   make bench-kernel — fused SpMM vs jnp sweep (semiring × B × density;
#                      CI gate: exits 1 below the 1.5× bool B=64 serve-
#                      shape floor or on kernel/oracle divergence;
#                      BENCH_kernels.json) + the measured roofline
#                      (results/roofline.json).  The perf sweep times
#                      the hardware backend; its parity cells run the
#                      Pallas kernel in interpret mode.
#   make test-kernel — fast fused-kernel parity suite in Pallas
#                      interpret mode (CI test matrix step)
#   make docs-check  — docs gate (CI lint step): every §N pointer in
#                      the tree resolves to a DESIGN.md section and
#                      every README ```python example executes

PY      ?= python
PYPATH  := src
DIST_FLAGS := --xla_force_host_platform_device_count=8

test:
	PYTHONPATH=$(PYPATH) $(PY) -m pytest -x -q

test-all:
	PYTHONPATH=$(PYPATH) $(PY) -m pytest -q -m "slow or not slow" --durations=20

test-dist:
	XLA_FLAGS=$(DIST_FLAGS) PYTHONPATH=$(PYPATH) $(PY) -m pytest -x -q tests/test_sharded.py

# Format-check only files changed since origin/main (or HEAD~1): the
# tree predates ruff-format, so a blanket --check fails on files the
# change never touched — same scoping as the CI lint job.
lint:
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check . || exit 1; \
		BASE=$$(git merge-base origin/main HEAD 2>/dev/null \
			|| git rev-parse HEAD~1 2>/dev/null \
			|| git rev-parse HEAD); \
		CHANGED=$$(git diff --name-only --diff-filter=ACMR "$$BASE" -- '*.py'); \
		if [ -z "$$CHANGED" ]; then \
			echo "no Python files changed — format check skipped"; \
		else \
			echo "$$CHANGED" | xargs ruff format --check; \
		fi; \
	else \
		echo "ruff not installed; skipping lint"; \
	fi

bench-smoke:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.run --quick --only sparse,serve,kernel,plan,incremental,sharded,replan

bench-sparse:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.sparse_scaling

bench-serve:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.serve_batch

bench-plan:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.plan_crossover --quick

bench-incremental:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.incremental_update

bench-sharded:
	XLA_FLAGS=$(DIST_FLAGS) PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.sharded_scaling

bench-replan:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.replan_adaptive

bench-check:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.check_regression \
		--metric-threshold speedup=0.2

bench-kernel:
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.kernel_bench
	PYTHONPATH=$(PYPATH) $(PY) -m benchmarks.roofline

test-kernel:
	PYTHONPATH=$(PYPATH) $(PY) -m pytest -x -q tests/test_coo_spmm.py

docs-check:
	PYTHONPATH=$(PYPATH) $(PY) tools/docs_check.py

.PHONY: test test-all test-dist lint bench-smoke bench-sparse \
	bench-serve bench-plan bench-incremental bench-sharded bench-replan \
	bench-check bench-kernel test-kernel docs-check
