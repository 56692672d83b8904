# Developer/CI gate for the TPU-native framework.
#
# `make test` is the merge gate: the full hermetic suite on a virtual
# 8-device CPU mesh (no TPU needed), wall-clock-capped so a wedged
# multi-process test fails CI instead of hanging it.

PYTHON ?= python
SHELL := /bin/bash

.PHONY: test test-fast bench smoke install lint native clean chaos \
  metrics-lint racecheck goodput-report slo-lint slo-report

install:
	$(PYTHON) -m pip install -e .

# both native libraries, through the loader that names each binary by
# its source's hash (tensorflowonspark_tpu/_native.py) — the same build
# the package does lazily at first use
native:
	$(PYTHON) -c "from tensorflowonspark_tpu import shm, _tfrecord_native; \
	  shm._load(); _tfrecord_native._load()"

# metric-catalog drift gate: every family tracing.METRIC_FAMILIES
# exports must have a docs/observability.md catalog row and vice versa
# (scripts/metrics_lint.py) — a prerequisite of the merge gate, so the
# catalog cannot drift from the code
metrics-lint:
	$(PYTHON) scripts/metrics_lint.py

# concurrency lint gate (PR 14): AST-based guarded-attribute race
# check, lock-order audit, and thread-lifecycle rules over the whole
# package (tensorflowonspark_tpu/analysis/, stdlib-ast only, ~2s).
# New findings fail CI; pre-existing benign ones live in
# analysis/baseline.json with written reasons. Rule catalog and the
# fix-vs-baseline workflow: docs/static_analysis.md
racecheck:
	$(PYTHON) -m tensorflowonspark_tpu.analysis

# SLO-spec drift gate (PR 20): every spec in slo.DEFAULT_SPECS (plus
# any deployment extras passed as args) must reference a family that
# exists in tracing.METRIC_FAMILIES with the right type — a spec
# naming a family the code no longer exports would evaluate against
# silence forever (scripts/slo_lint.py; merge-gate prerequisite)
slo-lint:
	$(PYTHON) scripts/slo_lint.py

# serving SLO plane (PR 20): render the budget/burn/canary verdict —
# hermetic demo here; point scripts/slo_report.py --url at a live
# fleet router for a real fleet (the burn-rate and canary e2es ride
# `make chaos`; `make bench` publishes the serving_fleet.slo leg)
slo-report:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/slo_report.py --demo

# goodput plane (PR 10): render the badput/straggler tables — hermetic
# demo here; point scripts/goodput_report.py --url at a live driver's
# stats port for a real job (the chaos goodput e2e rides `make chaos`
# via its chaos marker, and `make bench` publishes the goodput leg)
goodput-report:
	JAX_PLATFORMS=cpu $(PYTHON) scripts/goodput_report.py --demo

# per-suite wall clock cap via coreutils timeout (pytest-timeout is not a
# hard dependency); a wedged multi-process test fails CI instead of hanging
test: metrics-lint racecheck slo-lint
	timeout $(SUITE_TIMEOUT) $(PYTHON) -m pytest tests/ -q

# example-surface smokes (tests/test_examples.py) add ~12 min of
# subprocess training runs to the library suite (14 example drives as of
# round 5); 45 min keeps the cap meaningful with CI-box variance without
# killing real runs
SUITE_TIMEOUT ?= 2700

test-fast:
	$(PYTHON) -m pytest tests/ -q -x -m "not slow"

# Fault-injection suite (PR 3: chaos.py + the supervision plane e2e;
# PR 4 adds the serving leg — scheduler-kill auto-restart, decode
# stall, injected client disconnect from test_serving_lifecycle.py —
# and PR 6 the fleet leg from test_fleet.py: kill one replica of a
# 3-replica fleet mid-stream, zero client-visible failures, supervised
# restart + router readmit, MTTR recorded — all collected by the same
# `chaos` marker).
# These SIGKILL real trainer/executor processes and reform real
# clusters, so they run SERIALLY — one pytest process per test, which
# both isolates each kill's process tree and gives every test a hard
# per-test wall-clock cap via coreutils timeout (pytest-timeout is not
# a dependency). A wedged recovery fails in $(CHAOS_TEST_TIMEOUT)s
# instead of hanging the suite. The `chaos` marker is also `slow`, so
# tier-1 (`-m "not slow"`) never runs these under concurrent load —
# the regime in which they flaked.
CHAOS_TEST_TIMEOUT ?= 300
# The suite runs CHAOS_REPS times (PR 12): fault schedules are
# deterministic (fixed netchaos seeds in the specs, -p no:randomly for
# collection order), so a pass that only holds under one lucky timing
# interleaving fails here instead of on a user. CHAOS_REPS=1 for a
# quick local run.
CHAOS_REPS ?= 3
chaos:
	@set -e; \
	tests=$$($(PYTHON) -m pytest tests/ -q -m chaos --collect-only \
	  -p no:randomly 2>/dev/null | grep '::' || true); \
	test -n "$$tests" || { echo "no chaos tests collected"; exit 1; }; \
	for rep in $$(seq 1 $(CHAOS_REPS)); do \
	  echo "== chaos pass $$rep/$(CHAOS_REPS)"; \
	  for t in $$tests; do \
	    echo "== chaos: $$t"; \
	    timeout -k 30 $(CHAOS_TEST_TIMEOUT) \
	      $(PYTHON) -m pytest "$$t" -q -p no:randomly || exit 1; \
	  done; \
	done; \
	echo "chaos suite: all tests passed ($(CHAOS_REPS) passes)"

# one-line JSON benchmark; needs the chip (exits non-zero without one).
# The quickest proof that the system starts there is `python chip_smoke.py`
bench:
	$(PYTHON) bench.py

# CPU smoke of the full cluster-fed path (~4 min on one core): the one
# way to run bench.py without a chip is to pin JAX to the CPU yourself
smoke:
	JAX_PLATFORMS=cpu TFOS_TPU_DISTRIBUTED=0 \
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	$(PYTHON) bench.py

clean:
	rm -f tensorflowonspark_tpu/_lib*.so
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
