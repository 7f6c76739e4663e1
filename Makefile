# Build/CI entry points (role of the reference's Makefile:8-24)

PYTEST ?= python -m pytest

presubmit: verify test kernel-smoke  ## everything a PR needs to pass

verify: chaos soak  ## static checks + the chaos and soak gates: bytecode-compile, kcanalyze (all analysis passes, baseline-aware), build the native library
	python -m compileall -q karpenter_core_tpu tests chip_smoke.py __graft_entry__.py
	python tools/kcanalyze.py --strict
	$(MAKE) -C native

chaos:  ## tier-1 chaos subset with a fixed seed: seeded fault scenarios must converge leak-free (docs/CHAOS.md)
	KC_CHAOS_SEED=1729 $(PYTEST) tests/test_chaos_matrix.py tests/test_retry.py -q -m "not slow"

soak:  ## tier-1 soak smoke with a fixed seed: one deterministic trace-driven scenario must meet its SLO spec and replay byte-identically (docs/SOAK.md), the multi-tenant service soak (docs/SERVICE.md), plus the multi-process fleet-failover soak (docs/FLEET.md)
	KC_SOAK_SEED=1729 $(PYTEST) tests/test_soak.py tests/test_tenant_soak.py tests/test_fleet_soak.py -q -m "not slow"

test:  ## fast behavioral tier (virtual 8-device CPU mesh, ~2 min)
	$(PYTEST) tests/ -x -q -m "not compile and not slow"

test-all:  ## everything incl. the compile-heavy kernel/parity tier (~25 min)
	$(PYTEST) tests/ -x -q

kernel-smoke:  ## bounded kernel gate for presubmit: a parity slice compiles + solves (~1 min)
	$(PYTEST) tests/test_tpu_solver.py -x -q -k "homogeneous or two_sizes or pod_count_limit"

chip-smoke:  ## the served solve path end to end on the local TPU, chip-or-fail (refuses to run where JAX finds no TPU)
	python chip_smoke.py

graft-check:  ## driver contract: compile check + multi-chip dry run
	python __graft_entry__.py

.PHONY: presubmit verify chaos soak test test-all kernel-smoke chip-smoke graft-check
