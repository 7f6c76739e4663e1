"""Test configuration: force an 8-device virtual CPU platform, and meter
XLA compilations per test against the checked-in retrace-budget manifest.

Tests run on CPU; all sharding tests run against a virtual 8-device CPU mesh
(SURVEY.md §7 step 8).  The platform is pinned through jax.config before the
backend initializes, so the suite is CPU-bound whatever JAX_PLATFORMS says.

Retrace budget (kcanalyze's runtime half, docs/ANALYSIS.md): a
``jax.monitoring`` listener counts every backend compile; the autouse
fixture fails any test whose compile count exceeds its budget in
``karpenter_core_tpu/analysis/retrace_budget.json`` (``tests`` entry, else
``default_budget``).  A test that suddenly compiles 3× more than its budget
is the symptom PR 3 chased for a day — a non-static argument or a
cache-key miss silently retracing per call.  Knobs:

  KC_RETRACE_BUDGET=0       disable enforcement (triage)
  KC_RETRACE_RECORD=path    append one JSON line per test with the actual
                            count (how the manifest is regenerated)
"""

import json
import os

# no speculative background compiles in tests: suites meter compile counts
# (test_compile_reuse and the retrace-budget fixture) and a stray warmup
# thread would race the meters
os.environ.setdefault("KC_TPU_WARMUP", "0")

# the sharded solve path would AUTO-enable on this 8-device virtual mesh
# (parallel.mesh.solve_mesh_axes: on when >1 device) and flip every kernel
# test onto mesh executables, perturbing the metered compile counts and the
# pinned single-device behaviors.  Pin it off by default — exactly like the
# warmup pin above — and let the dedicated mesh suites
# (tests/test_mesh_dispatch.py, tests/test_catalog_sharded.py) opt in per
# test via monkeypatch.  Production keeps the >1-device auto-default.
os.environ.setdefault("KC_SOLVER_MESH", "0")

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax
import jax.monitoring
import pytest

jax.config.update("jax_platforms", "cpu")

assert jax.default_backend() == "cpu", "tests must run on the virtual CPU platform"
assert len(jax.devices()) == 8, "expected 8 virtual CPU devices for mesh tests"

# -- retrace budget -----------------------------------------------------------

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compile_count = {"n": 0}


def _count_compiles(event: str, duration: float, **kwargs) -> None:
    if event == _COMPILE_EVENT:
        _compile_count["n"] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compiles)

from karpenter_core_tpu.analysis.manifest import load_retrace_manifest

_MANIFEST = load_retrace_manifest()


def compile_count() -> int:
    """Process-wide XLA backend-compile count (exposed for tests)."""
    return _compile_count["n"]


def budget_for(nodeid: str) -> int:
    return int(
        _MANIFEST.get("tests", {}).get(
            nodeid, _MANIFEST.get("default_budget", 64)
        )
    )


@pytest.fixture(autouse=True)
def _chaos_disarm():
    """A chaos scenario armed by a failing test must never leak into the
    next test — the plane is process-global."""
    yield
    from karpenter_core_tpu import chaos

    chaos.disarm()


@pytest.fixture(autouse=True)
def _retrace_budget(request):
    if os.environ.get("KC_RETRACE_BUDGET", "1") == "0":
        yield
        return
    before = _compile_count["n"]
    yield
    used = _compile_count["n"] - before
    record = os.environ.get("KC_RETRACE_RECORD")
    if record:
        with open(record, "a") as f:
            f.write(json.dumps({"test": request.node.nodeid, "compiles": used}) + "\n")
    budget = budget_for(request.node.nodeid)
    if used > budget:
        pytest.fail(
            f"retrace budget exceeded: {used} XLA compiles > budget {budget} "
            f"for {request.node.nodeid} (manifest: "
            "karpenter_core_tpu/analysis/retrace_budget.json).  A compile "
            "count jump means a jit argument stopped being static or a "
            "compile-cache key axis is churning — find the retrace before "
            "raising the budget (docs/ANALYSIS.md, docs/KERNEL_PERF.md).",
            pytrace=False,
        )


@pytest.fixture()
def traced():
    """Tracing on, store clean; restores the disabled default afterwards."""
    from karpenter_core_tpu import tracing

    capacity = tracing.TRACE_STORE.capacity
    tracing.TRACE_STORE.clear()
    tracing.enable()
    yield
    tracing.disable()
    tracing.TRACE_STORE.clear()
    tracing.TRACE_STORE.set_capacity(capacity)
