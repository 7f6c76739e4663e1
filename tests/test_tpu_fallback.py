"""TPU-default facade + graceful host fallback on backend failure.

The Operator facade defaults the device kernel ON (matching the binary's
KC_TPU_KERNEL default, cmd/operator.py) — VERDICT r2 weak #7.  When the
backend faults at solve time (device lost, init failure), batches must land on
the host scheduler with no pods lost, and repeated faults open the shared
solver-backend circuit breaker (utils/retry.CircuitBreaker): batches run
degraded on the host path without touching the backend until the breaker's
half-open trial re-proves the device path.
"""

import pytest

from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
from karpenter_core_tpu.controllers import provisioning as prov_mod
from karpenter_core_tpu.operator.operator import Operator
from karpenter_core_tpu.testing import make_pods, make_provisioner
from karpenter_core_tpu.testing.harness import expect_provisioned, make_environment
from karpenter_core_tpu.utils import retry


class TestTPUDefaultOn:
    def test_operator_facade_defaults_tpu_kernel_on(self):
        op = Operator(cloud_provider=FakeCloudProvider())
        assert op.use_tpu_kernel is True

    def test_operator_wires_kernel_flag_into_controllers(self):
        op = Operator(cloud_provider=FakeCloudProvider()).with_controllers()
        assert op.provisioning.use_tpu_kernel is True
        assert op.deprovisioning.multi_node_consolidation.use_tpu_kernel is True


class _ExplodingSolver:
    """Stands in for TPUSolver when the backend is unreachable: any
    construction attempt raises the way a dead backend surfaces (RuntimeError
    from the first device op)."""

    calls = 0

    def __init__(self, *a, **kw):
        type(self).calls += 1
        raise RuntimeError("Unable to initialize backend 'tpu': UNAVAILABLE")


class TestGracefulFallback:
    @pytest.fixture
    def env(self):
        env = make_environment()
        env.provisioning.use_tpu_kernel = True
        env.provisioning.tpu_kernel_min_pods = 2
        env.kube.create(make_provisioner())
        return env

    def test_backend_failure_falls_back_to_host(self, env, monkeypatch):
        import karpenter_core_tpu.solver.tpu as tpu_mod

        _ExplodingSolver.calls = 0
        monkeypatch.setattr(tpu_mod, "TPUSolver", _ExplodingSolver)
        pods = make_pods(4, requests={"cpu": "100m"})
        result = expect_provisioned(env, *pods)
        # every pod scheduled despite the dead backend
        assert all(result[p.uid] is not None for p in pods)
        assert _ExplodingSolver.calls == 1

    def test_repeated_backend_failures_open_the_breaker(self, env, monkeypatch):
        import karpenter_core_tpu.solver.tpu as tpu_mod

        _ExplodingSolver.calls = 0
        monkeypatch.setattr(tpu_mod, "TPUSolver", _ExplodingSolver)
        for _ in range(prov_mod.TPU_KERNEL_MAX_FAILURES + 2):
            pods = make_pods(3, requests={"cpu": "100m"})
            result = expect_provisioned(env, *pods)
            assert all(result[p.uid] is not None for p in pods)
        # breaker opened after MAX_FAILURES; while open (FakeClock frozen),
        # later batches run degraded and never touch the solver
        assert _ExplodingSolver.calls == prov_mod.TPU_KERNEL_MAX_FAILURES
        assert env.provisioning.solver_breaker.state == retry.OPEN
        assert env.provisioning.degraded() is True
        # the device path stays CONFIGURED — recovery is the breaker's job
        assert env.provisioning.use_tpu_kernel is True

    def test_breaker_half_open_trial_restores_the_kernel_path(self, env, monkeypatch):
        import karpenter_core_tpu.solver.tpu as tpu_mod

        _ExplodingSolver.calls = 0
        monkeypatch.setattr(tpu_mod, "TPUSolver", _ExplodingSolver)
        for _ in range(prov_mod.TPU_KERNEL_MAX_FAILURES):
            expect_provisioned(env, *make_pods(3, requests={"cpu": "100m"}))
        assert env.provisioning.solver_breaker.state == retry.OPEN

        # past the reset timeout the breaker half-opens; a healthy trial
        # batch (stubbed solve) closes it and restores the device path.
        # KC_WATCHDOG=0 keeps the LEGACY real-batch trial this test pins
        # (still live for the remote topology and the kill switch) — the
        # canary-gated re-admission ladder has its own coverage in
        # tests/test_watchdog.py
        monkeypatch.setenv("KC_WATCHDOG", "0")
        env.clock.step(prov_mod.SOLVER_BREAKER_RESET_S + 1)
        assert env.provisioning.solver_breaker.state == retry.HALF_OPEN

        from karpenter_core_tpu.solver.scheduler import SchedulingResults

        monkeypatch.setattr(
            env.provisioning, "_schedule_tpu",
            lambda pods, state_nodes: SchedulingResults(),
        )
        pods = make_pods(3, requests={"cpu": "100m"})
        expect_provisioned(env, *pods)
        assert env.provisioning.solver_breaker.state == retry.CLOSED
        assert env.provisioning.degraded() is False

    def test_half_open_unsupported_routing_does_not_close_the_breaker(self, env, monkeypatch):
        import karpenter_core_tpu.solver.tpu as tpu_mod

        _ExplodingSolver.calls = 0
        monkeypatch.setattr(tpu_mod, "TPUSolver", _ExplodingSolver)
        for _ in range(prov_mod.TPU_KERNEL_MAX_FAILURES):
            expect_provisioned(env, *make_pods(3, requests={"cpu": "100m"}))
        # legacy real-batch trial (see the note in the restore test above):
        # the canary ladder would otherwise probe the exploding solver first
        monkeypatch.setenv("KC_WATCHDOG", "0")
        env.clock.step(prov_mod.SOLVER_BREAKER_RESET_S + 1)
        assert env.provisioning.solver_breaker.state == retry.HALF_OPEN

        # the trial batch shape-routes to the host (None): that is a shape
        # verdict, not backend evidence — the breaker must stay half-open
        # with the trial slot freed, not flap closed
        monkeypatch.setattr(
            env.provisioning, "_schedule_tpu", lambda pods, state_nodes: None
        )
        pods = make_pods(3, requests={"cpu": "100m"})
        result = expect_provisioned(env, *pods)
        assert all(result[p.uid] is not None for p in pods)  # host solved it
        assert env.provisioning.solver_breaker.state == retry.HALF_OPEN
        assert env.provisioning.solver_breaker.allow()  # next batch can probe

    @pytest.mark.compile  # the restored real solver compiles -- slow tier
    def test_success_resets_failure_counter(self, env, monkeypatch):
        import karpenter_core_tpu.solver.tpu as tpu_mod

        real_solver = tpu_mod.TPUSolver
        _ExplodingSolver.calls = 0

        # one failure, then a real solve, then another failure: the counter
        # must reset in between so a single flake never accumulates to a trip
        monkeypatch.setattr(tpu_mod, "TPUSolver", _ExplodingSolver)
        expect_provisioned(env, *make_pods(3, requests={"cpu": "100m"}))
        assert env.provisioning._tpu_failures == 1

        monkeypatch.setattr(tpu_mod, "TPUSolver", real_solver)
        pods = make_pods(3, requests={"cpu": "100m"})
        result = expect_provisioned(env, *pods)
        assert all(result[p.uid] is not None for p in pods)
        assert env.provisioning._tpu_failures == 0
        assert env.provisioning.use_tpu_kernel is True

    def test_consolidation_backend_failure_falls_back(self, env, monkeypatch):
        import karpenter_core_tpu.solver.consolidation as cons_mod

        class ExplodingSearch:
            def __init__(self, *a, **kw):
                raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(cons_mod, "TPUConsolidationSearch", ExplodingSearch)
        mnc = env.deprovisioning.multi_node_consolidation
        mnc.use_tpu_kernel = True
        assert mnc._tpu_search([object(), object(), object()]) is None
