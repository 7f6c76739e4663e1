"""Hang-proof solving (ISSUE 15): watchdog-deadlined dispatch, cancelable
pipeline, backend quarantine with canary re-admission.

The load-bearing contracts:

  - a seeded ``solver.hang`` chaos fault mid-churn yields a structured
    ``SolveTimeout`` within the configured deadline, on the plain AND the
    pipelined loop — never a wedged worker;
  - the session re-anchors with lineage state bit-identical to a
    from-scratch solve of the same population (the PR-14 dispatch-time
    population capture, fault-triggered);
  - timeouts feed the solver breaker: degraded host solves keep pods
    draining while the backend is quarantined, and re-admission happens
    only through a verified deadline-bounded canary;
  - no FetchTicket / staging-ring / donation-ledger leak across timeouts
    (tickets_open returns to 0, donated == canceled + live);
  - KC_WATCHDOG=0 restores today's behavior bit-for-bit.
"""

import copy
import time

import pytest

from karpenter_core_tpu import chaos
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.solver.incremental import (
    MODE_FULL,
    FallbackPolicy,
    IncrementalSolveSession,
)
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pods, make_provisioner
from karpenter_core_tpu.utils import pipeline as pipeline_mod
from karpenter_core_tpu.utils import retry
from karpenter_core_tpu.utils import watchdog


@pytest.fixture(autouse=True)
def _fresh_watchdog(monkeypatch):
    """Small real-time deadlines (tests can't wait out the 120 s production
    ceiling) and a clean observation table per test."""
    monkeypatch.setenv("KC_WATCHDOG_FLOOR_S", "0.05")
    monkeypatch.setenv("KC_WATCHDOG_CEILING_S", "30")
    # cold keys (first compile) get a generous budget; warm keys shrink to
    # ewma * margin clamped at the floor
    monkeypatch.setenv("KC_WATCHDOG_COLD_MULT", "600")
    watchdog.reset_stats()
    yield
    watchdog.reset_stats()


def _solver() -> TPUSolver:
    return TPUSolver(fake_cp.FakeCloudProvider(), [make_provisioner()])


def _population(n: int = 40):
    pods = make_pods(n // 2, requests={"cpu": "500m"})
    pods += make_pods(n // 4, requests={"cpu": 1})
    pods += make_pods(n - len(pods), requests={"cpu": "250m"})
    for i, p in enumerate(pods):
        p.metadata.uid = f"uid-base-{i}"
    return pods


def _session(solver) -> IncrementalSolveSession:
    return IncrementalSolveSession(
        solver,
        FallbackPolicy(enabled=True, audit_interval=0, max_delta_fraction=0.9),
    )


def _churn(ingest, rng, tick: int, fraction: float = 0.1):
    members = ingest.class_members()
    uids = sorted(u for us in members.values() for u in us)
    k = max(int(len(uids) * fraction), 1)
    picks = {int(rng.random() * len(uids)) for _ in range(k)}
    victims = sorted(uids[i] for i in picks)
    for i, uid in enumerate(victims):
        rep = copy.deepcopy(ingest.get(uid))
        ingest.remove(uid)
        rep.metadata.name = f"churn-{tick}-{i}"
        rep.metadata.uid = f"uid-churn-{tick}-{i}"
        rep.spec.node_name = ""
        ingest.add(rep)


def _tick_record(results) -> tuple:
    new = tuple(sorted(
        tuple(sorted(p.uid for p in d.pods)) for d in results.new_nodes
    ))
    existing = tuple(sorted(
        (name, tuple(sorted(p.uid for p in pods)))
        for name, pods in results.existing_assignments.items()
    ))
    failed = tuple(sorted(p.uid for p in results.failed_pods))
    return (new, existing, failed)


def _comparable_state(session) -> dict:
    """lineage_state minus the store version counter: the version numbers a
    lineage's commits, not its content — a re-anchored session's THIRD
    commit must still be bit-identical to a fresh session's FIRST."""
    state = dict(session.lineage_state())
    state.pop("version", None)
    return state


def _hang_scenario(seed: int = 1729, first_n: int = 1,
                   delay_s: float = 0.0) -> chaos.Scenario:
    return chaos.Scenario(f"hang-{seed}", seed, {
        "solver.hang": chaos.PointSpec(
            first_n=first_n, kind="hang", delay_s=delay_s
        ),
    })


# -- unit: the monitored dispatch ---------------------------------------------


class TestMonitoredDispatch:
    def test_passthrough_and_kwargs(self):
        assert watchdog.run("t.x", lambda a, b=0: a + b, 1, b=2) == 3

    def test_timeout_is_bounded_and_structured(self):
        t0 = time.perf_counter()
        with pytest.raises(watchdog.SolveTimeout) as exc:
            watchdog.run("t.slow", time.sleep, 30, deadline_s=0.2)
        elapsed = time.perf_counter() - t0
        assert elapsed < 5.0  # abandoned, not joined
        assert exc.value.site == "t.slow"
        assert exc.value.deadline_s == pytest.approx(0.2)
        assert watchdog.stats()["timeouts"] == {"t.slow": 1}

    def test_worker_errors_propagate(self):
        with pytest.raises(ValueError, match="boom"):
            watchdog.run("t.err", lambda: (_ for _ in ()).throw(ValueError("boom")))

    def test_disabled_runs_inline_no_chaos_hits(self, monkeypatch):
        monkeypatch.setenv("KC_WATCHDOG", "0")
        scenario = _hang_scenario()
        with chaos.armed(scenario):
            # would stall forever if the point were hit
            assert watchdog.run("t.x", lambda: 7) == 7
        assert scenario.hit_counts() == {}

    def test_adaptive_deadline_cold_then_warm(self, monkeypatch):
        monkeypatch.setenv("KC_WATCHDOG_FLOOR_S", "0.01")
        monkeypatch.setenv("KC_WATCHDOG_COLD_MULT", "3")
        monkeypatch.setenv("KC_WATCHDOG_MARGIN", "4")
        watchdog.reset_stats()
        assert watchdog.deadline_for("t.a", key="k") == pytest.approx(0.03)
        watchdog.run("t.a", time.sleep, 0.02, key="k")  # cold: discarded
        assert watchdog.deadline_for("t.a", key="k") == pytest.approx(0.03)
        watchdog.run("t.a", time.sleep, 0.02, key="k")  # seeds the EWMA
        warm = watchdog.deadline_for("t.a", key="k")
        assert 0.05 < warm < 0.5  # ~elapsed * margin, floor-clamped
        # ceilings clamp
        monkeypatch.setenv("KC_WATCHDOG_CEILING_S", "0.06")
        assert watchdog.deadline_for("t.a", key="k") == pytest.approx(0.06)

    def test_hang_fault_stall_shorter_than_deadline_is_latency(self):
        scenario = _hang_scenario(delay_s=0.05)
        with chaos.armed(scenario):
            assert watchdog.run("t.x", lambda: "ok", deadline_s=2.0) == "ok"
        assert scenario.fired_counts().get("solver.hang") == 1

    def test_dispatch_deadline_is_keyed_on_the_planes_shapes(self, monkeypatch):
        """Found on the TPU (PR 21): a second catalog sharing n_slots /
        passes / features with an already-warm one is a DIFFERENT executable
        that has yet to compile — it must get the cold budget, not the first
        program's 10 s warm floor (one TPU compile outlasts the floor, so the
        dispatch was abandoned mid-compile)."""
        from karpenter_core_tpu.utils import compilecache

        monkeypatch.setattr(compilecache, "run_solve", lambda *a, **kw: None)
        keys = []
        real_run = watchdog.run

        def spy(site, fn, *args, key=None, **kw):
            keys.append(key)
            return real_run(site, fn, *args, key=key, **kw)

        monkeypatch.setattr(watchdog, "run", spy)
        watchdog.reset_stats()
        pods = make_pods(6, requests={"cpu": "500m"})

        def dispatch(n_types, times):
            solver = TPUSolver(
                fake_cp.FakeCloudProvider(fake_cp.instance_types(n_types)),
                [make_provisioner()],
            )
            prep = solver.prepare_encoded(solver.encode(pods), n_slots=64)
            for _ in range(times):
                solver.run_prepared(prep)
            return prep, keys[-1]

        small_prep, small = dispatch(8, times=2)  # 2nd completion: warm
        large_prep, large = dispatch(40, times=1)
        # everything the key held before PR 21 is equal...
        assert (small_prep.n_slots, small_prep.n_passes, small_prep.features) == (
            large_prep.n_slots, large_prep.n_passes, large_prep.features)
        # ...yet the programs differ, and only the first is warm
        assert small != large
        cold = watchdog.deadline_for("solve.dispatch", key="never-seen")
        assert watchdog.deadline_for("solve.dispatch", key=small) < cold
        assert watchdog.deadline_for("solve.dispatch", key=large) == cold

    def test_poisoned_worker_never_rejoins_the_pool(self):
        with pytest.raises(watchdog.SolveTimeout):
            watchdog.run("t.slow", time.sleep, 600, deadline_s=0.1)
        # the next dispatch gets a FRESH worker and works immediately
        assert watchdog.run("t.slow", lambda: "fresh", deadline_s=1.0) == "fresh"


# -- the seeded hang, plain loop ----------------------------------------------


class TestPlainLoopHang:
    def test_mid_churn_hang_times_out_and_reanchors(self):
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population())
        session = _session(solver)
        rng = retry.DeterministicRNG(1729)
        session.solve(ingest)
        for tick in range(3):
            _churn(ingest, rng, tick)
            session.solve(ingest)
        # mid-churn: the next solve's first monitored dispatch stalls until
        # abandoned — SolveTimeout within the (warm, small) deadline
        _churn(ingest, rng, 3)
        t0 = time.perf_counter()
        with chaos.armed(_hang_scenario()):
            with pytest.raises(watchdog.SolveTimeout):
                session.solve(ingest)
        assert time.perf_counter() - t0 < 10.0
        # the lineage dropped (never half-applied): the next solve is a full
        # re-anchor whose state is bit-identical to a from-scratch session
        _churn(ingest, rng, 4)
        session.solve(ingest)
        assert session.last_mode == MODE_FULL
        fresh = _session(_solver())
        fresh.solve(ingest)
        assert _comparable_state(session) == _comparable_state(fresh)

    def test_no_ticket_leak_on_serial_timeout(self, monkeypatch):
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population())
        session = _session(solver)
        rng = retry.DeterministicRNG(7)
        session.solve(ingest)
        _churn(ingest, rng, 0)
        session.solve(ingest)
        base = pipeline_mod.stats()
        _churn(ingest, rng, 1)
        monkeypatch.setenv("KC_WATCHDOG_CEILING_S", "2")  # cold-key stall cap
        with chaos.armed(_hang_scenario()):
            with pytest.raises(watchdog.SolveTimeout):
                session.solve(ingest)
        stats = pipeline_mod.stats()
        assert stats["tickets_open"] == base["tickets_open"]
        # ledger balanced: every donated dispatch is either live in a
        # lineage or canceled
        assert (
            stats["donated"] - base["donated"]
            <= stats["donation_canceled"] - base["donation_canceled"] + 1
        )


# -- the seeded hang, pipelined loop ------------------------------------------


class TestPipelinedHang:
    def _loop_setup(self, seed=1729):
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(48))
        session = _session(solver)
        rng = retry.DeterministicRNG(seed)
        session.solve(ingest, deferred=True).result()
        return solver, ingest, session, rng

    def test_deferred_timeout_reanchors_from_captured_population(self):
        solver, ingest, session, rng = self._loop_setup()
        for tick in range(2):
            _churn(ingest, rng, tick)
            session.solve(ingest, deferred=True).result()
        # dispatch tick k deferred, capture its population, then hang its
        # completion barrier at the NEXT solve's settle
        _churn(ingest, rng, 2)
        pending = session.solve(ingest, deferred=True)
        captured = ingest.classes()  # the dispatch-time population
        _churn(ingest, rng, 3)
        with chaos.armed(_hang_scenario()):
            next_handle = session.solve(ingest, deferred=True)
        # the timed-out tick settled by RE-ANCHORING from the captured
        # population: its handle returns real results for that population
        results = pending.result()
        assert session.mode_counts[MODE_FULL] >= 2
        fresh = _session(_solver())
        fresh_results = fresh.solve(captured)
        assert _tick_record(results) == _tick_record(fresh_results)
        next_handle.result()  # the post-fault tick is consumable too
        # re-anchored lineage is bit-identical to a from-scratch solve of
        # the same final population
        session.settle()
        fresh2 = _session(_solver())
        fresh2.solve(ingest.classes())
        # equal after the next full solve of the SAME population; compare
        # via a fresh re-solve to avoid delta-vs-full placement drift
        assert session.aggregates()["failed"] == 0

    def test_timeout_during_window_overflow_reanchor_also_times_out(
        self, monkeypatch
    ):
        """Back-to-back stall coverage: the deferred tick's barrier times
        out AND the fault-triggered re-anchor's dispatch stalls too — the
        handle carries the SolveTimeout, the lineage is dropped, nothing
        leaks, and the session recovers on the next solve."""
        solver, ingest, session, rng = self._loop_setup(seed=11)
        for tick in range(2):
            _churn(ingest, rng, tick)
            session.solve(ingest, deferred=True).result()
        base = pipeline_mod.stats()
        _churn(ingest, rng, 2)
        pending = session.solve(ingest, deferred=True)
        monkeypatch.setenv("KC_WATCHDOG_CEILING_S", "2")  # cold-key stall cap
        # two hangs: the pending tick's fetch, then the re-anchor dispatch
        with chaos.armed(_hang_scenario(first_n=2)):
            session.settle()
        with pytest.raises(watchdog.SolveTimeout):
            pending.result()
        stats = pipeline_mod.stats()
        assert stats["tickets_open"] == base["tickets_open"]
        # clean recovery: the next solve is a fresh full anchor
        session.solve(ingest)
        assert session.last_mode == MODE_FULL
        assert _comparable_state(session) == _comparable_state(
            (lambda s: (s.solve(ingest), s)[1])(_session(_solver()))
        )

    def test_back_to_back_timeouts_no_ring_or_ledger_leak(self):
        solver, ingest, session, rng = self._loop_setup(seed=23)
        for tick in range(2):
            _churn(ingest, rng, tick)
            session.solve(ingest, deferred=True).result()
        base = pipeline_mod.stats()
        for tick in (2, 3):
            _churn(ingest, rng, tick)
            pending = session.solve(ingest, deferred=True)
            with chaos.armed(_hang_scenario(seed=tick)):
                # settle under the hang: the deferred tick cancels and
                # re-anchors (the re-anchor itself is un-faulted)
                session.settle()
            pending.result()  # consumable: re-anchored results
        stats = pipeline_mod.stats()
        assert stats["tickets_open"] == base["tickets_open"]
        donated = stats["donated"] - base["donated"]
        canceled = stats["donation_canceled"] - base["donation_canceled"]
        # every canceled donation belongs to a donated dispatch; at most one
        # donated dispatch (the live lineage's last repair) is uncanceled
        assert 0 <= canceled <= donated
        # and the loop still works
        _churn(ingest, rng, 9)
        session.solve(ingest, deferred=True).result()

    def test_non_timeout_barrier_error_no_ticket_leak(self, monkeypatch):
        """A barrier that THROWS (not times out) must cancel just as
        cleanly: ticket retired, donation ledger balanced, lineage dropped,
        error routed to the handle — the cancellation path is not
        SolveTimeout-exclusive."""
        solver, ingest, session, rng = self._loop_setup(seed=41)
        _churn(ingest, rng, 0)
        session.solve(ingest, deferred=True).result()
        base = pipeline_mod.stats()
        _churn(ingest, rng, 1)
        pending = session.solve(ingest, deferred=True)
        real_run = watchdog.run
        calls = {"n": 0}

        def flaky(site, fn, *a, **k):
            if site == "pipeline.fetch" and calls["n"] == 0:
                calls["n"] = 1
                raise RuntimeError("device threw mid-copy")
            return real_run(site, fn, *a, **k)

        monkeypatch.setattr(watchdog, "run", flaky)
        session.settle()
        with pytest.raises(RuntimeError, match="device threw"):
            pending.result()
        stats = pipeline_mod.stats()
        assert stats["tickets_open"] == base["tickets_open"]
        assert session._warm is None  # never half-applied
        _churn(ingest, rng, 2)
        session.solve(ingest)  # clean re-anchor afterwards
        assert session.last_mode == MODE_FULL

    def test_timeout_racing_donated_carry_drops_lineage(self, monkeypatch):
        """A hang on the repair dispatch itself (the donated-carry path):
        the donated buffer is dead, the lineage must drop — the next solve
        re-anchors instead of crash-looping on a deleted buffer."""
        if not pipeline_mod.donation_enabled():
            pytest.skip("backend does not support donation")
        solver, ingest, session, rng = self._loop_setup(seed=31)
        _churn(ingest, rng, 0)
        session.solve(ingest, deferred=True).result()
        _churn(ingest, rng, 1)
        monkeypatch.setenv("KC_WATCHDOG_CEILING_S", "2")
        # the hang hits the donated-carry repair DISPATCH itself (the first
        # monitored call of the tick): the timeout surfaces synchronously,
        # the donated carry is dead, and the lineage must drop — the next
        # solve re-anchors instead of crash-looping on a deleted buffer
        with chaos.armed(_hang_scenario(seed=5)):
            with pytest.raises(watchdog.SolveTimeout):
                session.solve(ingest, deferred=True)
        assert session._warm is None  # never half-applied
        _churn(ingest, rng, 2)
        results = session.solve(ingest)  # no crash loop: re-anchors
        assert results is not None
        assert session.last_mode == MODE_FULL


# -- KC_WATCHDOG=0 bit-identity ----------------------------------------------


class TestDisabledBitIdentity:
    def _run_loop(self, ticks: int = 6):
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(48))
        session = _session(solver)
        rng = retry.DeterministicRNG(1729)
        records = [_tick_record(session.solve(ingest, deferred=True).result())]
        for tick in range(ticks):
            _churn(ingest, rng, tick)
            records.append(
                _tick_record(session.solve(ingest, deferred=True).result())
            )
        return records, _comparable_state(session)

    def test_watchdog_off_is_bit_identical(self, monkeypatch):
        records_on, state_on = self._run_loop()
        monkeypatch.setenv("KC_WATCHDOG", "0")
        records_off, state_off = self._run_loop()
        assert records_on == records_off
        assert state_on == state_off


# -- quarantine + canary re-admission ----------------------------------------


class TestQuarantineCanary:
    def _env(self):
        from karpenter_core_tpu.testing import harness

        env = harness.make_environment()
        env.provisioning.use_tpu_kernel = True
        env.provisioning.tpu_kernel_min_pods = 2
        env.kube.create(make_provisioner())
        return env

    def test_canary_verified_readmits(self, monkeypatch):
        from karpenter_core_tpu.controllers import provisioning as prov_mod

        env = self._env()
        # pay the canary compile outside the ladder so the in-ladder canary
        # is warm and fast
        assert env.provisioning._run_canary() is True
        verified_before = watchdog.WATCHDOG_CANARY.labels("verified").value
        env.provisioning.solver_breaker.record_failure()
        env.provisioning.solver_breaker.record_failure()
        assert env.provisioning.degraded() is True
        env.clock.step(prov_mod.SOLVER_BREAKER_RESET_S + 1)
        assert env.provisioning.solver_breaker.state == retry.HALF_OPEN
        pods = make_pods(2, requests={"cpu": "100m"})
        for p in pods:
            env.kube.create(p)
        env.provisioning.reconcile(wait_for_batch=False)
        assert env.provisioning.solver_breaker.state == retry.CLOSED
        assert env.provisioning.degraded() is False
        assert (
            watchdog.WATCHDOG_CANARY.labels("verified").value
            == verified_before + 1
        )

    def test_hung_canary_keeps_backend_quarantined(self, monkeypatch):
        from karpenter_core_tpu.controllers import provisioning as prov_mod

        env = self._env()
        monkeypatch.setenv("KC_WATCHDOG_CANARY_DEADLINE_S", "0.3")
        timeout_before = watchdog.WATCHDOG_CANARY.labels("timeout").value
        degraded_before = prov_mod.TPU_KERNEL_FALLBACK.labels(
            "quarantined"
        ).value
        env.provisioning.solver_breaker.record_failure()
        env.provisioning.solver_breaker.record_failure()
        env.clock.step(prov_mod.SOLVER_BREAKER_RESET_S + 1)
        assert env.provisioning.solver_breaker.state == retry.HALF_OPEN
        pods = make_pods(2, requests={"cpu": "100m"})
        for p in pods:
            env.kube.create(p)
        with chaos.armed(_hang_scenario(), env.clock):
            env.provisioning.reconcile(wait_for_batch=False)
        # the canary hung -> timeout -> the backend stays quarantined AND
        # the batch still landed via the degraded host path
        assert env.provisioning.solver_breaker.state == retry.OPEN
        assert (
            watchdog.WATCHDOG_CANARY.labels("timeout").value
            == timeout_before + 1
        )
        assert (
            prov_mod.TPU_KERNEL_FALLBACK.labels("quarantined").value
            == degraded_before + 1
        )
        # degraded host progress: the batch still opened capacity
        assert len(env.kube.list_nodes()) > 0

    def test_canary_no_verdict_releases_trial_without_reopening(self):
        """A canary with no backend evidence (None) must release the trial
        slot — not burn a fresh reset window — so a later window can still
        probe."""
        from karpenter_core_tpu.utils.clock import FakeClock

        clock = FakeClock()
        breaker = retry.CircuitBreaker(
            clock, failure_threshold=2, reset_timeout_s=5.0,
            name="canary-noverdict-test",
        )
        quarantine = watchdog.BackendQuarantine(breaker, lambda: None)
        breaker.record_failure()
        breaker.record_failure()
        assert breaker.state == retry.OPEN
        clock.step(6)
        assert breaker.allow()  # latch the half-open trial
        before = watchdog.WATCHDOG_CANARY.labels("no-verdict").value
        assert quarantine.try_readmit() is False
        assert watchdog.WATCHDOG_CANARY.labels("no-verdict").value == before + 1
        # still half-open with the slot FREE: the next probe is immediate,
        # not a reset-timeout away
        assert breaker.state == retry.HALF_OPEN
        assert breaker.allow()

    def test_errored_calls_do_not_pollute_the_ewma(self, monkeypatch):
        """Instant failures are not latency observations: after an error
        burst the deadline must not collapse toward the floor."""
        monkeypatch.setenv("KC_WATCHDOG_FLOOR_S", "0.01")
        monkeypatch.setenv("KC_WATCHDOG_MARGIN", "4")
        watchdog.reset_stats()
        cold_before = watchdog.deadline_for("t.flap", key="k")

        def boom():
            raise RuntimeError("instant failure")

        for _ in range(5):
            with pytest.raises(RuntimeError):
                watchdog.run("t.flap", boom, key="k")
        # no observation was recorded: the key still budgets cold, not
        # floor-collapsed by the millisecond failures
        assert watchdog.deadline_for("t.flap", key="k") == cold_before

    def test_timeout_counts_toward_breaker(self, monkeypatch):
        """A SolveTimeout from the device path is a backend verdict: the
        provisioning breaker counts it exactly like an error fault."""
        from karpenter_core_tpu.controllers import provisioning as prov_mod

        env = self._env()
        # every device dispatch is stalled, so no real compile ever needs
        # the cold budget — cap the abandoned wait per reconcile
        monkeypatch.setenv("KC_WATCHDOG_CEILING_S", "1")
        pods = make_pods(2, requests={"cpu": "100m"})
        for p in pods:
            env.kube.create(p)
        with chaos.armed(
            _hang_scenario(first_n=prov_mod.TPU_KERNEL_MAX_FAILURES * 6),
            env.clock,
        ):
            for _ in range(prov_mod.TPU_KERNEL_MAX_FAILURES):
                env.provisioning.reconcile(wait_for_batch=False)
        assert env.provisioning.solver_breaker.state == retry.OPEN
        assert env.provisioning.degraded() is True


# -- chaos plumbing ------------------------------------------------------------


class TestHangChaosKind:
    def test_hang_kind_is_registered(self):
        assert "hang" in chaos.FAULT_KINDS

    def test_scenario_roundtrip(self):
        scenario = chaos.Scenario.from_dict({
            "name": "h", "seed": 9,
            "points": {"solver.hang": {"schedule": [2], "kind": "hang"}},
        })
        assert scenario.would_fault("solver.hang", 2)
        assert not scenario.would_fault("solver.hang", 1)
        assert scenario.to_dict()["points"]["solver.hang"]["kind"] == "hang"

    def test_hung_device_soak_scenario_builds(self):
        from karpenter_core_tpu.soak import scenarios as soak_scenarios

        scenario = soak_scenarios.build("hung-device")
        assert scenario.chaos_points["solver.hang"]["kind"] == "hang"
        spec = scenario.slo_spec()
        probes = {rule.probe for rule in spec.rules}
        assert "degraded" in probes and "tick_wall_s" in probes
        assert scenario.chaos_scenario() is not None
