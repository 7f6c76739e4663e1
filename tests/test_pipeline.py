"""Pipelined solve loop (ISSUE 14): dispatch/fetch split, double-buffered
deferred ticks, carry donation, and the serial-path batched fetch.

The load-bearing contract is BIT-IDENTITY: the pipelined loop reorders only
WHEN work happens (fetch under the next dispatch, decode under the next
device compute), never WHAT is computed — so a churn fuzz driven through
``solve(deferred=True)`` must produce, tick for tick, exactly the placements
and store digests the serial loop produces, on the plain path and on the
mesh.  KC_PIPELINE=0 must restore the serial loop outright, and a
solver.dispatch chaos fault mid-pipeline must surface exactly like the
serial fault — synchronously from solve(), with no wedged ring slot and
every already-dispatched handle still consumable.
"""

import copy

import numpy as np
import pytest

from karpenter_core_tpu import chaos, tracing
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.solver.incremental import (
    MODE_DELTA,
    MODE_FULL,
    FallbackPolicy,
    IncrementalSolveSession,
    PendingResults,
)
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pods, make_provisioner
from karpenter_core_tpu.utils import pipeline as pipeline_mod
from karpenter_core_tpu.utils import retry


def _solver() -> TPUSolver:
    return TPUSolver(fake_cp.FakeCloudProvider(), [make_provisioner()])


def _population(n: int = 40):
    pods = make_pods(n // 2, requests={"cpu": "500m"})
    pods += make_pods(n // 4, requests={"cpu": 1})
    pods += make_pods(n - len(pods), requests={"cpu": "250m"})
    for i, p in enumerate(pods):
        # deterministic uids: two legs running the same tick sequence build
        # bit-comparable memberships, supply digests, and tick records
        p.metadata.uid = f"uid-base-{i}"
    return pods


def _session(solver, max_delta_fraction=0.9) -> IncrementalSolveSession:
    return IncrementalSolveSession(
        solver,
        FallbackPolicy(enabled=True, audit_interval=0,
                       max_delta_fraction=max_delta_fraction),
    )


def _churn(ingest, rng, tick: int, fraction: float = 0.1):
    """Deterministic replace-churn with DETERMINISTIC uids, so two legs
    running the same tick sequence build bit-identical memberships (and
    therefore comparable lineage_state digests, supply included)."""
    members = ingest.class_members()
    uids = sorted(
        (u for us in members.values() for u in us)
    )
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
    """A canonical, uid-level record of ONE tick's returned placements."""
    new = tuple(sorted(
        tuple(sorted(p.uid for p in d.pods)) for d in results.new_nodes
    ))
    existing = tuple(sorted(
        (name, tuple(sorted(p.uid for p in pods)))
        for name, pods in results.existing_assignments.items()
    ))
    failed = tuple(sorted(p.uid for p in results.failed_pods))
    return (new, existing, failed)


def _run_loop(pipelined: bool, ticks: int = 20, n: int = 48,
              fraction: float = 0.1, consume_late: bool = True):
    """One churn-fuzz leg.  Returns (per-tick records, final lineage_state,
    mode counts).  The pipelined leg consumes tick k's handle AFTER tick
    k+1's dispatch — the canonical double-buffer ordering."""
    solver = _solver()
    ingest = PodIngest()
    ingest.add_all(_population(n))
    session = _session(solver)
    rng = retry.DeterministicRNG(1729)
    records = []
    handle = session.solve(ingest, deferred=pipelined)
    if pipelined:
        records.append(_tick_record(handle.result()))
    else:
        records.append(_tick_record(handle))
    pending = None
    for tick in range(ticks):
        _churn(ingest, rng, tick, fraction)
        if pipelined:
            h = session.solve(ingest, deferred=True)
            if pending is not None:
                records.append(_tick_record(pending.result()))
            pending = h
            if not consume_late:
                records.append(_tick_record(pending.result()))
                pending = None
        else:
            records.append(_tick_record(session.solve(ingest)))
    if pending is not None:
        records.append(_tick_record(pending.result()))
    state = session.lineage_state()
    return records, state, dict(session.mode_counts)


class TestPipelineParity:
    def test_churn_fuzz_bit_identical(self):
        """20-tick churn fuzz: the pipelined loop's per-tick results (uid
        for uid), final store digests, placement signature, and mode counts
        are exactly the serial loop's."""
        serial = _run_loop(False)
        pipelined = _run_loop(True)
        assert pipelined[0] == serial[0]  # every tick's placements
        assert pipelined[1] == serial[1]  # plane digests + signature + supply
        assert pipelined[2] == serial[2]  # same full/delta decisions
        assert pipelined[2][MODE_DELTA] >= 15  # the fuzz exercised repairs

    def test_mesh_leg_bit_identical(self, monkeypatch):
        """The same fuzz on the 8-device mesh (sharded dispatch + sharded
        donation): pipelined == serial, and both match the plain path's
        store digests."""
        plain = _run_loop(False, ticks=6)
        monkeypatch.setenv("KC_SOLVER_MESH", "1")
        serial = _run_loop(False, ticks=6)
        pipelined = _run_loop(True, ticks=6)
        assert pipelined[0] == serial[0]
        assert pipelined[1] == serial[1]
        assert pipelined[2] == serial[2]
        # mesh vs plain: identical placements tick for tick (Layer 5's
        # guarantee, preserved by the pipelined dispatch; plane DIGESTS
        # legitimately differ — the mesh encode pads the catalog axis
        # shard-aligned)
        assert pipelined[0] == plain[0]
        assert pipelined[1]["signature"] == plain[1]["signature"]

    def test_kc_pipeline_off_settles_inline(self, monkeypatch):
        """KC_PIPELINE=0: deferred calls return already-settled handles (the
        serial loop bit-for-bit), donation and staging disarm."""
        monkeypatch.setenv("KC_PIPELINE", "0")
        assert not pipeline_mod.pipeline_enabled()
        assert not pipeline_mod.donation_enabled()
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(24))
        session = _session(solver)
        handle = session.solve(ingest, deferred=True)
        assert isinstance(handle, PendingResults)
        assert handle.done()  # settled inline — nothing pending
        assert session._pending is None
        assert session._staging is None
        ingest.add_all(make_pods(2, requests={"cpu": "500m"}))
        handle = session.solve(ingest, deferred=True)
        assert handle.done()
        assert session.last_mode == MODE_DELTA

    def test_exhaustion_escalates_identically(self, monkeypatch):
        """A growth burst that overflows the bounded repair window: the
        deferred tick discovers exhaustion at settle and re-anchors from the
        CAPTURED population — same reason, same placements, same digests as
        the serial escalation, even though the caller's ingest has already
        moved on by settle time."""
        monkeypatch.setenv("KC_DELTA_WINDOW", "4")

        def leg(pipelined: bool):
            solver = _solver()
            ingest = PodIngest()
            base = make_pods(200, requests={"cpu": "500m"})
            for i, p in enumerate(base):
                p.metadata.uid = f"uid-b-{i}"
            ingest.add_all(base)
            session = _session(solver)
            session.solve(ingest, deferred=pipelined)
            # a burst of known-shape pods far larger than the bounded
            # window's fresh tail (KC_DELTA_WINDOW=4 caps it at 8 slots)
            burst = make_pods(80, requests={"cpu": "500m"})
            for i, p in enumerate(burst):
                p.metadata.uid = f"uid-burst-{i}"
            ingest.add_all(burst)
            h = session.solve(ingest, deferred=pipelined)
            if pipelined:
                # the caller moves on before consuming — the escalation must
                # still re-anchor from the captured tick population
                _churn(ingest, retry.DeterministicRNG(3), tick=99,
                       fraction=0.05)
                record = _tick_record(h.result())
            else:
                record = _tick_record(h)
            return record, session.last_reason, session.lineage_state()

        serial_rec, serial_reason, _ = leg(False)
        pipe_rec, pipe_reason, _ = leg(True)
        assert serial_reason == "slots-exhausted"
        assert pipe_reason == "slots-exhausted"
        assert pipe_rec == serial_rec

    def test_mixed_deferred_then_serial_keeps_handle_intact(self):
        """A deferred tick followed by SERIAL ticks: the unconsumed handle's
        decode must still see ITS tick's staged arrays — the settle at every
        solve entry flushes the undecoded handle before any later tick can
        rewrite its staging-ring slot (depth-2 ring, two serial ticks would
        land exactly on it)."""
        def leg(mixed: bool):
            solver = _solver()
            ingest = PodIngest()
            ingest.add_all(_population(32))
            session = _session(solver)
            session.solve(ingest)
            rng = retry.DeterministicRNG(17)
            _churn(ingest, rng, 0)
            if mixed:
                h = session.solve(ingest, deferred=True)  # tick 0 in flight
            else:
                record0 = _tick_record(session.solve(ingest))
            _churn(ingest, rng, 1)
            session.solve(ingest)  # serial: stages into the shared ring
            _churn(ingest, rng, 2)
            session.solve(ingest)  # serial: would rewrite tick 0's slot
            if mixed:
                record0 = _tick_record(h.result())
            return record0

        assert leg(True) == leg(False)

    def test_decode_failure_is_cached_on_the_handle(self, monkeypatch):
        """A deferred decode that fails must fail EVERY result() call — not
        raise once and silently return None afterwards."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(24))
        session = _session(solver)
        session.solve(ingest)
        _churn(ingest, retry.DeterministicRNG(19), 0)
        h = session.solve(ingest, deferred=True)
        session.settle()  # adopt; decode stays deferred on the handle
        monkeypatch.setattr(
            type(solver), "decode",
            lambda self, *a, **k: (_ for _ in ()).throw(ValueError("boom")),
        )
        with pytest.raises(ValueError):
            h.result()
        with pytest.raises(ValueError):
            h.result()  # cached, not swallowed into a silent None

    def test_late_consume_after_next_dispatch(self):
        """Launch-path reads (requests / offering lists) of tick k's results
        stay valid after tick k+1 dispatched with a donated carry — the
        lazy planes took owned copies at dispatch time."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(32))
        session = _session(solver)
        session.solve(ingest, deferred=True).result()
        rng = retry.DeterministicRNG(7)
        _churn(ingest, rng, 0)
        h0 = session.solve(ingest, deferred=True)
        _churn(ingest, rng, 1)
        h1 = session.solve(ingest, deferred=True)  # settles + may donate h0's carry
        r0 = h0.result()
        for d in r0.new_nodes:
            assert d.instance_type_names
            assert d.requests  # reads the `used` plane — owned copy
        h1.result()


class TestPipelineChaos:
    def test_dispatch_fault_mid_pipeline_drains_cleanly(self):
        """solver.dispatch chaos while a deferred tick is in flight: the
        fault surfaces synchronously from solve() (exactly the serial
        breaker's signal), the in-flight handle still resolves, no ring slot
        wedges, and the next solve repairs on the intact lineage."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(32))
        session = _session(solver)
        session.solve(ingest, deferred=True).result()
        rng = retry.DeterministicRNG(11)
        _churn(ingest, rng, 0)
        h0 = session.solve(ingest, deferred=True)  # in flight

        scenario = chaos.Scenario(
            "pipeline-fault", 1,
            {"solver.dispatch": chaos.PointSpec(prob=1.0, first_n=1)},
        )
        _churn(ingest, rng, 1)
        with chaos.armed(scenario):
            with pytest.raises(RuntimeError):
                session.solve(ingest, deferred=True)
        # h0 settled at the faulted call's entry (before the chaos point) —
        # its results are intact and the ring is empty
        assert h0.done()
        assert _tick_record(h0.result())
        assert session._pending is None
        # the lineage survived: the retry repairs instead of re-anchoring
        results = session.solve(ingest, deferred=True).result()
        assert session.last_mode == MODE_DELTA, session.last_reason
        assert results is not None
        agg = session.aggregates()
        assert agg["scheduled"] == len(ingest)

    @pytest.mark.skipif(
        not pipeline_mod.backend_supports_donation(),
        reason="backend ignores donate_argnums",
    )
    def test_decode_fault_after_donation_resets_lineage(self, monkeypatch):
        """A host-side decode failure on a donated delta tick must DROP the
        lineage: the carry's device buffers were consumed by the dispatch,
        so a kept ``_warm`` would re-read the deleted buffer on every later
        repair — one transient fault becoming a permanent crash loop (the
        confirmed pre-fix failure mode).  The next solve re-anchors full."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(32))
        session = _session(solver)
        session.solve(ingest)
        rng = retry.DeterministicRNG(13)
        _churn(ingest, rng, 0)
        original = type(solver).decode

        def boom(self, *a, **k):
            raise ValueError("decode exploded")

        monkeypatch.setattr(type(solver), "decode", boom)
        with pytest.raises(ValueError):
            session.solve(ingest)
        monkeypatch.setattr(type(solver), "decode", original)
        assert session._warm is None  # donated carry: lineage dropped
        # recovery: a clean full re-anchor, not a deleted-buffer crash
        results = session.solve(ingest)
        assert session.last_mode == MODE_FULL
        assert session.last_reason == "first"
        assert results is not None
        _churn(ingest, rng, 1)
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA  # repairs work again

    def test_solve_pipeline_driver_fault_leaves_handles_consumable(self):
        """The generic SolvePipeline ring: a dispatch() that raises enqueues
        nothing and already-dispatched handles drain normally."""

        class _Box:
            def __init__(self, v):
                self.v = v

            def result(self):
                return self.v

        pipe = pipeline_mod.SolvePipeline(depth=2)
        assert pipe.submit(lambda: _Box(1)) is None
        with pytest.raises(ValueError):
            pipe.submit(lambda: (_ for _ in ()).throw(ValueError("boom")))
        assert len(pipe) == 1  # the failed dispatch enqueued nothing
        assert pipe.submit(lambda: _Box(2)) == 1  # ring full: oldest retires
        assert pipe.drain() == [2]
        assert len(pipe) == 0


class TestPipelinePrimitives:
    def test_staging_ring_reuses_buffers(self):
        ring = pipeline_mod.HostStagingRing(depth=2)
        base = pipeline_mod.stats()["staging_reallocs"]
        a = (np.arange(6, dtype=np.int32), np.ones(3, dtype=np.float32))
        s1 = ring.stage(a)
        s2 = ring.stage(a)
        s3 = ring.stage((np.arange(6, dtype=np.int32) * 2,
                         np.zeros(3, dtype=np.float32)))
        # first fills are the working set, not drift: steady reuse counts 0
        assert pipeline_mod.stats()["staging_reallocs"] - base == 0
        # slot 0 reused for the third stage: same buffer objects, new values
        assert s3[0] is s1[0] and s3[1] is s1[1]
        assert s3[0][1] == 2 and s2[0][1] == 1
        # None and non-array leaves pass through
        assert ring.stage((None, 5, np.zeros(1)))[0] is None

    def test_staging_ring_realloc_on_shape_change(self):
        ring = pipeline_mod.HostStagingRing(depth=2)
        base = pipeline_mod.stats()["staging_reallocs"]
        ring.stage((np.zeros(4),))
        ring.stage((np.zeros(4),))
        assert pipeline_mod.stats()["staging_reallocs"] - base == 0
        ring.stage((np.zeros(8),))  # slot 0's buffer must REGROW: counted
        assert pipeline_mod.stats()["staging_reallocs"] - base == 1

    def test_fetch_ticket_overlap_record_and_span(self):
        import jax.numpy as jnp

        tracing.TRACE_STORE.clear()
        tracing.enable()
        try:
            with tracing.span("test.ticket"):
                ticket = pipeline_mod.FetchTicket(
                    (jnp.arange(4), None, jnp.ones(2)), label="test"
                )
                first = ticket.wait()
                again = ticket.wait()  # idempotent: same tuple, no re-fetch
            assert first is again
            assert first[1] is None
            assert ticket.done()
            rec = pipeline_mod.last_overlap()
            assert rec["hidden_s"] >= 0 and rec["exposed_s"] >= 0
            trace = tracing.TRACE_STORE.last(1)[0]
            spans = [s for s in trace.spans if s["name"] == "pipeline.overlap"]
            assert spans, "pipeline.overlap span not emitted"
            attrs = spans[0]["attrs"]
            assert attrs["label"] == "test"
            assert "hidden_s" in attrs and "exposed_s" in attrs
            assert attrs["staged"] is False
        finally:
            tracing.disable()
            tracing.TRACE_STORE.clear()

    def test_pipeline_depth_env(self, monkeypatch):
        monkeypatch.setenv("KC_PIPELINE_DEPTH", "3")
        assert pipeline_mod.pipeline_depth() == 3
        monkeypatch.setenv("KC_PIPELINE_DEPTH", "1")
        assert pipeline_mod.pipeline_depth() == 2  # floor: double buffer
        monkeypatch.setenv("KC_PIPELINE_DEPTH", "junk")
        assert pipeline_mod.pipeline_depth() == 2


class TestDecodeFetchSpan:
    def test_serial_decode_fetch_span_attrs_pinned(self):
        """Satellite: the serial path's decode.fetch is one span whose
        attributes say what varies — whether the barrier had already run
        and whether the copy was staged (docs/OBSERVABILITY.md) — and carry
        no constant."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(16))
        snapshot = solver.encode(ingest)
        out = solve_ops.solve(snapshot)
        tracing.TRACE_STORE.clear()
        tracing.enable()
        try:
            with tracing.span("test.decode"):
                results = solver.decode(snapshot, out)
            assert results.new_nodes
            trace = tracing.TRACE_STORE.last(1)[0]
            fetch = [s for s in trace.spans if s["name"] == "decode.fetch"]
            assert len(fetch) == 1
            attrs = fetch[0]["attrs"]
            assert attrs["prefetched"] is False  # no caller-side ticket
            assert attrs["staged"] is False
            assert set(attrs) <= {"prefetched", "staged", "tenant"}
        finally:
            tracing.disable()
            tracing.TRACE_STORE.clear()

    def test_solve_encoded_prefetches_once(self):
        """solve_encoded's exhaustion check and decode share ONE ticket: the
        decode.fetch span reports prefetched=True (barrier already ran)."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(16))
        snapshot = solver.encode(ingest)
        tracing.TRACE_STORE.clear()
        tracing.enable()
        try:
            with tracing.span("test.solve_encoded"):
                results = solver.solve_encoded(snapshot)
            assert results.new_nodes
            trace = tracing.TRACE_STORE.last(1)[0]
            fetch = [s for s in trace.spans if s["name"] == "decode.fetch"]
            assert len(fetch) == 1
            assert fetch[0]["attrs"]["prefetched"] is True
        finally:
            tracing.disable()
            tracing.TRACE_STORE.clear()


class TestDonation:
    def test_donation_disarmed_without_pipeline(self, monkeypatch):
        monkeypatch.setenv("KC_PIPELINE", "0")
        assert pipeline_mod.donation_enabled() is False

    @pytest.mark.skipif(
        not pipeline_mod.backend_supports_donation(),
        reason="backend ignores donate_argnums",
    )
    def test_steady_churn_donates_the_carry(self):
        """Pipelined repairs consume the carry's device buffers in place —
        the donation ledger moves on every warm dispatch."""
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(32))
        session = _session(solver)
        session.solve(ingest, deferred=True).result()
        rng = retry.DeterministicRNG(5)
        before = pipeline_mod.stats()
        pending = None
        for tick in range(4):
            _churn(ingest, rng, tick)
            h = session.solve(ingest, deferred=True)
            if pending is not None:
                pending.result()
            pending = h
        pending.result()
        delta = pipeline_mod.stats()["donated"] - before["donated"]
        assert delta >= 4
        assert session.mode_counts[MODE_DELTA] >= 4

    def test_serial_without_pipeline_counts_reallocs(self, monkeypatch):
        monkeypatch.setenv("KC_PIPELINE", "0")
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(24))
        session = _session(solver)
        session.solve(ingest)
        before = pipeline_mod.stats()
        _churn(ingest, retry.DeterministicRNG(9), 0)
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA
        after = pipeline_mod.stats()
        assert after["donation_reallocs"] > before["donation_reallocs"]
        assert after["donated"] == before["donated"]


class TestSoakReplayDigest:
    def test_tick_overlap_probe_registered_advisory(self):
        from karpenter_core_tpu.soak import slo

        assert slo.PROBES["tick_overlap_s"] is False  # wall-clock ⇒ advisory
        obs = slo.Observation(tick_overlap_s=0.25)
        assert obs.probe_values()["tick_overlap_s"] == 0.25

    def test_replay_digest_unchanged_by_pipeline(self, monkeypatch):
        """Satellite: the soak verdict's replay digest is pipeline-blind —
        the overlap is wall-clock-only, off the digest like tick_wall_s.
        Runs the scaled-down churn-steady scenario both ways."""
        from dataclasses import replace

        from karpenter_core_tpu.soak import run_scenario, scenarios, slo

        def digest(pipeline: str) -> str:
            monkeypatch.setenv("KC_PIPELINE", pipeline)
            scenario = replace(
                scenarios.build("churn-steady", seed=5),
                params={
                    "duration_s": 120.0, "period_s": 120.0,
                    "base_rate_per_s": 0.5, "peak_rate_per_s": 0.5,
                    "mean_lifetime_s": 120.0,
                },
                tick_s=30.0,
                settle_ticks=10,
            )
            report = run_scenario(scenario)
            assert report["verdict"]["converged"] is True
            return slo.replay_digest(report)

        assert digest("1") == digest("0")
