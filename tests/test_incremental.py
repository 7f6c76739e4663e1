"""Incremental warm-start solver (ISSUE 7): snapshot-store invariants,
fallback-policy decisions, and delta-vs-full parity.

Three layers:

  - ``models.store`` is pure host bookkeeping: version monotonicity,
    diff ∘ apply == identity over randomized membership maps, per-plane
    digest stability ACROSS PROCESSES (PYTHONHASHSEED independence), and
    input-digest sensitivity.
  - ``FallbackPolicy`` decisions are pinned per reason string — the
    ``solve.mode`` amortization contract docs/INCREMENTAL.md documents.
  - ``IncrementalSolveSession`` parity: over randomized steady-churn event
    sequences the delta lineage's final per-node assignment multiset must be
    IDENTICAL to a from-scratch full solve of the same population, at small N
    in tier-1 (kernel-scale churn is chip_smoke.churn_line under the slow
    marker below).  KC_SOLVER_INCREMENTAL=0 keeps the old path as the
    degenerate case.
"""

import copy
import json
import random
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from karpenter_core_tpu.apis.objects import new_uid
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.models import store as store_mod
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.models.store import (
    SnapshotDelta,
    SnapshotStore,
    diff_members,
    diff_snapshots,
)
from karpenter_core_tpu.solver.incremental import (
    MODE_DELTA,
    MODE_FULL,
    FallbackPolicy,
    IncrementalSolveSession,
    incremental_enabled,
    node_signature_of,
)
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pod, make_pods, make_provisioner


def _solver(n_provisioners: int = 1) -> TPUSolver:
    provisioners = [
        make_provisioner(name=f"prov-{i}") for i in range(n_provisioners)
    ]
    return TPUSolver(fake_cp.FakeCloudProvider(), provisioners)


def _population(n: int = 40):
    """A small mixed population: two generic shapes + a zone-spread shape."""
    pods = make_pods(n // 2, requests={"cpu": "500m"})
    pods += make_pods(n // 4, requests={"cpu": 1})
    pods += make_pods(
        n - len(pods),
        requests={"cpu": "250m"},
        labels={"app": "spread"},
    )
    return pods


# -- snapshot store ------------------------------------------------------------


class TestSnapshotStore:
    def test_version_monotonic(self):
        solver = _solver()
        store = SnapshotStore()
        versions = []
        for _ in range(3):
            ingest = PodIngest()
            ingest.add_all(_population(12))
            snap = solver.encode(ingest)
            versions.append(store.commit(snap).version)
        assert versions == [1, 2, 3]
        assert store.current.version == 3

    def test_ingest_version_counts_effective_mutations(self):
        ingest = PodIngest()
        assert ingest.version == 0
        pods = make_pods(3, requests={"cpu": 1})
        ingest.add_all(pods)
        assert ingest.version == 3
        assert ingest.remove(pods[0].uid) is True
        assert ingest.version == 4
        assert ingest.remove(pods[0].uid) is False  # no-op: not tracked
        assert ingest.version == 4
        assert ingest.get(pods[1].uid) is pods[1]
        assert ingest.get("nope") is None

    def test_diff_apply_identity_fuzz(self):
        rng = random.Random(1729)
        for trial in range(50):
            keys = [(("k", i),) for i in range(rng.randint(1, 6))]
            prev = {
                k: tuple(f"u{trial}-{i}-{j}" for j in range(rng.randint(0, 5)))
                for i, k in enumerate(keys)
                if rng.random() < 0.8
            }
            cur = {}
            for i, k in enumerate(keys):
                if rng.random() < 0.8:
                    survivors = tuple(
                        u for u in prev.get(k, ()) if rng.random() < 0.7
                    )
                    added = tuple(
                        f"n{trial}-{i}-{j}" for j in range(rng.randint(0, 3))
                    )
                    if survivors + added:
                        cur[k] = survivors + added
            delta = diff_members(prev, cur, from_version=7)
            assert delta.apply(prev) == cur, (trial, prev, cur)
            assert delta.to_version == 8
            assert delta.pods_before == sum(len(u) for u in prev.values())
            assert delta.pods_after == sum(len(u) for u in cur.values())

    def test_diff_snapshots_structure(self):
        solver = _solver()
        store = SnapshotStore()
        pods = make_pods(8, requests={"cpu": "500m"})
        other = make_pods(4, requests={"cpu": 2})
        ingest = PodIngest()
        ingest.add_all(pods + other)
        v1 = store.commit(solver.encode(ingest))

        ingest.remove(pods[0].uid)
        replacement = copy.deepcopy(pods[1])
        replacement.metadata.name = "repl"
        replacement.metadata.uid = new_uid()
        ingest.add(replacement)
        v2 = store.commit(solver.encode(ingest))

        delta = diff_snapshots(v1, v2)
        assert delta.from_version == 1 and delta.to_version == 2
        assert delta.added_count == 1 and delta.evicted_count == 1
        assert not delta.new_classes and not delta.removed_classes
        assert not delta.changed_planes  # same catalog, same axes
        assert 0 < delta.delta_fraction < 0.25
        # extents + touched partition the class axis
        touched = set(delta.touched_classes)
        spanned = set()
        for start, end in delta.unchanged_extents:
            spanned.update(range(start, end))
        assert not (touched & spanned)
        assert touched | spanned == set(range(len(v2.rows)))
        assert delta.touched_mask_words > 0
        # the store's convenience diff: current version ⇒ None, an older
        # version diffs FROM current (the reverse walk swaps add/evict)
        assert store.diff(v2) is None
        back = store.diff(v1)
        assert back.added_count == 1 and back.evicted_count == 1
        # replaying the membership delta reproduces v2's summary
        assert diff_members(v1.summary(), v2.summary()).apply(v1.summary()) \
            == v2.summary()

    def test_digest_stability_across_processes(self, tmp_path):
        """Same inputs ⇒ same per-plane digests in a different process with a
        different PYTHONHASHSEED — digests are content, not id()/hash()."""
        script = textwrap.dedent(
            """
            import json, sys
            from karpenter_core_tpu.cloudprovider import fake as fake_cp
            from karpenter_core_tpu.models import store as store_mod
            from karpenter_core_tpu.models.columnar import PodIngest
            from karpenter_core_tpu.solver.tpu import TPUSolver
            from karpenter_core_tpu.testing import make_pods, make_provisioner

            solver = TPUSolver(
                fake_cp.FakeCloudProvider(), [make_provisioner(name="p")]
            )
            ingest = PodIngest()
            pods = make_pods(6, requests={"cpu": "500m"})
            for i, p in enumerate(pods):
                p.metadata.name = f"pin-{i}"
                p.metadata.uid = f"uid-{i}"
            ingest.add_all(pods)
            print(json.dumps(store_mod.snapshot_digests(solver.encode(ingest))))
            """
        )
        outs = []
        for seed in ("0", "31337"):
            proc = subprocess.run(
                [sys.executable, "-c", script],
                capture_output=True, text=True, timeout=120,
                env={
                    **__import__("os").environ,
                    "PYTHONHASHSEED": seed,
                    "JAX_PLATFORMS": "cpu",
                },
                cwd=str(__import__("pathlib").Path(__file__).parent.parent),
            )
            assert proc.returncode == 0, proc.stderr[-2000:]
            outs.append(json.loads(proc.stdout.strip().splitlines()[-1]))
        assert outs[0] == outs[1]
        assert set(outs[0]) == {
            "catalog", "templates", "vocab", "classes", "groups", "axes",
            "policy",
        }

    def test_supply_digest_sensitivity(self):
        from karpenter_core_tpu.testing import harness

        env = harness.make_environment()
        env.kube.create(make_provisioner(name="default"))
        node_pods = make_pods(1, requests={"cpu": 1})
        # no nodes, no bound pods: stable empty digest
        assert store_mod.supply_digest([], []) == store_mod.supply_digest([], [])
        d0 = store_mod.supply_digest([], [])
        bound = make_pod(requests={"cpu": 1}, node_name="n-1", phase="Running")
        assert store_mod.supply_digest([], [bound]) != d0
        assert node_pods  # silence unused

    def test_catalog_digest_sensitivity(self):
        provs = [make_provisioner(name="p")]
        provider = fake_cp.FakeCloudProvider()
        by_name = {"p": provider.get_instance_types(provs[0])}
        d0 = store_mod.catalog_digest(provs, by_name)
        assert d0 == store_mod.catalog_digest(provs, by_name)
        provs2 = [make_provisioner(name="p")]
        provs2[0].metadata.resource_version = "999"
        assert store_mod.catalog_digest(provs2, by_name) != d0


# -- fallback policy -----------------------------------------------------------


def _mk_delta(**kw) -> SnapshotDelta:
    base = dict(from_version=1, to_version=2, pods_before=100, pods_after=100)
    base.update(kw)
    return SnapshotDelta(**base)


class TestFallbackPolicy:
    def test_reasons(self):
        pol = FallbackPolicy(
            enabled=True, max_delta_fraction=0.25, audit_interval=4
        )
        assert pol.decide(None, 0, 0) == (MODE_FULL, "first")
        d = _mk_delta(changed_planes=("supply",))
        assert pol.decide(d, 0, 0)[0] == MODE_FULL
        assert pol.decide(d, 0, 0)[1].startswith("supply-changed")
        d = _mk_delta(new_classes=(("unseen",),))
        assert pol.decide(d, 0, 0) == (MODE_FULL, "class-shape")
        # a key the previous tensors know repairs fine
        assert pol.decide(d, 0, 0, known_classes={("unseen",): 3})[0] \
            == MODE_DELTA
        # removed classes alone never force a full solve
        d = _mk_delta(removed_classes=(("gone",),))
        assert pol.decide(d, 0, 0)[0] == MODE_DELTA
        d = _mk_delta(added={("k",): tuple(f"u{i}" for i in range(30))})
        assert pol.decide(d, 0, 0)[1].startswith("delta-fraction")
        d = _mk_delta(added={("k",): ("u1",)})
        assert pol.decide(d, 4, 0) == (MODE_FULL, "audit")
        assert pol.decide(d, 3, 0) == (MODE_DELTA, "delta")

    def test_disabled_and_materialized(self, monkeypatch):
        assert FallbackPolicy(enabled=False).decide(None, 0, 0) \
            == (MODE_FULL, "disabled")
        pol = FallbackPolicy(enabled=True, materialized=True)
        d = _mk_delta(added={("k",): ("u1",)})
        assert pol.decide(d, 0, 1) == (MODE_FULL, "materialized-slots")
        assert pol.decide(d, 0, 0)[0] == MODE_DELTA
        monkeypatch.setenv("KC_SOLVER_INCREMENTAL", "0")
        assert not incremental_enabled()
        assert not FallbackPolicy.from_env().enabled
        monkeypatch.setenv("KC_SOLVER_INCREMENTAL", "1")
        assert incremental_enabled()

    def test_from_env_knobs(self, monkeypatch):
        monkeypatch.setenv("KC_DELTA_MAX_FRACTION", "0.5")
        monkeypatch.setenv("KC_DELTA_AUDIT_INTERVAL", "7")
        pol = FallbackPolicy.from_env(materialized=True)
        assert pol.max_delta_fraction == 0.5
        assert pol.audit_interval == 7
        assert pol.materialized is True


# -- session parity (kernel, small N) ------------------------------------------


def _mode_count(mode: str) -> float:
    from karpenter_core_tpu.solver.incremental import SOLVE_MODE

    for _name, labels, value in SOLVE_MODE.samples():
        if labels.get("mode") == mode:
            return value
    return 0.0


def _full_signature(solver, ingest):
    from karpenter_core_tpu.ops import solve as solve_ops
    import jax

    snapshot = solver.encode(ingest)
    out = solve_ops.solve(snapshot)
    a, ae = jax.device_get((out.assign, out.assign_existing))
    # stable class identities, not row indices (a fully-churned class
    # re-enters a fresh encode at a different row)
    keys = [store_mod.class_key(c) for c in snapshot.classes]
    return node_signature_of(np.asarray(a), keys) + node_signature_of(
        np.asarray(ae), keys
    )


def _churn(ingest, rng, fraction=0.1):
    """Replace ``fraction`` of the population with same-shaped fresh pods —
    the steady-state event the delta path amortizes."""
    members = ingest.class_members()
    uids = [(sig, u) for sig, us in members.items() for u in us]
    k = max(int(len(uids) * fraction), 1)
    victims = rng.sample(uids, k)
    for i, (_sig, uid) in enumerate(victims):
        rep = copy.deepcopy(ingest.get(uid))
        ingest.remove(uid)
        rep.metadata.name = f"churn-{rng.randint(0, 1 << 30)}-{i}"
        rep.metadata.uid = new_uid()
        rep.spec.node_name = ""
        ingest.add(rep)


class TestSessionParity:
    def test_steady_churn_matches_full_solve(self):
        """Randomized replace-churn sequences: every repair tick's cumulative
        assignments equal a from-scratch solve's (canonical per-node class
        loads) — the ISSUE 7 parity pin, at tier-1 scale."""
        rng = random.Random(7)
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(40))
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0,
                           max_delta_fraction=0.9),
        )
        session.solve(ingest)
        assert session.last_mode == MODE_FULL and session.last_reason == "first"
        for tick in range(4):
            _churn(ingest, rng, fraction=0.1)
            session.solve(ingest)
            assert session.last_mode == MODE_DELTA, session.last_reason
            assert session.node_signature() == _full_signature(solver, ingest), (
                f"tick {tick} diverged"
            )
        agg = session.aggregates()
        assert agg["scheduled"] == len(ingest)
        assert agg["failed"] == 0
        # capacity accounting: what the repairs returned is what the scan
        # charged, up to f32 rounding
        assert session.used_drift() < 1e-6

    def test_resource_matmuls_are_pinned_to_full_f32_precision(self):
        """Found on the TPU (PR 21): at default precision the chip rounds f32
        matmul operands to bf16, so ``repair_free`` returned 0.1001 cpu for
        every 0.1 the scan had charged and the used plane drifted (2e-3
        relative) until the lineage diverged from a from-scratch solve.  CPU
        cannot show the loss, so pin the contract on the traced program:
        every dot whose operands are both f32 asks for HIGHEST."""
        import jax
        import numpy as np

        from karpenter_core_tpu.ops import solve as solve_ops

        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(24))
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0, max_delta_fraction=0.9),
        )
        session.solve(ingest)
        w = session._warm
        n_classes, n_slots = w.assign.shape
        n_ex = w.assign_ex.shape[1]
        jaxpr = jax.make_jaxpr(solve_ops._repair_free_impl)(
            w.carry, np.zeros((n_classes, n_slots), np.int32),
            np.zeros((n_classes, n_ex), np.int32),
            np.asarray(w.prep.cls.requests), w.member_rows, w.own_inv_rows,
        )
        f32_dots = [
            eqn for eqn in jaxpr.jaxpr.eqns
            if eqn.primitive.name == "dot_general"
            and all(v.aval.dtype == np.float32 for v in eqn.invars)
        ]
        assert len(f32_dots) == 2  # used (new slots) + used (existing nodes)
        for eqn in f32_dots:
            assert eqn.params["precision"] == (
                jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST
            ), eqn

    def test_windowed_repair_matches_full_solve(self, monkeypatch):
        """Same parity with the bounded repair window forced on at tier-1
        scale (KC_DELTA_WINDOW shrinks the bucket below n_slots)."""
        monkeypatch.setenv("KC_DELTA_WINDOW", "16")
        rng = random.Random(11)
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(48))
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0,
                           max_delta_fraction=0.9),
        )
        session.solve(ingest)
        for _ in range(3):
            _churn(ingest, rng, fraction=0.08)
            session.solve(ingest)
            assert session.last_mode == MODE_DELTA, session.last_reason
            assert session.node_signature() == _full_signature(solver, ingest)

    def test_fully_churned_class_keeps_parity_across_row_reorder(self):
        """Evicting EVERY member of a class deletes its ingest slot; same-shape
        replacements re-mint it at the END of insertion order, so a fresh
        encode's class axis reorders among equal-request classes.  The parity
        signature labels loads by class identity, not row index — identical
        placements must not read as divergence."""
        solver = _solver()
        ingest = PodIngest()
        small = make_pods(4, requests={"cpu": "250m"})
        big = make_pods(36, requests={"cpu": "500m"})
        ingest.add_all(small + big)
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0,
                           max_delta_fraction=0.9),
        )
        session.solve(ingest)
        for p in small:
            ingest.remove(p.uid)
        for i in range(4):
            rep = copy.deepcopy(small[0])
            rep.metadata.name = f"remint-{i}"
            rep.metadata.uid = new_uid()
            rep.spec.node_name = ""
            ingest.add(rep)
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA, session.last_reason
        assert session.node_signature() == _full_signature(solver, ingest)

    def test_net_additions_keep_aggregate_parity(self):
        """Pure additions of known shapes repair without an encode; the
        aggregate outcome (everything scheduled) matches a full solve even
        where slot-level tie-breaking may not."""
        solver = _solver()
        ingest = PodIngest()
        base = make_pods(20, requests={"cpu": "500m"})
        ingest.add_all(base)
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0,
                           max_delta_fraction=0.9),
        )
        session.solve(ingest)
        extra = make_pods(3, requests={"cpu": "500m"})
        ingest.add_all(extra)
        results = session.solve(ingest)
        assert session.last_mode == MODE_DELTA
        assert session.aggregates()["scheduled"] == 23
        assert session.aggregates()["failed"] == 0
        placed = sum(len(d.pods) for d in results.new_nodes) + sum(
            len(ps) for ps in results.existing_assignments.values()
        )
        assert placed == 3  # the delta tick returns THIS tick's placements

    def test_unseen_class_escalates_to_full(self):
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(make_pods(16, requests={"cpu": "500m"}))
        session = IncrementalSolveSession(
            solver, FallbackPolicy(enabled=True, audit_interval=0)
        )
        session.solve(ingest)
        ingest.add_all(make_pods(2, requests={"cpu": 3}))  # new shape
        session.solve(ingest)
        assert session.last_mode == MODE_FULL
        assert session.last_reason == "class-shape"
        assert session.node_signature() == _full_signature(solver, ingest)

    def test_audit_interval_and_drift_reset(self):
        rng = random.Random(3)
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(_population(32))
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=2,
                           max_delta_fraction=0.9),
        )
        session.solve(ingest)
        modes = []
        for _ in range(5):
            _churn(ingest, rng, fraction=0.08)
            session.solve(ingest)
            modes.append((session.last_mode, session.last_reason))
        assert modes[0][0] == MODE_DELTA and modes[1][0] == MODE_DELTA
        assert modes[2] == (MODE_FULL, "audit")
        # the audit measured drift against the repair lineage
        assert session.last_audit_drift_nodes is None or isinstance(
            session.last_audit_drift_nodes, int
        )
        assert modes[3][0] == MODE_DELTA  # lineage re-anchored

    def test_catalog_change_forces_full(self):
        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(make_pods(12, requests={"cpu": "500m"}))
        session = IncrementalSolveSession(
            solver, FallbackPolicy(enabled=True, audit_interval=0)
        )
        session.solve(ingest)
        solver.provisioners[0].metadata.resource_version = "bumped"
        _churn(ingest, random.Random(5), fraction=0.1)
        session.solve(ingest)
        assert session.last_mode == MODE_FULL
        assert session.last_reason.startswith("supply-changed")

    def test_mode_counter_and_span_attribute(self):
        from karpenter_core_tpu import tracing

        solver = _solver()
        ingest = PodIngest()
        ingest.add_all(make_pods(12, requests={"cpu": "500m"}))
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0,
                           max_delta_fraction=0.9),
        )
        full0, delta0 = _mode_count("full"), _mode_count("delta")
        tracing.enable()
        try:
            session.solve(ingest)
            _churn(ingest, random.Random(9), fraction=0.1)
            session.solve(ingest)
        finally:
            tracing.disable()
        assert _mode_count("full") == full0 + 1
        assert _mode_count("delta") == delta0 + 1
        spans = [
            s
            for t in tracing.TRACE_STORE.last(None)
            for s in t.spans
            if s["name"] == "solve.incremental"
        ]
        assert spans, "solve.incremental span missing"
        modes = {s["attrs"].get("solve.mode") for s in spans}
        assert {"full", "delta"} <= modes

    def test_rendered_metric_reaches_exposition(self):
        text = REGISTRY.render()
        assert "karpenter_solve_mode_total" in text


# -- the soak smoke (kernel-path scenario wiring, host-sized) ------------------


class TestChurnSteadySmoke:
    def test_catalog_entry_targets_kernel_path(self):
        from karpenter_core_tpu.soak import scenarios

        scenario = scenarios.build("churn-steady", seed=3)
        assert scenario.use_tpu_kernel is True
        assert scenario.seed == 3
        probes = {r.probe for r in scenario.slo_spec().rules}
        assert "solve_latency_s" in probes
        trace = scenario.build_trace()
        # a 10k-fleet steady state: arrivals × lifetime ≈ standing population
        creates = sum(1 for e in trace.events if e.action == "create")
        assert creates > 5000

    def test_tiny_kernel_scenario_converges_and_counts_modes(self):
        """A scaled-down churn scenario through the runner with the kernel
        routing ON: proves the soak runner threads use_tpu_kernel into the
        provisioning controller and the run converges.  Batches stay under
        tpu_kernel_min_pods so solves take the host path — no XLA compiles in
        tier-1 (the full 10k kernel-path run is the slow matrix's job)."""
        from dataclasses import replace

        from karpenter_core_tpu.soak import run_scenario, scenarios

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
        host0 = _mode_count("host")
        report = run_scenario(scenario)
        assert report["verdict"]["converged"] is True
        deterministic = [
            r for r in report["verdict"]["slo"]
        ]
        assert all(r["passed"] for r in deterministic), json.dumps(
            report["verdict"], indent=2
        )
        # the kernel-routed controller still counted its (host-path) solves
        assert _mode_count("host") > host0


# -- kernel-scale churn (slow tier) --------------------------------------------


@pytest.mark.slow
class TestKernelScaleChurn:
    def test_churn_line_meets_acceptance(self):
        """The ISSUE 7 acceptance at kernel scale: warm repair ≥ 2x the full
        re-solve with identical assignments, through chip_smoke.churn_line."""
        import chip_smoke

        solver, pods = chip_smoke.build_inputs(20000, 40, n_provisioners=5)
        ingest = PodIngest()
        ingest.add_all(pods)
        solver.warmup()
        line = chip_smoke.churn_line(solver, ingest, churn_fraction=0.02, ticks=5)
        assert line["identical_assignments"] is True
        assert line["speedup"] >= 2.0, line
        assert line["modes"].get("delta", 0) >= 4
