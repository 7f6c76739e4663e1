"""Policy-objective subsystem (ISSUE 9): config resolution, objective-kernel
parity vs the host price oracles, decode selection, cost-delta consolidation,
counter-proposals, provider offering realism, and the incremental-session
policy-digest escalation.

The parity contract (docs/POLICY.md): with default weights the objective
argmin IS ``Offerings.cheapest()`` over each node's feasible offering set —
fuzzed here against the host oracles — and exact price ties prefer spot (the
``worst_launch_price`` ladder's purchase order), breaking remaining ties by
the catalog's stable (instance type, zone, capacity type) index order.
"""

import json
import random

import numpy as np
import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.cloudprovider.types import Offering, Offerings
from karpenter_core_tpu.controllers.deprovisioning import (
    Action,
    CandidateNode,
    worst_launch_price,
)
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.models import store as store_mod
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.ops import objective as objective_ops
from karpenter_core_tpu.policy import (
    PolicyConfig,
    build_planes,
    policy_input_digest,
    propose_resize,
)
from karpenter_core_tpu.scheduling import Requirement, Requirements
from karpenter_core_tpu.solver.incremental import (
    MODE_DELTA,
    MODE_FULL,
    FallbackPolicy,
    IncrementalSolveSession,
)
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import (
    harness,
    make_pod,
    make_pods,
    make_provisioner,
)

SEED = 20260803


# -- config resolution ---------------------------------------------------------


class TestPolicyConfig:
    def test_default_is_disabled(self):
        config = PolicyConfig()
        assert config.enabled is False
        assert config.cost_weight == 1.0
        assert config.risk_aversion == 0.0

    def test_resolve_overlays_highest_weight_provisioner(self):
        low = make_provisioner(
            name="low", weight=1, policy={"enabled": True, "riskAversion": 9.0}
        )
        high = make_provisioner(
            name="high", weight=5,
            policy={"enabled": True, "costWeight": 2.0, "spotPreference": False},
        )
        config = PolicyConfig.resolve([low, high])
        assert config.enabled is True
        assert config.cost_weight == 2.0
        assert config.spot_preference is False
        assert config.risk_aversion == 0.0  # low's block never applies

    def test_kill_switch_beats_provisioner_spec(self, monkeypatch):
        monkeypatch.setenv("KC_POLICY", "0")
        prov = make_provisioner(name="p", policy={"enabled": True})
        assert PolicyConfig.resolve([prov]).enabled is False

    def test_merged_parses_throughput_map_and_ignores_junk(self):
        config = PolicyConfig().merged({
            "enabled": True,
            "throughput": {"it-a": 2.0, "it-b": 1.0},
            "costWeight": "not-a-number",
            "unknownKnob": 42,
        })
        assert config.enabled is True
        assert config.throughput_of("it-a") == 2.0
        assert config.throughput_of("missing") == 0.0
        assert config.cost_weight == 1.0

    def test_digest_moves_with_knobs(self):
        a = PolicyConfig(enabled=True)
        assert a.digest() == PolicyConfig(enabled=True).digest()
        assert a.digest() != PolicyConfig(enabled=True, risk_aversion=0.5).digest()


# -- objective kernel parity vs the host oracles -------------------------------


def _random_catalog(rng, n_it=6, zones=("z1", "z2", "z3"), cts=("on-demand", "spot")):
    """(price f32[I,Z,CT], avail bool[I,Z,CT]) with deliberate price ties."""
    price = np.full((n_it, len(zones), len(cts)), np.inf, dtype=np.float32)
    avail = np.zeros((n_it, len(zones), len(cts)), dtype=bool)
    tie_pool = [0.1, 0.25, 0.5, 1.0]  # small pool forces frequent exact ties
    for i in range(n_it):
        for z in range(len(zones)):
            for c in range(len(cts)):
                if rng.random() < 0.7:
                    avail[i, z, c] = True
                    price[i, z, c] = rng.choice(tie_pool)
    return price, avail


def _host_offerings(price, avail, viable, zone_mask, ct_mask, cts):
    """The host-side Offerings set equivalent to one node's feasible cells."""
    out = Offerings()
    n_it, n_z, n_ct = price.shape
    for i in range(n_it):
        if not viable[i]:
            continue
        for z in range(n_z):
            if not zone_mask[z]:
                continue
            for c in range(n_ct):
                if not ct_mask[c] or not avail[i, z, c]:
                    continue
                out.append(Offering(cts[c], f"z{z + 1}", float(price[i, z, c])))
    return out


class TestObjectiveParity:
    """The tier-1 parity fuzz: objective argmin vs Offerings.cheapest /
    worst_launch_price over randomized catalogs and node masks.  One fixed
    shape keeps this at a single XLA compile across all iterations."""

    CTS = ("on-demand", "spot")

    def _select(self, price, avail, viable, zone_mask, ct_mask, config):
        import jax.numpy as jnp

        masked = np.where(avail, price, np.inf).astype(np.float32)
        n = viable.shape[0]
        return objective_ops.ObjectiveSelection(*(
            np.asarray(a) for a in objective_ops.select_offerings(
                jnp.asarray(viable), jnp.asarray(zone_mask), jnp.asarray(ct_mask),
                jnp.ones(n, dtype=bool), jnp.ones(n, dtype=np.int32),
                jnp.asarray(masked), jnp.zeros_like(jnp.asarray(masked)),
                jnp.zeros(price.shape[0], dtype=jnp.float32),
                jnp.asarray(np.array([c == "spot" for c in self.CTS])),
                objective_ops.weights_of(config),
            )
        ))

    def test_cheapest_and_worst_price_parity_fuzz(self):
        rng = random.Random(SEED)
        config = PolicyConfig(enabled=True)  # default weights: score == price
        checked = 0
        for _ in range(25):
            price, avail = _random_catalog(rng)
            n = 8
            viable = np.array(
                [[rng.random() < 0.6 for _ in range(price.shape[0])] for _ in range(n)]
            )
            zone_mask = np.array([[rng.random() < 0.7 for _ in range(3)] for _ in range(n)])
            ct_mask = np.array([[rng.random() < 0.8 for _ in range(2)] for _ in range(n)])
            sel = self._select(price, avail, viable, zone_mask, ct_mask, config)
            for node in range(n):
                offerings = _host_offerings(
                    price, avail, viable[node], zone_mask[node], ct_mask[node],
                    self.CTS,
                )
                cheapest = offerings.cheapest()
                if cheapest is None:
                    assert not sel.active[node]
                    continue
                checked += 1
                assert sel.active[node]
                # the objective argmin IS the host cheapest() price
                assert sel.price[node] == pytest.approx(cheapest.price)
                # spot-preferred tie break mirrors worst_launch_price's
                # purchase ladder: spot selected iff spot attains the min
                spot_attains = any(
                    o.capacity_type == "spot"
                    and o.price == pytest.approx(cheapest.price)
                    for o in offerings
                )
                selected_ct = self.CTS[int(sel.sel_ct[node])]
                assert (selected_ct == "spot") == spot_attains
                # cheapest never exceeds the spot-preferred worst launch price
                requirements = Requirements(
                    Requirement(
                        labels_api.LABEL_CAPACITY_TYPE, "In",
                        [self.CTS[c] for c in range(2) if ct_mask[node][c]],
                    ),
                    Requirement(
                        labels_api.LABEL_TOPOLOGY_ZONE, "In",
                        [f"z{z + 1}" for z in range(3) if zone_mask[node][z]],
                    ),
                )
                worst = worst_launch_price(offerings, requirements)
                assert sel.price[node] <= worst + 1e-6
        assert checked > 50  # the fuzz actually exercised populated nodes

    def test_tie_break_is_deterministic_lowest_index(self):
        config = PolicyConfig(enabled=True, spot_preference=False)
        price = np.full((3, 2, 2), 1.0, dtype=np.float32)
        avail = np.ones((3, 2, 2), dtype=bool)
        viable = np.ones((2, 3), dtype=bool)
        zone_mask = np.ones((2, 2), dtype=bool)
        ct_mask = np.ones((2, 2), dtype=bool)
        a = self._select(price, avail, viable, zone_mask, ct_mask, config)
        b = self._select(price, avail, viable, zone_mask, ct_mask, config)
        # full tie, spot preference off: the first (it, zone, ct) cell wins
        assert (a.sel_it == 0).all() and (a.sel_zone == 0).all() and (a.sel_ct == 0).all()
        for field_a, field_b in zip(a, b):
            assert np.array_equal(np.asarray(field_a), np.asarray(field_b))

    def test_spot_preference_wins_exact_ties(self):
        config = PolicyConfig(enabled=True, spot_preference=True)
        price = np.full((1, 1, 2), 2.5, dtype=np.float32)
        avail = np.ones((1, 1, 2), dtype=bool)
        sel = self._select(
            price, avail, np.ones((1, 1), dtype=bool),
            np.ones((1, 1), dtype=bool), np.ones((1, 2), dtype=bool), config,
        )
        assert self.CTS[int(sel.sel_ct[0])] == "spot"

    def test_risk_aversion_prefers_safe_offering(self):
        import jax.numpy as jnp

        config = PolicyConfig(enabled=True, risk_aversion=1.0)
        # spot is cheaper raw but carries 80% interruption risk:
        # expected spot = 1.0 * (1 + 0.8) = 1.8 > on-demand 1.5
        price = np.array([[[1.5, 1.0]]], dtype=np.float32)
        risk = np.array([[[0.0, 0.8]]], dtype=np.float32)
        sel = objective_ops.ObjectiveSelection(*(
            np.asarray(a) for a in objective_ops.select_offerings(
                jnp.ones((1, 1), dtype=bool), jnp.ones((1, 1), dtype=bool),
                jnp.ones((1, 2), dtype=bool), jnp.ones(1, dtype=bool),
                jnp.ones(1, dtype=np.int32), jnp.asarray(price),
                jnp.asarray(risk), jnp.zeros(1, dtype=jnp.float32),
                jnp.asarray(np.array([False, True])),
                objective_ops.weights_of(config),
            )
        ))
        assert int(sel.sel_ct[0]) == 0  # on-demand
        assert sel.expected[0] == pytest.approx(1.5)

    def test_throughput_weight_buys_the_faster_type(self):
        import jax.numpy as jnp

        config = PolicyConfig(enabled=True, throughput_weight=1.0)
        price = np.array([[[1.0]], [[1.2]]], dtype=np.float32)  # it-1 pricier
        throughput = np.array([0.0, 0.5], dtype=np.float32)  # ...but faster
        sel = objective_ops.ObjectiveSelection(*(
            np.asarray(a) for a in objective_ops.select_offerings(
                jnp.ones((1, 2), dtype=bool), jnp.ones((1, 1), dtype=bool),
                jnp.ones((1, 1), dtype=bool), jnp.ones(1, dtype=bool),
                jnp.ones(1, dtype=np.int32), jnp.asarray(price),
                jnp.zeros_like(jnp.asarray(price)), jnp.asarray(throughput),
                jnp.asarray(np.array([False])),
                objective_ops.weights_of(config),
            )
        ))
        assert int(sel.sel_it[0]) == 1  # 1.2 - 0.5 < 1.0 - 0.0


# -- decode-folded selection ---------------------------------------------------


class TestDecodeSelection:
    def _solver(self, policy=None, skew_prices=False):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(5))
        if skew_prices:
            for it in provider.get_instance_types(None):
                provider.set_price(
                    it.name, it.offerings[0].price * 0.5,
                    capacity_type="spot", zone="test-zone-2",
                )
        return provider, TPUSolver(
            provider, [make_provisioner(name="default")], policy=policy
        )

    def test_selection_pins_the_cheapest_cell(self):
        _, solver = self._solver(PolicyConfig(enabled=True), skew_prices=True)
        pods = make_pods(8, requests={"cpu": "500m"})
        results = solver.solve(pods)
        assert results.new_nodes and results.fleet_cost is not None
        for decision in results.new_nodes:
            assert decision.selected is not None
            # the skewed sheet makes zone-2 spot the strict argmin everywhere
            assert decision.zones == ["test-zone-2"]
            assert decision.capacity_types == ["spot"]
            assert decision.instance_type_names[0] == (
                decision.selected["instance_type"]
            )
            launchable = solver.to_launchable(decision)
            zone_req = launchable.requirements.get(labels_api.LABEL_TOPOLOGY_ZONE)
            assert zone_req.values_list() == ["test-zone-2"]
        # the fleet cost rides /metrics
        rendered = REGISTRY.render()
        assert 'karpenter_policy_fleet_cost{view="price"}' in rendered

    def test_disabled_policy_stamps_nothing(self):
        _, solver = self._solver(policy=None, skew_prices=True)
        results = solver.solve(make_pods(6, requests={"cpu": "500m"}))
        assert results.fleet_cost is None
        assert all(d.selected is None for d in results.new_nodes)

    def test_equal_prices_keep_placements_feasibility_identical(self):
        """The acceptance pin: on a uniform price sheet, policy-on and
        policy-off decodes of the same feasibility solve produce identical
        pod placements AND the objective's choice matches what the
        provider's own cheapest-pick would land (equal everywhere)."""
        _, solver = self._solver(PolicyConfig(enabled=True), skew_prices=False)
        pods = make_pods(10, requests={"cpu": "500m"})
        snapshot = solver.encode(pods)
        prep = solver.prepare_encoded(snapshot)
        outputs = solver.run_prepared(prep)
        results_on = solver.decode(snapshot, outputs)
        solver.policy = None
        results_off = solver.decode(snapshot, outputs)
        on = {
            tuple(sorted(p.uid for p in d.pods)) for d in results_on.new_nodes
        }
        off = {
            tuple(sorted(p.uid for p in d.pods)) for d in results_off.new_nodes
        }
        assert on == off
        for decision in results_on.new_nodes:
            # with every price equal, the selected price equals the
            # provider's cheapest-offering price for the node's viable set
            cheapest = min(
                o.price
                for name in decision.instance_type_names
                for o in solver._it_by_name[name].offerings.available()
            )
            assert decision.selected["price"] == pytest.approx(cheapest)


# -- policy-aware consolidation ------------------------------------------------


class TestConsolidationCostDelta:
    """Fewest-nodes vs cheapest-fleet genuinely disagree: a crafted sweep
    where the largest prefix needs a pricey replacement while a smaller
    prefix deletes outright.  Node-count scoring (policy off) must take the
    big REPLACE; cost-delta scoring (policy on) must take the small DELETE."""

    def _fixture(self, policy):
        from karpenter_core_tpu.solver.consolidation import TPUConsolidationSearch

        catalog = [
            fake_cp.new_instance_type(
                "big", resources={"cpu": 8.0},
                offerings=[Offering("on-demand", "test-zone-1", 10.0)],
            ),
            fake_cp.new_instance_type(
                "small", resources={"cpu": 2.0},
                offerings=[Offering("on-demand", "test-zone-1", 1.0)],
            ),
            fake_cp.new_instance_type(
                "mid", resources={"cpu": 6.0},
                offerings=[Offering("on-demand", "test-zone-1", 9.5)],
            ),
        ]
        provider = fake_cp.FakeCloudProvider(catalog)
        prov = make_provisioner(name="default")
        search = TPUConsolidationSearch(provider, [prov], policy=policy)
        snapshot = search.solver.encode([make_pod(requests={"cpu": "100m"})])
        by_name = {it.name: it for it in catalog}
        from karpenter_core_tpu.testing import make_node

        def candidate(name, it_name):
            return CandidateNode(
                node=make_node(name=name),
                state_node=None,
                instance_type=by_name[it_name],
                capacity_type="on-demand",
                zone="test-zone-1",
                provisioner=prov,
                disruption_cost=0.0,
            )

        candidates = [candidate("n-big", "big"), candidate("n-small", "small")]
        return search, snapshot, candidates

    def _fake_sweep(self, snapshot):
        from karpenter_core_tpu.ops.consolidate import SweepOutputs

        n_i = len(snapshot.it_names)
        n_z = len(snapshot.zones)
        n_ct = len(snapshot.capacity_types)
        viable = np.zeros((2, 1, n_i), dtype=bool)
        viable[1, 0, snapshot.it_names.index("mid")] = True
        zone = np.zeros((2, 1, n_z), dtype=bool)
        zone[1, 0, snapshot.zones.index("test-zone-1")] = True
        ct = np.zeros((2, 1, n_ct), dtype=bool)
        ct[1, 0, snapshot.capacity_types.index("on-demand")] = True
        used = np.zeros((2, 1, len(snapshot.resources)), dtype=np.float32)
        used[1, 0, snapshot.resources.index("cpu")] = 4.0
        return SweepOutputs(
            n_new=np.array([0, 1], dtype=np.int32),
            failed=np.zeros(2, dtype=np.int32),
            used_uninitialized=np.zeros(2, dtype=bool),
            new_viable=viable,
            new_zone=zone,
            new_ct=ct,
            new_used=used,
            new_tmpl=np.zeros((2, 1), dtype=np.int32),
            new_cost=np.array([0.0, 9.5], dtype=np.float32),
        )

    def _evaluate(self, policy, monkeypatch):
        import karpenter_core_tpu.solver.consolidation as consolidation_mod

        search, snapshot, candidates = self._fixture(policy)
        fake = self._fake_sweep(snapshot)
        monkeypatch.setattr(
            consolidation_mod.consolidate_ops, "sweep_pass",
            lambda *a, **k: fake,
        )
        return search._evaluate_sweep(
            snapshot, None, np.array([1, 2], dtype=np.int32), candidates,
        ).best()

    def test_node_count_scoring_takes_the_largest_prefix(self, monkeypatch):
        best, best_k = self._evaluate(None, monkeypatch)
        assert best_k == 2 and best.action == Action.REPLACE

    def test_cost_delta_scoring_takes_the_cheaper_fleet(self, monkeypatch):
        # DELETE of n-big saves 10.0; REPLACE of both saves 11 - 9.5 = 1.5
        best, best_k = self._evaluate(
            PolicyConfig(enabled=True), monkeypatch
        )
        assert best_k == 1 and best.action == Action.DELETE
        assert [n.name for n in best.nodes_to_remove] == ["n-big"]

    def test_cost_delta_still_prefers_replace_when_it_saves_more(self, monkeypatch):
        import karpenter_core_tpu.solver.consolidation as consolidation_mod

        search, snapshot, candidates = self._fixture(PolicyConfig(enabled=True))
        fake = self._fake_sweep(snapshot)
        # make the replacement nearly free: REPLACE saving 11 - 0.5 = 10.5
        fake = fake._replace(new_cost=np.array([0.0, 0.5], dtype=np.float32))
        monkeypatch.setattr(
            consolidation_mod.consolidate_ops, "sweep_pass",
            lambda *a, **k: fake,
        )
        best, best_k = search._evaluate_sweep(
            snapshot, None, np.array([1, 2], dtype=np.int32), candidates,
        ).best()
        assert best_k == 2 and best.action == Action.REPLACE


# -- counter-proposals ---------------------------------------------------------


class TestCounterProposal:
    def _catalog(self):
        return [
            fake_cp.new_instance_type(
                "cheap-small", resources={"cpu": 4.0},
                offerings=[Offering("on-demand", "test-zone-1", 1.0)],
            ),
            fake_cp.new_instance_type(
                "pricey-big", resources={"cpu": 32.0},
                offerings=[Offering("on-demand", "test-zone-1", 20.0)],
            ),
        ]

    def test_unschedulable_pod_gets_bounded_resize_hint(self):
        config = PolicyConfig(enabled=True, counter_proposals=True)
        # 40 cpu fits nothing; shrinking ~22% fits pricey-big — in bounds
        hint = propose_resize({"cpu": 40.0}, self._catalog(), config)
        assert hint is not None
        assert hint.instance_type == "pricey-big"
        assert hint.current_price == float("inf")
        assert 0.0 < hint.shrink_fraction <= config.max_resize_fraction
        assert hint.suggested_requests["cpu"] < 40.0
        assert "unschedulable" in hint.message()

    def test_shrink_beyond_bound_proposes_nothing(self):
        config = PolicyConfig(
            enabled=True, counter_proposals=True, max_resize_fraction=0.1
        )
        assert propose_resize({"cpu": 40.0}, self._catalog(), config) is None

    def test_cheaper_fit_hint_requires_strict_saving(self):
        config = PolicyConfig(enabled=True, counter_proposals=True)
        # 5 cpu fits pricey-big (20.0) now; shrinking ~22% fits cheap-small
        # (1.0) — strictly cheaper, so the hint fires with both prices
        hint = propose_resize({"cpu": 5.0}, self._catalog(), config)
        assert hint is not None
        assert hint.instance_type == "cheap-small"
        assert hint.current_price == pytest.approx(20.0)
        # ...but a pod that already fits the cheapest type gets nothing
        assert propose_resize({"cpu": 2.0}, self._catalog(), config) is None

    def test_controller_emits_shape_hint_event_and_counter(self):
        from karpenter_core_tpu.controllers.provisioning import (
            POLICY_COUNTERPROPOSALS,
        )

        env = harness.make_environment()
        env.kube.create(make_provisioner(
            name="default",
            policy={"enabled": True, "counterProposals": True},
        ))
        before = POLICY_COUNTERPROPOSALS.labels("resize").value
        # 24 cpu exceeds every default type; arm-instance-type (16 cpu)
        # fits after a ~34% shrink — in the default 50% bound
        pod = make_pod(requests={"cpu": 24})
        result = harness.expect_provisioned(env, pod)
        assert result[pod.uid] is None  # genuinely unschedulable
        hints = [e for e in env.recorder.events if e.reason == "ShapeHint"]
        assert hints and "arm-instance-type" in hints[0].message
        assert POLICY_COUNTERPROPOSALS.labels("resize").value == before + 1

    def test_kill_switch_silences_counterproposals(self, monkeypatch):
        from karpenter_core_tpu.controllers.provisioning import (
            POLICY_COUNTERPROPOSALS,
        )

        monkeypatch.setenv("KC_POLICY", "0")
        env = harness.make_environment()
        env.kube.create(make_provisioner(
            name="default",
            policy={"enabled": True, "counterProposals": True},
        ))
        before = POLICY_COUNTERPROPOSALS.labels("resize").value
        pod = make_pod(requests={"cpu": 24})
        harness.expect_provisioned(env, pod)
        assert not [e for e in env.recorder.events if e.reason == "ShapeHint"]
        assert POLICY_COUNTERPROPOSALS.labels("resize").value == before


# -- provider offering realism -------------------------------------------------


class TestFakeProviderKnobs:
    def test_set_price_updates_the_live_catalog(self):
        provider = fake_cp.FakeCloudProvider()
        n = provider.set_price(
            "default-instance-type", 42.0, capacity_type="spot", zone="test-zone-1"
        )
        assert n == 1
        it = next(
            i for i in provider.get_instance_types(None)
            if i.name == "default-instance-type"
        )
        assert it.offerings.get("spot", "test-zone-1").price == 42.0
        # untouched offerings keep their price
        assert it.offerings.get("on-demand", "test-zone-1").price != 42.0

    def test_interruption_rate_feeds_risk_planes(self):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(3))
        provider.set_interruption_rate("fake-it-1", 0.4)
        its = provider.get_instance_types(None)
        planes = build_planes(
            [it.name for it in its],
            ["test-zone-1", "test-zone-2", "test-zone-3"],
            ["on-demand", "spot"],
            {it.name: it for it in its},
            provider=provider,
        )
        spot = 1  # sorted capacity types
        assert planes.risk[1, 0, spot] == pytest.approx(0.4)
        assert planes.risk[0, 0, spot] == 0.0
        # a type actively failing creates (capacity_errors) reads as high risk
        provider.capacity_errors["fake-it-0"] = 2
        planes = build_planes(
            [it.name for it in its],
            ["test-zone-1", "test-zone-2", "test-zone-3"],
            ["on-demand", "spot"],
            {it.name: it for it in its},
            provider=provider,
        )
        assert planes.risk[0, 0, spot] >= 0.9

    def test_interrupt_spot_feeds_capacity_errors_deterministically(self):
        from karpenter_core_tpu.utils.retry import DeterministicRNG

        def run(seed):
            provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(4))
            for name in ("fake-it-0", "fake-it-2"):
                provider.set_interruption_rate(name, 0.9)
            interrupted = provider.interrupt_spot(DeterministicRNG(seed))
            return interrupted, dict(provider.capacity_errors)

        a_types, a_errors = run(7)
        b_types, b_errors = run(7)
        assert a_types == b_types and a_errors == b_errors
        assert set(a_errors) <= {"fake-it-0", "fake-it-2"}
        # rate ~0.9 on two types: at least one interruption at this seed
        assert a_types

    def test_policy_input_digest_sensitivity(self):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(2))
        by_name = {"p": provider.get_instance_types(None)}
        d0 = policy_input_digest(by_name)
        assert d0 == policy_input_digest(by_name)
        provider.set_price("fake-it-0", 123.0)
        d1 = policy_input_digest(by_name)
        assert d1 != d0
        provider.set_interruption_rate("fake-it-1", 0.3)
        assert policy_input_digest(by_name) != d1
        # config knobs are part of the digest too
        assert policy_input_digest(
            by_name, PolicyConfig(enabled=True)
        ) != policy_input_digest(by_name)


# -- incremental-session escalation (the satellite regression) -----------------


class TestPolicyDigestEscalation:
    def _session(self):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(4))
        solver = TPUSolver(provider, [make_provisioner(name="p")])
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0, max_delta_fraction=0.9),
        )
        ingest = PodIngest()
        ingest.add_all(make_pods(10, requests={"cpu": "500m"}))
        return provider, session, ingest

    def test_price_update_escalates_to_full(self):
        provider, session, ingest = self._session()
        session.solve(ingest)
        assert session.last_mode == MODE_FULL and session.last_reason == "first"
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA
        # the spot market moves between reconciles
        provider.set_price("fake-it-0", 77.0)
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_FULL
        assert session.last_reason.startswith("supply-changed")
        # lineage re-anchors: steady churn repairs again afterwards
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA

    def test_interruption_rate_update_escalates_to_full(self):
        provider, session, ingest = self._session()
        session.solve(ingest)
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA
        provider.set_interruption_rate("fake-it-1", 0.6)
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_FULL
        assert session.last_reason.startswith("supply-changed")

    def test_capacity_error_transition_escalates_to_full(self):
        """A type starting to ICE is a live risk change the no-encode digest
        must see (the risk planes read it at encode time); a count merely
        ticking down stays in delta mode — only the pending↔clear
        transitions escalate, matching what the plane encodes."""
        provider, session, ingest = self._session()
        session.solve(ingest)
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA
        provider.capacity_errors["fake-it-0"] = 3
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_FULL
        assert session.last_reason.startswith("supply-changed")
        # 3 -> 2: still pending, same binary state — repairs resume
        provider.capacity_errors["fake-it-0"] = 2
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA
        # pending -> clear: the risk prior vanishes, escalate again
        provider.capacity_errors["fake-it-0"] = 0
        ingest.add(make_pod(requests={"cpu": "500m"}))
        session.solve(ingest)
        assert session.last_mode == MODE_FULL

    def test_policy_plane_group_digests_the_price_sheet(self):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(3))
        solver = TPUSolver(provider, [make_provisioner(name="p")])
        pods = make_pods(4, requests={"cpu": "500m"})
        before = store_mod.snapshot_digests(solver.encode(pods))
        provider.set_price("fake-it-0", 55.0, capacity_type="spot")
        after = store_mod.snapshot_digests(solver.encode(pods))
        assert after["policy"] != before["policy"]
        # the price sheet is catalog input too; structure-only groups hold
        assert after["templates"] == before["templates"]
        assert after["vocab"] == before["vocab"]
        assert after["groups"] == before["groups"]


# -- risk-weighted replica variants (parallel.mesh) ----------------------------


class TestPolicyMonteCarlo:
    def test_zero_risk_replicas_agree(self):
        from karpenter_core_tpu.parallel import mesh as mesh_ops

        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(4))
        solver = TPUSolver(provider, [make_provisioner(name="p")])
        snapshot = solver.encode(make_pods(12, requests={"cpu": "500m"}))
        out = mesh_ops.policy_monte_carlo(snapshot, n_replicas=8, seed=3)
        assert out["replicas"] == 8
        assert out["feasible_replicas"] == 8
        assert (out["failed"] == 0).all()
        # zero risk: every sampled outcome is the unperturbed solve
        assert np.allclose(out["cost"], out["cost"][0])
        assert out["best_cost"] == pytest.approx(out["cost_mean"])

    def test_risky_offerings_raise_expected_cost(self):
        from karpenter_core_tpu.parallel import mesh as mesh_ops

        def study(rate):
            provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(4))
            if rate:
                for it in provider.get_instance_types(None):
                    provider.set_interruption_rate(it.name, rate)
            solver = TPUSolver(provider, [make_provisioner(name="p")])
            snapshot = solver.encode(make_pods(12, requests={"cpu": "500m"}))
            return mesh_ops.policy_monte_carlo(snapshot, n_replicas=8, seed=5)

        calm = study(0.0)
        stormy = study(0.95)
        # interruptions remove the cheap spot cells (or strand pods): the
        # risk-adjusted expectation can only move up
        assert stormy["expected_cost"] >= calm["expected_cost"]
        assert stormy["best_replica"] in range(8)


# -- soak: the spot-churn smoke ------------------------------------------------


class TestSpotChurnSoak:
    def test_spot_churn_meets_slo_with_fleet_cost_probe(self):
        from karpenter_core_tpu.soak import runner, scenarios

        report = runner.run_scenario(scenarios.build("spot-churn"))
        verdict = report["verdict"]
        assert verdict["passed"] is True, json.dumps(verdict, indent=2)
        assert verdict["converged"] is True
        rules = {r["probe"] for r in verdict["slo"]}
        assert "fleet_cost_per_tick" in rules
        probe = verdict["probes"]["fleet_cost_per_tick"]
        assert probe["max"] > 0.0  # the fleet was actually priced
        # the chaos capacity faults really fired (spot interruptions)
        assert report["diagnostics"]["chaos"]["fired"].get("cloud.create", 0) >= 1
        rendered = REGISTRY.render()
        assert 'probe="fleet_cost_per_tick"' in rendered
