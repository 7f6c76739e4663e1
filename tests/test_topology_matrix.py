"""Topology scenario matrix, ported from the reference's largest scheduling
suite (/root/reference/pkg/controllers/provisioning/scheduling/topology_test.go,
72 cases).  Every kernel-supported shape runs through the compare() parity
harness (host oracle AND TPU kernel on identical inputs); shapes the kernel
routes to the host path assert host behavior and the routing itself.
"""

import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    OP_EXISTS,
    OP_IN,
    OP_NOT_IN,
    LabelSelector,
    LabelSelectorRequirement,
    NodeSelectorRequirement,
    PodAffinityTerm,
    Toleration,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.snapshot import KernelUnsupported, classify_pods
from karpenter_core_tpu.testing import make_pod, make_pods, make_provisioner
from tests.test_tpu_solver import compare, host_solve, tpu_solve

# compare() parity runs the kernel per case -- the slow tier (`make test-all`)
pytestmark = pytest.mark.compile

ZONE = labels_api.LABEL_TOPOLOGY_ZONE
HOSTNAME = labels_api.LABEL_HOSTNAME
CT = labels_api.LABEL_CAPACITY_TYPE
ARCH = labels_api.LABEL_ARCH_STABLE

def spread(key=ZONE, skew=1, labels=None, when="DoNotSchedule", expressions=None):
    selector = LabelSelector(
        match_labels=dict(labels or {"app": "web"}),
        match_expressions=list(expressions or []),
    )
    return TopologySpreadConstraint(
        max_skew=skew, topology_key=key, when_unsatisfiable=when, label_selector=selector
    )

def zone_counts(result):
    counts = {}
    for node in result.new_nodes:
        assert len(node.zones) == 1, "spread nodes must commit to one zone"
        counts[node.zones[0]] = counts.get(node.zones[0], 0) + len(node.pods)
    return counts

class TestZonalSpread:
    """topology_test.go:66-378 — the zonal skew matrix."""

    def test_balance_pods_across_zones_match_labels(self):
        host, tpu = compare(
            lambda: make_pods(
                6, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread()],
            )
        )
        assert sorted(zone_counts(tpu).values()) == [2, 2, 2]

    def test_balance_pods_across_zones_match_expressions(self):
        host, tpu = compare(
            lambda: make_pods(
                6, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[
                    spread(labels={}, expressions=[
                        LabelSelectorRequirement(key="app", operator=OP_IN, values=["web"])
                    ])
                ],
            )
        )
        assert sorted(zone_counts(tpu).values()) == [2, 2, 2]

    def test_respects_provisioner_zonal_constraints(self):
        # topology_test.go:106 — provisioner spanning all three zones: 4 pods
        # land [1, 1, 2]
        prov = make_provisioner(
            requirements=[
                NodeSelectorRequirement(
                    ZONE, OP_IN, ["test-zone-1", "test-zone-2", "test-zone-3"]
                )
            ]
        )
        host, tpu = compare(
            lambda: make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread()],
            ),
            provisioners=[prov],
        )
        assert sorted(zone_counts(tpu).values()) == [1, 1, 2]

    def test_unreachable_domain_caps_reachable_zones(self):
        # topology_test.go:124-162 semantics: the skew min is measured over
        # ALL the pod's domains — a zone no provisioner can serve stays at
        # its count forever, capping every reachable zone at min + skew
        prov = make_provisioner(
            requirements=[
                NodeSelectorRequirement(ZONE, OP_IN, ["test-zone-1", "test-zone-2"])
            ]
        )
        host, tpu = compare(
            lambda: make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread()],
            ),
            provisioners=[prov],
        )
        # zone-3 is in the pod's domain universe (the catalog spans it) but
        # unreachable: only one pod per reachable zone fits under skew 1
        assert len(tpu.failed_pods) == 2
        assert sorted(zone_counts(tpu).values()) == [1, 1]

    def test_unknown_topology_key_fails_pod(self):
        # topology_test.go:38 "should ignore unknown topology keys" — the
        # reference leaves the pod pending; both our paths fail it
        pods = [
            make_pod(
                labels={"app": "web"},
                topology_spread=[spread(key="unknown.io/key")],
            )
        ]
        host = host_solve(pods, [make_provisioner()])
        assert len(host.failed_pods) == 1
        with pytest.raises(KernelUnsupported):
            classify_pods(pods)

    def test_max_skew_respected_at_every_count(self):
        for n in (3, 5, 7, 9, 11):
            host, tpu = compare(
                lambda n=n: make_pods(
                    n, labels={"app": "web"}, requests={"cpu": "10m"},
                    topology_spread=[spread()],
                )
            )
            counts = zone_counts(tpu)
            assert max(counts.values()) - min(counts.values() or [0]) <= 1

    def test_larger_max_skew_allows_imbalance(self):
        host, tpu = compare(
            lambda: make_pods(
                9, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread(skew=4)],
            )
        )
        counts = zone_counts(tpu)
        assert max(counts.values()) - min(counts.values()) <= 4

    def test_schedule_anyway_spreads_do_not_block(self):
        # ScheduleAnyway is a preference: all pods land even when skew breaks
        prov = make_provisioner(
            requirements=[NodeSelectorRequirement(ZONE, OP_IN, ["test-zone-1"])]
        )
        host, tpu = compare(
            lambda: make_pods(
                5, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread(when="ScheduleAnyway")],
            ),
            provisioners=[prov],
        )
        assert not tpu.failed_pods

    def test_no_label_selector_matches_all(self):
        # topology_test.go:341 — a selector-less spread counts every pod
        def pods():
            return [
                make_pod(
                    labels={"app": f"a{i}"}, requests={"cpu": "10m"},
                    topology_spread=[
                        TopologySpreadConstraint(max_skew=1, topology_key=ZONE)
                    ],
                )
                for i in range(6)
            ]

        host = host_solve(pods(), [make_provisioner()])
        assert not host.failed_pods
        # selector-less spreads don't self-select -> kernel admissible-mask
        # path handles them since round 2 (counts never move)
        tpu = tpu_solve(pods(), [make_provisioner()])
        assert not tpu.failed_pods

    def test_interdependent_selectors(self):
        # topology_test.go:353 — two deployments sharing one spread selector
        def pods():
            return make_pods(
                3, labels={"app": "web", "tier": "a"}, requests={"cpu": "10m"},
                topology_spread=[spread()],
            ) + make_pods(
                3, labels={"app": "web", "tier": "b"}, requests={"cpu": "10m"},
                topology_spread=[spread()],
            )

        host, tpu = compare(pods)
        assert sorted(zone_counts(tpu).values()) == [2, 2, 2]

class TestHostnameSpread:
    """topology_test.go:380-490."""

    def test_balance_pods_across_nodes(self):
        host, tpu = compare(
            lambda: make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread(key=HOSTNAME)],
            )
        )
        assert all(len(n.pods) == 1 for n in tpu.new_nodes)

    def test_balance_up_to_max_skew(self):
        host, tpu = compare(
            lambda: make_pods(
                8, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread(key=HOSTNAME, skew=4)],
            )
        )
        assert all(len(n.pods) <= 4 for n in tpu.new_nodes)

    def test_multiple_deployments_hostname_spread(self):
        # topology_test.go:412 — two deployments, each hostname-spread
        def pods():
            out = []
            for app in ("a", "b"):
                out += make_pods(
                    3, labels={"app": app}, requests={"cpu": "10m"},
                    topology_spread=[spread(key=HOSTNAME, labels={"app": app})],
                )
            return out

        host, tpu = compare(pods)
        for node in tpu.new_nodes:
            per_app = {}
            for pod in node.pods:
                app = pod.metadata.labels["app"]
                per_app[app] = per_app.get(app, 0) + 1
            assert all(v <= 1 for v in per_app.values())

class TestCapacityTypeAndArchSpread:
    """topology_test.go:492-783 — spreads over capacity-type and arch keys
    are region-class custom topologies the kernel doesn't model: they must
    route to the host path, and the host must honor them."""

    def test_capacity_type_spread_routes_to_host(self):
        """A capacity-type SPREAD is the kernel's
        (``kc.phase.ct_spread``; the in-process incremental session still
        sends it to the host, tests/test_tpu_provisioning.py); a pod
        (anti-)affinity term on capacity type still routes to the host."""
        (cls,) = classify_pods([
            make_pod(
                labels={"app": "web"},
                topology_spread=[spread(key=CT)],
            )
        ])
        assert cls.ct_spread is not None and cls.ct_spread.key == CT
        with pytest.raises(KernelUnsupported):
            classify_pods([
                make_pod(labels={"app": "web"}, pod_anti_affinity=[PodAffinityTerm(
                    topology_key=CT, label_selector=LabelSelector(match_labels={"app": "web"}))])
            ])

    def test_capacity_type_spread_host_balances(self):
        def pods():
            return make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread(key=CT)],
            )

        host = host_solve(pods(), [make_provisioner()])
        assert not host.failed_pods
        ct_counts = {}
        for node in host.new_nodes:
            reqs = node.requirements
            if reqs.has(CT):
                committed = tuple(sorted(reqs.get(CT).values_list()))
                ct_counts[committed] = ct_counts.get(committed, 0) + len(node.pods)
        if len(ct_counts) > 1:
            assert max(ct_counts.values()) - min(ct_counts.values()) <= 1

    def test_arch_spread_host_balances(self):
        def pods():
            return make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread(key=ARCH)],
            )

        host = host_solve(
            pods(), [make_provisioner()], fake_cp.instance_types_assorted()[:200]
        )
        assert not host.failed_pods

class TestCombinedConstraints:
    """topology_test.go:785-1029 — zone and hostname spreads together."""

    def test_zone_and_hostname_spread_together(self):
        host, tpu = compare(
            lambda: make_pods(
                6, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread(key=ZONE), spread(key=HOSTNAME)],
            )
        )
        assert sorted(zone_counts(tpu).values()) == [2, 2, 2]
        assert all(len(n.pods) == 1 for n in tpu.new_nodes)

    def test_spread_across_provisioner_requirements(self):
        # topology_test.go:825 adapted — single-zone provisioners covering
        # every zone balance the spread across them
        provs = [
            make_provisioner(
                name=f"zone-{i}",
                requirements=[NodeSelectorRequirement(ZONE, OP_IN, [f"test-zone-{i}"])],
            )
            for i in (1, 2, 3)
        ]
        host, tpu = compare(
            lambda: make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread()],
            ),
            provisioners=provs,
        )
        assert sorted(zone_counts(tpu).values()) == [1, 1, 2]

    def test_partial_provisioner_coverage_caps_at_unreachable_min(self):
        # two single-zone provisioners over a three-zone catalog: zone-3
        # stays at 0 forever, capping each covered zone at skew
        provs = [
            make_provisioner(
                name=f"zone-{i}",
                requirements=[NodeSelectorRequirement(ZONE, OP_IN, [f"test-zone-{i}"])],
            )
            for i in (1, 2)
        ]
        host, tpu = compare(
            lambda: make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                topology_spread=[spread()],
            ),
            provisioners=provs,
        )
        assert sorted(zone_counts(tpu).values()) == [1, 1]
        assert len(tpu.failed_pods) == 2

class TestSpreadLimitedByNodeConstraints:
    """topology_test.go:1031-1194 — the pod's own node constraints shrink the
    spread's domain universe."""

    def test_limit_spread_by_node_selector(self):
        host, tpu = compare(
            lambda: make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                node_selector={ZONE: "test-zone-1"},
                topology_spread=[spread()],
            )
        )
        assert set(zone_counts(tpu)) == {"test-zone-1"}
        assert not tpu.failed_pods

    def test_limit_spread_by_node_requirements(self):
        host, tpu = compare(
            lambda: make_pods(
                6, labels={"app": "web"}, requests={"cpu": "10m"},
                node_requirements=[
                    NodeSelectorRequirement(ZONE, OP_IN, ["test-zone-1", "test-zone-2"])
                ],
                topology_spread=[spread()],
            )
        )
        counts = zone_counts(tpu)
        assert set(counts) <= {"test-zone-1", "test-zone-2"}
        assert sorted(counts.values()) == [3, 3]

    def test_limit_spread_by_not_in(self):
        host, tpu = compare(
            lambda: make_pods(
                4, labels={"app": "web"}, requests={"cpu": "10m"},
                node_requirements=[
                    NodeSelectorRequirement(ZONE, OP_NOT_IN, ["test-zone-3"])
                ],
                topology_spread=[spread()],
            )
        )
        assert "test-zone-3" not in zone_counts(tpu)

class TestPodAffinity:
    """topology_test.go:1196-1510."""

    def test_empty_affinity_schedules(self):
        compare(lambda: make_pods(2, requests={"cpu": "10m"}))

    def test_self_affinity_hostname(self):
        # topology_test.go:1282 — all pods share one node
        def pods():
            return make_pods(
                3, labels={"app": "db"}, requests={"cpu": "10m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=HOSTNAME,
                        label_selector=LabelSelector(match_labels={"app": "db"}),
                    )
                ],
            )

        host, tpu = compare(pods)
        assert len(tpu.new_nodes) == 1
        assert len(tpu.new_nodes[0].pods) == 3

    def test_self_affinity_zone(self):
        def pods():
            return make_pods(
                4, labels={"app": "db"}, requests={"cpu": "10m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "db"}),
                    )
                ],
            )

        host, tpu = compare(pods)
        zones = {z for n in tpu.new_nodes for z in n.zones}
        assert len(zones) == 1

    def test_self_affinity_zone_with_constraint(self):
        # topology_test.go:1414 — self zone affinity + zone selector
        def pods():
            return make_pods(
                3, labels={"app": "db"}, requests={"cpu": "10m"},
                node_selector={ZONE: "test-zone-2"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "db"}),
                    )
                ],
            )

        host, tpu = compare(pods)
        assert {z for n in tpu.new_nodes for z in n.zones} == {"test-zone-2"}

    def test_affinity_to_nonexistent_pod_fails(self):
        # topology_test.go:1924
        def pods():
            return make_pods(
                2, labels={"app": "fol"}, requests={"cpu": "10m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "ghost"}),
                    )
                ],
            )

        host, tpu = compare(pods)
        assert len(tpu.failed_pods) == 2

    def test_affinity_constrained_target(self):
        # topology_test.go:1974 — followers land in the target's zone
        def pods():
            targets = make_pods(
                2, labels={"app": "tgt"}, requests={"cpu": "10m"},
                node_selector={ZONE: "test-zone-3"},
            )
            followers = make_pods(
                3, labels={"app": "fol"}, requests={"cpu": "10m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "tgt"}),
                    )
                ],
            )
            return targets + followers

        host, tpu = compare(pods)
        for node in tpu.new_nodes:
            if any(p.metadata.labels.get("app") == "fol" for p in node.pods):
                assert node.zones == ["test-zone-3"]

    def test_multiple_dependent_affinities(self):
        # topology_test.go:2003 — a -> b -> c chain colocates (within the
        # kernel's pass budget)
        def pods():
            a = make_pods(1, labels={"app": "a"}, requests={"cpu": "10m"},
                          node_selector={ZONE: "test-zone-1"})
            b = make_pods(
                1, labels={"app": "b"}, requests={"cpu": "10m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "a"}),
                    )
                ],
            )
            c = make_pods(
                1, labels={"app": "c"}, requests={"cpu": "10m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "b"}),
                    )
                ],
            )
            return a + b + c

        host, tpu = compare(pods)
        assert not tpu.failed_pods
        assert {z for n in tpu.new_nodes for z in n.zones} == {"test-zone-1"}

    def test_unsatisfiable_dependency_fails(self):
        # topology_test.go:2037 — follower requires a zone its target can't be in
        def pods():
            targets = make_pods(
                1, labels={"app": "tgt"}, requests={"cpu": "10m"},
                node_selector={ZONE: "test-zone-1"},
            )
            followers = make_pods(
                2, labels={"app": "fol"}, requests={"cpu": "10m"},
                node_selector={ZONE: "test-zone-2"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "tgt"}),
                    )
                ],
            )
            return targets + followers

        host, tpu = compare(pods)
        assert len(tpu.failed_pods) == 2

    def test_preferred_affinity_violation_allowed(self):
        # topology_test.go:1445 — preferred affinity to a ghost pod must not
        # block scheduling (relaxation drops it on the host; the kernel never
        # models preferences)
        def pods():
            return make_pods(
                2, labels={"app": "x"}, requests={"cpu": "10m"},
                pod_affinity_preferred=[
                    WeightedPodAffinityTerm(
                        weight=1,
                        pod_affinity_term=PodAffinityTerm(
                            topology_key=ZONE,
                            label_selector=LabelSelector(match_labels={"app": "ghost"}),
                        ),
                    )
                ],
            )

        host, tpu = compare(pods)
        assert not tpu.failed_pods

    def test_preferred_anti_affinity_violation_allowed(self):
        # topology_test.go:1478
        def pods():
            return make_pods(
                4, labels={"app": "x"}, requests={"cpu": "10m"},
                pod_anti_affinity_preferred=[
                    WeightedPodAffinityTerm(
                        weight=1,
                        pod_affinity_term=PodAffinityTerm(
                            topology_key=ZONE,
                            label_selector=LabelSelector(match_labels={"app": "x"}),
                        ),
                    )
                ],
            )

        host, tpu = compare(pods)
        assert not tpu.failed_pods

class TestPodAntiAffinity:
    """topology_test.go:1511-1923."""

    def test_simple_hostname_anti_affinity_separates(self):
        def pods():
            return make_pods(
                3, labels={"app": "db"}, requests={"cpu": "10m"},
                pod_anti_affinity=[
                    PodAffinityTerm(
                        topology_key=HOSTNAME,
                        label_selector=LabelSelector(match_labels={"app": "db"}),
                    )
                ],
            )

        host, tpu = compare(pods)
        assert all(len(n.pods) == 1 for n in tpu.new_nodes)

    def test_zone_anti_affinity_not_violated(self):
        # required zonal anti is in-kernel since round 5 with zone-committal
        # phases: the kernel places one member per admissible zone in batch
        # one (nodes pinned to distinct zones — never a violation), while
        # the host's record-time snapshots reach that fixpoint one pod per
        # batch (topology_test.go:1879-1923).  The parity fuzzer pins the
        # >=-with-validity contract; here the placements themselves matter.
        def pods():
            return make_pods(
                2, labels={"app": "db"}, requests={"cpu": "10m"},
                pod_anti_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "db"}),
                    )
                ],
            )

        host = host_solve(pods(), [make_provisioner()])
        assert sum(len(n.pods) for n in host.new_nodes) == 1
        assert len(host.failed_pods) == 1
        tpu = tpu_solve(pods(), [make_provisioner()])
        placed = [n for n in tpu.new_nodes if n.pods]
        assert sum(len(n.pods) for n in placed) == 2
        zones = [tuple(n.zones) for n in placed]
        assert all(len(z) == 1 for z in zones) and len(set(zones)) == 2, zones

    def test_inverse_anti_affinity_blocks_target(self):
        # topology_test.go:1677 — an anti-affinity OWNER repels the pods its
        # selector matches even though those pods carry no anti term
        def pods():
            owner = make_pods(
                1, labels={"app": "lonely"}, requests={"cpu": "10m"},
                pod_anti_affinity=[
                    PodAffinityTerm(
                        topology_key=HOSTNAME,
                        label_selector=LabelSelector(match_labels={"app": "noisy"}),
                    )
                ],
            )
            noisy = make_pods(2, labels={"app": "noisy"}, requests={"cpu": "10m"})
            return owner + noisy

        host, tpu = compare(pods)
        for node in tpu.new_nodes:
            apps = {p.metadata.labels["app"] for p in node.pods}
            assert apps != {"lonely", "noisy"}

    def test_anti_affinity_arch_key_routes_to_host(self):
        # arch-key anti-affinity is a custom-key topology: host path only
        pods = [
            make_pod(
                labels={"app": "db"},
                pod_anti_affinity=[
                    PodAffinityTerm(
                        topology_key=ARCH,
                        label_selector=LabelSelector(match_labels={"app": "db"}),
                    )
                ],
            )
        ]
        with pytest.raises(KernelUnsupported):
            classify_pods(pods)

    def test_exists_selector_anti_affinity(self):
        # anti-affinity with an Exists expression selector
        def pods():
            return make_pods(
                3, labels={"team": "a"}, requests={"cpu": "10m"},
                pod_anti_affinity=[
                    PodAffinityTerm(
                        topology_key=HOSTNAME,
                        label_selector=LabelSelector(
                            match_expressions=[
                                LabelSelectorRequirement(key="team", operator=OP_EXISTS)
                            ]
                        ),
                    )
                ],
            )

        host, tpu = compare(pods)
        assert all(len(n.pods) == 1 for n in tpu.new_nodes)

class TestTolerationsAndTaints:
    """topology_test.go:2210-2256 tail cases."""

    def test_startup_taint_does_not_block(self):
        from karpenter_core_tpu.apis.objects import Taint

        prov = make_provisioner(startup_taints=[Taint("init.sh/agent", "true")])
        host, tpu = compare(
            lambda: make_pods(2, requests={"cpu": "10m"}), provisioners=[prov]
        )
        assert not tpu.failed_pods

    def test_tolerated_taints_schedule(self):
        from karpenter_core_tpu.apis.objects import Taint

        prov = make_provisioner(taints=[Taint("dedicated", "db")])
        host, tpu = compare(
            lambda: make_pods(
                2, requests={"cpu": "10m"},
                tolerations=[Toleration(key="dedicated", operator="Exists")],
            ),
            provisioners=[prov],
        )
        assert not tpu.failed_pods

class TestExistingPodCounting:
    """topology_test.go:124-162, 308-340 — countDomains seeding: pre-existing
    bound pods participate in spread counts; pods without matching labels or
    on domain-less nodes do not."""

    def _env(self):
        from karpenter_core_tpu.testing import make_node
        from karpenter_core_tpu.testing.harness import make_environment

        env = make_environment()
        env.kube.create(make_provisioner())
        return env, make_node

    def _node(self, env, make_node, name, zone):
        node = make_node(
            name=name,
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                labels_api.LABEL_CAPACITY_TYPE: "spot",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                labels_api.LABEL_TOPOLOGY_ZONE: zone,
            },
            allocatable={"cpu": 8, "memory": "8Gi", "pods": 20},
        )
        env.kube.create(node)
        return node

    def _solve(self, env, pods):
        from karpenter_core_tpu.solver.tpu import TPUSolver

        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        return solver.solve(
            pods,
            state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )

    def test_existing_matching_pods_seed_counts(self):
        env, make_node = self._env()
        n1 = self._node(env, make_node, "z1", "test-zone-1")
        self._node(env, make_node, "z2", "test-zone-2")
        self._node(env, make_node, "z3", "test-zone-3")
        # two matching pods already in zone-1: new spread pods avoid it until
        # the other zones catch up
        for _ in range(2):
            env.kube.create(
                make_pod(labels={"app": "web"}, requests={"cpu": "100m"},
                         node_name=n1.name, unschedulable=False)
            )
        new = make_pods(
            4, labels={"app": "web"}, requests={"cpu": "100m"},
            topology_spread=[spread()],
        )
        res = self._solve(env, new)
        assert not res.failed_pods
        placed_z1 = len(res.existing_assignments.get("z1", []))
        # zone-1 starts at 2; balancing 4 more lands [0, 2, 2]
        assert placed_z1 == 0

    def test_non_matching_existing_pods_do_not_count(self):
        env, make_node = self._env()
        n1 = self._node(env, make_node, "z1", "test-zone-1")
        self._node(env, make_node, "z2", "test-zone-2")
        self._node(env, make_node, "z3", "test-zone-3")
        for _ in range(3):  # different labels: invisible to the spread
            env.kube.create(
                make_pod(labels={"app": "other"}, requests={"cpu": "100m"},
                         node_name=n1.name, unschedulable=False)
            )
        new = make_pods(
            3, labels={"app": "web"}, requests={"cpu": "100m"},
            topology_spread=[spread()],
        )
        res = self._solve(env, new)
        assert not res.failed_pods
        per_zone = {}
        for name, placed in res.existing_assignments.items():
            per_zone[name] = len(placed)
        for node in res.new_nodes:
            per_zone[node.zones[0]] = per_zone.get(node.zones[0], 0) + len(node.pods)
        # counts start level: one pod per zone
        assert sorted(per_zone.values()) == [1, 1, 1]
