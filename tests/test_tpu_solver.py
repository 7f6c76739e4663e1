"""Parity tests: the TPU class-scan kernel vs the host oracle scheduler.

Aggregate outcomes (scheduled count, failed count, node count, zone skew) must
agree with the host Scheduler — the exact-semantics mirror of the reference —
on every kernel-supported scenario.  Tie-breaking (which specific node gets
which pod) is allowed to differ, exactly as the reference's own unstable sort
makes pod placement nondeterministic (scheduler.go:183).
"""

import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    OP_IN,
    OP_NOT_IN,
    LabelSelector,
    NodeSelectorRequirement,
    PodAffinityTerm,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.snapshot import KernelUnsupported, classify_pods
from karpenter_core_tpu.operator.kubeclient import KubeClient
from karpenter_core_tpu.solver.builder import build_scheduler
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_pod, make_pods, make_provisioner

# kernel/oracle parity compiles many solve shapes -- the slow tier (`make test-all`)
pytestmark = pytest.mark.compile

ZONE = labels_api.LABEL_TOPOLOGY_ZONE
HOSTNAME = labels_api.LABEL_HOSTNAME

def host_solve(pods, provisioners, instance_types=None):
    kube = KubeClient()
    for p in provisioners:
        kube.create(p)
    provider = fake_cp.FakeCloudProvider(instance_types)
    scheduler = build_scheduler(
        kube, provider, cluster=None, pods=pods, state_nodes=[], daemonset_pods=[]
    )
    return scheduler.solve(pods)

def tpu_solve(pods, provisioners, instance_types=None):
    provider = fake_cp.FakeCloudProvider(instance_types)
    solver = TPUSolver(provider, provisioners)
    return solver.solve(pods)

def compare(pods_factory, provisioners=None, instance_types=None):
    """Run both paths on identical inputs; compare aggregates."""
    provisioners = provisioners or [make_provisioner()]
    host = host_solve(pods_factory(), provisioners, instance_types)
    tpu = tpu_solve(pods_factory(), provisioners, instance_types)
    host_scheduled = sum(len(n.pods) for n in host.new_nodes)
    tpu_scheduled = sum(len(n.pods) for n in tpu.new_nodes)
    assert tpu_scheduled == host_scheduled, (
        f"scheduled: tpu={tpu_scheduled} host={host_scheduled}"
    )
    assert len(tpu.failed_pods) == len(host.failed_pods), (
        f"failed: tpu={len(tpu.failed_pods)} host={len(host.failed_pods)}"
    )
    assert len(tpu.new_nodes) == len(host.new_nodes), (
        f"nodes: tpu={len(tpu.new_nodes)} host={len(host.new_nodes)}"
    )
    return host, tpu

class TestKernelParity:
    def test_homogeneous_batch(self):
        compare(lambda: make_pods(40, requests={"cpu": "500m"}))

    def test_pod_count_limit(self):
        # default types cap at 5 pods/node
        compare(lambda: make_pods(17, requests={"cpu": "1m"}))

    def test_two_sizes(self):
        compare(
            lambda: make_pods(10, requests={"cpu": 2}) + make_pods(20, requests={"cpu": "250m"})
        )

    def test_impossible_pod(self):
        host, tpu = compare(
            lambda: make_pods(2, requests={"cpu": 10000}) + make_pods(3, requests={"cpu": 1})
        )
        assert len(tpu.failed_pods) == 2

    def test_gpu_resources_split(self):
        compare(
            lambda: [
                make_pod(requests={fake_cp.RESOURCE_GPU_VENDOR_A: 1}),
                make_pod(requests={fake_cp.RESOURCE_GPU_VENDOR_B: 1}),
                make_pod(requests={"cpu": 1}),
            ]
        )

    def test_zone_selector(self):
        host, tpu = compare(
            lambda: make_pods(4, node_selector={ZONE: "test-zone-2"}, requests={"cpu": "100m"})
        )
        for node in tpu.new_nodes:
            assert node.zones == ["test-zone-2"]

    def test_node_affinity_not_in(self):
        host, tpu = compare(
            lambda: make_pods(
                4,
                requests={"cpu": "100m"},
                node_requirements=[NodeSelectorRequirement(ZONE, OP_NOT_IN, ["test-zone-1"])],
            )
        )
        for node in tpu.new_nodes:
            assert "test-zone-1" not in node.zones

    def test_incompatible_zone(self):
        host, tpu = compare(
            lambda: make_pods(2, node_selector={ZONE: "nope"})
        )
        assert len(tpu.failed_pods) == 2

    def test_taints(self):
        tainted = make_provisioner(name="tainted", taints=[Taint("special", "true")])
        host, tpu = compare(lambda: make_pods(3), provisioners=[tainted])
        assert len(tpu.failed_pods) == 3

    def test_toleration_and_weight_order(self):
        heavy = make_provisioner(name="heavy", weight=100, taints=[Taint("special", "true")])
        light = make_provisioner(name="light", weight=1)
        host, tpu = compare(
            lambda: make_pods(
                3, tolerations=[Toleration(key="special", operator="Exists")]
            ),
            provisioners=[heavy, light],
        )
        assert all(n.provisioner_name == "heavy" for n in tpu.new_nodes)

    def test_custom_label_requirement(self):
        prov = make_provisioner(
            requirements=[NodeSelectorRequirement("team", OP_IN, ["a", "b"])]
        )
        compare(
            lambda: make_pods(
                3,
                requests={"cpu": "100m"},
                node_requirements=[NodeSelectorRequirement("team", OP_IN, ["a"])],
            ),
            provisioners=[prov],
        )

    def test_custom_label_undefined_fails(self):
        host, tpu = compare(
            lambda: make_pods(
                2, node_requirements=[NodeSelectorRequirement("team", OP_IN, ["a"])]
            )
        )
        assert len(tpu.failed_pods) == 2

def spread_pods(n, key=ZONE, max_skew=1, requests=None):
    return [
        make_pod(
            labels={"app": "web"},
            requests=requests or {"cpu": "10m"},
            topology_spread=[
                TopologySpreadConstraint(
                    max_skew=max_skew,
                    topology_key=key,
                    label_selector=LabelSelector(match_labels={"app": "web"}),
                )
            ],
        )
        for _ in range(n)
    ]

def anti_pods(n, key=HOSTNAME, requests=None):
    return [
        make_pod(
            labels={"app": "db"},
            requests=requests or {"cpu": "10m"},
            pod_anti_affinity=[
                PodAffinityTerm(
                    topology_key=key,
                    label_selector=LabelSelector(match_labels={"app": "db"}),
                )
            ],
        )
        for _ in range(n)
    ]

class TestKernelTopologyParity:
    def test_zonal_spread(self):
        host, tpu = compare(lambda: spread_pods(9))
        zone_counts = {}
        for node in tpu.new_nodes:
            assert len(node.zones) == 1
            zone_counts[node.zones[0]] = zone_counts.get(node.zones[0], 0) + len(node.pods)
        assert sorted(zone_counts.values()) == [3, 3, 3]

    def test_zonal_spread_that_does_not_select_itself_commits_its_nodes(self):
        # upstream narrows such a pod's node to one admissible zone; a node
        # left multi-zone could be committed later to a zone the pod was never
        # admitted to, where the groups its labels match count it
        def pods():
            watch = TopologySpreadConstraint(
                max_skew=1, topology_key=ZONE,
                label_selector=LabelSelector(match_labels={"app": "web"}))
            return (make_pods(2, labels={"app": "other"}, requests={"cpu": "1"})
                    + make_pods(3, labels={"app": "watch"}, requests={"cpu": "250m"},
                                topology_spread=[watch]))

        host, tpu = compare(pods)
        held = [n for n in tpu.new_nodes
                if any(p.metadata.labels["app"] == "watch" for p in n.pods)]
        assert held and all(len(n.zones) == 1 for n in held)

    def test_zonal_spread_uneven(self):
        host, tpu = compare(lambda: spread_pods(7))
        zone_counts = {}
        for node in tpu.new_nodes:
            zone_counts[node.zones[0]] = zone_counts.get(node.zones[0], 0) + len(node.pods)
        assert max(zone_counts.values()) - min(zone_counts.values()) <= 1

    def test_hostname_spread(self):
        host, tpu = compare(lambda: spread_pods(5, key=HOSTNAME))
        assert all(len(n.pods) == 1 for n in tpu.new_nodes)

    def test_hostname_anti_affinity(self):
        host, tpu = compare(lambda: anti_pods(4))
        assert all(len(n.pods) == 1 for n in tpu.new_nodes)

    def test_zonal_anti_affinity_in_kernel(self):
        # required zonal anti is in-kernel since round 5 with ZONE-COMMITTAL
        # phases: batch one places one member per admissible zone, each node
        # pinned to its zone — the fixpoint the host only reaches over
        # batches (one per batch as each node's zone registers,
        # topology_test.go:1879-1923).  Contract (test_parity_fuzz): never
        # fewer than the host, same fixpoint, placements validity-checked.
        host = host_solve(anti_pods(4, key=ZONE), [make_provisioner()])
        assert sum(len(n.pods) for n in host.new_nodes) == 1
        assert len(host.failed_pods) == 3
        tpu = tpu_solve(anti_pods(4, key=ZONE), [make_provisioner()])
        placed = [n for n in tpu.new_nodes if n.pods]
        assert sum(len(n.pods) for n in placed) == 3  # one per zone
        zones = [tuple(n.zones) for n in placed]
        assert all(len(z) == 1 for z in zones), zones  # committed singletons
        assert len(set(zones)) == 3, zones  # all distinct
        assert len(tpu.failed_pods) + len(tpu.spread_residual_pods) == 1

    def test_spread_with_zone_restriction(self):
        def pods():
            return [
                make_pod(
                    labels={"app": "web"},
                    requests={"cpu": "10m"},
                    node_requirements=[
                        NodeSelectorRequirement(ZONE, OP_IN, ["test-zone-1", "test-zone-2"])
                    ],
                    topology_spread=[
                        TopologySpreadConstraint(
                            max_skew=1,
                            topology_key=ZONE,
                            label_selector=LabelSelector(match_labels={"app": "web"}),
                        )
                    ],
                )
                for _ in range(6)
            ]

        host, tpu = compare(pods)
        zones = set()
        for node in tpu.new_nodes:
            zones.update(node.zones)
        assert zones == {"test-zone-1", "test-zone-2"}

    def test_mixed_batch(self):
        def pods():
            return (
                make_pods(20, requests={"cpu": "500m"})
                + spread_pods(6)
                + anti_pods(3)
            )

        compare(pods)

def affinity_pods(n, key=HOSTNAME, requests=None):
    return [
        make_pod(
            labels={"app": "db"},
            requests=requests or {"cpu": "10m"},
            pod_affinity=[
                PodAffinityTerm(
                    topology_key=key,
                    label_selector=LabelSelector(match_labels={"app": "db"}),
                )
            ],
        )
        for _ in range(n)
    ]

class TestKernelSelfAffinity:
    def test_hostname_self_affinity_colocates(self):
        host, tpu = compare(lambda: affinity_pods(3))
        # all three on a single node
        assert len([n for n in tpu.new_nodes if n.pods]) == 1
        assert len(tpu.new_nodes[0].pods) == 3

    def test_hostname_self_affinity_overflow_fails(self):
        # default instance types cap at 5 pods/node: the 6th+ cannot colocate
        host, tpu = compare(lambda: affinity_pods(8, requests={"cpu": "1m"}))
        assert len(tpu.failed_pods) == 3  # 5 fit, 3 fail

    def test_zone_self_affinity_single_zone(self):
        host, tpu = compare(lambda: affinity_pods(12, key=ZONE, requests={"cpu": "900m"}))
        zones = set()
        for node in tpu.new_nodes:
            if node.pods:
                zones.update(node.zones)
        assert len(zones) == 1

    def test_full_benchmark_mix(self):
        """The reference benchmark's diverse mix (generic + spreads + affinity,
        scheduling_benchmark_test.go:185-197) is fully kernel-supported."""
        def pods():
            # distinct labels per group: same-label groups couple across
            # classes and take the host path (see classify_pods)
            zonal = spread_pods(3)
            hostname = [
                make_pod(
                    labels={"app": "hweb"},
                    requests={"cpu": "10m"},
                    topology_spread=[
                        TopologySpreadConstraint(
                            max_skew=1,
                            topology_key=HOSTNAME,
                            label_selector=LabelSelector(match_labels={"app": "hweb"}),
                        )
                    ],
                )
                for _ in range(3)
            ]
            return (
                make_pods(15, requests={"cpu": "500m"}) + zonal + hostname + affinity_pods(6)
            )

        compare(pods)

    def test_coupled_selector_classes_parity(self):
        """Two spread groups sharing one label (zonal + hostname, both on
        app=web) couple across classes — shared-group counting must match the
        host's hash-deduped topology groups."""
        host, tpu = compare(lambda: spread_pods(4) + spread_pods(4, key=HOSTNAME))

    def test_cross_group_affinity_parity(self):
        """Affinity to a different group: followers colocate with a
        zone-pinned target class (topology_test.go zone-affinity cases)."""
        def pods():
            targets = [
                make_pod(
                    labels={"app": "tgt"},
                    requests={"cpu": "10m"},
                    node_selector={ZONE: "test-zone-2"},
                )
                for _ in range(2)
            ]
            followers = [
                make_pod(
                    labels={"app": "fol"},
                    requests={"cpu": "10m"},
                    pod_affinity=[
                        PodAffinityTerm(
                            topology_key=ZONE,
                            label_selector=LabelSelector(match_labels={"app": "tgt"}),
                        )
                    ],
                )
                for _ in range(3)
            ]
            return targets + followers

        host, tpu = compare(pods)
        # followers land in the targets' zone
        for node in tpu.new_nodes:
            if any(p.metadata.labels.get("app") == "fol" for p in node.pods):
                assert node.zones == ["test-zone-2"]

    def test_cross_group_affinity_late_target_parity(self):
        """Follower class scans BEFORE its target (bigger cpu): pass 2 places
        it where the host's queue re-push does (scheduler.go:117-123)."""
        def pods():
            targets = [
                make_pod(
                    labels={"app": "tgt"},
                    requests={"cpu": "10m"},
                    node_selector={ZONE: "test-zone-2"},
                )
                for _ in range(2)
            ]
            followers = [
                make_pod(
                    labels={"app": "fol"},
                    requests={"cpu": "900m"},
                    pod_affinity=[
                        PodAffinityTerm(
                            topology_key=ZONE,
                            label_selector=LabelSelector(match_labels={"app": "tgt"}),
                        )
                    ],
                )
                for _ in range(3)
            ]
            return targets + followers

        host, tpu = compare(pods)
        for node in tpu.new_nodes:
            if any(p.metadata.labels.get("app") == "fol" for p in node.pods):
                assert node.zones == ["test-zone-2"]

    def test_inverse_anti_affinity_parity(self):
        """Pods selected by another class's anti-affinity avoid its nodes."""
        def pods():
            guards = [
                make_pod(
                    labels={"app": "lonely"},
                    requests={"cpu": "10m"},
                    pod_anti_affinity=[
                        PodAffinityTerm(
                            topology_key=HOSTNAME,
                            label_selector=LabelSelector(match_labels={"role": "noisy"}),
                        )
                    ],
                )
            ]
            noisy = [
                make_pod(labels={"role": "noisy"}, requests={"cpu": "10m"})
                for _ in range(2)
            ]
            return guards + noisy

        host, tpu = compare(pods)
        for node in tpu.new_nodes:
            apps = {p.metadata.labels.get("app") or p.metadata.labels.get("role") for p in node.pods}
            assert not ({"lonely", "noisy"} <= apps), "guard and noisy pods must not share a node"

class TestKernelUnsupported:
    def test_affinity_to_absent_group_fails_everywhere(self):
        # affinity to a group with no pods anywhere: unsatisfiable, and not a
        # bootstrap case since the selector doesn't match the pod itself
        # (reference: 'should not schedule pods with affinity to a non-existent
        # pod', topology_test.go:1924)
        host, tpu = compare(
            lambda: [
                make_pod(
                    labels={"app": "a"},
                    pod_affinity=[
                        PodAffinityTerm(
                            topology_key=ZONE,
                            label_selector=LabelSelector(match_labels={"app": "other"}),
                        )
                    ],
                )
                for _ in range(3)
            ]
        )
        assert len(tpu.failed_pods) == 3

    def test_region_spread_rejected(self):
        pods = [
            make_pod(
                labels={"app": "a"},
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key="topology.kubernetes.io/region",
                        label_selector=LabelSelector(match_labels={"app": "a"}),
                    )
                ],
            )
        ]
        with pytest.raises(KernelUnsupported):
            classify_pods(pods)

    def test_host_port_conflicts_parity(self):
        """Same host port forces separate nodes; distinct ports share
        (hostportusage.go:31-56)."""
        host, tpu = compare(
            lambda: [make_pod(host_ports=[8080], requests={"cpu": "1m"}) for _ in range(3)]
        )
        assert all(len(n.pods) == 1 for n in tpu.new_nodes if n.pods)
        host, tpu = compare(
            lambda: [
                make_pod(host_ports=[8080], requests={"cpu": "1m"}),
                make_pod(host_ports=[8081], requests={"cpu": "1m"}),
            ]
        )
        assert len([n for n in tpu.new_nodes if n.pods]) == 1

    def test_specific_host_ip_ports_rejected(self):
        from karpenter_core_tpu.apis.objects import ContainerPort

        pod = make_pod(requests={"cpu": "1m"})
        pod.spec.containers[0].ports.append(
            ContainerPort(host_port=80, host_ip="10.0.0.1")
        )
        with pytest.raises(KernelUnsupported):
            classify_pods([pod])

    def test_non_self_selecting_spread_accepted(self):
        """A spread whose own pods don't count reduces to a static
        within-skew domain mask — kernel-supported since round 2 (the
        admissible-zone phase in ops/solve.py) with host parity."""
        classes = classify_pods(
            [
                make_pod(
                    labels={"app": "a"},
                    topology_spread=[
                        TopologySpreadConstraint(
                            max_skew=1,
                            topology_key=ZONE,
                            label_selector=LabelSelector(match_labels={"app": "OTHER"}),
                        )
                    ],
                )
            ]
        )
        assert classes[0].zone_spread is not None
        compare(
            lambda: make_pods(6, labels={"app": "web"}, requests={"cpu": "500m"})
            + make_pods(
                3,
                labels={"app": "watch"},
                requests={"cpu": "250m"},
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "web"}),
                    )
                ],
            )
        )

class TestClassify:
    def test_identical_pods_one_class(self):
        classes = classify_pods(make_pods(10, requests={"cpu": 1}))
        assert len(classes) == 1
        assert classes[0].count == 10

    def test_ffd_order(self):
        classes = classify_pods(
            make_pods(2, requests={"cpu": 1})
            + make_pods(2, requests={"cpu": 4})
            + make_pods(2, requests={"cpu": 2, "memory": "1Gi"})
        )
        cpus = [c.requests.get("cpu") for c in classes]
        assert cpus == sorted(cpus, reverse=True)

class TestKernelLimits:
    def test_limits_constrain_instance_choice(self):
        """Provisioner limits filter instance types during the solve
        (scheduler.go:292-309), not just at launch."""
        prov = make_provisioner(limits={"cpu": 4})
        host, tpu = compare(
            lambda: make_pods(10, requests={"cpu": 3}),
            provisioners=[prov],
            instance_types=fake_cp.instance_types(8),
        )
        # pessimistic subtract-max exhausts the budget quickly on both paths
        assert len(tpu.failed_pods) == len(host.failed_pods) > 0

    def test_zero_limit_blocks_everything(self):
        prov = make_provisioner(limits={"cpu": 0})
        host, tpu = compare(
            lambda: make_pods(2, requests={"cpu": 1}), provisioners=[prov]
        )
        assert len(tpu.failed_pods) == 2

    def test_weighted_fallback_when_first_provisioner_limited(self):
        limited = make_provisioner(name="limited", weight=100, limits={"cpu": 0})
        fallback = make_provisioner(name="fallback", weight=1)
        host, tpu = compare(
            lambda: make_pods(2, requests={"cpu": 1}),
            provisioners=[limited, fallback],
        )
        assert all(n.provisioner_name == "fallback" for n in tpu.new_nodes if n.pods)

class TestPhaseFamilyCombos:
    """Constraint combos that would need intersected phase plans route to the
    host path (mixed-batch split); under the reference's pessimistic new-node
    committal they schedule ~1 pod before deadlocking (topology_test.go:1896),
    so exact per-pod semantics matter more than kernel throughput here."""

    def _combo_pod(self, zone_spread=False, zone_anti=False, host_aff=False):
        sel = LabelSelector(match_labels={"app": "x"})
        return make_pod(
            labels={"app": "x"},
            topology_spread=(
                [TopologySpreadConstraint(max_skew=1, topology_key=ZONE, label_selector=sel)]
                if zone_spread else None
            ),
            pod_anti_affinity=(
                [PodAffinityTerm(topology_key=ZONE, label_selector=sel)]
                if zone_anti else None
            ),
            pod_affinity=(
                [PodAffinityTerm(topology_key=HOSTNAME, label_selector=sel)]
                if host_aff else None
            ),
        )

    def test_zone_spread_plus_zone_anti_routes_to_host(self):
        with pytest.raises(KernelUnsupported):
            classify_pods([self._combo_pod(zone_spread=True, zone_anti=True)])

    def test_zone_spread_plus_host_affinity_routes_to_host(self):
        with pytest.raises(KernelUnsupported):
            classify_pods([self._combo_pod(zone_spread=True, host_aff=True)])

    def test_zone_anti_plus_host_affinity_routes_to_host(self):
        with pytest.raises(KernelUnsupported):
            classify_pods([self._combo_pod(zone_anti=True, host_aff=True)])

    def test_zone_spread_plus_hostname_anti_stays_on_kernel(self):
        # composes through per-node hostname caps — must NOT route to host
        sel = LabelSelector(match_labels={"app": "x"})
        pods = [
            make_pod(
                name=f"p{i}", labels={"app": "x"}, requests={"cpu": "10m"},
                topology_spread=[
                    TopologySpreadConstraint(max_skew=1, topology_key=ZONE, label_selector=sel)
                ],
                pod_anti_affinity=[PodAffinityTerm(topology_key=HOSTNAME, label_selector=sel)],
            )
            for i in range(4)
        ]
        classify_pods(list(pods))  # no KernelUnsupported
        host, tpu = compare(lambda: list(pods))
        assert all(len(n.pods) <= 1 for n in tpu.new_nodes)
