"""Parity tests: kernel existing-node placement vs the host ExistingNode path."""

import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_node, make_pod, make_pods, make_provisioner
from karpenter_core_tpu.testing.harness import make_environment

# kernel existing-node solves compile per plane shape -- the slow tier (`make test-all`)
pytestmark = pytest.mark.compile

ZONE = labels_api.LABEL_TOPOLOGY_ZONE

def owned_ready_node(env, cpu=4, zone="test-zone-1", instance_type="default-instance-type", name=None):
    node = make_node(
        name=name,
        labels={
            labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
            labels_api.LABEL_INSTANCE_TYPE_STABLE: instance_type,
            labels_api.LABEL_CAPACITY_TYPE: "spot",
            labels_api.LABEL_NODE_INITIALIZED: "true",
            ZONE: zone,
        },
        allocatable={"cpu": cpu, "memory": "4Gi", "pods": 10},
    )
    env.kube.create(node)
    return node

class TestExistingNodes:
    def test_pods_fill_existing_before_new(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        owned_ready_node(env, cpu=4)
        pods = make_pods(3, requests={"cpu": "1"})
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            pods,
            state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        assert not res.failed_pods
        placed_existing = sum(len(v) for v in res.existing_assignments.values())
        assert placed_existing == 3
        assert not res.new_nodes

    def test_overflow_opens_new_node(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        owned_ready_node(env, cpu=2)
        pods = make_pods(4, requests={"cpu": "1"})
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert not res.failed_pods
        placed_existing = sum(len(v) for v in res.existing_assignments.values())
        assert placed_existing == 2
        assert sum(len(n.pods) for n in res.new_nodes) == 2

    def test_existing_capacity_accounts_bound_pods(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_ready_node(env, cpu=4)
        bound = make_pod(requests={"cpu": 3}, node_name=node.name, unschedulable=False)
        env.kube.create(bound)
        pods = make_pods(2, requests={"cpu": "1"})
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert not res.failed_pods
        placed_existing = sum(len(v) for v in res.existing_assignments.values())
        assert placed_existing == 1  # only 1 cpu free
        assert sum(len(n.pods) for n in res.new_nodes) == 1

    def test_zone_selector_respects_existing_zone(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        owned_ready_node(env, cpu=8, zone="test-zone-1")
        pods = [make_pod(requests={"cpu": 1}, node_selector={ZONE: "test-zone-2"})]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        # zone-2 pod can't use the zone-1 node
        assert not res.existing_assignments
        assert sum(len(n.pods) for n in res.new_nodes) == 1

    def test_taints_block_existing(self):
        from karpenter_core_tpu.apis.objects import Taint

        env = make_environment()
        env.kube.create(make_provisioner())
        node = make_node(
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                ZONE: "test-zone-1",
            },
            taints=[Taint("dedicated", "x")],
            allocatable={"cpu": 8, "memory": "8Gi", "pods": 10},
        )
        env.kube.create(node)
        pods = [make_pod(requests={"cpu": 1})]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert not res.existing_assignments
        assert sum(len(n.pods) for n in res.new_nodes) == 1

    def test_hostname_spread_counts_existing_pods(self):
        from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint

        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_ready_node(env, cpu=8)
        # one matching pod already on the node
        existing_pod = make_pod(
            labels={"app": "web"}, node_name=node.name, unschedulable=False,
            requests={"cpu": "100m"},
        )
        env.kube.create(existing_pod)
        spread = [
            make_pod(
                labels={"app": "web"},
                requests={"cpu": "100m"},
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key=labels_api.LABEL_HOSTNAME,
                        label_selector=LabelSelector(match_labels={"app": "web"}),
                    )
                ],
            )
            for _ in range(2)
        ]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            spread, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert not res.failed_pods
        # node already holds 1 matching pod (cap=skew=1): both new pods need new nodes
        placed_existing = sum(len(v) for v in res.existing_assignments.values())
        assert placed_existing == 0
        assert len(res.new_nodes) == 2

    def test_host_parity_on_mixed_existing_scenario(self):
        """Aggregate parity vs the host scheduler with existing capacity."""
        from karpenter_core_tpu.solver.builder import build_scheduler

        env = make_environment()
        env.kube.create(make_provisioner())
        owned_ready_node(env, cpu=4, zone="test-zone-1", name="ex-1")
        owned_ready_node(env, cpu=4, zone="test-zone-2", name="ex-2")

        def pods():
            return make_pods(10, requests={"cpu": "1"})

        host_sched = build_scheduler(
            env.kube, env.provider, env.cluster, pods(), env.cluster.snapshot_nodes(),
            daemonset_pods=[],
        )
        host = host_sched.solve(pods())
        host_existing = sum(len(n.pods) for n in host.existing_nodes)
        host_new = sum(len(n.pods) for n in host.new_nodes)

        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        tpu = solver.solve(
            pods(), state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        tpu_existing = sum(len(v) for v in tpu.existing_assignments.values())
        tpu_new = sum(len(n.pods) for n in tpu.new_nodes)
        assert (tpu_existing, tpu_new) == (host_existing, host_new)
        assert len(tpu.failed_pods) == len(host.failed_pods) == 0

class TestReviewRegressions:
    """Scenarios from review: kernel/host divergences that are now fixed."""

    def test_bound_anti_affinity_guards_node(self):
        """A bound pod's anti-affinity term blocks the pods it selects even
        when no pending pod owns an identical term (inverse topologies from
        cluster pods, topology.go:185-198)."""
        from karpenter_core_tpu.apis.objects import LabelSelector, PodAffinityTerm

        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_ready_node(env, cpu=8)
        guard = make_pod(
            labels={"app": "lonely"},
            node_name=node.name,
            unschedulable=False,
            requests={"cpu": "100m"},
            pod_anti_affinity=[
                PodAffinityTerm(
                    topology_key=labels_api.LABEL_HOSTNAME,
                    label_selector=LabelSelector(match_labels={"role": "noisy"}),
                )
            ],
        )
        env.kube.create(guard)
        noisy = [make_pod(labels={"role": "noisy"}, requests={"cpu": "100m"})]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            noisy, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        # the guarded node must not receive the noisy pod
        assert node.name not in res.existing_assignments
        assert sum(len(n.pods) for n in res.new_nodes) == 1

    def test_cross_group_affinity_late_target_second_pass(self):
        """Follower class (bigger cpu, scans first) with affinity to a target
        class that scans later: the follower's pods fail pass 1, then place in
        pass 2 seeded by the target's recorded counts — the kernel equivalent
        of the host queue's re-push (scheduler.go:117-123)."""
        from karpenter_core_tpu.apis.objects import LabelSelector, PodAffinityTerm
        from karpenter_core_tpu.models.snapshot import affinity_scan_passes, classify_pods
        from karpenter_core_tpu.cloudprovider import fake as fake_cp
        from karpenter_core_tpu.testing import make_provisioner as mk_prov

        targets = [
            make_pod(labels={"app": "tgt"}, requests={"cpu": "10m"},
                     node_selector={ZONE: "test-zone-2"})
        ]
        followers = [
            make_pod(
                requests={"cpu": "500m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "tgt"}),
                    )
                ],
            )
            for _ in range(3)
        ]
        classes = classify_pods(targets + followers)
        assert affinity_scan_passes(classes) == 2

        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(10))
        solver = TPUSolver(provider, [mk_prov()])
        res = solver.solve(targets + followers)
        assert not res.failed_pods
        # followers colocate with the zone-2-pinned target
        for node in res.new_nodes:
            assert node.zones == ["test-zone-2"]

    def test_cross_group_affinity_no_target_still_fails(self):
        """Followers whose target never schedules keep failing across passes
        (host parity: retry makes no progress)."""
        from karpenter_core_tpu.apis.objects import LabelSelector, PodAffinityTerm
        from karpenter_core_tpu.cloudprovider import fake as fake_cp
        from karpenter_core_tpu.testing import make_provisioner as mk_prov

        followers = [
            make_pod(
                requests={"cpu": "500m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "ghost"}),
                    )
                ],
            )
            for _ in range(2)
        ]
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(10))
        solver = TPUSolver(provider, [mk_prov()])
        res = solver.solve(followers)
        assert len(res.failed_pods) == 2

    def test_zone_affinity_bootstrap_capacity_aware(self):
        """Bootstrap must pick a zone some template actually offers."""
        from karpenter_core_tpu.apis.objects import (
            LabelSelector,
            NodeSelectorRequirement,
            OP_IN,
            PodAffinityTerm,
        )
        from karpenter_core_tpu.testing import make_pods

        provisioner = make_provisioner(
            requirements=[
                NodeSelectorRequirement(ZONE, OP_IN, ["test-zone-2", "test-zone-3"])
            ]
        )
        pods = [
            make_pod(
                labels={"grp": "a"},
                requests={"cpu": "100m"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"grp": "a"}),
                    )
                ],
            )
            for _ in range(3)
        ]
        provider = env_provider = __import__(
            "karpenter_core_tpu.cloudprovider.fake", fromlist=["FakeCloudProvider"]
        ).FakeCloudProvider()
        solver = TPUSolver(provider, [provisioner])
        res = solver.solve(pods)
        assert not res.failed_pods
        zones = {z for n in res.new_nodes for z in n.zones}
        assert zones <= {"test-zone-2", "test-zone-3"}

    def test_bound_host_port_blocks_existing_node(self):
        """A bound pod's host port blocks a pending pod using the same port
        from that node (hostportusage seed from bound pods)."""
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_ready_node(env, cpu=8)
        bound = make_pod(
            host_ports=[8080], node_name=node.name, unschedulable=False,
            requests={"cpu": "100m"},
        )
        env.kube.create(bound)
        pending = [make_pod(host_ports=[8080], requests={"cpu": "100m"})]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            pending, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert node.name not in res.existing_assignments
        assert sum(len(n.pods) for n in res.new_nodes) == 1

    def test_limits_account_existing_node_capacity(self):
        """Kernel limit budget subtracts the solve's own state nodes
        (scheduler.go:244-246), not the async counter status — a stale status
        must not allow over-provisioning past the limit."""
        env = make_environment()
        env.kube.create(make_provisioner(limits={"cpu": 8}))
        # an 8-cpu owned node exists; counter has NOT reconciled status
        node = make_node(
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "arm-instance-type",
                labels_api.LABEL_CAPACITY_TYPE: "spot",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                ZONE: "test-zone-1",
            },
            capacity={"cpu": 8, "memory": "8Gi", "pods": 5},
            allocatable={"cpu": 1, "memory": "8Gi", "pods": 5},
        )
        env.kube.create(node)
        # pod doesn't fit the existing node (1 cpu free), and the budget is
        # exhausted by the existing node's capacity: must fail, not launch
        pods = [make_pod(requests={"cpu": 2})]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert len(res.failed_pods) == 1
        assert not res.new_nodes

class TestVolumeLimits:
    """Kernel volume attach-limit plane vs the host ExistingNode path
    (volumeusage.go:33-236, existingnode.go:77-130)."""

    def _volume_env(self, attach_limit=2, cpu=16):
        from karpenter_core_tpu.apis.objects import (
            CSINode,
            CSINodeDriver,
            ObjectMeta,
            StorageClass,
        )

        env = make_environment()
        env.kube.create(make_provisioner())
        env.kube.create(
            StorageClass(metadata=ObjectMeta(name="fast"), provisioner="csi.test")
        )
        node = owned_ready_node(env, cpu=cpu)
        env.kube.create(
            CSINode(
                metadata=ObjectMeta(name=node.name),
                drivers=[CSINodeDriver(name="csi.test", allocatable_count=attach_limit)],
            )
        )
        return env, node

    def _claim(self, env, name):
        from karpenter_core_tpu.apis.objects import (
            ObjectMeta,
            PersistentVolumeClaim,
            PersistentVolumeClaimSpec,
        )

        env.kube.create(
            PersistentVolumeClaim(
                metadata=ObjectMeta(name=name, namespace="default"),
                spec=PersistentVolumeClaimSpec(storage_class_name="fast"),
            )
        )

    def test_attach_limit_caps_existing_node(self):
        env, node = self._volume_env(attach_limit=2)
        pods = []
        for i in range(4):  # statefulset-style: one PVC per pod
            self._claim(env, f"claim-{i}")
            pods.append(make_pod(requests={"cpu": "100m"}, pvcs=[f"claim-{i}"]))
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert not res.failed_pods
        assert sum(len(v) for v in res.existing_assignments.values()) == 2
        # overflow opens a new node (no CSINode yet -> unlimited there)
        assert sum(len(n.pods) for n in res.new_nodes) == 2

    def test_shared_pvc_within_class_counts_once(self):
        env, node = self._volume_env(attach_limit=1)
        self._claim(env, "shared")
        pods = [make_pod(requests={"cpu": "100m"}, pvcs=["shared"]) for _ in range(3)]
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        # one distinct PVC: the whole class fits under the limit of 1
        assert not res.failed_pods
        assert sum(len(v) for v in res.existing_assignments.values()) == 3
        assert not res.new_nodes

    def test_bound_pod_volumes_count_against_limit(self):
        env, node = self._volume_env(attach_limit=2)
        self._claim(env, "bound-claim")
        bound = make_pod(
            requests={"cpu": "100m"}, pvcs=["bound-claim"],
            node_name=node.name, unschedulable=False,
        )
        env.kube.create(bound)
        self._claim(env, "new-1")
        self._claim(env, "new-2")
        pods = [
            make_pod(requests={"cpu": "100m"}, pvcs=["new-1"]),
            make_pod(requests={"cpu": "100m"}, pvcs=["new-2"]),
        ]
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        # 1 mounted + 2 new > 2: only one of the new claims fits
        assert sum(len(v) for v in res.existing_assignments.values()) == 1
        assert sum(len(n.pods) for n in res.new_nodes) == 1
        assert not res.failed_pods

    def test_bound_pod_sharing_class_pvc_adds_nothing(self):
        env, node = self._volume_env(attach_limit=1)
        self._claim(env, "shared")
        bound = make_pod(
            requests={"cpu": "100m"}, pvcs=["shared"],
            node_name=node.name, unschedulable=False,
        )
        env.kube.create(bound)
        pods = [make_pod(requests={"cpu": "100m"}, pvcs=["shared"]) for _ in range(2)]
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        # the class's PVC is already mounted: zero incremental attach cost
        assert sum(len(v) for v in res.existing_assignments.values()) == 2
        assert not res.new_nodes
        assert not res.failed_pods

    def test_over_limit_node_blocks_all_pods(self):
        env, node = self._volume_env(attach_limit=1)
        self._claim(env, "a")
        self._claim(env, "b")
        for claim in ("a", "b"):
            env.kube.create(
                make_pod(
                    requests={"cpu": "100m"}, pvcs=[claim],
                    node_name=node.name, unschedulable=False,
                )
            )
        pods = [make_pod(requests={"cpu": "100m"})]  # volume-less
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        # mounted (2) exceeds limit (1): the node accepts nothing, volume-less
        # pods included (VolumeCount.exceeds gates can_add wholesale)
        assert node.name not in res.existing_assignments
        assert sum(len(n.pods) for n in res.new_nodes) == 1

    def test_cross_class_pvc_sharing_routes_to_host(self):
        import pytest

        from karpenter_core_tpu.models.snapshot import KernelUnsupported

        env, node = self._volume_env()
        self._claim(env, "shared")
        pods = [
            make_pod(requests={"cpu": "100m"}, pvcs=["shared"]),
            make_pod(requests={"cpu": "200m"}, pvcs=["shared"]),  # distinct class
        ]
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        with pytest.raises(KernelUnsupported):
            solver.solve(
                pods,
                state_nodes=env.cluster.snapshot_nodes(),
                bound_pods=env.kube.list_pods(),
            )

    def test_host_parity_with_attach_limits(self):
        from karpenter_core_tpu.solver.builder import build_scheduler

        def build():
            env, node = self._volume_env(attach_limit=2)
            pods = []
            for i in range(5):
                self._claim(env, f"c-{i}")
                pods.append(make_pod(requests={"cpu": "100m"}, pvcs=[f"c-{i}"]))
            return env, pods

        env, pods = build()
        host_sched = build_scheduler(
            env.kube, env.provider, env.cluster, pods, env.cluster.snapshot_nodes(),
            daemonset_pods=[],
        )
        host = host_sched.solve(pods)
        host_existing = sum(len(n.pods) for n in host.existing_nodes)

        env, pods = build()
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        tpu = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        tpu_existing = sum(len(v) for v in tpu.existing_assignments.values())
        assert tpu_existing == host_existing == 2
        assert len(tpu.failed_pods) == len(host.failed_pods) == 0

    def test_statefulset_pods_stay_one_class(self):
        # one-PVC-per-pod must NOT explode the class count (claim identity is
        # excluded from the class signature; PERPOD mode counts per pod)
        env, node = self._volume_env(attach_limit=2)
        pods = []
        for i in range(6):
            self._claim(env, f"sts-{i}")
            pods.append(make_pod(requests={"cpu": "100m"}, pvcs=[f"sts-{i}"]))
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        snapshot = solver.encode(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert len(snapshot.classes) == 1
        assert snapshot.class_volumes[0]["per_pod"] == {"csi.test": 1}

    def test_cross_class_sharing_without_limits_stays_on_kernel(self):
        # sharing through a driver nobody limits is harmless — no host fallback
        from karpenter_core_tpu.apis.objects import ObjectMeta, StorageClass

        env = make_environment()
        env.kube.create(make_provisioner())
        env.kube.create(
            StorageClass(metadata=ObjectMeta(name="fast"), provisioner="csi.test")
        )
        owned_ready_node(env, cpu=16)  # no CSINode -> no limits anywhere
        self._claim(env, "shared")
        pods = [
            make_pod(requests={"cpu": "100m"}, pvcs=["shared"]),
            make_pod(requests={"cpu": "200m"}, pvcs=["shared"]),
        ]
        solver = TPUSolver(
            env.provider, env.kube.list_provisioners(), kube_client=env.kube
        )
        res = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        assert sum(len(v) for v in res.existing_assignments.values()) == 2
        assert not res.failed_pods

class TestNonSelfSelectingSpread:
    """Spreads whose own pods don't match the selector: the skew formula
    (count + 0 - min <= maxSkew) reduces to a static admissible-domain mask
    (topologygroup.go:155-182 with selects(pod)=false)."""

    def _spread(self, key, skew=1):
        from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint

        return [
            TopologySpreadConstraint(
                max_skew=skew,
                topology_key=key,
                label_selector=LabelSelector(match_labels={"app": "web"}),
            )
        ]

    def test_zone_mask_excludes_over_skew_zones(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        n1 = owned_ready_node(env, cpu=8, zone="test-zone-1", name="n1")
        n2 = owned_ready_node(env, cpu=8, zone="test-zone-2", name="n2")
        # web counts: zone-1 = 2, zone-2 = 1, zone-3 = 0 -> admissible (skew 1)
        # for a non-counting pod: zones with count <= min+1 = {zone-2, zone-3}
        for node, n in ((n1, 2), (n2, 1)):
            for _ in range(n):
                env.kube.create(
                    make_pod(labels={"app": "web"}, requests={"cpu": "100m"},
                             node_name=node.name, unschedulable=False)
                )
        watchers = [
            make_pod(
                labels={"app": "watch"}, requests={"cpu": "100m"},
                topology_spread=self._spread(ZONE),
            )
            for _ in range(4)
        ]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            watchers, state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        assert not res.failed_pods
        assert "n1" not in res.existing_assignments  # zone-1 is over skew
        for node in res.new_nodes:
            assert "test-zone-1" not in node.zones

    def test_hostname_count_gate(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        crowded = owned_ready_node(env, cpu=8, name="crowded")
        quiet = owned_ready_node(env, cpu=8, name="quiet")
        for _ in range(2):  # crowded: web count 2 > skew 1 -> blocked
            env.kube.create(
                make_pod(labels={"app": "web"}, requests={"cpu": "100m"},
                         node_name=crowded.name, unschedulable=False)
            )
        env.kube.create(  # quiet: web count 1 <= skew 1 -> open, unlimited
            make_pod(labels={"app": "web"}, requests={"cpu": "100m"},
                     node_name=quiet.name, unschedulable=False)
        )
        watchers = [
            make_pod(
                labels={"app": "watch"}, requests={"cpu": "100m"},
                topology_spread=self._spread(labels_api.LABEL_HOSTNAME),
            )
            for _ in range(3)
        ]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            watchers, state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        assert not res.failed_pods
        assert "crowded" not in res.existing_assignments
        assert len(res.existing_assignments.get("quiet", [])) == 3

    def test_host_parity_mixed_batch(self):
        from karpenter_core_tpu.solver.builder import build_scheduler

        def build():
            env = make_environment()
            env.kube.create(make_provisioner())
            pods = [
                make_pod(labels={"app": "web"}, requests={"cpu": "500m"})
                for _ in range(6)
            ] + [
                make_pod(
                    labels={"app": "watch"}, requests={"cpu": "250m"},
                    topology_spread=self._spread(ZONE),
                )
                for _ in range(4)
            ]
            return env, pods

        env, pods = build()
        host = build_scheduler(
            env.kube, env.provider, env.cluster, pods, env.cluster.snapshot_nodes(),
            daemonset_pods=[],
        ).solve(pods)
        env, pods = build()
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        tpu = solver.solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods()
        )
        host_new = sum(len(n.pods) for n in host.new_nodes)
        tpu_new = sum(len(n.pods) for n in tpu.new_nodes)
        assert tpu_new == host_new
        assert len(tpu.failed_pods) == len(host.failed_pods) == 0

    def test_no_capacity_in_admissible_zones_fails_pods(self):
        from karpenter_core_tpu.apis.objects import NodeSelectorRequirement, OP_IN

        env = make_environment()
        # templates only offer zone-1; web count zone-1 = 1 > skew 0, so the
        # only admissible zones for the non-counting watcher have no capacity
        env.kube.create(
            make_provisioner(
                requirements=[NodeSelectorRequirement(ZONE, OP_IN, ["test-zone-1"])]
            )
        )
        node = owned_ready_node(env, cpu=8, zone="test-zone-1", name="n1")
        env.kube.create(
            make_pod(labels={"app": "web"}, requests={"cpu": "100m"},
                     node_name=node.name, unschedulable=False)
        )
        watchers = [
            make_pod(
                labels={"app": "watch"}, requests={"cpu": "100m"},
                topology_spread=self._spread(ZONE, skew=0),
            )
        ]
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        res = solver.solve(
            watchers, state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        assert len(res.failed_pods) == 1

class TestCapacityAwareSpread:
    """Spread quotas must anticipate per-zone intake: a zone reachable only
    through existing nodes saturates mid-fill, freezing its count, which then
    bounds every other zone at frozen+maxSkew — the reference measures skew
    against the min over ALL the pod's domains each placement
    (topologygroup.go:155-182), so an exhausted zone keeps gating the rest."""

    def _catalog_z1_only_launchable(self, cpu=4.0):
        """One instance type whose universe spans zone-1+zone-2 but whose
        zone-2 offering is unavailable: zone-2 participates in skew math yet
        only pre-existing nodes can absorb pods there."""
        it = fake_cp.new_instance_type(
            "cap-it",
            resources={"cpu": cpu, "memory": 8 * fake_cp.GI, "pods": 32.0},
            offerings=[
                fake_cp.Offering("spot", "test-zone-1", 1.0),
                fake_cp.Offering("spot", "test-zone-2", 1.0),
            ],
        )
        from dataclasses import replace as dc_replace

        idx = next(
            i for i, o in enumerate(it.offerings) if o.zone == "test-zone-2"
        )
        it.offerings[idx] = dc_replace(it.offerings[idx], available=False)
        return [it]

    def _spread_pods(self, n):
        from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint

        return [
            make_pod(
                name=f"web-{i}", labels={"app": "web"}, requests={"cpu": "1"},
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1, topology_key=ZONE,
                        label_selector=LabelSelector(match_labels={"app": "web"}),
                    )
                ],
            )
            for i in range(n)
        ]

    def _solve_both(self, node_cpu, n_pods):
        from karpenter_core_tpu.solver.builder import build_scheduler

        def build():
            env = make_environment(instance_types=self._catalog_z1_only_launchable())
            env.kube.create(make_provisioner())
            owned_ready_node(
                env, cpu=node_cpu, zone="test-zone-2", instance_type="cap-it"
            )
            return env, self._spread_pods(n_pods)

        env, pods = build()
        host = build_scheduler(
            env.kube, env.provider, cluster=None, pods=pods,
            state_nodes=env.cluster.snapshot_nodes(), daemonset_pods=[],
        ).solve(pods)
        env, pods = build()
        tpu = TPUSolver(env.provider, env.kube.list_provisioners()).solve(
            pods, state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        return host, tpu

    @staticmethod
    def _placed(host, tpu):
        host_placed = sum(len(n.pods) for n in host.new_nodes) + sum(
            len(e.pods) for e in host.existing_nodes
        )
        tpu_placed = sum(len(n.pods) for n in tpu.new_nodes) + sum(
            len(v) for v in tpu.existing_assignments.values()
        )
        return host_placed, tpu_placed

    def test_existing_only_zone_saturates_and_bounds_skew(self):
        host, tpu = self._solve_both(node_cpu=2, n_pods=10)
        host_placed, tpu_placed = self._placed(host, tpu)
        assert tpu_placed == host_placed == 5
        assert len(tpu.failed_pods) == len(host.failed_pods) == 5
        # zone-2 intake is 2; frozen there, zone-1 rises to 2+skew = 3
        assert sum(len(v) for v in tpu.existing_assignments.values()) == 2
        assert sum(len(n.pods) for n in tpu.new_nodes) == 3

    def test_zero_intake_zone_freezes_min_at_zero(self):
        # the zone-2 node can't fit even one pod: its count freezes at 0 and
        # caps zone-1 at maxSkew
        host, tpu = self._solve_both(node_cpu="500m", n_pods=10)
        host_placed, tpu_placed = self._placed(host, tpu)
        assert tpu_placed == host_placed == 1
        assert len(tpu.failed_pods) == len(host.failed_pods) == 9

class TestUnknownZoneNode:
    """An existing node WITHOUT a zone label encodes as an all-zones mask.
    Committed-zone spread phases must not tap it twice with stale intake:
    once it takes pods in one zone phase its live mask narrows, excluding it
    from the rest (the reference places on label-less nodes through the
    DoesNotExist branch of nextDomainTopologySpread, topologygroup.go:176-180,
    without ever counting them twice)."""

    def test_no_double_placement_on_label_less_node(self):
        from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint
        from karpenter_core_tpu.solver.builder import build_scheduler

        def build():
            env = make_environment()
            env.kube.create(make_provisioner())
            node = make_node(
                labels={
                    labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                    labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                    labels_api.LABEL_CAPACITY_TYPE: "spot",
                    labels_api.LABEL_NODE_INITIALIZED: "true",
                },  # no zone label
                allocatable={"cpu": 2, "memory": "4Gi", "pods": 10},
            )
            env.kube.create(node)
            sel = LabelSelector(match_labels={"app": "web"})
            pods = [
                make_pod(
                    name=f"w{i}", labels={"app": "web"}, requests={"cpu": "1"},
                    topology_spread=[
                        TopologySpreadConstraint(
                            max_skew=1, topology_key=ZONE, label_selector=sel
                        )
                    ],
                )
                for i in range(6)
            ]
            return env, pods

        env, pods = build()
        host = build_scheduler(
            env.kube, env.provider, cluster=None, pods=pods,
            state_nodes=env.cluster.snapshot_nodes(), daemonset_pods=[],
        ).solve(pods)
        env, pods = build()
        tpu = TPUSolver(env.provider, env.kube.list_provisioners()).solve(
            pods, state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        tpu_existing = sum(len(v) for v in tpu.existing_assignments.values())
        host_existing = sum(len(e.pods) for e in host.existing_nodes)
        # intake is 2 cpu: more than 2 pods on the node means a phase re-read
        # stale capacity
        assert tpu_existing == host_existing == 2
        assert len(tpu.failed_pods) == len(host.failed_pods) == 0
        assert sum(len(n.pods) for n in tpu.new_nodes) == sum(
            len(n.pods) for n in host.new_nodes
        ) == 4


class TestNodeOrderBreaksZoneTies:
    """PR 27: the reference tries existing nodes in index order before any new
    node, so where several zones tie — the zone-spread pod left over once the
    zones stand level, the first pod of a zone self-affinity group — the zone
    of the FIRST existing node that can take the pod wins, not the first zone
    by name.  With no existing node the order of the zones stands."""

    @staticmethod
    def _solve_both(make_pending, zones=("test-zone-2", "test-zone-1", "test-zone-3")):
        from karpenter_core_tpu.solver.builder import build_scheduler

        def build():
            env = make_environment()
            env.kube.create(make_provisioner())
            for i, zone in enumerate(zones):
                owned_ready_node(env, cpu=8, zone=zone, name=f"ex-{i}")
            return env, make_pending()

        env, pods = build()
        host = build_scheduler(
            env.kube, env.provider, cluster=None, pods=pods,
            state_nodes=env.cluster.snapshot_nodes(), daemonset_pods=[],
        ).solve(pods)
        env, pods = build()
        tpu = TPUSolver(env.provider, env.kube.list_provisioners()).solve(
            pods, state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods(),
        )
        host_per_node = {n.name: len(n.pods) for n in host.existing_nodes if n.pods}
        tpu_per_node = {name: len(p) for name, p in tpu.existing_assignments.items() if p}
        assert not tpu.failed_pods and not host.failed_pods
        return host_per_node, tpu_per_node

    @pytest.mark.parametrize("n_pods,expected", [
        (1, {"ex-0": 1}),  # the first node's zone, test-zone-2, not test-zone-1
        (2, {"ex-0": 1, "ex-1": 1}),
        (4, {"ex-0": 2, "ex-1": 1, "ex-2": 1}),
    ])
    def test_zone_spread_leftover_follows_the_first_node(self, n_pods, expected):
        from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint

        sel = LabelSelector(match_labels={"app": "web"})

        def pending():
            return [
                make_pod(labels={"app": "web"}, requests={"cpu": "1"}, topology_spread=[
                    TopologySpreadConstraint(max_skew=1, topology_key=ZONE, label_selector=sel)
                ])
                for _ in range(n_pods)
            ]

        host, tpu = self._solve_both(pending)
        assert tpu == host == expected

    def test_zone_self_affinity_bootstraps_on_the_first_node(self):
        from karpenter_core_tpu.apis.objects import LabelSelector, PodAffinityTerm

        sel = LabelSelector(match_labels={"app": "db"})

        def pending():
            return [
                make_pod(labels={"app": "db"}, requests={"cpu": "1"}, pod_affinity=[
                    PodAffinityTerm(topology_key=ZONE, label_selector=sel)
                ])
                for _ in range(3)
            ]

        host, tpu = self._solve_both(pending)
        assert tpu == host == {"ex-0": 3}

    def test_water_fill_without_existing_nodes_is_what_it_was(self):
        """Left-over pods go by count, then zone index, exactly as before the
        node-order rule; with a running sum they go where a node comes first."""
        import jax.numpy as jnp
        import numpy as np

        from karpenter_core_tpu.ops.solve import _water_fill

        import jax

        filled = jax.jit(_water_fill)  # one program, not one per jnp op

        def fill(counts, m, ex_cum=None):
            allowed = jnp.ones(len(counts), dtype=bool)
            return np.asarray(filled(
                jnp.asarray(counts, jnp.int32), allowed, jnp.int32(m), ex_cum
            )).tolist()

        assert fill([5, 5, 5], 1) == [1, 0, 0]
        assert fill([5, 3, 5], 4) == [1, 3, 0]
        assert fill([0, 0, 0], 7) == [3, 2, 2]
        closed = jnp.zeros((1, 3), jnp.int32)  # one closed placeholder node
        assert fill([5, 5, 5], 1, closed) == [1, 0, 0]
        # node 0 sits in zone 2, node 1 in zone 0, each with room for two
        ex_cap = jnp.asarray([[0, 0, 2], [2, 0, 0]], jnp.int32)
        cum = jnp.cumsum(ex_cap, axis=0)
        assert fill([5, 5, 5], 1, cum) == [0, 0, 1]
        assert fill([5, 5, 5], 2, cum) == [1, 0, 1]
        assert fill([5, 3, 5], 4, cum) == [1, 2, 1]
        # zone 2's node is full once the level fill gave it two: zone 0 is next
        assert fill([3, 5, 3], 5, cum) == [3, 0, 2]
