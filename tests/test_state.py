"""Cluster state suite (modeled on /root/reference/pkg/controllers/state/suite_test.go)."""

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    ObjectMeta,
    PersistentVolumeClaim,
    PersistentVolumeClaimSpec,
    StorageClass,
)
from karpenter_core_tpu.state.cluster import (
    STATE_NODE_REBUILD_PODS,
    STATE_NODE_REBUILDS,
    StateNode,
)
from karpenter_core_tpu.testing import make_daemonset_pod, make_node, make_pod, make_provisioner
from karpenter_core_tpu.testing.harness import make_environment
from karpenter_core_tpu.utils import pod as pod_util


def owned_node(env, name=None, instance_type="default-instance-type", **kwargs):
    node = make_node(
        name=name,
        labels={
            labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
            labels_api.LABEL_INSTANCE_TYPE_STABLE: instance_type,
            **kwargs.pop("labels", {}),
        },
        **kwargs,
    )
    env.kube.create(node)
    return node


class TestClusterState:
    def test_node_tracked_on_create(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        nodes = env.cluster.snapshot_nodes()
        assert len(nodes) == 1
        assert nodes[0].node.name == node.name

    def test_pod_binding_updates_usage(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        pod = make_pod(requests={"cpu": 2}, node_name=node.name, unschedulable=False)
        env.kube.create(pod)
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.pod_requests_total()["cpu"] == 2
        assert state_node.available()["cpu"] == state_node.allocatable()["cpu"] - 2

    def test_pod_deletion_releases_usage(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        pod = make_pod(requests={"cpu": 2}, node_name=node.name, unschedulable=False)
        env.kube.create(pod)
        env.kube.delete(pod, force=True)
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.pod_requests_total().get("cpu", 0) == 0

    def test_inflight_capacity_from_instance_type(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        # node registers with zero capacity (kubelet not up yet)
        node = owned_node(env, allocatable={}, capacity={})
        state_node = env.cluster.snapshot_nodes()[0]
        # capacity stands in from the instance type until initialized
        assert state_node.allocatable()["cpu"] > 0

    def test_node_deletion_untracked(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        env.kube.delete(node, force=True)
        assert env.cluster.snapshot_nodes() == []

    def test_anti_affinity_pod_index(self):
        from karpenter_core_tpu.apis.objects import LabelSelector, PodAffinityTerm

        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        pod = make_pod(
            labels={"app": "a"},
            node_name=node.name,
            unschedulable=False,
            pod_anti_affinity=[
                PodAffinityTerm(
                    topology_key=labels_api.LABEL_HOSTNAME,
                    label_selector=LabelSelector(match_labels={"app": "a"}),
                )
            ],
        )
        env.kube.create(pod)
        visited = []
        env.cluster.for_pods_with_anti_affinity(lambda p, n: visited.append((p.name, n.name)) or True)
        assert visited == [(pod.name, node.name)]

    def test_consolidation_state_changes_on_events(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        state0 = env.cluster.cluster_consolidation_state()
        env.clock.step(1)
        owned_node(env)
        assert env.cluster.cluster_consolidation_state() != state0

    def test_consolidation_state_forced_refresh(self):
        env = make_environment()
        state0 = env.cluster.cluster_consolidation_state()
        env.clock.step(301)  # 5-minute forced refresh
        assert env.cluster.cluster_consolidation_state() != state0

    def test_nomination_expires(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        env.cluster.nominate_node_for_pod(node.name)
        assert env.cluster.is_node_nominated(node.name)
        env.clock.step(21)
        assert not env.cluster.is_node_nominated(node.name)

    def test_startup_taints_filtered_until_initialized(self):
        from karpenter_core_tpu.apis.objects import Taint

        env = make_environment()
        env.kube.create(
            make_provisioner(startup_taints=[Taint("example.com/startup", "", "NoSchedule")])
        )
        node = owned_node(env, taints=[Taint("example.com/startup", "", "NoSchedule")])
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.taints() == []  # startup taint hidden while uninitialized
        node.metadata.labels[labels_api.LABEL_NODE_INITIALIZED] = "true"
        env.kube.apply(node)
        state_node = env.cluster.snapshot_nodes()[0]
        assert len(state_node.taints()) == 1


class TestResourceLevelMatrix:
    """state/suite_test.go:92-565 — the resource accounting table."""

    def test_inflight_capacity_combines_node_and_instance_type(self):
        # suite_test.go:105-133: values the node reports win; the instance
        # type stands in for the rest until the kubelet catches up
        env = make_environment()
        env.kube.create(make_provisioner())
        owned_node(env, allocatable={"cpu": 2}, capacity={"cpu": 2})
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.allocatable()["cpu"] == 2  # node-reported wins
        assert state_node.allocatable()["memory"] > 0  # instance-type stand-in

    def test_unbound_pods_not_counted(self):
        # suite_test.go:135-165
        env = make_environment()
        env.kube.create(make_provisioner())
        owned_node(env)
        env.kube.create(make_pod(requests={"cpu": 2}))  # pending, unbound
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.pod_requests_total().get("cpu", 0) == 0

    def test_terminal_pods_not_counted(self):
        # suite_test.go:280-317
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        for phase in ("Succeeded", "Failed"):
            env.kube.create(
                make_pod(
                    name=f"done-{phase.lower()}", requests={"cpu": 1},
                    node_name=node.name, unschedulable=False, phase=phase,
                )
            )
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.pod_requests_total().get("cpu", 0) == 0

    def test_pod_rebind_moves_usage(self):
        # suite_test.go:356-427: a missed delete shows up as the same pod
        # bound elsewhere; usage must move, not double-count
        env = make_environment()
        env.kube.create(make_provisioner())
        node1 = owned_node(env, name="n1")
        node2 = owned_node(env, name="n2")
        pod = make_pod(requests={"cpu": 2}, node_name=node1.name, unschedulable=False)
        env.kube.create(pod)
        pod.spec.node_name = node2.name
        env.kube.apply(pod)
        by_name = {n.node.name: n for n in env.cluster.snapshot_nodes()}
        assert by_name["n1"].pod_requests_total().get("cpu", 0) == 0
        assert by_name["n2"].pod_requests_total()["cpu"] == 2

    def test_usage_correct_across_churn(self):
        # suite_test.go:428-492
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        pods = [
            make_pod(name=f"churn-{i}", requests={"cpu": 1},
                     node_name=node.name, unschedulable=False)
            for i in range(5)
        ]
        for p in pods:
            env.kube.create(p)
        for p in pods[:3]:
            env.kube.delete(p, force=True)
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.pod_requests_total()["cpu"] == 2
        assert state_node.pod_count() == 2

    def test_daemonset_requests_tracked_separately(self):
        # suite_test.go:493-567
        from karpenter_core_tpu.testing import make_daemonset_pod

        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        env.kube.create(
            make_daemonset_pod(
                requests={"cpu": 1}, node_name=node.name, unschedulable=False
            )
        )
        env.kube.create(
            make_pod(requests={"cpu": 2}, node_name=node.name, unschedulable=False)
        )
        state_node = env.cluster.snapshot_nodes()[0]
        assert state_node.daemon_set_requests()["cpu"] == 1
        assert state_node.pod_requests_total()["cpu"] == 3  # daemons count too


class TestAntiAffinityTracking:
    """state/suite_test.go:617-792 — the anti-affinity pod index."""

    def _anti_pod(self, preferred=False, **kwargs):
        from karpenter_core_tpu.apis.objects import (
            LabelSelector,
            PodAffinityTerm,
            WeightedPodAffinityTerm,
        )

        term = PodAffinityTerm(
            topology_key=labels_api.LABEL_HOSTNAME,
            label_selector=LabelSelector(match_labels={"app": "a"}),
        )
        if preferred:
            kwargs["pod_anti_affinity_preferred"] = [
                WeightedPodAffinityTerm(weight=1, pod_affinity_term=term)
            ]
        else:
            kwargs["pod_anti_affinity"] = [term]
        return make_pod(labels={"app": "a"}, unschedulable=False, **kwargs)

    def _tracked(self, env):
        visited = []
        env.cluster.for_pods_with_anti_affinity(
            lambda p, n: visited.append(p.name) or True
        )
        return visited

    def test_preferred_anti_affinity_not_tracked(self):
        # suite_test.go:657-698
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        env.kube.create(self._anti_pod(preferred=True, node_name=node.name))
        assert self._tracked(env) == []

    def test_deleted_anti_pod_untracked(self):
        # suite_test.go:699-747
        env = make_environment()
        env.kube.create(make_provisioner())
        node = owned_node(env)
        pod = self._anti_pod(node_name=node.name)
        env.kube.create(pod)
        assert self._tracked(env) == [pod.name]
        env.kube.delete(pod, force=True)
        assert self._tracked(env) == []

    def test_anti_pod_bound_before_node_registers(self):
        # suite_test.go:748-792: the pod watch can fire before the node's;
        # the index must still resolve once the node arrives
        env = make_environment()
        env.kube.create(make_provisioner())
        pod = self._anti_pod(node_name="late-node")
        env.kube.create(pod)
        owned_node(env, name="late-node")
        visited = []
        env.cluster.for_pods_with_anti_affinity(
            lambda p, n: visited.append((p.name, n.name)) or True
        )
        assert visited == [(pod.name, "late-node")]


class TestRebuildReadsTheNodesPodsThroughTheIndex:
    """Cluster.update_node rebuilds a state node from the pods the store's
    spec.nodeName index holds for it — the same node the walk of every stored
    pod built, at the cost of the node's own pods."""

    ELSEWHERE = 5_000

    @staticmethod
    def _from_the_scan(env, node) -> StateNode:
        # the path this replaced: LIST every pod, keep the node's
        expected = StateNode(node, env.kube)
        for pod in env.kube.list_pods(selector=lambda p: p.spec.node_name == node.name):
            if not pod_util.is_terminal(pod):
                expected.update_for_pod(pod)
        return expected

    @staticmethod
    def _view(n: StateNode):
        return (
            n.pod_requests, n.pod_limits, n.daemonset_requests, n.daemonset_limits,
            n.host_port_usage().reserved, n.volume_usage().volumes,
            n.volume_usage().pod_volumes,
        )

    def _rebuild(self, env, node):
        """(state node, rebuilds, pods read) of one update_node."""
        rebuilds0 = STATE_NODE_REBUILDS.labels().value
        read0 = STATE_NODE_REBUILD_PODS.labels().value
        assert env.cluster.update_node(node) is None
        (state_node,) = [n for n in env.cluster.snapshot_nodes() if n.node.name == node.name]
        return (
            state_node,
            STATE_NODE_REBUILDS.labels().value - rebuilds0,
            STATE_NODE_REBUILD_PODS.labels().value - read0,
        )

    def test_a_rebuild_reads_the_nodes_pods_and_builds_what_the_scan_built(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        env.kube.create(StorageClass(metadata=ObjectMeta(name="sc", namespace=""), provisioner="ebs"))
        env.kube.create(
            PersistentVolumeClaim(
                metadata=ObjectMeta(name="claim", namespace="default"),
                spec=PersistentVolumeClaimSpec(storage_class_name="sc"),
            )
        )
        others = [owned_node(env, name=f"other-{i}") for i in range(10)]
        for i in range(self.ELSEWHERE):
            env.kube.create(
                make_pod(requests={"cpu": "10m"}, node_name=others[i % 10].name,
                         unschedulable=False)
            )
        node = owned_node(env, name="the-node")
        bound = [
            make_pod(requests={"cpu": 1, "memory": "1Gi"}, limits={"cpu": 2}),
            make_pod(requests={"cpu": "250m"}, host_ports=[8080, 9090]),
            make_pod(requests={"cpu": "100m"}, pvcs=["claim"]),
            make_daemonset_pod(requests={"cpu": "50m"}),
            make_pod(requests={"memory": "2Gi"}),
            make_pod(requests={"cpu": 3}, phase="Succeeded"),  # read, not counted
        ]
        for pod in bound:
            pod.spec.node_name = node.name
            env.kube.create(pod)
        k = len(bound)

        state_node, rebuilds, read = self._rebuild(env, node)
        assert (rebuilds, read) == (1, k)  # not k + 5 000
        assert self._view(state_node) == self._view(self._from_the_scan(env, node))
        assert state_node.pod_count() == k - 1
        assert state_node.host_port_usage().reserved[(bound[1].namespace, bound[1].name)]
        assert state_node.volume_usage().volumes == {"ebs": {"default/claim"}}
        live = {(p.namespace, p.name) for p in bound[:-1]}
        assert {key for key, name in env.cluster.bindings.items() if name == node.name} == live

        # a rebind on the live reference, written back: the pod leaves this node
        bound[0].spec.node_name = others[0].name
        env.kube.apply(bound[0])
        state_node, _, read = self._rebuild(env, node)
        assert read == k - 1
        assert self._view(state_node) == self._view(self._from_the_scan(env, node))
        assert (bound[0].namespace, bound[0].name) not in state_node.pod_requests
        assert env.cluster.bindings[(bound[0].namespace, bound[0].name)] == others[0].name
        other, _, read = self._rebuild(env, others[0])
        assert read == self.ELSEWHERE // 10 + 1
        assert self._view(other) == self._view(self._from_the_scan(env, others[0]))

        # a deletion: the index forgets the pod with the store
        env.kube.delete(bound[1], force=True)
        state_node, _, read = self._rebuild(env, node)
        assert read == k - 2
        assert self._view(state_node) == self._view(self._from_the_scan(env, node))
        assert state_node.host_port_usage().reserved == {
            (p.namespace, p.name): [] for p in bound[2:-1]
        }

    def test_a_node_nothing_is_bound_to_reads_nothing(self):
        env = make_environment()
        env.kube.create(make_provisioner())
        busy = owned_node(env, name="busy")
        for _ in range(50):
            env.kube.create(make_pod(node_name=busy.name, unschedulable=False))
        read0 = STATE_NODE_REBUILD_PODS.labels().value
        fresh = owned_node(env, name="fresh")  # the informer rebuilds it on ADDED
        state_node, _, read = self._rebuild(env, fresh)
        assert STATE_NODE_REBUILD_PODS.labels().value == read0 and read == 0
        assert state_node.pod_count() == 0


class TestConsolidationStateTriggers:
    def test_provisioner_update_changes_state(self):
        # state/suite_test.go:793-820 (generation-change filter lives in the
        # informer: only spec updates count)
        env = make_environment()
        prov = make_provisioner()
        env.kube.create(prov)
        state0 = env.cluster.cluster_consolidation_state()
        env.clock.step(1)
        prov.spec.weight = 50
        prov.metadata.generation += 1
        env.kube.apply(prov)
        assert env.cluster.cluster_consolidation_state() != state0
