"""In-memory cluster state: the input snapshot for every solve.

Mirror of /root/reference/pkg/controllers/state/{cluster.go:42-407,
node.go:38-190}: nodes keyed by provider id, pod→node bindings, an
anti-affinity pod index, node nomination with a TTL window, mark-for-deletion,
and a consolidation-state timestamp that gates deprovisioning work.
"""

from __future__ import annotations

import copy
import threading
from typing import Callable, Dict, List, Optional, Tuple

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import Node, Pod, Taint
from karpenter_core_tpu.apis.v1alpha5 import Provisioner
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.scheduling import HostPortUsage, VolumeCount, VolumeUsage
from karpenter_core_tpu.utils import pod as pod_util
from karpenter_core_tpu.utils import resources as resources_util
from karpenter_core_tpu.utils.clock import Clock

STATE_NODE_REBUILDS = REGISTRY.counter(
    "karpenter_cluster_state_node_rebuilds_total",
    "State nodes rebuilt from their API object (Cluster.update_node): each "
    "reads the node's pods through the store's spec.nodeName index.",
)
STATE_NODE_REBUILD_PODS = REGISTRY.counter(
    "karpenter_cluster_state_node_rebuild_pods_total",
    "Pods the state-node rebuilds read from the store (terminal ones "
    "included): what a rebuild costs, 0 for a node nothing is bound to.",
)

TAINT_NODE_NOT_READY = "node.kubernetes.io/not-ready"
TAINT_NODE_UNREACHABLE = "node.kubernetes.io/unreachable"


class StateNode:
    """state.Node: a node with cached pod usage and inflight capacity."""

    def __init__(self, node: Node, kube_client=None) -> None:
        self.node = node
        self.inflight_allocatable: resources_util.ResourceList = {}
        self.inflight_capacity: resources_util.ResourceList = {}
        self.startup_taints: List[Taint] = []
        self.daemonset_requests: Dict[Tuple[str, str], resources_util.ResourceList] = {}
        self.daemonset_limits: Dict[Tuple[str, str], resources_util.ResourceList] = {}
        self.pod_requests: Dict[Tuple[str, str], resources_util.ResourceList] = {}
        self.pod_limits: Dict[Tuple[str, str], resources_util.ResourceList] = {}
        self._host_port_usage = HostPortUsage()
        self._volume_usage = VolumeUsage(kube_client)
        self._volume_limits = VolumeCount()
        self.marked_for_deletion = False
        self.nominated_until = 0.0

    # -- predicates ------------------------------------------------------------

    def initialized(self) -> bool:
        return self.node.metadata.labels.get(labels_api.LABEL_NODE_INITIALIZED) == "true"

    def owned(self) -> bool:
        return bool(self.node.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY))

    def marked(self) -> bool:
        return self.marked_for_deletion or self.node.metadata.deletion_timestamp is not None

    def nominated(self, clock: Clock) -> bool:
        return self.nominated_until > clock.now()

    # -- resources (node.go:80-145) ---------------------------------------------

    def taints(self) -> List[Taint]:
        """Node taints minus ephemeral/startup taints (node.go:61-78)."""
        ephemeral = [
            Taint(key=TAINT_NODE_NOT_READY, effect="NoSchedule"),
            Taint(key=TAINT_NODE_UNREACHABLE, effect="NoSchedule"),
        ]
        if not self.initialized() and self.owned():
            ephemeral.extend(self.startup_taints)
        return [
            t
            for t in self.node.spec.taints
            if not any(
                e.key == t.key and e.value == t.value and e.effect == t.effect
                for e in ephemeral
            )
        ]

    def capacity(self) -> resources_util.ResourceList:
        if not self.initialized() and self.owned():
            out = dict(self.node.status.capacity)
            for name, qty in self.inflight_capacity.items():
                if resources_util.is_zero(out.get(name, 0.0)):
                    out[name] = qty
            return out
        return dict(self.node.status.capacity)

    def allocatable(self) -> resources_util.ResourceList:
        if not self.initialized() and self.owned():
            out = dict(self.node.status.allocatable)
            for name, qty in self.inflight_allocatable.items():
                if resources_util.is_zero(out.get(name, 0.0)):
                    out[name] = qty
            return out
        return dict(self.node.status.allocatable)

    def available(self) -> resources_util.ResourceList:
        return resources_util.subtract(self.allocatable(), self.pod_requests_total())

    def pod_requests_total(self) -> resources_util.ResourceList:
        return resources_util.merge(*self.pod_requests.values())

    def pod_limits_total(self) -> resources_util.ResourceList:
        return resources_util.merge(*self.pod_limits.values())

    def daemon_set_requests(self) -> resources_util.ResourceList:
        return resources_util.merge(*self.daemonset_requests.values())

    def daemon_set_limits(self) -> resources_util.ResourceList:
        return resources_util.merge(*self.daemonset_limits.values())

    def host_port_usage(self) -> HostPortUsage:
        return self._host_port_usage

    def volume_usage(self) -> VolumeUsage:
        return self._volume_usage

    def volume_limits(self) -> VolumeCount:
        return self._volume_limits

    def pod_count(self) -> int:
        return len(self.pod_requests)

    # -- pod tracking (node.go:161-180) ------------------------------------------

    def update_for_pod(self, pod: Pod) -> None:
        key = (pod.namespace, pod.name)
        self.pod_requests[key] = resources_util.requests_for_pods(pod)
        self.pod_limits[key] = resources_util.limits_for_pods(pod)
        if pod_util.is_owned_by_daemon_set(pod):
            self.daemonset_requests[key] = resources_util.requests_for_pods(pod)
            self.daemonset_limits[key] = resources_util.limits_for_pods(pod)
        self._host_port_usage.add(pod)
        self._volume_usage.add(pod)

    def cleanup_for_pod(self, key: Tuple[str, str]) -> None:
        self._host_port_usage.delete_pod(key)
        self._volume_usage.delete_pod(key)
        self.pod_requests.pop(key, None)
        self.pod_limits.pop(key, None)
        self.daemonset_requests.pop(key, None)
        self.daemonset_limits.pop(key, None)

    def deep_copy(self) -> "StateNode":
        out = StateNode(copy.deepcopy(self.node), self._volume_usage.kube_client)
        out.inflight_allocatable = dict(self.inflight_allocatable)
        out.inflight_capacity = dict(self.inflight_capacity)
        out.startup_taints = list(self.startup_taints)
        out.daemonset_requests = copy.deepcopy(self.daemonset_requests)
        out.daemonset_limits = copy.deepcopy(self.daemonset_limits)
        out.pod_requests = copy.deepcopy(self.pod_requests)
        out.pod_limits = copy.deepcopy(self.pod_limits)
        out._host_port_usage = self._host_port_usage.deep_copy()
        out._volume_usage = self._volume_usage.deep_copy()
        out._volume_limits = VolumeCount(self._volume_limits)
        out.marked_for_deletion = self.marked_for_deletion
        out.nominated_until = self.nominated_until
        return out


def nomination_window(settings) -> float:
    """2× batch max duration, min 10s (node.go:184-190)."""
    period = 2.0 * settings.batch_max_duration
    return max(period, 10.0)


class Cluster:
    def __init__(self, clock: Clock, kube_client, cloud_provider, settings=None) -> None:
        self.clock = clock
        self.kube_client = kube_client
        self.cloud_provider = cloud_provider
        self.settings = settings
        self._mu = threading.RLock()
        self.nodes: Dict[str, StateNode] = {}  # provider id -> node
        self.bindings: Dict[Tuple[str, str], str] = {}  # pod key -> node name
        self.name_to_provider_id: Dict[str, str] = {}
        self.anti_affinity_pods: Dict[Tuple[str, str], Pod] = {}
        self._consolidation_state: float = 0.0

    # -- iteration ---------------------------------------------------------------

    def for_pods_with_anti_affinity(self, fn: Callable[[Pod, Node], bool]) -> None:
        with self._mu:
            items = list(self.anti_affinity_pods.items())
        for key, pod in items:
            with self._mu:
                node_name = self.bindings.get(key)
                if node_name is None:
                    continue
                state_node = self.nodes.get(self.name_to_provider_id.get(node_name, ""))
                if state_node is None:
                    continue
                node = state_node.node
            if not fn(pod, node):
                return

    def for_each_node(self, fn: Callable[[StateNode], bool]) -> None:
        with self._mu:
            nodes = list(self.nodes.values())
        for node in nodes:
            if not fn(node):
                return

    def snapshot_nodes(self) -> List[StateNode]:
        """Deep-copied state nodes (the scheduler mutates them)."""
        with self._mu:
            return [n.deep_copy() for n in self.nodes.values()]

    # -- nomination / deletion marks ----------------------------------------------

    def is_node_nominated(self, name: str) -> bool:
        with self._mu:
            node = self.nodes.get(self.name_to_provider_id.get(name, ""))
            return node.nominated(self.clock) if node else False

    def nominate_node_for_pod(self, name: str) -> None:
        window = nomination_window(self.settings) if self.settings else 10.0
        with self._mu:
            node = self.nodes.get(self.name_to_provider_id.get(name, ""))
            if node is not None:
                node.nominated_until = self.clock.now() + window

    def mark_for_deletion(self, *names: str) -> None:
        with self._mu:
            for name in names:
                node = self.nodes.get(self.name_to_provider_id.get(name, ""))
                if node is not None:
                    node.marked_for_deletion = True

    def unmark_for_deletion(self, *names: str) -> None:
        with self._mu:
            for name in names:
                node = self.nodes.get(self.name_to_provider_id.get(name, ""))
                if node is not None:
                    node.marked_for_deletion = False

    # -- consolidation state (cluster.go:195-215) -----------------------------------

    def record_consolidation_change(self) -> None:
        self._consolidation_state = self.clock.now()

    def cluster_consolidation_state(self) -> float:
        cs = self._consolidation_state
        # force a refresh at least every 5 minutes
        if self.clock.now() > cs + 300.0:
            self.record_consolidation_change()
            return self._consolidation_state
        return cs

    # -- ingestion (cluster.go:152-196, 227-343) --------------------------------------

    def update_node(self, node: Node) -> Optional[str]:
        with self._mu:
            if not node.spec.provider_id:
                node.spec.provider_id = node.name
            old = self.nodes.get(node.spec.provider_id)
            new, err = self._new_state_from_node(node, old)
            if err is not None:
                return err
            self.nodes[node.spec.provider_id] = new
            self.name_to_provider_id[node.name] = node.spec.provider_id
            return None

    def delete_node(self, node_name: str) -> None:
        with self._mu:
            provider_id = self.name_to_provider_id.get(node_name)
            if provider_id:
                self.nodes.pop(provider_id, None)
                del self.name_to_provider_id[node_name]
                self.record_consolidation_change()

    def update_pod(self, pod: Pod) -> Optional[str]:
        err = None
        if pod_util.is_terminal(pod):
            self._update_node_usage_from_pod_completion((pod.namespace, pod.name))
        else:
            err = self._update_node_usage_from_pod(pod)
        self._update_pod_anti_affinities(pod)
        return err

    def delete_pod(self, pod_key: Tuple[str, str]) -> None:
        with self._mu:
            self.anti_affinity_pods.pop(pod_key, None)
        self._update_node_usage_from_pod_completion(pod_key)
        self.record_consolidation_change()

    def reset(self) -> None:
        with self._mu:
            self.nodes = {}
            self.name_to_provider_id = {}
            self.bindings = {}
            self.anti_affinity_pods = {}

    # -- internals -------------------------------------------------------------------

    def _new_state_from_node(
        self, node: Node, old: Optional[StateNode]
    ) -> Tuple[Optional[StateNode], Optional[str]]:
        STATE_NODE_REBUILDS.inc()
        n = StateNode(node, self.kube_client)
        if old is not None:
            n.marked_for_deletion = old.marked_for_deletion
            n.nominated_until = old.nominated_until
        for populate in (
            self._populate_startup_taints,
            self._populate_inflight,
            self._populate_resource_requests,
            self._populate_volume_limits,
        ):
            err = populate(n)
            if err is not None:
                return None, err
        self._trigger_consolidation_on_change(old, n)
        return n, None

    def _get_provisioner(self, n: StateNode) -> Optional[Provisioner]:
        name = n.node.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY)
        if not name:
            return None
        return self.kube_client.get(Provisioner, name)

    def _populate_startup_taints(self, n: StateNode) -> Optional[str]:
        if not n.owned():
            return None
        provisioner = self._get_provisioner(n)
        if provisioner is not None:
            n.startup_taints = list(provisioner.spec.startup_taints)
        return None

    def _populate_inflight(self, n: StateNode) -> Optional[str]:
        if not n.owned():
            return None
        provisioner = self._get_provisioner(n)
        if provisioner is None:
            return None
        instance_types = self.cloud_provider.get_instance_types(provisioner)
        it_name = n.node.metadata.labels.get(labels_api.LABEL_INSTANCE_TYPE_STABLE)
        instance_type = next((it for it in instance_types if it.name == it_name), None)
        if instance_type is None:
            return f"instance type {it_name!r} not found"
        n.inflight_capacity = dict(instance_type.capacity)
        n.inflight_allocatable = instance_type.allocatable()
        return None

    def _populate_volume_limits(self, n: StateNode) -> Optional[str]:
        csi_node = self.kube_client.get_csi_node(n.node.name)
        if csi_node is not None:
            for driver in csi_node.drivers:
                if driver.allocatable_count is not None:
                    n._volume_limits[driver.name] = driver.allocatable_count
        return None

    def _populate_resource_requests(self, n: StateNode) -> Optional[str]:
        pods = self.kube_client.pods_on_node(n.node.name)
        STATE_NODE_REBUILD_PODS.inc(len(pods))
        for pod in pods:
            if pod_util.is_terminal(pod):
                continue
            self._cleanup_old_bindings(pod)
            n.update_for_pod(pod)
            self.bindings[(pod.namespace, pod.name)] = pod.spec.node_name
        return None

    def _update_node_usage_from_pod(self, pod: Pod) -> Optional[str]:
        if not pod.spec.node_name:
            return None
        with self._mu:
            node = self.nodes.get(self.name_to_provider_id.get(pod.spec.node_name, ""))
            if node is None:
                return f"node {pod.spec.node_name} not found"
            self._cleanup_old_bindings(pod)
            node.update_for_pod(pod)
            self.bindings[(pod.namespace, pod.name)] = pod.spec.node_name
            return None

    def _update_node_usage_from_pod_completion(self, pod_key: Tuple[str, str]) -> None:
        with self._mu:
            node_name = self.bindings.pop(pod_key, None)
            if node_name is None:
                return
            node = self.nodes.get(self.name_to_provider_id.get(node_name, ""))
            if node is not None:
                node.cleanup_for_pod(pod_key)

    def _cleanup_old_bindings(self, pod: Pod) -> None:
        key = (pod.namespace, pod.name)
        old_node_name = self.bindings.get(key)
        if old_node_name is not None:
            if old_node_name == pod.spec.node_name:
                return
            old_node = self.nodes.get(self.name_to_provider_id.get(old_node_name, ""))
            if old_node is not None:
                old_node.cleanup_for_pod(key)
                del self.bindings[key]
        self.record_consolidation_change()

    def _update_pod_anti_affinities(self, pod: Pod) -> None:
        key = (pod.namespace, pod.name)
        with self._mu:
            if pod_util.has_pod_anti_affinity(pod):
                self.anti_affinity_pods[key] = pod
            else:
                self.anti_affinity_pods.pop(key, None)

    def _trigger_consolidation_on_change(
        self, old: Optional[StateNode], new: Optional[StateNode]
    ) -> None:
        if old is None or new is None:
            self.record_consolidation_change()
            return
        if old.initialized() != new.initialized():
            self.record_consolidation_change()
            return
        if old.marked() != new.marked():
            self.record_consolidation_change()
