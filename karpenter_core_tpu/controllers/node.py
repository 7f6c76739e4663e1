"""Node lifecycle: initialization, emptiness stamping, finalizer, drift.

Mirror of /root/reference/pkg/controllers/node/{controller.go:86-137,
initialization.go:39-125, emptiness.go:44-92, finalizer.go:36-49,
drift.go:39-60}: a sub-reconciler chain over nodes owned by a provisioner.
"""

from __future__ import annotations

import logging
from typing import Optional, Tuple

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import Node, OwnerReference
from karpenter_core_tpu.apis.v1alpha5 import Machine, MachineSpec, MachineStatus, Provisioner
from karpenter_core_tpu.state.cluster import Cluster
from karpenter_core_tpu.utils import node as node_util
from karpenter_core_tpu.utils import pod as pod_util
from karpenter_core_tpu.utils import resources as resources_util
from karpenter_core_tpu.utils.clock import Clock

log = logging.getLogger(__name__)

DRIFT_POLL_INTERVAL = 5 * 60.0  # drift.go: 5 minute requeue


class Initialization:
    """Sets karpenter.sh/initialized=true once Ready + startup taints removed +
    extended resources registered (initialization.go:39-125)."""

    def __init__(self, cloud_provider) -> None:
        self.cloud_provider = cloud_provider

    def reconcile(self, provisioner: Optional[Provisioner], node: Node) -> Optional[float]:
        if node.metadata.labels.get(labels_api.LABEL_NODE_INITIALIZED) == "true":
            return None
        instance_type = self._get_instance_type(
            provisioner, node.metadata.labels.get(labels_api.LABEL_INSTANCE_TYPE_STABLE)
        )
        if not self._is_initialized(node, provisioner, instance_type):
            return None
        node.metadata.labels[labels_api.LABEL_NODE_INITIALIZED] = "true"
        return None

    def _get_instance_type(self, provisioner, name):
        if provisioner is None:
            return None
        for it in self.cloud_provider.get_instance_types(provisioner):
            if it.name == name:
                return it
        return None

    def _is_initialized(self, node: Node, provisioner, instance_type) -> bool:
        condition = node_util.get_condition(node, "Ready")
        if condition is None or condition.status != "True":
            return False
        if not startup_taint_removed(node, provisioner)[1]:
            return False
        if not extended_resource_registered(node, instance_type)[1]:
            return False
        return True


def startup_taint_removed(node: Node, provisioner) -> Tuple[Optional[object], bool]:
    if provisioner is not None:
        for startup_taint in provisioner.spec.startup_taints:
            for taint in node.spec.taints:
                if (
                    startup_taint.key == taint.key
                    and startup_taint.value == taint.value
                    and startup_taint.effect == taint.effect
                ):
                    return taint, False
    return None, True


def extended_resource_registered(node: Node, instance_type) -> Tuple[str, bool]:
    """Device-plugin resources show as zero allocatable until registered
    (initialization.go:108-125)."""
    if instance_type is None:
        return "", True
    for name, quantity in instance_type.capacity.items():
        if resources_util.is_zero(quantity):
            continue
        if resources_util.is_zero(node.status.allocatable.get(name, 0.0)):
            return name, False
    return "", True


class EmptinessStamper:
    """Stamps/clears the emptiness-timestamp annotation (emptiness.go:44-92)."""

    def __init__(self, clock: Clock, kube_client, cluster: Cluster) -> None:
        self.clock = clock
        self.kube_client = kube_client
        self.cluster = cluster

    def reconcile(self, provisioner: Optional[Provisioner], node: Node) -> Optional[float]:
        if provisioner is None or provisioner.spec.ttl_seconds_after_empty is None:
            return None
        if node.metadata.labels.get(labels_api.LABEL_NODE_INITIALIZED) != "true":
            return None
        empty = self._is_empty(node)
        if self.cluster.is_node_nominated(node.name):
            return None
        has_timestamp = labels_api.EMPTINESS_TIMESTAMP_ANNOTATION_KEY in node.metadata.annotations
        if not empty and has_timestamp:
            del node.metadata.annotations[labels_api.EMPTINESS_TIMESTAMP_ANNOTATION_KEY]
            log.info("removed emptiness TTL from node %s", node.name)
        elif empty and not has_timestamp:
            node.metadata.annotations[labels_api.EMPTINESS_TIMESTAMP_ANNOTATION_KEY] = str(
                self.clock.now()
            )
            log.info("added TTL to empty node %s", node.name)
        return 60.0

    def _is_empty(self, node: Node) -> bool:
        for pod in self.kube_client.pods_on_node(node.name):
            if (
                not pod_util.is_terminal(pod)
                and not pod_util.is_owned_by_daemon_set(pod)
                and not pod_util.is_owned_by_node(pod)
            ):
                return False
        return True


class Finalizer:
    """Ensures the termination finalizer and provisioner owner-ref
    (finalizer.go:36-49)."""

    def reconcile(self, provisioner: Optional[Provisioner], node: Node) -> Optional[float]:
        if node.metadata.deletion_timestamp is not None:
            return None
        if labels_api.TERMINATION_FINALIZER not in node.metadata.finalizers:
            node.metadata.finalizers.append(labels_api.TERMINATION_FINALIZER)
        if provisioner is not None and not any(
            ref.kind == "Provisioner" for ref in node.metadata.owner_references
        ):
            node.metadata.owner_references.append(
                OwnerReference(
                    api_version="karpenter.sh/v1alpha5",
                    kind="Provisioner",
                    name=provisioner.name,
                    uid=provisioner.metadata.uid,
                )
            )
        return None


class DriftDetector:
    """Polls CloudProvider.is_machine_drifted and annotates (drift.go:39-60)."""

    def __init__(self, cloud_provider, settings) -> None:
        self.cloud_provider = cloud_provider
        self.settings = settings

    def reconcile(self, provisioner: Optional[Provisioner], node: Node) -> Optional[float]:
        if not self.settings.drift_enabled:
            return None
        if (
            node.metadata.annotations.get(labels_api.VOLUNTARY_DISRUPTION_ANNOTATION_KEY)
            == labels_api.VOLUNTARY_DISRUPTION_DRIFTED_ANNOTATION_VALUE
        ):
            return DRIFT_POLL_INTERVAL
        machine = machine_from_node(node)
        if self.cloud_provider.is_machine_drifted(machine):
            node.metadata.annotations[labels_api.VOLUNTARY_DISRUPTION_ANNOTATION_KEY] = (
                labels_api.VOLUNTARY_DISRUPTION_DRIFTED_ANNOTATION_VALUE
            )
        return DRIFT_POLL_INTERVAL


def machine_from_node(node: Node) -> Machine:
    """utils/machine.NewFromNode (machine.go:45)."""
    machine = Machine(
        spec=MachineSpec(taints=list(node.spec.taints)),
        status=MachineStatus(
            provider_id=node.spec.provider_id,
            capacity=dict(node.status.capacity),
            allocatable=dict(node.status.allocatable),
        ),
    )
    machine.metadata.name = node.name
    machine.metadata.labels = dict(node.metadata.labels)
    machine.metadata.annotations = dict(node.metadata.annotations)
    return machine


class NodeController:
    """Sub-reconciler chain over owned, non-deleting nodes (controller.go:86-99)."""

    name = "node"

    def __init__(self, clock, kube_client, cloud_provider, cluster, settings) -> None:
        self.kube_client = kube_client
        self.initialization = Initialization(cloud_provider)
        self.emptiness = EmptinessStamper(clock, kube_client, cluster)
        self.finalizer = Finalizer()
        self.drift = DriftDetector(cloud_provider, settings)

    @tracing.traced("node.reconcile")
    def reconcile(self, node: Node) -> Optional[float]:
        stored = self.kube_client.get_node(node.name)
        if stored is None or stored.metadata.deletion_timestamp is not None:
            return None
        provisioner_name = stored.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY)
        if not provisioner_name:
            return None
        provisioner = self.kube_client.get(Provisioner, provisioner_name)
        from karpenter_core_tpu.apis.objects import deep_copy

        before = deep_copy(stored)
        requeue: Optional[float] = None
        for sub in (self.initialization, self.emptiness, self.finalizer, self.drift):
            after = sub.reconcile(provisioner, stored)
            if after is not None:
                requeue = after if requeue is None else min(requeue, after)
        # write only on change: an unconditional apply would re-trigger this
        # controller through its own watch forever
        if stored != before:
            self.kube_client.apply(stored)
        return requeue

    def reconcile_all(self) -> None:
        for node in self.kube_client.list_nodes():
            self.reconcile(node)
