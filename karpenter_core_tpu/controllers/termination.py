"""Termination: finalizer-driven graceful drain and instance deletion.

Mirror of /root/reference/pkg/controllers/termination/{controller.go:44-116,
terminate.go:50-170, eviction.go:40-120}: when a node has a deletion timestamp
and carries the termination finalizer — cordon (plus exclude-balancers label),
drain (do-not-evict aborts; skip tolerating/static pods; critical pods last)
through a rate-limited eviction queue, then CloudProvider.delete and finalizer
removal.
"""

from __future__ import annotations

import logging
import threading
from typing import List, Optional, Set, Tuple

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import Node, Pod
from karpenter_core_tpu.cloudprovider import MachineNotFoundError
from karpenter_core_tpu.controllers.node import machine_from_node
from karpenter_core_tpu.events import events as evt
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.utils import pod as pod_util
from karpenter_core_tpu.utils.clock import Clock

log = logging.getLogger(__name__)

TERMINATION_SUMMARY = REGISTRY.summary(
    "karpenter_nodes_termination_time_seconds",
    "The time taken between a node's deletion request and the removal of its finalizer",
)

EVICTION_QUEUE_BASE_DELAY = 0.1
EVICTION_QUEUE_MAX_DELAY = 10.0


class NodeDrainError(Exception):
    pass


class EvictionQueue:
    """Rate-limited async eviction worker (eviction.go:40-120).  In the
    standalone framework 'evicting' a pod = deleting it through the kube store,
    honoring PDBs the way the Evict API's 429 does."""

    def __init__(self, kube_client, recorder, clock: Optional[Clock] = None, synchronous: bool = True) -> None:
        self.kube_client = kube_client
        self.recorder = recorder
        self.clock = clock or Clock()
        self._set: Set[Tuple[str, str]] = set()
        self._queue: List[Tuple[str, str]] = []
        self._failures = {}
        self._lock = threading.Lock()
        self.synchronous = synchronous

    def add(self, pods: List[Pod]) -> None:
        with self._lock:
            for pod in pods:
                key = (pod.namespace, pod.name)
                if key not in self._set:
                    self._set.add(key)
                    self._queue.append(key)
        if self.synchronous:
            self.drain_queue()

    def drain_queue(self) -> None:
        """Process everything currently queued (one pass)."""
        while True:
            with self._lock:
                if not self._queue:
                    return
                key = self._queue.pop(0)
            if self._evict(key):
                with self._lock:
                    self._set.discard(key)
                    self._failures.pop(key, None)
            else:
                with self._lock:
                    failures = self._failures.get(key, 0) + 1
                    self._failures[key] = failures
                    self._queue.append(key)
                delay = min(
                    EVICTION_QUEUE_BASE_DELAY * (2 ** (failures - 1)), EVICTION_QUEUE_MAX_DELAY
                )
                self.clock.sleep(delay)
                if failures > 8:  # bounded retries per pass in synchronous mode
                    return

    def _evict(self, key: Tuple[str, str]) -> bool:
        namespace, name = key
        pod = self.kube_client.get_pod(namespace, name)
        if pod is None:
            return True  # 404: already gone
        # PDB check stands where the Evict API's 429 stands
        from karpenter_core_tpu.controllers.deprovisioning import PDBLimits

        pdbs = PDBLimits(self.kube_client)
        violated, ok = pdbs.can_evict_pods([pod])
        if not ok:
            if self.recorder is not None:
                self.recorder.publish(
                    evt.node_failed_to_drain(
                        Node(), f"evicting pod {namespace}/{name} violates pdb {violated}"
                    )
                )
            return False
        try:
            self.kube_client.delete(pod, force=True)
        except Exception:  # noqa: BLE001 - delete races are eviction failures
            return False
        if self.recorder is not None:
            self.recorder.publish(evt.evict_pod(pod))
        return True


class Terminator:
    def __init__(self, clock: Clock, kube_client, cloud_provider, eviction_queue: EvictionQueue) -> None:
        self.clock = clock
        self.kube_client = kube_client
        self.cloud_provider = cloud_provider
        self.eviction_queue = eviction_queue

    def cordon(self, node: Node) -> None:
        node.spec.unschedulable = True
        node.metadata.labels[labels_api.LABEL_NODE_EXCLUDE_BALANCERS] = "karpenter"
        self.kube_client.apply(node)
        log.info("cordoned node %s", node.name)

    def drain(self, node: Node) -> Optional[str]:
        """Error string while pods remain (drain is re-entrant, terminate.go:71-96)."""
        pods = self._get_pods(node)
        pods_to_evict = []
        for p in pods:
            if pod_util.has_do_not_evict(p):
                return f"pod {p.namespace}/{p.name} has do-not-evict annotation"
            if pod_util.tolerates_unschedulable_taint(p):
                continue
            if pod_util.is_owned_by_node(p):
                continue
            pods_to_evict.append(p)
        self._evict(pods_to_evict)
        if pods_to_evict:
            return f"{len(pods_to_evict)} pods are waiting to be evicted"
        return None

    def terminate(self, node: Node) -> Optional[str]:
        try:
            self.cloud_provider.delete(machine_from_node(node))
        except MachineNotFoundError:
            pass
        except Exception as e:  # noqa: BLE001
            return f"terminating cloudprovider instance, {e}"
        self.kube_client.remove_finalizer(node, labels_api.TERMINATION_FINALIZER)
        log.info("deleted node %s", node.name)
        return None

    def _get_pods(self, node: Node) -> List[Pod]:
        pods = []
        for p in self.kube_client.pods_on_node(node.name):
            if pod_util.is_terminal(p):
                continue
            if self._is_stuck_terminating(p):
                continue
            pods.append(p)
        return pods

    def _evict(self, pods: List[Pod]) -> None:
        """Critical pods evict last (terminate.go:136-156)."""
        critical, non_critical = [], []
        for pod in pods:
            if pod.metadata.deletion_timestamp is not None:
                continue
            if pod.spec.priority_class_name in ("system-cluster-critical", "system-node-critical"):
                critical.append(pod)
            else:
                non_critical.append(pod)
        if not non_critical:
            self.eviction_queue.add(critical)
        else:
            self.eviction_queue.add(non_critical)

    def _is_stuck_terminating(self, pod: Pod) -> bool:
        if pod.metadata.deletion_timestamp is None:
            return False
        return self.clock.now() > pod.metadata.deletion_timestamp + 60.0


class TerminationController:
    """Finalizes deleting nodes (controller.go:92-116)."""

    name = "termination"

    def __init__(self, clock: Clock, kube_client, cloud_provider, recorder=None) -> None:
        self.clock = clock
        self.kube_client = kube_client
        self.eviction_queue = EvictionQueue(kube_client, recorder, clock)
        self.terminator = Terminator(clock, kube_client, cloud_provider, self.eviction_queue)

    @tracing.traced("termination.reconcile")
    def reconcile(self, node: Node) -> Optional[float]:
        """Requeue seconds while draining, None when finalized."""
        stored = self.kube_client.get_node(node.name)
        if stored is None:
            return None
        if stored.metadata.deletion_timestamp is None:
            return None
        if labels_api.TERMINATION_FINALIZER not in stored.metadata.finalizers:
            return None
        self.terminator.cordon(stored)
        err = self.terminator.drain(stored)
        if err is not None:
            log.debug("draining node %s, %s", stored.name, err)
            return 1.0  # requeue while pods remain
        err = self.terminator.terminate(stored)
        if err is not None:
            log.error("%s", err)
            return 1.0
        TERMINATION_SUMMARY.observe(
            max(self.clock.now() - (stored.metadata.deletion_timestamp or 0.0), 0.0)
        )
        return None

    def reconcile_all(self) -> None:
        """Drive every deleting node to completion (or stuck-on-drain)."""
        for node in list(self.kube_client.list_nodes()):
            for _ in range(8):
                if self.reconcile(node) is None:
                    break
