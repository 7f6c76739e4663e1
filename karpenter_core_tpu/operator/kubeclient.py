"""In-memory object store standing where the kube-apiserver stands.

The reference's distributed communication backend is the apiserver watch/list
plane (SURVEY.md §5.8; controller-runtime informers).  This framework is
standalone: the KubeClient is the single source of truth for API objects, with
list/get/create/update/delete plus watch callbacks that pump the state cluster
informers (karpenter_core_tpu.state.informer).  Thread-safe; watch events are
delivered synchronously in the mutating thread.
"""

from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional

from karpenter_core_tpu.apis.objects import (
    CSINode,
    Lease,
    LabelSelector,
    Namespace,
    Node,
    PersistentVolume,
    PersistentVolumeClaim,
    Pod,
    PodDisruptionBudget,
    StorageClass,
    deep_copy,
)
from karpenter_core_tpu.apis.v1alpha5 import Machine, Provisioner
from karpenter_core_tpu.chaos import plane as _chaos

WatchFunc = Callable[[str, object], None]  # (event_type, object); ADDED|MODIFIED|DELETED

# the kubeapi.put injection point covers every client-side mutation (create/
# update/apply/delete) on BOTH kube backends: the in-memory client fires it in
# _throttle(), the apiserver transport (kubeapi/client.py) imports this Point
# and fires it per mutating HTTP request — one name, one registration.
KUBEAPI_PUT = _chaos.point("kubeapi.put")


class ConflictError(Exception):
    pass


class NotFoundError(Exception):
    pass


def raise_injected_kubeapi_fault(fault: "_chaos.Fault") -> None:
    """Map an injected kubeapi fault onto the client error surface callers
    already handle: 404 → NotFoundError, 409 → ConflictError, anything else
    (incl. timeout kinds) → InjectedFault.  Shared by both backends so a
    chaos scenario behaves identically against either."""
    if fault.code == 404:
        raise NotFoundError(fault.describe())
    if fault.code == 409:
        raise ConflictError(fault.describe())
    raise _chaos.InjectedFault(fault)


class RateLimiter:
    """Client-side mutation throttle (--kube-client-qps/-burst,
    options.go:61-62): token bucket over create/update/delete.  Shared by the
    in-memory KubeClient and the apiserver-backed client (kubeapi.client) so
    both backends meter writes identically.  ``qps`` None/0 disables."""

    def __init__(self, qps: "Optional[float]", burst: "Optional[int]",
                 now=None, sleep=None) -> None:
        import time as _time

        self._now = now or _time.time
        self._sleep = sleep or _time.sleep
        self._qps = qps
        if qps:
            self._burst = max(burst if burst is not None else int(qps * 1.5), 1)
        else:
            self._burst = None
        self._tokens = float(self._burst or 0)
        self._last_refill = self._now()
        self._lock = threading.Lock()

    def take(self) -> None:
        if not self._qps:
            return
        while True:
            with self._lock:
                now = self._now()
                self._tokens = min(
                    float(self._burst), self._tokens + (now - self._last_refill) * self._qps
                )
                self._last_refill = now
                if self._tokens >= 1.0:
                    self._tokens -= 1.0
                    return
                wait = (1.0 - self._tokens) / self._qps
            self._sleep(wait)


class PodNodeIndex:
    """``spec.nodeName`` → the pods bound to it: the informer cache's field
    index (operator.go:131), so a node's pods are read in O(pods on the node)
    instead of a walk of every stored pod.  A pod with no node name is not
    indexed.  Not locked: the owning store mutates and reads it under its own
    lock (KubeClient._lock, Reflector.lock)."""

    def __init__(self) -> None:
        self._by_node: Dict[str, Dict[tuple, Pod]] = {}
        # pod key → the node name it is indexed under.  Both stores hand out
        # live references and callers bind in place, so by the time a write
        # arrives the old node name is no longer on the object.
        self._node_of: Dict[tuple, str] = {}

    def put(self, key: tuple, pod: Pod) -> None:
        node_name = pod.spec.node_name
        if self._node_of.get(key) != node_name:
            self.drop(key)
        if node_name:
            self._by_node.setdefault(node_name, {})[key] = pod
            self._node_of[key] = node_name

    def drop(self, key: tuple) -> None:
        node_name = self._node_of.pop(key, None)
        if node_name is None:
            return
        pods = self._by_node[node_name]
        del pods[key]
        if not pods:
            del self._by_node[node_name]

    def pods_on_node(self, node_name: str) -> List[Pod]:
        return list(self._by_node.get(node_name, {}).values())


class _Store:
    """One kind's storage: keyed by (namespace, name) or name for cluster scope."""

    def __init__(self, namespaced: bool, by_node: Optional[PodNodeIndex] = None) -> None:
        self.namespaced = namespaced
        self.objects: Dict[tuple, object] = {}
        self.watchers: List[WatchFunc] = []
        self.by_node = by_node

    def key(self, obj) -> tuple:
        meta = obj.metadata
        return (meta.namespace, meta.name) if self.namespaced else (meta.name,)

    def put(self, key: tuple, obj) -> None:
        self.objects[key] = obj
        if self.by_node is not None:
            self.by_node.put(key, obj)

    def remove(self, key: tuple) -> None:
        del self.objects[key]
        if self.by_node is not None:
            self.by_node.drop(key)


class KubeClient:
    def __init__(self, clock=None, qps: "Optional[float]" = None, burst: "Optional[int]" = None) -> None:
        import time as _time

        self._now = clock.now if clock is not None else _time.time
        self._sleep = clock.sleep if clock is not None else _time.sleep
        self._limiter = RateLimiter(qps, burst, now=self._now, sleep=self._sleep)
        self._lock = threading.RLock()
        self._stores: Dict[type, _Store] = {
            Pod: _Store(True, by_node=PodNodeIndex()),
            Node: _Store(False),
            Provisioner: _Store(False),
            Machine: _Store(False),
            Namespace: _Store(False),
            PodDisruptionBudget: _Store(True),
            PersistentVolumeClaim: _Store(True),
            PersistentVolume: _Store(False),
            StorageClass: _Store(False),
            CSINode: _Store(False),
            Lease: _Store(True),
        }
        self._resource_version = 0

    # -- generic CRUD ---------------------------------------------------------

    def _store(self, kind: type) -> _Store:
        if kind not in self._stores:
            self._stores[kind] = _Store(hasattr(kind, "namespace"))
        return self._stores[kind]

    def _throttle(self) -> None:
        self._limiter.take()
        fault = KUBEAPI_PUT.hit(
            kinds=(_chaos.KIND_ERROR, _chaos.KIND_TIMEOUT), backend="memory"
        )
        if fault is not None and fault.kind in (_chaos.KIND_ERROR, _chaos.KIND_TIMEOUT):
            raise_injected_kubeapi_fault(fault)

    def create(self, obj) -> object:
        self._throttle()
        return self._create(obj)

    def _create(self, obj) -> object:
        with self._lock:
            store = self._store(type(obj))
            key = store.key(obj)
            if key in store.objects:
                raise ConflictError(f"{type(obj).__name__} {key} already exists")
            self._resource_version += 1
            obj.metadata.resource_version = self._resource_version
            if not obj.metadata.creation_timestamp:
                obj.metadata.creation_timestamp = self._now()
            store.put(key, obj)
            watchers = list(store.watchers)
        for w in watchers:
            w("ADDED", obj)
        return obj

    def get(self, kind: type, name: str, namespace: Optional[str] = None):
        with self._lock:
            store = self._store(kind)
            key = (namespace, name) if store.namespaced else (name,)
            return store.objects.get(key)

    def update(self, obj) -> object:
        self._throttle()
        return self._update(obj)

    def _update(self, obj, expected_version: "Optional[int]" = None) -> object:
        with self._lock:
            store = self._store(type(obj))
            key = store.key(obj)
            stored = store.objects.get(key)
            if stored is None:
                raise NotFoundError(f"{type(obj).__name__} {key} not found")
            if (
                expected_version is not None
                and stored.metadata.resource_version != expected_version
            ):
                raise ConflictError(
                    f"{type(obj).__name__} {key} resourceVersion "
                    f"{stored.metadata.resource_version} != {expected_version}"
                )
            self._resource_version += 1
            obj.metadata.resource_version = self._resource_version
            store.put(key, obj)
            watchers = list(store.watchers)
        for w in watchers:
            w("MODIFIED", obj)
        return obj

    def update_with_version(self, obj, expected_resource_version: int) -> object:
        """Optimistic-concurrency update: fails with ConflictError when the
        stored object's resourceVersion moved past ``expected`` — the CAS the
        leader-election lease protocol needs (client-go semantics).

        ``obj`` must be the caller's own COPY and ``expected`` the version
        snapshotted at read time: this in-memory client hands out live object
        references, so a CAS against a shared mutated object is vacuous."""
        self._throttle()
        return self._update(obj, expected_version=expected_resource_version)

    def apply(self, obj) -> object:
        """create-or-update.  Watch callbacks must never fire under the store
        lock (informer callbacks take Cluster locks whose holders call back
        into this client — AB-BA), so this composes the unlocked primitives."""
        self._throttle()
        try:
            return self._create(obj)
        except ConflictError:
            return self._update(obj)

    def delete(self, obj, *, force: bool = False) -> None:
        """Sets deletion timestamp; the object is removed once finalizers clear
        (or immediately with no finalizers) — k8s deletion semantics."""
        self._throttle()
        with self._lock:
            store = self._store(type(obj))
            key = store.key(obj)
            stored = store.objects.get(key)
            if stored is None:
                raise NotFoundError(f"{type(obj).__name__} {key} not found")
            if stored.metadata.finalizers and not force:
                if stored.metadata.deletion_timestamp is None:
                    stored.metadata.deletion_timestamp = self._now()
                    self._resource_version += 1
                    stored.metadata.resource_version = self._resource_version
                    # a write like any other: held by its finalizer, the pod
                    # stays indexed under the node name it carries now
                    store.put(key, stored)
                    watchers = list(store.watchers)
                    event = ("MODIFIED", stored)
                else:
                    return
            else:
                store.remove(key)
                watchers = list(store.watchers)
                event = ("DELETED", stored)
        for w in watchers:
            w(*event)

    def remove_finalizer(self, obj, finalizer: str) -> None:
        with self._lock:
            store = self._store(type(obj))
            stored = store.objects.get(store.key(obj))
            if stored is None:
                return
            if finalizer in stored.metadata.finalizers:
                stored.metadata.finalizers = [
                    f for f in stored.metadata.finalizers if f != finalizer
                ]
            should_remove = (
                stored.metadata.deletion_timestamp is not None
                and not stored.metadata.finalizers
            )
        self.update(stored)
        if should_remove:
            self.delete(stored, force=True)

    def list(self, kind: type, namespace: Optional[str] = None, selector=None) -> list:
        with self._lock:
            store = self._store(kind)
            out = []
            for key, obj in store.objects.items():
                if namespace is not None and store.namespaced and key[0] != namespace:
                    continue
                if selector is not None and not _selector_matches(selector, obj):
                    continue
                out.append(obj)
            return out

    def watch(self, kind: type, callback: WatchFunc, *, replay: bool = True) -> None:
        with self._lock:
            store = self._store(kind)
            store.watchers.append(callback)
            existing = list(store.objects.values()) if replay else []
        for obj in existing:
            callback("ADDED", obj)

    # -- typed conveniences (shapes used by scheduler/topology/volumes) -------

    def list_pods(self, namespace: Optional[str] = None, selector=None) -> List[Pod]:
        return self.list(Pod, namespace=namespace, selector=selector)

    def pods_on_node(self, node_name: str) -> List[Pod]:
        """The pods whose ``spec.nodeName`` is ``node_name`` as of their last
        write through this client, terminal ones included, in no particular
        order — ``list_pods`` filtered by node name, read from the index."""
        with self._lock:
            return self._stores[Pod].by_node.pods_on_node(node_name)

    def get_pod(self, namespace: str, name: str) -> Optional[Pod]:
        return self.get(Pod, name, namespace)

    def get_node(self, name: str) -> Optional[Node]:
        return self.get(Node, name)

    def list_nodes(self) -> List[Node]:
        return self.list(Node)

    def list_namespaces(self, selector=None) -> List[Namespace]:
        return self.list(Namespace, selector=selector)

    def list_provisioners(self) -> List[Provisioner]:
        return self.list(Provisioner)

    def get_persistent_volume_claim(self, namespace: str, name: str):
        return self.get(PersistentVolumeClaim, name, namespace)

    def get_persistent_volume(self, name: str):
        return self.get(PersistentVolume, name)

    def get_storage_class(self, name: str):
        return self.get(StorageClass, name)

    def get_csi_node(self, name: str):
        return self.get(CSINode, name)

    def deep_copy(self, obj):
        return deep_copy(obj)


def _selector_matches(selector, obj) -> bool:
    if isinstance(selector, LabelSelector):
        return selector.matches(obj.metadata.labels)
    if isinstance(selector, dict):
        return all(obj.metadata.labels.get(k) == v for k, v in selector.items())
    if callable(selector):
        return selector(obj)
    raise TypeError(f"unsupported selector {selector!r}")
