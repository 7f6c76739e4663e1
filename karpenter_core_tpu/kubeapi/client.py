"""Apiserver-backed KubeClient: the in-memory client's surface over real HTTP.

Speaks the Kubernetes list/watch protocol (typed GET/LIST/POST/PUT/DELETE plus
chunked watch streams with resourceVersion resume) against any server that
implements the subset — the hermetic ``testing.fakeapiserver`` or a real
kube-apiserver proxy.  Design decisions that keep it drop-in compatible with
``operator.kubeclient.KubeClient`` (the whole controller stack is written
against that surface):

  - **Reads come from the reflector store.**  Every kind lazily gets a
    Reflector whose start blocks on the initial LIST, so a fresh process
    warm-starts cluster state from the server (the §5.4 restart-rebuild gap).
    get/list return the store's live references — the same aliasing the
    in-memory client exposes.

  - **Self-originated mutations dispatch synchronously.**  After a successful
    write, the writing thread applies the event (through the per-key
    resourceVersion guard) and runs watch callbacks itself, exactly like the
    in-memory client's synchronous delivery; the watch stream's later replay
    of the same event is dropped by the guard.  External writers' events
    arrive through the reflector thread.

  - **Optimistic concurrency is opt-in**, mirroring in-memory semantics:
    ``update`` sends resourceVersion 0 (unconditional replace, real-apiserver
    behavior for an empty resourceVersion) while ``update_with_version`` sends
    the expected version and maps HTTP 409 to ConflictError — the CAS leader
    election needs.

  - **Deletion timestamps come from the client's clock**, not the server's
    wall clock, so FakeClock-driven TTL semantics (expiry, emptiness) hold in
    tests; finalizer handling composes the same primitives as the in-memory
    client (MODIFIED-with-deletionTimestamp, then DELETED once clear).

  - Mutations meter through the shared RateLimiter (``--kube-client-qps``),
    and every request carries a timeout; watch streams ride long-poll
    timeouts with server bookmarks as keepalives.
"""

from __future__ import annotations

import json
import logging
import threading
from http.client import HTTPConnection
from typing import Dict, List, Optional
from urllib.parse import urlsplit

from karpenter_core_tpu.apis.objects import (
    CSINode,
    Namespace,
    Node,
    PersistentVolume,
    PersistentVolumeClaim,
    Pod,
    deep_copy,
)
from karpenter_core_tpu.apis.v1alpha5 import Provisioner
from karpenter_core_tpu.kubeapi.reflector import Reflector
from karpenter_core_tpu.kubeapi.resources import spec_for
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.operator.kubeclient import (
    KUBEAPI_PUT,
    ConflictError,
    NotFoundError,
    RateLimiter,
    WatchFunc,
    raise_injected_kubeapi_fault,
)

log = logging.getLogger(__name__)

REQUESTS = REGISTRY.counter(
    "karpenter_kubeapi_requests_total",
    "Apiserver requests by verb and HTTP status code.",
    ("verb", "code"),
)


class ApiServerError(Exception):
    def __init__(self, status: int, body: str) -> None:
        super().__init__(f"apiserver returned {status}: {body[:300]}")
        self.status = status


class _Transport:
    """One apiserver endpoint: request/response plumbing with timeouts.

    Plain requests open a short-lived connection each (the operator's request
    rate is QPS-limited well below connection-setup costs mattering); watch
    streams own a dedicated connection with a long read timeout."""

    def __init__(self, base_url: str, timeout_s: float = 30.0) -> None:
        parts = urlsplit(base_url)
        if parts.scheme not in ("http", ""):
            raise ValueError(
                f"unsupported apiserver scheme {parts.scheme!r} (http only; "
                f"terminate TLS in a sidecar/kubectl-proxy)"
            )
        self.host = parts.hostname or "127.0.0.1"
        self.port = parts.port or 80
        self.timeout_s = timeout_s

    def request(self, method: str, path: str, body: Optional[dict] = None) -> dict:
        if method != "GET":
            # same chaos point, fault mapping, AND kind filter as the
            # in-memory backend, so one scenario replays against either
            fault = KUBEAPI_PUT.hit(
                kinds=("error", "timeout"),
                backend="apiserver", verb=method, path=path,
            )
            if fault is not None and fault.kind in ("error", "timeout"):
                raise_injected_kubeapi_fault(fault)
        conn = HTTPConnection(self.host, self.port, timeout=self.timeout_s)
        try:
            payload = json.dumps(body).encode() if body is not None else None
            headers = {"Content-Type": "application/json"} if payload else {}
            conn.request(method, path, body=payload, headers=headers)
            resp = conn.getresponse()
            data = resp.read().decode()
            REQUESTS.labels(method, str(resp.status)).inc()
            if resp.status == 404:
                raise NotFoundError(data or path)
            if resp.status == 409:
                raise ConflictError(data or path)
            if resp.status >= 400:
                raise ApiServerError(resp.status, data)
            return json.loads(data) if data else {}
        finally:
            conn.close()

    def stream(self, method: str, path: str, timeout: float):
        """Open a watch stream; returns the live HTTPResponse (caller closes).
        The connection is parked on the response object so closing the
        response tears the socket down."""
        conn = HTTPConnection(self.host, self.port, timeout=timeout)
        conn.request(method, path)
        resp = conn.getresponse()
        REQUESTS.labels("WATCH", str(resp.status)).inc()
        resp._kc_conn = conn  # keep the connection alive with the stream
        _orig_close = resp.close

        def close():
            _orig_close()
            conn.close()

        resp.close = close
        return resp


class ApiServerClient:
    """KubeClient-compatible facade over a kube-apiserver endpoint."""

    def __init__(
        self,
        base_url: str,
        clock=None,
        qps: Optional[float] = None,
        burst: Optional[int] = None,
        *,
        request_timeout_s: float = 30.0,
        watch_timeout_s: float = 60.0,
        backoff_base_s: float = 0.2,
        backoff_cap_s: float = 30.0,
        rng=None,
    ) -> None:
        import time as _time

        self._clock = clock
        self._now = clock.now if clock is not None else _time.time
        self._sleep = clock.sleep if clock is not None else _time.sleep
        self._limiter = RateLimiter(qps, burst, now=self._now, sleep=self._sleep)
        self.transport = _Transport(base_url, timeout_s=request_timeout_s)
        self._watch_timeout_s = watch_timeout_s
        self._backoff_base_s = backoff_base_s
        self._backoff_cap_s = backoff_cap_s
        # seedable watch-recovery jitter source, shared across this client's
        # reflectors (tests/chaos scenarios pass retry.DeterministicRNG(seed))
        self._rng = rng
        self._reflectors: Dict[type, Reflector] = {}
        self._lock = threading.Lock()
        self._closed = False

    # -- reflector management --------------------------------------------------

    def reflector(self, kind: type) -> Reflector:
        """The kind's reflector, started (initial LIST synced) on first use."""
        with self._lock:
            refl = self._reflectors.get(kind)
        if refl is not None:
            # a concurrent creator may still be inside start(): reads must
            # not see the store before the initial LIST has been applied
            refl.wait_synced()
            return refl
        with self._lock:
            refl = self._reflectors.get(kind)
            if refl is not None:
                refl.wait_synced()
                return refl
            if self._closed:
                raise RuntimeError("client is closed")
            refl = Reflector(
                spec_for(kind),
                self.transport,
                backoff_base_s=self._backoff_base_s,
                backoff_cap_s=self._backoff_cap_s,
                watch_timeout_s=self._watch_timeout_s,
                rng=self._rng,
                clock=self._clock,
            )
            self._reflectors[kind] = refl
        refl.start()
        return refl

    def close(self) -> None:
        with self._lock:
            self._closed = True
            reflectors = list(self._reflectors.values())
        for refl in reflectors:
            refl.stop()

    # -- generic CRUD (KubeClient surface) -------------------------------------

    def create(self, obj) -> object:
        self._limiter.take()
        return self._post(obj)

    def get(self, kind: type, name: str, namespace: Optional[str] = None):
        refl = self.reflector(kind)
        key = (namespace, name) if refl.spec.namespaced else (name,)
        return refl.get(key)

    def update(self, obj) -> object:
        self._limiter.take()
        return self._put(obj, expected_version=None)

    def update_with_version(self, obj, expected_resource_version: int) -> object:
        """CAS update (client-go semantics): ConflictError when the stored
        resourceVersion moved past ``expected``.  Unlike the in-memory client
        the apiserver hands out decoded copies, so the caller's object is
        already private — but the contract (pass your own copy + the version
        snapshotted at read) stays the same."""
        self._limiter.take()
        return self._put(obj, expected_version=expected_resource_version)

    def _put(self, obj, expected_version: Optional[int]) -> object:
        spec = spec_for(type(obj))
        wire = spec.to_dict(obj)
        # rv 0 = unconditional replace (apiserver treats an empty
        # resourceVersion as "no optimistic check"), matching in-memory update
        wire["metadata"]["resourceVersion"] = (
            expected_version if expected_version is not None else 0
        )
        ns = obj.metadata.namespace if spec.namespaced else None
        out = self.transport.request(
            "PUT", spec.object_path(obj.metadata.name, ns), wire
        )
        return self._absorb_write("MODIFIED", obj, out)

    def apply(self, obj) -> object:
        """create-or-update, composed from the unconditional primitives."""
        self._limiter.take()
        try:
            return self._post(obj)
        except ConflictError:
            return self._put(obj, expected_version=None)

    def _post(self, obj):
        spec = spec_for(type(obj))
        if not obj.metadata.creation_timestamp:
            obj.metadata.creation_timestamp = self._now()
        wire = spec.to_dict(obj)
        wire["metadata"]["resourceVersion"] = 0
        ns = obj.metadata.namespace if spec.namespaced else None
        out = self.transport.request("POST", spec.base_path(ns), wire)
        return self._absorb_write("ADDED", obj, out)

    def delete(self, obj, *, force: bool = False) -> None:
        """k8s deletion semantics, composed client-side so deletionTimestamp
        comes from this client's clock (FakeClock-driven TTL tests): with
        finalizers present the first delete stamps deletionTimestamp via PUT;
        the object is removed once finalizers clear (or immediately with
        ``force``)."""
        self._limiter.take()
        spec = spec_for(type(obj))
        ns = obj.metadata.namespace if spec.namespaced else None
        refl = self.reflector(type(obj))
        key = (ns, obj.metadata.name) if spec.namespaced else (obj.metadata.name,)
        stored = refl.get(key)
        if stored is None:
            raise NotFoundError(f"{type(obj).__name__} {key} not found")
        if stored.metadata.finalizers and not force:
            if stored.metadata.deletion_timestamp is None:
                # stamp a COPY: mutating the live store object before the PUT
                # would desync the cache if the request fails (and make the
                # caller's retry a silent no-op).  On success the PUT's
                # self-applied event installs the stamped copy in the store.
                stamped = deep_copy(stored)
                stamped.metadata.deletion_timestamp = self._now()
                self._put(stamped, expected_version=None)
            return
        out = self.transport.request(
            "DELETE", spec.object_path(obj.metadata.name, ns)
        )
        rv = int(out.get("metadata", {}).get("resourceVersion", 0) or 0)
        refl.apply_event("DELETED", stored, rv)

    def remove_finalizer(self, obj, finalizer: str) -> None:
        stored = self.get(
            type(obj),
            obj.metadata.name,
            obj.metadata.namespace if spec_for(type(obj)).namespaced else None,
        )
        if stored is None:
            return
        # strip on a copy (same failed-PUT cache-desync concern as delete())
        stripped = deep_copy(stored)
        stripped.metadata.finalizers = [
            f for f in stripped.metadata.finalizers if f != finalizer
        ]
        should_remove = (
            stripped.metadata.deletion_timestamp is not None
            and not stripped.metadata.finalizers
        )
        self.update(stripped)
        if should_remove:
            self.delete(stripped, force=True)

    def list(self, kind: type, namespace: Optional[str] = None, selector=None) -> list:
        refl = self.reflector(kind)
        out = []
        for key, obj in refl.items():
            if namespace is not None and refl.spec.namespaced and key[0] != namespace:
                continue
            if selector is not None and not _selector_matches(selector, obj):
                continue
            out.append(obj)
        return out

    def watch(self, kind: type, callback: WatchFunc, *, replay: bool = True) -> None:
        refl = self.reflector(kind)
        # snapshot AND replay under the dispatch lock: live events are held
        # off until the replay finishes, so the callback can never see a
        # stale replayed ADDED after a fresher live DELETED/MODIFIED
        with refl.dispatch_lock:
            with refl.lock:
                refl.callbacks.append(callback)
                existing = refl.snapshot() if replay else []
            for obj in existing:
                callback("ADDED", obj)

    # -- write absorption ------------------------------------------------------

    def _absorb_write(self, event_type: str, obj, out: dict) -> object:
        """Reflect a successful write locally: adopt the server-assigned
        resourceVersion onto the caller's object (in-memory client mutates it
        the same way) and deliver the event synchronously through the per-key
        guard, so a caller observes its own write immediately."""
        rv = int(out.get("metadata", {}).get("resourceVersion", 0) or 0)
        obj.metadata.resource_version = rv
        refl = self.reflector(type(obj))
        refl.apply_event(event_type, obj, rv)
        return obj

    # -- typed conveniences (KubeClient parity) --------------------------------

    def list_pods(self, namespace: Optional[str] = None, selector=None) -> List[Pod]:
        return self.list(Pod, namespace=namespace, selector=selector)

    def pods_on_node(self, node_name: str) -> List[Pod]:
        """``list_pods`` filtered by ``spec.nodeName``, read from the pod
        reflector's field index (KubeClient.pods_on_node's contract)."""
        return self.reflector(Pod).pods_on_node(node_name)

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
        from karpenter_core_tpu.apis.objects import StorageClass

        return self.get(StorageClass, name)

    def get_csi_node(self, name: str):
        return self.get(CSINode, name)

    def deep_copy(self, obj):
        return deep_copy(obj)


def _selector_matches(selector, obj) -> bool:
    from karpenter_core_tpu.apis.objects import LabelSelector

    if isinstance(selector, LabelSelector):
        return selector.matches(obj.metadata.labels)
    if isinstance(selector, dict):
        return all(obj.metadata.labels.get(k) == v for k, v in selector.items())
    if callable(selector):
        return selector(obj)
    raise TypeError(f"unsupported selector {selector!r}")
