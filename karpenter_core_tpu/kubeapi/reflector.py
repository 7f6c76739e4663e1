"""Reflector: the list/watch pump behind the apiserver-backed KubeClient.

Mirror of client-go's reflector/informer pair (the plane the reference gets
from controller-runtime, operator.go:91-133): one thread per kind runs

    LIST (capture resourceVersion) → WATCH from it → apply events → repeat

with the full robustness ladder:

  - exponential backoff with jitter on stream drops / connection errors
  - BOOKMARK events advance the resume resourceVersion without dispatch
  - ``410 Gone`` (compacted history, as an ERROR event or HTTP status)
    triggers a relist that DIFFS against the local store — vanished objects
    get synthesized DELETED events, changed ones MODIFIED — so downstream
    caches (state.Cluster) reconverge without a process restart
  - per-key resourceVersion guards drop stale/duplicate events, which lets
    the client deliver self-originated mutations synchronously (in-memory
    KubeClient semantics) while the watch stream replays them later

The store the reflector maintains is the read path for get/list, which is
what makes a fresh process warm-start from a LIST: start() blocks until the
initial sync completes.
"""

from __future__ import annotations

import json
import logging
import threading
from typing import Callable, Dict, List, Optional, Tuple

from karpenter_core_tpu.apis.objects import Pod
from karpenter_core_tpu.chaos import plane as chaos
from karpenter_core_tpu.kubeapi.resources import ResourceSpec
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.operator.kubeclient import PodNodeIndex
from karpenter_core_tpu.utils import retry

log = logging.getLogger(__name__)

# watch.stream: faults watch establishment (error/timeout/410) and event
# delivery (duplicate) — the reflector's whole recovery ladder under one name
WATCH_STREAM = chaos.point("watch.stream")

WATCH_RESTARTS = REGISTRY.counter(
    "karpenter_kubeapi_watch_restarts_total",
    "Watch stream restarts by kind and reason (drop/gone/error).",
    ("kind", "reason"),
)
RELISTS = REGISTRY.counter(
    "karpenter_kubeapi_relists_total",
    "Full relists by kind (initial sync and 410-Gone recoveries).",
    ("kind",),
)


class Reflector:
    """One kind's list/watch loop feeding a keyed store + watch callbacks."""

    def __init__(
        self,
        spec: ResourceSpec,
        transport,  # kubeapi.client._Transport
        *,
        backoff_base_s: float = 0.2,
        backoff_cap_s: float = 30.0,
        watch_timeout_s: float = 60.0,
        rng: Optional[retry.DeterministicRNG] = None,
        clock=None,
    ) -> None:
        self.spec = spec
        self.transport = transport
        self.backoff_base_s = backoff_base_s
        self.backoff_cap_s = backoff_cap_s
        self.watch_timeout_s = watch_timeout_s
        # watch-recovery backoff used to call module-level random.random()
        # with an unseeded global RNG, making recovery timing unreplayable;
        # the injected DeterministicRNG (seedable by tests/chaos scenarios)
        # keeps the same min(base*2^n, cap) * [0.5, 1.5) shape
        self._backoff = retry.Backoff(
            backoff_base_s, backoff_cap_s,
            max_exponent=16, jitter=retry.JITTER_HALF, rng=rng,
        )
        # restart budget: the backoff resets on every successful LIST, so a
        # server that accepts the connect and instantly drops the stream
        # would otherwise hot-loop at base_s forever; once the budget drains,
        # every further restart in the window waits the full cap.  The clock
        # is injected (like the rng) so the window is steppable by FakeClock
        # suites and unperturbed by chaos clock.skew scenarios
        if clock is None:
            from karpenter_core_tpu.utils.clock import Clock

            clock = Clock()
        self._restart_budget = retry.RetryBudget(
            clock, budget=10, window_s=60.0,
            name=f"watch-{spec.kind_name}",
        )

        self.lock = threading.RLock()
        # serializes callback DISPATCH (not store access): a watch()
        # registration snapshot-replays ADDED events under this lock so a
        # concurrent live DELETED/MODIFIED can't interleave with (or precede)
        # the stale replay and resurrect an object downstream.  RLock because
        # callbacks re-enter the client (informer -> controller -> write ->
        # self-delivery -> apply_event) on the same thread.
        self.dispatch_lock = threading.RLock()
        self.store: Dict[tuple, object] = {}  # key -> decoded object
        # the pod reflector's spec.nodeName field index (client-go's
        # AddIndexers on the pod informer); every write to ``store`` goes
        # through apply_event, the relist's too, so it is kept there
        self.by_node = PodNodeIndex() if spec.kind is Pod else None
        # per-key applied-resourceVersion high-water marks; deleted keys keep
        # a tombstone so a late watch replay of the pre-delete MODIFIED can't
        # resurrect the object (pruned on relist)
        self.applied_rv: Dict[tuple, int] = {}
        self.callbacks: List[Callable[[str, object], None]] = []
        self._resume_rv = 0
        self._stop = threading.Event()
        self._synced = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._current_response = None

    # -- lifecycle -------------------------------------------------------------

    def start(self, sync_timeout_s: float = 30.0) -> "Reflector":
        self._thread = threading.Thread(
            target=self._run, name=f"reflector-{self.spec.plural}", daemon=True
        )
        self._thread.start()
        if not self._synced.wait(timeout=sync_timeout_s):
            raise TimeoutError(
                f"reflector for {self.spec.kind_name} failed initial LIST "
                f"within {sync_timeout_s}s"
            )
        return self

    def wait_synced(self, timeout_s: float = 30.0) -> None:
        """Block until the initial LIST has been applied (no-op once set)."""
        if not self._synced.wait(timeout=timeout_s):
            raise TimeoutError(
                f"reflector for {self.spec.kind_name} not synced within {timeout_s}s"
            )

    def stop(self) -> None:
        self._stop.set()
        resp = self._current_response
        if resp is not None:
            try:
                resp.close()
            except Exception:  # noqa: BLE001 - teardown
                pass
        if self._thread is not None:
            self._thread.join(timeout=5)

    # -- key helpers -----------------------------------------------------------

    def key_of(self, obj) -> tuple:
        meta = obj.metadata
        return (meta.namespace, meta.name) if self.spec.namespaced else (meta.name,)

    # -- event application (shared with the client's self-delivery path) -------

    def apply_event(self, event_type: str, obj, rv: int) -> bool:
        """Apply one event to the store and dispatch callbacks; returns False
        when the event is stale (per-key rv guard) and was dropped.  Callbacks
        run outside the store lock (in-memory KubeClient discipline: informer
        callbacks take Cluster locks whose holders call back into the
        client)."""
        key = self.key_of(obj)
        with self.dispatch_lock:
            with self.lock:
                if rv <= self.applied_rv.get(key, 0):
                    return False
                self.applied_rv[key] = rv
                if event_type == "DELETED":
                    self.store.pop(key, None)
                    if self.by_node is not None:
                        self.by_node.drop(key)
                else:
                    self.store[key] = obj
                    if self.by_node is not None:
                        self.by_node.put(key, obj)
                callbacks = list(self.callbacks)
            for cb in callbacks:
                cb(event_type, obj)
        return True

    # -- the loop --------------------------------------------------------------

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self._list_and_sync()
                self._synced.set()
                self._backoff.reset()
                self._watch()
            except _Gone:
                WATCH_RESTARTS.labels(self.spec.kind_name, "gone").inc()
                log.info("watch %s: history compacted (410), relisting",
                         self.spec.plural)
                self._resume_rv = 0  # force a fresh LIST next round
                # a lone 410 relists immediately (the designed recovery), but
                # each iteration's successful LIST resets the backoff, so a
                # server stuck answering 410 would spin full relists at line
                # rate — the restart budget floors that storm at the cap
                if not self._restart_budget.allow():
                    self._stop.wait(self.backoff_cap_s)
                continue
            except Exception as e:  # noqa: BLE001 - stream drops are routine
                if self._stop.is_set():
                    return
                WATCH_RESTARTS.labels(self.spec.kind_name, "drop").inc()
                delay = self._next_restart_delay()
                log.warning(
                    "watch %s dropped (%s: %s); retry %d in %.2fs",
                    self.spec.plural, type(e).__name__, e,
                    self._backoff.failures, delay,
                )
                self._stop.wait(delay)

    def _next_restart_delay(self) -> float:
        """Jittered exponential backoff, floored at the cap once the rolling
        restart budget is spent — the per-kind storm backstop."""
        delay = self._backoff.next()
        if not self._restart_budget.allow():
            return max(delay, self.backoff_cap_s)
        return delay

    def _list_and_sync(self) -> None:
        """LIST and reconcile the store against it: the initial sync and every
        410 recovery.  Objects present only locally get DELETED synthesized;
        listed objects apply through the per-key rv guard (so a relist racing
        a concurrent self-delivered write can't regress the store)."""
        if self._resume_rv and self._synced.is_set():
            return  # healthy resume: watch continues from the last-seen rv
        RELISTS.labels(self.spec.kind_name).inc()
        body = self.transport.request("GET", self.spec.base_path())
        listed = body.get("items", [])
        list_rv = int(body.get("metadata", {}).get("resourceVersion", 0) or 0)
        decoded = [self.spec.from_dict(item) for item in listed]
        listed_keys = {self.key_of(obj) for obj in decoded}
        with self.lock:
            vanished = [
                (key, obj) for key, obj in self.store.items() if key not in listed_keys
            ]
            # prune tombstones of keys the server no longer knows: their
            # history is gone, so no stale replay can arrive for them
            for key in list(self.applied_rv):
                if key not in listed_keys and key not in self.store:
                    del self.applied_rv[key]
        for key, obj in vanished:
            with self.lock:
                rv = self.applied_rv.get(key, 0)
            self.apply_event("DELETED", obj, max(rv + 1, list_rv))
        for obj in decoded:
            event = "MODIFIED" if self.key_of(obj) in self.store else "ADDED"
            self.apply_event(event, obj, obj.metadata.resource_version)
        self._resume_rv = max(self._resume_rv, list_rv)

    def _watch(self) -> None:
        duplicate_events = False
        fault = WATCH_STREAM.hit(
            kinds=(chaos.KIND_ERROR, chaos.KIND_TIMEOUT, chaos.KIND_DUPLICATE),
            kind_name=self.spec.kind_name, rv=self._resume_rv,
        )
        if fault is not None:
            if fault.code == 410:
                raise _Gone()
            if fault.kind in (chaos.KIND_ERROR, chaos.KIND_TIMEOUT):
                raise IOError(fault.describe())
            duplicate_events = fault.kind == chaos.KIND_DUPLICATE
        path = (
            f"{self.spec.base_path()}?watch=true&resourceVersion={self._resume_rv}"
            f"&allowWatchBookmarks=true"
        )
        resp = self.transport.stream("GET", path, timeout=self.watch_timeout_s)
        if resp.status == 410:
            resp.close()
            raise _Gone()
        if resp.status != 200:
            body = resp.read()
            resp.close()
            raise IOError(f"watch {self.spec.plural}: HTTP {resp.status} {body[:200]!r}")
        self._current_response = resp
        try:
            while not self._stop.is_set():
                line = resp.readline()
                if not line:
                    WATCH_RESTARTS.labels(self.spec.kind_name, "eof").inc()
                    return  # orderly end of stream: re-watch from resume rv
                line = line.strip()
                if not line:
                    continue
                event = json.loads(line)
                etype, wire = event.get("type"), event.get("object", {})
                rv = int(wire.get("metadata", {}).get("resourceVersion", 0) or 0)
                if etype == "BOOKMARK":
                    self._resume_rv = max(self._resume_rv, rv)
                    continue
                if etype == "ERROR":
                    if wire.get("code") == 410:
                        raise _Gone()
                    raise IOError(f"watch error event: {wire}")
                self.apply_event(etype, self.spec.from_dict(wire), rv)
                if duplicate_events:
                    # duplicate delivery: the per-key rv guard must drop the
                    # replay — exactly the at-least-once semantics a real
                    # watch resume exhibits
                    self.apply_event(etype, self.spec.from_dict(wire), rv)
                self._resume_rv = max(self._resume_rv, rv)
        finally:
            self._current_response = None
            try:
                resp.close()
            except Exception:  # noqa: BLE001 - teardown
                pass

    # -- read surface ----------------------------------------------------------

    def get(self, key: tuple):
        with self.lock:
            return self.store.get(key)

    def snapshot(self) -> List[object]:
        with self.lock:
            return list(self.store.values())

    def items(self) -> List[Tuple[tuple, object]]:
        with self.lock:
            return list(self.store.items())

    def pods_on_node(self, node_name: str) -> List[object]:
        """The pod reflector's read of its ``spec.nodeName`` index."""
        with self.lock:
            return self.by_node.pods_on_node(node_name)


class _Gone(Exception):
    """Watch history compacted past the resume rv (HTTP/event 410)."""
