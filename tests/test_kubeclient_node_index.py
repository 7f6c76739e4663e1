"""The pod store's ``spec.nodeName`` index against the scan it replaces.

``pods_on_node(name)`` must answer what
``list_pods(selector=lambda p: p.spec.node_name == name)`` answers after every
write through the client, on both stores: the in-memory ``KubeClient`` and the
``ApiServerClient``'s pod reflector (hermetic, against testing.fakeapiserver).
Both hand out live references and callers bind in place, so the sequence binds
the way the package does: mutate the stored object, then write it."""

import random
import sys
import threading

import pytest

from karpenter_core_tpu.kubeapi.client import ApiServerClient
from karpenter_core_tpu.operator.kubeclient import KubeClient, NotFoundError
from karpenter_core_tpu.testing.factories import make_pod
from karpenter_core_tpu.testing.fakeapiserver import FakeApiServer
from karpenter_core_tpu.utils.clock import FakeClock

NODES = [f"n{i}" for i in range(6)]
GHOST = "no-pod-was-ever-bound-here"
FINALIZER = "test/hold"


@pytest.fixture(params=["memory", "apiserver"])
def kube(request):
    if request.param == "memory":
        yield KubeClient(FakeClock())
        return
    server = FakeApiServer(bookmark_interval_s=0.2).start()
    client = ApiServerClient(server.url, FakeClock(), backoff_base_s=0.05, backoff_cap_s=0.5)
    try:
        yield client
    finally:
        client.close()
        server.stop()


def key(pod):
    return (pod.namespace, pod.name)


def scan(kube, node_name):
    return kube.list_pods(selector=lambda p: p.spec.node_name == node_name)


def assert_index_is_the_scan(kube, step=""):
    for name in (*NODES, GHOST):
        indexed = kube.pods_on_node(name)
        keys = [key(p) for p in indexed]
        assert len(keys) == len(set(keys)), (step, name)
        assert set(keys) == {key(p) for p in scan(kube, name)}, (step, name)
        # the index hands out what the store holds, not a copy of it
        assert all(p is kube.get_pod(p.namespace, p.name) for p in indexed), (step, name)
    assert kube.pods_on_node(GHOST) == []


class Writes:
    """One seeded writer: every op is a write through the client on a pod the
    store holds (read back first — a delete held by a finalizer or a CAS update
    leaves a different object in the store than the caller's)."""

    def __init__(self, kube, seed):
        self.kube = kube
        self.rng = random.Random(seed)
        self.live = []  # pod keys the store should hold
        self.made = 0

    def stored(self):
        ns, name = self.rng.choice(self.live)
        return self.kube.get_pod(ns, name)

    def new_pod(self, **kwargs):
        self.made += 1
        finalizer = self.rng.random() < 0.3
        pod = make_pod(name=f"p{self.made}", **kwargs)
        if finalizer:
            pod.metadata.finalizers.append(FINALIZER)
        self.live.append(key(pod))
        return pod

    # -- the ops ---------------------------------------------------------------

    def create_bound(self):
        self.kube.create(self.new_pod(node_name=self.rng.choice(NODES), unschedulable=False))

    def create_pending(self):
        self.kube.create(self.new_pod())

    def bind_through_apply(self):
        pod = self.stored()
        pod.spec.node_name = self.rng.choice(NODES)
        self.kube.apply(pod)

    def rebind_through_update(self):
        pod = self.stored()
        pod.spec.node_name = self.rng.choice([n for n in NODES if n != pod.spec.node_name])
        self.kube.update(pod)

    def rebind_a_copy_with_version(self):
        pod = self.stored()
        mine = self.kube.deep_copy(pod)
        mine.spec.node_name = self.rng.choice(NODES)
        self.kube.update_with_version(mine, pod.metadata.resource_version)

    def unbind(self):
        pod = self.stored()
        pod.spec.node_name = ""
        self.kube.update(pod)

    def finish(self):
        pod = self.stored()
        pod.status.phase = self.rng.choice(["Succeeded", "Failed"])
        self.kube.update(pod)

    def delete(self):
        pod = self.stored()
        held = bool(pod.metadata.finalizers)
        self.kube.delete(pod)
        if not held:
            self.live.remove(key(pod))

    def remove_finalizer(self):
        pod = self.stored()
        terminating = pod.metadata.deletion_timestamp is not None
        self.kube.remove_finalizer(pod, FINALIZER)
        if terminating:
            self.live.remove(key(pod))

    def force_delete(self):
        pod = self.stored()
        self.kube.delete(pod, force=True)
        self.live.remove(key(pod))

    def delete_the_missing(self):
        with pytest.raises(NotFoundError):
            self.kube.delete(make_pod(name="never-created", node_name=NODES[0]))

    OPS = (
        (create_bound, 5), (create_pending, 4), (bind_through_apply, 6),
        (rebind_through_update, 5), (rebind_a_copy_with_version, 3), (unbind, 1),
        (finish, 2), (delete, 4), (remove_finalizer, 3), (force_delete, 2),
        (delete_the_missing, 1),
    )

    def step(self):
        ops, weights = zip(*self.OPS)
        op = self.rng.choices(ops, weights)[0]
        if not self.live and op not in (Writes.create_bound, Writes.create_pending,
                                        Writes.delete_the_missing):
            op = Writes.create_bound
        op(self)
        return op.__name__


@pytest.mark.parametrize("seed", [39, 2147483659])
def test_index_equals_the_scan_after_every_write(kube, seed):
    writes = Writes(kube, seed)
    assert_index_is_the_scan(kube, "empty")
    seen_ops = set()
    for i in range(520):
        name = writes.step()
        seen_ops.add(name)
        assert_index_is_the_scan(kube, f"step {i}: {name}")
    assert seen_ops == {op.__name__ for op, _ in Writes.OPS}  # the sequence ran them all
    assert {key(p) for p in kube.list_pods()} == set(writes.live)
    # a pod held by its finalizer stayed indexed; terminal pods are in the index
    assert any(
        p.metadata.deletion_timestamp is not None or p.status.phase in ("Succeeded", "Failed")
        for n in NODES for p in kube.pods_on_node(n)
    )


def test_a_pending_pod_is_not_indexed_and_a_removed_one_leaves_nothing(kube):
    pod = make_pod(name="lone")
    kube.create(pod)
    assert all(kube.pods_on_node(n) == [] for n in NODES)
    pod.spec.node_name = "n0"
    kube.apply(pod)
    assert [key(p) for p in kube.pods_on_node("n0")] == [key(pod)]
    pod.spec.node_name = "n1"
    kube.update(pod)
    assert kube.pods_on_node("n0") == []
    assert [key(p) for p in kube.pods_on_node("n1")] == [key(pod)]
    # the answer is a copy: a caller's edit of it is not an edit of the index
    kube.pods_on_node("n1").clear()
    assert len(kube.pods_on_node("n1")) == 1
    kube.delete(pod)
    assert kube.pods_on_node("n1") == []


def test_concurrent_binds_and_deletes_leave_the_index_equal_to_the_scan(kube):
    writers, readers, rounds = 8, 8, 25
    stop = threading.Event()
    errors = []

    def write(w):
        rng = random.Random(w)
        try:
            for r in range(rounds):
                pod = make_pod(name=f"w{w}-r{r}")
                kube.create(pod)
                pod.spec.node_name = rng.choice(NODES)
                kube.apply(pod)
                pod.spec.node_name = rng.choice(NODES)
                kube.update(pod)
                if r % 3:
                    kube.delete(pod)
        except Exception as e:  # noqa: BLE001 - reported by the asserting thread
            errors.append(e)

    def read():
        try:
            while not stop.is_set():
                for name in NODES:
                    keys = [key(p) for p in kube.pods_on_node(name)]
                    assert len(keys) == len(set(keys))
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reading = [threading.Thread(target=read) for _ in range(readers)]
        writing = [threading.Thread(target=write, args=(w,)) for w in range(writers)]
        for t in (*reading, *writing):
            t.start()
        for t in writing:
            t.join(timeout=120)
        stop.set()
        for t in reading:
            t.join(timeout=30)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in (*reading, *writing))
    assert errors == []
    assert_index_is_the_scan(kube, "after the storm")
    kept = sum(1 for r in range(rounds) if not r % 3) * writers
    assert sum(len(kube.pods_on_node(n)) for n in NODES) == kept
