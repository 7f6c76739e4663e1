"""The sidecar's collector policy (service/collector.py, docs/OBSERVABILITY.md):
while a sidecar is up the automatic generation-2 trigger is out of reach and
the server runs the full pass itself, at a request boundary, by a clock and by
resident memory; ``server.stop()`` hands the collector back.  Everything here
is counted through ``gc.callbacks`` — never timed."""

import gc
import weakref

import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import codec, labels as labels_api
from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
from karpenter_core_tpu.metrics.registry import SOLVER_GC_COLLECTIONS, SOLVER_GC_SECONDS
from karpenter_core_tpu.service import collector
from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverClient, serve
from karpenter_core_tpu.testing import make_node, make_pod, make_provisioner

FOUND = (100, 2, 2)  # low, so a few hundred pods reach every generation


class Hands:
    """The two things the policy observes, in the test's hands."""

    def __init__(self) -> None:
        self.now = 1000.0
        self.resident = 1 << 30

    def clock(self) -> float:
        return self.now

    def rss(self) -> int:
        return self.resident


class Passes:
    """What ``gc.callbacks`` saw: passes by generation, and full passes that
    began while ``inside`` was set."""

    def __init__(self) -> None:
        self.by_generation = [0, 0, 0]
        self.full_inside = 0
        self.inside = False

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "stop":
            self.by_generation[info["generation"]] += 1
        elif info["generation"] == 2 and self.inside:
            self.full_inside += 1


@pytest.fixture()
def own_collector(monkeypatch):
    """A policy of this test's own in the shared slot, over known thresholds:
    a sidecar another test of this worker left running holds the old one."""
    was = gc.get_threshold()
    gc.set_threshold(*FOUND)
    hands = Hands()
    policy = collector.CollectorPolicy(clock=hands.clock, rss=hands.rss)
    monkeypatch.setattr(collector, "POLICY", policy)
    seen = Passes()
    gc.callbacks.append(seen)
    try:
        yield policy, hands, seen
    finally:
        gc.callbacks.remove(seen)
        assert not policy.installed, "a test left its sidecar running"
        gc.set_threshold(*was)


def _sidecar():
    server, port = serve(FakeCloudProvider(instance_types(12)))
    return server, SnapshotSolverClient(f"127.0.0.1:{port}")


def _close(server, client) -> None:
    client.close()
    server.stop(0).wait()
    server.kc_service.shutdown()


def _cluster(n_nodes: int, pods_a_node: int) -> list:
    """``nodes=`` as an operator ships them: every node with its bound pods."""
    return [{
        "node": codec.node_to_dict(make_node(
            name=f"node-{i}",
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "fake-it-7",
                labels_api.LABEL_CAPACITY_TYPE: "on-demand",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                labels_api.LABEL_TOPOLOGY_ZONE: "test-zone-1",
            },
            allocatable={"cpu": 8, "memory": "16Gi", "pods": 110},
        )),
        "pods": [
            codec.pod_to_dict(make_pod(
                name=f"bound-{i}-{j}", node_name=f"node-{i}",
                labels={"app": f"app-{j % 5}"}, requests={"cpu": "100m"}))
            for j in range(pods_a_node)
        ],
        "volumeLimits": {},
    } for i in range(n_nodes)]


def _watch_handler(server, seen: Passes) -> None:
    """Set ``seen.inside`` for the length of the /SolveClasses handler."""
    inner = server.kc_service._solve_classes

    def handler(request, context):
        seen.inside = True
        try:
            return inner(request, context)
        finally:
            seen.inside = False

    server.kc_service._solve_classes = handler


# -- installation ---------------------------------------------------------------


def test_serve_puts_the_full_trigger_out_of_reach_and_stop_puts_it_back(own_collector):
    policy, _, _ = own_collector
    server, client = _sidecar()
    young, middle, full = gc.get_threshold()
    assert (young, middle) == FOUND[:2]
    assert full == collector.FULL_THRESHOLD_OUT_OF_REACH > 100_000
    assert policy.installed and policy._on_pass in gc.callbacks
    _close(server, client)
    assert gc.get_threshold() == FOUND
    assert not policy.installed and policy._on_pass not in gc.callbacks


def test_sidecars_share_one_installation_first_installs_last_restores(own_collector):
    policy, _, _ = own_collector
    first, client1 = _sidecar()
    second, client2 = _sidecar()
    assert gc.callbacks.count(policy._on_pass) == 1
    _close(first, client1)
    first.stop(0)  # a second stop of the same server releases nothing more
    assert policy.installed
    assert gc.get_threshold()[2] == collector.FULL_THRESHOLD_OUT_OF_REACH
    _close(second, client2)
    assert gc.get_threshold() == FOUND and not policy.installed


def test_stop_keeps_young_thresholds_someone_else_set_meanwhile(own_collector):
    server, client = _sidecar()
    gc.set_threshold(50, 3, gc.get_threshold()[2])  # memlimit.apply does this
    _close(server, client)
    assert gc.get_threshold() == (50, 3, FOUND[2])


# -- no full pass inside a request ------------------------------------------------


@pytest.mark.parametrize("paced", [True, False])
def test_full_passes_inside_a_request_with_shipped_nodes(own_collector, monkeypatch, paced):
    """300 bound pods on 20 shipped nodes: without the policy the allocation
    count starts a full pass inside the handler; with it, none."""
    policy, _, seen = own_collector
    if not paced:
        monkeypatch.setattr(policy, "acquire", lambda: lambda: None)
    nodes = _cluster(20, 15)
    pods = [make_pod(requests={"cpu": f"{100 + 10 * (i % 7)}m"}) for i in range(40)]
    server, client = _sidecar()
    _watch_handler(server, seen)
    # nothing old is left to count against the rule's "a quarter of what the
    # last full pass found alive": the request's own objects decide
    gc.collect()
    gc.freeze()
    try:
        gc.collect()
        out = client.solve_classes(pods, [make_provisioner()], nodes=nodes)
    finally:
        gc.unfreeze()
        _close(server, client)
    assert not out["failedPodIndices"]
    assert seen.by_generation[1] > FOUND[2], "the request must reach the old trigger"
    if paced:
        assert seen.full_inside == 0
    else:
        assert seen.full_inside >= 1


# -- pacing -------------------------------------------------------------------------


def _due_by_interval(hands: Hands) -> None:
    hands.now += collector.FULL_INTERVAL_S


def _due_by_memory(hands: Hands) -> None:
    hands.resident = int(hands.resident * collector.RSS_GROWTH_FACTOR) + 1


def _not_due(hands: Hands) -> None:
    hands.now += collector.FULL_INTERVAL_S - 1.0
    hands.resident = int(hands.resident * (collector.RSS_GROWTH_FACTOR - 0.1))


@pytest.mark.parametrize("advance,passes", [
    (_not_due, 0), (_due_by_interval, 1), (_due_by_memory, 1),
], ids=["neither", "interval", "resident-memory"])
def test_the_paced_pass_runs_at_a_request_boundary_once_due_and_not_before(
        own_collector, advance, passes):
    policy, hands, seen = own_collector
    release = policy.acquire()
    try:
        in_handler = []

        def handler(request, context):
            in_handler.append(seen.by_generation[2])
            return b"reply"

        paced = policy.paced(handler)
        assert paced(b"", None) == b"reply"
        before = seen.by_generation[2]
        advance(hands)
        assert paced(b"", None) == b"reply"
        # not inside the handler: at its exit
        assert in_handler == [before, before]
        assert seen.by_generation[2] - before == passes
        # the pass is the new baseline: the next boundary is not due again
        assert paced(b"", None) == b"reply"
        assert seen.by_generation[2] - before == passes
    finally:
        release()


def test_a_full_pass_someone_else_ran_counts_as_the_last_one(own_collector):
    policy, hands, seen = own_collector
    release = policy.acquire()
    try:
        hands.now += collector.FULL_INTERVAL_S - 1.0
        gc.collect()  # an embedder's own, or the benchmark's before its window
        hands.now += collector.FULL_INTERVAL_S - 1.0
        before = seen.by_generation[2]
        policy.paced(lambda request, context: b"")(b"", None)
        assert seen.by_generation[2] == before
    finally:
        release()


def test_an_idle_sidecar_still_collects(own_collector, monkeypatch):
    policy, hands, seen = own_collector
    monkeypatch.setattr(collector, "IDLE_POLL_S", 0.01)
    release = policy.acquire()
    try:
        before = seen.by_generation[2]
        _due_by_interval(hands)
        for _ in range(500):
            if seen.by_generation[2] > before:
                break
            policy._stop.wait(0.01)
        assert seen.by_generation[2] == before + 1
    finally:
        release()


def test_a_handler_in_flight_keeps_the_housekeeping_thread_off(own_collector, monkeypatch):
    policy, hands, seen = own_collector
    monkeypatch.setattr(collector, "IDLE_POLL_S", 0.01)
    release = policy.acquire()
    try:
        inside = []

        def handler(request, context):
            before = seen.by_generation[2]
            _due_by_interval(hands)
            policy._stop.wait(0.2)  # twenty ticks of the thread
            inside.append(seen.by_generation[2] - before)
            return b""

        policy.paced(handler)(b"", None)
        assert inside == [0]
    finally:
        release()


class _Knot:
    """An unreachable cycle once dropped: only a full pass can free it."""

    def __init__(self) -> None:
        self.me = self


def test_a_cycle_made_inside_a_handler_is_gone_after_the_next_paced_pass(own_collector):
    policy, hands, _ = own_collector
    release = policy.acquire()
    try:
        alive = []

        def handler(request, context):
            knot = _Knot()
            alive.append(weakref.ref(knot))
            gc.collect(1)  # it survives the young generations, as a decoded pod does
            return b""

        paced = policy.paced(handler)
        paced(b"", None)
        gc.collect(1)
        assert alive[0]() is not None, "no young pass frees an old cycle"
        _due_by_interval(hands)
        policy.paced(lambda request, context: b"")(b"", None)
        assert alive[0]() is None
    finally:
        release()


# -- what it reports ------------------------------------------------------------------


def _counter(metric) -> list:
    by_label = {labels["generation"]: value for _, labels, value in metric.samples()}
    return [by_label.get(str(g), 0.0) for g in range(3)]


def test_the_counters_read_what_gc_callbacks_saw(own_collector):
    policy, _, seen = own_collector
    passes0, seconds0 = _counter(SOLVER_GC_COLLECTIONS), _counter(SOLVER_GC_SECONDS)
    gc.disable()  # no pass between this reading and the installation
    try:
        seen.by_generation = [0, 0, 0]
        server, client = _sidecar()
    finally:
        gc.enable()
    try:
        client.solve_classes(
            [make_pod(requests={"cpu": "250m"}) for _ in range(30)],
            [make_provisioner()], nodes=_cluster(4, 10))
        gc.disable()  # no pass between the two readings
        try:
            policy._publish()
            passes, seconds = _counter(SOLVER_GC_COLLECTIONS), _counter(SOLVER_GC_SECONDS)
            saw = list(seen.by_generation)
        finally:
            gc.enable()
    finally:
        _close(server, client)
    assert [int(a - b) for a, b in zip(passes, passes0)] == saw
    assert saw[0] > 0 and saw[1] > 0
    for generation in range(3):
        assert (seconds[generation] > seconds0[generation]) == (saw[generation] > 0)


@pytest.fixture()
def traced():
    tracing.TRACE_STORE.clear()
    tracing.enable()
    yield
    tracing.disable()
    tracing.TRACE_STORE.clear()


@pytest.mark.parametrize("forced", [0, 2])
def test_the_root_span_carries_the_full_passes_that_began_inside_it(
        own_collector, traced, forced):
    _, _, seen = own_collector
    server, client = _sidecar()
    try:
        _watch_handler(server, seen)
        stateless = server.kc_service._solve_classes_stateless

        def with_full_passes(req, context, t0):
            for _ in range(forced):
                gc.collect()
            return stateless(req, context, t0)

        server.kc_service._solve_classes_stateless = with_full_passes
        client.solve_classes([make_pod(requests={"cpu": "250m"})], [make_provisioner()])
    finally:
        _close(server, client)
    (root,) = [s for t in tracing.TRACE_STORE.last(None) for s in t.spans
               if s["name"] == "service.solve_classes"]
    assert root["attrs"]["gc_full"] == seen.full_inside == forced
    assert (root["attrs"]["gc_full_s"] > 0) == (forced > 0)
    assert root["attrs"]["request_bytes"] > 0


def test_the_housekeeping_thread_starts_with_the_lock_free(own_collector, monkeypatch):
    """``Thread.start()`` waits for the new thread, and a collection inside
    that thread may finalize a sidecar dropped unstopped, whose release takes
    the policy's lock: started under the lock, the two wait on each other."""
    import threading

    policy, _, _ = own_collector
    free_at_start = []
    start = threading.Thread.start

    def checked_start(thread):
        if thread.name == "kc-collector":
            other = threading.Thread(target=lambda: free_at_start.append(
                policy._lock.acquire(timeout=5) and policy._lock.release() is None))
            start(other)
            other.join(10)
        return start(thread)

    monkeypatch.setattr(threading.Thread, "start", checked_start)
    release = policy.acquire()
    try:
        assert free_at_start == [True]
        assert policy.installed
    finally:
        release()
    assert not policy.installed


def test_a_sidecar_dropped_unstopped_is_released_by_the_pass_that_frees_it(own_collector):
    """``weakref.finalize`` on the server: the paced pass that collects it runs
    the release from inside the pass, under the policy's own lock."""
    policy, hands, _ = own_collector
    keep = policy.acquire()
    try:
        server, client = _sidecar()
        client.close()
        grpc_stop = type(server).stop
        grpc_stop(server, 0).wait()  # the transport down, the wrapper not called
        server.kc_service.shutdown()
        del server, client
        assert policy._holders == 2
        _due_by_interval(hands)
        policy.paced(lambda request, context: b"")(b"", None)
        assert policy._holders == 1
    finally:
        keep()
