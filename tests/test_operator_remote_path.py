"""The deployed topology end to end, in one process: an ``Operator`` composed
by ``with_controllers()`` and started, its ``KC_SOLVER_ADDRESS`` pointing at a
sidecar composed as ``cmd/solver.compose`` composes it — pods created in the
store, one provisioning pass with the batch window closed, machines launched.
The path ``suite-400.operator`` measures (benchmark/traffic/kinds/
operator_cycle.py), at 300 pods x 100 types on the CPU."""

import collections
import time
import types

import pytest

from benchmark.harness.podmix import pod_mix, seeded
from benchmark.harness.sut import Sidecar
from benchmark.traffic.kinds import operator_cycle
from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
from karpenter_core_tpu.operator.kubeclient import KubeClient
from karpenter_core_tpu.operator.operator import Operator
from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverClient
from karpenter_core_tpu.state.cluster import STATE_NODE_REBUILDS
from karpenter_core_tpu.testing.harness import nominations

pytestmark = pytest.mark.compile  # the sidecar compiles the solve kernel

PODS, TYPES, SEED = 300, 100, 11
NEW_SPANS = ("provisioning.pending", "provisioning.split", "provisioning.wire",
             "provisioning.launch")


def _config() -> dict:
    from benchmark.harness import manifest

    return manifest.load_cell("suite-400.operator", rehearse=True).config


def _batch(stream: str = "batch0") -> list:
    return pod_mix(PODS, seeded(SEED, stream), _config()["pod_mix"])


def _wait(done, what: str, seconds: float = 30.0) -> None:
    end = time.monotonic() + seconds
    while not done():
        assert time.monotonic() < end, f"{what}: not within {seconds} s"
        time.sleep(0.005)


class Deployed:
    """The operator beside the sidecar, the provisioning loop taken out."""

    def __init__(self, monkeypatch) -> None:
        self.side = Sidecar(TYPES, 1, traced=False)
        self.address = operator_cycle.dialled(self.side.client)
        monkeypatch.setenv("KC_SOLVER_ADDRESS", self.address)
        self.kube = KubeClient()
        self.provider = FakeCloudProvider(self.side.catalog)
        self.operator = Operator(cloud_provider=self.provider, kube_client=self.kube,
                                 use_tpu_kernel=True).with_controllers()
        self.operator._singletons = [
            s for s in self.operator._singletons if s.name != "provisioning"]
        self.kube.create(self.side.provisioners[0])
        self.operator.start()
        _wait(lambda: self.operator.is_leader() and all(
            c._thread is not None for c in self.operator._watchers), "leadership")

    def provision(self, pods: list):
        for pod in pods:
            self.kube.create(pod)
        self.operator.recorder.reset()
        return self.operator.provisioning.reconcile(wait_for_batch=False)

    def quiet(self) -> bool:
        return all(w._queue.empty() and not w._pending for w in self.operator._watchers)

    def scale_down(self, pods: list) -> None:
        _wait(self.quiet, "the watch controllers drain the launch")
        for pod in pods:
            self.kube.delete(pod)
        for node in self.kube.list_nodes():
            self.kube.delete(node)
        _wait(lambda: not self.kube.list_nodes() and not self.provider.created_machines()
              and self.quiet(), "scale-down")

    def close(self) -> None:
        self.operator.stop()
        self.side.close()


@pytest.fixture()
def deployed(monkeypatch, tmp_path):
    monkeypatch.setenv("KC_LEASE_STATE", str(tmp_path / "leases.json"))
    env = Deployed(monkeypatch)
    yield env
    env.close()


def _launched_types(kube) -> collections.Counter:
    return collections.Counter(
        n.metadata.labels[labels_api.LABEL_INSTANCE_TYPE_STABLE] for n in kube.list_nodes())


def test_the_operator_launches_what_a_bare_solve_decides_and_the_host_totals(deployed):
    pods = _batch()
    assert deployed.provision(pods) is None
    nominated = nominations(deployed.operator.recorder)
    assert set(nominated) == {p.uid for p in pods}
    assert deployed.operator.provisioning._solver_client is not None  # it went over the wire
    launched = _launched_types(deployed.kube)

    # a bare client, the same batch: the sidecar's own decision
    bare = SnapshotSolverClient(deployed.address)
    try:
        reply = bare.solve_classes(_batch(), deployed.side.provisioners)
    finally:
        bare.close()
    assert not reply["failedPodIndices"] and not reply["residualPodIndices"]
    assert len(reply["newNodes"]) == sum(launched.values())
    index = {it.name: i for i, it in enumerate(deployed.side.catalog)}  # price grows with it
    cheapest = collections.Counter(
        min(node["instanceTypes"], key=index.__getitem__) for node in reply["newNodes"])
    assert launched == cheapest

    # the host path, a plain environment of its own: the same totals
    kube, provider = KubeClient(), FakeCloudProvider(deployed.side.catalog)
    host = Operator(cloud_provider=provider, kube_client=kube,
                    use_tpu_kernel=False).with_controllers()
    kube.create(deployed.side.provisioners[0])
    for pod in _batch():
        kube.create(pod)
    assert host.provisioning.reconcile(wait_for_batch=False) is None
    assert len(nominations(host.recorder)) == PODS
    assert len(kube.list_nodes()) == sum(launched.values())


def test_each_phase_span_opens_once_a_reconcile_with_its_counts(deployed, traced):
    tracing.TRACE_STORE.set_capacity(4096)  # a node reconcile is a root trace too
    rebuilds0 = STATE_NODE_REBUILDS.labels().value
    assert deployed.provision(_batch()) is None
    (trace,) = [t for t in tracing.TRACE_STORE.last() if t.name == "provisioning.reconcile"]
    spans = collections.defaultdict(list)
    for span in trace.spans:
        spans[span["name"]].append(span["attrs"])
    nodes = len(deployed.kube.list_nodes())
    assert all(len(spans[name]) == 1 for name in NEW_SPANS), {k: len(v) for k, v in spans.items()}
    assert "provisioning.remainder" not in spans  # nothing of this mix goes to the host
    assert spans["provisioning.pending"][0] == {"pods": PODS, "listed": PODS}
    split = spans["provisioning.split"][0]
    assert (split["pods"], split["host_pods"], split["interned"]) == (PODS, 0, 0)
    assert split["classes"] == spans["client.pack"][0]["classes"] > 0
    assert spans["provisioning.wire"][0] == {"nodes": 0, "bound_pods": 0, "claims": 0}
    launch = spans["provisioning.launch"][0]
    assert (launch["machines"], launch["created"], launch["events"]) == (nodes, nodes, PODS)
    # launch's own update_node and the informer's, a node; the node controller's
    # apply may land inside the span or after it
    assert 2 * nodes <= launch["state_rebuilds"] <= 4 * nodes
    # ... each a read of the node's own pods through the store's index: a
    # fresh node has none (the walk this replaced read PODS a rebuild)
    assert launch["rebuild_pods"] == 0
    # the attribute is the counter's movement while the span was open: once the
    # node controller has caught up the counter stands at three a node
    _wait(deployed.quiet, "the watch controllers drain the launch")
    moved = STATE_NODE_REBUILDS.labels().value - rebuilds0
    assert launch["state_rebuilds"] <= moved == 3 * nodes
    (rpc,) = spans["client.rpc"]  # exactly one /SolveClasses a pass
    assert rpc["request_bytes"] > 0
    assert not spans["client.classify"]  # members= rode the wire: the split had grouped them

    # the same shapes again: the interner knew every one
    deployed.scale_down(deployed.kube.list_pods())
    assert deployed.provision(_batch()) is None
    again = [t for t in tracing.TRACE_STORE.last() if t.name == "provisioning.reconcile"][-1]
    (split,) = [s["attrs"] for s in again.spans if s["name"] == "provisioning.split"]
    assert split["interned"] == split["classes"] > 0


def test_after_the_scale_down_the_next_reconcile_sees_an_empty_cluster(deployed):
    first = _batch()
    assert deployed.provision(first) is None
    before = _launched_types(deployed.kube)
    created = len(deployed.provider.create_calls)
    deployed.scale_down(first)
    cluster = deployed.operator.cluster
    assert not cluster.snapshot_nodes() and not cluster.bindings
    assert not cluster.name_to_provider_id and not deployed.kube.list_pods()
    assert len(deployed.provider.delete_calls) == created == sum(before.values())
    assert deployed.operator.provisioning.reconcile(wait_for_batch=False) is None  # nothing to do
    assert not deployed.kube.list_nodes()

    # the same shapes under fresh names: the same fleet, none of it nominated before
    second = _batch()
    assert not {p.uid for p in first} & {p.uid for p in second}
    assert deployed.provision(second) is None
    assert _launched_types(deployed.kube) == before
    assert set(nominations(deployed.operator.recorder)) == {p.uid for p in second}
    assert all(n.nominated(cluster.clock) for n in cluster.snapshot_nodes())


def test_a_unit_longer_than_the_batch_window_is_provisioned_once(monkeypatch, tmp_path):
    """With the provisioning loop left running, a unit that outlasts the 1 s
    idle window is provisioned a second time by the loop.  The kind takes the
    loop out before ``start()``; every other controller runs."""
    monkeypatch.setenv("KC_LEASE_STATE", str(tmp_path / "leases.json"))
    side = Sidecar(TYPES, 1, traced=False)
    config = _config()
    ctx = types.SimpleNamespace(
        config={**config, "batch_sizes": [PODS], "timed_sizes": [PODS], "oracle": {"pods": PODS}},
        traffic={"warm_sizes": "batch_sizes", "sizes": "timed_sizes", "batches_per_size": 2},
        seed=SEED, sidecar=side, timeout=120.0)
    kind = operator_cycle.Kind(ctx)
    try:
        operator = kind.operator
        assert [s.name for s in operator._singletons] == [
            "deprovisioning", "metrics_state", "inflightchecks"]
        assert [w.name for w in operator._watchers] == [
            "node", "provisioning_trigger", "counter"]
        assert kind.group == 2
        inflight = next(s for s in operator._singletons if s.name == "inflightchecks")
        assert kind.setup() == []
        # set-up's last gap restarted the inflight loop on a thread of its own
        assert all(c._thread.is_alive() for c in operator._watchers + operator._singletons)
        assert len(inflight._thread.name) and operator.inflight_checks._last_scan
        assert operator.provisioning.solver_endpoint == operator_cycle.dialled(side.client)
        assert [len(o.pods) for o, _wall in kind.sized + kind.warm] == [PODS] * 3
        nodes = len(kind.warm[0][0].nodes)

        slow = side.service._solve_classes

        def outlasts_the_window(request, context):
            time.sleep(1.0 + 0.4)  # Settings.batch_idle_duration and a margin
            return slow(request, context)

        side.service._solve_classes = outlasts_the_window
        solves0 = kind.solves
        out = kind.unit(0)
        assert out[1].client_s > operator.settings.batch_idle_duration
        time.sleep(0.3)  # a loop, were one running, would be inside its second pass by now
        assert kind.solves - solves0 == 1
        side.service._solve_classes = slow
        assert kind.settle(0, out) == (PODS, [])
        assert len(kind.last[0].nodes) == nodes
        assert kind.check()["failures"] == []
        # the warm-up size, the two draws in set-up, the one timed unit
        created = sum(len(o.nodes) for o, _wall in kind.sized + kind.warm) + nodes
        assert len(kind.provider.create_calls) == len(kind.provider.delete_calls) == created
        assert not kind.kube.list_nodes() and not kind.kube.list_pods()
        assert not operator.cluster.snapshot_nodes() and not kind.provider.created_machines()
    finally:
        side.close()
