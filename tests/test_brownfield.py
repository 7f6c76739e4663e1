"""The served path against a live cluster (ISSUE 27): ``/SolveClasses`` with
``nodes=`` — every node as the operator's wire dict with every pod bound to it
— against the host oracle given the same cluster as state nodes, down to the
pods placed on EACH existing node, at sizes the CPU holds.

The clusters, the pending batches and the reference are the benchmark's own
(``benchmark/traffic/kinds/cluster_cycle.py`` / ``cluster_reference.py``), so
what decides ``correct`` in the cell ``brownfield-5k.full`` is what is tested
here: bound pods are members of the pending pods' hostname-spread, zone-spread
and affinity groups and seed the topology counts.
"""

import json
import os

import pytest

from benchmark.harness import checks
from benchmark.harness.podmix import draw, seeded
from benchmark.harness.sut import Sidecar
from benchmark.traffic.kinds import cluster_cycle
from benchmark.traffic.kinds import cluster_reference as reference
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import Taint
from karpenter_core_tpu.testing import make_pod

pytestmark = pytest.mark.compile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "benchmark", "configs", "brownfield-5k.json")) as f:
    CONFIG = json.load(f)
TYPES = 40  # fake.instance_types(40) holds the configuration's node types


@pytest.fixture(scope="module")
def sidecar():
    side = Sidecar(TYPES, 1, traced=False)
    yield side
    side.close()


def _cluster(side, nodes: int, utilisation: float, seed: int) -> list:
    config = {**CONFIG, "existing_nodes": nodes, "utilisation": utilisation}
    return cluster_cycle.build_cluster(config, seed, side.catalog, side.provisioners[0].name)


def _pending(n: int, seed: int) -> list:
    return cluster_cycle.by_workload(draw(n, seeded(seed, "pending"), CONFIG["pod_mix"]))


def _taint(cluster, e):
    cluster[e][0].spec.taints.append(Taint("dedicated", "batch", "NoSchedule"))


def _uninitialize(cluster, e):
    del cluster[e][0].metadata.labels[labels_api.LABEL_NODE_INITIALIZED]


# (nodes, utilisation, pending pods, what is done to the seeded cluster)
CASES = {
    "8-nodes-empty": (8, 0.0, 120, ()),
    "16-nodes-60pct": (16, 0.60, 160, ()),
    "64-nodes-95pct": (64, 0.95, 500, ()),
    "12-nodes-60pct-over-a-bucket-edge": (12, 0.60, 120, ()),
    "24-nodes-60pct-tainted-and-uninitialized": (
        24, 0.60, 160, ((_taint, 0), (_taint, 5), (_uninitialize, 1), (_uninitialize, 7))),
}


def _solve_and_compare(side, cluster, pods, volume_limits=None, claim_drivers=None):
    reply, call = side.call(
        side.client.solve_classes, pods, side.provisioners,
        nodes=cluster_cycle.wire_nodes(cluster, volume_limits),
        claim_drivers=claim_drivers, timeout=600.0)
    assert reply is not None, call.error
    host = reference.oracle_totals(pods, cluster, side.catalog, side.provisioners,
                                   volume_limits, claim_drivers)
    assert reference.differences(reference.totals(reply), host) == []
    got = checks.counts(reply)
    assert got["scheduled"] + got["failed"] + got["residual"] == len(pods)
    assert reference.existing_capacity(reply, pods, cluster) == []
    assert checks.capacity(reply, pods, side.catalog) == []
    return reply, host


@pytest.mark.parametrize("case", sorted(CASES))
def test_served_path_equals_the_oracle_per_existing_node(sidecar, case):
    nodes, utilisation, n_pods, edits = CASES[case]
    cluster = _cluster(sidecar, nodes, utilisation, seed=27)
    for edit, e in edits:
        edit(cluster, e)
    pods = _pending(n_pods, seed=27)
    reply, host = _solve_and_compare(sidecar, cluster, pods)
    assert checks.accounting(reply, len(pods)) == []
    assert reference.topology(reply, pods, cluster, seeded(27, "sample")) == []
    assert host["existing"], "the case must put pods on existing nodes"
    tainted = {cluster[e][0].name for edit, e in edits if edit is _taint}
    assert not tainted & set(reply["existingAssignments"])
    if utilisation >= 0.9:
        assert host["nodes"] > 0, "a full cluster must also open new nodes"


def test_a_volume_limited_node_takes_one_workloads_claim(sidecar):
    cluster = _cluster(sidecar, 8, 0.0, seed=28)
    first = cluster[0][0].name
    # two workloads, each of two pods on one shared claim of its own; the
    # first node may attach one volume: it takes one workload, not both
    pods = []
    for workload, claim in enumerate(("claim-a", "claim-b")):
        for _ in range(2):
            pod = make_pod(labels={"app": claim}, requests={"cpu": "100m"}, pvcs=[claim])
            pod.metadata.creation_timestamp = float(workload)
            pods.append(pod)
    reply, host = _solve_and_compare(
        sidecar, cluster, pods, volume_limits={first: {"csi.test": 1}},
        claim_drivers={"default/claim-a": "csi.test", "default/claim-b": "csi.test"})
    assert host["existing"][first] == 2 and host["scheduled"] == 4


def test_one_more_node_over_the_bucket_edge_changes_nothing(sidecar):
    """E = 8 pads to 8, E = 9 to 12 (``ops.solve.bucket``): another executable,
    the same answer on the eight nodes both clusters share."""
    cluster = _cluster(sidecar, 9, 0.60, seed=29)
    cluster[8] = (cluster[8][0], [])  # the ninth node holds nothing (no topology counts)
    _taint(cluster, 8)  # and takes nothing
    pods = _pending(100, seed=29)
    small, _ = _solve_and_compare(sidecar, cluster[:8], pods)
    large, _ = _solve_and_compare(sidecar, cluster, pods)

    def workloads(indices):  # replicas of one workload are interchangeable
        return sorted((sorted(pods[i].metadata.labels.items()),
                       sorted(pods[i].spec.containers[0].resources.requests.items()))
                      for i in indices)

    def placed(reply):
        return ({name: workloads(idx) for name, idx in reply["existingAssignments"].items()},
                [(n["instanceTypes"], n["zones"], workloads(n["podIndices"]))
                 for n in reply["newNodes"]])

    assert placed(small) == placed(large)
    assert small["failedPodIndices"] == large["failedPodIndices"] == []


def test_the_configurations_pod_mix_is_the_suites_byte_for_byte():
    with open(os.path.join(REPO, "benchmark", "configs", "upstream-suite-400.json")) as f:
        suite = json.load(f)
    assert json.dumps(CONFIG["pod_mix"], indent=2) == json.dumps(suite["pod_mix"], indent=2)
    assert CONFIG["types"] == suite["types"] and CONFIG["provisioners"] == suite["provisioners"]
    assert CONFIG["reduced"] == [] and CONFIG["backlogs"] == [10000, 10000]
    assert CONFIG["existing_nodes"] == 5000 and CONFIG["utilisation"] == 0.60


def test_the_cluster_comes_from_the_seed_alone(sidecar):
    def shape(cluster):
        return [(node.name, node.metadata.labels[labels_api.LABEL_TOPOLOGY_ZONE],
                 node.status.allocatable["cpu"],
                 [(tuple(sorted(p.metadata.labels.items())),
                   tuple(sorted(p.spec.containers[0].resources.requests.items())))
                  for p in bound]) for node, bound in cluster]

    a, b = _cluster(sidecar, 20, 0.60, seed=5), _cluster(sidecar, 20, 0.60, seed=5)
    assert shape(a) == shape(b) != shape(_cluster(sidecar, 20, 0.60, seed=6))
    for node, bound in a:
        need = reference._needs(bound).sum(axis=0)
        assert need[0] <= 0.60 * node.status.allocatable["cpu"] + 1e-9
        assert need[1] <= 0.60 * node.status.allocatable["memory"] + 1e-9
        assert all(p.spec.node_name == node.name for p in bound)
