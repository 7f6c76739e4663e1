"""``deployment_cycle``'s generator — how a backlog groups into Deployments,
how kinds are dealt, that replicas are identical and seeds reproducible — and
``deployment_reference``: each of its four guarantees failing on a doctored
answer.  No solver runs here."""

import collections

import pytest

from benchmark.harness import manifest
from benchmark.harness.podmix import seeded
from benchmark.traffic.kinds import deployment_cycle as cycle
from benchmark.traffic.kinds import deployment_reference as reference

CONFIG = manifest.load_cell("manyshape-50k.full").config
ZONE, HOSTNAME = "topology.kubernetes.io/zone", "kubernetes.io/hostname"
ZONES = ["test-zone-1", "test-zone-2", "test-zone-3"]


def _deal(n: int, seed, stream: str = "batch0", **replace) -> list:
    return cycle.deal(n, {**CONFIG, **replace}, seeded(seed, stream))


def _shape(pod) -> tuple:
    """Everything the solver can read of a pod but its name and uid."""
    spec = pod.spec
    spreads = tuple((c.max_skew, c.topology_key, tuple(c.label_selector.match_labels.items()))
                    for c in spec.topology_spread_constraints)
    terms = ()
    if spec.affinity is not None and spec.affinity.pod_affinity is not None:
        terms = tuple((t.topology_key, tuple(t.label_selector.match_labels.items()),
                       tuple(t.namespaces)) for t in spec.affinity.pod_affinity.required)
    requests = tuple(sorted(spec.containers[0].resources.requests.items()))
    return (pod.namespace, tuple(sorted(pod.metadata.labels.items())), requests, spreads,
            terms, pod.metadata.creation_timestamp)


@pytest.mark.parametrize("seed", [1, 7, 3000000019])
def test_50k_pods_are_5467_deployments_and_3120_groups_in_every_seed(seed):
    dealt = _deal(50_000, seed)
    assert cycle.summary(dealt) == {
        "pods": 50_000, "deployments": 5_467, "namespaces": 17,
        "by_tier": {"big": 50, "medium": 417, "small": 5_000},
        "groups": 3_120, "largest_hostname_spread": 250,
    }
    assert len({d.key for d in dealt}) == 5_467
    # 7 big + 59 medium + 714 small of each of the four constrained kinds
    kinds = collections.Counter((d.tier, d.kind, d.topology) for d in dealt)
    for tier, each in (("big", 7), ("medium", 59), ("small", 714)):
        assert kinds[tier, "spread", "zone"] == kinds[tier, "spread", "hostname"] == each
        assert kinds[tier, "affinity", "zone"] == 2 * each
    assert sum(n for (_, kind, _), n in kinds.items() if kind == "generic") == 5_467 - 3_120


@pytest.mark.parametrize("n, namespace_pods, want", [
    (3_000, 3_000, {"big": [250] * 3, "medium": [30] * 25, "small": [5] * 300}),
    (2_000, 3_000, {"big": [250] * 2, "medium": [30] * 16 + [20], "small": [5] * 200}),
    (1_000, 1_000, {"big": [250], "medium": [30] * 8 + [10], "small": [5] * 100}),
    (700, 700, {"big": [175], "medium": [30] * 5 + [25], "small": [5] * 70}),
    (7, 3_000, {"big": [1], "medium": [1], "small": [5]}),
])
def test_a_tiers_last_deployment_takes_what_is_left_of_its_quota(n, namespace_pods, want):
    slots = cycle.layout(n, namespace_pods, CONFIG["tiers"])
    got = collections.defaultdict(list)
    for namespace, tier, k, replicas in slots:
        assert namespace == 0 and k == len(got[tier])
        got[tier].append(replicas)
    assert dict(got) == want and sum(map(sum, got.values())) == n


def test_the_same_seed_deals_the_same_backlog_and_another_seed_another():
    a, b = _deal(6_000, 11), _deal(6_000, 11)
    assert a == b
    assert [_shape(p) for p in cycle.pods_of(a)] == [_shape(p) for p in cycle.pods_of(b)]
    other, stream = _deal(6_000, 12), _deal(6_000, 11, "batch1")
    assert [d.key for d in other] == [d.key for d in a]  # the layout is the sizes' alone
    assert other != a and stream != a


def test_every_pod_of_a_deployment_is_identical_and_selects_its_own_name():
    dealt = _deal(3_000, 5)
    pods = cycle.pods_of(dealt)
    assert len(pods) == 3_000
    at = 0
    for created, d in enumerate(dealt):
        replicas = pods[at: at + d.replicas]
        at += d.replicas
        assert len({_shape(p) for p in replicas}) == 1
        assert len({p.uid for p in replicas}) == d.replicas
        pod = replicas[0]
        assert pod.namespace == d.namespace == "ns-0"
        assert pod.metadata.labels == {"name": d.name, "group": "load"}
        assert pod.metadata.creation_timestamp == float(created)
        _, _, requests, spreads, terms, _ = _shape(pod)
        key = {"zone": ZONE, "hostname": HOSTNAME}.get(d.topology)
        selected = (("name", d.name),)
        assert spreads == (((1, key, selected),) if d.kind == "spread" else ())
        assert terms == (((key, selected, ()),) if d.kind == "affinity" else ())
        assert d.cpu in CONFIG["pod_mix"]["cpu"] and d.memory in CONFIG["pod_mix"]["memory"]
        assert len(requests) == 2


# -- the reference ------------------------------------------------------------


def _backlog():
    """One Deployment of each constrained kind and a generic one, and an
    answer that keeps every guarantee."""
    D = cycle.Deployment
    dealt = [
        D("ns-0", "big-0", "big", 4, "spread", "hostname", "100m", "100Mi"),
        D("ns-0", "medium-0", "medium", 5, "spread", "zone", "100m", "100Mi"),
        D("ns-0", "medium-1", "medium", 3, "affinity", "zone", "100m", "100Mi"),
        D("ns-1", "medium-1", "medium", 2, "generic", None, "100m", "100Mi"),
    ]
    pods = cycle.pods_of(dealt)
    node = lambda zones, idx: {"zones": zones, "podIndices": idx}  # noqa: E731
    reply = {"newNodes": [
        node(["test-zone-1"], [0, 4, 9, 10, 12]),
        node(["test-zone-2"], [1, 5, 7]),
        node(["test-zone-3"], [2, 6, 8, 13]),
        node(["test-zone-1", "test-zone-2"], [3, 11]),
    ]}
    return dealt, pods, reply


def test_an_answer_that_keeps_every_guarantee_passes():
    dealt, pods, reply = _backlog()
    assert reference.largest_hostname_spread(dealt) == 4
    assert reference.guarantees(reply, pods, dealt, ZONES) == []
    placed = reference.placements(reply, pods)
    assert sorted(map(len, placed.values())) == [2, 3, 4, 5]
    assert set(placed) == {d.key for d in dealt}


def _doctored(move):
    dealt, pods, reply = _backlog()
    move(reply["newNodes"])
    return reference.guarantees(reply, pods, dealt, ZONES)


def test_two_pods_of_a_hostname_spread_deployment_on_one_node_fail():
    def move(nodes):
        nodes[3]["podIndices"].remove(3)
        nodes[0]["podIndices"].append(3)
    (message,) = _doctored(move)
    assert message.startswith("hostname spread ns-0/big-0") and "[0]" in message


def test_a_zone_spread_deployment_two_over_its_emptiest_zone_fails():
    def move(nodes):  # zone counts 1 / 2 / 2 -> 3 / 2 / 0
        nodes[2]["podIndices"] = [2, 13]
        nodes[0]["podIndices"] += [6, 8]
    (message,) = _doctored(move)
    assert message.startswith("zone spread ns-0/medium-0") and "'test-zone-3': 0" in message


def test_an_unpinned_node_is_allowed_for_everywhere_and_counted_nowhere():
    def move(nodes):  # 1 / 1 / 2 pinned + 1 on a node that may launch in either zone
        nodes[1]["podIndices"].remove(7)
        nodes[3]["podIndices"].append(7)
    assert _doctored(move) == []


def test_an_affinity_deployment_in_two_zones_fails():
    def move(nodes):
        nodes[0]["podIndices"].remove(9)
        nodes[2]["podIndices"].append(9)
    (message,) = _doctored(move)
    assert message.startswith("zone affinity ns-0/medium-1") and "share no zone" in message


def test_a_fleet_under_the_largest_hostname_spread_deployment_fails():
    def move(nodes):
        nodes[0]["podIndices"] += nodes.pop()["podIndices"]
    messages = _doctored(move)
    assert any(m.startswith("3 new nodes for a hostname-spread Deployment of 4") for m in messages)


def test_a_replica_the_answer_does_not_place_is_named():
    def move(nodes):
        nodes[0]["podIndices"].remove(12)
    (message,) = _doctored(move)
    assert message == "Deployment ns-1/medium-1: 1 of 2 replicas are on new nodes"


# -- the cut's comparison with the host ---------------------------------------


@pytest.mark.parametrize("host_nodes, host_scheduled, want", [
    (4, 14, []),  # equal
    (6, 14, []),  # the host pinned its nodes to one zone and opened two more
    (3, 14, ["oracle cut: nodes: kernel 4 opens more than the host's 3"]),
    (4, 13, ["oracle cut: scheduled: kernel 14 vs host 13"]),
])
def test_the_cut_holds_the_kernel_to_a_fleet_no_larger_than_the_hosts(
        monkeypatch, capsys, host_nodes, host_scheduled, want):
    from benchmark.harness import checks
    from karpenter_core_tpu.cloudprovider.fake import instance_types

    dealt, pods, reply = _backlog()
    reply.update(failedPodIndices=[], residualPodIndices=[], existingAssignments={})
    for node in reply["newNodes"]:
        node["instanceTypes"] = ["fake-it-40"]
    host = {"nodes": host_nodes, "scheduled": host_scheduled, "failed": 0, "residual": 0}
    monkeypatch.setattr(checks, "oracle_totals", lambda *a: host)
    assert reference.oracle(reply, pods, dealt, instance_types(50), [], ZONES) == want
    assert '"oracle_cut"' in capsys.readouterr().out


def test_a_smaller_fleet_is_no_excuse_for_a_broken_guarantee(monkeypatch):
    from benchmark.harness import checks
    from karpenter_core_tpu.cloudprovider.fake import instance_types

    dealt, pods, reply = _backlog()
    reply.update(failedPodIndices=[], residualPodIndices=[], existingAssignments={})
    nodes = reply["newNodes"]
    nodes[0]["podIndices"] += nodes.pop()["podIndices"]  # 3 nodes: two pods of big-0 on one
    for node in nodes:
        node["instanceTypes"] = ["fake-it-40"]
    host = {"nodes": 4, "scheduled": 14, "failed": 0, "residual": 0}
    monkeypatch.setattr(checks, "oracle_totals", lambda *a: host)
    bad = reference.oracle(reply, pods, dealt, instance_types(50), [], ZONES)
    assert any("hostname spread ns-0/big-0" in m for m in bad)
    assert any("3 new nodes for a hostname-spread Deployment of 4" in m for m in bad)
