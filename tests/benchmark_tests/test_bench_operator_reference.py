"""``operator_reference``: the plain reference of the ``operator_cycle`` kind
passes a true outcome and catches each planted fault — every fault a case of
one parametrised test, so each counts."""

import collections

import pytest

from benchmark.traffic.kinds import operator_reference as reference
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint
from karpenter_core_tpu.cloudprovider.fake import instance_types
from karpenter_core_tpu.testing import make_pod

CATALOG = instance_types(8)  # fake-it-0 .. fake-it-7: (i + 1) cpu, 2 (i + 1) Gi
ZONES = ["test-zone-1", "test-zone-2", "test-zone-3"]


def _node(name: str, instance_type: str, zone: str) -> reference.NodeView:
    return reference.NodeView(name, f"fake://{name}", (reference.FINALIZER,), instance_type, zone)


def _true_outcome() -> reference.Outcome:
    """Three nodes: two generic pods on a two-cpu node, and a hostname-spread
    group of two that takes a node each."""
    generic = [make_pod(labels={"my-label": "a"}, requests={"cpu": "500m", "memory": "512Mi"})
               for _ in range(2)]
    spread = [make_pod(labels={"my-host-spread": "b"}, requests={"cpu": "250m"},
                       topology_spread=[TopologySpreadConstraint(
                           max_skew=1, topology_key=labels_api.LABEL_HOSTNAME,
                           label_selector=LabelSelector(match_labels={"my-host-spread": "b"}))])
              for _ in range(2)]
    nodes = [_node("n0", "fake-it-1", ZONES[0]), _node("n1", "fake-it-0", ZONES[0]),
             _node("n2", "fake-it-0", ZONES[1])]
    nominated = {generic[0].uid: ["n0"], generic[1].uid: ["n0"],
                 spread[0].uid: ["n1"], spread[1].uid: ["n2"]}
    reply = {"newNodes": [
        {"instanceTypes": ["fake-it-1", "fake-it-2", "fake-it-3"], "classCounts": [[0, 2]]},
        {"instanceTypes": ["fake-it-0", "fake-it-1"], "classCounts": [[1, 1]]},
        {"instanceTypes": ["fake-it-0", "fake-it-1"], "classCounts": [[1, 1]]},
    ]}
    return reference.Outcome(generic + spread, nominated, [], nodes,
                             [n.provider_id for n in nodes], reply)


def test_a_true_outcome_passes_every_guarantee():
    outcome = _true_outcome()
    assert reference.check(outcome, CATALOG) == []
    assert reference.same(outcome, _true_outcome()) == []  # other names and uids
    assert reference.totals(outcome) == {"nodes": 3, "scheduled": 4, "failed": 0, "residual": 0}


def _nominated_twice(o):
    o.nominated[o.pods[0].uid].append("n1")
    return "nominated more than once"


def _not_nominated(o):
    del o.nominated[o.pods[3].uid]
    return "not nominated"


def _over_the_launched_type(o):
    # the reply lists fake-it-1 .. 3 and the two pods fit them; the provider
    # launched a one-cpu type, which the wire's own check would never see
    o.nodes[0] = o.nodes[0]._replace(instance_type="fake-it-0")
    return "need over what the launched type allows"


def _two_spread_members_on_one_node(o):
    o.nominated[o.pods[3].uid] = ["n1"]
    return "hold more than one member"


def _type_outside_the_replys_list(o):
    o.nodes[0] = o.nodes[0]._replace(instance_type="fake-it-7")
    return "nodes of 2 pods: launched ['fake-it-7'] are not each listed"


def _listed_but_not_the_cheapest(o):
    o.nodes[0] = o.nodes[0]._replace(instance_type="fake-it-2")
    return "the cheapest listed is fake-it-1"


def _machine_without_a_node(o):
    o.machines.append("fake://stray")
    return "machine(s) without a node"


def _failed_scheduling(o):
    o.failed.append(o.pods[0].uid)
    return "failed to schedule"


def _node_without_its_machine(o):
    o.machines.remove("fake://n2")
    return "the provider holds no machine"


def _a_node_more_than_the_reply(o):
    o.nodes.append(_node("n3", "fake-it-0", ZONES[2]))
    o.machines.append("fake://n3")
    return "4 nodes launched, the reply has 3 newNodes"


def _pods_a_node_differ(o):
    o.reply["newNodes"][0]["classCounts"] = [[0, 2], [1, 1]]  # the reply put three there
    return "pods a node"


def _one_of_two_types_unlisted(o):
    # two nodes of one pod: matched as multisets against two reply nodes
    o.nodes[2] = o.nodes[2]._replace(instance_type="fake-it-5")
    return "are not each listed by a reply node of that pod count"


@pytest.mark.parametrize("plant", [
    _nominated_twice, _not_nominated, _over_the_launched_type,
    _two_spread_members_on_one_node, _type_outside_the_replys_list,
    _listed_but_not_the_cheapest,
    _machine_without_a_node, _failed_scheduling, _node_without_its_machine,
    _a_node_more_than_the_reply, _pods_a_node_differ, _one_of_two_types_unlisted,
], ids=lambda f: f.__name__.strip("_"))
def test_each_planted_fault_is_caught(plant):
    outcome = _true_outcome()
    message = plant(outcome)
    found = reference.check(outcome, CATALOG)
    assert any(message in f for f in found), found


def _mix_pod(kind: str, value: str):
    from benchmark.harness.podmix import draw

    mix = {"parts_of": 1, "cpu": ["100m"], "memory": ["100Mi"], "label_values": [value],
           "kinds": [{"kind": "generic", "parts": 0, "label_key": "my-label"},
                     {"kind": kind.split("/")[0], "parts": 1, "topology": "zone",
                      "label_key": "my-" + kind, "selector": "own"}]}
    import random

    return draw(1, random.Random(0), mix)[0][1]


@pytest.mark.parametrize("kind, zones, message", [
    ("spread", ZONES[:1] * 3, "zone spread"),  # 3 | 0 | 0 over the catalog's three zones
    ("affinity", ZONES[:2], "zone affinity"),  # a group in two zones
])
def test_zone_rules_read_the_launched_nodes_labels(kind, zones, message):
    pods = [_mix_pod(kind, "a") for _ in zones]
    nodes = [_node(f"n{i}", "fake-it-0", zone) for i, zone in enumerate(zones)]
    outcome = reference.Outcome(
        pods, {p.uid: [n.name] for p, n in zip(pods, nodes)}, [], nodes,
        [n.provider_id for n in nodes], {"newNodes": []})
    assert any(message in f for f in reference.topology(outcome, ZONES))
    # the same pods a zone each (spread) or all in one (affinity) pass
    good = ZONES[:len(pods)] if kind == "spread" else ZONES[:1] * len(pods)
    fixed = outcome._replace(nodes=[n._replace(zone=z) for n, z in zip(nodes, good)])
    assert reference.topology(fixed, ZONES) == []


def test_the_last_outcome_is_held_to_the_warm_up_as_a_multiset():
    warm, last = _true_outcome(), _true_outcome()
    last.nodes[2] = last.nodes[2]._replace(zone=ZONES[2])
    (message,) = reference.same(last, warm)
    assert "differs from the warm-up" in message


def test_events_are_read_as_the_programs_own_tests_read_them():
    from karpenter_core_tpu.events import Recorder
    from karpenter_core_tpu.events import events as evt
    from karpenter_core_tpu.testing import make_node
    from karpenter_core_tpu.testing.harness import nominations

    recorder, pods, node = Recorder(), [make_pod() for _ in range(3)], make_node()
    for pod in pods[:2]:
        recorder.publish(evt.nominate_pod(pod, node))
    recorder.publish(evt.pod_failed_to_schedule(pods[2], "no capacity"))
    nominated, failed = reference.read_events(recorder.events)
    assert nominated == {uid: [name] for uid, name in nominations(recorder).items()}
    assert failed == [pods[2].uid] and isinstance(nominated, dict)
    assert collections.Counter(map(len, nominated.values())) == {1: 2}


def _torn_down():
    """A provider, a store and a cluster state after a clean tear-down: one
    machine created and deleted, nothing left."""
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.state.cluster import Cluster
    from karpenter_core_tpu.utils.clock import Clock

    kube, provider = KubeClient(), FakeCloudProvider(CATALOG)
    provider.create_calls.append("m0")
    provider.delete_calls.append("m0")
    return provider, kube, Cluster(Clock(), kube, provider, None)


def _a_machine_never_deleted(provider, kube):
    provider.create_calls.append("m1")
    return "created 2 machines and deleted 1"


def _a_node_left_in_the_store(provider, kube):
    from karpenter_core_tpu.testing import make_node

    kube.create(make_node())
    return "'nodes': 1"


def _a_pod_left_in_the_store(provider, kube):
    kube.create(make_pod())
    return "'pods': 1"


def test_a_clean_tear_down_leaks_nothing():
    assert reference.leaks(*_torn_down()) == []


@pytest.mark.parametrize("plant", [
    _a_machine_never_deleted, _a_node_left_in_the_store, _a_pod_left_in_the_store,
], ids=lambda f: f.__name__.strip("_"))
def test_each_leak_after_the_last_tear_down_is_caught(plant):
    provider, kube, cluster = _torn_down()
    message = plant(provider, kube)
    found = reference.leaks(provider, kube, cluster)
    assert any(message in f for f in found), found
