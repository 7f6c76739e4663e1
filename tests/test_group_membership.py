"""``models.snapshot.group_membership`` — the one routine behind
``grp_member`` (pending classes) and ``encode_existing``'s bound-pod group
counts — against the product loop it replaced: the same booleans, bit for
bit, on fuzzed scopes; and, on a namespace of Deployments, the kernel's
totals against the host oracle's."""

import random

import numpy as np
import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    LabelSelector,
    LabelSelectorRequirement,
    PodAffinityTerm,
    TopologySpreadConstraint,
)
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.models.snapshot import GroupScope, group_membership
from karpenter_core_tpu.operator.kubeclient import KubeClient
from karpenter_core_tpu.solver.builder import build_scheduler
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.state.cluster import StateNode
from karpenter_core_tpu.testing import make_node, make_pod, make_provisioner

NAMESPACES = ["", "default", "ns-0", "ns-1", "ns-2"]
KEYS = ["name", "group", "tier", "app"]
VALUES = ["a", "b", "c", "load"]
OPERATORS = ["In", "NotIn", "Exists", "DoesNotExist", "Bogus"]


def product(pods, scopes) -> np.ndarray:
    """The loop ``_populate_class_planes`` and ``encode_existing`` ran."""
    member = np.zeros((len(pods), len(scopes)), dtype=bool)
    for i, pod in enumerate(pods):
        for g, scope in enumerate(scopes):
            member[i, g] = scope is not None and scope.matches_pod(pod)
    return member


def fuzz_pods(rng: random.Random, n: int) -> list:
    pods = []
    for _ in range(n):
        labels = {k: rng.choice(VALUES) for k in KEYS if rng.random() < 0.6}
        pods.append(make_pod(namespace=rng.choice(NAMESPACES), labels=labels))
    return pods


def fuzz_scope(rng: random.Random):
    shape = rng.choice(["none", "no-selector", "empty", "labels", "labels", "labels",
                        "expressions", "both"])
    if shape == "none":
        return None
    namespaces = frozenset(rng.sample(NAMESPACES + ["absent"], rng.randint(0, 3)))
    if shape == "no-selector":
        return GroupScope(None, namespaces)
    match_labels, expressions = {}, []
    if shape in ("labels", "both"):
        match_labels = {k: rng.choice(VALUES + ["nobody"])
                        for k in rng.sample(KEYS, rng.randint(1, 3))}
    if shape in ("expressions", "both"):
        expressions = [
            LabelSelectorRequirement(key=rng.choice(KEYS), operator=rng.choice(OPERATORS),
                                     values=rng.sample(VALUES, rng.randint(0, 3)))
            for _ in range(rng.randint(1, 2))
        ]
    return GroupScope(LabelSelector(match_labels=match_labels,
                                    match_expressions=expressions), namespaces)


@pytest.mark.parametrize("seed", range(12))
def test_the_index_equals_the_product_loop_on_fuzzed_scopes(seed):
    rng = random.Random(seed)
    pods = fuzz_pods(rng, rng.randint(0, 60))
    scopes = [fuzz_scope(rng) for _ in range(rng.randint(0, 40))]
    member, cost = group_membership(pods, scopes)
    want = product(pods, scopes)
    assert member.dtype == np.bool_ and member.shape == want.shape
    assert np.array_equal(member, want)
    assert cost["members"] == int(want.sum())
    assert cost["namespaces"] == len({p.namespace or "" for p in pods})
    assert cost["members"] <= cost["candidates"] <= len(pods) * len(scopes)


def test_a_selector_per_deployment_costs_a_lookup_a_group_not_the_product():
    pods = [make_pod(namespace=f"ns-{i % 4}", labels={"name": f"d-{i}", "group": "load"})
            for i in range(200)]
    scopes = [GroupScope(LabelSelector(match_labels={"name": f"d-{i}"}),
                         frozenset({f"ns-{i % 4}"})) for i in range(0, 200, 2)]
    member, cost = group_membership(pods, scopes)
    assert np.array_equal(member, product(pods, scopes))
    assert cost == {"namespaces": 4, "candidates": 100, "members": 100}


def test_a_label_value_that_is_not_text_takes_the_plain_path():
    """``labels.get(key) != None`` is false for a pod WITHOUT the key: the
    product loop counts it a member, and so must the index."""
    pods = [make_pod(labels={"name": "a"}), make_pod(labels={})]
    scopes = [GroupScope(LabelSelector(match_labels={"tier": None}), frozenset({"default"}))]
    assert np.array_equal(group_membership(pods, scopes)[0], product(pods, scopes))
    assert group_membership(pods, scopes)[0].all()


# -- through the two encoders --------------------------------------------------

ZONE = labels_api.LABEL_TOPOLOGY_ZONE
HOSTNAME = labels_api.LABEL_HOSTNAME


def constrained_pods(rng: random.Random, n_workloads: int, namespaces: list) -> list:
    """Workloads with a selector of their own — ``match_labels``, an
    expression, or both — over a few namespaces and shared label values."""
    pods = []
    for w in range(n_workloads):
        namespace = rng.choice(namespaces)
        labels = {"name": f"w-{rng.randint(0, n_workloads // 2)}", "group": "load"}
        if rng.random() < 0.3:
            del labels["group"]
        how = rng.choice(["labels", "expression", "both", "generic"])
        selector = LabelSelector(
            match_labels={"name": labels["name"]} if how in ("labels", "both") else {},
            match_expressions=[LabelSelectorRequirement("group", "Exists")]
            if how in ("expression", "both") else [],
        )
        extra = {}
        if how != "generic":
            if rng.random() < 0.5:
                extra = {"topology_spread": [TopologySpreadConstraint(
                    max_skew=1, topology_key=rng.choice([ZONE, HOSTNAME]),
                    label_selector=selector)]}
            else:
                extra = {"pod_affinity": [PodAffinityTerm(
                    topology_key=ZONE, label_selector=selector,
                    namespaces=rng.sample(namespaces, 2) if rng.random() < 0.4 else [])]}
        for _ in range(rng.randint(1, 3)):
            pods.append(make_pod(namespace=namespace, labels=dict(labels),
                                 requests={"cpu": "100m"}, **extra))
    return pods


def solver():
    return TPUSolver(fake_cp.FakeCloudProvider(fake_cp.instance_types(12)),
                     [make_provisioner()])


@pytest.mark.parametrize("seed", range(6))
def test_grp_member_of_the_pending_classes_equals_the_product(seed):
    rng = random.Random(1000 + seed)
    pods = constrained_pods(rng, 24, ["ns-0", "ns-1", "default"])
    ingest = PodIngest()
    ingest.add_all(pods)
    snapshot = solver().encode(ingest)
    reps = [cls.pods[0] for cls in snapshot.classes]
    G = len(snapshot.groups)
    assert G > 0 and snapshot.grp_member.shape == (len(reps), G + 1)
    assert np.array_equal(snapshot.grp_member[:, :G], product(reps, snapshot.group_selectors))
    assert not snapshot.grp_member[:, G].any()  # the sentinel column: no group


@pytest.mark.parametrize("seed", range(6))
def test_bound_pod_group_counts_equal_the_product(seed):
    rng = random.Random(2000 + seed)
    namespaces = ["ns-0", "ns-1", "default"]
    pending = constrained_pods(rng, 16, namespaces)
    nodes = [make_node(name=f"live-{e}", labels={ZONE: f"test-zone-{1 + e % 3}"})
             for e in range(5)]
    state_nodes, bound = [], []
    for node in nodes:
        state_node = StateNode(node, None)
        for pod in constrained_pods(rng, 6, namespaces):
            pod.spec.node_name = node.name
            pod.status.phase = "Running"
            state_node.update_for_pod(pod)
            bound.append(pod)
        state_nodes.append(state_node)
    tpu = solver()
    ingest = PodIngest()
    ingest.add_all(pending)
    snapshot = tpu.encode(ingest, state_nodes, bound)
    _, ex_static = tpu.encode_existing(snapshot, state_nodes, bound)
    want = np.zeros_like(np.asarray(ex_static.grp_node_member))
    node_index = {n.name: e for e, n in enumerate(nodes)}
    for pod in bound:
        for g, scope in enumerate(snapshot.group_selectors):
            if scope is not None and scope.matches_pod(pod):
                want[g, node_index[pod.spec.node_name]] += 1
    assert want.any()
    assert np.array_equal(np.asarray(ex_static.grp_node_member), want)


# -- kernel vs host oracle on a namespace of Deployments -----------------------


@pytest.mark.compile
@pytest.mark.parametrize("seed", [1, 3])
def test_kernel_equals_host_oracle_on_a_namespace_of_deployments(seed):
    """The benchmark's oracle cut through the library: one namespace of 1 000
    pods x 1 000 types x 5 provisioners.  (At 100 types resources, not a
    hostname-spread Deployment, set the fleet, and there the kernel packs 34
    and 31 nodes where the host opens 35 and 33 on these two seeds — PERF.md
    section 7, finding 22-4; not this test's subject.)"""
    from benchmark.harness import manifest
    from benchmark.harness.podmix import seeded
    from benchmark.traffic.kinds import deployment_cycle

    config = manifest.load_cell("manyshape-50k.full").config
    dealt = deployment_cycle.deal(1000, {**config, "namespace_pods": 1000},
                                  seeded(seed, "oracle"))
    assert len(dealt) == 110
    catalog = fake_cp.instance_types(1000)
    provisioners = [make_provisioner(name=f"prov-{i}", weight=5 - i) for i in range(5)]

    kube = KubeClient()
    for provisioner in provisioners:
        kube.create(provisioner)
    pods = deployment_cycle.pods_of(dealt)
    host = build_scheduler(kube, fake_cp.FakeCloudProvider(catalog), cluster=None,
                           pods=pods, state_nodes=[], daemonset_pods=[]).solve(pods)
    pods = deployment_cycle.pods_of(dealt)
    kernel = TPUSolver(fake_cp.FakeCloudProvider(catalog), provisioners).solve(pods)
    totals = lambda r: (sum(len(n.pods) for n in r.new_nodes),  # noqa: E731
                        len(r.failed_pods), len(r.new_nodes))
    assert totals(kernel) == totals(host)
    assert totals(kernel)[:2] == (1000, 0)
