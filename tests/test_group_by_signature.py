"""``models.columnar.group_by_signature``: the two cold batch paths —
``classify_pods`` and the channel client's ``solve_classes`` — group a batch
by the fast key and derive one ``_class_signature`` per distinct key
(ISSUE 36, docs/KERNEL_PERF.md "Layer 6").

Four contracts pinned here:

  - the grouping IS the per-pod loop's: the same signatures in the same
    order, each with the same ascending member indices — over fuzzed pods,
    the punt shapes the key refuses, and pairs whose labels / selectors
    differ only in insertion order (two keys, one signature), on the Python
    twin and on the kc_sig C extension;
  - ``_class_signature`` runs once per distinct fast key on the benchmark's
    mix, from both callers, and the spans say so (``fast_keys``, ``punted``);
  - ``classify_pods`` returns the classes the loop returned, pod for pod,
    ladder variants included;
  - a served ``/SolveClasses`` and a served ``/Consolidate`` send and answer
    the bytes the per-pod loop's classification sends and gets.
"""

from __future__ import annotations

import copy
import json
import os
import random

import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    SCHEDULE_ANYWAY,
    Container,
    LabelSelector,
    PodAffinityTerm,
    ResourceRequirements,
    Toleration,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
)
from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
from karpenter_core_tpu.models import columnar, nativesig
from karpenter_core_tpu.models import snapshot as snapshot_mod
from karpenter_core_tpu.models.columnar import _fast_sig_key_py, group_by_signature
from karpenter_core_tpu.models.snapshot import (
    _class_signature,
    build_pod_ladder,
    classify_pods,
    finalize_classes,
    ladder_chain,
)
from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverClient, serve
from karpenter_core_tpu.testing import make_pod, make_provisioner

from tests.test_encode_delta import _corpus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ZONE, HOSTNAME = labels_api.LABEL_TOPOLOGY_ZONE, labels_api.LABEL_HOSTNAME


# -- the loops this PR replaced, kept here as the reference --------------------


def loop_by_sig(pods) -> dict:
    """``SnapshotSolverClient.solve_classes``' classification before PR 36."""
    by_sig: dict = {}
    for i, pod in enumerate(pods):
        by_sig.setdefault(_class_signature(pod), []).append(i)
    return by_sig


def loop_classify_pods(pods) -> list:
    """``models.snapshot.classify_pods`` before PR 36."""
    groups: dict = {}
    order: list = []
    for pod in pods:
        sig = _class_signature(pod)
        cls = groups.get(sig)
        if cls is None:
            cls = build_pod_ladder(pod)
            groups[sig] = cls
            order.append(sig)
        cls.pods.append(pod)
    return finalize_classes([groups[sig] for sig in order])


# -- pods ------------------------------------------------------------------------


def _shuffled(rng: random.Random, mapping: dict) -> dict:
    items = list(mapping.items())
    rng.shuffle(items)
    return dict(items)


def punt_pods() -> list:
    """One of each shape the fast key refuses: a claim, a host port, limits,
    two containers, an init container."""
    two = make_pod(requests={"cpu": "100m"}, labels={"app": "two"})
    two.spec.containers.append(copy.deepcopy(two.spec.containers[0]))
    # an init container smaller than the main one: the pod's ceiling, and so
    # its signature, is the plain pod's — a punted pod that JOINS a fast group
    init = make_pod(requests={"cpu": "250m", "memory": "256Mi"})
    init.spec.init_containers.append(Container(resources=ResourceRequirements(
        requests=dict(init.spec.containers[0].resources.requests))))
    return [
        make_pod(requests={"cpu": "100m"}, pvcs=["claim-a"]),
        make_pod(requests={"cpu": "100m"}, host_ports=[8080]),
        make_pod(requests={"cpu": "100m"}, limits={"cpu": "200m"}),
        two,
        init,
    ]


def order_pairs(rng: random.Random) -> list:
    """Pods whose labels, ``matchLabels`` or node selector differ only in
    insertion order: two fast keys, one signature."""
    labels = {"app": "web", "tier": "front", "team": "a"}
    selector = {"disktype": "ssd", "pool": "blue"}
    pods = []
    for _ in range(3):
        pods.append(make_pod(requests={"cpu": "250m"}, labels=_shuffled(rng, labels)))
        pods.append(make_pod(requests={"cpu": "500m"}, node_selector=_shuffled(rng, selector)))
        pods.append(make_pod(
            requests={"cpu": "250m"}, labels={"app": "zs"},
            topology_spread=[TopologySpreadConstraint(
                max_skew=1, topology_key=ZONE,
                label_selector=LabelSelector(match_labels=_shuffled(rng, labels)))],
        ))
    # reversed outright, so no shuffle can leave every pair in one order
    pods.append(make_pod(requests={"cpu": "250m"}, labels=dict(reversed(labels.items()))))
    pods.append(make_pod(requests={"cpu": "500m"},
                         node_selector=dict(reversed(selector.items()))))
    pods.append(make_pod(
        requests={"cpu": "250m"}, labels={"app": "zs"},
        topology_spread=[TopologySpreadConstraint(
            max_skew=1, topology_key=ZONE,
            label_selector=LabelSelector(match_labels=dict(reversed(labels.items()))))],
    ))
    return pods


def fuzz_pods(rng: random.Random, n: int) -> list:
    """Random draws over every fast-key branch, few enough values that most
    pods share a class."""
    def one():
        app = rng.choice(["a", "b", "c"])
        kwargs = dict(
            requests={"cpu": rng.choice(["100m", "250m", 1]),
                      "memory": rng.choice(["128Mi", "1Gi"])},
            namespace=rng.choice(["default", "other"]),
            labels=_shuffled(rng, {"app": app, "tier": rng.choice(["x", "y"])}),
        )
        roll = rng.random()
        if roll < 0.15:
            kwargs["node_selector"] = _shuffled(rng, {"disktype": "ssd", "pool": "blue"})
        elif roll < 0.3:
            kwargs["tolerations"] = [Toleration(key="dedicated", operator="Equal",
                                                value=app, effect="NoSchedule")]
        elif roll < 0.45:
            kwargs["topology_spread"] = [TopologySpreadConstraint(
                max_skew=1, topology_key=rng.choice([ZONE, HOSTNAME]),
                label_selector=LabelSelector(match_labels={"app": app}))]
        elif roll < 0.6:
            kwargs["pod_affinity"] = [PodAffinityTerm(
                topology_key=ZONE, label_selector=LabelSelector(match_labels={"app": app}))]
        elif roll < 0.7:
            kwargs["pod_anti_affinity"] = [PodAffinityTerm(
                topology_key=HOSTNAME, label_selector=LabelSelector(match_labels={"app": app}))]
        elif roll < 0.8:
            kwargs["limits"] = {"cpu": 2}
        return make_pod(**kwargs)

    return [one() for _ in range(n)]


def mixed_batch(seed: int) -> list:
    rng = random.Random(seed)
    pods = (_corpus(n_per_shape=3) + fuzz_pods(rng, 300) + order_pairs(rng)
            + punt_pods() + punt_pods())
    rng.shuffle(pods)
    return pods


def benchmark_mix(n_pods: int, seed: int = 1) -> list:
    """A draw of the mix every ``backlog-50k`` request sends."""
    from benchmark.harness import podmix

    with open(os.path.join(REPO, "benchmark", "configs", "northstar-50k-1k.json")) as f:
        mix = json.load(f)["pod_mix"]
    return podmix.pod_mix(n_pods, podmix.seeded(seed, "backlog0"), mix)


# -- fixtures --------------------------------------------------------------------


@pytest.fixture(params=["0", "1"], ids=["python-twin", "kc_sig"])
def fast_key(request, monkeypatch):
    """Resolve the fast key afresh under ``KC_NATIVE_SIG`` = 0 and 1; the
    process's own resolution comes back at teardown."""
    monkeypatch.setenv("KC_NATIVE_SIG", request.param)
    monkeypatch.setattr(columnar, "_sig_key_cached", None)
    if request.param == "1" and nativesig.load() is None:
        pytest.skip("kc_sig extension unavailable (no toolchain/headers)")
    assert (columnar._sig_key_impl() is _fast_sig_key_py) == (request.param == "0")
    return request.param


@pytest.fixture()
def signature_calls(monkeypatch):
    """Count ``_class_signature`` calls (``group_by_signature`` resolves the
    name per call, so the wrapper is what it runs)."""
    calls = []

    def counting(pod):
        calls.append(pod)
        return _class_signature(pod)

    monkeypatch.setattr(snapshot_mod, "_class_signature", counting)
    return calls


@pytest.fixture()
def channel():
    server, port = serve(FakeCloudProvider(instance_types(100)))
    client = SnapshotSolverClient(f"127.0.0.1:{port}")
    yield server, client
    client.close()
    server.stop(0)
    server.kc_service.shutdown()


def _spans(name: str) -> list:
    return [s for t in tracing.TRACE_STORE.last(None) for s in t.spans if s["name"] == name]


# -- (a) the grouping is the loop's ------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_groups_equal_the_per_pod_loop(fast_key, seed):
    pods = mixed_batch(seed)
    by_sig, fast_keys, punted = group_by_signature(pods)
    want = loop_by_sig(pods)
    # class order and member order, not just the same sets
    assert list(by_sig.items()) == list(want.items())
    keys = [_fast_sig_key_py(p) for p in pods]
    assert punted == sum(k is None for k in keys) > 0
    assert fast_keys == len({k for k in keys if k is not None})
    assert fast_keys + punted > len(by_sig)  # some keys and punts merged


def test_keys_that_sort_to_one_signature_are_merged_ascending(fast_key):
    rng = random.Random(7)
    pods = order_pairs(rng)
    by_sig, fast_keys, punted = group_by_signature(pods)
    assert punted == 0 and len(by_sig) == 3 and fast_keys >= 6
    assert list(by_sig.items()) == list(loop_by_sig(pods).items())
    for idxs in by_sig.values():
        assert idxs == sorted(idxs) and len(idxs) == 4
        assert len({_fast_sig_key_py(pods[i]) for i in idxs}) >= 2


def test_punted_pods_pay_the_signature_and_join_its_group(fast_key, signature_calls):
    plain = [make_pod(requests={"cpu": "250m", "memory": "256Mi"}) for _ in range(3)]
    pods = plain[:2] + punt_pods() + plain[2:]
    by_sig, fast_keys, punted = group_by_signature(pods)
    assert (fast_keys, punted) == (1, 5)
    assert len(signature_calls) == fast_keys + punted
    assert list(by_sig.items()) == list(loop_by_sig(pods).items())
    # the init-container pod (index 6) sits between the plain pods of its class
    assert by_sig[_class_signature(plain[0])] == [0, 1, 6, 7]


def test_an_empty_batch_and_a_batch_of_punts_alone(fast_key):
    assert group_by_signature([]) == ({}, 0, 0)
    pods = punt_pods() + punt_pods()
    by_sig, fast_keys, punted = group_by_signature(pods)
    assert (fast_keys, punted) == (0, 10)
    assert list(by_sig.items()) == list(loop_by_sig(pods).items())


# -- (b) one derivation per distinct fast key, from both callers ----------------


def test_classify_pods_derives_one_signature_per_fast_key(fast_key, signature_calls, traced):
    pods = benchmark_mix(5000)
    distinct = len({_fast_sig_key_py(p) for p in pods})
    classes = classify_pods(pods)
    assert len(signature_calls) == distinct < len(pods) // 5
    assert len(classes) == distinct  # this mix: one key a class, nothing punts
    (span,) = _spans("encode.classify")
    assert span["attrs"] == {"pods": 5000, "classes": distinct,
                             "fast_keys": distinct, "punted": 0}


def test_solve_classes_derives_one_signature_per_fast_key(
        fast_key, signature_calls, traced, channel):
    _, client = channel
    pods = benchmark_mix(5000)
    distinct = len({_fast_sig_key_py(p) for p in pods})
    at_the_wire = []
    rpc = client._solve_classes

    def counted_rpc(request, timeout=None):
        at_the_wire.append(len(signature_calls))  # before the server's own use
        return rpc(request, timeout=timeout)

    client._solve_classes = counted_rpc
    out = client.solve_classes(pods, [make_provisioner()])
    assert at_the_wire == [distinct]
    assert sum(len(n["podIndices"]) for n in out["newNodes"]) == len(pods)
    (span,) = _spans("client.classify")
    assert span["attrs"] == {"pods": 5000, "classes": distinct,
                             "fast_keys": distinct, "punted": 0}


# -- (c) classify_pods returns the loop's classes, pod for pod -------------------


def ladder_batch(seed: int) -> list:
    """Fast-key shapes with and without a preference ladder, replicas
    interleaved (host ports and claims are ``KernelUnsupported`` or need a
    resolver: not this function's to classify)."""
    rng = random.Random(seed)
    shapes = [
        dict(requests={"cpu": 1}),
        dict(requests={"cpu": "250m"}, labels={"app": "web", "tier": "x"}),
        dict(requests={"cpu": "250m"}, labels={"tier": "x", "app": "web"}),
        dict(requests={"cpu": "500m"}, labels={"app": "soft"}, topology_spread=[
            TopologySpreadConstraint(
                max_skew=1, topology_key=ZONE, when_unsatisfiable=SCHEDULE_ANYWAY,
                label_selector=LabelSelector(match_labels={"app": "soft"}))]),
        dict(requests={"cpu": "500m"}, labels={"app": "shy"}, pod_anti_affinity_preferred=[
            WeightedPodAffinityTerm(weight=1, pod_affinity_term=PodAffinityTerm(
                topology_key=HOSTNAME,
                label_selector=LabelSelector(match_labels={"app": "shy"})))]),
        dict(requests={"cpu": 2}, limits={"cpu": 4}),  # punted, kernel-supported
    ]
    pods = [make_pod(**copy.deepcopy(rng.choice(shapes))) for _ in range(120)]
    return pods


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_classify_pods_returns_the_loops_classes(fast_key, seed):
    pods = ladder_batch(seed)
    got, want = classify_pods(pods), loop_classify_pods(pods)
    assert len(got) == len(want) > 5  # roots and their variants
    assert any(c.is_ladder_variant for c in got)
    for mine, theirs in zip(got, want):
        # the same pod OBJECTS in the same order (variants: equal copies)
        if mine.is_ladder_variant:
            assert theirs.is_ladder_variant and len(mine.pods) == len(theirs.pods) == 1
            assert _class_signature(mine.pods[0]) == _class_signature(theirs.pods[0])
        else:
            assert [id(p) for p in mine.pods] == [id(p) for p in theirs.pods]
        assert mine.requests == theirs.requests
        assert len(ladder_chain(mine)) == len(ladder_chain(theirs))
        assert (mine.relax_to is None) == (theirs.relax_to is None)


# -- (d) the served bytes are the loop's -----------------------------------------


def _capture(client, attr: str) -> list:
    """Record (request, reply) bytes of one of the client's raw calls."""
    seen = []
    raw = getattr(client, attr)

    def recording(request, timeout=None):
        reply = raw(request, timeout=timeout)
        seen.append((request, reply))
        return reply

    setattr(client, attr, recording)
    return seen


def test_served_solve_classes_sends_and_answers_the_loops_bytes(channel):
    """``backlog-50k.full`` at its rehearsal size: 1 400 pods x 100 types."""
    _, client = channel
    pods = benchmark_mix(1400, seed=7)
    provisioners = [make_provisioner()]
    seen = _capture(client, "_solve_classes")
    mine = client.solve_classes(pods, provisioners)
    theirs = client.solve_classes(pods, provisioners,
                                  members=list(loop_by_sig(pods).values()))
    assert seen[0] == seen[1]  # request bytes and reply bytes
    assert mine == theirs and not mine["failedPodIndices"]


def test_served_consolidate_answers_the_loops_bytes(channel, monkeypatch):
    """``consolidate-5k.sweep`` below its rehearsal size (24 nodes x 100
    types): the sweep's ``TPUSolver.encode`` classifies the candidates' bound
    pods through ``classify_pods``."""
    from benchmark.harness.podmix import seeded
    from benchmark.traffic.kinds import consolidate_cycle

    server, client = channel
    with open(os.path.join(REPO, "benchmark", "configs", "consolidate-5k.json")) as f:
        config = {**json.load(f), "existing_nodes": 24}
    cluster = consolidate_cycle.build_cluster(config, 7, instance_types(100), "default")
    consolidate_cycle.stamp_by_workload(cluster)
    nodes = consolidate_cycle.wire_nodes(cluster)
    order = consolidate_cycle.candidates_in_order(cluster, "default", seeded(7, "order0"))
    provisioners = [make_provisioner(consolidation_enabled=True)]
    seen = _capture(client, "_consolidate")
    classified = []
    real = snapshot_mod.classify_pods

    def through(which):
        def classify(pods):
            classified.append((which.__name__, len(pods)))
            return which(pods)
        return classify

    monkeypatch.setattr(snapshot_mod, "classify_pods", through(real))
    mine = client.consolidate(order, [], provisioners, nodes=nodes)
    monkeypatch.setattr(snapshot_mod, "classify_pods", through(loop_classify_pods))
    theirs = client.consolidate(order, [], provisioners, nodes=nodes)
    assert seen[0] == seen[1]
    assert mine == theirs and mine["action"] in ("delete", "replace", "do nothing")
    # both forms really classified the cluster's bound pods
    bound = sum(len(b) for _n, b in cluster)
    assert ("classify_pods", bound) in classified
    assert ("loop_classify_pods", bound) in classified
