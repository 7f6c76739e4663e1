"""Topology spread on karpenter.sh/capacity-type in the kernel
(ops/solve.py ``kc.phase.ct_spread``): parity with the host scheduler, the
port of upstream's, on seeded Deployments at small sizes.

A Deployment's pods spread with maxSkew 1 on capacity type alone, on zone and
capacity type, or on zone, hostname and capacity type, beside generic
Deployments; on the assorted catalog every pod selects an os and some select
arm64.  Scheduled pods, failed pods and new nodes equal the host's — the
kernel gives open nodes their pods in the order of upstream's own list
(``NodeState.order``); every new node that holds a capacity-type (zone)
spread pod lists one capacity type (zone), and each Deployment's counts stay
within maxSkew over the catalog's domains.
"""

import collections
import random

import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.cloudprovider import Offering
from karpenter_core_tpu.models.snapshot import KernelUnsupported, classify_pods
from karpenter_core_tpu.operator.kubeclient import KubeClient
from karpenter_core_tpu.solver.builder import build_scheduler
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_node, make_pod, make_pods, make_provisioner
from karpenter_core_tpu.testing.harness import expect_provisioned, make_environment

pytestmark = pytest.mark.compile

CT = labels_api.LABEL_CAPACITY_TYPE
ZONE = labels_api.LABEL_TOPOLOGY_ZONE
HOSTNAME = labels_api.LABEL_HOSTNAME
OS = labels_api.LABEL_OS_STABLE
ARCH = labels_api.LABEL_ARCH_STABLE
KEYS = {"ct": (CT,), "zone-ct": (ZONE, CT), "zone-host-ct": (ZONE, HOSTNAME, CT)}
DOMAINS = {CT: 2, ZONE: 3}


def incrementing_every_pair(n: int) -> list:
    """The incrementing catalog (i vcpu, 2i Gi) with every type offered in
    both capacity types in every zone: a type fixes neither."""
    out = []
    for it in fake_cp.instance_types(n):
        price = it.offerings[0].price
        offerings = [Offering(ct, zone, price)
                     for zone in ("test-zone-1", "test-zone-2", "test-zone-3")
                     for ct in ("spot", "on-demand")]
        out.append(fake_cp.new_instance_type(it.name, resources=dict(it.capacity),
                                             offerings=offerings))
    return out


CATALOGS = {
    "assorted": fake_cp.instance_types_assorted,
    # every type offers spot in two zones and on-demand in three: a node that
    # commits to zone 3 cannot be spot
    "incrementing": lambda: fake_cp.instance_types(12),
    "incrementing-every-pair": lambda: incrementing_every_pair(12),
}


def deployments(seed: int, catalog: str, keys: tuple) -> list:
    """Two to five Deployments, the first spreading on ``keys``, the rest on
    ``keys`` or nothing; a fresh list of pods each call."""
    rng = random.Random(seed)
    pods = []
    for d in range(rng.randint(2, 5)):
        spread_on = keys if d == 0 or rng.random() < 0.6 else ()
        selector = LabelSelector(match_labels={"name": f"d{d}"})
        node_selector = {}
        if catalog == "assorted":
            node_selector[OS] = rng.choice(["linux", "linux", "windows"])
            if rng.random() < 0.3:
                node_selector[ARCH] = labels_api.ARCHITECTURE_ARM64
        requests = {"cpu": rng.choice(["100m", "500m", "1", "1500m"]),
                    "memory": rng.choice(["100Mi", "1Gi", "2Gi"])}
        pods += make_pods(
            rng.randint(1, 13), labels={"name": f"d{d}"}, requests=requests,
            node_selector=node_selector, creation_timestamp=float(d),
            topology_spread=[TopologySpreadConstraint(max_skew=1, topology_key=k,
                                                      label_selector=selector)
                             for k in spread_on],
        )
    return pods


def host_solve(pods, instance_types):
    kube = KubeClient()
    kube.create(make_provisioner())
    return build_scheduler(
        kube, fake_cp.FakeCloudProvider(instance_types), cluster=None, pods=pods,
        state_nodes=[], daemonset_pods=[],
    ).solve(pods)


def spread_counts(placements) -> dict:
    """``{(deployment, key): Counter(domain)}`` over ``(pod, zones, cts)``
    rows; asserts each spread pod's node is pinned on the keys it spreads."""
    counts = collections.defaultdict(collections.Counter)
    for pod, zones, cts in placements:
        for c in pod.spec.topology_spread_constraints:
            domains = {ZONE: zones, CT: cts}.get(c.topology_key)
            if domains is None:
                continue
            assert len(domains) == 1, (pod.metadata.labels, c.topology_key, domains)
            counts[pod.metadata.labels["name"], c.topology_key][domains[0]] += 1
    return counts


def assert_skew(counts: dict) -> None:
    for (name, key), seen in counts.items():
        values = list(seen.values()) + [0] * (DOMAINS[key] - len(seen))
        assert max(values) - min(values) <= 1, (name, key, dict(seen))


def kernel_placements(result) -> list:
    return [(p, n.zones, n.capacity_types) for n in result.new_nodes for p in n.pods]


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("keys", sorted(KEYS))
@pytest.mark.parametrize("catalog", sorted(CATALOGS))
def test_kernel_matches_host(catalog, keys, seed):
    instance_types = CATALOGS[catalog]()
    host = host_solve(deployments(seed, catalog, KEYS[keys]), instance_types)
    solver = TPUSolver(fake_cp.FakeCloudProvider(instance_types), [make_provisioner()])
    snapshot = solver.encode(deployments(seed, catalog, KEYS[keys]))
    assert snapshot.features.ct_spread
    tpu = solver.solve_encoded(snapshot)
    scheduled = [sum(len(n.pods) for n in r.new_nodes) for r in (tpu, host)]
    assert scheduled[0] == scheduled[1]
    assert len(tpu.failed_pods) == len(host.failed_pods)
    assert not tpu.spread_residual_pods
    assert len(tpu.new_nodes) == len(host.new_nodes)
    assert_skew(spread_counts(kernel_placements(tpu)))
    assert tpu.ct_committed_nodes == sum(
        any(p.spec.topology_spread_constraints and any(
            c.topology_key == CT for c in p.spec.topology_spread_constraints) for p in n.pods)
        for n in tpu.new_nodes
    )


@pytest.mark.parametrize("catalog", sorted(CATALOGS))
def test_every_type_a_node_lists_offers_its_pair(catalog):
    instance_types = CATALOGS[catalog]()
    by_name = {it.name: it for it in instance_types}
    solver = TPUSolver(fake_cp.FakeCloudProvider(instance_types), [make_provisioner()])
    result = solver.solve(deployments(7, catalog, KEYS["zone-ct"]))
    pinned = [n for n in result.new_nodes if len(n.capacity_types) == 1 and len(n.zones) == 1]
    assert pinned
    for node in pinned:
        for name in node.instance_type_names:
            assert any(o.zone == node.zones[0] and o.capacity_type == node.capacity_types[0]
                       for o in by_name[name].offerings.available()), name


def test_windows_and_arm64_selectors_hold():
    catalog = fake_cp.instance_types_assorted()
    by_name = {it.name: it for it in catalog}
    pods = (make_pods(6, labels={"name": "w"}, requests={"cpu": "500m"},
                      node_selector={OS: "windows"},
                      topology_spread=[TopologySpreadConstraint(
                          max_skew=1, topology_key=CT,
                          label_selector=LabelSelector(match_labels={"name": "w"}))])
            + make_pods(5, labels={"name": "a"}, requests={"cpu": "500m"},
                        node_selector={OS: "linux", ARCH: labels_api.ARCHITECTURE_ARM64}))
    result = TPUSolver(fake_cp.FakeCloudProvider(catalog), [make_provisioner()]).solve(pods)
    assert not result.failed_pods
    for node in result.new_nodes:
        wanted = {(OS, p.spec.node_selector[OS]) for p in node.pods}
        wanted |= {(ARCH, p.spec.node_selector[ARCH]) for p in node.pods
                   if ARCH in p.spec.node_selector}
        for name in node.instance_type_names:
            reqs = by_name[name].requirements
            for key, value in wanted:
                assert reqs.get(key).has(value), (name, key, value)


def existing_node(env, ct: str, zone: str, cpu: int = 8):
    node = make_node(
        labels={
            labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
            labels_api.LABEL_INSTANCE_TYPE_STABLE: "fake-it-7",
            CT: ct, ZONE: zone,
            labels_api.LABEL_NODE_INITIALIZED: "true",
        },
        allocatable={"cpu": cpu, "memory": "16Gi", "pods": 10},
    )
    env.kube.create(node)
    return node


@pytest.mark.parametrize("keys", ["ct", "zone-ct"])
def test_existing_nodes_of_both_capacity_types_count_their_members(keys):
    """Bound members on spot and on-demand nodes seed the counts: the host
    and the kernel place the new replicas on the same capacity types."""
    catalog = incrementing_every_pair(12)

    def cluster():
        env = make_environment(instance_types=catalog)
        env.kube.create(make_provisioner())
        spot = existing_node(env, "spot", "test-zone-1")
        on_demand = existing_node(env, "on-demand", "test-zone-2", cpu=1)
        for node, n in ((spot, 3), (on_demand, 1)):
            for _ in range(n):
                env.kube.create(make_pod(labels={"name": "web"}, requests={"cpu": "100m"},
                                         node_name=node.name, unschedulable=False))
        return env

    def pending():
        selector = LabelSelector(match_labels={"name": "web"})
        return make_pods(7, labels={"name": "web"}, requests={"cpu": "500m"},
                         topology_spread=[TopologySpreadConstraint(
                             max_skew=1, topology_key=k, label_selector=selector)
                             for k in KEYS[keys]])

    env_h = cluster()
    pods = pending()
    host = build_scheduler(env_h.kube, env_h.provider, cluster=env_h.cluster, pods=pods,
                           state_nodes=env_h.cluster.snapshot_nodes(),
                           daemonset_pods=[]).solve(pods)
    env_t = cluster()
    tpu = TPUSolver(env_t.provider, env_t.kube.list_provisioners()).solve(
        pending(), state_nodes=env_t.cluster.snapshot_nodes(),
        bound_pods=env_t.kube.list_pods())

    def by_ct(env, new_nodes, existing):
        out = collections.Counter()
        for node in new_nodes:
            cts = (node.capacity_types if hasattr(node, "capacity_types")
                   else node.requirements.get(CT).values_list())
            out[cts[0] if len(cts) == 1 else None] += len(node.pods)
        for name, placed in existing.items():
            out[env.kube.get_node(name).metadata.labels[CT]] += len(placed)
        return out

    counts = by_ct(env_t, tpu.new_nodes, tpu.existing_assignments)
    host_existing = {n.name: n.pods for n in host.existing_nodes if n.pods}
    assert counts == by_ct(env_h, host.new_nodes, host_existing)
    # 3 spot + 1 on-demand bound: the 7 new replicas end 5 | 6 at most 1 apart
    assert abs((counts["spot"] + 3) - (counts["on-demand"] + 1)) <= 1
    assert not tpu.failed_pods and not host.failed_pods


def test_hostname_zone_and_ct_rounds_through_the_kernel(monkeypatch):
    """test_topology_matrix2's rounds (topology_test.go:993-1030) with the
    controller on the kernel path and the host scheduler forbidden: nine
    rounds, each onto the nodes and bound pods the earlier ones left, every
    key's max skew held each round."""
    from karpenter_core_tpu.solver import scheduler as scheduler_mod

    monkeypatch.setenv("KC_SOLVER_INCREMENTAL", "0")

    def host_path(self, pods):
        raise AssertionError("the batch left the kernel")

    monkeypatch.setattr(scheduler_mod.Scheduler, "solve", host_path)
    labels = {"test": "test"}
    env = make_environment(instance_types=fake_cp.instance_types_assorted())
    env.kube.create(make_provisioner())
    env.provisioning.use_tpu_kernel = True
    env.provisioning.tpu_kernel_min_pods = 1
    selector = LabelSelector(match_labels=labels)
    topos = [TopologySpreadConstraint(max_skew=skew, topology_key=key, label_selector=selector)
             for key, skew in ((CT, 1), (ZONE, 2), (HOSTNAME, 3))]

    def skew(key):
        counts = collections.Counter()
        for pod in env.kube.list_pods():
            if pod.spec.node_name and pod.metadata.labels == labels:
                domain = env.kube.get_node(pod.spec.node_name).metadata.labels.get(key)
                if domain is not None:
                    counts[domain] += 1
        return max(counts.values()) - min(counts.values()) if counts else 0

    for i in range(1, 10):
        pods = make_pods(i, labels=labels, requests={"cpu": "10m"}, topology_spread=list(topos))
        result = expect_provisioned(env, *pods)
        assert all(result[p.uid] is not None for p in pods)
        assert skew(CT) <= 1 and skew(ZONE) <= 2 and skew(HOSTNAME) <= 3


def test_repair_and_consolidation_paths_refuse():
    """The tenant/incremental session and the consolidation sweep do not keep
    the counts the family reads: both raise KernelUnsupported, as for any
    shape the kernel does not serve."""
    from types import SimpleNamespace

    from karpenter_core_tpu.solver.consolidation import TPUConsolidationSearch
    from karpenter_core_tpu.solver.incremental import IncrementalSolveSession

    catalog = incrementing_every_pair(8)
    provider = fake_cp.FakeCloudProvider(catalog)
    pods = deployments(3, "incrementing", KEYS["ct"])
    session = IncrementalSolveSession()
    session.rebind(TPUSolver(provider, [make_provisioner()]))
    with pytest.raises(KernelUnsupported, match="capacity-type"):
        session.solve(classify_pods(pods))

    env = make_environment(instance_types=catalog)
    env.kube.create(make_provisioner())
    node = existing_node(env, "spot", "test-zone-1")
    bound = make_pod(labels={"name": "d0"}, requests={"cpu": "100m"}, node_name=node.name,
                     unschedulable=False,
                     topology_spread=[TopologySpreadConstraint(
                         max_skew=1, topology_key=CT,
                         label_selector=LabelSelector(match_labels={"name": "d0"}))])
    env.kube.create(bound)
    state_nodes = env.cluster.snapshot_nodes()
    search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
    candidate = SimpleNamespace(node=state_nodes[0], pods=[bound])
    with pytest.raises(KernelUnsupported, match="capacity-type"):
        search.compute_command([candidate], [], state_nodes, env.kube.list_pods())


def test_affinity_on_capacity_type_still_routes_to_the_host():
    from karpenter_core_tpu.apis.objects import PodAffinityTerm

    pod = make_pod(labels={"name": "x"}, pod_anti_affinity=[PodAffinityTerm(
        topology_key=CT, label_selector=LabelSelector(match_labels={"name": "x"}))])
    with pytest.raises(KernelUnsupported, match="capacity-type"):
        classify_pods([pod])


@pytest.mark.parametrize("keys", [(ZONE,), (ZONE, HOSTNAME), ()])
def test_a_program_with_the_family_serves_other_batches_as_before(keys):
    """The flag only widens: an executable traced with ``ct_spread`` (a
    superset the compile cache may hand any later batch) places a batch
    without a capacity-type spread exactly as the program without it."""
    import numpy as np

    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.utils import compilecache

    solver = TPUSolver(fake_cp.FakeCloudProvider(fake_cp.instance_types(12)),
                       [make_provisioner()])
    snapshot = solver.encode(deployments(5, "incrementing", keys))
    assert not snapshot.features.ct_spread
    outputs = []
    for widened in (False, True):
        compilecache.reset_memo()
        snapshot.features = snapshot.features._replace(ct_spread=widened)
        outputs.append(solve_ops.sync_outputs(solve_ops.solve(snapshot)))
    compilecache.reset_memo()
    for field in ("assign", "failed"):
        np.testing.assert_array_equal(*(np.asarray(getattr(o, field)) for o in outputs))
    np.testing.assert_array_equal(*(np.asarray(o.state.zone) for o in outputs))
