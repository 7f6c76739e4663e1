"""``drawn_reference`` — upstream's topology rules replayed over an answer —
on hand-built answers it must refuse and admit, then the system against it
and the host scheduler on small seeded mixes of the upstream suite AS DRAWN
(``upstream-suite-400-as-drawn``: spread pods select ``my-label``, which
generic pods carry; hostname self-affinity on the hostname key), a tiny case
where a generic class counts in a zone-spread group it does not own, and the
``encode.groups`` counters that say a mix has such members."""

import numpy as np
import pytest

from benchmark.harness import checks, manifest
from benchmark.harness.podmix import draw, seeded
from benchmark.traffic.kinds import drawn_reference as reference
from benchmark.traffic.kinds.cluster_cycle import by_workload

ZONE, HOSTNAME = "topology.kubernetes.io/zone", "kubernetes.io/hostname"
ZONES = ["test-zone-1", "test-zone-2", "test-zone-3"]
DRAWN = manifest.load_cell("suite-400-as-drawn.mixed").config
SUITE = manifest.load_cell("suite-400.mixed").config


def _pod(label: str, term: str = "", selects: str = "", cpu: str = "1", created: float = 0.0):
    """A pod labelled ``app=<label>``; ``term`` one of ``zone-spread``,
    ``host-spread``, ``zone-affinity``, ``host-affinity`` selecting
    ``app=<selects>``."""
    from karpenter_core_tpu.apis.objects import (
        LabelSelector,
        PodAffinityTerm,
        TopologySpreadConstraint,
    )
    from karpenter_core_tpu.testing import make_pod

    kw = {}
    if term:
        where, kind = term.split("-")
        key = ZONE if where == "zone" else HOSTNAME
        selector = LabelSelector(match_labels={"app": selects})
        if kind == "spread":
            kw["topology_spread"] = [TopologySpreadConstraint(
                max_skew=1, topology_key=key, label_selector=selector)]
        else:
            kw["pod_affinity"] = [PodAffinityTerm(topology_key=key, label_selector=selector)]
    return make_pod(labels={"app": label}, requests={"cpu": cpu},
                    creation_timestamp=created, **kw)


def _answer(*nodes) -> dict:
    """``nodes``: ``(zones, [pod index, ...])`` per new node."""
    return {"newNodes": [{"podIndices": list(idx), "zones": list(zones)}
                         for zones, idx in nodes]}


def _refused(pods, answer) -> list:
    return [i for i, _ in reference.violations(answer, pods, ZONES)]


# -- the rules, on hand-built answers ------------------------------------------


def test_a_hostname_spread_pod_beside_a_member_of_its_group_is_refused():
    pods = [_pod("web", cpu="2"), _pod("w", "host-spread", "web")]
    assert _refused(pods, _answer((ZONES[:1], [0, 1]))) == []  # one member: skew 1
    pods = [_pod("web", cpu="2"), _pod("web", "host-spread", "web")]
    assert _refused(pods, _answer((ZONES[:1], [0, 1]))) == [1]  # itself makes two
    assert _refused(pods, _answer((ZONES[:1], [0]), (ZONES[:1], [1]))) == []
    pods = [_pod("web", cpu="2"), _pod("web", cpu="2"), _pod("w", "host-spread", "web")]
    assert _refused(pods, _answer((ZONES[:1], [0, 1, 2]))) == [2]


def test_a_zone_spread_pod_in_a_zone_over_the_minimum_and_its_skew_is_refused():
    # x narrows its node to zone 1 first, so both tallies count the web pods
    pods = ([_pod("x", "zone-spread", "x", cpu="3")] + [_pod("web", cpu="2") for _ in range(2)]
            + [_pod("w", "zone-spread", "web")])
    # two members in zone 1, none in zones 2 and 3: zone 1 is over by one
    assert _refused(pods, _answer((ZONES[:1], [0, 1, 2, 3]))) == [3]
    assert _refused(pods, _answer((ZONES[:1], [0, 1, 2]), (ZONES[1:2], [3]))) == []
    # a node that may still launch in zone 1 or 2 is held in both
    assert _refused(pods, _answer((ZONES[:1], [0, 1, 2]), (ZONES[:2], [3]))) == [3]


def test_a_hostname_affinity_pod_away_from_every_match_is_refused():
    pods = [_pod("db", cpu="2"), _pod("db", "host-affinity", "db")]
    assert _refused(pods, _answer((ZONES[:1], [0]), (ZONES[:1], [1]))) == [1]
    assert _refused(pods, _answer((ZONES[:1], [0, 1]))) == []
    # no member anywhere: the first pod that selects itself bootstraps
    pods = [_pod("db", "host-affinity", "db", cpu="2"), _pod("db", "host-affinity", "db")]
    assert _refused(pods, _answer((ZONES[:1], [0]), (ZONES[:1], [1]))) == [1]
    assert _refused(pods, _answer((ZONES[:1], [0, 1]))) == []
    # a pod that does not select itself cannot bootstrap
    assert _refused([_pod("x", "host-affinity", "db")], _answer((ZONES[:1], [0]))) == [0]


def test_a_zone_affinity_pod_outside_its_members_zones_is_refused():
    pods = [_pod("db", "zone-affinity", "db", cpu="2"), _pod("db", "zone-affinity", "db")]
    assert _refused(pods, _answer((ZONES[:1], [0]), (ZONES[1:2], [1]))) == [1]
    assert _refused(pods, _answer((ZONES[:1], [0]), (ZONES[:1], [1]))) == []


def test_a_workload_stands_where_some_order_of_its_pods_does():
    # four replicas that select themselves, 2 + 1 + 1 over three zones: the
    # answer lists them zone 1 first, a scheduler may place them 1, 2, 3, 1
    pods = [_pod("w", "zone-spread", "w", created=1.0) for _ in range(4)]
    fair = _answer((ZONES[:1], [0, 1]), (ZONES[1:2], [2]), (ZONES[2:], [3]))
    assert _refused(pods, fair) == []
    lopsided = _answer((ZONES[:1], [0, 1, 2]), (ZONES[1:2], [3]))
    assert len(_refused(pods, lopsided)) == 2  # no order spares the 2nd and 3rd in zone 1
    # replicas are the pods that tie on cpu, memory AND creation time: the two
    # in zone 1, created first, come before the others can even the zones out
    for pod in pods[:2]:
        pod.metadata.creation_timestamp = 0.0
    assert _refused(pods, fair) == [1]


def test_a_placement_stands_where_either_tally_admits_it():
    # web lands on a node that may still launch anywhere; x narrows it to
    # zone 1 later.  As upstream counts, web is never in zone 1 (its record
    # took a node of three zones); as launched it is from the start.
    pods = [_pod("web", cpu="3"), _pod("x", "zone-spread", "x", cpu="2"),
            _pod("web", "zone-spread", "web", cpu="1")]
    answer = _answer((ZONES[:1], [0, 1]), (ZONES[:1], [2]))
    counts = reference._Counts(sorted({reference.rule_of(p) for p in pods[1:]}), ZONES)
    for i in (0, 1):
        counts.record(pods[i], reference.rule_of(pods[i]), 0, tuple(ZONES[:1]))
    web = reference.rule_of(pods[2])
    assert [tally[web]["test-zone-1"] for tally in counts.by_zone] == [0, 1]
    assert _refused(pods, answer) == []  # 0 + 1 - 0 as upstream counts; 1 + 1 - 0 as launched
    # a web pod with a zone term of its own narrows the node before it is counted
    pods.insert(1, _pod("web", "zone-spread", "x", cpu="2500m"))
    answer = _answer((ZONES[:1], [0, 1, 2]), (ZONES[:1], [3]))
    assert _refused(pods, answer) == [3]  # 1 + 1 - 0 as upstream counts, 2 + 1 - 0 as launched


# -- the system against the reference and the host -----------------------------


def _kernel_answer(pods, catalog, foreign: bool = True) -> dict:
    """The library's answer in ``solve_classes``' shape; ``foreign=False``
    solves as a kernel that counts no class in a group it does not own."""
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_provisioner

    solver = TPUSolver(FakeCloudProvider(catalog), [make_provisioner()])
    if not foreign:
        encode = solver.encode

        def owners_only(*args, **kwargs):
            snap = encode(*args, **kwargs)
            owned = np.zeros_like(snap.grp_member)
            owned[np.arange(owned.shape[0])[:, None], snap.cls_groups] = True
            snap.grp_member = snap.grp_member & owned
            return snap

        solver.encode = owners_only
    results = solver.solve(pods)
    index = {p.uid: i for i, p in enumerate(pods)}
    return {
        "newNodes": [{"podIndices": [index[p.uid] for p in n.pods], "zones": list(n.zones)}
                     for n in results.new_nodes],
        "existingAssignments": {},
        "failedPodIndices": [index[p.uid] for p in results.failed_pods],
        "residualPodIndices": [],
    }


def _drawn(n: int, seed: int) -> list:
    return by_workload(draw(n, seeded(seed, "test"), DRAWN["pod_mix"]))


@pytest.fixture(scope="module")
def catalog():
    from karpenter_core_tpu.cloudprovider.fake import instance_types

    return instance_types(100)


@pytest.mark.parametrize("n, seed", [(300, 1), (500, 2)])
def test_the_system_keeps_upstreams_rules_and_the_hosts_totals_with_a_fleet_no_larger(
        n, seed, catalog):
    from karpenter_core_tpu.testing import make_provisioner

    pods = _drawn(n, seed)
    answer = _kernel_answer(pods, catalog)
    assert reference.violations(answer, pods, reference.catalog_zones(catalog)) == []
    kernel = checks.counts(answer)
    host = checks.oracle_totals(pods, catalog, [make_provisioner()])
    assert (kernel["scheduled"], kernel["failed"]) == (host["scheduled"], host["failed"]) == (n, 0)
    assert 0 < kernel["nodes"] <= host["nodes"]


def _tiny() -> list:
    """A node committed to a zone by a spread of ``x``; four generic ``web``
    pods that join it; then three pods of a zone spread that selects ``web``
    and not itself: as many or more in ``web``'s zone would be refused."""
    return ([_pod("x", "zone-spread", "x", cpu="2", created=1.0)]
            + [_pod("web", cpu="250m", created=2.0) for _ in range(4)]
            + [_pod("watch", "zone-spread", "web", cpu="100m", created=3.0) for _ in range(3)])


def test_a_generic_class_counts_in_a_zone_spread_group_it_does_not_own(catalog):
    pods = _tiny()
    answer = _kernel_answer(pods, catalog)
    assert reference.violations(answer, pods, ZONES) == []
    web_zones = {tuple(n["zones"]) for n in answer["newNodes"]
                 if any(pods[i].metadata.labels["app"] == "web" for i in n["podIndices"])}
    watch_zones = {tuple(n["zones"]) for n in answer["newNodes"]
                   if any(pods[i].metadata.labels["app"] == "watch" for i in n["podIndices"])}
    assert len(web_zones) == 1 and not web_zones & watch_zones


@pytest.mark.parametrize("mix", ["tiny", "seeded"])
def test_a_kernel_that_ignores_foreign_members_counts_is_refused(mix, catalog):
    pods = _tiny() if mix == "tiny" else _drawn(500, 2)
    answer = _kernel_answer(pods, catalog, foreign=False)
    assert reference.violations(answer, pods, reference.catalog_zones(catalog))


# -- the counters on encode.groups ----------------------------------------------


@pytest.mark.parametrize("config, foreign, hostname_affinity", [
    (DRAWN, True, 7), (SUITE, False, 0)])
def test_encode_groups_counts_foreign_members_and_hostname_affinity_groups(
        traced, config, foreign, hostname_affinity, catalog):
    from karpenter_core_tpu import tracing
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_provisioner

    pods = by_workload(draw(700, seeded(3, "counters"), config["pod_mix"]))
    with tracing.span("test") as root:
        TPUSolver(FakeCloudProvider(catalog), [make_provisioner()]).encode(pods)
    (groups,) = [s for s in tracing.TRACE_STORE.find(root.trace_id).spans
                 if s["name"] == "encode.groups"]
    attrs = groups["attrs"]
    assert (attrs["foreign_members"] > 0) is foreign
    assert attrs["host_affinity_groups"] == hostname_affinity


def _host_answer(pods, catalog) -> dict:
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.solver.builder import build_scheduler
    from karpenter_core_tpu.testing import make_provisioner

    kube = KubeClient()
    kube.create(make_provisioner())
    results = build_scheduler(kube, FakeCloudProvider(catalog), cluster=None, pods=pods,
                              state_nodes=[], daemonset_pods=[]).solve(pods)
    index = {p.uid: i for i, p in enumerate(pods)}
    zone = labels_api.LABEL_TOPOLOGY_ZONE
    return {"newNodes": [
        {"podIndices": [index[p.uid] for p in n.pods],
         "zones": (n.template.requirements.get(zone).values_list()
                   if n.template.requirements.has(zone) else [])}
        for n in results.new_nodes]}


def test_the_hosts_extra_node_is_its_emptiest_first_order_not_a_rule(catalog):
    """PERF.md section 6, PR 40: two nodes pinned by a spread of ``s``, four
    replicas of a generic ``d``, then a hostname spread of ``e`` that selects
    ``d`` and not itself — it may join a node holding at most one ``d``.  The
    host gives each ``d`` to the emptiest node (two and two) and needs a third
    node; the kernel fills the first node with room (four and none)."""
    def pods():
        return ([_pod("s", "zone-spread", "s", cpu="2", created=1.0) for _ in range(2)]
                + [_pod("d", cpu="1", created=2.0) for _ in range(4)]
                + [_pod("e", "host-spread", "d", cpu="500m", created=3.0)])

    kernel_pods, host_pods = pods(), pods()
    kernel, host = _kernel_answer(kernel_pods, catalog), _host_answer(host_pods, catalog)
    assert reference.violations(kernel, kernel_pods, ZONES) == []
    assert reference.violations(host, host_pods, ZONES) == []
    d_counts = sorted(sum(host_pods[i].metadata.labels["app"] == "d" for i in n["podIndices"])
                      for n in host["newNodes"])
    assert (len(kernel["newNodes"]), len(host["newNodes"]), d_counts) == (2, 3, [0, 2, 2])
