"""``TPUSolver.encode_existing`` answers ``tol[c, e]`` and the bound pods'
group counts once per distinct pair of signatures (a node's taint set, a
class's toleration set, a bound pod's namespace + labels) and scatters the
answers.  These tests hold every plane it builds to the plain product loops,
and count the predicate calls so the product loops cannot come back."""

from __future__ import annotations

import numpy as np
import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    LabelSelector,
    LabelSelectorRequirement,
    PodAffinityTerm,
    Taint,
    Toleration,
    TopologySpreadConstraint,
)
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.snapshot import GroupScope
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.scheduling import Requirements, Taints
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.state.cluster import TAINT_NODE_NOT_READY, StateNode
from karpenter_core_tpu.testing import make_node, make_pod, make_provisioner
from karpenter_core_tpu.utils import resources as resources_util

ZONES = ("test-zone-1", "test-zone-2", "test-zone-3")


def reference_encode_existing(solver, snapshot, state_nodes, bound_pods=None):
    """The plain reference: the function as it stood before the signature
    tables — ``tolerates`` once per (class, node), ``matches_pod`` once per
    (bound pod, group) — kept here as the loop version the tables must equal
    on every plane."""
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.scheduling import Taints

    vocab = snapshot.vocab
    E = max(len(state_nodes), 1)
    C = len(snapshot.classes)
    R = len(snapshot.resources)
    Z = len(snapshot.zones)
    CT = len(snapshot.capacity_types)
    K, W = vocab.n_keys, vocab.width

    G1 = len(snapshot.groups) + 1
    used = np.zeros((E, R), dtype=np.float32)
    alloc = np.zeros((E, R), dtype=np.float32)
    kmask = np.ones((E, K, W), dtype=bool)
    kdef = np.zeros((E, K), dtype=bool)
    kneg = np.zeros((E, K), dtype=bool)
    kgt = np.full((E, K), -np.inf, dtype=np.float32)
    klt = np.full((E, K), np.inf, dtype=np.float32)
    zone = np.zeros((E, Z), dtype=bool)
    ct = np.zeros((E, CT), dtype=bool)
    pod_count = np.zeros(E, dtype=np.int32)
    open_ = np.zeros(E, dtype=bool)
    init = np.zeros(E, dtype=bool)
    tol = np.zeros((C, E), dtype=bool)
    P = len(snapshot.ports)
    ports = np.zeros((E, P), dtype=bool)
    grp_node_member = np.zeros((G1, E), dtype=np.int32)
    grp_node_owner = np.zeros((G1, E), dtype=np.int32)
    node_capacity = np.zeros((E, R), dtype=np.float32)
    node_tmpl = np.zeros(E, dtype=np.int32)
    node_owned = np.zeros(E, dtype=bool)
    port_idx = {key: i for i, key in enumerate(snapshot.ports)}
    tmpl_index = {t.provisioner_name: i for i, t in enumerate(solver.templates)}

    tmpl_by_name = {t.provisioner_name: t for t in solver.templates}
    zone_idx = {z: i for i, z in enumerate(snapshot.zones)}
    ct_idx = {c: i for i, c in enumerate(snapshot.capacity_types)}

    for e, state_node in enumerate(state_nodes):
        node = state_node.node
        available = state_node.available()
        for r, name in enumerate(snapshot.resources):
            alloc[e, r] = available.get(name, 0.0)
        template = tmpl_by_name.get(
            node.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY, "")
        )
        if template is not None and template.requests:
            remaining = resources_util.subtract(
                template.requests, state_node.daemon_set_requests()
            )
            for r, name in enumerate(snapshot.resources):
                used[e, r] = max(remaining.get(name, 0.0), 0.0)
        reqs = Requirements.from_labels(node.metadata.labels)
        kmask[e], kdef[e], kneg[e], kgt[e], klt[e] = vocab.encode_requirements(reqs)
        z = node.metadata.labels.get(labels_api.LABEL_TOPOLOGY_ZONE)
        if z is None:
            zone[e, :] = True  # unknown zone: any
        elif z in zone_idx:
            zone[e, zone_idx[z]] = True
        c_label = node.metadata.labels.get(labels_api.LABEL_CAPACITY_TYPE)
        if c_label is None:
            ct[e, :] = True
        elif c_label in ct_idx:
            ct[e, ct_idx[c_label]] = True
        open_[e] = True
        init[e] = state_node.initialized()
        capacity = state_node.capacity()
        for r, name in enumerate(snapshot.resources):
            node_capacity[e, r] = capacity.get(name, 0.0)
        t_idx = tmpl_index.get(
            node.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY, "")
        )
        if t_idx is not None:
            node_tmpl[e] = t_idx
            node_owned[e] = True
        taints = Taints.of(state_node.taints())
        for c, cls in enumerate(snapshot.classes):
            tol[c, e] = taints.tolerates(cls.pods[0]) is None

    # pre-existing pod counts per topology group (countDomains semantics,
    # topology.go:231-276): members (forward) and anti-term owners
    # (inverse); pods being scheduled this solve are excluded
    from karpenter_core_tpu.models.snapshot import (
        GRP_ANTI,
        UNLIMITED,
        _group_spec,
        term_namespaces,
    )

    node_index = {n.node.name: e for e, n in enumerate(state_nodes)}
    group_of = {spec: g for g, spec in enumerate(snapshot.groups)}
    scheduling_uids = {p.uid for cls in snapshot.classes for p in cls.pods}
    for pod in bound_pods or []:
        e = node_index.get(pod.spec.node_name)
        if e is None or pod.uid in scheduling_uids:
            continue
        from karpenter_core_tpu.models.snapshot import pod_port_keys as _ppk

        for key in _ppk(pod):
            i = port_idx.get(key)
            if i is not None:
                ports[e, i] = True
        for g, scope in enumerate(snapshot.group_selectors):
            if scope is not None and scope.matches_pod(pod):
                grp_node_member[g, e] += 1
        affinity = pod.spec.affinity
        if affinity is not None and affinity.pod_anti_affinity is not None:
            for term in affinity.pod_anti_affinity.required:
                try:
                    spec = _group_spec(
                        GRP_ANTI, term.topology_key, term.label_selector,
                        UNLIMITED, term_namespaces(pod, term),
                    )
                except Exception:  # noqa: BLE001 - unsupported keys don't track
                    continue
                g = group_of.get(spec)
                if g is not None:
                    grp_node_owner[g, e] += 1

    # -- volume attach-limit planes (volumeusage.go:33-236 as per-driver
    # counters; existingnode.go:77-130 enforcement).  Only existing nodes
    # carry limits (CSINode); the axis covers drivers mounted by a
    # scheduling class plus drivers already over their limit (which block
    # every add, volume-less pods included — VolumeCount.exceeds).
    from karpenter_core_tpu.models.snapshot import UNLIMITED

    class_volumes = snapshot.class_volumes or [
        {"shared": {}, "per_pod": {}} for _ in snapshot.classes
    ]
    drivers = sorted(
        {d for vols in class_volumes for d in vols["shared"]}
        | {d for vols in class_volumes for d in vols["per_pod"]}
    )
    for state_node in state_nodes:
        limits = state_node.volume_limits()
        mounted = state_node.volume_usage().volumes
        for d, lim in limits.items():
            if d not in drivers and len(mounted.get(d, ())) > lim:
                drivers.append(d)
    D = max(len(drivers), 1)
    vol_used = np.zeros((E, D), dtype=np.int32)
    vol_limit = np.full((E, D), UNLIMITED, dtype=np.int32)
    cls_vol_add = np.zeros((C, E, D), dtype=np.int32)
    cls_vol_per_pod = np.zeros((C, D), dtype=np.int32)
    for i, d in enumerate(drivers):
        for c, vols in enumerate(class_volumes):
            cls_vol_per_pod[c, i] = vols["per_pod"].get(d, 0)
    for e, state_node in enumerate(state_nodes):
        mounted = state_node.volume_usage().volumes
        limits = state_node.volume_limits()
        for i, d in enumerate(drivers):
            have = mounted.get(d, set())
            vol_used[e, i] = len(have)
            if d in limits:
                vol_limit[e, i] = limits[d]
            for c, vols in enumerate(class_volumes):
                new = vols["shared"].get(d)
                if new:
                    cls_vol_add[c, e, i] = len(new - have)

    # planes stay numpy: utils.compilecache bucket-pads them before the
    # device upload (ops/solve.pad_planes), so converting here would cost
    # an extra host→device round trip
    ex_state = solve_ops.ExistingState(
        used=np.asarray(used),
        kmask=np.asarray(kmask),
        kdef=np.asarray(kdef),
        kneg=np.asarray(kneg),
        kgt=np.asarray(kgt),
        klt=np.asarray(klt),
        zone=np.asarray(zone),
        ct=np.asarray(ct),
        ports=np.asarray(ports),
        vol_used=np.asarray(vol_used),
        pod_count=np.asarray(pod_count),
        open_=np.asarray(open_),
    )
    ex_static = solve_ops.ExistingStatic(
        alloc=np.asarray(alloc),
        init=np.asarray(init),
        tol=np.asarray(tol),
        grp_node_member=np.asarray(grp_node_member),
        grp_node_owner=np.asarray(grp_node_owner),
        node_capacity=np.asarray(node_capacity),
        node_tmpl=np.asarray(node_tmpl),
        node_owned=np.asarray(node_owned),
        vol_limit=np.asarray(vol_limit),
        cls_vol_add=np.asarray(cls_vol_add),
        cls_vol_per_pod=np.asarray(cls_vol_per_pod),
    )
    return ex_state, ex_static


# -- scenarios ----------------------------------------------------------------


def _solver(provisioners=None):
    provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(16))
    return TPUSolver(provider, provisioners or [make_provisioner()]), provider


def _state_node(
    provider, name, zone=ZONES[0], taints=(), bound=(), initialized=True,
    startup_taints=(), provisioner="default",
):
    it = provider.get_instance_types(None)[8]
    labels = {
        labels_api.PROVISIONER_NAME_LABEL_KEY: provisioner,
        labels_api.LABEL_INSTANCE_TYPE_STABLE: it.name,
        labels_api.LABEL_TOPOLOGY_ZONE: zone,
        labels_api.LABEL_CAPACITY_TYPE: "on-demand",
    }
    if initialized:
        labels[labels_api.LABEL_NODE_INITIALIZED] = "true"
    state_node = StateNode(
        make_node(
            name=name, labels=labels, taints=list(taints),
            allocatable=it.allocatable(), capacity=dict(it.capacity),
        )
    )
    state_node.startup_taints = list(startup_taints)
    for pod in bound:
        state_node.update_for_pod(pod)
    return state_node


def _bound(node_name, **kwargs):
    kwargs.setdefault("requests", {"cpu": "100m"})
    return make_pod(node_name=node_name, phase="Running", **kwargs)


def _spread(key, selector, skew=1):
    return TopologySpreadConstraint(
        max_skew=skew, topology_key=key, label_selector=selector
    )


def _app(value):
    return LabelSelector(match_labels={"app": value})


def _pending(app, n=3, **kwargs):
    """``n`` identical pending pods of one Deployment, spread over hostnames
    by their own label: one class, one group its bound twins are members of."""
    kwargs.setdefault("labels", {"app": app})
    kwargs.setdefault(
        "topology_spread", [_spread(labels_api.LABEL_HOSTNAME, _app(app))]
    )
    kwargs.setdefault("requests", {"cpu": "250m"})
    return [make_pod(**kwargs) for _ in range(n)]


def _no_taints():
    solver, provider = _solver()
    bound, nodes = [], []
    for i in range(6):
        here = [
            _bound(f"plain-{i}", labels={"app": ("a", "b")[j % 2]})
            for j in range(i % 4)
        ]
        bound += here
        nodes.append(_state_node(provider, f"plain-{i}", ZONES[i % 3], bound=here))
    pods = _pending("a") + _pending("b") + [make_pod(requests={"cpu": "1"})]
    return solver, pods, nodes, bound


def _node_pools():
    """Node pools with their own taints (one untainted); classes tolerating by
    Equal, by Exists, by an empty-key Exists, by effect, and not at all."""
    solver, provider = _solver()
    pools = [
        [Taint(key="team", value="ml", effect="NoSchedule")],
        [
            Taint(key="team", value="web", effect="NoSchedule"),
            Taint(key="spot", value="true", effect="NoExecute"),
        ],
        [],
        # the first pool's taint under another effect: only the effect differs
        [Taint(key="team", value="ml", effect="NoExecute")],
    ]
    nodes = [
        _state_node(provider, f"pool{i % 4}-{i}", ZONES[i % 3], taints=pools[i % 4])
        for i in range(12)
    ]
    tolerations = [
        [],
        [Toleration(key="team", operator="Equal", value="ml", effect="NoSchedule")],
        [Toleration(key="team", operator="Equal", value="web")],
        [Toleration(key="team", operator="Exists")],
        # the same key, value and effect as the one above: only the operator differs
        [Toleration(key="team", operator="Equal")],
        [Toleration(operator="Exists")],
        [Toleration(operator="Exists", effect="NoSchedule")],
        [
            Toleration(key="team", operator="Exists", effect="NoSchedule"),
            Toleration(key="spot", operator="Equal", value="true", effect="NoExecute"),
        ],
        # an empty key with Equal tolerates nothing
        [Toleration(operator="Equal", value="ml")],
    ]
    pods = []
    for i, tols in enumerate(tolerations):
        # two classes a toleration set: the sets repeat across classes
        pods += _pending(f"t{i}", n=2, tolerations=tols)
        pods += [make_pod(requests={"cpu": f"{i + 1}"}, tolerations=tols)]
    return solver, pods, nodes, []


def _all_distinct():
    """The degenerate case: every node its own taint set, every class its own
    toleration set, every bound pod its own labels."""
    solver, provider = _solver()
    bound, nodes = [], []
    for i in range(7):
        here = [_bound(f"own-{i}", labels={"app": "a", "pod": f"p{i}"})]
        bound += here
        nodes.append(
            _state_node(
                provider, f"own-{i}", ZONES[i % 3], bound=here,
                taints=[Taint(key="own", value=f"v{i}", effect="NoSchedule")],
            )
        )
    pods = []
    for i in range(7):
        pods += _pending(
            "a", n=1, requests={"cpu": f"{100 + i}m"},
            tolerations=[Toleration(key="own", operator="Equal", value=f"v{(i * 3) % 7}")],
        )
    return solver, pods, nodes, bound


def _ephemeral_and_startup():
    """state_node.taints() drops the not-ready taint everywhere and a start-up
    taint on an owned node that is not initialised yet — and only there."""
    startup = Taint(key="example.com/agent-not-ready", value="true", effect="NoSchedule")
    solver, provider = _solver([make_provisioner(startup_taints=[startup])])
    not_ready = Taint(key=TAINT_NODE_NOT_READY, effect="NoSchedule")
    nodes = [
        _state_node(provider, "ready", taints=[]),
        _state_node(provider, "not-ready", taints=[not_ready]),
        _state_node(
            provider, "starting", taints=[startup, not_ready], initialized=False,
            startup_taints=[startup],
        ),
        # initialised: the same taint is now an ordinary one
        _state_node(provider, "stuck", taints=[startup], startup_taints=[startup]),
        # not owned by a provisioner: nothing is filtered as start-up
        _state_node(
            provider, "foreign", taints=[startup], initialized=False,
            startup_taints=[startup], provisioner="",
        ),
    ]
    pods = _pending("a") + _pending(
        "b", tolerations=[Toleration(key=startup.key, operator="Exists")]
    )
    return solver, pods, nodes, []


def _namespaces_and_expressions():
    solver, provider = _solver()
    hostname = labels_api.LABEL_HOSTNAME

    def expr(key, operator, *values):
        return LabelSelector(
            match_expressions=[LabelSelectorRequirement(key, operator, list(values))]
        )

    pods = []
    for i, selector in enumerate(
        [
            expr("tier", "In", "web", "api"),
            expr("tier", "NotIn", "db"),
            expr("canary", "Exists"),
            expr("canary", "DoesNotExist"),
        ]
    ):
        pods += _pending(
            f"e{i}", n=2, labels={"app": f"e{i}", "tier": "web"},
            requests={"cpu": f"{200 + i}m"},
            topology_spread=[_spread(hostname, selector)],
        )
    # the same selector from two namespaces: two groups, told apart by scope
    pods += _pending("a", namespace="default") + _pending("a", namespace="other")
    pods += _pending(
        "zonal", topology_spread=[_spread(labels_api.LABEL_TOPOLOGY_ZONE, _app("zonal"))]
    )
    pods += [make_pod(labels={"app": "ported"}, host_ports=[8080])]
    in_batch = pods[0]

    label_sets = [
        {"app": "a"},
        {"app": "a", "tier": "web"},
        {"app": "e0", "tier": "api", "canary": "yes"},
        {"app": "e1", "tier": "db"},
        {"app": "zonal"},
        {},
    ]
    bound, nodes = [], []
    for i in range(5):
        here = [
            _bound(f"ns-{i}", labels=dict(labels), namespace=namespace)
            for j, labels in enumerate(label_sets)
            for namespace in ("default", "other")
            if (i + j) % 3 != 0
        ]
        if i == 1:
            here.append(_bound("ns-1", labels={"app": "ported"}, host_ports=[8080]))
            # an anti-affinity owner against the pending pods' label
            here.append(
                _bound(
                    "ns-1", labels={"app": "loner"},
                    pod_anti_affinity=[
                        PodAffinityTerm(topology_key=hostname, label_selector=_app("a"))
                    ],
                )
            )
        bound += here
        nodes.append(_state_node(provider, f"ns-{i}", ZONES[i % 3], bound=here))
    # a pod of the scheduling batch listed as bound, and one bound to a node
    # that was not shipped: neither counts
    in_batch.spec.node_name = "ns-0"
    bound.append(in_batch)
    bound.append(_bound("not-shipped", labels={"app": "a"}))
    return solver, pods, nodes, bound


def _one_node_no_bound_pods():
    solver, provider = _solver()
    nodes = [
        _state_node(
            provider, "only", taints=[Taint(key="team", value="ml", effect="NoSchedule")]
        )
    ]
    pods = _pending("a") + _pending(
        "b", tolerations=[Toleration(key="team", operator="Exists")]
    )
    return solver, pods, nodes, None


def _assert_no_taints(ex_state, ex_static):
    assert ex_static.tol.all() and ex_static.grp_node_member.any()


def _assert_node_pools(ex_state, ex_static):
    tol = ex_static.tol
    assert tol.any() and not tol.all()
    # the untainted pool takes every class; no two pools read alike
    assert tol[:, 2].all()
    assert len({tuple(tol[:, pool]) for pool in range(4)}) == 4


def _assert_all_distinct(ex_state, ex_static):
    assert (ex_static.tol.sum(axis=1) == 1).all()
    assert ex_static.grp_node_member.sum() == 7


def _assert_ephemeral(ex_state, ex_static):
    # columns: ready, not-ready, starting | stuck, foreign
    assert ex_static.tol[:, :3].all()
    assert ex_static.tol[:, 3:].any() and not ex_static.tol[:, 3:].all()


def _assert_one_node(ex_state, ex_static):
    assert ex_static.tol.any() and not ex_static.tol.all()
    assert not ex_static.grp_node_member.any()


def _assert_namespaces(ex_state, ex_static):
    members = ex_static.grp_node_member
    assert members.sum() > 0 and len({tuple(row) for row in members}) > 4
    assert ex_static.grp_node_owner.sum() == 1
    assert ex_state.ports.sum() == 1


SCENARIOS = {
    "no_taints": (_no_taints, _assert_no_taints),
    "node_pools": (_node_pools, _assert_node_pools),
    "all_distinct": (_all_distinct, _assert_all_distinct),
    "ephemeral_and_startup_taints": (_ephemeral_and_startup, _assert_ephemeral),
    "namespaces_and_expressions": (_namespaces_and_expressions, _assert_namespaces),
    "one_node_no_bound_pods": (_one_node_no_bound_pods, _assert_one_node),
}


@pytest.mark.parametrize("name", list(SCENARIOS))
def test_planes_equal_the_product_loops(name):
    build, assert_scenario = SCENARIOS[name]
    solver, pods, state_nodes, bound_pods = build()
    snapshot = solver.encode(pods, state_nodes, bound_pods)
    got = solver.encode_existing(snapshot, state_nodes, bound_pods)
    want = reference_encode_existing(solver, snapshot, state_nodes, bound_pods)
    for planes, ref_planes in zip(got, want):
        assert type(planes) is type(ref_planes)
        for field in planes._fields:
            a, b = getattr(planes, field), getattr(ref_planes, field)
            assert a.dtype == b.dtype and a.shape == b.shape, field
            assert np.array_equal(a, b), field
    # the scenario exercises what it says: the equality is not of empty planes
    assert_scenario(*got)


# -- the guard against the product loops coming back ---------------------------


def test_predicates_run_once_per_distinct_signature_pair(monkeypatch, traced):
    solver, provider = _solver()
    pools = [
        [Taint(key="team", value="ml", effect="NoSchedule")],
        [Taint(key="team", value="web", effect="NoSchedule")],
    ]
    tolerations = [
        [],
        [Toleration(key="team", operator="Equal", value="ml")],
        [Toleration(key="team", operator="Exists")],
    ]
    label_sets = [
        ("default", {"app": "c0"}),
        ("default", {"app": "c1"}),
        ("other", {"app": "c0"}),
        ("default", {"app": "c2", "tier": "web"}),
        ("default", {}),
    ]
    bound, nodes = [], []
    for i in range(200):
        here = []
        for j in range(5):
            namespace, labels = label_sets[(i + j) % 5]
            here.append(_bound(f"n-{i}", labels=dict(labels), namespace=namespace))
        bound += here
        nodes.append(
            _state_node(provider, f"n-{i}", ZONES[i % 3], taints=pools[i % 2], bound=here)
        )
    pods = []
    for c in range(30):
        pods += _pending(
            f"c{c}", n=2, requests={"cpu": f"{100 + c}m"}, tolerations=tolerations[c % 3]
        )
    snapshot = solver.encode(pods, nodes, bound)
    assert len(snapshot.classes) == 30 and len(bound) == 1000
    n_groups = len(snapshot.group_selectors)
    assert n_groups >= 30

    calls = {"tolerates": 0, "matches_pod": 0}
    tolerates, matches_pod = Taints.tolerates, GroupScope.matches_pod

    def counting_tolerates(self, pod):
        calls["tolerates"] += 1
        return tolerates(self, pod)

    def counting_matches_pod(self, pod):
        calls["matches_pod"] += 1
        return matches_pod(self, pod)

    monkeypatch.setattr(Taints, "tolerates", counting_tolerates)
    monkeypatch.setattr(GroupScope, "matches_pod", counting_matches_pod)
    prep = solver.prepare_encoded(snapshot, nodes, bound)
    assert 0 < calls["tolerates"] <= 2 * 3
    assert 0 < calls["matches_pod"] <= 5 * n_groups
    assert prep.ex_static is not None

    spans = [
        rec
        for trace in tracing.TRACE_STORE.last()
        for rec in trace.spans
        if rec["name"] == "encode.existing"
    ]
    assert len(spans) == 1
    attrs = spans[0]["attrs"]
    assert (attrs["state_nodes"], attrs["bound_pods"], attrs["classes"]) == (200, 1000, 30)
    assert (attrs["taint_sets"], attrs["toleration_sets"], attrs["pod_signatures"]) == (2, 3, 5)
