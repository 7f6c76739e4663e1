"""The scan step reads and records the topology planes BY ROW.

``ops.solve._class_step`` gathers the rows of ``TopoCounts`` a class owns
(``cls.groups``) or is a member of (``cls.member_idx``) and adds a class's
placements into those rows alone; it never builds or rewrites a ``[G1, .]``
plane.  The record is linear, so the four final planes have a closed form in
the solve's own assignments — held here array for array on fuzzed snapshots
of every constraint family, next to the host oracle's verdict on the same
batches, the dummy row's zero, the member list's round trip through
``pad_planes``, the all-members corner (M = G) and the windowed warm repair
over zone groups.
"""

import copy
import random
from collections import Counter

import numpy as np
import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    LabelSelector,
    LabelSelectorRequirement,
    PodAffinityTerm,
    TopologySpreadConstraint,
    WeightedPodAffinityTerm,
    new_uid,
)
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.solver.builder import build_scheduler
from karpenter_core_tpu.solver.incremental import (
    MODE_DELTA,
    FallbackPolicy,
    IncrementalSolveSession,
    node_signature_of,
)
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_node, make_pod, make_provisioner
from karpenter_core_tpu.testing.harness import make_environment

pytestmark = pytest.mark.compile  # every case compiles a solve shape

ZONE = labels_api.LABEL_TOPOLOGY_ZONE
HOSTNAME = labels_api.LABEL_HOSTNAME
ZONES = ("test-zone-1", "test-zone-2", "test-zone-3")
SIZES = ({"cpu": "100m"}, {"cpu": "500m"}, {"cpu": 1}, {"cpu": "250m", "memory": "512Mi"})

# (family, topology key): what one fuzzed class owns, always on its own label
FAMILIES = (
    ("generic", None),
    ("spread", ZONE), ("spread", HOSTNAME),
    ("affinity", ZONE), ("affinity", HOSTNAME),
    ("anti", ZONE), ("anti", HOSTNAME),
    ("soft_anti", ZONE), ("soft_anti", HOSTNAME),
)
SEEDS = tuple(range(18))
# the step goes by row where 4 x M < G1 (ops.solve._class_step): with M at
# its floor of 8 that takes 32 groups, which these one-pod classes supply
FILLER_GROUPS = 34


def filler_pods(n: int = FILLER_GROUPS):
    """``n`` one-pod classes, each owning a hostname spread on its own label:
    groups that change no other class's answer."""
    return [
        make_pod(
            labels={"app": f"rows-fill-{i}"}, requests={"cpu": "100m"},
            topology_spread=[TopologySpreadConstraint(
                max_skew=1, topology_key=HOSTNAME,
                label_selector=LabelSelector(match_labels={"app": f"rows-fill-{i}"}))],
        )
        for i in range(n)
    ]


def goes_by_row(prep) -> bool:
    g1 = np.shape(solve_ops.StaticArrays(*prep.statics_arrays).grp_skew)[0]
    return solve_ops.step_goes_by_row(np.shape(prep.cls.member_idx)[1], g1)


def test_the_rule_is_short_lists_beside_many_groups():
    assert not solve_ops.step_goes_by_row(8, 25)  # five of the six cells
    assert solve_ops.step_goes_by_row(8, 33) and solve_ops.step_goes_by_row(8, 4097)
    assert solve_ops.step_goes_by_row(12, 49) and not solve_ops.step_goes_by_row(12, 48)
    assert not solve_ops.step_goes_by_row(0, 4097)  # no lists kept


def fuzz_class(rng: random.Random, index: int, family: str, key, shared: bool):
    labels = {"app": f"rows-{index}"}
    if shared:
        labels["tier"] = "shared"
    selector = LabelSelector(match_labels={"app": labels["app"]})
    kwargs = dict(labels=labels, requests=rng.choice(SIZES))
    if family == "spread":
        kwargs["topology_spread"] = [TopologySpreadConstraint(
            max_skew=rng.choice((1, 2)), topology_key=key, label_selector=selector)]
    elif family == "affinity":
        kwargs["pod_affinity"] = [PodAffinityTerm(topology_key=key, label_selector=selector)]
    elif family == "anti":
        kwargs["pod_anti_affinity"] = [PodAffinityTerm(topology_key=key, label_selector=selector)]
    elif family == "soft_anti":
        kwargs["pod_anti_affinity_preferred"] = [WeightedPodAffinityTerm(
            weight=10, pod_affinity_term=PodAffinityTerm(topology_key=key, label_selector=selector))]
    return [make_pod(**kwargs) for _ in range(rng.randrange(1, 7))]


def fuzz_batch(seed: int):
    """Pending pods of 5–8 classes: the seed's own family first (so the
    eighteen seeds reach every family twice), the rest drawn; odd seeds put a
    ``tier`` label on every class and let one hostname spread select it, so
    every class is a member of a group it does not own (member count 2); the
    upper nine seeds add ``FILLER_GROUPS`` groups, so their steps go by row."""
    rng = random.Random(seed)
    shared = seed % 2 == 1
    families = [FAMILIES[seed % len(FAMILIES)]] + [
        rng.choice(FAMILIES) for _ in range(rng.randrange(4, 8))
    ]
    pods = []
    for index, (family, key) in enumerate(families):
        pods.extend(fuzz_class(rng, index, family, key, shared))
    if shared:
        pods.extend(make_pod(
            labels={"app": "rows-wide", "tier": "shared"}, requests={"cpu": "100m"},
            topology_spread=[TopologySpreadConstraint(
                max_skew=4, topology_key=HOSTNAME,
                label_selector=LabelSelector(match_labels={"tier": "shared"}))],
        ) for _ in range(3))
    if seed >= len(FAMILIES):
        pods.extend(filler_pods())
    rng.shuffle(pods)
    return pods


def fuzz_environment(seed: int):
    """Seeds divisible by three get a live cluster: a node a zone, on each a
    bound MEMBER of the first pending class and, on the first, a bound
    hostname-anti OWNER whose term selects that class."""
    env = make_environment()
    env.kube.create(make_provisioner())
    if seed % 3:
        return env
    for i, zone in enumerate(ZONES):
        node = make_node(
            name=f"rows-ex-{i}",
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                labels_api.LABEL_CAPACITY_TYPE: "spot",
                labels_api.LABEL_NODE_INITIALIZED: "true",
                ZONE: zone,
            },
            allocatable={"cpu": 4, "memory": "4Gi", "pods": 10},
        )
        env.kube.create(node)
        env.kube.create(make_pod(
            labels={"app": "rows-0"}, requests={"cpu": "100m"},
            node_name=node.name, unschedulable=False,
        ))
        if i == 0:
            env.kube.create(make_pod(
                labels={"app": "rows-guard"}, requests={"cpu": "100m"},
                node_name=node.name, unschedulable=False,
                pod_anti_affinity=[PodAffinityTerm(
                    topology_key=HOSTNAME,
                    label_selector=LabelSelector(match_labels={"app": "rows-0"}))],
            ))
    return env


def raw_solve(env, pods, pad: bool = True, monkeypatch=None):
    """(snapshot, SolvePrep, SolveOutputs on the host) through TPUSolver's
    prepare / run split — the planes as the kernel saw and left them."""
    import jax

    if not pad:
        monkeypatch.setenv("KC_TPU_SHAPE_BUCKETS", "0")
    solver = TPUSolver(env.provider, env.kube.list_provisioners())
    state_nodes, bound = env.cluster.snapshot_nodes(), env.kube.list_pods()
    snapshot = solver.encode(pods, state_nodes, bound)
    prep = solver.prepare_encoded(snapshot, state_nodes, bound)
    outputs, _ = solver.grow_until_fits(prep, solver.run_prepared(prep))
    return snapshot, prep, jax.device_get(outputs)


def closed_form(prep, outputs):
    """The four planes from the answer alone: every placed pod adds its
    class's membership (forward) and required anti ownership (inverse) to its
    node's column; existing nodes start from their bound pods' seeds."""
    cls, sa = prep.cls, solve_ops.StaticArrays(*prep.statics_arrays)
    member = np.asarray(sa.grp_member).astype(np.int64)  # [C, G1]
    g1 = member.shape[1]
    groups, soft = np.asarray(cls.groups), np.asarray(cls.anti_soft)
    own_inv = np.zeros_like(member)
    for slot, col in ((4, 0), (5, 1)):
        g = groups[:, slot]
        rows = np.flatnonzero((g < g1 - 1) & ~soft[:, col])
        np.add.at(own_inv, (rows, g[rows]), 1)
    assign = np.asarray(outputs.assign).astype(np.int64)
    assign_ex = np.asarray(outputs.assign_existing).astype(np.int64)
    if prep.ex_static is None:
        seed_fwd = seed_inv = np.zeros((g1, assign_ex.shape[1]), np.int64)
    else:
        open_ = np.asarray(prep.ex_state.open_).astype(np.int64)[None, :]
        seed_fwd = np.asarray(prep.ex_static.grp_node_member) * open_
        seed_inv = np.asarray(prep.ex_static.grp_node_owner) * open_
    return solve_ops.TopoCounts(
        fwd_ex=seed_fwd + member.T @ assign_ex,
        inv_ex=seed_inv + own_inv.T @ assign_ex,
        fwd_new=member.T @ assign,
        inv_new=own_inv.T @ assign,
    )


@pytest.mark.parametrize("seed", SEEDS)
def test_planes_equal_their_closed_form(seed):
    env = fuzz_environment(seed)
    _, prep, outputs = raw_solve(env, fuzz_batch(seed))
    assert goes_by_row(prep) == (seed >= len(FAMILIES))
    want = closed_form(prep, outputs)
    for name in solve_ops.TopoCounts._fields:
        got = np.asarray(getattr(outputs.topo, name))
        assert got.dtype == np.int32
        np.testing.assert_array_equal(got, getattr(want, name), err_msg=f"seed {seed} {name}")
    # the dummy row is read by every class that lacks a slot: it stays zero
    for name in solve_ops.TopoCounts._fields:
        assert not np.asarray(getattr(outputs.topo, name))[-1].any(), name


def test_fuzz_reaches_every_family():
    """The seeds above are only worth their name if those that go by row —
    the lower nine repeat the families on the whole-plane form — together
    trace every phase family, two scan passes, a preference ladder, real
    existing planes and a class in more groups than it owns."""
    features, passes, ladders, existing, members = set(), set(), 0, 0, 0
    for seed in SEEDS[len(FAMILIES):]:  # the seeds whose steps go by row
        env = fuzz_environment(seed)
        solver = TPUSolver(env.provider, env.kube.list_provisioners())
        state_nodes, bound = env.cluster.snapshot_nodes(), env.kube.list_pods()
        snapshot = solver.encode(fuzz_batch(seed), state_nodes, bound)
        f = solve_ops.snapshot_features(snapshot)
        features |= {name for name, on in zip(f._fields, f) if on}
        passes.add(snapshot.scan_passes)
        ladders += int((np.asarray(snapshot.cls_relax_next) >= 0).any())
        existing += bool(state_nodes)
        members = max(members, int(np.asarray(snapshot.grp_member).sum(axis=1).max()))
    assert features >= {
        "zone_spread", "host_spread", "zone_affinity", "host_affinity", "zone_anti",
        "required_zone_anti", "host_anti", "inv_zone_anti", "inv_host_anti",
    }
    assert max(passes) >= 2 and ladders and existing and members >= 2


def per_class(pods, scheduled_uids):
    return Counter(p.metadata.labels["app"] for p in pods if p.uid in scheduled_uids)


@pytest.mark.parametrize("seed", SEEDS[::2])
def test_fuzzed_batches_match_the_host_oracle(seed):
    """Scheduled per class as ``test_parity_fuzz`` holds the two engines:
    equal, but never fewer than the host for required zonal anti (the kernel
    reaches the fixpoint in batch one) and schedulable-iff for hostname
    self-affinity (which node a group pins is packing luck).  Preferred anti
    terms relax down their ladder on both sides.  The even seeds only: where
    a foreign selector counts every class (odd seeds) which pods a shared
    hostname cap turns away follows queue order on the host and scan order in
    the kernel (PERF.md §7, the mix's departures) — those are held by the
    closed form above."""
    env = fuzz_environment(seed)
    pods = fuzz_batch(seed)
    state_nodes, bound = env.cluster.snapshot_nodes(), env.kube.list_pods()
    host = build_scheduler(
        env.kube, env.provider, env.cluster, copy.deepcopy(pods), state_nodes, daemonset_pods=[]
    ).solve(pods)
    tpu = TPUSolver(env.provider, env.kube.list_provisioners()).solve(
        pods, state_nodes=state_nodes, bound_pods=bound
    )
    host_failed = {p.uid for p in host.failed_pods}
    tpu_failed = {p.uid for p in tpu.failed_pods}
    uids = {p.uid for p in pods}
    host_by, tpu_by = per_class(pods, uids - host_failed), per_class(pods, uids - tpu_failed)
    anti_zone, host_aff = set(), set()
    for pod in pods:
        affinity = pod.spec.affinity
        if affinity is None:
            continue
        if affinity.pod_anti_affinity is not None and any(
            t.topology_key == ZONE for t in affinity.pod_anti_affinity.required
        ):
            anti_zone.add(pod.metadata.labels["app"])
        if affinity.pod_affinity is not None and any(
            t.topology_key == HOSTNAME for t in affinity.pod_affinity.required
        ):
            host_aff.add(pod.metadata.labels["app"])
    for app in set(host_by) | set(tpu_by):
        if app in anti_zone:
            assert tpu_by[app] >= host_by[app], (seed, app, tpu_by, host_by)
        elif app in host_aff:
            assert (tpu_by[app] > 0) == (host_by[app] > 0), (seed, app, tpu_by, host_by)
        else:
            assert tpu_by[app] == host_by[app], (seed, app, tpu_by, host_by)


@pytest.mark.parametrize("pad", (True, False), ids=("padded", "unpadded"))
@pytest.mark.parametrize("seed", (0, 5, 12, 15))
def test_dummy_row_stays_zero(seed, pad, monkeypatch):
    """With G padded the dummy is the new last row and the old one an empty
    group; unpadded it is row G.  Either way no step may add into it: padded
    member entries name it, and so does every slot a class does not own."""
    env = fuzz_environment(seed)
    snapshot, prep, outputs = raw_solve(
        env, fuzz_batch(seed), pad=pad, monkeypatch=monkeypatch
    )
    n_groups = len(snapshot.groups)
    g1 = np.shape(solve_ops.StaticArrays(*prep.statics_arrays).grp_skew)[0]
    assert g1 == (solve_ops.bucket(n_groups, floor=4) if pad else n_groups) + 1
    assert goes_by_row(prep) == (seed >= len(FAMILIES))
    for name in solve_ops.TopoCounts._fields:
        plane = np.asarray(getattr(outputs.topo, name))
        assert plane.shape[0] == g1
        assert not plane[n_groups:].any(), name  # the dummy, and any padded group


def member_plane(counts, g: int):
    """bool[C, G + 1]: class c a member of the first ``counts[c]`` groups,
    rotated by c so the lists differ; the dummy column stays False."""
    member = np.zeros((len(counts), g + 1), dtype=bool)
    for c, k in enumerate(counts):
        member[c, (np.arange(k) + c) % g] = True
    return member


@pytest.mark.parametrize("counts, g, m", [
    ((0, 1, 3, 8), 12, 8),       # the floor: every cell of the benchmark today
    ((2, 9, 0), 12, 12),         # nine groups: the next bucket
    ((12, 4, 1), 40, 12),        # the longest list a step walks
    ((5, 13, 1), 20, 0),         # one class past it: no lists, whole planes
    ((20, 20), 20, 0),           # every class in all G groups
    ((), 3, 8),                  # no class at all
])
def test_member_idx_round_trips_grp_member(counts, g, m):
    member = member_plane(counts, g)
    idx = solve_ops.member_index(member)
    assert idx.dtype == np.int32 and idx.shape == (len(counts), m)
    assert m <= solve_ops.ROW_LIST_MAX
    for c, k in enumerate(counts if m else ()):
        assert list(idx[c, :k]) == list(np.flatnonzero(member[c]))  # ascending
        assert (idx[c, k:] == g).all()  # then the dummy
    # through pad_planes: the dummy moves to the new last row, padded class
    # rows name nothing else, and the list still spells grp_member
    if not counts:
        return
    env = make_environment()
    env.kube.create(make_provisioner())
    solver = TPUSolver(env.provider, env.kube.list_provisioners())
    pods = [make_pod(labels={"app": f"c{c}"}, requests={"cpu": "100m"}) for c in range(len(counts))]
    snapshot = solver.encode(pods)
    assert len(snapshot.classes) == len(counts)
    snapshot.grp_member = member
    snapshot.grp_skew = np.ones(g + 1, np.int32)
    snapshot.grp_is_zone = np.zeros(g + 1, bool)
    snapshot.grp_is_anti = np.zeros(g + 1, bool)
    snapshot.cls_groups = np.full((len(counts), 6), g, np.int32)
    cls, sa, khb = solve_ops.prepare_host(snapshot)
    cls_p, sa_p, _, _, _ = solve_ops.pad_planes(cls, sa, khb)
    g1_p = sa_p.grp_skew.shape[0]
    assert g1_p == solve_ops.bucket(g, floor=4) + 1
    idx_p = np.asarray(cls_p.member_idx)
    assert idx_p.dtype == np.int32
    assert idx_p.shape == (solve_ops.bucket(len(counts)), m)
    assert (idx_p[len(counts):] == g1_p - 1).all()
    if m:
        back = np.zeros(np.shape(sa_p.grp_member), dtype=bool)
        rows, cols = np.nonzero(idx_p < g1_p - 1)
        back[rows, idx_p[rows, cols]] = True
        np.testing.assert_array_equal(back, np.asarray(sa_p.grp_member))
    assert (np.asarray(cls_p.groups) == g1_p - 1).all()


def all_members_batch(n_groups: int):
    """``n_groups`` zone-spread classes in one namespace, each selecting
    EVERY pod (its own ``NotIn`` keeps the groups distinct): M = G."""
    pods = []
    for i in range(n_groups):
        selector = LabelSelector(match_expressions=[LabelSelectorRequirement(
            key="app", operator="NotIn", values=[f"nobody-{i}"])])
        pods.extend(make_pod(
            labels={"app": f"all-{i}"}, requests={"cpu": "100m"},
            topology_spread=[TopologySpreadConstraint(
                max_skew=1 + i, topology_key=ZONE, label_selector=selector)],
        ) for _ in range(3))
    return pods


@pytest.mark.parametrize("n_groups", (3, 10, 40))
def test_all_members_snapshot_matches_host_oracle(n_groups):
    env = make_environment()
    env.kube.create(make_provisioner())
    pods = all_members_batch(n_groups)
    snapshot, prep, outputs = raw_solve(env, pods)
    member = np.asarray(snapshot.grp_member)
    assert member[:, :n_groups].all() and len(snapshot.groups) == n_groups
    width = solve_ops.bucket(n_groups)
    assert np.shape(prep.cls.member_idx)[1] == (width if width <= solve_ops.ROW_LIST_MAX else 0)
    assert not goes_by_row(prep)
    want = closed_form(prep, outputs)
    np.testing.assert_array_equal(np.asarray(outputs.topo.fwd_new), want.fwd_new)
    host = build_scheduler(
        env.kube, env.provider, env.cluster, copy.deepcopy(pods), [], daemonset_pods=[]
    ).solve(pods)
    tpu = TPUSolver(env.provider, env.kube.list_provisioners()).solve(pods)
    assert len(tpu.failed_pods) == len(host.failed_pods)
    assert sum(len(n.pods) for n in tpu.new_nodes) == sum(len(n.pods) for n in host.new_nodes)


def test_long_member_lists_go_by_row():
    """Ten classes that all select ``tier=shared`` on hostname spreads of
    their own (distinct skews keep the groups apart) sit in ten groups each:
    M = 12, and beside 60 filler groups the step still goes by row."""
    pods = filler_pods(60)
    for i in range(10):
        pods.extend(make_pod(
            labels={"app": f"rows-long-{i}", "tier": "shared"}, requests={"cpu": "100m"},
            topology_spread=[TopologySpreadConstraint(
                max_skew=20 + i, topology_key=HOSTNAME,
                label_selector=LabelSelector(match_labels={"tier": "shared"}))],
        ) for _ in range(3))
    env = make_environment()
    env.kube.create(make_provisioner())
    snapshot, prep, outputs = raw_solve(env, pods)
    assert int(np.asarray(snapshot.grp_member).sum(axis=1).max()) == 10
    assert np.shape(prep.cls.member_idx)[1] == 12 and goes_by_row(prep)
    want = closed_form(prep, outputs)
    for name in solve_ops.TopoCounts._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(outputs.topo, name)), getattr(want, name), err_msg=name
        )
    assert int(np.asarray(outputs.failed).sum()) == 0
    assert np.asarray(outputs.topo.fwd_new)[: len(snapshot.groups)].sum() == 60 + 10 * 30


@pytest.mark.parametrize("groups, fillers, want", [
    (3, 0, {"g_padded": 5, "m_padded": 8, "members_max": 3}),
    (3, FILLER_GROUPS, {"g_padded": 49, "m_padded": 8, "members_max": 4}),  # a filler: its own + the 3
    (20, 0, {"g_padded": 25, "m_padded": 0, "members_max": 20}),
], ids=("whole", "by-row", "no-lists"))
def test_prepare_span_counts_the_member_axis(traced, groups, fillers, want):
    """``m_padded`` and ``members_max`` beside ``c_padded``, ``g_padded`` on
    both returns of ``prepare_encoded`` (the second call reuses the prep)."""
    from karpenter_core_tpu import tracing

    env = make_environment()
    env.kube.create(make_provisioner())
    solver = TPUSolver(env.provider, env.kube.list_provisioners())
    ingest = PodIngest()
    ingest.add_all(all_members_batch(groups) + filler_pods(fillers))
    for _ in range(2):
        snapshot = solver.encode(ingest)
        solver.prepare_encoded(snapshot)
    spans = [
        s for t in tracing.TRACE_STORE.last(None) for s in t.spans if s["name"] == "prepare"
    ]
    assert len(spans) == 2
    for span in spans:
        got = {k: span["attrs"][k] for k in want}
        assert got == want
        assert span["attrs"]["c_padded"] == solve_ops.bucket(len(snapshot.classes))


def zone_population(n: int):
    """Generic pods, a zone spread and a zone self-affinity Deployment: the
    windowed repair then reads ``topo_base`` rows for groups it owns."""
    spread = LabelSelector(match_labels={"app": "zs"})
    pods = [make_pod(requests={"cpu": "500m"}) for _ in range(n // 2)]
    pods += [make_pod(
        labels={"app": "zs"}, requests={"cpu": "250m"},
        topology_spread=[TopologySpreadConstraint(
            max_skew=1, topology_key=ZONE, label_selector=spread)],
    ) for _ in range(n // 4)]
    pods += [make_pod(
        labels={"app": "za"}, requests={"cpu": "250m"},
        pod_affinity=[PodAffinityTerm(
            topology_key=ZONE, label_selector=LabelSelector(match_labels={"app": "za"}))],
    ) for _ in range(n - len(pods))]
    return pods


@pytest.mark.parametrize("fillers", (0, FILLER_GROUPS), ids=("whole", "by-row"))
@pytest.mark.parametrize("window", ("16", "0"), ids=("windowed", "full-width"))
def test_warm_repair_over_zone_groups_matches_full_solve(window, fillers, monkeypatch):
    """docs/INCREMENTAL.md: a repair tick (``gather_repair_window`` → solve →
    scatter when the window is on) leaves the lineage where a from-scratch
    solve of the same population lands."""
    import jax

    from karpenter_core_tpu.models import store as store_mod

    monkeypatch.setenv("KC_DELTA_WINDOW", window)
    rng = random.Random(5)
    solver = TPUSolver(fake_cp.FakeCloudProvider(), [make_provisioner()])
    ingest = PodIngest()
    ingest.add_all(zone_population(48) + filler_pods(fillers))
    session = IncrementalSolveSession(
        solver, FallbackPolicy(enabled=True, audit_interval=0, max_delta_fraction=0.9)
    )
    session.solve(ingest)
    assert goes_by_row(session._warm.prep) == bool(fillers)
    for tick in range(3):
        members = ingest.class_members()
        uids = [
            u for us in members.values() for u in us
            if not ingest.get(u).metadata.labels.get("app", "").startswith("rows-fill")
        ]
        for i, uid in enumerate(rng.sample(uids, 4)):
            fresh = copy.deepcopy(ingest.get(uid))
            ingest.remove(uid)
            fresh.metadata.name = f"rows-churn-{tick}-{i}"
            fresh.metadata.uid = new_uid()
            ingest.add(fresh)
        session.solve(ingest)
        assert session.last_mode == MODE_DELTA, session.last_reason
        snapshot = solver.encode(ingest)
        full = solve_ops.solve(snapshot)
        a, ae = jax.device_get((full.assign, full.assign_existing))
        keys = [store_mod.class_key(c) for c in snapshot.classes]
        assert session.node_signature() == node_signature_of(
            np.asarray(a), keys
        ) + node_signature_of(np.asarray(ae), keys), f"tick {tick}"
