"""The scan's node-slot axis: N follows the nodes a batch can open
(ops.solve.estimate_slots), the answer does not depend on N while there is
room, and an N too small costs solves, never pods (TPUSolver.grow_until_fits).
"""

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
from karpenter_core_tpu.metrics.registry import SOLVER_SLOT_RETRIES
from karpenter_core_tpu.models.columnar import PodIngest
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.solver.incremental import IncrementalSolveSession
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.testing import make_node, make_pod, make_provisioner
from karpenter_core_tpu.testing.harness import make_environment
from karpenter_core_tpu.utils import compilecache

pytestmark = pytest.mark.compile  # kernel compiles: the slow tier

ZONE = labels_api.LABEL_TOPOLOGY_ZONE
HOST = labels_api.LABEL_HOSTNAME
SIZES = [{"cpu": "250m", "memory": "256Mi"}, {"cpu": "1", "memory": "1Gi"},
         {"cpu": "1500m", "memory": "512Mi"}]


def _spread(key, labels):
    return [TopologySpreadConstraint(
        max_skew=1, topology_key=key, label_selector=LabelSelector(match_labels=labels))]


def _term(key, labels):
    return [PodAffinityTerm(topology_key=key, label_selector=LabelSelector(match_labels=labels))]


def _family(name: str, n: int = 36) -> list:
    """``n`` pods of one family of the upstream mix, three sizes, two labels."""
    pods = []
    for i in range(n):
        labels = {"app": f"{name}-{i % 2}"}
        kw = {
            "generic": {},
            "zonal-spread": {"topology_spread": _spread(ZONE, labels)},
            "hostname-spread": {"topology_spread": _spread(HOST, labels)},
            "zone-affinity": {"pod_affinity": _term(ZONE, labels)},
            "hostname-anti": {"pod_anti_affinity": _term(HOST, labels)},
        }[name]
        pods.append(make_pod(labels=labels, requests=SIZES[i % len(SIZES)], **kw))
    return pods


def _repelling(n: int) -> list:
    """``n`` pods of three sizes in ONE hostname anti-affinity group: a node each."""
    labels = {"app": "one-group"}
    return [make_pod(labels=labels, requests=SIZES[i % len(SIZES)],
                     pod_anti_affinity=_term(HOST, labels)) for i in range(n)]


def _solver(n_types: int = 24) -> TPUSolver:
    return TPUSolver(
        fake_cp.FakeCloudProvider(fake_cp.instance_types(n_types)), [make_provisioner()]
    )


def _retries() -> float:
    return sum(value for _, _, value in SOLVER_SLOT_RETRIES.samples())


def _fleet(results) -> list:
    """The answer as a multiset of nodes: each the sorted names of its pods."""
    return sorted(sorted(p.name for p in n.pods) for n in results.new_nodes)


class TestSlotCountInvariance:
    @pytest.mark.parametrize("family", [
        "generic", "zonal-spread", "hostname-spread", "zone-affinity",
        "hostname-anti", "existing-nodes",
    ])
    def test_answer_is_the_same_at_four_times_the_slots(self, family):
        """N enters the kernel only as ``free_slots = n_slots - n_next``: with
        room to spare the placements are bit-identical at any N."""
        state_nodes = bound = None
        if family == "existing-nodes":
            env = make_environment()
            env.kube.create(make_provisioner())
            for zone in ("test-zone-1", "test-zone-2"):
                env.kube.create(make_node(
                    labels={
                        labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                        labels_api.LABEL_INSTANCE_TYPE_STABLE: "default-instance-type",
                        labels_api.LABEL_CAPACITY_TYPE: "spot",
                        labels_api.LABEL_NODE_INITIALIZED: "true",
                        ZONE: zone,
                    },
                    allocatable={"cpu": 4, "memory": "4Gi", "pods": 10},
                ))
            solver = TPUSolver(env.provider, env.kube.list_provisioners())
            state_nodes, bound = env.cluster.snapshot_nodes(), env.kube.list_pods()
            pods = _family("generic", 24) + _family("zonal-spread", 24)
        else:
            solver = _solver()
            pods = _family(family)
        snapshot = solver.encode(pods, state_nodes, bound)
        n = solve_ops.estimate_slots(snapshot)

        def planes(n_slots):
            prep = solver.prepare_encoded(snapshot, state_nodes, bound, n_slots)
            out = solver.run_prepared(prep)
            return (np.asarray(out.assign), np.asarray(out.assign_existing),
                    np.asarray(out.failed), int(out.state.n_next))

        assign, assign_ex, failed, n_next = planes(n)
        assign4, assign_ex4, failed4, n_next4 = planes(4 * n)
        assert n_next <= n and not failed.any()
        assert assign4.shape[1] == 4 * n
        assert np.array_equal(assign4[:, :n], assign) and not assign4[:, n:].any()
        assert np.array_equal(assign_ex4, assign_ex)
        assert np.array_equal(failed4, failed)
        assert n_next4 == n_next


class TestGrowUntilFits:
    def test_forced_small_start_fails_no_pod(self):
        """40 pods that repel one another need 40 nodes: from N = 8 the solve
        runs again at 16, 32 and 64, and ends on the ample answer."""
        pods = _repelling(40)
        solver = _solver()
        before = _retries()
        ample = solver.solve(pods, n_slots=64)
        assert _retries() == before, "a batch that fits counts no retry"
        assert not ample.failed_pods and len(ample.new_nodes) == 40

        grown = solver.solve(pods, n_slots=8)
        assert _retries() - before == 3  # 8 -> 16 -> 32 -> 64
        assert not grown.failed_pods
        assert grown.n_slots_used == 40
        assert _fleet(grown) == _fleet(ample)

    @pytest.mark.parametrize("deferred", [False, True])
    def test_session_anchor_grows_the_same_way(self, monkeypatch, deferred):
        pods = _repelling(40)
        ample = _solver().solve(pods, n_slots=64)
        monkeypatch.setattr(solve_ops, "estimate_slots", lambda snapshot: 8)
        session = IncrementalSolveSession(_solver())
        ingest = PodIngest()
        ingest.add_all(pods)
        before = _retries()
        results = session.solve(ingest, deferred=deferred)
        if deferred:
            results = results.result()
        assert _retries() - before == 3
        assert not results.failed_pods
        assert _fleet(results) == _fleet(ample)
        assert session._warm.n_next == 40 and session._warm.assign.shape[1] == 64

    def test_continued_anchor_asks_for_room_over_what_the_lineage_opened(self):
        """A re-anchor that continues the population (audit, repair out of
        room) asks for a quarter over the slots the lineage had opened."""
        session = IncrementalSolveSession(_solver())
        ingest = PodIngest()
        ingest.add_all(_family("generic", 30))
        session.solve(ingest)
        width = session._warm.assign.shape[1]
        assert session._continued_slots("first") == 0
        session._warm.n_next = width - 1  # as after many repairs
        assert session._continued_slots("audit") == 2 * width
        assert session._continued_slots("slots-exhausted") == 2 * width
        assert session._continued_slots("class-shape") == 0


def _upstream_mix(n_pods: int, rng: random.Random) -> list:
    """makeDiversePods as the benchmark's configurations send it
    (scheduling_benchmark_test.go:185-197; departures: PERF.md §7): a seventh
    each of generic, zonal spread, hostname spread and twice zonal
    self-affinity, the remainder generic; 5 x 6 sizes, 7 label values."""
    cpus = ["100m", "250m", "500m", "1000m", "1500m"]
    mems = ["100Mi", "256Mi", "512Mi", "1024Mi", "2048Mi", "4096Mi"]

    def one(kind):
        value = rng.choice("abcdefg")
        requests = {"cpu": rng.choice(cpus), "memory": rng.choice(mems)}
        if kind == "generic":
            return make_pod(labels={"my-label": value}, requests=requests)
        key, topology, spread = {
            "zone-spread": ("my-zone-spread", ZONE, True),
            "host-spread": ("my-host-spread", HOST, True),
            "zone-affinity": ("my-affinity", ZONE, False),
        }[kind]
        labels = {key: value}
        if spread:
            return make_pod(labels=labels, requests=requests,
                            topology_spread=_spread(topology, labels))
        return make_pod(labels=labels, requests=requests, pod_affinity=_term(topology, labels))

    kinds = ["generic", "zone-spread", "host-spread", "zone-affinity", "zone-affinity"]
    pods = [one(k) for k in kinds for _ in range(n_pods // 7)]
    return pods + [one("generic") for _ in range(n_pods - len(pods))]


class TestEstimateOnTheUpstreamMix:
    @pytest.mark.parametrize("n_pods", [1400, 5000])
    def test_estimate_covers_the_fleet_within_four_times(self, n_pods):
        compilecache.reset_memo()  # no earlier test's slot count to snap to
        rng = random.Random(n_pods)
        pods = _upstream_mix(n_pods, rng)
        solver = _solver(n_types=400)
        snapshot = solver.encode(pods)
        n = solve_ops.estimate_slots(snapshot)
        before = _retries()
        results = solver.solve_encoded(snapshot)
        assert _retries() == before and not results.failed_pods
        used = len(results.new_nodes)
        assert used <= n <= 4 * used, (used, n)

        # a new class, then a few more pods of old ones: N does not move
        wobble = pods + [make_pod(labels={"my-label": "h"}, requests={"cpu": "2"})]
        assert solve_ops.estimate_slots(solver.encode(wobble)) == n
        wobble += _upstream_mix(14, rng)
        assert solve_ops.estimate_slots(solver.encode(wobble)) == n

    def test_groups_that_exclude_one_another_add(self):
        """Two self-repelling groups share nodes (the larger sets the fleet);
        let one's term select the other's label too and they cannot."""
        def group(name, n, also=None):
            values = [name] + ([also] if also else [])
            selector = LabelSelector(match_expressions=[
                LabelSelectorRequirement("app", "In", values)])
            return [make_pod(labels={"app": name}, requests={"cpu": "100m"},
                             pod_anti_affinity=[PodAffinityTerm(
                                 topology_key=HOST, label_selector=selector)])
                    for _ in range(n)]

        solver = _solver()
        compilecache.reset_memo()
        sharing = solve_ops.estimate_slots(solver.encode(group("a", 100) + group("b", 90)))
        compilecache.reset_memo()
        apart = solve_ops.estimate_slots(
            solver.encode(group("a", 100, also="b") + group("b", 90)))
        assert sharing == 128 and apart == 256
        results = solver.solve(group("a", 100, also="b") + group("b", 90))
        assert not results.failed_pods and len(results.new_nodes) == 190
