"""TPU consolidation sweep vs the host consolidation logic."""

import pytest

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import OP_IN, NodeSelectorRequirement
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.controllers.deprovisioning import (
    Action,
    candidate_nodes,
)
from karpenter_core_tpu.solver.consolidation import MAX_LANES, TPUConsolidationSearch
from karpenter_core_tpu.testing import make_pod, make_provisioner
from karpenter_core_tpu.testing.harness import expect_provisioned, make_environment

# device subset sweeps compile per cluster shape -- the slow tier (`make test-all`)
pytestmark = pytest.mark.compile

CT = labels_api.LABEL_CAPACITY_TYPE

def build_cluster(n_nodes, pods_per_node, pod_cpu="600m", instance_types=5, oversize=False):
    """Provision n_nodes one at a time so each lands on its own node.

    With ``oversize`` each round also schedules a large pod that is deleted
    afterwards, leaving big nodes holding only small pods — the shape where
    replacement consolidation is strictly cheaper (linear synthetic pricing
    makes equal-capacity splits cost-neutral)."""
    env = make_environment(instance_types=fake_cp.instance_types(instance_types))
    env.kube.create(
        make_provisioner(
            consolidation_enabled=True,
            requirements=[
                NodeSelectorRequirement(CT, OP_IN, [labels_api.CAPACITY_TYPE_ON_DEMAND])
            ],
        )
    )
    big_pods = []
    for _ in range(n_nodes):
        pods = [make_pod(requests={"cpu": pod_cpu}) for _ in range(pods_per_node)]
        if oversize:
            big = make_pod(requests={"cpu": 4})
            pods.append(big)
            big_pods.append(big)
        expect_provisioned(env, *pods)
        env.make_all_nodes_ready()
    for big in big_pods:
        env.kube.delete(env.kube.get_pod(big.namespace, big.name), force=True)
    env.clock.step(21)
    return env

def get_candidates(env):
    dep = env.deprovisioning
    return sorted(
        candidate_nodes(
            env.cluster, env.kube, env.clock, env.provider,
            dep.multi_node_consolidation.should_deprovision,
        ),
        key=lambda c: c.disruption_cost,
    )

class TestTPUConsolidation:
    def test_empty_candidates_deleted(self):
        env = build_cluster(n_nodes=2, pods_per_node=1, pod_cpu="600m")
        # remove all pods: both nodes empty -> sweep proposes deleting both
        for pod in env.kube.list_pods():
            env.kube.delete(pod, force=True)
        candidates = get_candidates(env)
        assert len(candidates) == 2
        search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
        cmd = search.compute_command(
            candidates,
            pending_pods=[],
            state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        assert cmd.action == Action.DELETE
        assert len(cmd.nodes_to_remove) == 2

    def test_multi_node_replace_with_cheaper(self):
        # two oversized nodes holding small pods consolidate into one cheaper
        env = build_cluster(n_nodes=2, pods_per_node=1, pod_cpu="500m", oversize=True)
        candidates = get_candidates(env)
        assert len(candidates) == 2
        search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
        cmd = search.compute_command(
            candidates,
            pending_pods=[],
            state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        assert cmd.action == Action.REPLACE
        assert len(cmd.nodes_to_remove) == 2
        replacement = cmd.replacement_nodes[0]
        assert replacement.instance_type_options, "price-filtered options remain"
        # replacement is cheaper than the two originals combined
        old_price = sum(
            c.instance_type.offerings.get(c.capacity_type, c.zone).price
            for c in candidates
        )
        from karpenter_core_tpu.controllers.deprovisioning import worst_launch_price

        new_price = min(
            worst_launch_price(it.offerings.available(), replacement.requirements)
            for it in replacement.instance_type_options
        )
        assert new_price < old_price

    def test_agrees_with_host_on_action(self):
        env = build_cluster(n_nodes=3, pods_per_node=1, pod_cpu="500m", oversize=True)
        candidates = get_candidates(env)
        search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
        tpu_cmd = search.compute_command(
            candidates,
            pending_pods=[],
            state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        host_cmd = env.deprovisioning.multi_node_consolidation.first_n_consolidation_option(
            candidates, len(candidates)
        )
        assert tpu_cmd.action == host_cmd.action
        # the sweep examines every prefix, so it must remove at least as many
        assert len(tpu_cmd.nodes_to_remove) >= len(host_cmd.nodes_to_remove)

    def test_nothing_to_do_when_full(self):
        env = build_cluster(n_nodes=1, pods_per_node=4, pod_cpu="900m", instance_types=1)
        candidates = get_candidates(env)
        search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
        cmd = search.compute_command(
            candidates,
            pending_pods=[],
            state_nodes=env.cluster.snapshot_nodes(),
            bound_pods=env.kube.list_pods(),
        )
        # the single node is full (4x0.9 cpu on 1-cpu... node fits?) - at
        # minimum the sweep must not propose an invalid removal
        if cmd.action == Action.DELETE:
            raise AssertionError("full node must not be deleted")

class _Verdicts:
    """What ``evaluate`` returns, over a plain validity function."""

    def __init__(self, sizes, valid):
        self.sizes, self.valid = [int(k) for k in sizes], valid

    def command(self, k):
        assert k in self.sizes, "the walk asked for a size the pass did not simulate"
        return ("cmd", k) if self.valid(k) else None

    def best(self):
        k = max((k for k in self.sizes if self.valid(k)), default=0)
        return (("cmd", k) if k else None), k


def host_binary_search(n, valid):
    """``first_n_consolidation_option`` over a validity function:
    (answer, the sizes it probed in order)."""
    lo_idx, hi_idx, last_saved, probed = 1, n - 1, None, []
    while lo_idx <= hi_idx:
        mid = (lo_idx + hi_idx) // 2
        probed.append(mid + 1)
        if valid(mid + 1):
            last_saved, lo_idx = ("cmd", mid + 1), mid + 1
        else:
            hi_idx = mid - 1
    return last_saved, probed


class TestSearchLargestPrefix:
    """Above the lane ladder the search is the host's binary search, LEVELS
    levels a pass on the low rung: its probes, so its answer, whatever the
    shape of feasibility.  Up to the top rung one pass simulates every
    prefix."""

    def _run(self, n, valid, refine=True):
        from karpenter_core_tpu.solver.consolidation import search_largest_prefix

        passes = []

        def evaluate(sizes, levels=0):
            assert list(sizes) == sorted(set(int(k) for k in sizes))
            passes.append(([int(k) for k in sizes], levels))
            return _Verdicts(sizes, valid)

        return search_largest_prefix(n, evaluate, refine=refine), passes

    def _holds_against_the_host(self, n, valid):
        from karpenter_core_tpu.ops.consolidate import LANE_LADDER
        from karpenter_core_tpu.solver.consolidation import LEVELS

        best, passes = self._run(n, valid)
        wanted, probed = host_binary_search(n, valid)
        assert best == wanted
        assert LEVELS == 3 and all(len(sizes) <= 7 <= LANE_LADDER[0] for sizes, _ in passes)
        assert len(passes) == -(-len(probed) // LEVELS)
        # pass i simulated the host's probes 3i .. 3i + 2, and says how many
        # levels it speculated
        for i, (sizes, levels) in enumerate(passes):
            mine = probed[LEVELS * i: LEVELS * (i + 1)]
            assert set(mine) <= set(sizes)
            assert len(mine) <= levels <= LEVELS
        return best, len(passes)

    @pytest.mark.parametrize("n,boundary,passes", [
        (40, 17, 1),                  # small: one exhaustive pass
        (72, 72, 1),                  # the top rung itself
        (500, 123, 3),
        (300_000, 123_456, 7),
        (100_000, 0, 6),              # no valid prefix
        (100_000, 100_000, 6),        # all valid
        (73, 2, 2),                   # the first size past the ladder
        (300, 1, 3), (300, 150, 3), (300, 209, 3), (300, 300, 3),
        (5_000, 2, 4), (5_000, 3_133, None), (5_000, 4_999, None), (5_000, 5_000, 5),
    ])
    def test_pins_the_boundary(self, n, boundary, passes):
        valid = lambda k: k <= boundary  # noqa: E731
        if n <= MAX_LANES:
            best, seen = self._run(n, valid)
            assert best == (("cmd", boundary) if boundary else None)
            assert seen == [(list(range(1, n + 1)), 0)]
            return
        best, took = self._holds_against_the_host(n, valid)
        assert took == passes if passes else took in (4, 5)
        # a monotone boundary is pinned exactly (size 1 is no multi-node command)
        assert best == (("cmd", boundary) if boundary >= 2 else None)

    @pytest.mark.parametrize("n", [300, 5_000])
    def test_every_boundary_of_a_cluster(self, n):
        counts = {}
        for boundary in range(0, n + 1, 1 if n <= 300 else 37):
            _, took = self._holds_against_the_host(n, lambda k: k <= boundary)
            counts[took] = counts.get(took, 0) + 1
        assert set(counts) == ({3} if n == 300 else {4, 5})

    @pytest.mark.parametrize("seed", range(24))
    def test_non_monotone_feasibility_is_the_hosts_answer(self, seed):
        """Holes below the boundary and islands above it (a REPLACE that is
        valid one size past an invalid one): a grid answers by where its points
        fall, the host's probes answer as the host does."""
        import random

        rng = random.Random(seed)
        n = rng.choice([73, 150, 300, 1_000, 5_000])
        boundary = rng.randrange(2, n)
        holes = {rng.randrange(2, boundary + 1) for _ in range(rng.randrange(1, 12))}
        islands = {
            min(n, boundary + rng.randrange(1, 40)) for _ in range(rng.randrange(1, 12))
        }
        self._holds_against_the_host(
            n, lambda k: (k <= boundary and k not in holes) or k in islands
        )

    def test_scored_search_is_one_coarse_pass(self):
        best, passes = self._run(5_000, lambda k: k <= 3_000, refine=False)
        (sizes, levels), = passes
        assert len(sizes) == MAX_LANES and levels == 0
        assert best == ("cmd", max(k for k in sizes if k <= 3_000))
