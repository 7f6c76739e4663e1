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

class TestSearchLargestPrefix:
    """The lane-sweep search must pin the exact boundary in ceil(log72(n))
    passes, whatever the candidate count."""

    def _run(self, n, boundary):
        from karpenter_core_tpu.solver.consolidation import search_largest_prefix

        passes = []

        def evaluate(sizes):
            passes.append(len(sizes))
            valid = [int(k) for k in sizes if k <= boundary]
            if not valid:
                return None, 0
            return ("cmd", max(valid)), max(valid)

        best = search_largest_prefix(n, evaluate)
        return best, passes

    def test_small_exact_single_pass(self):
        best, passes = self._run(40, boundary=17)
        assert best == ("cmd", 17)
        assert len(passes) == 1

    def test_coarse_gap_refined_exactly(self):
        best, passes = self._run(500, boundary=123)
        assert best == ("cmd", 123)
        assert len(passes) <= 2

    def test_beyond_4096_multi_round(self):
        best, passes = self._run(300_000, boundary=123_456)
        assert best == ("cmd", 123_456)
        assert len(passes) <= 4
        assert all(p <= MAX_LANES for p in passes)

    def test_no_valid_prefix(self):
        best, passes = self._run(100_000, boundary=0)
        assert best is None
        assert len(passes) == 1

    def test_all_valid(self):
        best, _ = self._run(100_000, boundary=100_000)
        assert best == ("cmd", 100_000)
