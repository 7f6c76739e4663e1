"""The consolidation sweep on a brown-field cluster of the benchmark's mix
(``benchmark/traffic/kinds/cluster_cycle.build_cluster``: four node types,
bound pods of five kinds dealt to 60 %), every node a candidate.

  right      the host's own simulation accepts every command the sweep
             returns (``validate_command``, what the controller runs before it
             acts), and the sweep's prefix is never shorter than the host
             binary search's
  normal     the sweep's executables come from the compile cache, a cluster
             uses at most one per rung of the lane ladder whatever brackets
             the search leaves, a repeat builds none
  watched    deadlines are kept per executable: a short pass never sets a
             wide pass's deadline
"""

import json
import os

import numpy as np
import pytest

from benchmark.traffic.kinds import consolidate_cycle, consolidate_reference
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.controllers.deprovisioning import Action
from karpenter_core_tpu.ops import consolidate as consolidate_ops
from karpenter_core_tpu.solver.consolidation import TPUConsolidationSearch
from karpenter_core_tpu.testing import make_pod, make_provisioner
from karpenter_core_tpu.utils import compilecache, watchdog

pytestmark = pytest.mark.compile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TYPES = 100


def live_cluster(n_nodes: int, seed: int, pending: int = 0):
    """(environment, candidates in disruption order, pending pods)."""
    with open(os.path.join(REPO, "benchmark", "configs", "consolidate-5k.json")) as f:
        config = {**json.load(f), "existing_nodes": n_nodes}
    catalog = fake_cp.instance_types(TYPES)
    cluster = consolidate_cycle.build_cluster(config, seed, catalog, "prov-0")
    consolidate_cycle.stamp_by_workload(cluster)
    waiting = [make_pod(requests={"cpu": "250m", "memory": "256Mi"}) for _ in range(pending)]
    env, found = consolidate_reference.host_environment(
        cluster, catalog, make_provisioner(name="prov-0", consolidation_enabled=True), waiting)
    candidates = sorted(found.values(), key=lambda c: c.disruption_cost)
    assert len(candidates) == n_nodes
    return env, candidates, waiting


def sweep(env, candidates, pending=()):
    search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
    command = search.compute_command(
        candidates, pending_pods=list(pending),
        state_nodes=env.cluster.snapshot_nodes(), bound_pods=env.kube.list_pods(),
    )
    return search, command


@pytest.mark.parametrize("seed,pending", [(1, 0), (2, 0), (3, 0), (4, 8)])
def test_the_host_accepts_the_sweeps_command(seed, pending):
    env, candidates, waiting = live_cluster(40, seed, pending)
    host = env.deprovisioning.multi_node_consolidation
    assert len(env.provisioning.get_pending_pods()) == pending
    _, command = sweep(env, candidates, waiting)
    wanted = host.first_n_consolidation_option(candidates, len(candidates))
    assert command.action in (Action.DELETE, Action.REPLACE)
    assert host.validate_command(command, candidates)
    assert len(command.nodes_to_remove) >= len(wanted.nodes_to_remove) >= 2
    if len(command.nodes_to_remove) == len(wanted.nodes_to_remove):
        assert command.action == wanted.action


@pytest.mark.parametrize("n_nodes,passes,rungs", [
    (5, 1, 1),     # one pass on the low rung
    (40, 1, 1),    # one pass on the top rung
    (70, 1, 1),    # still one pass on the top rung
    (130, 2, 2),   # the coarse pass, then a bracket of one size
])
def test_a_clusters_sweeps_stay_on_the_lane_ladder(n_nodes, passes, rungs):
    env, candidates, _ = live_cluster(n_nodes, seed=5)
    compilecache.reset_memo()
    search, first = sweep(env, candidates)
    builds = compilecache.stats()["builds"]
    assert search.last_passes >= passes
    assert 0 < builds <= rungs <= len(consolidate_ops.LANE_LADDER)
    search, again = sweep(env, candidates)
    assert compilecache.stats()["builds"] == builds  # none on a repeat
    assert [n.name for n in again.nodes_to_remove] == [n.name for n in first.nodes_to_remove]
    assert again.action == first.action


def test_lane_rungs():
    assert [consolidate_ops.lane_rung(n) for n in (1, 2, 8, 9, 64, 72)] == [8, 8, 8, 72, 72, 72]
    assert consolidate_ops.lane_rung(3, multiple=4) == 8
    assert consolidate_ops.lane_rung(80) == 80  # a library caller's, past the ladder


def test_a_short_pass_does_not_set_a_wide_passes_deadline():
    env, candidates, _ = live_cluster(5, seed=6)
    search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
    state_nodes, bound = env.cluster.snapshot_nodes(), env.kube.list_pods()
    pods = [p for c in candidates for p in c.pods]
    snapshot = search.solver.encode(pods, state_nodes, bound)
    ex_state, ex_static = search.solver.encode_existing(
        snapshot, state_nodes, bound, count_scheduling=True)
    rank = np.arange(len(state_nodes), dtype=np.int32)
    counts = np.zeros((len(snapshot.classes), len(state_nodes)), dtype=np.int32)
    planes = consolidate_ops.prepare_sweep(snapshot, ex_state, ex_static, rank, counts)
    watchdog.reset_stats()
    for _ in range(3):  # the first completion is cold; the next two are samples
        out = consolidate_ops.sweep_pass(planes, np.array([1, 2], dtype=np.int32))
    assert np.asarray(out.failed).shape == (2,)
    short, wide = (consolidate_ops.sweep_key(planes, lanes, 16) for lanes in consolidate_ops.LANE_LADDER)
    for site in ("consolidate.dispatch", "consolidate.sweep"):
        assert (site, short) in watchdog._ewma
        assert (site, wide) not in watchdog._ewma and (site, None) not in watchdog._ewma
        cold = min(max(watchdog.floor_s() * watchdog.cold_mult(), watchdog.floor_s()),
                   watchdog.ceiling_s())
        assert watchdog.deadline_for(site, wide) == cold
