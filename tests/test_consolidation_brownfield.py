"""The consolidation sweep on a brown-field cluster of the benchmark's mix
(``benchmark/traffic/kinds/cluster_cycle.build_cluster``: four node types,
bound pods of five kinds dealt to 60 %), every node a candidate.

  right      the host's own simulation accepts every command the sweep
             returns (``validate_command``, what the controller runs before it
             acts); above the lane ladder the sweep's command is the host
             binary search's in action and size, up to the top rung (every
             prefix simulated) never shorter
  normal     the sweep's executables come from the compile cache, a cluster
             uses one rung of the lane ladder whatever its answer, a repeat
             builds none; a request counts the sizes it simulated
  watched    deadlines are kept per executable: a short pass never sets a
             wide pass's deadline
"""

import json
import os

import numpy as np
import pytest

from benchmark.traffic.kinds import consolidate_cycle, consolidate_reference
from karpenter_core_tpu import tracing
from karpenter_core_tpu.cloudprovider import fake as fake_cp
from karpenter_core_tpu.controllers.deprovisioning import Action
from karpenter_core_tpu.ops import consolidate as consolidate_ops
from karpenter_core_tpu.solver.consolidation import (
    CONSOLIDATE_PROBES, LEVELS, MAX_LANES, TPUConsolidationSearch,
)
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


@pytest.mark.parametrize("n_nodes,seed,pending", [
    (40, 1, 0), (40, 2, 0), (40, 3, 0), (40, 4, 8),
    # above the top rung: the host's probes, so the host's command
    (150, 1, 0), (150, 2, 0),
    # k = 208 is invalid and k = 209 a valid REPLACE: feasibility is not
    # monotone at the boundary, and host and sweep both probe 209
    (300, 3, 0),
])
def test_the_host_accepts_the_sweeps_command(n_nodes, seed, pending):
    env, candidates, waiting = live_cluster(n_nodes, seed, pending)
    host = env.deprovisioning.multi_node_consolidation
    assert len(env.provisioning.get_pending_pods()) == pending
    _, command = sweep(env, candidates, waiting)
    wanted = host.first_n_consolidation_option(candidates, len(candidates))
    assert command.action in (Action.DELETE, Action.REPLACE)
    assert host.validate_command(command, candidates)
    assert len(command.nodes_to_remove) >= len(wanted.nodes_to_remove) >= 2
    if n_nodes > MAX_LANES:
        assert len(command.nodes_to_remove) == len(wanted.nodes_to_remove)
    if len(command.nodes_to_remove) == len(wanted.nodes_to_remove):
        assert command.action == wanted.action
    if (n_nodes, seed) == (300, 3):
        assert (command.action, len(command.nodes_to_remove)) == (Action.REPLACE, 209)


@pytest.mark.parametrize("n_nodes,passes", [
    (5, 1),     # one pass on the low rung
    (40, 1),    # one pass on the top rung
    (70, 1),    # still one pass on the top rung
    (130, 3),   # 7 or 8 levels of the binary search, three a pass, on the low rung
    (300, 3),   # 8 or 9 levels
])
def test_a_clusters_sweeps_stay_on_the_lane_ladder(n_nodes, passes):
    env, candidates, _ = live_cluster(n_nodes, seed=5)
    compilecache.reset_memo()
    search, first = sweep(env, candidates)
    builds = compilecache.stats()["builds"]
    assert search.last_passes == passes
    assert builds == 1  # one rung a cluster, whatever its answer
    search, again = sweep(env, candidates)
    assert compilecache.stats()["builds"] == builds  # none on a repeat
    assert [n.name for n in again.nodes_to_remove] == [n.name for n in first.nodes_to_remove]
    assert again.action == first.action


def test_a_request_counts_the_sizes_it_simulates(traced):
    env, candidates, _ = live_cluster(150, seed=1)
    counted = CONSOLIDATE_PROBES.labels("levels").value
    elsewhere = sum(CONSOLIDATE_PROBES.labels(form).value for form in ("exhaustive", "scored"))
    search, _ = sweep(env, candidates)
    passes = [s["attrs"] for trace in tracing.TRACE_STORE.last(16) for s in trace.spans
              if s["name"] == "consolidate.sweep"]
    assert [a["pass"] for a in passes] == [1, 2, 3]
    assert all(a["lanes"] <= 2 ** LEVELS - 1 and a["lanes_padded"] == 8 for a in passes)
    assert [a["levels"] for a in passes] == [3, 3, 1]  # 149 interior sizes: 7 levels
    assert search.last_probes == sum(a["lanes"] for a in passes) == 15
    assert CONSOLIDATE_PROBES.labels("levels").value - counted == search.last_probes
    assert elsewhere == sum(
        CONSOLIDATE_PROBES.labels(form).value for form in ("exhaustive", "scored"))


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
