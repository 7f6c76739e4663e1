"""The plain reference of ``cluster_cycle`` and the guarantees its ``correct``
holds, all from the API objects the generator made — no plane, count or
decision of the solver under test is consulted.

  ``oracle_totals``      the host scheduler (solver/scheduler.py, the port of
                         the upstream Go scheduler) given the cluster as state
                         nodes: scheduled, failed, new nodes and the pods it
                         put on EACH existing node
  ``existing_capacity``  on every existing node, bound + newly assigned
                         requests fit the node's allocatable
  ``topology``           over bound + assigned pods of a sample of groups: a
                         hostname-spread pod lands where no member was, zone
                         spread keeps its skew, a zone-affinity pod lands in a
                         zone that holds a member

Not a kind: ``manifest.load_kind`` never names this module.
"""

import collections

from benchmark.harness import checks

RESOURCES = checks.RESOURCES
SAMPLE_GROUPS = 4  # groups checked per constrained kind


def totals(reply: dict) -> dict:
    """``checks.counts`` and the pods placed on each existing node."""
    return {**checks.counts(reply),
            "existing": {name: len(idx)
                         for name, idx in reply["existingAssignments"].items() if idx}}


def state_nodes(cluster: list, kube=None, volume_limits=None) -> list:
    """The cluster as the scheduler's state nodes, from the API objects;
    ``volume_limits`` is ``{node name: {driver: count}}``, a CSINode's."""
    from karpenter_core_tpu.state.cluster import StateNode

    out = []
    for node, bound in cluster:
        state_node = StateNode(node, kube)
        for driver, limit in ((volume_limits or {}).get(node.name) or {}).items():
            state_node._volume_limits[driver] = int(limit)
        for pod in bound:
            state_node.update_for_pod(pod)
        out.append(state_node)
    return out


def oracle_totals(pods: list, cluster: list, catalog: list, provisioners: list,
                  volume_limits=None, claim_drivers=None) -> dict:
    """The host scheduler on the same pending pods against the same cluster;
    its kube client holds the nodes and their bound pods, which is where the
    oracle counts the topology domains from (topology.go:231-276), and a
    claim and a storage class per entry of ``claim_drivers``
    (``{"<namespace>/<claim>": driver}``, what the operator ships)."""
    from karpenter_core_tpu.apis.objects import (
        ObjectMeta,
        PersistentVolumeClaim,
        PersistentVolumeClaimSpec,
        StorageClass,
    )
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.solver.builder import build_scheduler

    kube = KubeClient()
    for provisioner in provisioners:
        kube.create(provisioner)
    for node, bound in cluster:
        kube.create(node)
        for pod in bound:
            kube.create(pod)
    for driver in sorted(set((claim_drivers or {}).values())):
        kube.create(StorageClass(metadata=ObjectMeta(name=f"sc-{driver}"), provisioner=driver))
    for claim, driver in (claim_drivers or {}).items():
        namespace, name = claim.split("/", 1)
        kube.create(PersistentVolumeClaim(
            metadata=ObjectMeta(name=name, namespace=namespace),
            spec=PersistentVolumeClaimSpec(storage_class_name=f"sc-{driver}")))
    results = build_scheduler(
        kube, FakeCloudProvider(catalog), cluster=None, pods=pods,
        state_nodes=state_nodes(cluster, kube if claim_drivers else None, volume_limits),
        daemonset_pods=[],
    ).solve(pods)
    existing = {n.name: len(n.pods) for n in results.existing_nodes if n.pods}
    return {
        "nodes": len(results.new_nodes),
        "scheduled": sum(len(n.pods) for n in results.new_nodes) + sum(existing.values()),
        "failed": len(results.failed_pods),
        "residual": 0,
        "existing": existing,
    }


def differences(kernel: dict, host: dict) -> list:
    bad = [f"oracle cut: {key}: kernel {kernel[key]} vs host {host[key]}"
           for key in ("scheduled", "failed", "residual", "nodes") if kernel[key] != host[key]]
    names = sorted(set(kernel["existing"]) | set(host["existing"]))
    off = [(name, kernel["existing"].get(name, 0), host["existing"].get(name, 0))
           for name in names
           if kernel["existing"].get(name, 0) != host["existing"].get(name, 0)]
    if off:
        bad.append(f"oracle cut: pods per existing node differ on {len(off)} of "
                   f"{len(names)} nodes (node, kernel, host): {off[:5]}")
    return bad


def _needs(pods: list):
    import numpy as np

    from karpenter_core_tpu.utils import resources as resources_util

    return np.array([
        [resources_util.requests_for_pods(p).get(r, 0.0) for r in RESOURCES]
        for p in pods
    ]).reshape(len(pods), len(RESOURCES))


def existing_capacity(reply: dict, pods: list, cluster: list) -> list:
    """Bound + newly assigned requests fit each existing node's allocatable."""
    import numpy as np

    need = _needs(pods)
    by_name = {node.name: (node, bound) for node, bound in cluster}
    bad = []
    for name, idx in reply["existingAssignments"].items():
        if name not in by_name:
            bad.append(f"existing node {name!r} is not of the cluster")
            continue
        node, bound = by_name[name]
        total = _needs(bound).sum(axis=0) + need[idx].sum(axis=0)
        alloc = np.array([node.status.allocatable.get(r, 0.0) for r in RESOURCES])
        if np.any(total > alloc * (1 + 1e-9) + 1e-9):
            bad.append(f"existing node {name}: bound + assigned pods need {total.tolist()} "
                       f"of {RESOURCES}, it allows {alloc.tolist()}")
    return bad[:5]


def _placements(reply: dict, pods: list, cluster: list):
    """``(pod, host, zones, new)`` for every bound and every newly placed pod;
    a new node's host is its index, its zones the ones the answer lists."""
    from karpenter_core_tpu.apis import labels as labels_api

    zone_of = {node.name: node.metadata.labels[labels_api.LABEL_TOPOLOGY_ZONE]
               for node, _ in cluster}
    for node, bound in cluster:
        for pod in bound:
            yield pod, node.name, (zone_of[node.name],), False
    for name, idx in reply["existingAssignments"].items():
        for i in idx:
            yield pods[i], name, (zone_of[name],), True
    for k, new in enumerate(reply["newNodes"]):
        for i in new["podIndices"]:
            yield pods[i], ("new", k), tuple(new["zones"]), True


def _constraint(pod):
    """``(kind, topology key, selector's match_labels as a tuple)`` of a mix
    pod's one constraint, or None for a generic pod."""
    for cs in pod.spec.topology_spread_constraints:
        return "spread", cs.topology_key, tuple(sorted(cs.label_selector.match_labels.items()))
    affinity = pod.spec.affinity
    if affinity is not None and affinity.pod_affinity is not None:
        for term in affinity.pod_affinity.required:
            return ("affinity", term.topology_key,
                    tuple(sorted(term.label_selector.match_labels.items())))
    return None


def topology(reply: dict, pods: list, cluster: list, rng) -> list:
    """Hostname-spread skew, zone-spread skew and the zone of affinity pods,
    over bound + assigned pods, on a seeded sample of the pending groups."""
    from karpenter_core_tpu.apis import labels as labels_api

    groups = sorted({c for c in map(_constraint, pods) if c is not None})
    sample = []
    for kind, key in sorted({(g[0], g[1]) for g in groups}):
        of_kind = [g for g in groups if (g[0], g[1]) == (kind, key)]
        sample += rng.sample(of_kind, min(SAMPLE_GROUPS, len(of_kind)))
    zones = sorted({node.metadata.labels[labels_api.LABEL_TOPOLOGY_ZONE] for node, _ in cluster}
                   | {z for new in reply["newNodes"] for z in new["zones"]})
    placements = list(_placements(reply, pods, cluster))
    bad = []
    for kind, key, selector in sample:
        members = [(host, where, new) for pod, host, where, new in placements
                   if all(pod.metadata.labels.get(k) == v for k, v in selector)]
        if kind == "spread" and key == labels_api.LABEL_HOSTNAME:
            # max_skew 1 against a minimum of 0 (a fresh node is always a
            # domain): a new member lands only where it is the first
            per_host = collections.Counter(host for host, _, _ in members)
            over = sorted({str(host) for host, _, new in members if new and per_host[host] > 1})
            if over:
                bad.append(f"hostname spread {dict(selector)}: a pod joined a member on {over[:3]}")
        elif kind == "spread":
            # max_skew 1: every pod goes to a zone at the minimum, so a zone
            # that took a pod ends at most one above the final minimum.  A new
            # node that still lists several zones may launch in any of them:
            # it is counted nowhere and allowed for everywhere.
            final = collections.Counter(where[0] for _, where, _ in members if len(where) == 1)
            loose = sum(1 for _, where, _ in members if len(where) > 1)
            took = {where[0] for _, where, new in members if new and len(where) == 1}
            least = min(final.get(z, 0) for z in zones)
            over = {z: final[z] for z in took if final[z] > least + 1 + loose}
            if over:
                bad.append(f"zone spread {dict(selector)}: {over} took pods over a minimum "
                           f"of {least} (+{loose} unpinned)")
        else:
            # the first pod needs a member's zone, and so by induction do all
            held = {where[0] for _, where, new in members if not new}
            stray = sorted({z for _, where, new in members if new for z in where} - held)
            if held and stray:
                bad.append(f"zone affinity {dict(selector)}: pods placed in {stray}, "
                           f"members are bound in {sorted(held)}")
    return bad[:5]
