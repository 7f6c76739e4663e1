"""The plain reference of ``operator_cycle`` and the guarantees its ``correct``
holds, from what a unit left in the API — the batch's pods, the recorder's
events, the store's nodes, the provider's machines — the catalog, and the
unit's one ``/SolveClasses`` reply.  No class, plane, count or decision of
the solver or of a controller is consulted; the cut alone asks the host
scheduler (``checks.oracle_totals``).

An ``Outcome`` is plain data, taken by ``operator_cycle.settle`` before the
scale-down changes the objects (the termination controller cordons a node and
strips its finalizer).  Per outcome:

  ``nominations``  each pod of the batch nominated exactly once, none failed
  ``machines``     each nominated node is in the store with the termination
                   finalizer and a provider id whose machine the provider
                   holds; no machine without a node (``machine_leaks``' rule)
  ``capacity``     per node, the summed requests of its nominated pods fit the
                   allocatable of the instance type the provider LAUNCHED
                   (stronger than the wire's "fits every listed type")
  ``topology``     a hostname-spread pod shares its node with no member of
                   its group; a zone-spread group's zones end within 1 of
                   each other over the catalog's zones; the nodes of a
                   zone-affinity group share a zone — by the launched nodes'
                   labels, every group of the batch
  ``decided``      the operator launched what the sidecar decided: as many
                   nodes as the reply has ``newNodes``, the pods-a-node
                   multiset equal to the reply's (the sum of a node's
                   ``classCounts``, the wire's own form of ``podIndices``),
                   and among the nodes of one pod count each launched type
                   listed by a reply node of that count, matched one to one
                   (where a count has one node on each side, also the
                   cheapest listed: the fake provider's own rule).  Never pod
                   by pod: the reply indexes the operator's class-major order,
                   which a reference must not re-derive.

and across outcomes ``same`` (the last outcome of a draw equals its warm-up
outcome as multisets of (type, zone, pods) — never names), ``leaks`` (after
the last tear-down the provider has deleted what it created) and ``cut`` (the
operator's totals on the oracle batch equal the host scheduler's).

Not a kind: ``manifest.load_kind`` never names this module.
"""

import collections
from typing import NamedTuple

from benchmark.harness import checks
from benchmark.traffic.kinds.cluster_reference import _constraint
from benchmark.traffic.kinds.deployment_reference import catalog_zones

RESOURCES = checks.RESOURCES
MAX_MESSAGES = 5  # per guarantee
FINALIZER = "karpenter.sh/termination"
INSTANCE_TYPE = "node.kubernetes.io/instance-type"
ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"


class NodeView(NamedTuple):
    """What the store held of one node when the unit returned."""

    name: str
    provider_id: str
    finalizers: tuple
    instance_type: str
    zone: str


class Outcome(NamedTuple):
    pods: list  # the batch, as created in the store
    nominated: dict  # pod uid -> [node name per Nominated event]
    failed: list  # pod uids with a FailedScheduling event
    nodes: list  # NodeView per node in the store
    machines: list  # provider id per machine the provider held
    reply: dict  # the unit's /SolveClasses answer as the wire carried it, unpacked


def read_events(events) -> tuple:
    """``(nominated, failed)`` from the recorder's events: a copy of the one
    place the program's own tests parse the message
    (``testing.harness.nominations``), kept apart from it."""
    nominated, failed = collections.defaultdict(list), []
    for event in events:
        if event.reason == "Nominated":
            nominated[event.involved_object.uid].append(event.message.rsplit(" ", 1)[-1])
        elif event.reason == "FailedScheduling":
            failed.append(event.involved_object.uid)
    return dict(nominated), failed


def view(node) -> NodeView:
    labels = node.metadata.labels
    return NodeView(node.name, node.spec.provider_id, tuple(node.metadata.finalizers),
                    labels.get(INSTANCE_TYPE, ""), labels.get(ZONE, ""))


def totals(outcome: Outcome) -> dict:
    """The operator's totals, in ``checks.oracle_totals``' keys."""
    return {
        "nodes": len(outcome.nodes),
        "scheduled": sum(1 for p in outcome.pods if len(outcome.nominated.get(p.uid, ())) == 1),
        "failed": len(outcome.failed),
        "residual": 0,
    }


def nominations(outcome: Outcome) -> list:
    bad = []
    twice = [p.name for p in outcome.pods if len(outcome.nominated.get(p.uid, ())) > 1]
    never = [p.name for p in outcome.pods if p.uid not in outcome.nominated]
    strangers = set(outcome.nominated) - {p.uid for p in outcome.pods}
    if twice:
        bad.append(f"{len(twice)} pod(s) nominated more than once: {twice[:3]}")
    if never:
        bad.append(f"{len(never)} pod(s) of the batch not nominated: {never[:3]}")
    if strangers:
        bad.append(f"{len(strangers)} nomination(s) of pods that are not of the batch")
    if outcome.failed:
        bad.append(f"{len(outcome.failed)} pod(s) failed to schedule")
    return bad


def machines(outcome: Outcome) -> list:
    by_name = {n.name: n for n in outcome.nodes}
    held = set(outcome.machines)
    bad = []
    for name in sorted({name for names in outcome.nominated.values() for name in names}):
        node = by_name.get(name)
        if node is None:
            bad.append(f"nominated node {name} is not in the store")
        elif FINALIZER not in node.finalizers:
            bad.append(f"node {name} lacks the termination finalizer")
        elif node.provider_id not in held:
            bad.append(f"node {name}: the provider holds no machine {node.provider_id!r}")
    orphans = sorted(held - {n.provider_id for n in outcome.nodes})
    if orphans:
        bad.append(f"{len(orphans)} machine(s) without a node: {orphans[:3]}")
    return bad[:MAX_MESSAGES]


def _pods_by_node(outcome: Outcome) -> dict:
    """``{node name: [pod]}`` over every nomination."""
    by_node = collections.defaultdict(list)
    for pod in outcome.pods:
        for name in outcome.nominated.get(pod.uid, ()):
            by_node[name].append(pod)
    return by_node


def capacity(outcome: Outcome, catalog: list) -> list:
    from karpenter_core_tpu.utils import resources as resources_util

    allocatable = {it.name: it.allocatable() for it in catalog}
    type_of = {n.name: n.instance_type for n in outcome.nodes}
    bad = []
    for name, pods in sorted(_pods_by_node(outcome).items()):
        allowed = allocatable.get(type_of.get(name))
        if allowed is None:
            bad.append(f"node {name}: launched type {type_of.get(name)!r} is not of the catalog")
            continue
        need = resources_util.requests_for_pods(*pods)  # with the 'pods' count
        over = {r: (need.get(r, 0.0), allowed.get(r, 0.0)) for r in RESOURCES
                if need.get(r, 0.0) > allowed.get(r, 0.0) * (1 + 1e-9) + 1e-9}
        if over:
            bad.append(f"node {name} ({type_of[name]}): {len(pods)} pods need over what the "
                       f"launched type allows (resource: need, allowed): {over}")
    return bad[:MAX_MESSAGES]


def topology(outcome: Outcome, zones: list) -> list:
    """Every group of the batch: a group is one (kind, topology key,
    selector) and its members the batch's pods the selector matches."""
    zone_of = {n.name: n.zone for n in outcome.nodes}
    bad = []
    for kind, key, selector in sorted({c for c in map(_constraint, outcome.pods) if c}):
        members = [name for pod in outcome.pods
                   if all(pod.metadata.labels.get(k) == v for k, v in selector)
                   for name in outcome.nominated.get(pod.uid, ())]
        if kind == "spread" and key == HOSTNAME:
            shared = sorted(n for n, c in collections.Counter(members).items() if c > 1)
            if shared:
                bad.append(f"hostname spread {dict(selector)}: node(s) {shared[:3]} hold "
                           "more than one member")
        elif kind == "spread":
            per_zone = collections.Counter(zone_of.get(name, "") for name in members)
            counts = [per_zone.get(z, 0) for z in zones]
            if set(per_zone) - set(zones) or max(counts) - min(counts) > 1:
                bad.append(f"zone spread {dict(selector)}: {dict(per_zone)} over zones {zones}")
        else:
            held = sorted({zone_of.get(name, "") for name in members})
            if len(held) > 1:
                bad.append(f"zone affinity {dict(selector)}: its pods' nodes are in {held}")
    return bad[:MAX_MESSAGES]


def _matched(launched: list, listed: list) -> bool:
    """A one-to-one match of launched types onto reply nodes that list them
    (augmenting paths; a pod count holds a handful of nodes)."""
    owner = {}  # reply node -> launched node

    def place(i: int, seen: set) -> bool:
        for j, names in enumerate(listed):
            if launched[i] in names and j not in seen:
                seen.add(j)
                if j not in owner or place(owner[j], seen):
                    owner[j] = i
                    return True
        return False

    return all(place(i, set()) for i in range(len(launched)))


def decided(outcome: Outcome, catalog: list) -> list:
    reply_nodes = outcome.reply["newNodes"]
    if len(outcome.nodes) != len(reply_nodes):
        return [f"{len(outcome.nodes)} nodes launched, the reply has {len(reply_nodes)} newNodes"]
    by_node = _pods_by_node(outcome)
    launched = collections.defaultdict(list)  # pods on the node -> launched types
    for node in outcome.nodes:
        launched[len(by_node.get(node.name, ()))].append(node.instance_type)
    listed = collections.defaultdict(list)  # pods on the node -> listed types
    for entry in reply_nodes:
        # the wire's form of a node's pods: [(class, count)]; never indices
        pods = sum(count for _cls, count in entry["classCounts"])
        listed[pods].append(set(entry["instanceTypes"]))
    if {k: len(v) for k, v in launched.items()} != {k: len(v) for k, v in listed.items()}:
        return ["pods a node: launched "
                f"{sorted((k, len(v)) for k, v in launched.items())} vs the reply's "
                f"{sorted((k, len(v)) for k, v in listed.items())} (pods, nodes)"]
    price = {it.name: min(o.price for o in it.offerings.available()) for it in catalog}
    order = {it.name: i for i, it in enumerate(catalog)}
    bad = []
    for count in sorted(launched):
        if not _matched(launched[count], listed[count]):
            bad.append(f"nodes of {count} pods: launched {sorted(launched[count])[:4]} "
                       "are not each listed by a reply node of that pod count")
        elif len(launched[count]) == 1:
            cheapest = min(listed[count][0], key=lambda name: (price[name], order[name]))
            if launched[count][0] != cheapest:
                bad.append(f"the node of {count} pods launched {launched[count][0]}, "
                           f"the cheapest listed is {cheapest}")
    return bad[:MAX_MESSAGES]


def check(outcome: Outcome, catalog: list) -> list:
    """Every per-outcome guarantee."""
    return (nominations(outcome) + machines(outcome) + capacity(outcome, catalog)
            + topology(outcome, catalog_zones(catalog)) + decided(outcome, catalog))


def fleet(outcome: Outcome) -> collections.Counter:
    """The outcome as a multiset: (launched type, zone, pods on the node)."""
    by_node = _pods_by_node(outcome)
    return collections.Counter(
        (n.instance_type, n.zone, len(by_node.get(n.name, ()))) for n in outcome.nodes)


def same(last: Outcome, warm: Outcome) -> list:
    a, b = fleet(last), fleet(warm)
    if a == b:
        return []
    return [f"the last outcome differs from the warm-up outcome: {sum(a.values())} vs "
            f"{sum(b.values())} nodes; only last {sorted((a - b).items())[:3]}, "
            f"only warm-up {sorted((b - a).items())[:3]}"]


def leaks(provider, kube, cluster) -> list:
    """After the last tear-down."""
    bad = []
    if len(provider.create_calls) != len(provider.delete_calls):
        bad.append(f"the provider created {len(provider.create_calls)} machines and "
                   f"deleted {len(provider.delete_calls)}")
    left = {"machines": len(provider.created_machines()), "nodes": len(kube.list_nodes()),
            "pods": len(kube.list_pods()), "state nodes": len(cluster.snapshot_nodes())}
    if any(left.values()):
        bad.append(f"left after the last scale-down: {left}")
    return bad


def cut(outcome: Outcome, catalog: list, provisioners: list) -> list:
    """The operator's totals on the oracle batch against the host scheduler's
    on the same pods."""
    mine, host = totals(outcome), checks.oracle_totals(outcome.pods, catalog, provisioners)
    return [] if mine == host else [f"oracle cut: operator {mine} vs host {host}"]
