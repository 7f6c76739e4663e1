"""The plain reference of ``consolidate_cycle`` and the guarantees its
``correct`` holds.

``command`` judges one ``/Consolidate`` answer from the API objects the
generator made and the catalog alone — no plane, count or decision of the
sweep under test is consulted:

  the removed nodes are a PREFIX of the disruption order sent, two or more
  resource by resource, what the pods of the removed nodes request fits what
    is free on the nodes that stay plus the replacement's smallest listed type
    (any listed type may launch; prices are linear in size, so the smallest is
    the cheapest)
  every type a replacement lists launches for less than the summed price of
    the nodes it replaces; none is the type of a removed node at no saving;
    never spot for a set that is all spot
  ``podRefs`` name each displaced pod exactly once

``host_verdict`` is the oracle cut: the host's own simulation
(``controllers/deprovisioning.py``: ``validate_command``, what the controller
runs before it acts on any command — validation.go:110-172) must accept the
served command, and action and prefix size must equal the host binary
search's (``first_n_consolidation_option``, multinodeconsolidation.go:74-114).
A larger prefix that the host accepts is allowed and reported; a smaller one
is a failure.

Not a kind: ``manifest.load_kind`` never names this module.
"""

import json
from types import SimpleNamespace

from benchmark.harness import checks

RESOURCES = checks.RESOURCES
SPOT, ON_DEMAND = "spot", "on-demand"


def line(**fields) -> str:
    return json.dumps(fields)


def launch_price(instance_type, zones: list, capacity_types: list) -> float:
    """What launching this type may cost at worst: the dearest available
    offering in the zones allowed, of spot where spot is allowed (it is
    preferred), else of on-demand.  An empty list allows every value."""
    for capacity_type in (SPOT, ON_DEMAND):
        if capacity_types and capacity_type not in capacity_types:
            continue
        prices = [o.price for o in instance_type.offerings.available()
                  if o.capacity_type == capacity_type and (not zones or o.zone in zones)]
        if prices:
            return max(prices)
    return float("inf")


def _requests(pods: list) -> list:
    from karpenter_core_tpu.utils import resources as resources_util

    total = [0.0] * len(RESOURCES)
    for pod in pods:
        need = resources_util.requests_for_pods(pod)
        for r, name in enumerate(RESOURCES):
            total[r] += need.get(name, 0.0)
    return total


def command(reply: dict, order: list, cluster: list, catalog: list) -> list:
    """What is wrong with one answer; ``order`` is the candidates as sent."""
    from karpenter_core_tpu.apis import labels as labels_api

    action, removed = reply.get("action"), list(reply.get("nodesToRemove", []))
    replacements = list(reply.get("replacements", []))
    if action == "do nothing":
        return (["do nothing, yet it removes nodes or launches a replacement"]
                if removed or replacements else [])
    if action not in ("delete", "replace"):
        return [f"action {action!r}"]
    bad = []
    k = len(removed)
    if k < 2 or removed != [c["name"] for c in order[:k]]:
        return [f"the {k} removed nodes are not a prefix (of two or more) of the order sent"]
    if len(replacements) != (1 if action == "replace" else 0):
        return [f"{action} with {len(replacements)} replacements"]
    by_name = {node.name: (node, bound) for node, bound in cluster}
    types = {it.name: it for it in catalog}
    gone = set(removed)

    # capacity, from the API objects alone
    need = _requests([pod for name in removed for pod in by_name[name][1]])
    room = [0.0] * len(RESOURCES)
    for node, bound in cluster:
        if node.name in gone:
            continue
        held = _requests(bound)
        for r, name in enumerate(RESOURCES):
            room[r] += node.status.allocatable.get(name, 0.0) - held[r]
    for replacement in replacements:
        listed = [types[name] for name in replacement["instanceTypes"] if name in types]
        if not listed or len(listed) != len(replacement["instanceTypes"]):
            return ["the replacement lists no type, or one the catalog does not have"]
        smallest = [min(it.allocatable().get(name, 0.0) for it in listed) for name in RESOURCES]
        asked = [replacement["requests"].get(name, 0.0) for name in RESOURCES]
        if any(a > s * (1 + 1e-6) + 1e-6 for a, s in zip(asked, smallest)):
            bad.append(f"the replacement's requests {asked} of {RESOURCES} pass its "
                       f"smallest listed type's {smallest}")
        room = [r + s for r, s in zip(room, smallest)]
    if any(n > r * (1 + 1e-6) + 1e-6 for n, r in zip(need, room)):
        bad.append(f"the removed nodes' pods request {need} of {RESOURCES}; the nodes that "
                   f"stay and the replacement have room for {room}")

    # price rules
    if replacements:
        price_of, by_type = {}, {}
        for name in removed:
            labels = by_name[name][0].metadata.labels
            it = types[labels[labels_api.LABEL_INSTANCE_TYPE_STABLE]]
            price_of[name] = launch_price(it, [labels[labels_api.LABEL_TOPOLOGY_ZONE]],
                                          [labels[labels_api.LABEL_CAPACITY_TYPE]])
            by_type[it.name] = min(by_type.get(it.name, float("inf")), price_of[name])
        total = sum(price_of.values())
        replacement = replacements[0]
        zones, capacity_types = replacement["zones"], replacement["capacityTypes"]
        for name in replacement["instanceTypes"]:
            price = launch_price(types[name], zones, capacity_types)
            if not price < total:
                bad.append(f"replacement type {name} may launch at {price}, the removed "
                           f"nodes cost {total}")
            elif not price < by_type.get(name, float("inf")):
                bad.append(f"replacement type {name} is a removed node's type at no saving "
                           f"({price} against {by_type[name]})")
        all_spot = all(by_name[name][0].metadata.labels[labels_api.LABEL_CAPACITY_TYPE] == SPOT
                       for name in removed)
        if all_spot and (not capacity_types or SPOT in capacity_types):
            bad.append("a spot replacement for nodes that are all spot")
        # every displaced pod named once
        refs = [tuple(ref) for ref in replacement["podRefs"]]
        want = {(name, i) for name in removed for i in range(len(by_name[name][1]))}
        if len(refs) != len(set(refs)):
            bad.append(f"podRefs name {len(refs) - len(set(refs))} pods more than once")
        if set(refs) != want:
            bad.append(f"podRefs name {len(set(refs))} pods, the removed nodes hold {len(want)}")
    return bad[:5]


def host_environment(cluster: list, catalog: list, provisioner, pending=()) -> tuple:
    """``(environment, {node name: CandidateNode})``: the cluster as the host's
    controllers hold it — kube client, cluster state, provisioning — and the
    candidates ``candidate_nodes`` finds in it for multi-node consolidation."""
    from karpenter_core_tpu.controllers.deprovisioning import candidate_nodes
    from karpenter_core_tpu.testing.harness import make_environment

    env = make_environment(instance_types=catalog)
    env.kube.create(provisioner)
    for node, bound in cluster:
        env.kube.create(node)
        for pod in bound:
            env.kube.create(pod)
    for pod in pending:
        env.kube.create(pod)
    host = env.deprovisioning.multi_node_consolidation
    return env, {c.node.name: c for c in candidate_nodes(
        env.cluster, env.kube, env.clock, env.provider, host.should_deprovision)}


def host_verdict(reply: dict, order: list, cluster: list, catalog: list, provisioner) -> dict:
    """``{"failures": [...], "report": {...}}``: the served command under the
    host's validation, and beside the host's own binary search over the same
    candidates in the same order."""
    from karpenter_core_tpu.controllers.deprovisioning import Action, Command

    env, found = host_environment(cluster, catalog, provisioner)
    host = env.deprovisioning.multi_node_consolidation
    if len(found) != len(order):
        return {"failures": [f"oracle cut: the host finds {len(found)} candidates of "
                             f"{len(order)} sent"], "report": {}}
    candidates = [found[c["name"]] for c in order]
    types = {it.name: it for it in catalog}
    served = Command(
        Action(reply["action"]),
        [found[name].node for name in reply["nodesToRemove"]],
        [SimpleNamespace(instance_type_options=[types[name] for name in r["instanceTypes"]])
         for r in reply["replacements"]],
    )
    wanted = host.first_n_consolidation_option(candidates, len(candidates))
    k, host_k = len(served.nodes_to_remove), len(wanted.nodes_to_remove)
    accepted = served.action == Action.DO_NOTHING or host.validate_command(served, candidates)
    report = {"nodes": len(cluster), "pods": sum(len(bound) for _, bound in cluster),
              "served": [served.action.value, k], "host": [wanted.action.value, host_k],
              "host_accepts": bool(accepted)}
    failures = []
    if not accepted:
        failures.append(f"oracle cut: the host's simulation refuses the served command "
                        f"({served.action.value} {k})")
    if k < host_k:
        failures.append(f"oracle cut: served {served.action.value} {k}, the host's binary "
                        f"search removes {host_k}")
    elif k == host_k and served.action != wanted.action:
        failures.append(f"oracle cut: served {served.action.value} {k}, the host "
                        f"{wanted.action.value} {host_k}")
    return {"failures": failures, "report": report}
