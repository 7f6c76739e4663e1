"""The plain reference of ``assorted_cycle``: the guarantees its ``correct``
holds over EVERY new node and EVERY spread Deployment of an answer, from the
pods (their labels, node selectors and topology spread constraints), the
answer and the catalog alone — no class, group, plane or count of the
solver under test is consulted.

One pass over the answer's new nodes:

  selectors     every type a node lists carries each value of
                ``kubernetes.io/os`` and ``kubernetes.io/arch`` that a pod on
                the node selects
  pinned        a node holding a pod that spreads on zone lists types whose
                offerings are all in one zone; one holding a pod that spreads
                on capacity type, types whose offerings are all of one
                capacity type (the catalog's types have one offering each, so
                a type fixes both; a node of a capacity-type spread alone may
                still list several zones, as upstream's scheduler leaves it)
  hostname      no node holds two pods of a Deployment that spreads on
                ``kubernetes.io/hostname``

then per Deployment (namespace and ``name`` label) and key: its pods per
domain of the catalog — 3 zones, 2 capacity types, a domain it missed
counting 0 — end within the constraint's maxSkew of each other.

``oracle`` is the cut's comparison with the host scheduler: scheduled,
failed and new nodes equal (the configuration's ``assumed``, "oracle cut:
nodes").

Not a kind: ``manifest.load_kind`` never names this module.
"""

import collections
import json

from benchmark.harness import checks

MAX_MESSAGES = 5  # per guarantee
ZONE = "topology.kubernetes.io/zone"
HOSTNAME = "kubernetes.io/hostname"
CAPACITY_TYPE = "karpenter.sh/capacity-type"
SELECTED = ("kubernetes.io/os", "kubernetes.io/arch")


def _offered(catalog: list) -> dict:
    """``{type name: ({key: values}, zones, capacity types)}``."""
    out = {}
    for it in catalog:
        offerings = it.offerings.available()
        labels = {key: set(it.requirements.get(key).values_list())
                  for key in SELECTED if it.requirements.has(key)}
        out[it.name] = (labels, {o.zone for o in offerings},
                        {o.capacity_type for o in offerings})
    return out


def guarantees(reply: dict, pods: list, catalog: list) -> list:
    """A message per node or Deployment that breaks a guarantee (at most
    ``MAX_MESSAGES`` a guarantee)."""
    types = _offered(catalog)
    domains = {ZONE: sorted({z for _, zs, _ in types.values() for z in zs}),
               CAPACITY_TYPE: sorted({c for _, _, cs in types.values() for c in cs})}
    selectors, pinned, hostname, skew = [], [], [], []
    counts = collections.defaultdict(collections.Counter)
    max_skew = {}
    for k, node in enumerate(reply["newNodes"]):
        names = node["instanceTypes"]
        on_node = [pods[i] for i in node["podIndices"]]
        wanted = {(key, value) for p in on_node
                  for key, value in p.spec.node_selector.items() if key in SELECTED}
        for key, value in sorted(wanted):
            off = [n for n in names if value not in types[n][0].get(key, ())]
            if off:
                selectors.append(f"node {k}: {len(off)} listed type(s) lack {key}={value} "
                                 f"a pod selects, e.g. {off[0]}")
        where = {ZONE: set().union(*(types[n][1] for n in names)) if names else set(),
                 CAPACITY_TYPE: set().union(*(types[n][2] for n in names)) if names else set()}
        per_deployment = collections.Counter()
        for pod in on_node:
            deployment = (pod.namespace, pod.metadata.labels.get("name"))
            for c in pod.spec.topology_spread_constraints:
                if c.topology_key == HOSTNAME:
                    per_deployment[deployment] += 1
                elif c.topology_key in where:
                    max_skew[deployment, c.topology_key] = c.max_skew
                    if len(where[c.topology_key]) == 1:
                        counts[deployment, c.topology_key][next(iter(where[c.topology_key]))] += 1
                    else:
                        pinned.append(f"node {k} holds a {c.topology_key} spread pod of "
                                      f"{deployment[0]}/{deployment[1]} and its types are "
                                      f"offered in {sorted(where[c.topology_key])}")
        for (namespace, name), n in per_deployment.items():
            if n > 1:
                hostname.append(f"hostname spread {namespace}/{name}: node {k} holds {n} "
                                "of its pods")
    for (deployment, key), seen in sorted(counts.items()):
        values = [seen.get(d, 0) for d in domains[key]]
        stray = sorted(set(seen) - set(domains[key]))
        if stray or max(values) - min(values) > max_skew[deployment, key]:
            skew.append(f"{key} spread {deployment[0]}/{deployment[1]}: "
                        f"{dict(zip(domains[key], values))} (outside the catalog: {stray}) "
                        f"is not within {max_skew[deployment, key]}")
    bad = []
    for held in (selectors, pinned, hostname, skew):
        bad += held[:MAX_MESSAGES]
    return bad


def oracle(reply: dict, pods: list, catalog: list, provisioners: list) -> list:
    """The cut: a valid answer, and the host's scheduled, failed and fleet."""
    bad = (checks.accounting(reply, len(pods)) + checks.capacity(reply, pods, catalog)
           + guarantees(reply, pods, catalog))
    kernel, host = checks.counts(reply), checks.oracle_totals(pods, catalog, provisioners)
    print(json.dumps({"oracle_cut": {"kernel": kernel, "host": host}}), flush=True)
    bad += [f"{key}: kernel {kernel[key]} vs host {host[key]}"
            for key in ("nodes", "scheduled", "failed", "residual") if kernel[key] != host[key]]
    return [f"oracle cut: {f}" for f in bad]
