"""The plain reference of ``deployment_cycle``: the guarantees its ``correct``
holds over EVERY Deployment of an answer, from the API objects and the
generator's own table alone — no class, group, plane or count of the solver
under test is consulted.

One pass over the answer's placements, grouped by the pods' namespace and
``name`` label (never groups x pods), then per Deployment:

  hostname spread  no new node holds two of its pods
  zone spread      its zones end within 1 of each other over the catalog's
                   zones; a new node that still lists several zones may launch
                   in any of them: it is counted nowhere and allowed for
                   everywhere (as ``cluster_reference.topology`` counts it)
  zone affinity    all of its pods in one zone: the zone lists of the nodes
                   that hold them share a zone
  the fleet        new nodes >= the replicas of the largest hostname-spread
                   Deployment (a bound no packer beats)

and ``oracle``, the cut's comparison with the host scheduler: scheduled and
failed equal; new nodes equal OR FEWER, never more.  Fewer happens (PERF.md
section 6, PR 31: 3 seeds of 183, always by two nodes): the host gives each pod
to the emptiest in-flight node, so the replicas of one zone-affinity
Deployment early in its queue land on as many nodes as it has replicas and pin
every one of them to its zone; the zone-spread pods that come later then need
a new node in each other zone.  The kernel fills a class onto the first nodes
with room and stays at the bound.  Which node takes which pod is a tie either
scheduler may break its own way (upstream's own sort is unstable); what the
cut holds the kernel to is a fleet no larger than the host's, every placement
of it checked from the API objects by the guarantees above, by
``checks.accounting`` and by ``checks.capacity``.

Not a kind: ``manifest.load_kind`` never names this module.
"""

import collections
import json

from benchmark.harness import checks

MAX_MESSAGES = 5  # per guarantee


def catalog_zones(catalog: list) -> list:
    return sorted({o.zone for it in catalog for o in it.offerings.available()})


def largest_hostname_spread(deployments: list) -> int:
    return max((d.replicas for d in deployments
                if d.kind == "spread" and d.topology == "hostname"), default=0)


def placements(reply: dict, pods: list) -> dict:
    """``{(namespace, name): [(new node's index, its zones), ...]}``, a row per
    placed pod, in one pass over the answer."""
    placed = collections.defaultdict(list)
    for k, new in enumerate(reply["newNodes"]):
        where = (k, tuple(new["zones"]))
        for i in new["podIndices"]:
            pod = pods[i]
            placed[pod.namespace, pod.metadata.labels["name"]].append(where)
    return placed


def guarantees(reply: dict, pods: list, deployments: list, zones: list) -> list:
    """A message per Deployment that breaks its guarantee (at most
    ``MAX_MESSAGES`` a guarantee), and one if the fleet is under its bound."""
    placed = placements(reply, pods)
    hostname, zone_spread, affinity, missing = [], [], [], []
    for d in deployments:
        rows = placed.get(d.key, ())
        if len(rows) != d.replicas:
            missing.append(f"Deployment {d.namespace}/{d.name}: {len(rows)} of "
                           f"{d.replicas} replicas are on new nodes")
        if d.kind == "spread" and d.topology == "hostname":
            per_node = collections.Counter(k for k, _ in rows)
            shared = sorted(k for k, n in per_node.items() if n > 1)
            if shared:
                hostname.append(f"hostname spread {d.namespace}/{d.name}: new node(s) "
                                f"{shared[:3]} hold more than one of its pods")
        elif d.kind == "spread":
            final = collections.Counter(where[0] for _, where in rows if len(where) == 1)
            loose = sum(1 for _, where in rows if len(where) != 1)
            counts = [final.get(z, 0) for z in zones]
            stray = sorted(set(final) - set(zones))
            if stray or max(counts) - min(counts) > 1 + loose:
                zone_spread.append(
                    f"zone spread {d.namespace}/{d.name}: {dict(zip(zones, counts))} "
                    f"(+{loose} unpinned, outside the catalog: {stray}) is not within 1")
        elif d.kind == "affinity":
            shared = set(rows[0][1]) if rows else set()
            for _, where in rows:
                shared &= set(where)
            if rows and not shared:
                seen = sorted({where for _, where in rows})
                affinity.append(f"zone affinity {d.namespace}/{d.name}: its pods' nodes "
                                f"share no zone: {seen[:4]}")
    bad = []
    for held in (missing, hostname, zone_spread, affinity):
        bad += held[:MAX_MESSAGES]
    bound = largest_hostname_spread(deployments)
    if len(reply["newNodes"]) < bound:
        bad.append(f"{len(reply['newNodes'])} new nodes for a hostname-spread Deployment "
                   f"of {bound} replicas")
    return bad


def oracle(reply: dict, pods: list, deployments: list, catalog: list,
           provisioners: list, zones: list) -> list:
    """The cut: a valid answer, the host's scheduled and failed, and a fleet
    no larger than the host's (its own floor is among ``guarantees``)."""
    bad = (checks.accounting(reply, len(pods)) + checks.capacity(reply, pods, catalog)
           + guarantees(reply, pods, deployments, zones))
    kernel, host = checks.counts(reply), checks.oracle_totals(pods, catalog, provisioners)
    print(json.dumps({"oracle_cut": {"kernel": kernel, "host": host}}), flush=True)
    bad += [f"{key}: kernel {kernel[key]} vs host {host[key]}"
            for key in ("scheduled", "failed", "residual") if kernel[key] != host[key]]
    if kernel["nodes"] > host["nodes"]:
        bad.append(f"nodes: kernel {kernel['nodes']} opens more than the host's {host['nodes']}")
    return [f"oracle cut: {f}" for f in bad]
