"""The plain reference of ``drawn_cycle``: an answer replayed pod by pod and
every constrained pod held to upstream's rules, from the API objects, the
answer and the catalog's zones alone — no class, group, plane or count of the
solver under test is consulted.

Upstream's rules (``topologygroup.go`` / ``topology.go``, as
``solver/topology.py`` ports them), for the suite's mix as drawn:

  hostname spread  the min is 0 (a new node is always a domain), so the pod's
                   node holds at most ``maxSkew`` members of the group with
                   it, itself counted where it selects itself
  zone spread      over the catalog's zones, count + self - min <= maxSkew,
                   for every zone the pod's node may launch in
  hostname / zone  the pod lands where a pod its selector matches already is
  affinity         (its node, or its zone), or bootstraps when none is
                   counted anywhere and it selects itself

The replay takes the pods in the order the scheduler's queue takes them
(``queue.go:74-110``: cpu desc, memory desc, creation time), so a member
counts for the pods after it.  Pods that tie on all three are replicas of one
workload (``cluster_cycle.by_workload`` stamps them so) and either scheduler
may place them in any order: a workload is admissible where SOME order of its
pods is (``_replay_workload`` takes the admissible ones first, the one in the
least counted zone first).

A pod counts on its node for the hostname key.  For the zone key there are
two tallies, and a placement stands where EITHER admits it:

  as upstream counts  in its node's zone only if the node was narrowed to one
                      zone when the pod came — by this pod or an earlier one
                      of a zone term (upstream's ``record`` takes a pod with a
                      single domain only: a generic pod on a node that a later
                      spread pod narrows is never counted)
  as launched         in the zone the answer lists for its node, from the
                      moment it is placed: where the pods are once the nodes
                      launch

A node the answer lists with several zones may launch in any of them: its pods
are counted in no zone, and a spread pod on it is held in each.  The host
scheduler keeps the first tally by construction; a solver that reads its
nodes' zones as they are narrowed later keeps the second; a placement that
breaks both is wrong by any reading.

Not a kind: ``manifest.load_kind`` never names this module.
"""

import collections
from typing import NamedTuple, Optional

MAX_MESSAGES = 5  # per rule
TALLIES = ("as upstream counts", "as launched")


class Rule(NamedTuple):
    """A constrained pod's one term, read from its API object."""

    kind: str  # spread | affinity
    host: bool  # the hostname key, else the zone key
    namespace: str
    selector: tuple  # match_labels as sorted items
    max_skew: int

    def selects(self, pod) -> bool:
        labels = pod.metadata.labels
        return pod.namespace == self.namespace and all(
            labels.get(k) == v for k, v in self.selector)


def rule_of(pod) -> Optional[Rule]:
    """The pod's one topology term, or None for a generic pod; a pod of two
    terms is not of this mix."""
    from karpenter_core_tpu.apis import labels as labels_api

    terms = []
    for cs in pod.spec.topology_spread_constraints:
        terms.append(Rule("spread", cs.topology_key == labels_api.LABEL_HOSTNAME,
                          pod.namespace, tuple(sorted(cs.label_selector.match_labels.items())),
                          int(cs.max_skew)))
    affinity = pod.spec.affinity
    if affinity is not None and affinity.pod_affinity is not None:
        for term in affinity.pod_affinity.required:
            terms.append(Rule("affinity", term.topology_key == labels_api.LABEL_HOSTNAME,
                              pod.namespace,
                              tuple(sorted(term.label_selector.match_labels.items())), 0))
    if len(terms) > 1:
        raise ValueError(f"pod {pod.name}: {len(terms)} topology terms; the mix has one a pod")
    return terms[0] if terms else None


def catalog_zones(catalog: list) -> list:
    return sorted({o.zone for it in catalog for o in it.offerings.available()})


def queue_key(pod) -> tuple:
    """The host queue's order less its last tie-break (the uid)."""
    from karpenter_core_tpu.utils import resources as resources_util

    need = resources_util.requests_for_pods(pod)
    return (-need.get(resources_util.CPU, 0.0), -need.get(resources_util.MEMORY, 0.0),
            pod.metadata.creation_timestamp)


class _Counts:
    """Members of each rule's group placed so far: by node, anywhere, and by
    zone in each of the two tallies."""

    def __init__(self, rules: list, zones: list) -> None:
        self.rules = rules
        self.zones = zones
        self.narrowed: set = set()  # nodes a zone term has narrowed to one zone
        self.by_node = {r: collections.Counter() for r in rules}
        self.placed = collections.Counter()
        self.by_zone = [{r: collections.Counter() for r in rules} for _ in TALLIES]

    def record(self, pod, rule: Optional[Rule], node, zones: tuple) -> None:
        one = zones[0] if len(zones) == 1 else None
        if one is not None and rule is not None and not rule.host:
            self.narrowed.add(node)
        counted = (one if node in self.narrowed else None, one)
        for r in self.rules:
            if r.selects(pod):
                self.by_node[r][node] += 1
                self.placed[r] += 1
                for tally, zone in zip(self.by_zone, counted):
                    if zone is not None:
                        tally[r][zone] += 1


def _admits_zone(counts, zones_all: list, r: Rule, own: int, zones: tuple) -> Optional[str]:
    """The zone key's rule against one tally (``{zone: count}``)."""
    if r.kind == "spread":
        least = min(counts[z] for z in zones_all)
        over = {z: counts[z] for z in zones if counts[z] + own - least > r.max_skew}
        if over:
            return f"zone count(s) {over} against a minimum of {least}"
        return None
    held = {z for z in zones_all if counts[z] > 0}
    if held and not set(zones) <= held:
        return f"placed in {sorted(zones)}, members are in {sorted(held)}"
    if not held and not own:
        return "no member counted in a zone, and it does not select itself"
    return None


def _admits(counts: _Counts, r: Rule, pod, node, zones: tuple) -> Optional[str]:
    """None where upstream's rule admits the pod on ``node`` (which may
    launch in ``zones``), else why not."""
    own = 1 if r.selects(pod) else 0
    if r.kind == "spread" and r.host:
        held = counts.by_node[r][node]
        if held + own > r.max_skew:
            return f"hostname spread: its node holds {held} member(s) already"
        return None
    if r.host:
        if counts.by_node[r][node] == 0 and not (counts.placed[r] == 0 and own):
            return (f"hostname affinity: no member on its node "
                    f"({counts.placed[r]} placed elsewhere)")
        return None
    why = [_admits_zone(tally[r], counts.zones, r, own, zones) for tally in counts.by_zone]
    if None in why:
        return None
    return f"zone {r.kind}: " + "; ".join(
        f"{w} ({name})" for w, name in zip(why, TALLIES))


def _replay_workload(counts: _Counts, rows: list) -> list:
    """Place one workload's pods in some admissible order where there is one:
    the admissible first and, of those, the one in the least counted zone.
    Returns ``(pod, why)`` for each pod no order admits."""
    bad, left = [], list(rows)
    while left:
        best = best_key = None
        for j, (pod, rule, node, zones) in enumerate(left):
            if rule is None or _admits(counts, rule, pod, node, zones) is None:
                key = (counts.by_zone[0][rule][zones[0]]
                       if rule is not None and len(zones) == 1 else 0)
                if best is None or key < best_key:
                    best, best_key = j, key
        if best is None:  # no order admits what is left: report one, count it
            pod, rule, node, zones = left.pop(0)
            bad.append((pod, _admits(counts, rule, pod, node, zones)))
        else:
            pod, rule, node, zones = left.pop(best)
        counts.record(pod, rule, node, zones)
    return bad


def placements(reply: dict) -> dict:
    """``{pod index: (new node's index, its zones)}``."""
    where = {}
    for k, new in enumerate(reply["newNodes"]):
        for i in new["podIndices"]:
            where[i] = (k, tuple(new["zones"]))
    return where


def violations(reply: dict, pods: list, zones: list) -> list:
    """``(pod index, why)`` for every placement of the answer that upstream's
    rules refuse, in the queue's order."""
    rules = sorted({r for r in map(rule_of, pods) if r is not None})
    counts = _Counts(rules, zones)
    where = placements(reply)
    by_key = collections.defaultdict(list)
    for i in where:
        by_key[queue_key(pods[i])].append(i)
    bad = []
    for key in sorted(by_key):
        rows = [(pods[i], rule_of(pods[i]), *where[i]) for i in by_key[key]]
        index = {id(pods[i]): i for i in by_key[key]}
        bad += [(index[id(pod)], why) for pod, why in _replay_workload(counts, rows)]
    return bad


def guarantees(reply: dict, pods: list, zones: list) -> list:
    """Per rule, one message with its count of refused placements and the
    first ``MAX_MESSAGES`` of them."""
    where = placements(reply)
    by_rule = collections.defaultdict(list)
    for i, why in violations(reply, pods, zones):
        by_rule[why.split(":")[0]].append(f"pod {i} on new node {where[i][0]}: {why}")
    out = []
    for rule, messages in sorted(by_rule.items()):
        out.append(f"{rule}: {len(messages)} placement(s) upstream's rules refuse")
        out += messages[:MAX_MESSAGES]
    return out
