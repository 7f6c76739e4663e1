"""Stateless ``/Consolidate`` over a live cluster: every node of one seeded
cluster a candidate, sent in disruption order with the whole cluster, the next
request when the last returns (one leader that waits for each reply: a closed
loop with one client).

What the deprovisioning controller sends when multi-node consolidation runs on
the solver sidecar (``controllers/deprovisioning.py`` ``_remote_search``): the
candidates as ``candidate_nodes`` returns them, sorted by disruption cost, and
every state node with every pod bound to it.  The sidecar answers with the
largest prefix of that order that can be deleted, or replaced by ONE cheaper
node (multinodeconsolidation.go:74-114).  Nothing of the cluster is kept on
the sidecar between requests.

traffic parameters:
  orders  how many disruption orders alternate — a number, or the name of the
          configuration key that holds one.  A node's disruption cost is its
          count of reschedulable pods (upstream's sum of eviction costs, each
          1.0, times a lifetime of 1.0 without a TTL); nodes of equal cost are
          in an order shuffled from the seed, one stream per order, so the
          requests differ and no memo keyed on a request's content can ever be
          read as a speed-up.

configuration keys this kind reads: ``cluster_cycle``'s (``existing_nodes``,
``node_types``, ``utilisation``, ``pod_mix`` — the cluster is built by its
``build_cluster`` and shipped by its ``wire_nodes``), and
  oracle  ``{"nodes": n}``: the cut the host holds — the cluster's first n
          nodes, every one a candidate in the first order's order, served once
          more after the window and held to the host's own simulation

Bound pods carry creation times by workload (``stamp_by_workload``), as
``cluster_cycle`` stamps its pending batches: the host's queue then takes a
size's workloads one after another, as the sweep's scan takes its classes.

The harness wraps the ``/SolveClasses`` handler alone; ``Kind.__init__`` moves
that same wrapper (``Sidecar._handler``: root span, handler wall, last reply)
onto the ``/Consolidate`` handler, which the server resolves per request.
"""

from benchmark.harness.podmix import seeded
from benchmark.traffic.kinds import consolidate_reference as reference
from benchmark.traffic.kinds.cluster_cycle import build_cluster, wire_nodes

ACTIONS = ("delete", "replace", "do nothing")


def stamp_by_workload(cluster: list) -> None:
    """Stamp each bound pod with the creation time of its workload: replicas
    of one ReplicaSet are created together, workloads one after another in the
    order the cluster first shows them (``cluster_cycle.by_workload``, for
    pods that are already bound).  A workload is what the mix drew for the
    pod: namespace, labels, requests, and its one constraint.  Times start at
    1: a kube client stamps an object whose creation time is 0 with its own
    clock, and the host's cluster would then differ from the one on the wire."""
    from karpenter_core_tpu.utils import resources as resources_util

    first: dict = {}
    for _node, bound in cluster:
        for pod in bound:
            spread = tuple((c.topology_key, c.max_skew)
                           for c in pod.spec.topology_spread_constraints)
            affinity = pod.spec.affinity
            terms = tuple(t.topology_key for t in affinity.pod_affinity.required) \
                if affinity is not None and affinity.pod_affinity is not None else ()
            workload = (
                pod.namespace, tuple(sorted(pod.metadata.labels.items())),
                tuple(sorted(resources_util.requests_for_pods(pod).items())),
                spread, terms,
            )
            pod.metadata.creation_timestamp = float(first.setdefault(workload, len(first) + 1))


def candidates_in_order(cluster: list, provisioner: str, rng) -> list:
    """Every node as a ``/Consolidate`` candidate, sorted by disruption cost,
    equal costs in ``rng``'s order."""
    from karpenter_core_tpu.apis import labels as labels_api

    wire = []
    for node, bound in cluster:
        labels = node.metadata.labels
        wire.append({
            "name": node.name,
            "instanceType": labels[labels_api.LABEL_INSTANCE_TYPE_STABLE],
            "capacityType": labels[labels_api.LABEL_CAPACITY_TYPE],
            "zone": labels[labels_api.LABEL_TOPOLOGY_ZONE],
            "provisioner": provisioner,
            "disruptionCost": float(len(bound)),
        })
    rng.shuffle(wire)
    return sorted(wire, key=lambda c: c["disruptionCost"])


class Kind:
    def __init__(self, ctx) -> None:
        from karpenter_core_tpu.testing import make_provisioner

        self.ctx = ctx
        side = ctx.sidecar
        # the harness's own wrapper, moved from /SolveClasses (which this
        # traffic never calls, and which gets its plain handler back)
        side.service._solve_classes = side._inner
        side._inner = side.service._consolidate
        side.service._consolidate = side._handler
        # the sidecar's one provisioner, with consolidation enabled
        own = side.provisioners[0]
        self.provisioner = make_provisioner(
            name=own.name, weight=own.spec.weight, consolidation_enabled=True)
        self.cluster = build_cluster(ctx.config, ctx.seed, side.catalog, own.name)
        stamp_by_workload(self.cluster)
        self.nodes = wire_nodes(self.cluster)
        self.pods = sum(len(bound) for _node, bound in self.cluster)
        orders = ctx.traffic["orders"]
        self.orders = [
            candidates_in_order(self.cluster, own.name, seeded(ctx.seed, f"order{j}"))
            for j in range(int(ctx.config[orders] if isinstance(orders, str) else orders))
        ]
        self.group = len(self.orders)  # units in one cycle
        self.reference: list = []  # the warm-up answer per order
        self.last: list = [None] * self.group

    def _call(self, candidates: list, nodes: list):
        side = self.ctx.sidecar
        return side.call(side.client.consolidate, candidates, [], [self.provisioner],
                         nodes=nodes, timeout=self.ctx.timeout)

    def setup(self) -> list:
        failures = []
        for j, order in enumerate(self.orders):
            reply, call = self._call(order, self.nodes)
            if reply is None:
                failures.append(f"warm-up of order {j} raised: {call.error}")
            self.reference.append(reply)
        return failures

    def unit(self, i: int):
        return self._call(self.orders[i % self.group], self.nodes)

    def settle(self, i: int, out) -> tuple:
        """(reschedulable pods bound to the candidates sent — every one is
        placed again in every lane of the sweep —, failure per failed call)."""
        reply, call = out
        if reply is None:
            return 0, [f"unit {i}: {call.error}"]
        self.last[i % self.group] = reply
        if reply.get("action") not in ACTIONS:
            return 0, [f"unit {i}: action {reply.get('action')!r}"]
        return self.pods, []

    def kernel_pods(self):
        """None: the roofline's bytes function has no existing-node shapes,
        and the sweep is no kernel of its own."""
        return None

    def check(self) -> dict:
        side, failures = self.ctx.sidecar, []
        for j, (order, ref, last) in enumerate(zip(self.orders, self.reference, self.last)):
            answer = last if last is not None else ref
            if answer is None:
                continue
            if last is not None and last != ref:
                failures.append(f"order {j}: the last answer differs from the warm-up answer")
            failures += [f"order {j}: {f}"
                         for f in reference.command(answer, order, self.cluster, side.catalog)]
            print(reference.line(answers={"order": j, "action": answer["action"],
                                          "removed": len(answer["nodesToRemove"]),
                                          "of": len(order)}), flush=True)
        failures += self._oracle_cut()
        return {"failures": failures}

    def _oracle_cut(self) -> list:
        """The served command on the cut against the host's own simulation
        and its binary search: one more served request of the first nodes."""
        side = self.ctx.sidecar
        cluster = self.cluster[: int(self.ctx.config["oracle"]["nodes"])]
        names = {node.name for node, _ in cluster}
        order = [c for c in self.orders[0] if c["name"] in names]
        reply, call = self._call(order, self.nodes[: len(cluster)])
        if reply is None:
            return [f"oracle cut: the served request raised: {call.error}"]
        bad = [f"oracle cut: {f}"
               for f in reference.command(reply, order, cluster, side.catalog)]
        verdict = reference.host_verdict(reply, order, cluster, side.catalog, self.provisioner)
        print(reference.line(oracle_cut=verdict["report"]), flush=True)
        return bad + verdict["failures"]
