"""Stateless ``/SolveClasses`` against a live cluster: a fixed cycle of seeded
backlogs, each sent with EVERY node of one seeded cluster and every pod bound
to it, the next request when the last returns (one leader that waits for each
reply: a closed loop with one client).

What the provisioning controller sends on every solve
(``controllers/provisioning.py``: every state node with every pod bound to it,
``nodes=``), what the scheduler does first (existing nodes in order, then new
ones) and what every consolidation validates against.  The cluster crosses the
wire whole in every request — no side entry, no pre-decoded nodes, no cache.

traffic parameters:
  sizes   the pending batch sizes in the order they are sent — a list, or the
          name of the configuration key that holds one (``backlogs``).  A
          fresh seeded batch of the configuration's ``pod_mix`` per entry.

configuration keys this kind reads (besides ``pod_mix``, ``oracle``):
  existing_nodes  how many nodes the cluster has
  node_types      instance-type names of the sidecar's catalog; the nodes are
                  an equal share of each, in an order shuffled from the seed; a
                  node's zone is its type's on-demand offerings in rotation
  utilisation     bound pods are seeded draws of the same ``pod_mix`` (kinds
                  shuffled), dealt node by node until the next would pass this
                  share of the node's allocatable cpu or memory; that pod opens
                  the next node's deal
  oracle          ``{"nodes": n, "pods": m}``: the cut the host oracle holds —
                  the cluster's first n nodes (with their bound pods) against a
                  seeded batch of m, served once more after the window

The ``nodes=`` contract (``SnapshotSolverClient.solve_classes``): a list of
``{"node": codec.node_to_dict(node), "pods": [codec.pod_to_dict(p), ...],
"volumeLimits": {driver: count}}``; the answer's ``existingAssignments`` maps a
node's name to the indices of the pending pods placed on it.

The cluster and every backlog come from ``--seed`` alone, never from a solve
of the program under test.  Several distinct backlogs, so that no memo keyed
on a request's content can ever be read as a speed-up.
"""

from benchmark.harness import checks
from benchmark.harness.podmix import draw, pod_mix, seeded
from benchmark.traffic.kinds import cluster_reference as reference
from benchmark.traffic.kinds import size_cycle


def build_cluster(config: dict, seed: int, catalog: list, provisioner: str,
                  stream: str = "cluster") -> list:
    """``[(Node, [bound Pod, ...]), ...]`` from the seed: API objects as the
    kubelet registers them and the kube-scheduler binds them."""
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.apis.objects import OP_IN
    from karpenter_core_tpu.testing import make_node
    from karpenter_core_tpu.utils import resources as resources_util

    rng = seeded(seed, stream)
    by_name = {it.name: it for it in catalog}
    types = [by_name[name] for name in config["node_types"]]
    share = float(config["utilisation"])
    rotation = {it.name: 0 for it in types}
    pool: list = []

    def next_pod():
        if not pool:
            drawn = pod_mix(4096, rng, config["pod_mix"])
            rng.shuffle(drawn)
            pool.extend(drawn)
        return pool.pop()

    # an equal share of each type in a seeded order, not a draw a node: the
    # cluster's capacity, and with it the bound pods every request carries,
    # then differs between seeds by the pods' sizes alone (~0.3 %, not ~2 %)
    n_nodes = int(config["existing_nodes"])
    of_node = [types[e % len(types)] for e in range(n_nodes)]
    rng.shuffle(of_node)
    cluster = []
    held = None  # the pod that would have passed the last node's share
    for e, it in enumerate(of_node):
        zones = [o.zone for o in it.offerings.available() if o.capacity_type == "on-demand"]
        zone = zones[rotation[it.name] % len(zones)]
        rotation[it.name] += 1
        labels = {
            key: it.requirements.get(key).values_list()[0]
            for key in it.requirements.keys()
            if it.requirements.get(key).operator() == OP_IN
        }
        labels.update({
            labels_api.LABEL_TOPOLOGY_ZONE: zone,
            labels_api.LABEL_CAPACITY_TYPE: "on-demand",
            labels_api.PROVISIONER_NAME_LABEL_KEY: provisioner,
            labels_api.LABEL_NODE_INITIALIZED: "true",
        })
        allocatable = it.allocatable()
        node = make_node(name=f"live-{e:05d}", labels=labels,
                         allocatable=allocatable, capacity=dict(it.capacity))
        room = {r: share * allocatable[r] for r in (resources_util.CPU, resources_util.MEMORY)}
        bound = []
        while share > 0:
            pod = held if held is not None else next_pod()
            held = None
            need = resources_util.requests_for_pods(pod)
            if any(need.get(r, 0.0) > room[r] for r in room):
                held = pod
                break
            for r in room:
                room[r] -= need.get(r, 0.0)
            pod.spec.node_name = node.name
            pod.status.phase = "Running"
            pod.status.conditions = []
            bound.append(pod)
        cluster.append((node, bound))
    return cluster


def by_workload(pods: list) -> list:
    """Stamp each pod with the creation time of its workload: replicas of one
    ReplicaSet are created together, workloads one after another in the order
    the batch first shows them.  The host scheduler's queue orders pods by cpu,
    memory, creation time, uid (queue.go:74-110), so it then takes pods of
    equal size workload by workload — the order in which the kernel's scan
    takes its classes.  Unstamped (every pod created at once, uid the only
    tie-break) the host interleaves a size's workloads pod by pod; both answers
    are then valid and place the same pods, on different nodes."""
    first: dict = {}
    for workload, pod in pods:
        pod.metadata.creation_timestamp = float(first.setdefault(workload, len(first)))
    return [pod for _workload, pod in pods]


def wire_nodes(cluster: list, volume_limits=None) -> list:
    """The ``nodes=`` argument, as ``controllers/provisioning.py`` builds it;
    ``volume_limits`` is ``{node name: {driver: count}}``, a CSINode's."""
    from karpenter_core_tpu.apis import codec

    return [
        {"node": codec.node_to_dict(node),
         "pods": [codec.pod_to_dict(p) for p in bound],
         "volumeLimits": dict((volume_limits or {}).get(node.name) or {})}
        for node, bound in cluster
    ]


class Kind(size_cycle.Kind):
    """``size_cycle``'s cycle, set-up, unit and settle (its sums count
    ``existingAssignments``), with the cluster in every request."""

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        side = ctx.sidecar
        self.cluster = build_cluster(ctx.config, ctx.seed, side.catalog,
                                     side.provisioners[0].name)
        self.nodes = wire_nodes(self.cluster)
        self.batches = [
            by_workload(draw(n, seeded(ctx.seed, f"batch{j}"), ctx.config["pod_mix"]))
            for j, n in enumerate(self.sizes())
        ]
        self.group = len(self.batches)  # units in one cycle
        self.reference: list = []  # the warm-up answer per batch
        self.last: list = [None] * self.group

    def _call(self, pods: list, nodes: list):
        side = self.ctx.sidecar
        return side.call(side.client.solve_classes, pods, side.provisioners,
                         nodes=nodes, timeout=self.ctx.timeout)

    def _send(self, j: int):
        return self._call(self.batches[j], self.nodes)

    def kernel_pods(self):
        """None: the roofline's bytes function has no existing-node shapes."""
        return None

    def check(self) -> dict:
        side, failures = self.ctx.sidecar, []
        nodes = placed = 0
        for j, (pods, ref, last) in enumerate(zip(self.batches, self.reference, self.last)):
            answer = last if last is not None else ref
            if answer is None:
                continue
            if last is not None and last != ref:
                failures.append(f"batch {j}: the last answer differs from the warm-up answer")
            for held in (
                checks.accounting(answer, len(pods)),
                checks.capacity(answer, pods, side.catalog),
                reference.existing_capacity(answer, pods, self.cluster),
                reference.topology(answer, pods, self.cluster,
                                   seeded(self.ctx.seed, f"sample{j}")),
            ):
                failures += [f"batch {j}: {f}" for f in held]
            got = checks.counts(answer)
            nodes, placed = nodes + got["nodes"], placed + got["scheduled"]
        failures += self._oracle_cut()
        return {"failures": failures, "nodes": nodes, "pods_placed": placed}

    def _oracle_cut(self) -> list:
        """Kernel vs host oracle with state nodes, per existing node, on the
        cut: one more served solve of the cluster's first nodes."""
        cut = self.ctx.config["oracle"]
        side = self.ctx.sidecar
        cluster = self.cluster[: int(cut["nodes"])]
        pods = by_workload(draw(int(cut["pods"]), seeded(self.ctx.seed, "oracle"),
                                self.ctx.config["pod_mix"]))
        reply, call = self._call(pods, self.nodes[: len(cluster)])
        if reply is None:
            return [f"oracle cut: the served solve raised: {call.error}"]
        kernel = reference.totals(reply)
        host = reference.oracle_totals(pods, cluster, side.catalog, side.provisioners)
        return reference.differences(kernel, host)
