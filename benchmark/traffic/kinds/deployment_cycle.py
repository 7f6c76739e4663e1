"""``size_cycle``'s stateless ``/SolveClasses`` cycle over backlogs grouped as a
cluster holds its pods: every pod a replica of a Deployment, every Deployment
with a ``name`` label and a selector of its own.

How a backlog groups into Deployments is Kubernetes' own scalability load
test (kubernetes/perf-tests clusterloader2/testing/load/config.yaml):
namespaces of ``namespace_pods`` pods, in each a ``share`` of the pods per
tier, in Deployments of the tier's ``replicas``.  What a pod asks for is the
upstream Karpenter suite's ``pod_mix``, drawn once per Deployment.

traffic parameters:
  sizes   as ``size_cycle``: the backlog sizes, or the configuration key that
          holds them (``backlogs``).  A seeded deal of its own per entry.

configuration keys this kind reads (besides ``oracle``):
  namespace_pods  pods in a namespace; the last namespace takes what is left
  tiers           ``[{"name", "replicas", "share"}, ...]``: a tier's quota is
                  ``share`` (a fraction, as text) of its namespace's pods,
                  rounded down, the last tier taking the remainder; a tier's
                  last Deployment takes what is left of the quota
  pod_mix         ``cpu``, ``memory``, ``parts_of`` and per kind ``kind``,
                  ``parts``, ``topology``: within each tier ``parts /
                  parts_of`` of the backlog's Deployments (floor) is DEALT to
                  each kind and the rest are generic, which Deployment gets
                  which decided by a seeded shuffle (as ``podmix.draw`` deals
                  pods) — so the counts of classes and of topology groups, and
                  with them the padded shapes, are the same in every seed
  oracle          ``{"pods": n}``: one namespace of n pods under the same rule,
                  served once more after the window and compared with the host
                  scheduler (``deployment_reference.oracle``: scheduled and
                  failed equal, new nodes equal or fewer)

Every replica of a Deployment is identical: namespace ``ns-<i>``, labels
``{"name": "<tier>-<k>", "group": "load"}``, one draw of cpu x memory; a
constrained Deployment selects ``matchLabels {"name": <its own>}`` with
``maxSkew`` 1 on its kind's topology key, an affinity term's namespaces left
to default to the pod's own.  Creation times go by Deployment
(``cluster_cycle.by_workload``'s reason).
"""

import json
from fractions import Fraction
from typing import NamedTuple, Optional

from benchmark.harness import checks
from benchmark.harness.podmix import TOPOLOGY, seeded
from benchmark.traffic.kinds import deployment_reference as reference
from benchmark.traffic.kinds import size_cycle

GROUP_LABEL = "load"  # the load test's `group` label, on every pod


class Deployment(NamedTuple):
    """What the generator decided for one Deployment: the reference reads
    this, never a class or a group of the program's."""

    namespace: str
    name: str
    tier: str
    replicas: int
    kind: str  # generic | spread | affinity
    topology: Optional[str]  # zone | hostname, None for generic
    cpu: str
    memory: str

    @property
    def key(self) -> tuple:
        return self.namespace, self.name


def layout(n_pods: int, namespace_pods: int, tiers: list) -> list:
    """``(namespace index, tier name, k, replicas)`` per Deployment, from the
    sizes alone: the same in every seed."""
    out = []
    for i, start in enumerate(range(0, n_pods, namespace_pods)):
        pods = min(namespace_pods, n_pods - start)
        quotas = [int(pods * Fraction(tier["share"])) for tier in tiers]
        quotas[-1] += pods - sum(quotas)
        for tier, quota in zip(tiers, quotas):
            size = int(tier["replicas"])
            for k, at in enumerate(range(0, quota, size)):
                out.append((i, tier["name"], k, min(size, quota - at)))
    return out


def deal(n_pods: int, config: dict, rng) -> list:
    """The backlog's Deployments, kinds dealt within each tier and shapes
    drawn from ``rng``, in the order their pods are sent."""
    mix = config["pod_mix"]
    slots = layout(n_pods, int(config["namespace_pods"]), config["tiers"])
    filler = next(k for k in mix["kinds"] if k["kind"] == "generic")
    kind_of: dict = {}
    for tier in config["tiers"]:
        of_tier = [j for j, slot in enumerate(slots) if slot[1] == tier["name"]]
        dealt = []
        for kind in mix["kinds"]:
            dealt += [kind] * (len(of_tier) * kind["parts"] // mix["parts_of"])
        dealt += [filler] * (len(of_tier) - len(dealt))
        rng.shuffle(dealt)
        kind_of.update(zip(of_tier, dealt))
    return [
        Deployment(
            namespace=f"ns-{i}", name=f"{tier}-{k}", tier=tier, replicas=replicas,
            kind=kind_of[j]["kind"], topology=kind_of[j].get("topology"),
            cpu=rng.choice(mix["cpu"]), memory=rng.choice(mix["memory"]),
        )
        for j, (i, tier, k, replicas) in enumerate(slots)
    ]


def pods_of(deployments: list) -> list:
    """The replicas, Deployment after Deployment."""
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.apis.objects import (
        LabelSelector,
        PodAffinityTerm,
        TopologySpreadConstraint,
    )
    from karpenter_core_tpu.testing import make_pod

    pods = []
    for created, d in enumerate(deployments):
        for _ in range(d.replicas):
            constraint = {}
            if d.kind != "generic":
                selector = LabelSelector(match_labels={"name": d.name})
                topology_key = getattr(labels_api, TOPOLOGY[d.topology])
                if d.kind == "spread":
                    constraint = {"topology_spread": [TopologySpreadConstraint(
                        max_skew=1, topology_key=topology_key, label_selector=selector)]}
                elif d.kind == "affinity":
                    constraint = {"pod_affinity": [PodAffinityTerm(
                        topology_key=topology_key, label_selector=selector)]}
                else:
                    raise KeyError(f"pod_mix: no kind {d.kind!r}")
            pods.append(make_pod(
                namespace=d.namespace, labels={"name": d.name, "group": GROUP_LABEL},
                requests={"cpu": d.cpu, "memory": d.memory},
                creation_timestamp=float(created), **constraint))
    return pods


def summary(deployments: list) -> dict:
    """Counts of what was dealt, for the run's own account."""
    tiers: dict = {}
    for d in deployments:
        tiers[d.tier] = tiers.get(d.tier, 0) + 1
    return {
        "pods": sum(d.replicas for d in deployments),
        "deployments": len(deployments),
        "namespaces": len({d.namespace for d in deployments}),
        "by_tier": tiers,
        "groups": sum(1 for d in deployments if d.kind != "generic"),
        "largest_hostname_spread": reference.largest_hostname_spread(deployments),
    }


class Kind(size_cycle.Kind):
    """``size_cycle``'s cycle, set-up, unit and settle over backlogs of
    Deployments; ``check`` holds every Deployment to its guarantee."""

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.deployments = [
            deal(n, ctx.config, seeded(ctx.seed, f"batch{j}"))
            for j, n in enumerate(self.sizes())
        ]
        self.batches = [pods_of(dealt) for dealt in self.deployments]
        self.group = len(self.batches)  # units in one cycle
        self.reference: list = []  # the warm-up answer per batch
        self.last: list = [None] * self.group
        print(json.dumps({"backlogs": [summary(d) for d in self.deployments]}), flush=True)

    def kernel_pods(self):
        """None: no metric of this traffic's cells reads the roofline's
        shapes, and a traced run would spend a whole library solve on them."""
        return None

    def check(self) -> dict:
        side, failures = self.ctx.sidecar, []
        zones = reference.catalog_zones(side.catalog)
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
                reference.guarantees(answer, pods, self.deployments[j], zones),
            ):
                failures += [f"batch {j}: {f}" for f in held]
            got = checks.counts(answer)
            nodes, placed = nodes + got["nodes"], placed + got["scheduled"]
        failures += self._oracle_cut(zones)
        return {"failures": failures, "nodes": nodes, "pods_placed": placed}

    def _oracle_cut(self, zones: list) -> list:
        """Kernel vs host oracle on one namespace of the same rule: one more
        served solve, held to the same guarantees (``reference.oracle``)."""
        side = self.ctx.sidecar
        n = int(self.ctx.config["oracle"]["pods"])
        dealt = deal(n, {**self.ctx.config, "namespace_pods": n},
                     seeded(self.ctx.seed, "oracle"))
        pods = pods_of(dealt)
        reply, call = side.call(side.client.solve_classes, pods, side.provisioners,
                                timeout=self.ctx.timeout)
        if reply is None:
            return [f"oracle cut: the served solve raised: {call.error}"]
        return reference.oracle(reply, pods, dealt, side.catalog, side.provisioners, zones)
