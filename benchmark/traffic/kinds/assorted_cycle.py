"""``deployment_cycle``'s stateless ``/SolveClasses`` cycle over backlogs of
Deployments on upstream's assorted catalog, with the workloads a mixed
Linux/Windows, amd64/arm64 cluster runs: services spread over spot and
on-demand capacity as Karpenter's docs show, every workload selecting its os.

How a backlog groups into Deployments is ``deployment_cycle``'s: its
``layout`` and ``deal`` by import, the same tiers, a kind dealt to each
Deployment within its tier by sevenths.  What a kind sends is data, in the
configuration's ``pod_mix.kinds``: the topology keys its Deployment spreads on
(``spread``, maxSkew 1 each, selecting the Deployment's own ``name``) and the
``node_selector`` every replica carries.

traffic parameters:
  sizes   as ``size_cycle``: the backlog sizes, or the configuration key that
          holds them (``backlogs``).  A seeded deal of its own per entry.

configuration keys this kind reads (besides ``oracle``):
  catalog         the catalog the sidecar serves: ``assorted`` is
                  ``cloudprovider.fake.instance_types_assorted``.  ``setup``
                  puts it into the list the harness built (``Sidecar.catalog``,
                  which the provider hands every solver) before the first
                  request; ``types`` must be its length
  namespace_pods, tiers   as ``deployment_cycle``
  pod_mix         ``cpu``, ``memory``, ``parts_of`` and per kind ``kind``,
                  ``parts``, ``spread`` (keys: ``zone``, ``hostname``,
                  ``capacity-type``) and ``node_selector``; the first
                  ``generic`` kind takes what the sevenths leave
  oracle          ``{"pods": n}``: one namespace of n pods under the same
                  rule, served once more after the window and compared with
                  the host scheduler: scheduled, failed and new nodes equal

``check`` holds the last answer of each backlog, and the cut's answer, to
``assorted_reference.guarantees`` (from the pods, the answer and the catalog
alone), ``checks.accounting`` and ``checks.capacity``.  Creation times go by
Deployment (``cluster_cycle.by_workload``'s reason).
"""

import json

from benchmark.harness import checks
from benchmark.harness.podmix import seeded
from benchmark.traffic.kinds import assorted_reference as reference
from benchmark.traffic.kinds import size_cycle
from benchmark.traffic.kinds.deployment_cycle import GROUP_LABEL, deal

KEYS = {"zone": "LABEL_TOPOLOGY_ZONE", "hostname": "LABEL_HOSTNAME",
        "capacity-type": "LABEL_CAPACITY_TYPE"}


def catalog(name: str) -> list:
    from karpenter_core_tpu.cloudprovider import fake

    return {"assorted": fake.instance_types_assorted}[name]()


def pods_of(deployments: list, kinds: list) -> list:
    """The replicas, Deployment after Deployment, as the dealt kind says."""
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint
    from karpenter_core_tpu.testing import make_pod

    by_kind = {k["kind"]: k for k in kinds}
    pods = []
    for created, d in enumerate(deployments):
        kind = by_kind[d.kind]
        selector = LabelSelector(match_labels={"name": d.name})
        for _ in range(d.replicas):
            pods.append(make_pod(
                namespace=d.namespace, labels={"name": d.name, "group": GROUP_LABEL},
                requests={"cpu": d.cpu, "memory": d.memory},
                node_selector=dict(kind["node_selector"]),
                creation_timestamp=float(created),
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1, topology_key=getattr(labels_api, KEYS[key]),
                        label_selector=selector)
                    for key in kind["spread"]
                ] or None,
            ))
    return pods


def summary(deployments: list, kinds: list) -> dict:
    """Counts of what was dealt, for the run's own account."""
    spread = {k["kind"]: k["spread"] for k in kinds}
    by_kind: dict = {}
    for d in deployments:
        by_kind[d.kind] = by_kind.get(d.kind, 0) + 1
    return {
        "pods": sum(d.replicas for d in deployments),
        "deployments": len(deployments),
        "namespaces": len({d.namespace for d in deployments}),
        "by_kind": by_kind,
        "groups": sum(len(spread[d.kind]) for d in deployments),
        "ct_spread_pods": sum(d.replicas for d in deployments
                              if "capacity-type" in spread[d.kind]),
    }


class Kind(size_cycle.Kind):
    """``size_cycle``'s cycle, unit and settle over backlogs of Deployments;
    ``setup`` first swaps the catalog in, ``check`` holds every answer to the
    reference and the cut to the host scheduler."""

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.kinds = ctx.config["pod_mix"]["kinds"]
        self.deployments = [
            deal(n, ctx.config, seeded(ctx.seed, f"batch{j}"))
            for j, n in enumerate(self.sizes())
        ]
        self.batches = [pods_of(dealt, self.kinds) for dealt in self.deployments]
        self.group = len(self.batches)  # units in one cycle
        self.reference: list = []  # the warm-up answer per batch
        self.last: list = [None] * self.group
        print(json.dumps({"backlogs": [summary(d, self.kinds) for d in self.deployments]}),
              flush=True)

    def setup(self) -> list:
        """The configuration's catalog, in place: the provider hands this very
        list to every solver the sidecar builds, and none is built before the
        first request.  A backlog the sidecar refuses leaves the cell nothing
        to measure: the run stops here, with the refusal, and exits non-zero."""
        side = self.ctx.sidecar
        side.catalog[:] = catalog(self.ctx.config["catalog"])
        if len(side.catalog) != int(self.ctx.config["types"]):
            raise ValueError(f"catalog {self.ctx.config['catalog']!r} has {len(side.catalog)} "
                             f"types, the configuration states {self.ctx.config['types']}")
        failures = super().setup()
        if any(reply is None for reply in self.reference):
            raise RuntimeError("; ".join(failures))
        return failures

    def kernel_pods(self) -> list:
        """The first backlog: its library solve shows the kernel's shapes
        (slots, padded classes) in the traced run's line."""
        return self.batches[0]

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
                reference.guarantees(answer, pods, side.catalog),
            ):
                failures += [f"batch {j}: {f}" for f in held]
            got = checks.counts(answer)
            nodes, placed = nodes + got["nodes"], placed + got["scheduled"]
        failures += self._oracle_cut()
        return {"failures": failures, "nodes": nodes, "pods_placed": placed}

    def _oracle_cut(self) -> list:
        """Kernel vs host oracle on one namespace of the same rule: one more
        served solve, held to the same guarantees (``reference.oracle``)."""
        side = self.ctx.sidecar
        n = int(self.ctx.config["oracle"]["pods"])
        dealt = deal(n, {**self.ctx.config, "namespace_pods": n},
                     seeded(self.ctx.seed, "oracle"))
        pods = pods_of(dealt, self.kinds)
        reply, call = side.call(side.client.solve_classes, pods, side.provisioners,
                                timeout=self.ctx.timeout)
        if reply is None:
            return [f"oracle cut: the served solve raised: {call.error}"]
        return reference.oracle(reply, pods, side.catalog, side.provisioners)
