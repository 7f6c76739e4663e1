"""``size_cycle``'s stateless ``/SolveClasses`` cycle over the upstream
suite's mix AS DRAWN: spread pods select a second, independent draw of
``my-label`` — the key generic pods carry too — and the hostname-affinity
seventh keeps the hostname key, so a group counts the pods of classes that do
not own it.  ``checks.oracle``'s equal node totals cannot hold here (the
kernel packs tighter than the host, PERF.md section 6), so every answer is
held to upstream's rules by a reference of its own instead.

traffic parameters:
  sizes   as ``size_cycle``: the batch sizes in the order they are sent, or
          the configuration key that holds them (``batch_sizes``).  A fresh
          seeded batch of the configuration's ``pod_mix`` per entry, stamped
          by workload (``cluster_cycle.by_workload``): a workload's pods are
          created together, so the host's queue takes pods of equal size
          workload by workload, as the scan takes its classes.

configuration keys this kind reads (besides ``pod_mix``):
  oracle  ``{"pods": n, "fleet_pods": f}``: the batches of the cycle of
          those sizes are the cuts

``check`` holds, on the last answer of every size (``kinds/drawn_reference``
reads only API objects, the answer and the catalog's zones):

  every pod answered once and none failed; each node's pods fit every type
  it lists; the last answer equals the warm-up answer of the same batch
  every placement admissible by upstream's topology rules, replayed in the
    host queue's order (``drawn_reference.guarantees``)
  the cuts: scheduled and failed equal to the host scheduler's on both; at
    ``fleet_pods`` new nodes AS MANY OR FEWER too (one-sided, as
    ``manyshape-50k.full`` holds it).  At ``pods`` the two fleets differ by a
    node or two either way — which node a pod of a workload joins is a tie
    each scheduler breaks its own way (PERF.md section 6, PR 40) — so there
    the node counts are printed, not held
"""

import json

from benchmark.harness import checks
from benchmark.harness.podmix import draw, seeded
from benchmark.traffic.kinds import drawn_reference as reference
from benchmark.traffic.kinds import size_cycle
from benchmark.traffic.kinds.cluster_cycle import by_workload


class Kind(size_cycle.Kind):
    """``size_cycle``'s cycle, set-up, unit and settle; ``check`` holds every
    size's last answer to the reference and the cuts to the host."""

    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.batches = [
            by_workload(draw(n, seeded(ctx.seed, f"batch{j}"), ctx.config["pod_mix"]))
            for j, n in enumerate(self.sizes())
        ]
        self.group = len(self.batches)  # units in one cycle
        self.reference: list = []  # the warm-up answer per batch
        self.last: list = [None] * self.group

    def kernel_pods(self):
        """None: no metric of this traffic's cell reads the roofline's
        shapes, and a traced run would spend a whole library solve on them."""
        return None

    def check(self) -> dict:
        side, failures = self.ctx.sidecar, []
        zones = reference.catalog_zones(side.catalog)
        nodes = placed = 0
        fleets = {}
        for j, (pods, ref, last) in enumerate(zip(self.batches, self.reference, self.last)):
            answer = last if last is not None else ref
            if answer is None:
                continue
            if last is not None and last != ref:
                failures.append(f"batch {j}: the last answer differs from the warm-up answer")
            for held in (
                checks.accounting(answer, len(pods)),
                checks.capacity(answer, pods, side.catalog),
                reference.guarantees(answer, pods, zones),
            ):
                failures += [f"batch {j}: {f}" for f in held]
            got = checks.counts(answer)
            fleets[len(pods)] = got["nodes"]
            nodes, placed = nodes + got["nodes"], placed + got["scheduled"]
        print(json.dumps({"fleets": fleets}), flush=True)
        failures += self._oracle_cut()
        return {"failures": failures, "nodes": nodes, "pods_placed": placed}

    def _oracle_cut(self) -> list:
        """The cycle's batches of the cuts' sizes against the host scheduler:
        scheduled and failed equal, and at ``fleet_pods`` new nodes as many
        or fewer."""
        cut, side = self.ctx.config["oracle"], self.ctx.sidecar
        answered = {len(pods): (pods, ref) for pods, ref in zip(self.batches, self.reference)
                    if ref is not None}
        bad = []
        for size, fleet in ((int(cut["pods"]), False), (int(cut["fleet_pods"]), True)):
            if size not in answered:
                bad.append(f"no answered batch of {size} pods in the cycle")
                continue
            pods, reply = answered[size]
            kernel = checks.counts(reply)
            host = checks.oracle_totals(pods, side.catalog, side.provisioners)
            print(json.dumps({"oracle_cut": {"pods": size, "kernel": kernel, "host": host}}),
                  flush=True)
            bad += [f"{size} pods, {key}: kernel {kernel[key]} vs host {host[key]}"
                    for key in ("scheduled", "failed", "residual") if kernel[key] != host[key]]
            if fleet and kernel["nodes"] > host["nodes"]:
                bad.append(f"{size} pods, nodes: kernel {kernel['nodes']} opens more than "
                           f"the host's {host['nodes']}")
        return [f"oracle cut: {f}" for f in bad]
