"""TPU-accelerated consolidation search.

Couples the kernel subset sweep (ops.consolidate) with the reference's
validity rules (consolidation.go:190-290): prefixes of the disruption-sorted
candidate list are simulated side by side on device, one lane each; the host
applies price filtering, the spot→spot prohibition, and the same-type price
sanity filter to a lane's decoded replacement.  A small cluster has every
prefix simulated in one pass and the largest valid one wins; above the lane
ladder the passes are the reference's own binary search, three levels each
(``search_largest_prefix``).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import numpy as np

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import OP_IN, Pod
from karpenter_core_tpu.cloudprovider import InstanceType
from karpenter_core_tpu.controllers.deprovisioning import (
    Action,
    CandidateNode,
    Command,
    filter_by_price,
    MultiNodeConsolidation,
)
from karpenter_core_tpu import tracing
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.models.snapshot import KernelUnsupported, refuse_ct_spread
from karpenter_core_tpu.ops import consolidate as consolidate_ops
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.scheduling import Requirement, Requirements
from karpenter_core_tpu.solver.tpu import TPUSolver

MAX_LANES = consolidate_ops.LANE_LADDER[-1]
# binary-search levels one pass speculates: the deepest tree of probes,
# 2**LEVELS - 1 sizes, that the ladder's low rung holds (3 levels, 7 sizes on
# 8 lanes).  A lane is not free on the chip — a scan step costs ~0.12 ms and
# ~0.04 ms more per lane at 6 144 nodes — so passes x step is least on the
# low rung, not on the widest one (docs/KERNEL_PERF.md "What a lane costs")
LEVELS = (consolidate_ops.LANE_LADDER[0] + 1).bit_length() - 1

CONSOLIDATE_PASSES = REGISTRY.counter(
    "karpenter_solver_consolidate_passes_total",
    "Device passes of the multi-node consolidation sweep (one over every "
    "prefix of a small cluster; above the lane ladder one per three levels "
    "of the binary search).",
)
CONSOLIDATE_PROBES = REGISTRY.counter(
    "karpenter_solver_consolidate_probes_total",
    "Prefix sizes the multi-node consolidation sweep simulated (real lanes, "
    "not a rung's padding), by the form of the search: levels (the binary "
    "search, three levels a pass), exhaustive (every prefix, one pass), "
    "scored (one coarse pass under the policy objective).",
    ("search",),
)
CONSOLIDATE_SECONDS = REGISTRY.histogram(
    "karpenter_solver_consolidate_seconds",
    "Wall of one multi-node consolidation search (encode, split, every pass "
    "and its decode), by the action it returned.",
    ("action",),
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0),
)


def search_form(n: int, refine: bool = True) -> str:
    """Which search ``n`` candidates get — decided by what can be observed, n
    against the lane ladder, and by the objective: ``scored`` (policy
    objective: one coarse pass), ``exhaustive`` (n fits the top rung: every
    prefix in one pass), else ``levels``."""
    if not refine:
        return "scored"
    return "exhaustive" if n <= MAX_LANES else "levels"


def reachable_sizes(lo_idx: int, hi_idx: int, levels: int = LEVELS) -> np.ndarray:
    """The prefix sizes (``mid + 1``) that the next ``levels`` levels of
    ``first_n_consolidation_option``'s binary search can probe from the state
    ``(lo_idx, hi_idx)``, ascending: 1 + 2 + 4 tree nodes, fewer near the
    leaves."""
    sizes, frontier = [], [(lo_idx, hi_idx)]
    for _ in range(levels):
        children = []
        for lo, hi in frontier:
            if lo <= hi:
                mid = (lo + hi) // 2
                sizes.append(mid + 1)
                children += [(lo, mid - 1), (mid + 1, hi)]
        frontier = children
    return np.array(sorted(sizes), dtype=np.int32)


def search_largest_prefix(n, evaluate, refine: bool = True):
    """The consolidation command for ``n`` disruption-sorted candidates.

    ``evaluate(sizes, levels=0)`` runs one device pass over the given prefix
    sizes and returns its verdicts: ``.command(k)`` — the command that closes
    the first ``k`` candidates, None where the simulation or a price rule
    refuses it — and ``.best()`` — ``(command, k)`` by the pass's own scoring.

    By ``search_form``:

    * ``exhaustive`` (n fits the top rung): every prefix is simulated in one
      pass and the largest valid one wins — the one search that assumes
      nothing about the shape of feasibility.
    * ``levels``: the reference's binary search
      (multinodeconsolidation.go:86-113; here
      ``MultiNodeConsolidation.first_n_consolidation_option``), LEVELS levels
      a pass.  A pass simulates the sizes those levels can reach from
      ``(lo_idx, hi_idx)`` on the low rung; the host walks them with the
      lanes' verdicts and keeps the command of the last valid probe on its
      path.  Probe for probe the host search's — so its answer too, whatever
      the shape of feasibility (valid sizes above an invalid one exist on
      live clusters) — in ceil(levels / LEVELS) passes: 3 at 300 candidates,
      4 or 5 at 5 000.
    * ``scored`` (``refine=False``): the policy objective scores the lanes of
      ONE coarse pass of the top rung; larger-k-wins does not hold for a
      saving, so no pass follows it."""
    form = search_form(n, refine)
    if form != "levels":
        if n <= MAX_LANES:
            sizes = np.arange(1, n + 1, dtype=np.int32)
        else:
            sizes = np.unique(np.round(np.linspace(1, n, MAX_LANES)).astype(np.int32))
        return evaluate(sizes).best()[0]

    lo_idx, hi_idx, last_saved = 1, n - 1, None
    while lo_idx <= hi_idx:
        sizes = reachable_sizes(lo_idx, hi_idx)
        # a probe tree d levels deep holds 2**(d-1) to 2**d - 1 sizes
        verdicts = evaluate(sizes, levels=len(sizes).bit_length())
        for _ in range(LEVELS):
            if lo_idx > hi_idx:
                break
            mid = (lo_idx + hi_idx) // 2
            command = verdicts.command(mid + 1)
            if command is not None:
                last_saved, lo_idx = command, mid + 1
            else:
                hi_idx = mid - 1
    return last_saved


@dataclass
class TPUReplacement:
    """Launchable replacement description compatible with
    ProvisioningController.launch (duck-typed like solver.node.SchedulingNode)."""

    template: object
    instance_type_options: List[InstanceType]
    requests: dict
    pods: List[Pod] = field(default_factory=list)

    @property
    def provisioner_name(self) -> str:
        return self.template.provisioner_name

    @property
    def requirements(self) -> Requirements:
        return self.template.requirements


class TPUConsolidationSearch:
    """Multi-node consolidation on the device: ``compute_command`` encodes the
    cluster once, uploads the sweep's planes once, and runs
    ``search_largest_prefix`` over them — each pass one ``sweep_pass`` of
    ``solve_core`` lanes, each lane one prefix of the candidates closed.

    With no policy objective the command is the one
    ``MultiNodeConsolidation.first_n_consolidation_option`` returns on the
    host, in action and in the number of nodes removed, wherever a lane and
    the host's simulation of the same prefix agree: up to the lane ladder's
    top rung (72 candidates) every prefix is simulated, so the largest valid
    one is found even where the binary search would miss it; above it the
    search probes the sizes the host's binary search probes.  ``last_passes``
    and ``last_probes`` count the newest command's device passes and the
    prefix sizes they simulated."""

    def __init__(self, cloud_provider, provisioners, policy=None) -> None:
        # policy (policy.PolicyConfig): with the objective enabled, lanes are
        # scored by FLEET COST DELTA (old subset price minus replacement
        # cost) instead of node count — the cheapest fleet wins even when a
        # smaller prefix removes fewer nodes (docs/POLICY.md).  None/disabled
        # keeps the reference behavior: the largest valid prefix wins.
        self.policy = policy
        self.cost_scoring = policy is not None and getattr(policy, "enabled", False)
        self.last_passes = self.last_probes = 0
        self.solver = TPUSolver(cloud_provider, provisioners, policy=policy)
        self.it_by_name = {
            it.name: it
            for p in self.solver.provisioners
            for it in self.solver.instance_types.get(p.name, [])
        }

    def compute_command(
        self,
        candidates: List[CandidateNode],
        pending_pods: List[Pod],
        state_nodes: list,
        bound_pods: Optional[List[Pod]] = None,
    ) -> Command:
        """candidates must be disruption-cost sorted.  Raises KernelUnsupported
        when the pod shapes need the host path."""
        self.last_passes = self.last_probes = 0
        if not candidates:
            return Command(Action.DO_NOTHING)

        t0 = time.perf_counter()
        with tracing.span("consolidate.encode",
                          state_nodes=len(state_nodes)) as sp:
            # the host's queue pops pods by cpu, memory, creation time, uid
            # (queue.go:74-110) and the class scan takes equal-sized classes
            # in the order their first pod shows: shown in the queue's own
            # order, a size's workloads are simulated in the order the host's
            # re-simulation (validate_command) will take them
            all_pods = sorted(
                list(pending_pods) + [p for c in candidates for p in c.pods],
                key=lambda p: (p.metadata.creation_timestamp, p.uid),
            )
            sp.set(pods=len(all_pods))
            if not all_pods:
                # no pods anywhere: every candidate is empty, deleting all is
                # trivially valid (the simulation would open zero new nodes)
                return Command(Action.DELETE, [c.node for c in candidates])
            snapshot = self.solver.encode(all_pods, state_nodes, bound_pods)
            refuse_ct_spread(snapshot.classes, "the consolidation sweep")
            with tracing.span(
                "encode.existing", state_nodes=len(state_nodes),
                bound_pods=len(bound_pods or ()), classes=len(snapshot.classes),
                e_padded=solve_ops.bucket(len(state_nodes), floor=8),
            ):
                ex_state, ex_static = self.solver.encode_existing(
                    snapshot, state_nodes, bound_pods, count_scheduling=True
                )
            sp.set(classes=len(snapshot.classes))

        with tracing.span("consolidate.split", classes=len(snapshot.classes)) as sp:
            # split class counts: pending (base) vs on-candidate (per-node),
            # one scatter over (class, node) index pairs
            node_index = {n.node.name: e for e, n in enumerate(state_nodes)}
            E = max(len(state_nodes), 1)
            C = len(snapshot.classes)
            rank = np.full(E, consolidate_ops.NOT_A_CANDIDATE, dtype=np.int32)
            for i, candidate in enumerate(candidates):
                rank[node_index[candidate.node.name]] = i
            pod_class, pod_node = [], []
            for c, cls in enumerate(snapshot.classes):
                if cls.is_ladder_variant:
                    continue  # variants hold one representative copy, not real pods
                pod_class += [c] * len(cls.pods)
                pod_node += [node_index.get(pod.spec.node_name, -1) for pod in cls.pods]
            pod_class = np.asarray(pod_class, dtype=np.intp)
            pod_node = np.asarray(pod_node, dtype=np.intp)
            on_candidate = pod_node >= 0
            on_candidate[on_candidate] = (
                rank[pod_node[on_candidate]] != consolidate_ops.NOT_A_CANDIDATE
            )
            ex_cls_count = np.zeros((C, E), dtype=np.int32)
            np.add.at(ex_cls_count, (pod_class[on_candidate], pod_node[on_candidate]), 1)
            snapshot.cls_count = np.bincount(
                pod_class[~on_candidate], minlength=C
            ).astype(np.int32)
            with tracing.span("prepare"):
                # padded on the /SolveClasses ladder and uploaded once: every
                # pass of the search ships its lane sizes alone
                planes = consolidate_ops.prepare_sweep(
                    snapshot, ex_state, ex_static, rank, ex_cls_count
                )
            c_padded, e_padded = planes.args[3].tol.shape
            sp.set(c_padded=int(c_padded), e_padded=int(e_padded))

        best = search_largest_prefix(
            len(candidates),
            lambda sizes, levels=0: self._evaluate_sweep(
                snapshot, planes, sizes, candidates, levels
            ),
            refine=not self.cost_scoring,
        )
        cmd = best if best is not None else Command(Action.DO_NOTHING)
        CONSOLIDATE_PASSES.labels().inc(self.last_passes)
        CONSOLIDATE_PROBES.labels(
            search_form(len(candidates), not self.cost_scoring)
        ).inc(self.last_probes)
        CONSOLIDATE_SECONDS.labels(cmd.action.value).observe(time.perf_counter() - t0)
        return cmd

    def _candidate_price_cumsum(self, candidates) -> np.ndarray:
        """Cumulative current-offering price of the first-k candidates
        (nan-poisoned past any candidate whose offering is unknown, which
        drops those lanes out of cost scoring without failing the sweep)."""
        prices = np.full(len(candidates), np.nan, dtype=np.float64)
        for i, c in enumerate(candidates):
            offering = c.instance_type.offerings.get(c.capacity_type, c.zone)
            if offering is not None:
                prices[i] = offering.price
        return np.cumsum(prices)

    def _evaluate_sweep(self, snapshot, planes, sizes, candidates, levels=0):
        """One device pass (``consolidate.sweep``) over the given prefix sizes
        and its verdicts, decoded on demand (``LaneVerdicts``)."""
        # the sweep auto-routes onto the 2D (catalog × lane) mesh when
        # KC_SOLVER_MESH enables it (parallel.mesh.lane_mesh_axes): prefix
        # lanes split across the lane axis, the catalog shards within each
        # lane group.  Assignments/viability/zone planes are bit-identical
        # to the unsharded sweep (mesh parity suite); the f32 per-lane
        # new_cost SUMS agree only to reduction-order ulp (XLA reassociates
        # across programs), so a razor-thin cost-delta tie can in principle
        # resolve differently with the mesh on vs off — same caveat as any
        # recompile (docs/KERNEL_PERF.md "Layer 5")
        self.last_passes += 1
        self.last_probes += len(sizes)
        with tracing.span(
            "consolidate.sweep", lanes=len(sizes),
            lanes_padded=consolidate_ops.lane_rung(len(sizes)), levels=levels,
            lo=int(sizes[0]), hi=int(sizes[-1]), **{"pass": self.last_passes},
        ):
            out = consolidate_ops.sweep_pass(planes, sizes)
        return LaneVerdicts(self, snapshot, out, sizes, candidates)

    def _decode_replacement(
        self, snapshot, viable_row, zone_row, ct_row, used_row, tmpl_idx, subset
    ) -> Optional[TPUReplacement]:
        options = [
            self.it_by_name[snapshot.it_names[i]]
            for i in np.nonzero(viable_row)[0]
            if snapshot.it_names[i] in self.it_by_name
        ]
        zones = [snapshot.zones[z] for z in np.nonzero(zone_row)[0]]
        cts = [snapshot.capacity_types[c] for c in np.nonzero(ct_row)[0]]
        template = self.solver.templates[tmpl_idx]

        requirements = Requirements(*template.requirements.values())
        if zones:
            requirements.add(Requirement(labels_api.LABEL_TOPOLOGY_ZONE, OP_IN, zones))
        if cts:
            requirements.add(Requirement(labels_api.LABEL_CAPACITY_TYPE, OP_IN, cts))

        # price rules (consolidation.go:227-267)
        old_price = 0.0
        for c in subset:
            offering = c.instance_type.offerings.get(c.capacity_type, c.zone)
            if offering is None:
                return None
            old_price += offering.price
        options = filter_by_price(options, requirements, old_price)
        if not options:
            return None
        all_spot = all(
            c.capacity_type == labels_api.CAPACITY_TYPE_SPOT for c in subset
        )
        ct_req = requirements.get(labels_api.LABEL_CAPACITY_TYPE)
        if all_spot and ct_req.has(labels_api.CAPACITY_TYPE_SPOT):
            return None
        if ct_req.has(labels_api.CAPACITY_TYPE_SPOT) and ct_req.has(
            labels_api.CAPACITY_TYPE_ON_DEMAND
        ):
            requirements.add(
                Requirement(
                    labels_api.LABEL_CAPACITY_TYPE, OP_IN, [labels_api.CAPACITY_TYPE_SPOT]
                )
            )
        # same-type price sanity for multi-node (multinodeconsolidation.go:132-165)
        from dataclasses import replace as dc_replace

        out_template = dc_replace(template, requirements=requirements)
        requests = {
            name: float(used_row[r])
            for r, name in enumerate(snapshot.resources)
            if used_row[r] > 0
        }
        replacement = TPUReplacement(
            template=out_template,
            instance_type_options=options,
            requests=requests,
            pods=[p for c in subset for p in c.pods],
        )
        if len(subset) >= 2:
            replacement.instance_type_options = MultiNodeConsolidation.filter_out_same_type(
                replacement, subset
            )
            if not replacement.instance_type_options:
                return None
        return replacement


class LaneVerdicts:
    """One pass's lanes → commands under the price rules, built when asked
    for (``consolidate.decode``; a command lists its k nodes, so none is built
    for a size the search does not reach)."""

    def __init__(self, search, snapshot, out, sizes, candidates) -> None:
        self.search, self.snapshot, self.out = search, snapshot, out
        self.sizes, self.candidates = np.asarray(sizes), candidates
        # what the simulation alone decides: every pod placed, no
        # uninitialized node relied on, at most one node opened
        self.accepted = (out.failed == 0) & ~out.used_uninitialized & (out.n_new <= 1)
        self.lanes_valid = int(self.accepted.sum())

    def _decoding(self, **attrs):
        # callers open a "decode" span inside it: the decode layer's own span
        # name (the fetched planes → objects), so the layer's shared metric
        # reads a sweep too
        return tracing.span(
            "consolidate.decode", lanes=len(self.sizes),
            lanes_valid=self.lanes_valid, **attrs,
        )

    def _lane_command(self, lane: int, k: int):
        """(command, replacement cost) of one accepted lane — None where a
        price rule refuses its replacement."""
        out, subset = self.out, self.candidates[:k]
        if int(out.n_new[lane]) == 0:
            return Command(Action.DELETE, [c.node for c in subset]), 0.0
        replacement = self.search._decode_replacement(
            self.snapshot, out.new_viable[lane, 0], out.new_zone[lane, 0],
            out.new_ct[lane, 0], out.new_used[lane, 0],
            int(out.new_tmpl[lane, 0]), subset,
        )
        if replacement is None:
            return None, 0.0
        return (Command(Action.REPLACE, [c.node for c in subset], [replacement]),
                float(out.new_cost[lane]))

    def command(self, k: int) -> Optional[Command]:
        """The command that closes the first ``k`` candidates — the reference's
        verdict on one probe (consolidation.go:190-290)."""
        lane = int(np.searchsorted(self.sizes, k))
        with self._decoding(k=k) as sp, tracing.span("decode"):
            command = None
            if self.accepted[lane]:
                command, _ = self._lane_command(lane, k)
            sp.set(valid=command is not None)
        return command

    def best(self):
        """(best command, its prefix size) across the pass.

        Default scoring is the reference's: the LARGEST valid prefix wins
        (most nodes removed).  With the policy objective enabled, lanes are
        scored by fleet-cost saving — old subset price minus the lane's
        replacement cost (the kernel's ``new_cost``) — and the largest
        saving wins, node count breaking ties; fewest-nodes and
        cheapest-fleet genuinely disagree when a large prefix forces a
        pricey replacement while a smaller one deletes outright
        (tests/test_policy.py pins both directions)."""
        cost_scoring = self.search.cost_scoring
        old_cum = (
            self.search._candidate_price_cumsum(self.candidates) if cost_scoring else None
        )
        best: Optional[Command] = None
        best_k = 0
        best_saving = -np.inf
        with self._decoding() as sp, tracing.span("decode"):
            # largest first: where the largest valid prefix wins (no cost
            # scoring) the first lane that yields a command is the answer, and
            # the O(k) build of every smaller lane's command is never paid
            for lane in np.flatnonzero(self.accepted)[::-1].tolist():
                k = int(self.sizes[lane])
                cmd, lane_cost = self._lane_command(lane, k)
                if cmd is None:
                    continue
                if not cost_scoring:
                    best, best_k = cmd, k
                    break
                saving = float(old_cum[k - 1]) - lane_cost if k >= 1 else 0.0
                if np.isnan(saving):
                    saving = -np.inf  # unpriceable subset: never preferred
                if saving > best_saving or (saving == best_saving and k > best_k):
                    best, best_k, best_saving = cmd, k, saving
            sp.set(best_k=best_k)
        return best, best_k
