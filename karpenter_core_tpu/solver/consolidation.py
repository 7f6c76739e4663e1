"""TPU-accelerated consolidation search.

Couples the kernel subset sweep (ops.consolidate) with the reference's
validity rules (consolidation.go:190-290): every prefix of the disruption-
sorted candidate list is simulated in parallel on device; the host then
applies price filtering, the spot→spot prohibition, and the same-type price
sanity filter to each lane's decoded replacement, and picks the largest valid
prefix — the result the binary search converges to, computed in one pass.
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
from karpenter_core_tpu.models.snapshot import KernelUnsupported
from karpenter_core_tpu.ops import consolidate as consolidate_ops
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.scheduling import Requirement, Requirements
from karpenter_core_tpu.solver.tpu import TPUSolver

MAX_LANES = consolidate_ops.LANE_LADDER[-1]

CONSOLIDATE_PASSES = REGISTRY.counter(
    "karpenter_solver_consolidate_passes_total",
    "Device passes of the multi-node consolidation sweep (one coarse pass over "
    "the candidates, then one per re-grid of the bracket it leaves).",
)
CONSOLIDATE_SECONDS = REGISTRY.histogram(
    "karpenter_solver_consolidate_seconds",
    "Wall of one multi-node consolidation search (encode, split, every pass "
    "and its decode), by the action it returned.",
    ("action",),
    buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 20.0, 40.0, 80.0),
)


def search_largest_prefix(n, evaluate, refine: bool = True):
    """Largest valid consolidation prefix via batched lane sweeps.

    ``evaluate(sizes) -> (best_command_or_None, best_k)`` runs one device
    sweep over the given prefix sizes and reports the largest valid one.  Up
    to MAX_LANES sizes cover [1, n] per pass; when the coarse grid leaves a
    gap between the best lane and the next, further passes re-grid the
    bracket, shrinking it ~MAX_LANES× each time — the boundary pins exactly
    in ceil(log72(n)) passes (2 up to 5 184 candidates, 3 to 373k) vs the
    reference's ~log2(n) sequential full simulations
    (multinodeconsolidation.go:86-113).

    ``refine=False`` stops after the coarse pass — cost-delta scoring
    (policy objective) picks its optimum WITHIN a pass, and the bracket
    refinement's larger-k-wins assumption would let a worse-saving larger
    prefix displace it."""
    if n <= MAX_LANES:
        sizes = np.arange(1, n + 1, dtype=np.int32)
    else:
        sizes = np.unique(np.round(np.linspace(1, n, MAX_LANES)).astype(np.int32))
    best, best_k = evaluate(sizes)
    if n <= MAX_LANES or best is None or not refine:
        return best

    lo = best_k
    hi = int(sizes[np.searchsorted(sizes, best_k) + 1]) if best_k < int(sizes[-1]) else None
    while hi is not None and hi - lo > 1:
        span = np.arange(lo + 1, hi, dtype=np.int32)
        if len(span) > MAX_LANES:
            span = np.unique(
                np.round(np.linspace(lo + 1, hi - 1, MAX_LANES)).astype(np.int32)
            )
        refined, refined_k = evaluate(span)
        if refined is not None and refined_k > lo:
            best, best_k = refined, refined_k
            lo = refined_k
            if refined_k < int(span[-1]):
                hi = int(span[np.searchsorted(span, refined_k) + 1])
            # else: the bracket (refined_k, hi) is already one grid interval
        else:
            hi = int(span[0])
    return best


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
    def __init__(self, cloud_provider, provisioners, policy=None) -> None:
        # policy (policy.PolicyConfig): with the objective enabled, lanes are
        # scored by FLEET COST DELTA (old subset price minus replacement
        # cost) instead of node count — the cheapest fleet wins even when a
        # smaller prefix removes fewer nodes (docs/POLICY.md).  None/disabled
        # keeps the reference behavior: the largest valid prefix wins.
        self.policy = policy
        self.last_passes = 0  # device passes of the newest compute_command
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
        self.last_passes = 0
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
            lambda sizes: self._evaluate_sweep(snapshot, planes, sizes, candidates),
            refine=not (
                self.policy is not None and getattr(self.policy, "enabled", False)
            ),
        )
        cmd = best if best is not None else Command(Action.DO_NOTHING)
        CONSOLIDATE_PASSES.labels().inc(self.last_passes)
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

    def _evaluate_sweep(self, snapshot, planes, sizes, candidates):
        """(best command, its prefix size) across the given lane sizes: one
        device pass (``consolidate.sweep``) and its decode
        (``consolidate.decode``: lanes → commands under the price rules).

        Default scoring is the reference's: the LARGEST valid prefix wins
        (most nodes removed).  With the policy objective enabled, lanes are
        scored by fleet-cost saving — old subset price minus the lane's
        replacement cost (the kernel's ``new_cost``) — and the largest
        saving wins, node count breaking ties; fewest-nodes and
        cheapest-fleet genuinely disagree when a large prefix forces a
        pricey replacement while a smaller one deletes outright
        (tests/test_policy.py pins both directions)."""
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
        with tracing.span(
            "consolidate.sweep", lanes=len(sizes),
            lanes_padded=consolidate_ops.lane_rung(len(sizes)),
            lo=int(sizes[0]), hi=int(sizes[-1]), **{"pass": self.last_passes},
        ):
            out = consolidate_ops.sweep_pass(planes, sizes)
        # "decode" inside it is the decode layer's own span name (the fetched
        # planes → objects), so the layer's shared metric reads a sweep too
        with tracing.span("consolidate.decode", lanes=len(sizes)) as sp, \
                tracing.span("decode"):
            best, best_k, lanes_valid = self._decode_lanes(
                snapshot, out, sizes, candidates
            )
            sp.set(lanes_valid=lanes_valid, best_k=best_k)
        return best, best_k

    def _decode_lanes(self, snapshot, out, sizes, candidates):
        """(best command, its prefix size, lanes that gave a command)."""
        n_new = np.asarray(out.n_new)
        failed = np.asarray(out.failed)
        uninit = np.asarray(out.used_uninitialized)
        viable = np.asarray(out.new_viable)
        zone = np.asarray(out.new_zone)
        ct = np.asarray(out.new_ct)
        used = np.asarray(out.new_used)
        tmpl_id = np.asarray(out.new_tmpl)
        new_cost = np.asarray(out.new_cost)
        cost_scoring = self.policy is not None and getattr(
            self.policy, "enabled", False
        )
        old_cum = self._candidate_price_cumsum(candidates) if cost_scoring else None

        # what the simulation alone decides: every pod placed, no
        # uninitialized node relied on, at most one node opened
        accepted = (failed == 0) & ~uninit & (n_new <= 1)
        best: Optional[Command] = None
        best_k = 0
        best_saving = -np.inf
        # largest first: where the largest valid prefix wins (no cost scoring)
        # the first lane that yields a command is the answer, and the O(k)
        # build of every smaller lane's command is never paid
        for lane in np.flatnonzero(accepted)[::-1].tolist():
            k = int(sizes[lane])
            subset = candidates[:k]
            if int(n_new[lane]) == 0:
                cmd = Command(Action.DELETE, [c.node for c in subset])
                lane_cost = 0.0
            else:
                replacement = self._decode_replacement(
                    snapshot, viable[lane, 0], zone[lane, 0], ct[lane, 0],
                    used[lane, 0], int(tmpl_id[lane, 0]), subset,
                )
                if replacement is None:
                    continue
                cmd = Command(
                    Action.REPLACE, [c.node for c in subset], [replacement]
                )
                lane_cost = float(new_cost[lane])
            if not cost_scoring:
                best, best_k = cmd, k
                break
            saving = float(old_cum[k - 1]) - lane_cost if k >= 1 else 0.0
            if np.isnan(saving):
                saving = -np.inf  # unpriceable subset: never preferred
            if saving > best_saving or (saving == best_saving and k > best_k):
                best, best_k, best_saving = cmd, k, saving
        return best, best_k, int(accepted.sum())

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
