"""Incremental warm-start solving over the versioned snapshot store.

The full kernel solve re-encodes and re-solves the whole cluster snapshot
every reconcile.  At steady-state churn rates only a handful of pods change
between ticks, so this module amortizes: a ``IncrementalSolveSession`` keeps
the previous solve's padded tensors (solver.tpu.SolvePrep), its final scan
carry (ops.solve.WarmCarry, device-resident), and host-side placement
bookkeeping; each reconcile a ``FallbackPolicy`` decides **full** vs
**delta**:

  full    encode → commit to the SnapshotStore → solve from scratch → adopt
          the carry.  Chosen on the first solve, on any supply-side change
          (nodes / bound pods / catalog / templates), on a class-shape change
          (new/removed equivalence classes — the tensor axes moved), when the
          delta fraction exceeds ``max_delta_fraction``, and periodically as
          the optimality **audit** (``audit_interval``) that measures and
          resets accumulated repair drift.
  delta   no encode at all: evicted pods' capacity/topology counts are
          returned to the carry (``ops.solve.repair_free``), then ONE repair
          executable runs over the previous padded tensors with a class-count
          vector holding only the new (plus previously-failed) pods, resumed
          from the carry.  Same class step, same phases, same constraint
          semantics — the repair is literally the full solve's scan continued.

Decisions surface as the ``solve.mode`` span attribute and the
``karpenter_solve_mode_total{mode}`` counter so the amortization is
observable.  ``KC_SOLVER_INCREMENTAL=0`` disables the session entirely — the
degenerate case is exactly the old full-solve-every-reconcile path.
See docs/INCREMENTAL.md.
"""

from __future__ import annotations

import copy
import logging
import os
from dataclasses import dataclass, field, replace as dc_replace
from typing import Dict, List, Optional, Tuple

import numpy as np

from karpenter_core_tpu import tracing
from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.models import store as store_mod
from karpenter_core_tpu.models.snapshot import refuse_ct_spread
from karpenter_core_tpu.models.store import (
    SnapshotStore,
    VersionedSnapshot,
    class_key,
    diff_members,
)
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.utils import pipeline as pipeline_mod
from karpenter_core_tpu.utils.watchdog import SolveTimeout

log = logging.getLogger(__name__)

SOLVE_MODE = REGISTRY.counter(
    "karpenter_solve_mode_total",
    "Kernel solve dispatches by mode: full re-solve vs incremental delta "
    "repair (docs/INCREMENTAL.md).",
    ("mode",),
)

MODE_FULL = "full"
MODE_DELTA = "delta"


def incremental_enabled() -> bool:
    """Process-wide kill switch: KC_SOLVER_INCREMENTAL=0 keeps the old
    full-solve-every-reconcile path as the degenerate case."""
    return os.environ.get("KC_SOLVER_INCREMENTAL", "1") != "0"


@dataclass
class FallbackPolicy:
    """Per-reconcile full-vs-delta decision (module docstring)."""

    enabled: bool = True
    # delta fraction (added+evicted over population) above which a repair
    # stops being the right amortization — the phases run per dirty class
    # anyway, so past this a full solve is both faster and drift-free
    max_delta_fraction: float = 0.25
    # delta reconciles between full-solve audits (0 = never audit); the audit
    # both measures repair drift (objective = opened-node count) and resets it
    audit_interval: int = 16
    # materialized sessions (the provisioning controller, whose previous
    # decisions become real nodes) may only repair when the previous solve
    # opened no new slots — an opened slot was launched and must re-enter as
    # a real existing node (supply change ⇒ full) rather than be re-decided
    materialized: bool = False

    @classmethod
    def from_env(cls, materialized: bool = False) -> "FallbackPolicy":
        def _f(name: str, default: float) -> float:
            try:
                return float(os.environ.get(name, default))
            except ValueError:
                return default

        return cls(
            enabled=incremental_enabled(),
            max_delta_fraction=_f("KC_DELTA_MAX_FRACTION", 0.25),
            audit_interval=int(_f("KC_DELTA_AUDIT_INTERVAL", 16)),
            materialized=materialized,
        )

    def decide(self, delta, delta_ticks: int, prev_slots_used: int,
               known_classes=None, mesh_changed: bool = False
               ) -> Tuple[str, str]:
        """(mode, reason).  ``delta`` is a models.store.SnapshotDelta (or None
        on the first solve); ``delta_ticks`` counts repairs since the last
        full solve; ``prev_slots_used`` the slots the previous solve opened;
        ``known_classes`` the class keys the previous padded tensors can
        express — a "new" class returning to a known (emptied) row repairs
        fine, while a genuinely unseen key means the class axis moved and the
        snapshot must re-encode.  Removed classes never force a full solve:
        an emptied row idles as a zero-count scan step.  ``mesh_changed``:
        the live solve-mesh topology (parallel.mesh.solve_mesh_axes) no
        longer matches the one the warm prep was built for — the carry's
        planes are sharded for the OLD layout and the catalog pad multiple
        moved with it, so the lineage re-anchors with a full solve on the
        new topology."""
        if not self.enabled:
            return MODE_FULL, "disabled"
        if delta is None:
            return MODE_FULL, "first"
        if mesh_changed:
            return MODE_FULL, "mesh-changed"
        if delta.node_side_changed:
            return MODE_FULL, "supply-changed:" + ",".join(delta.changed_planes)
        unknown = tuple(
            k for k in delta.new_classes
            if known_classes is None or k not in known_classes
        )
        if unknown:
            return MODE_FULL, "class-shape"
        if self.materialized and prev_slots_used > 0:
            return MODE_FULL, "materialized-slots"
        if self.audit_interval and delta_ticks >= self.audit_interval:
            return MODE_FULL, "audit"
        if delta.delta_fraction > self.max_delta_fraction:
            return MODE_FULL, f"delta-fraction:{delta.delta_fraction:.3f}"
        return MODE_DELTA, "delta"


@dataclass
class _WarmState:
    """Everything one delta reconcile needs, carried from the last full solve
    and updated by every repair."""

    versioned: VersionedSnapshot
    prep: object  # solver.tpu.SolvePrep (padded tensors; reused verbatim)
    carry: object  # ops.solve.WarmCarry (device)
    assign: np.ndarray  # i32[C_pad, N] cumulative new-slot placements
    assign_ex: np.ndarray  # i32[C_pad, E_pad] cumulative existing placements
    n_next: int  # slots the scan has opened so far
    members: Dict[tuple, Tuple[str, ...]]  # class key -> live member uids
    class_index: Dict[tuple, int]  # class key -> class row
    pod_loc: Dict[str, Tuple[int, str, int]]  # uid -> (row, "new"|"ex", idx)
    row_key: Dict[int, tuple]  # class row -> class key (pod_loc's inverse leg)
    failed_pods: Dict[str, Tuple[int, object]]  # uid -> (row, Pod), unplaced
    member_rows: np.ndarray  # i32[C_pad, G1] topology membership per class
    own_inv_rows: np.ndarray  # i32[C_pad, G1] inverse-ownership per class
    supply: str
    state_nodes: list = field(default_factory=list)
    delta_ticks: int = 0
    initial_slots_used: int = 0  # slots open at full-solve time
    # lineage-placed pods that have since BOUND: physically on their node now,
    # still counted by the carry, excluded from the membership and supply
    # views (IncrementalSolveSession._absorb_bound)
    materialized: set = field(default_factory=set)


@dataclass
class _PendingTick:
    """One dispatched-but-unsettled deferred tick (the pipeline's in-flight
    slot).  ``kind`` is "delta" (a repair: ``data`` holds the dispatch
    record, the post-tick membership, and the captured population snapshot a
    settle-time window/slot exhaustion re-anchors from) or "full" (an
    anchor: ``data`` holds the committed snapshot, the prep, the device
    outputs, and the fetch ticket whose copies are already in flight)."""

    kind: str  # "delta" | "full"
    box: "PendingResults"
    data: dict


class PendingResults:
    """Deferred TPUSolveResults handle (``solve(deferred=True)``).

    ``result()`` settles the session's pending tick if it still is pending
    (the completion barrier), then materializes the decode — by the time the
    canonical double-buffered loop calls it, the barrier already ran at the
    next solve's entry and only host materialize is left, overlapped with
    that solve's device compute.  Safe to call any number of times; raises
    whatever the tick's settle raised."""

    __slots__ = ("_session", "_results", "_error", "_decode", "_settled")

    def __init__(self, session, results=None, error=None) -> None:
        self._session = session
        self._results = results
        self._error = error
        self._decode = None  # set at settle for delta ticks
        self._settled = results is not None or error is not None

    def _settle_with(self, results=None, error=None, decode=None) -> None:
        self._results = results
        self._error = error
        self._decode = decode
        self._settled = True

    def done(self) -> bool:
        return self._settled

    def result(self):
        if not self._settled:
            self._session.settle()
        if self._error is not None:
            raise self._error
        if self._results is None and self._decode is not None:
            decode, self._decode = self._decode, None
            try:
                self._results = decode()
            except BaseException as e:  # noqa: BLE001 - cached, then raised
                # record the failure so every later result() re-raises it
                # instead of silently returning None
                self._error = e
                raise
        return self._results


class IncrementalSolveSession:
    """One warm-start solve lineage: full solves adopt state, delta solves
    repair it.  Bind a fresh TPUSolver each reconcile via ``rebind`` (the
    controller rebuilds its solver per batch); the session survives as long
    as the fallback policy keeps judging deltas safe.

    ``solve(..., deferred=True)`` runs the tick through the double-buffered
    pipeline (docs/KERNEL_PERF.md "Layer 7"): the repair dispatches and the
    call returns a PendingResults immediately; the completion barrier,
    bookkeeping, and decode settle at the NEXT solve's entry (or at
    ``result()``), so the next tick's planning and the previous tick's host
    materialize overlap this tick's device compute and device→host copy.
    ``KC_PIPELINE=0`` makes deferred calls settle inline — the serial loop
    exactly."""

    def __init__(self, solver=None, policy: Optional[FallbackPolicy] = None,
                 run_prepared=None) -> None:
        self.solver = solver
        self.policy = policy or FallbackPolicy.from_env()
        self.store = SnapshotStore()
        self._warm: Optional[_WarmState] = None
        self.last_mode: Optional[str] = None
        self.last_reason: Optional[str] = None
        self.last_audit_drift_nodes: Optional[int] = None
        self.mode_counts: Dict[str, int] = {MODE_FULL: 0, MODE_DELTA: 0}
        # dispatch hook: ``run_prepared(prep, **kw)`` replaces
        # ``solver.run_prepared`` so a host (the multi-tenant solver service)
        # can route the device execution through its batch coalescer — the
        # prep/decode bookkeeping around it is unchanged.  Full solves AND
        # delta repairs route through it: compatible repair windows from
        # different tenants fuse on one vmapped dispatch (docs/SERVICE.md
        # "Solve fusion").  Hooked repairs never donate the carry — the
        # coalescer may stack it into a batched program whose member buffers
        # must stay readable — so the hook passes donate_carry=False through.
        self._run_prepared = run_prepared
        self._forced_reason: Optional[str] = None
        # pipelined-loop state: the in-flight deferred tick, the two-deep
        # ring of reusable host staging buffers its fetches land in, and the
        # last settled-but-undecoded box (materialized before its staging
        # slot can be rewritten)
        self._pending: Optional[_PendingTick] = None
        self._staging = None
        self._undecoded: Optional[PendingResults] = None

    def rebind(self, solver) -> None:
        self.solver = solver

    def reset(self) -> None:
        """Drop the warm lineage (next solve is full).  A pending deferred
        tick settles first so its handle stays consumable."""
        if self._pending is not None:
            try:
                self.settle()
            except Exception:  # noqa: BLE001 - the handle carries the error
                pass
        self._warm = None

    def force_full(self, reason: str) -> None:
        """Make the NEXT solve a full re-anchor with this reason, whatever the
        fallback policy would have decided.  The multi-tenant service uses it
        for lineage trust failures the policy cannot see server-side: a
        client claiming a session version this process doesn't hold
        (``session-lost`` after a server restart or an LRU/TTL eviction), a
        client that itself restarted, or a supply-digest mismatch."""
        self._forced_reason = reason

    def lineage_version(self) -> int:
        """The warm lineage's snapshot-store version (0 = no lineage) — what
        the tenant protocol echoes to clients so a restarted server is
        detectable (docs/SERVICE.md)."""
        self.settle()
        if self._warm is None:
            return 0
        return int(self._warm.versioned.version)

    def lineage_state(self) -> Dict[str, object]:
        """Cross-process-stable verification summary of the warm lineage —
        what the durable-session journal (service/journal.py) writes with
        every record and what recovery compares a REPLAYED lineage against
        before trusting it (never-trust: any field differing downgrades the
        tenant to the ``session-lost`` re-anchor).  Everything here is a
        plain msgpack-able scalar/str/dict: the store's per-plane content
        digests and supply anchor are sha256 hex (PYTHONHASHSEED-free by
        construction), and the placement signature canonicalizes its class
        keys through models.store.stable_digest because they hold frozensets
        whose raw repr order is hash-randomized."""
        self.settle()
        w = self._warm
        if w is None:
            return {"version": 0}
        return {
            "version": int(w.versioned.version),
            "supply": w.supply,
            "planes": dict(w.versioned.digests),
            "aggregates": self.aggregates(),
            "signature": store_mod.stable_digest(self.node_signature()),
            "delta_ticks": int(w.delta_ticks),
        }

    # -- membership extraction -------------------------------------------------

    @staticmethod
    def _members_of(pods_or_classes):
        """(class key -> uids, uid -> Pod getter, classes-or-None) from a
        PodIngest or a prebuilt PodClass list — riding the ingest's
        bookkeeping, no signature re-derivation per pod and no per-pod
        materialization (the getter resolves only the delta's uids).  A
        class whose pods are counted (``CountedPods``, the tenant wire)
        gives its ``CountedUids`` and copies a member only when the getter
        asks for it."""
        from karpenter_core_tpu.models.columnar import PodIngest

        if isinstance(pods_or_classes, PodIngest):
            return pods_or_classes.class_members(), pods_or_classes.get, None
        classes = list(pods_or_classes)
        members = {}
        by_uid = {}
        counted = {}
        for cls in classes:
            if getattr(cls, "is_ladder_variant", False):
                continue
            key = class_key(cls)
            if isinstance(cls.pods, store_mod.CountedPods):
                members[key] = cls.pods.uids
                counted[cls.pods.uids.base] = cls.pods
                continue
            members[key] = tuple(p.uid for p in cls.pods)
            for p in cls.pods:
                by_uid[p.uid] = p
        if counted:
            return members, _MemberResolver(by_uid, counted), classes
        return members, by_uid.get, classes

    # -- the solve entry -------------------------------------------------------

    def solve(
        self,
        pods_or_classes,
        state_nodes: Optional[list] = None,
        bound_pods: Optional[list] = None,
        deferred: bool = False,
    ):
        """TPUSolveResults for the current population.  Full reconciles see
        the whole picture (every node decision); delta reconciles return only
        this tick's placements (new pods onto new/existing capacity), which
        is exactly what the controller needs to act on.  Raises
        models.snapshot.KernelUnsupported exactly like TPUSolver.solve.

        ``deferred=True`` returns a PendingResults handle instead of
        results: delta ticks dispatch and settle at the NEXT solve call (the
        pipelined loop — class docstring); full solves settle inline and the
        handle is immediately consumable.  With KC_PIPELINE=0 the handle is
        always settled inline — the serial loop bit-for-bit."""
        from karpenter_core_tpu.solver.tpu import SOLVER_DISPATCH

        # everything the tick decides before it touches the solver — the
        # membership view, the three digests, the diff and the policy's
        # verdict — is one span (docs/OBSERVABILITY.md)
        with tracing.span("session.diff") as diff_sp:
            # settle the in-flight deferred tick FIRST: this tick's membership
            # diff and eviction plan read the bookkeeping that tick rewrites
            self.settle()
            # ``deferred`` shapes the RETURN TYPE (a handle); ``pipelined``
            # whether the tick actually stays in flight — KC_PIPELINE=0 settles
            # inline, so the handle is just the serial results in a box
            pipelined = deferred and pipeline_mod.pipeline_enabled()
            members, by_uid, classes = self._members_of(pods_or_classes)
            if self._warm is not None:
                self._absorb_bound({p.uid for p in (bound_pods or [])})
            from karpenter_core_tpu.policy import planes as policy_planes

            catalog = store_mod.catalog_digest(
                self.solver.provisioners, self.solver.instance_types
            ) + policy_planes.policy_input_digest(
                # the policy side of the supply: offering prices + interruption
                # priors + objective knobs + the provider's pending-ICE set.  A
                # set_price between reconciles (the spot market moving), a weight
                # change, or a type starting to fail creates flips this digest
                # and the fallback policy escalates to a full solve — a repair
                # would otherwise keep optimizing against a stale price/risk
                # sheet (docs/INCREMENTAL.md "Policy-digest escalation")
                self.solver.instance_types, getattr(self.solver, "policy", None),
                provider=getattr(self.solver, "cloud_provider", None),
            )
            # the comparison digest excludes bound pods this lineage placed itself
            # (their binding is the lineage's own work materializing, not a supply
            # change); the ANCHOR a full solve stores is unfiltered, because a
            # fresh encode sees — and accounts — every bound pod
            known = self._warm.materialized if self._warm is not None else ()
            supply = store_mod.supply_digest(
                state_nodes,
                [p for p in (bound_pods or []) if p.uid not in known]
                if known else bound_pods,
            ) + catalog
            supply_anchor = supply if not known else (
                store_mod.supply_digest(state_nodes, bound_pods) + catalog
            )

            delta = None
            if self._warm is not None:
                delta = diff_members(
                    self._warm.members, members,
                    from_version=self._warm.versioned.version,
                    supply_changed=() if supply == self._warm.supply else ("supply",),
                )
            # mesh-topology watch: the warm carry is sharded for (and its repair
            # executable keyed on) the topology captured at prepare time — a
            # KC_SOLVER_MESH flip or a device-count change escalates to full
            from karpenter_core_tpu.parallel import mesh as mesh_mod

            mesh_changed = self._warm is not None and (
                getattr(self._warm.prep, "mesh_axes", None)
                != mesh_mod.solve_mesh_axes()
            )
            mode, reason = self.policy.decide(
                delta,
                self._warm.delta_ticks if self._warm is not None else 0,
                self._warm.n_next - self._warm.initial_slots_used
                if self._warm is not None else 0,
                known_classes=self._warm.class_index
                if self._warm is not None else None,
                mesh_changed=mesh_changed,
            )
            forced = self._forced_reason
            if forced is not None:
                # lineage trust override (force_full): full re-anchor, one shot
                mode, reason = MODE_FULL, forced
                self._forced_reason = None
            diff_sp.set(**{"classes": len(members), "solve.mode": mode})
            if delta is not None and tracing.enabled():
                # a pass over the dirty classes: only a live span pays for it
                diff_sp.set(
                    arrivals=delta.added_count, departures=delta.evicted_count,
                    dirty_classes=len(set(delta.added) | set(delta.evicted)),
                    counted_classes=delta.counted_classes,
                    uid_classes=delta.uid_classes,
                )

        try:
            fault = SOLVER_DISPATCH.hit(
                kinds=("error", "timeout"), op="solve", classes=len(members)
            )
            if fault is not None and fault.kind in ("error", "timeout"):
                raise RuntimeError(fault.describe())

            with tracing.span("solve.incremental") as sp:
                if mode == MODE_DELTA and pipelined:
                    handle = self._delta_dispatch_deferred(
                        delta, by_uid,
                        pods_or_classes if classes is None else classes,
                        members, state_nodes, bound_pods, supply_anchor,
                    )
                    if handle is not None:
                        sp.set(**{"solve.mode": mode,
                                  "solve.mode.reason": reason,
                                  "solve.deferred": True})
                        # mode accounting waits for the settle — a window
                        # exhaustion discovered there escalates to full
                        return handle
                    mode, reason = MODE_FULL, "slots-exhausted"
                elif mode == MODE_DELTA:
                    results = self._delta_solve(delta, by_uid, state_nodes)
                    if results is None:  # repair ran out of room: escalate
                        mode, reason = MODE_FULL, "slots-exhausted"
                if mode == MODE_FULL:
                    results = self._full_solve(
                        pods_or_classes if classes is None else classes,
                        members, state_nodes, bound_pods, supply_anchor, reason,
                        deferred=pipelined,
                    )
                    if isinstance(results, PendingResults):
                        sp.set(**{"solve.mode": mode,
                                  "solve.mode.reason": reason,
                                  "solve.deferred": True})
                        # mode accounting waits for the settle
                        return results
                sp.set(**{"solve.mode": mode, "solve.mode.reason": reason})
        except Exception:
            if forced is not None:
                # the forced re-anchor never answered (fault/ejection): it is
                # still owed, so the RETRY carries the same reason — a
                # post-restart session-lost must not relabel itself "first"
                # just because chaos ate the first attempt
                self._forced_reason = forced
            raise
        SOLVE_MODE.labels(mode).inc()
        self.last_mode, self.last_reason = mode, reason
        self.mode_counts[mode] = self.mode_counts.get(mode, 0) + 1
        if deferred:
            return PendingResults(self, results=results)
        return results

    def _absorb_bound(self, bound_uids) -> None:
        """Lineage-placed pods that have since BOUND leave the pending
        population as the lineage's own work materializing, not as evictions:
        their capacity stays committed in the carry (they now physically
        occupy the node the repair placed them on), they leave the membership
        view so the diff never frees them, and the supply comparison excludes
        them so their binding doesn't read as a supply change.  Genuinely
        foreign bound pods still flip the supply digest ⇒ full solve."""
        w = self._warm
        if not bound_uids:
            return
        moved = [uid for uid in w.pod_loc if uid in bound_uids]
        if not moved:
            return
        trimmed: Dict[tuple, List[str]] = {}
        for uid in moved:
            row, _kind, _idx = w.pod_loc.pop(uid)
            key = w.row_key.get(row)
            if key is not None:
                trimmed.setdefault(key, []).append(uid)
            w.materialized.add(uid)
        for key, uids in trimmed.items():
            # counted while the bound pods were the class's tail; a pod that
            # bound out of the middle leaves a uid tuple
            left = store_mod.members_without(w.members.get(key, ()), uids)
            if left:
                w.members[key] = left
            else:
                w.members.pop(key, None)

    # -- full path -------------------------------------------------------------

    def _full_solve(self, pods_or_classes, members, state_nodes, bound_pods,
                    supply, reason, deferred: bool = False):
        solver = self.solver
        prev_nodes = self.node_count() if self._warm is not None else None
        try:
            if isinstance(pods_or_classes, list):
                snapshot = solver.encode_classes(
                    pods_or_classes, state_nodes=state_nodes, bound_pods=bound_pods
                )
            else:
                snapshot = solver.encode(pods_or_classes, state_nodes, bound_pods)
            refuse_ct_spread(snapshot.classes, "the incremental path")
            versioned = self.store.commit(snapshot, supply=supply)
            prep = solver.prepare_encoded(
                snapshot, state_nodes, bound_pods,
                max(solve_ops.estimate_slots(snapshot), self._continued_slots(reason)),
            )
            run = self._run_prepared or solver.run_prepared
            outputs = run(prep)
            if deferred and pipeline_mod.pipeline_enabled():
                # the pipelined anchor: the encode/commit/prepare above ran
                # host-side; the device solve is in flight — settle (barrier,
                # slot-exhaustion retry, adoption) waits for the next solve's
                # entry and decode for the handle, both overlapping this
                # solve's device compute
                if self._staging is None:
                    self._staging = pipeline_mod.HostStagingRing()
                ticket = solver.begin_fetch(outputs, ring=self._staging)
                box = PendingResults(self)
                self._pending = _PendingTick(
                    kind="full", box=box, data=dict(
                        snapshot=snapshot, versioned=versioned, prep=prep,
                        run=run, outputs=outputs, ticket=ticket,
                        members=dict(members), supply=supply,
                        state_nodes=list(state_nodes or ()),
                        prev_nodes=prev_nodes, reason=reason, solver=solver,
                    ),
                )
                return box
            outputs, ticket = solver.grow_until_fits(prep, outputs, run=run)
            results = solver.decode(
                snapshot, outputs, state_nodes or [], fetched=ticket
            )
        except Exception:
            self._warm = None  # a half-built lineage must not seed repairs
            raise
        self._adopt(versioned, prep, outputs, results, members, supply,
                    state_nodes, prev_nodes, reason)
        return results

    def _continued_slots(self, reason: str) -> int:
        """The least slots for an anchor that CONTINUES this lineage's
        population (an audit, or a repair that ran out of room): a quarter
        over what the lineage had opened, as a power of two; 0 where the
        anchor starts over.  Repairs open fresh slots faster than departures
        empty whole nodes, so ``n_next`` creeps between audits (PERF.md §6,
        PR 26: +26 a cycle at 50k pods); an estimate that fits the answer
        with little to spare would otherwise send such a tenant back here
        every few ticks."""
        w = self._warm
        if w is None or not (reason.startswith("audit") or reason == "slots-exhausted"):
            return 0
        return 1 << (w.n_next + w.n_next // 4 - 1).bit_length() if w.n_next else 0

    def _settle_full(self, pending: _PendingTick) -> None:
        """Retire a deferred anchor: completion barrier, the slot-exhaustion
        retries (synchronous, rare), adoption; decode stays deferred to the
        handle's ``result()``."""
        f = pending.data

        def adopt(outputs, ticket):
            # a retry's ticket is adopted BEFORE its barrier: a wait() that
            # fails must leave THIS ticket reachable for the settle error
            # path's invalidate, not leak it behind the consumed original
            f["outputs"], f["ticket"] = outputs, ticket

        f["solver"].grow_until_fits(
            f["prep"], f["outputs"], ticket=f["ticket"], run=f["run"],
            ring=self._staging, adopt=adopt,
        )
        self._adopt(
            f["versioned"], f["prep"], f["outputs"], None, f["members"],
            f["supply"], f["state_nodes"], f["prev_nodes"], f["reason"],
        )
        pending.box._settle_with(decode=lambda: f["solver"].decode(
            f["snapshot"], f["outputs"], f["state_nodes"], fetched=f["ticket"]
        ))
        self._undecoded = pending.box

    @tracing.traced("session.adopt")
    def _adopt(self, versioned, prep, outputs, results, members, supply,
               state_nodes, prev_nodes, reason):
        import jax

        from karpenter_core_tpu.utils import watchdog

        carry = solve_ops.warm_carry_of(outputs)
        assign, assign_ex, n_next = watchdog.run(
            "pipeline.fetch", jax.device_get,
            (outputs.assign, outputs.assign_existing, outputs.state.n_next),
            key="adopt",
        )
        assign = np.asarray(assign, dtype=np.int32).copy()
        assign_ex = np.asarray(assign_ex, dtype=np.int32).copy()
        snapshot = versioned.snapshot
        pod_loc, unplaced = _locate_pods(snapshot, assign, assign_ex)
        all_pods = {
            p.uid: p for cls in snapshot.classes for p in cls.pods
        }
        failed_pods = {uid: (row, all_pods[uid]) for uid, row in unplaced}
        member_rows, own_inv_rows = _topology_rows(prep)
        if pipeline_mod.pipeline_enabled():
            # upload the padded planes ONCE: every repair in this lineage
            # then re-dispatches over the same device buffers — only the
            # per-tick count vector crosses the host→device boundary again
            # (KC_PIPELINE=0 keeps the old re-upload-per-tick path)
            prep = self.solver.upload_prep(prep)
        index = versioned.index_of()
        row_key = {i: row.key for i, row in enumerate(versioned.rows)}
        tracing.set_attrs(placed=len(pod_loc), failed=len(failed_pods),
                          classes=len(row_key))
        self.last_audit_drift_nodes = None
        if prev_nodes is not None and reason.startswith("audit"):
            fresh = int(np.sum(np.sum(assign, axis=0) > 0))
            self.last_audit_drift_nodes = prev_nodes - fresh
            if self.last_audit_drift_nodes:
                log.info(
                    "incremental solve audit: repair lineage carried %+d "
                    "node(s) of drift vs the fresh full solve",
                    self.last_audit_drift_nodes,
                )
        self._warm = _WarmState(
            versioned=versioned,
            prep=prep,
            carry=carry,
            assign=assign,
            assign_ex=assign_ex,
            n_next=int(n_next),
            members=dict(members),
            class_index=index,
            pod_loc=pod_loc,
            row_key=row_key,
            failed_pods=failed_pods,
            member_rows=member_rows,
            own_inv_rows=own_inv_rows,
            supply=supply,
            state_nodes=list(state_nodes or []),
            initial_slots_used=0,
        )
        if carry is None:
            self._warm = None  # outputs predate the carry fields

    def adopt_restored(self, versioned, prep, carry, *, assign, assign_ex,
                       n_next, members, pod_loc, failed_rows, supply,
                       state_nodes, delta_ticks=0, initial_slots_used=0,
                       materialized=()) -> None:
        """Adopt a deserialized warm lineage (fleet/checkpoint.py restore).

        The tensor-level twin of ``_adopt``: instead of fetching a just-run
        solve's outputs it takes checkpointed planes verbatim — the padded
        prep, the warm scan carry, the cumulative assignment planes, the
        membership bookkeeping — and rebuilds the exact ``_WarmState`` the
        originating replica held, so the next delta repairs over the restored
        carry bit-for-bit instead of replaying the request chain.  The CALLER
        owns the never-trust verification: ``versioned`` must be a fresh
        commit whose plane digests equal the checkpointed ones before this
        runs, and ``lineage_state()`` must equal the checkpointed state after
        (fleet/checkpoint.restore_session).  Any inconsistency raises — the
        restore ladder falls to journal replay, never a stale answer."""
        import jax

        if self._pending is not None:
            self.settle()
        if getattr(prep, "mesh_axes", None) is not None:
            # a mesh-sharded carry would need resharding onto THIS replica's
            # device topology; the ladder's replay rung covers that case
            raise ValueError("mesh-sharded lineage cannot adopt a checkpoint")
        carry = jax.device_put(carry)
        assign = np.asarray(assign, dtype=np.int32).copy()
        assign_ex = np.asarray(assign_ex, dtype=np.int32).copy()
        snapshot = versioned.snapshot
        all_pods = {p.uid: p for cls in snapshot.classes for p in cls.pods}
        if snapshot.cls_root is not None:
            root_of = [int(r) for r in snapshot.cls_root]
        else:
            root_of = list(range(len(snapshot.classes)))
        failed_pods = {}
        for uid, row in dict(failed_rows or {}).items():
            pod = all_pods.get(uid)
            if pod is None:
                # a pod that joined on a DELTA tick after the anchor: absent
                # from the anchor snapshot, but class members are fungible
                # copies of the representative differing only in uid
                # (service tenant path, models.store.CountedPods) — rebuild it
                root = root_of[int(row)] if int(row) < len(root_of) else -1
                reps = (snapshot.classes[root].pods
                        if 0 <= root < len(snapshot.classes) else ())
                if not reps:
                    raise ValueError(
                        f"checkpointed failed pod {uid!r} has no class "
                        f"representative in the re-encoded anchor snapshot"
                    )
                pod = store_mod.member_copy(reps[0], uid)
            failed_pods[uid] = (int(row), pod)
        member_rows, own_inv_rows = _topology_rows(prep)
        if pipeline_mod.pipeline_enabled():
            prep = self.solver.upload_prep(prep)
        self._warm = _WarmState(
            versioned=versioned,
            prep=prep,
            carry=carry,
            assign=assign,
            assign_ex=assign_ex,
            n_next=int(n_next),
            members={k: tuple(v) for k, v in members.items()},
            class_index=versioned.index_of(),
            pod_loc={u: (int(r), str(kind), int(i))
                     for u, (r, kind, i) in pod_loc.items()},
            row_key={i: row.key for i, row in enumerate(versioned.rows)},
            failed_pods=failed_pods,
            member_rows=member_rows,
            own_inv_rows=own_inv_rows,
            supply=supply,
            state_nodes=list(state_nodes or []),
            delta_ticks=int(delta_ticks),
            initial_slots_used=int(initial_slots_used),
            materialized=set(materialized),
        )

    def export_lineage(self) -> Optional[Dict[str, object]]:
        """The warm lineage as host-side data — what the fleet checkpoint
        (fleet/checkpoint.py) serializes, and the exact argument set
        ``adopt_restored`` consumes on the adopting replica.  Device-resident
        leaves (the scan carry, an uploaded prep) are fetched here under the
        pipeline-fetch watchdog; class keys — frozenset-bearing tuples, not
        msgpack-able — are translated to class ROWS, which the restorer
        inverts through its freshly committed ``versioned.rows``.  None when
        there is no warm lineage (nothing to checkpoint)."""
        import jax

        from karpenter_core_tpu.utils import watchdog

        self.settle()
        w = self._warm
        if w is None or w.carry is None:
            return None
        if getattr(w.prep, "mesh_axes", None) is not None:
            return None  # sharded carries restore via replay, never tensors
        prep, carry = watchdog.run(
            "pipeline.fetch", jax.device_get, (w.prep, w.carry),
            key="lineage-export",
        )
        # strict: a members key outside the committed class index would mean
        # the lineage invariant broke — let the KeyError surface; the
        # checkpoint plane degrades that tenant to the replay rung
        members_rows = sorted(
            (int(w.class_index[key]), sorted(uids))
            for key, uids in w.members.items()
        )
        return {
            "version": int(w.versioned.version),
            "supply": w.supply,
            "state": self.lineage_state(),
            "prep": prep,
            "carry": carry,
            "assign": w.assign.copy(),
            "assign_ex": w.assign_ex.copy(),
            "n_next": int(w.n_next),
            "members_rows": members_rows,
            "pod_loc": {uid: [int(r), str(kind), int(i)]
                        for uid, (r, kind, i) in w.pod_loc.items()},
            "failed_rows": {uid: int(row)
                            for uid, (row, _pod) in w.failed_pods.items()},
            "delta_ticks": int(w.delta_ticks),
            "initial_slots_used": int(w.initial_slots_used),
            "materialized": sorted(w.materialized),
        }

    # -- delta path ------------------------------------------------------------
    #
    # One delta tick is four stages — plan (host), dispatch (device, async),
    # settle (completion barrier + bookkeeping), decode (host materialize).
    # The serial path (_delta_solve) runs them back to back in the exact
    # pre-pipeline order; the deferred path (_delta_dispatch_deferred) stops
    # after dispatch and settles at the next solve's entry, so the stages of
    # consecutive ticks overlap (docs/KERNEL_PERF.md "Layer 7").

    @tracing.traced("session.plan")
    def _delta_plan(self, delta, by_uid):
        """The host-side tick plan: eviction free planes, the delta count
        vector, and the post-tick membership.  None when an unseen class key
        means the padded tensors cannot express the delta (caller escalates
        to a full solve)."""
        w = self._warm
        c_pad = w.prep.cls.count.shape[0]  # shape read only: may be device
        n_slots = w.assign.shape[1]
        e_pad = w.assign_ex.shape[1]

        # evictions: return departed pods' capacity and counts to the carry
        free_new = np.zeros((c_pad, n_slots), dtype=np.int32)
        free_ex = np.zeros((c_pad, e_pad), dtype=np.int32)
        evicted_locs: List[Tuple[str, Tuple[int, str, int]]] = []
        for key, uids in delta.evicted.items():
            for uid in uids:
                loc = w.pod_loc.get(uid)
                if loc is None:
                    continue  # was failed/unplaced: nothing to free
                row, kind, idx = loc
                (free_new if kind == "new" else free_ex)[row, idx] += 1
                evicted_locs.append((uid, loc))

        # additions (+ retry of previously-failed pods): a count vector with
        # only the delta, scanned over the SAME padded tensors
        evicted_set = {u for us in delta.evicted.values() for u in us}
        pods_by_root: Dict[int, List[object]] = {}
        for key, uids in delta.added.items():
            row = w.class_index.get(key)
            if row is None:
                return None  # unseen class key: tensors can't express it
            pods_by_root.setdefault(row, []).extend(by_uid(uid) for uid in uids)
        # still-pending failures retry every repair tick under their own class
        # row — their capacity was never committed to the carry, so a retry is
        # a plain re-placement (the host queue's re-push equivalent).  Iterates
        # the (tiny) failure set, not the whole membership.
        for uid, (row, pod) in w.failed_pods.items():
            if uid not in evicted_set:
                pods_by_root.setdefault(row, []).append(pod)
        counts = np.zeros(c_pad, dtype=np.int32)
        for row, pods in pods_by_root.items():
            counts[row] = len(pods)

        # membership after this tick lands: previous minus evicted plus added,
        # a counted membership staying counted while it is a prefix
        members = dict(w.members)
        for key, uids in delta.evicted.items():
            if key in members:
                members[key] = store_mod.members_without(members[key], uids)
        for key, uids in delta.added.items():
            members[key] = store_mod.members_with(members.get(key, ()), uids)
        members_after = {k: v for k, v in members.items() if v}
        tracing.set_attrs(evictions=len(evicted_locs), dirty_rows=len(pods_by_root),
                          placements=int(counts.sum()),
                          resolved=getattr(by_uid, "resolved", 0))
        return {
            "delta": delta, "free_new": free_new, "free_ex": free_ex,
            "evicted_locs": evicted_locs, "pods_by_root": pods_by_root,
            "counts": counts, "members_after": members_after,
        }

    def _delta_dispatch(self, plan):
        """Dispatch the repair onto the device (asynchronously) and start
        its device→host fetch.  The dispatch routes through the
        ``_run_prepared`` hook when one is set — the tenant service's batch
        coalescer fuses compatible repair windows from different tenants
        onto one vmapped dispatch (docs/SERVICE.md "Solve fusion"); hooked
        repairs never donate.  Unhooked warm dispatches donate the carry
        when the pipeline is armed (utils.pipeline): the pre-dispatch carry
        is dead after this call — only ``keep_carry`` (the full-width carry
        of a WINDOWED repair, which the settle's scatter consumes) may be
        read again, and an exception anywhere past the donating call drops
        the lineage (the except below and its twins in _delta_solve/settle):
        a kept ``_warm`` pointing at a donated buffer would turn one
        transient fault into a crash loop on every later repair."""
        w = self._warm
        free_new, free_ex = plan["free_new"], plan["free_ex"]
        evicted_locs, counts = plan["evicted_locs"], plan["counts"]
        n_slots = w.assign.shape[1]
        # hooked dispatches (the tenant service's coalescer) never donate:
        # the batch program stacks COPIES of member carries, so the solo
        # donation bookkeeping would free buffers the fused path still reads
        # — donation is a solo-dispatch optimization only
        run = self._run_prepared or self.solver.run_prepared
        hooked = self._run_prepared is not None
        donate = pipeline_mod.donation_enabled() and not hooked and not (
            self.solver.policy is not None
            and getattr(self.solver.policy, "enabled", False)
        )
        carry = w.carry
        donated = False

        # bounded repair window (docs/INCREMENTAL.md): gather the dirty slots
        # — freed holes plus a fresh tail — into a fixed power-of-two window
        # so the repair's per-class-step cost scales with the dirty region,
        # not the fleet.  The freed-hole planes double as the placement
        # preference: fills refill the exact slots departures vacated before
        # falling back to the normal order, so steady-state churn keeps the
        # lineage's assignments identical to a from-scratch solve.
        g1 = w.member_rows.shape[1]
        n_zones = w.prep.statics_arrays.tmpl_zone.shape[1]
        hole_slots = sorted({loc[2] for _, loc in evicted_locs if loc[1] == "new"})
        window = _window_indices(hole_slots, w.n_next, n_slots)
        try:
            if evicted_locs:
                free_fn = (
                    solve_ops.repair_free_donated if donate
                    else solve_ops.repair_free
                )
                donated = donate
                carry = free_fn(
                    carry, free_new, free_ex,
                    _as_request_plane(w.prep.cls.requests),
                    w.member_rows, w.own_inv_rows,
                )
            if window is not None:
                idx, n_open_w = window
                win_carry, base = solve_ops.gather_repair_window(
                    carry, idx, np.int32(n_open_w)
                )
                repair_plan = solve_ops.RepairPlan(
                    pref_new=free_new[:, idx],
                    pref_ex=free_ex,
                    base_fwd_sing=base[0],
                    base_fwd_full=base[1],
                    base_inv_full=base[2],
                )
                keep_carry = carry
                outputs = run(
                    w.prep, count=counts, warm_carry=win_carry,
                    repair_plan=repair_plan, n_slots=len(idx),
                    donate_carry=donate,
                )
                donated = donated or donate
            else:
                zeros_gz = np.zeros((g1, n_zones), dtype=np.int32)
                repair_plan = solve_ops.RepairPlan(
                    pref_new=free_new, pref_ex=free_ex,
                    base_fwd_sing=zeros_gz, base_fwd_full=zeros_gz,
                    base_inv_full=zeros_gz,
                )
                keep_carry = None
                outputs = run(
                    w.prep, count=counts, warm_carry=carry,
                    repair_plan=repair_plan, donate_carry=donate,
                )
                donated = donated or donate
            if self._staging is None and pipeline_mod.pipeline_enabled():
                self._staging = pipeline_mod.HostStagingRing()
            ticket = self.solver.begin_fetch(outputs, ring=self._staging)
        except BaseException:
            if donated or donate:
                self._warm = None  # the carry was donated: lineage is gone
            raise
        # decode consumes a delta VIEW of the snapshot: same planes, classes
        # carry only this tick's pods (built here, while the device works)
        delta_view = _delta_view(w.versioned.snapshot, plan["pods_by_root"])
        return {
            "plan": plan, "outputs": outputs, "ticket": ticket,
            "window": window, "keep_carry": keep_carry, "donated": donated,
            "delta_view": delta_view, "state_nodes": w.state_nodes,
            "solver": self.solver,
        }

    @staticmethod
    def _delta_exhausted(disp, fetched) -> bool:
        """Out of slots/window: the repair could not place everything it was
        given room for — the tick escalates to a full solve."""
        w_slots = (
            len(disp["window"][0]) if disp["window"] is not None
            else disp["outputs"].assign.shape[1]
        )
        from karpenter_core_tpu.solver.tpu import TPUSolver

        return TPUSolver.fetch_exhausted(fetched, w_slots)

    def _delta_results(self, disp):
        """Host materialize: decode over the delta view (the fetch ticket's
        staged arrays — no device re-touch), dropping node decisions the
        repair placed nothing on (previously-decided nodes must not be
        re-launched)."""
        results = disp["solver"].decode(
            disp["delta_view"], disp["outputs"], disp["state_nodes"],
            fetched=disp["ticket"],
        )
        results.new_nodes = [d for d in results.new_nodes if d.pods]
        return results

    @tracing.traced("session.adopt")
    def _delta_adopt(self, disp, fetched) -> None:
        """Bookkeeping: fold the repair's placements into the lineage.  Runs
        only after the device work succeeded (the ticket's barrier)."""
        w = self._warm
        plan = disp["plan"]
        window = disp["window"]
        outputs = disp["outputs"]
        c_pad = w.prep.cls.count.shape[0]
        n_slots = w.assign.shape[1]
        from karpenter_core_tpu.solver.tpu import TPUSolver

        assign_d = np.asarray(fetched[TPUSolver.FETCH_ASSIGN], dtype=np.int32)
        assign_ex_d = np.asarray(
            fetched[TPUSolver.FETCH_ASSIGN_EX], dtype=np.int32
        )
        n_next_h = int(fetched[TPUSolver.FETCH_N_NEXT])
        loc_d, unplaced = _locate_pods(disp["delta_view"], assign_d, assign_ex_d)
        if window is not None:
            # scatter the windowed repair back to the full-width lineage:
            # assignment columns, pod locations, and the device carry.  The
            # donating twin writes the window into the full carry's device
            # memory in place (the full carry is dead after this call).
            idx, n_open_w = window
            scatter = (
                solve_ops.scatter_repair_window_donated if disp["donated"]
                else solve_ops.scatter_repair_window
            )
            new_carry = scatter(
                disp["keep_carry"], solve_ops.warm_carry_of(outputs), idx,
                np.int32(n_open_w),
            )
            assign_g = np.zeros((c_pad, n_slots), dtype=np.int32)
            assign_g[:, idx] = assign_d
            assign_d = assign_g
            loc_d = {
                uid: (row, kind, int(idx[i]) if kind == "new" else i)
                for uid, (row, kind, i) in loc_d.items()
            }
            n_next_h = w.n_next + (n_next_h - n_open_w)
        else:
            new_carry = solve_ops.warm_carry_of(outputs)
        for uid, loc in plan["evicted_locs"]:
            row, kind, slot = loc
            (w.assign if kind == "new" else w.assign_ex)[row, slot] -= 1
            del w.pod_loc[uid]
        w.assign += assign_d
        w.assign_ex += assign_ex_d
        w.pod_loc.update(loc_d)
        # every non-evicted failure was retried this tick, so the repair's
        # unplaced tail IS the new failure set
        delta_pods = {
            p.uid: p for pods in plan["pods_by_root"].values() for p in pods
        }
        w.failed_pods = {
            uid: (row, delta_pods[uid]) for uid, row in unplaced
        }
        w.carry = new_carry
        w.n_next = n_next_h
        w.members = plan["members_after"]
        w.delta_ticks += 1
        tracing.set_attrs(placed=len(loc_d), evicted=len(plan["evicted_locs"]),
                          failed=len(w.failed_pods), windowed=window is not None)

    def _delta_solve(self, delta, by_uid, state_nodes):
        """The serial delta tick, stage order exactly as before the
        pipelined loop: dispatch → barrier → exhaustion check → decode →
        adopt.  None escalates to a full solve."""
        plan = self._delta_plan(delta, by_uid)
        if plan is None:
            return None
        disp = self._delta_dispatch(plan)
        try:
            fetched = disp["ticket"].wait()
        except BaseException:
            # ANY failed barrier — the device going quiet (SolveTimeout) or
            # throwing — cancels the tick cleanly: ticket retired from the
            # open ledger, donation ledger balanced, lineage dropped so
            # nothing is ever half-applied.  The error surfaces to the
            # caller's breaker; the next solve re-anchors from scratch.
            self._cancel_tick(disp)
            raise
        try:
            if self._delta_exhausted(disp, fetched):
                return None
            results = self._delta_results(disp)
            self._delta_adopt(disp, fetched)
        except BaseException:
            if disp["donated"]:
                # the carry was donated: a kept lineage would re-read the
                # deleted buffer on every later repair — drop it so the
                # next solve re-anchors (KC_PIPELINE=0 keeps the old
                # keep-the-lineage behavior, nothing was donated there)
                self._warm = None
            raise
        return results

    def _cancel_tick(self, disp) -> None:
        """Invalidate a timed-out tick's in-flight device state: the
        FetchTicket retires from the open ledger (its device refs drop, so
        an abandoned copy cannot pin buffers into the next tick), a donated
        dispatch's ledger entry is balanced, and the warm lineage drops —
        its carry is either donated-dead or aliased by the abandoned fetch,
        and a half-applied lineage must never seed repairs."""
        disp["ticket"].invalidate()
        if disp["donated"]:
            pipeline_mod.record_donation_canceled()
        self._warm = None

    def _delta_dispatch_deferred(self, delta, by_uid, pods_or_classes,
                                 members, state_nodes, bound_pods,
                                 supply_anchor):
        """The pipelined tick: plan + dispatch now, settle at the next
        solve's entry.  Returns the PendingResults handle, or None when the
        plan cannot be expressed (caller escalates inline, exactly like the
        serial path).  The current population's classes are captured so a
        settle-time exhaustion re-anchors from THIS tick's population even
        though the caller's ingest has moved on by then."""
        plan = self._delta_plan(delta, by_uid)
        if plan is None:
            return None
        disp = self._delta_dispatch(plan)
        # capture AFTER dispatch so the snapshot build overlaps device work.
        # PodIngest.classes() is a fresh finalized list (fresh pods lists);
        # a prebuilt class list gets shallow pod-list copies for the same
        # isolation from caller-side churn.
        from karpenter_core_tpu.models.columnar import PodIngest

        try:
            if isinstance(pods_or_classes, PodIngest):
                captured = pods_or_classes.classes()
            else:
                captured = [
                    cls if getattr(cls, "is_ladder_variant", False)
                    else dc_replace(cls, pods=list(cls.pods))
                    for cls in pods_or_classes
                ]
        except BaseException:
            if disp["donated"]:
                self._warm = None  # dispatched with a donated carry
            raise
        box = PendingResults(self)
        self._pending = _PendingTick(
            kind="delta", box=box, data=dict(
                disp=disp, members_after=plan["members_after"],
                captured_classes=captured, members_at=dict(members),
                state_nodes=list(state_nodes or ()),
                bound_pods=list(bound_pods or ()),
                supply_anchor=supply_anchor,
            ),
        )
        return box

    def settle(self) -> None:
        """Retire the in-flight deferred tick: completion barrier, window
        exhaustion check (a delta escalates to a full re-anchor of the
        CAPTURED population — same semantics as the serial escalation; a
        full retries with doubled slots), bookkeeping adoption, and mode
        accounting.  Decode stays deferred to the handle's ``result()`` so
        it overlaps the next tick's device compute; a handle still undecoded
        by the NEXT settle materializes here first (its staging-ring slot is
        about to be rewritten).  Never raises: a settle failure lands in the
        handle and drops the lineage (the next solve re-anchors)."""
        # flush the last settled-but-undecoded handle FIRST — and do it even
        # when nothing is pending: its staged arrays live in the shared ring,
        # and ANY later tick (a serial one included) would rewrite that slot
        # under the handle.  In the canonical loop the consumer already
        # called result(), making this a no-op; failures are cached in the
        # box (PendingResults.result) and re-raised to its consumer.
        if self._undecoded is not None:
            try:
                self._undecoded.result()
            except Exception:  # noqa: BLE001 - recorded in the box
                pass
            self._undecoded = None
        pending = self._pending
        if pending is None:
            return
        self._pending = None
        if pending.kind == "full":
            mode, reason = MODE_FULL, pending.data["reason"]
        else:
            mode, reason = MODE_DELTA, "delta"
        try:
            if pending.kind == "full":
                self._settle_full(pending)
            else:
                disp = pending.data["disp"]
                try:
                    fetched = disp["ticket"].wait()
                except SolveTimeout:
                    # fault-triggered re-anchor: the watchdog abandoned this
                    # tick's barrier, so cancel its in-flight state cleanly
                    # and rebuild the lineage from the DISPATCH-TIME
                    # population capture — the same escalation the deferred
                    # window overflow takes, now driven by a hang instead of
                    # slot pressure.  The re-anchor's own dispatch is still
                    # watchdog-bounded: a persistently quiet device surfaces
                    # as a SolveTimeout in the handle and the caller's
                    # breaker quarantines the backend.
                    self._cancel_tick(disp)
                    mode, reason = MODE_FULL, "watchdog-timeout"
                    results = self._full_solve(
                        pending.data["captured_classes"],
                        pending.data["members_at"],
                        pending.data["state_nodes"],
                        pending.data["bound_pods"],
                        pending.data["supply_anchor"], reason,
                    )
                    pending.box._settle_with(results=results)
                except BaseException:
                    # a non-timeout barrier fault: same clean cancellation
                    # (ticket/donation ledgers must not leak on ANY error),
                    # but no re-anchor — the error routes to the handle.
                    # (A SolveTimeout raised by the RE-ANCHOR above is not
                    # caught here — sibling except clauses don't catch
                    # exceptions raised inside each other.)
                    self._cancel_tick(disp)
                    raise
                else:
                    if self._delta_exhausted(disp, fetched):
                        mode, reason = MODE_FULL, "slots-exhausted"
                        results = self._full_solve(
                            pending.data["captured_classes"],
                            pending.data["members_at"],
                            pending.data["state_nodes"],
                            pending.data["bound_pods"],
                            pending.data["supply_anchor"], reason,
                        )
                        pending.box._settle_with(results=results)
                    else:
                        self._delta_adopt(disp, fetched)
                        pending.box._settle_with(
                            decode=lambda: self._delta_results(disp)
                        )
                        self._undecoded = pending.box
        except BaseException as e:  # noqa: BLE001 - routed to the handle
            if pending.kind == "full" or pending.data["disp"]["donated"]:
                self._warm = None  # serial parity: a failed anchor resets
            # keep the ticket ledger leak-free on EVERY error path — a
            # failed anchor barrier (timed out or thrown) leaves a ticket
            # whose copy was never consumed
            ticket = (
                pending.data.get("ticket") if pending.kind == "full"
                else pending.data["disp"]["ticket"]
            )
            if ticket is not None and not ticket.done():
                ticket.invalidate()
            pending.box._settle_with(error=e)
            SOLVE_MODE.labels(mode).inc()
            self.last_mode, self.last_reason = mode, f"{reason}:failed"
            self.mode_counts[mode] = self.mode_counts.get(mode, 0) + 1
            return
        SOLVE_MODE.labels(mode).inc()
        self.last_mode, self.last_reason = mode, reason
        self.mode_counts[mode] = self.mode_counts.get(mode, 0) + 1

    # -- aggregate views (bench / parity tests) --------------------------------
    # Each settles the in-flight deferred tick first: the view must reflect
    # every dispatched solve (a no-op outside the pipelined loop).

    def node_count(self) -> int:
        self.settle()
        w = self._warm
        if w is None:
            return 0
        return int(np.sum(np.sum(w.assign, axis=0) > 0))

    def aggregates(self) -> Dict[str, int]:
        """The session lineage's current placement totals."""
        self.settle()
        w = self._warm
        if w is None:
            return {"scheduled": 0, "failed": 0, "nodes": 0}
        return {
            "scheduled": int(w.assign.sum() + w.assign_ex.sum()),
            "failed": len(w.failed_pods),
            "nodes": self.node_count(),
        }

    def used_drift(self) -> float:
        """Largest relative gap between the carry's per-slot ``used`` plane
        and an exact (float64) recount of it from the lineage's own
        assignments: zero up to f32 rounding when every repair returned
        exactly what the scan charged.  A matmul that runs below f32
        precision on the device (ops.solve._EXACT_F32) shows here as ~1e-3."""
        import jax

        from karpenter_core_tpu.utils import watchdog

        self.settle()
        w = self._warm
        if w is None:
            return 0.0
        used, tmpl_id, open_, requests, daemon = watchdog.run(
            "solve.sync", jax.device_get,
            (w.carry.state.used, w.carry.state.tmpl_id, w.carry.state.open_,
             w.prep.cls.requests, w.prep.statics_arrays.tmpl_daemon),
            key="used-drift",
        )
        want = np.asarray(daemon, dtype=np.float64)[np.asarray(tmpl_id)] + np.einsum(
            "cn,cr->nr", w.assign.astype(np.float64),
            np.asarray(requests, dtype=np.float64)[: w.assign.shape[0]],
        )
        live = np.asarray(open_, dtype=bool) & (w.assign.sum(axis=0) > 0)
        if not live.any():
            return 0.0
        gap = np.abs(np.asarray(used, dtype=np.float64) - want)[live]
        return float((gap / np.maximum(np.abs(want[live]), 1e-30)).max())

    def node_signature(self):
        """Canonical multiset of per-node class loads, labeled by stable
        class identity — the assignment-identity view churn parity checks
        compare against a from-scratch full solve (order- and
        row-index-independent)."""
        self.settle()
        w = self._warm
        if w is None:
            return ()
        keys = [w.row_key.get(i, i) for i in range(w.assign.shape[0])]
        return node_signature_of(w.assign, keys) + node_signature_of(
            w.assign_ex, keys
        )


class _MemberResolver:
    """uid -> Pod over one request's classes: a listed class answers from
    its pods, a counted class (``CountedPods``) copies the member
    ``<base>#<j>`` asked for and nothing else.  ``resolved`` counts the
    members taken from counted classes."""

    __slots__ = ("by_uid", "counted", "resolved")

    def __init__(self, by_uid, counted) -> None:
        self.by_uid = by_uid  # uid -> Pod of the listed classes
        self.counted = counted  # uid base -> CountedPods
        self.resolved = 0

    def __call__(self, uid):
        pod = self.by_uid.get(uid)
        if pod is not None:
            return pod
        pods = self.counted.get(uid.rpartition("#")[0])
        j = pods.uids.position(uid) if pods is not None else None
        if j is None:
            return None
        self.resolved += 1
        return pods[j]


_WINDOW_MIN = 256
_WINDOW_FRESH = 64


def _window_indices(hole_slots, n_next: int, n_slots: int):
    """The bounded repair window's global slot indices: every freed-hole slot
    (ascending — all open, they held placed pods), open filler below
    ``n_next`` if the power-of-two bucket needs it, then the fresh tail.
    Returns (idx i32[S], open_count) or None when windowing is off
    (KC_DELTA_WINDOW=0), the bucket would not shrink the solve, or the
    geometry doesn't fit — callers then run the repair at full width, which
    is always correct.  S rides a power-of-two ladder (min
    max(KC_DELTA_WINDOW, holes + fresh headroom)) so steady churn reuses ONE
    windowed executable per bucket."""
    env = os.environ.get("KC_DELTA_WINDOW", "")
    if env == "0":
        return None
    try:
        min_s = max(int(env), 1) if env else min(_WINDOW_MIN, n_slots // 4)
    except ValueError:
        min_s = _WINDOW_MIN
    # fresh headroom scales down with tiny fleets so small solves window too
    fresh_headroom = min(_WINDOW_FRESH, max(8, n_slots // 16))
    want = max(min_s, len(hole_slots) + fresh_headroom)
    s = 1
    while s < want:
        s <<= 1
    if s >= n_slots:
        return None
    fresh = list(range(n_next, min(n_next + (s - len(hole_slots)), n_slots)))
    filler_needed = s - len(hole_slots) - len(fresh)
    open_w = list(hole_slots)
    if filler_needed > 0:
        holes = set(hole_slots)
        filler = []
        slot = n_next - 1
        while slot >= 0 and len(filler) < filler_needed:
            if slot not in holes:
                filler.append(slot)
            slot -= 1
        if len(filler) < filler_needed:
            return None
        open_w = sorted(open_w + filler)
    idx = np.asarray(open_w + fresh, dtype=np.int32)
    return idx, len(open_w)


def node_signature_of(assign: np.ndarray, keys=None):
    """Sorted tuple of per-node (class, count) loads, empty slots dropped —
    two solves with identical placements (up to slot naming) produce equal
    signatures.  ``keys`` maps class row -> a stable class identity; without
    it the raw row index labels the load, which only compares correctly
    between solves that share ONE encode's class order (a fully-churned
    class re-enters a fresh encode at a different row)."""
    sig = []
    arr = np.asarray(assign)
    # class keys are nested tuples that may hold unorderable members
    # (frozensets), so canonicalize by repr — identical values repr equal
    for col in range(arr.shape[1]):
        loads = tuple(sorted(
            (
                ((keys[int(c)] if keys is not None else int(c)), int(arr[c, col]))
                for c in np.nonzero(arr[:, col])[0]
            ),
            key=repr,
        ))
        if loads:
            sig.append(loads)
    return tuple(sorted(sig, key=repr))


def _locate_pods(snapshot, assign, assign_ex):
    """uid -> (class row, "new"|"ex", index) plus the unplaced tail as
    (uid, root row) pairs, in the exact cursor order TPUSolver.decode
    consumes pods (ladder rows share their root's cursor)."""
    n_classes = len(snapshot.classes)
    if snapshot.cls_root is not None:
        root_of = [int(r) for r in snapshot.cls_root]
    else:
        root_of = list(range(n_classes))
    cursors = [0] * n_classes
    loc: Dict[str, Tuple[int, str, int]] = {}
    unplaced: List[str] = []
    for c in range(n_classes):
        r = root_of[c]
        pods = snapshot.classes[r].pods
        cursor = cursors[r]
        ex_idx = np.nonzero(assign_ex[c] > 0)[0]
        for e, take in zip(ex_idx.tolist(), assign_ex[c][ex_idx].tolist()):
            for pod in pods[cursor:cursor + take]:
                loc[pod.uid] = (c, "ex", int(e))
            cursor += take
        node_idx = np.nonzero(assign[c] > 0)[0]
        for n, take in zip(node_idx.tolist(), assign[c][node_idx].tolist()):
            for pod in pods[cursor:cursor + take]:
                loc[pod.uid] = (c, "new", int(n))
            cursor += take
        cursors[r] = cursor
    for c in range(n_classes):
        if root_of[c] != c:
            continue
        unplaced.extend((p.uid, c) for p in snapshot.classes[c].pods[cursors[c]:])
    return loc, unplaced


def _as_request_plane(requests):
    """The per-pod request plane for repair_free: a device-resident prep's
    plane passes straight through (already f32 on device — no host round
    trip per tick); a host prep's numpy plane gets the f32 cast the jit
    expects."""
    if isinstance(requests, np.ndarray):
        return np.asarray(requests, dtype=np.float32)
    return requests


def _topology_rows(prep) -> Tuple[np.ndarray, np.ndarray]:
    """(member, own_inv) i32[C_pad, G1] rows for ops.solve.repair_free: which
    group counts each class's placements incremented — membership from the
    padded grp_member plane, inverse ownership from the owned anti slots
    (preferred terms register no inverse counts, matching the record step)."""
    member = np.asarray(prep.statics_arrays.grp_member).astype(np.int32)
    c_pad, g1 = member.shape
    own_inv = np.zeros((c_pad, g1), dtype=np.int32)
    groups = np.asarray(prep.cls.groups)
    anti_soft = np.asarray(prep.cls.anti_soft)
    g_dummy = g1 - 1
    for c in range(c_pad):
        g_zan, g_han = int(groups[c, 4]), int(groups[c, 5])
        if g_zan < g_dummy and not bool(anti_soft[c, 0]):
            own_inv[c, g_zan] += 1
        if g_han < g_dummy and not bool(anti_soft[c, 1]):
            own_inv[c, g_han] += 1
    return member, own_inv


def _delta_view(snapshot, pods_by_root: Dict[int, List[object]]):
    """A shallow snapshot view whose root classes carry only this tick's
    pods (delta additions + retried failures) — what decode's cursor walk
    consumes; every tensor plane is shared with the original."""
    view = copy.copy(snapshot)
    classes = []
    for c, cls in enumerate(snapshot.classes):
        if cls.is_ladder_variant:
            classes.append(cls)
            continue
        classes.append(dc_replace(cls, pods=list(pods_by_root.get(c, ()))))
    view.classes = classes
    return view
