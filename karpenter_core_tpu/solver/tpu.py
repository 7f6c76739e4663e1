"""TPU solver facade: encode → kernel → decode.

Stands behind the same Solve() contract as the host Scheduler
(solver.scheduler) for the batch shapes the kernel models (see
models.snapshot.classify_pods); callers use ``supports()``/KernelUnsupported to
route between the tensor path and the host path.  This is the Solver the
BASELINE.json north star describes: cluster snapshots in, node decisions out,
with the bin-pack running as a batch tensor program on the TPU.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass, field
from typing import Dict, List, NamedTuple, Optional

import jax
import numpy as np

from karpenter_core_tpu import chaos, tracing
from karpenter_core_tpu.apis.objects import Pod
from karpenter_core_tpu.apis.v1alpha5 import Provisioner, order_by_weight
from karpenter_core_tpu.cloudprovider import CloudProvider, InstanceType
from karpenter_core_tpu.metrics.registry import SOLVER_SLOT_RETRIES
from karpenter_core_tpu.models.snapshot import (
    EncodedSnapshot,
    KernelUnsupported,
    encode_snapshot,
)
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.scheduling import Requirement, Requirements
from karpenter_core_tpu.solver import modes as modes_mod
from karpenter_core_tpu.solver.machinetemplate import MachineTemplate
from karpenter_core_tpu.solver.scheduler import _daemon_overhead
from karpenter_core_tpu.utils import resources as resources_util

log = logging.getLogger(__name__)

# solver.dispatch: faults device-backend work at kernel dispatch — here and in
# solver/incremental.py (which imports this Point).  Error kinds surface as
# the backend RuntimeError the provisioning breaker counts (docs/CHAOS.md).
SOLVER_DISPATCH = chaos.point("solver.dispatch")


class _LazyPlanes:
    """Per-solve node planes (viable/zone/used), fetched device→host once on
    first access.  Construction starts async copies so the transfer overlaps
    the host-side pod-assignment decode; the big bool planes ship bit-packed
    (8× fewer bytes over the device link)."""

    __slots__ = ("_viable_p", "_zone_p", "_ct_p", "_used_d", "_n_it",
                 "_n_zones", "_n_ct", "_viable", "_zone", "_ct", "_used")

    def __init__(self, state) -> None:
        from karpenter_core_tpu.utils import pipeline as pipeline_mod

        self._n_it = state.viable.shape[-1]
        self._n_zones = state.zone.shape[-1]
        self._n_ct = state.ct.shape[-1]
        self._viable_p = solve_ops.pack_bool(state.viable)
        self._zone_p = solve_ops.pack_bool(state.zone)
        self._ct_p = solve_ops.pack_bool(state.ct)
        used = state.used
        if pipeline_mod.donation_enabled():
            # the pipelined loop donates the carry these planes alias on the
            # NEXT dispatch; node decisions consume `used` lazily (launch
            # path) possibly after that, so take an owned device copy now.
            # The packed planes above are already fresh arrays.
            import jax.numpy as jnp

            used = jnp.copy(used) if hasattr(used, "is_deleted") else used
        self._used_d = used
        self._viable = self._zone = self._ct = self._used = None

    def prefetch(self) -> None:
        """Start async device→host copies.  Called *after* the solve's eager
        fetch so the big planes don't queue ahead of it on the device link."""
        for arr in (self._viable_p, self._zone_p, self._ct_p, self._used_d):
            try:
                arr.copy_to_host_async()
            except AttributeError:  # non-jax (already host) arrays
                pass

    def _fetch(self) -> None:
        if self._viable is None:
            from karpenter_core_tpu.utils import watchdog

            with tracing.span("materialize"):
                # deadline-bounded: the big-plane copy crosses the same
                # device link the solve fetch does, and can hang the same way
                viable_p, zone_p, ct_p, used = watchdog.run(
                    "pipeline.fetch", jax.device_get,
                    (self._viable_p, self._zone_p, self._ct_p, self._used_d),
                    key="planes",
                )
                self._viable = solve_ops.unpack_bool(viable_p, self._n_it)
                self._zone = solve_ops.unpack_bool(zone_p, self._n_zones)
                self._ct = solve_ops.unpack_bool(ct_p, self._n_ct)
                self._used = used
                # release the device buffers — node decisions can outlive the
                # solve (launch path), and holding both copies doubles memory
                self._viable_p = self._zone_p = self._ct_p = self._used_d = None

    @property
    def viable(self) -> np.ndarray:
        self._fetch()
        return self._viable

    @property
    def zone(self) -> np.ndarray:
        self._fetch()
        return self._zone

    @property
    def ct(self) -> np.ndarray:
        self._fetch()
        return self._ct

    @property
    def used(self) -> np.ndarray:
        self._fetch()
        return self._used


class TPUNodeDecision:
    """One node the kernel decided to create.  Instance-type/zone name lists
    and the request vector materialize lazily — at 50k-pod scale eager
    materialization of ~7k nodes × ~1k type names dominates decode time, and
    the underlying planes only cross the device link when first consumed
    (launch path), off the solve critical path.

    ``selected`` carries the policy objective's argmin offering for this node
    (ops.objective, stamped by TPUSolver decode when the policy stage is
    enabled): the launch then lands on exactly that (instance type, zone,
    capacity type) cell — zone/ct pinned, the selected type ordered first —
    instead of whichever offering the provider's first-compatible walk
    happens to hit.  None (the default) keeps today's behavior exactly."""

    __slots__ = ("provisioner_name", "pods", "selected", "_snapshot",
                 "_planes", "_slot")

    def __init__(self, provisioner_name, snapshot, planes, slot):
        self.provisioner_name = provisioner_name
        self.pods: List[Pod] = []
        self.selected: Optional[dict] = None
        self._snapshot = snapshot
        self._planes = planes
        self._slot = slot

    @property
    def instance_type_names(self) -> List[str]:
        row = self._planes.viable[self._slot]
        names = [self._snapshot.it_names[i] for i in np.nonzero(row)[0]]
        if self.selected is not None:
            chosen = self.selected["instance_type"]
            if chosen in names:
                names = [chosen] + [n for n in names if n != chosen]
        return names

    @property
    def zones(self) -> List[str]:
        if self.selected is not None:
            return [self.selected["zone"]]
        row = self._planes.zone[self._slot]
        return [self._snapshot.zones[z] for z in np.nonzero(row)[0]]

    @property
    def capacity_types(self) -> List[str]:
        if self.selected is not None:
            return [self.selected["capacity_type"]]
        row = self._planes.ct[self._slot]
        return [self._snapshot.capacity_types[c] for c in np.nonzero(row)[0]]

    @property
    def requests(self) -> resources_util.ResourceList:
        row = self._planes.used[self._slot]
        return {
            name: float(row[r])
            for r, name in enumerate(self._snapshot.resources)
            if row[r] > 0
        }


def _scan_axes(cls, statics_arrays) -> dict:
    """The ``prepare`` span's account of the axes the scan will see, beside
    ``n_slots``: the class, group and member-list axes as padded (``m_padded``
    0: no lists kept, ``ops.solve.member_index``), and the largest member
    count of a class."""
    g1 = np.shape(statics_arrays.grp_skew)[0]
    lists = np.asarray(cls.member_idx) < g1 - 1
    members = lists if lists.shape[1] else np.asarray(statics_arrays.grp_member)
    return {
        "c_padded": np.shape(cls.count)[0],
        "g_padded": g1,
        "m_padded": lists.shape[1],
        "members_max": int(members.sum(axis=1).max(initial=0)),
    }


def _attach_pol(snapshot, statics_arrays):
    """The snapshot's policy objective planes (policy.planes.planes_of),
    catalog-padded to the prep's instance-type extent.  The pol planes share
    the snapshot's I axis (attach_planes stamps them at encode time, after any
    mesh alignment), so the pad is a no-op in production — it guards planes
    prepared outside that path (pad value +inf price = never-selected, the
    same sentinel the encode uses for absent offerings)."""
    from karpenter_core_tpu.policy import planes as planes_mod

    pol = planes_mod.planes_of(snapshot)
    if pol is None:
        return None
    n_it = int(np.asarray(statics_arrays.it_alloc).shape[0])
    if int(np.asarray(pol.price).shape[0]) != n_it:
        pol = pol._replace(
            price=solve_ops._pad_axis(
                np.asarray(pol.price, dtype=np.float32), 0, n_it, np.inf
            ),
            risk=solve_ops._pad_axis(
                np.asarray(pol.risk, dtype=np.float32), 0, n_it, 0.0
            ),
            throughput=solve_ops._pad_axis(
                np.asarray(pol.throughput, dtype=np.float32), 0, n_it, 0.0
            ),
        )
    return pol


def _signature_index(table: Dict[tuple, tuple], signature: tuple, row) -> int:
    """The index of ``signature`` in ``table`` (signature -> (index, the first
    row seen with it)), entered on first sight: how ``encode_existing`` groups
    nodes, classes and bound pods whose predicates cannot differ."""
    return table.setdefault(signature, (len(table), row))[0]


class SolvePrep(NamedTuple):
    """One snapshot's kernel inputs, prepared (and bucket-padded) once.

    The seam the incremental session (solver.incremental) needs: a delta
    reconcile reuses a previous reconcile's SolvePrep verbatim — same padded
    tensors, same executable shape — and only swaps the class-count vector,
    so the jit cache stays warm across the whole churn regime."""

    cls: object  # ops.solve.ClassTensors (padded host/device pytree)
    statics_arrays: object  # ops.solve.StaticArrays
    key_has_bounds: tuple
    ex_state: object  # Optional[ops.solve.ExistingState]
    ex_static: object  # Optional[ops.solve.ExistingStatic]
    n_slots: int
    n_passes: int
    features: object  # ops.solve.SnapshotFeatures
    # mesh topology the prep was built for (parallel.mesh.solve_mesh_axes at
    # prepare time; None = unsharded).  Captured HERE so a lineage of repairs
    # keeps dispatching onto the topology its carry is sharded over — the
    # incremental session escalates to a full solve when the live topology
    # moves (solver.incremental "mesh-changed")
    mesh_axes: object = None
    # policy objective planes (policy.planes.ObjectivePlanes) for the relax
    # solver family's linear cost — attached FRESH on every prepare (prices
    # move while the shape anchors stay identical, so the warm-prep fast path
    # must never serve a cached sheet); None when the snapshot predates the
    # policy encode.  The scan variants never read it.
    pol: object = None


@dataclass
class TPUSolveResults:
    new_nodes: List[TPUNodeDecision] = field(default_factory=list)
    # existing-node placements: node name -> pods nominated onto it
    existing_assignments: Dict[str, List[Pod]] = field(default_factory=dict)
    failed_pods: List[Pod] = field(default_factory=list)
    # pods the kernel could not place but flagged spread_suspect: the
    # zone-spread water-fill could not prove host-oracle parity for their
    # class, so the host might still place them — callers must either route
    # them through the host path (ProvisioningController._schedule_tpu does)
    # or treat them as failed; they are never silently dropped (VERDICT r2 #2)
    spread_residual_pods: List[Pod] = field(default_factory=list)
    # zone the kernel committed each assignment-carrying existing node to
    # (singleton post-solve zone masks only) — the host re-route stamps these
    # onto zone-less nodes so both engines see one consistent commitment
    existing_committed_zones: Dict[str, str] = field(default_factory=dict)
    n_slots_used: int = 0
    # policy objective results (ops.objective, set when the policy stage ran):
    # the summed selected-offering price over this solve's open slots, raw and
    # risk-weighted.  None when policy is disabled — the planes never ran.
    fleet_cost: Optional[float] = None
    fleet_expected_cost: Optional[float] = None


@dataclass
class LaunchableNode:
    """Launch-path adapter (duck-typed like solver.node.SchedulingNode):
    template + instance types + requests + pods, consumable by
    ProvisioningController.launch."""

    template: object
    instance_type_options: List[InstanceType]
    requests: dict
    pods: List[Pod] = field(default_factory=list)

    @property
    def provisioner_name(self) -> str:
        return self.template.provisioner_name

    @property
    def requirements(self):
        return self.template.requirements


class TPUSolver:
    def __init__(
        self,
        cloud_provider: CloudProvider,
        provisioners: List[Provisioner],
        daemonset_pods: Optional[List[Pod]] = None,
        kube_client=None,
        policy=None,
    ) -> None:
        # kube_client resolves PVC -> CSI driver for volume attach-limit
        # planes (volumeusage.go:65-90); None matches the host oracle's
        # behavior of treating unresolvable volumes as unconstrained
        self.kube_client = kube_client
        # the policy-objective config (policy.PolicyConfig): None/disabled =
        # feasibility-only decode, exactly the pre-policy pipeline.  The
        # provider handle stays on the solver so the risk planes can read its
        # live capacity-error state at encode time (policy.planes).
        self.policy = policy
        # the last cold solve's solver-family outcome ("scan" | "relax" |
        # "relax-fallback:<reason>") — observability convenience mirroring the
        # solve.mode span / karpenter_solve_mode_total counter
        self.last_solve_mode = "scan"
        self.cloud_provider = cloud_provider
        self.provisioners = order_by_weight(
            [p for p in provisioners if p.metadata.deletion_timestamp is None]
        )
        self.templates = [MachineTemplate.from_provisioner(p) for p in self.provisioners]
        self.instance_types: Dict[str, List[InstanceType]] = {
            p.name: cloud_provider.get_instance_types(p) for p in self.provisioners
        }
        overhead = _daemon_overhead(self.templates, daemonset_pods or [])
        for template in self.templates:
            template.requests = overhead[id(template)]
        self._it_by_name = {
            it.name: it for its in self.instance_types.values() for it in its
        }

    def encode(
        self,
        pods,
        state_nodes: Optional[list] = None,
        bound_pods: Optional[List[Pod]] = None,
    ) -> EncodedSnapshot:
        """Raises models.snapshot.KernelUnsupported when the batch needs the
        host path.  Existing-node label values widen the vocabulary so NotIn
        checks against them stay exact; bound pods' anti-affinity terms
        register as groups so their inverse blocking reaches the kernel.

        ``pods`` is a pod list or a models.columnar.PodIngest; with an ingest
        the per-pod classification cost was already paid at watch-event time
        and encode runs in O(distinct classes)."""
        from karpenter_core_tpu.models.columnar import PodIngest

        classes = None
        if isinstance(pods, PodIngest):
            classes = pods.classes()
            # class representatives cover every distinct label set, which is
            # all the anti-affinity relevance check below needs
            pods = [cls.pods[0] for cls in classes]
        return self._encode_with_classes(pods, classes, state_nodes, bound_pods)

    def encode_classes(
        self,
        classes: list,
        state_nodes: Optional[list] = None,
        bound_pods: Optional[List[Pod]] = None,
    ) -> EncodedSnapshot:
        """Encode from prebuilt PodClass objects (the class-columnar wire path:
        the channel ships one representative pod + count per distinct shape).
        Orders/validates the classes in place (models.snapshot.finalize_classes)."""
        from karpenter_core_tpu.models.snapshot import finalize_classes

        classes = finalize_classes(list(classes))
        reps = [cls.pods[0] for cls in classes]
        return self._encode_with_classes(reps, classes, state_nodes, bound_pods)

    def _encode_with_classes(
        self,
        pods: List[Pod],
        classes: Optional[list],
        state_nodes: Optional[list],
        bound_pods: Optional[List[Pod]],
    ) -> EncodedSnapshot:
        with tracing.span(
            "encode",
            classes=len(classes) if classes is not None else None,
            state_nodes=len(state_nodes or ()),
        ) as sp:
            snapshot = self._encode_with_classes_impl(
                pods, classes, state_nodes, bound_pods
            )
            # delta-consuming encode provenance: True when the class planes
            # were shared by reference from the previous same-shape encode
            sp.set(**{"encode.reused": snapshot.encode_reused})
            return snapshot

    def _encode_with_classes_impl(
        self,
        pods: List[Pod],
        classes: Optional[list],
        state_nodes: Optional[list],
        bound_pods: Optional[List[Pod]],
    ) -> EncodedSnapshot:
        from karpenter_core_tpu.models.snapshot import (
            GRP_ANTI,
            UNLIMITED,
            KernelUnsupported,
            _group_spec,
        )

        extra = [
            Requirements.from_labels(n.node.metadata.labels) for n in (state_nodes or [])
        ]
        from karpenter_core_tpu.models.snapshot import term_namespaces

        extra_anti = []
        for pod in bound_pods or []:
            affinity = pod.spec.affinity
            if affinity is None or affinity.pod_anti_affinity is None:
                continue
            for term in affinity.pod_anti_affinity.required:
                try:
                    spec = _group_spec(
                        GRP_ANTI, term.topology_key, term.label_selector, UNLIMITED,
                        term_namespaces(pod, term),
                    )
                except KernelUnsupported:
                    # an unrepresentable anti key/scope only matters if it can
                    # gate a scheduling pod: selector match within the term's
                    # static scope (or any pod when the scope is dynamic)
                    if term.namespace_selector is not None:
                        scoped = list(pods)
                    else:
                        scope_ns = term_namespaces(pod, term)
                        scoped = [p for p in pods if (p.namespace or "") in scope_ns]
                    if term.label_selector is not None and any(
                        term.label_selector.matches(p.metadata.labels) for p in scoped
                    ):
                        raise
                    continue
                extra_anti.append((spec, term.label_selector))
        from karpenter_core_tpu.models.snapshot import pod_port_keys

        extra_ports = [key for pod in bound_pods or [] for key in pod_port_keys(pod)]
        # shard-aligned catalog extent: when the sharded solve path is on
        # (parallel.mesh, KC_SOLVER_MESH), the encode pads the instance-type
        # axis to the mesh's catalog-axis multiple so the shard_map dispatch
        # splits it evenly — one consistent padded extent everywhere
        from karpenter_core_tpu.parallel import mesh as mesh_mod

        snapshot = encode_snapshot(
            pods, self.provisioners, self.templates, self.instance_types,
            extra_requirement_sets=extra,
            extra_anti_groups=extra_anti,
            cache_host=self,
            extra_host_ports=extra_ports,
            classes=classes,
            catalog_pad_multiple=mesh_mod.catalog_pad_multiple(),
        )
        snapshot.class_volumes = self._resolve_class_volumes(
            snapshot.classes, state_nodes
        )
        # objective planes ride every encode (price sheet / risk priors /
        # throughput weights) so the ``policy`` digest group versions the
        # economics even while the objective stage itself is disabled
        from karpenter_core_tpu.policy import planes as policy_planes

        policy_planes.attach_planes(
            snapshot, self._it_by_name, config=self.policy,
            provider=self.cloud_provider,
        )
        return snapshot

    def _resolve_class_volumes(self, classes, state_nodes) -> list:
        """Per-class volume profile for the kernel's attach-limit planes
        (volumeusage.go:65-90 resolution).  Each entry:

          {"shared": {driver: {pvc ids}}, "per_pod": {driver: count}}

        Only drivers with a finite limit on some state node can ever bind
        (new nodes have no CSINode), so claims on unlimited drivers are
        dropped up front — sharing through them is harmless.  For the rest a
        class must be either SHARED (every member mounts the same claim set —
        the per-node contribution is count-independent) or PERPOD (members
        mount pairwise-disjoint sets with equal per-driver counts, nothing
        overlapping other classes or already-mounted sets — the contribution
        is count-dependent).  Anything else routes to the host path, as do
        unresolvable references (the host path surfaces the per-pod error)."""
        from karpenter_core_tpu.scheduling import VolumeUsage

        empty = [{"shared": {}, "per_pod": {}} for _ in classes]
        if self.kube_client is None:
            return empty
        limited = {
            driver
            for state_node in state_nodes or []
            for driver in state_node.volume_limits()
        }
        has_claims = any(
            v.persistent_volume_claim is not None
            for cls in classes
            for v in cls.pods[0].spec.volumes
        )
        if not limited or not has_claims:
            return empty

        mounted_ids = {
            pvc_id
            for state_node in state_nodes or []
            for driver, ids in state_node.volume_usage().volumes.items()
            if driver in limited
            for pvc_id in ids
        }
        usage = VolumeUsage(self.kube_client)
        resolve_cache: Dict[tuple, dict] = {}  # claim names -> limited-driver sets

        def resolve(pod) -> dict:
            key = (
                pod.namespace or "",
                tuple(
                    sorted(
                        v.persistent_volume_claim.claim_name
                        for v in pod.spec.volumes
                        if v.persistent_volume_claim is not None
                    )
                ),
            )
            hit = resolve_cache.get(key)
            if hit is None:
                volumes, err = usage._validate(pod)
                if err is not None:
                    raise KernelUnsupported(f"volume resolution: {err}")
                hit = {d: ids for d, ids in volumes.items() if d in limited}
                resolve_cache[key] = hit
            return hit

        class_volumes = []
        seen: Dict[str, int] = {}  # pvc id -> class index
        for c, cls in enumerate(classes):
            if cls.is_ladder_variant:
                # ladder variants schedule the ROOT's pods, so they carry the
                # root's volume profile — resolving their lone representative
                # would misread the shared claims as cross-class sharing
                class_volumes.append(None)
                continue
            member_sets = [resolve(pod) for pod in cls.pods]
            first = member_sets[0]
            for ids in first.values():
                for pvc_id in ids:
                    if seen.setdefault(pvc_id, c) != c:
                        raise KernelUnsupported(
                            f"pvc {pvc_id} shared across pod classes not kernel-supported"
                        )
            if all(m == first for m in member_sets):
                class_volumes.append({"shared": first, "per_pod": {}})
                continue
            # PERPOD: pairwise-disjoint member sets, uniform count vector,
            # nothing shared with other classes or already mounted
            counts = {d: len(ids) for d, ids in first.items()}
            all_ids: set = set()
            for m in member_sets:
                if {d: len(ids) for d, ids in m.items()} != counts:
                    raise KernelUnsupported(
                        "mixed volume shapes within a pod class not kernel-supported"
                    )
                for ids in m.values():
                    for pvc_id in ids:
                        if pvc_id in all_ids or pvc_id in mounted_ids:
                            raise KernelUnsupported(
                                f"pvc {pvc_id} shared across pods not kernel-supported"
                            )
                        if seen.setdefault(pvc_id, c) != c:
                            raise KernelUnsupported(
                                f"pvc {pvc_id} shared across pod classes not kernel-supported"
                            )
                        all_ids.add(pvc_id)
            class_volumes.append({"shared": {}, "per_pod": counts})
        # backfill variants with their root's profile (chain order: the root
        # always precedes its variants in the finalized class list)
        index_of = {id(cls): c for c, cls in enumerate(classes)}
        for c, cls in enumerate(classes):
            if cls.relax_to is not None:
                class_volumes[index_of[id(cls.relax_to)]] = class_volumes[c]
        return class_volumes

    def encode_existing(
        self,
        snapshot: EncodedSnapshot,
        state_nodes: list,
        bound_pods: Optional[List[Pod]] = None,
        count_scheduling: bool = False,
    ):
        """(ExistingState, ExistingStatic) numpy planes for the kernel; the
        per-group member/owner node counts seed the kernel's topology counts.

        ``count_scheduling``: the consolidation sweep's classes hold every
        candidate's bound pods, displaced in some lanes and not in others — a
        lane drops the pods of the nodes it closes through the open mask, so
        the seeds must count them where they sit (excluded, a pod that stays
        on an open candidate stops holding its hostname, its zone's count and
        its ports, and the lane admits what the host's re-simulation refuses).

        Mirrors ExistingNode construction (existingnode.go:43-75): available
        capacity, remaining daemonset overhead, label requirements, ephemeral-
        taint-filtered toleration checks; and topology countDomains
        (topology.go:231-276) for pre-existing matching pods.
        """
        from karpenter_core_tpu.apis import labels as labels_api
        from karpenter_core_tpu.models.snapshot import (
            GRP_ANTI,
            UNLIMITED,
            _group_spec,
            group_membership,
            pod_port_keys,
            term_namespaces,
        )
        from karpenter_core_tpu.scheduling import Taints

        vocab = snapshot.vocab
        E = max(len(state_nodes), 1)
        C = len(snapshot.classes)
        R = len(snapshot.resources)
        Z = len(snapshot.zones)
        CT = len(snapshot.capacity_types)
        K, W = vocab.n_keys, vocab.width

        G1 = len(snapshot.groups) + 1
        used = np.zeros((E, R), dtype=np.float32)
        alloc = np.zeros((E, R), dtype=np.float32)
        kmask = np.ones((E, K, W), dtype=bool)
        kdef = np.zeros((E, K), dtype=bool)
        kneg = np.zeros((E, K), dtype=bool)
        kgt = np.full((E, K), -np.inf, dtype=np.float32)
        klt = np.full((E, K), np.inf, dtype=np.float32)
        zone = np.zeros((E, Z), dtype=bool)
        ct = np.zeros((E, CT), dtype=bool)
        pod_count = np.zeros(E, dtype=np.int32)
        open_ = np.zeros(E, dtype=bool)
        init = np.zeros(E, dtype=bool)
        P = len(snapshot.ports)
        ports = np.zeros((E, P), dtype=bool)
        grp_node_member = np.zeros((G1, E), dtype=np.int32)
        grp_node_owner = np.zeros((G1, E), dtype=np.int32)
        node_capacity = np.zeros((E, R), dtype=np.float32)
        node_tmpl = np.zeros(E, dtype=np.int32)
        node_owned = np.zeros(E, dtype=bool)
        port_idx = {key: i for i, key in enumerate(snapshot.ports)}
        tmpl_index = {t.provisioner_name: i for i, t in enumerate(self.templates)}

        tmpl_by_name = {t.provisioner_name: t for t in self.templates}
        zone_idx = {z: i for i, z in enumerate(snapshot.zones)}
        ct_idx = {c: i for i, c in enumerate(snapshot.capacity_types)}
        # tol[c, e] and the bound pods' group counts depend only on a
        # signature of each side (a taint set, a toleration set, a pod's
        # namespace + labels), and signatures repeat — node pools, Deployments'
        # replicas: each predicate is evaluated once per distinct pair below
        # and scattered (tables: _signature_index)
        taint_sets: Dict[tuple, tuple] = {}
        node_sig = np.zeros(len(state_nodes), dtype=np.intp)

        for e, state_node in enumerate(state_nodes):
            node = state_node.node
            available = state_node.available()
            for r, name in enumerate(snapshot.resources):
                alloc[e, r] = available.get(name, 0.0)
            template = tmpl_by_name.get(
                node.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY, "")
            )
            if template is not None and template.requests:
                remaining = resources_util.subtract(
                    template.requests, state_node.daemon_set_requests()
                )
                for r, name in enumerate(snapshot.resources):
                    used[e, r] = max(remaining.get(name, 0.0), 0.0)
            reqs = Requirements.from_labels(node.metadata.labels)
            kmask[e], kdef[e], kneg[e], kgt[e], klt[e] = vocab.encode_requirements(reqs)
            z = node.metadata.labels.get(labels_api.LABEL_TOPOLOGY_ZONE)
            if z is None:
                zone[e, :] = True  # unknown zone: any
            elif z in zone_idx:
                zone[e, zone_idx[z]] = True
            c_label = node.metadata.labels.get(labels_api.LABEL_CAPACITY_TYPE)
            if c_label is None:
                ct[e, :] = True
            elif c_label in ct_idx:
                ct[e, ct_idx[c_label]] = True
            open_[e] = True
            init[e] = state_node.initialized()
            capacity = state_node.capacity()
            for r, name in enumerate(snapshot.resources):
                node_capacity[e, r] = capacity.get(name, 0.0)
            t_idx = tmpl_index.get(
                node.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY, "")
            )
            if t_idx is not None:
                node_tmpl[e] = t_idx
                node_owned[e] = True
            taints = state_node.taints()
            node_sig[e] = _signature_index(
                taint_sets, tuple((t.key, t.value, t.effect) for t in taints), taints
            )

        # what Taints.tolerates reads of a pod is its tolerations alone
        toleration_sets: Dict[tuple, tuple] = {}
        cls_sig = np.zeros(C, dtype=np.intp)
        for c, cls in enumerate(snapshot.classes):
            pod = cls.pods[0]
            cls_sig[c] = _signature_index(
                toleration_sets,
                tuple(
                    (t.key, t.operator, t.value, t.effect)
                    for t in pod.spec.tolerations
                ),
                pod,
            )
        tolerated = np.zeros((len(toleration_sets), len(taint_sets)), dtype=bool)
        for j, node_taints in taint_sets.values():
            taints = Taints.of(node_taints)
            for i, pod in toleration_sets.values():
                tolerated[i, j] = taints.tolerates(pod) is None
        tol = np.zeros((C, E), dtype=bool)
        tol[:, : len(state_nodes)] = tolerated[np.ix_(cls_sig, node_sig)]

        # pre-existing pod counts per topology group (countDomains semantics,
        # topology.go:231-276): members (forward) and anti-term owners
        # (inverse); pods being scheduled this solve are excluded
        node_index = {n.node.name: e for e, n in enumerate(state_nodes)}
        group_of = {spec: g for g, spec in enumerate(snapshot.groups)}
        scheduling_uids = (
            set() if count_scheduling
            else {p.uid for cls in snapshot.classes for p in cls.pods}
        )
        # what GroupScope.matches_pod reads of a pod: namespace and labels
        pod_signatures: Dict[tuple, tuple] = {}
        bound_sig: List[int] = []  # per bound pod that counts: its signature,
        bound_node: List[int] = []  # and its node
        for pod in bound_pods or []:
            e = node_index.get(pod.spec.node_name)
            if e is None or pod.uid in scheduling_uids:
                continue
            for key in pod_port_keys(pod):
                i = port_idx.get(key)
                if i is not None:
                    ports[e, i] = True
            bound_sig.append(
                _signature_index(
                    pod_signatures,
                    (pod.namespace or "", frozenset(pod.metadata.labels.items())),
                    pod,
                )
            )
            bound_node.append(e)
            affinity = pod.spec.affinity
            if affinity is not None and affinity.pod_anti_affinity is not None:
                for term in affinity.pod_anti_affinity.required:
                    try:
                        spec = _group_spec(
                            GRP_ANTI, term.topology_key, term.label_selector,
                            UNLIMITED, term_namespaces(pod, term),
                        )
                    except Exception:  # noqa: BLE001 - unsupported keys don't track
                        continue
                    g = group_of.get(spec)
                    if g is not None:
                        grp_node_owner[g, e] += 1
        member, _ = group_membership(
            [pod for _, pod in pod_signatures.values()], snapshot.group_selectors
        )
        sig_of = np.asarray(bound_sig, dtype=np.intp)
        node_of = np.asarray(bound_node, dtype=np.intp)
        for g in np.flatnonzero(member.any(axis=0)):
            grp_node_member[g] = np.bincount(node_of[member[sig_of, g]], minlength=E)
        tracing.set_attrs(
            taint_sets=len(taint_sets),
            toleration_sets=len(toleration_sets),
            pod_signatures=len(pod_signatures),
        )

        # -- volume attach-limit planes (volumeusage.go:33-236 as per-driver
        # counters; existingnode.go:77-130 enforcement).  Only existing nodes
        # carry limits (CSINode); the axis covers drivers mounted by a
        # scheduling class plus drivers already over their limit (which block
        # every add, volume-less pods included — VolumeCount.exceeds).
        class_volumes = snapshot.class_volumes or [
            {"shared": {}, "per_pod": {}} for _ in snapshot.classes
        ]
        drivers = sorted(
            {d for vols in class_volumes for d in vols["shared"]}
            | {d for vols in class_volumes for d in vols["per_pod"]}
        )
        for state_node in state_nodes:
            limits = state_node.volume_limits()
            mounted = state_node.volume_usage().volumes
            for d, lim in limits.items():
                if d not in drivers and len(mounted.get(d, ())) > lim:
                    drivers.append(d)
        D = max(len(drivers), 1)
        vol_used = np.zeros((E, D), dtype=np.int32)
        vol_limit = np.full((E, D), UNLIMITED, dtype=np.int32)
        cls_vol_add = np.zeros((C, E, D), dtype=np.int32)
        cls_vol_per_pod = np.zeros((C, D), dtype=np.int32)
        for i, d in enumerate(drivers):
            for c, vols in enumerate(class_volumes):
                cls_vol_per_pod[c, i] = vols["per_pod"].get(d, 0)
        for e, state_node in enumerate(state_nodes):
            mounted = state_node.volume_usage().volumes
            limits = state_node.volume_limits()
            for i, d in enumerate(drivers):
                have = mounted.get(d, set())
                vol_used[e, i] = len(have)
                if d in limits:
                    vol_limit[e, i] = limits[d]
                for c, vols in enumerate(class_volumes):
                    new = vols["shared"].get(d)
                    if new:
                        cls_vol_add[c, e, i] = len(new - have)

        # planes stay numpy: utils.compilecache bucket-pads them before the
        # device upload (ops/solve.pad_planes), so converting here would cost
        # an extra host→device round trip
        ex_state = solve_ops.ExistingState(
            used=np.asarray(used),
            kmask=np.asarray(kmask),
            kdef=np.asarray(kdef),
            kneg=np.asarray(kneg),
            kgt=np.asarray(kgt),
            klt=np.asarray(klt),
            zone=np.asarray(zone),
            ct=np.asarray(ct),
            ports=np.asarray(ports),
            vol_used=np.asarray(vol_used),
            pod_count=np.asarray(pod_count),
            open_=np.asarray(open_),
        )
        ex_static = solve_ops.ExistingStatic(
            alloc=np.asarray(alloc),
            init=np.asarray(init),
            tol=np.asarray(tol),
            grp_node_member=np.asarray(grp_node_member),
            grp_node_owner=np.asarray(grp_node_owner),
            node_capacity=np.asarray(node_capacity),
            node_tmpl=np.asarray(node_tmpl),
            node_owned=np.asarray(node_owned),
            vol_limit=np.asarray(vol_limit),
            cls_vol_add=np.asarray(cls_vol_add),
            cls_vol_per_pod=np.asarray(cls_vol_per_pod),
        )
        return ex_state, ex_static

    def solve(
        self,
        pods,
        state_nodes: Optional[list] = None,
        bound_pods: Optional[List[Pod]] = None,
        n_slots: int = 0,
    ) -> TPUSolveResults:
        with tracing.span("tpu.solve"):
            snapshot = self.encode(pods, state_nodes, bound_pods)
            return self.solve_encoded(snapshot, state_nodes, bound_pods, n_slots)

    def warmup(
        self,
        n_pods: int = 4096,
        state_nodes: Optional[list] = None,
        bound_pods: Optional[List[Pod]] = None,
    ) -> bool:
        """Speculatively build the solve executable for the standard shape
        buckets before the first real batch needs it (the compile hides under
        the batcher's 10 s max window, settings.go:39-40 parity).

        The synthetic mix covers the common class shapes — several request
        sizes, a zonal spread, a hostname spread — against the REAL catalog
        and templates, so the padded buckets (ops/solve.pad_planes) this
        compiles are the ones steady-state batches land in.  Runs end to end
        (encode → compile → tiny device solve).  Purely an optimization: a
        failure is logged at warning with its exception and returns False —
        the first real solve then meets the same error on the solve path.
        """
        from karpenter_core_tpu.apis import labels as labels_api
        from karpenter_core_tpu.apis.objects import (
            Affinity,
            Container,
            LabelSelector,
            ObjectMeta,
            PodAffinity,
            PodAffinityTerm,
            PodSpec,
            ResourceRequirements,
            TopologySpreadConstraint,
        )

        def pod(requests, labels=None, spread_key=None, affinity_key=None):
            spec = PodSpec(
                containers=[Container(resources=ResourceRequirements(requests=dict(requests)))]
            )
            if spread_key is not None:
                spec.topology_spread_constraints = [
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key=spread_key,
                        label_selector=LabelSelector(match_labels=dict(labels)),
                    )
                ]
            if affinity_key is not None:
                spec.affinity = Affinity(
                    pod_affinity=PodAffinity(
                        required=[
                            PodAffinityTerm(
                                topology_key=affinity_key,
                                label_selector=LabelSelector(match_labels=dict(labels)),
                            )
                        ]
                    )
                )
            return Pod(
                metadata=ObjectMeta(name="warmup", labels=dict(labels or {})),
                spec=spec,
            )

        # the mix spans the common SnapshotFeatures tier (zone/host spread +
        # zone self-affinity), so the feature-keyed executable this compiles
        # is the one steady-state batches request (or a superset
        # compilecache.snap_features widens them to)
        protos = [
            pod({"cpu": 0.5, "memory": 512 * 2**20}),
            pod({"cpu": 1.0, "memory": 2 * 2**30}),
            pod({"cpu": 0.25, "memory": 256 * 2**20}, {"app": "warm-zspread"},
                labels_api.LABEL_TOPOLOGY_ZONE),
            pod({"cpu": 0.25, "memory": 256 * 2**20}, {"app": "warm-hspread"},
                labels_api.LABEL_HOSTNAME),
            pod({"cpu": 0.25, "memory": 256 * 2**20}, {"app": "warm-zaff"},
                affinity_key=labels_api.LABEL_TOPOLOGY_ZONE),
        ]
        per = max(n_pods // len(protos), 1)
        pods: List[Pod] = []
        for proto in protos:
            pods.extend([proto] * per)  # shared objects: shapes, not identity
        try:
            self.solve(pods, state_nodes, bound_pods)
            return True
        except Exception:  # noqa: BLE001 - warmup runs off the solve path
            import logging

            logging.getLogger(__name__).warning(
                "kernel warmup failed", exc_info=True
            )
            return False

    # snapshot fields whose identity anchors the warm-prep reuse: everything
    # prepare_host reads EXCEPT cls_count (the per-tick delta).  The
    # delta-native encode shares these by reference across same-shape ticks,
    # so a repeat prepare ships only the fresh count vector.
    _PREP_ANCHOR_FIELDS = (
        "cls_mask", "cls_defined", "cls_negative", "cls_gt", "cls_lt",
        "cls_zone", "cls_ct", "cls_it", "cls_requests", "cls_tol", "cls_ports",
        "cls_groups", "cls_relax_next", "cls_anti_soft", "cls_root",
        "it_mask", "it_defined", "it_negative", "it_gt", "it_lt",
        "it_alloc", "it_avail", "it_capacity",
        "tmpl_mask", "tmpl_defined", "tmpl_negative", "tmpl_gt", "tmpl_lt",
        "tmpl_zone", "tmpl_ct", "tmpl_it", "tmpl_daemon", "tmpl_limits",
        "valid", "is_custom", "vocab_ints",
        "grp_skew", "grp_is_zone", "grp_is_anti", "grp_member",
    )

    # the ``prepare`` stage on the served path (``ops.solve.solve`` opens the
    # same span on the library path): slot estimate, host planes, padding
    @tracing.traced("prepare")
    def prepare_encoded(
        self,
        snapshot: EncodedSnapshot,
        state_nodes: Optional[list] = None,
        bound_pods: Optional[List[Pod]] = None,
        n_slots: int = 0,
    ) -> SolvePrep:
        """Kernel inputs for one encoded snapshot, existing-node planes
        included, bucket-padded (unless KC_TPU_SHAPE_BUCKETS=0) and ready for
        ``run_prepared``.  Splitting prepare from run is what lets the
        incremental session hold a prep across reconciles and re-run it with
        a delta count vector + warm carry (docs/INCREMENTAL.md).

        The delta-native fast path (docs/KERNEL_PERF.md "Layer 6"): when the
        snapshot's shape planes are IDENTICAL (by reference — the delta
        encode's contract) to the last prepared ones and no existing-node
        planes are needed, the previous prep is reused with only a fresh
        padded count vector — the compact delta is all that moves."""
        from karpenter_core_tpu.parallel import mesh as mesh_mod
        from karpenter_core_tpu.utils import compilecache

        ex_state = ex_static = None
        pad = os.environ.get("KC_TPU_SHAPE_BUCKETS", "1") != "0"
        if state_nodes:
            with tracing.span(
                "encode.existing", state_nodes=len(state_nodes),
                bound_pods=len(bound_pods or ()), classes=len(snapshot.classes),
                # the [E] axis as the kernel will see it (ops.solve.pad_planes)
                e_padded=solve_ops.bucket(len(state_nodes), floor=8)
                if pad else len(state_nodes),
            ):
                ex_state, ex_static = self.encode_existing(
                    snapshot, state_nodes, bound_pods
                )
        if n_slots <= 0:
            n_slots = solve_ops.estimate_slots(snapshot)  # snap_slots applied inside
        tracing.set_attrs(classes=len(snapshot.classes), n_slots=n_slots)
        features = solve_ops.features_with_existing(snapshot, ex_static)
        anchors = None
        if ex_state is None and pad:
            anchors = tuple(
                getattr(snapshot, f, None) for f in self._PREP_ANCHOR_FIELDS
            )
            cached = getattr(self, "_prep_cache", None)
            if cached is not None and all(
                a is b for a, b in zip(cached["anchors"], anchors)
            ):
                prev: SolvePrep = cached["prep"]
                c_pad = np.asarray(prev.cls.count).shape[0]
                count = solve_ops._pad_axis(
                    np.asarray(snapshot.cls_count, dtype=np.int32), 0, c_pad, 0
                )
                tracing.set_attrs(**_scan_axes(prev.cls, prev.statics_arrays))
                return SolvePrep(
                    cls=prev.cls._replace(count=count),
                    statics_arrays=prev.statics_arrays,
                    key_has_bounds=prev.key_has_bounds,
                    ex_state=None, ex_static=None,
                    n_slots=n_slots, n_passes=snapshot.scan_passes,
                    features=features,
                    mesh_axes=compilecache.resolve_mesh_axes(
                        mesh_mod.solve_mesh_axes(),
                        solve_ops.StaticArrays(*prev.statics_arrays),
                    ),
                    pol=_attach_pol(
                        snapshot, solve_ops.StaticArrays(*prev.statics_arrays)
                    ),
                )
        cls, statics_arrays, key_has_bounds = solve_ops.prepare_host(snapshot)
        if pad:
            cls, statics_arrays, key_has_bounds, ex_state, ex_static = (
                solve_ops.pad_planes(
                    cls, statics_arrays, key_has_bounds, ex_state, ex_static
                )
            )
        tracing.set_attrs(**_scan_axes(cls, statics_arrays))
        prep = SolvePrep(
            cls=cls, statics_arrays=statics_arrays, key_has_bounds=key_has_bounds,
            ex_state=ex_state, ex_static=ex_static, n_slots=n_slots,
            n_passes=snapshot.scan_passes, features=features,
            mesh_axes=compilecache.resolve_mesh_axes(
                mesh_mod.solve_mesh_axes(), solve_ops.StaticArrays(*statics_arrays)
            ),
            pol=_attach_pol(
                snapshot, solve_ops.StaticArrays(*statics_arrays)
            ),
        )
        if anchors is not None:
            self._prep_cache = {"anchors": anchors, "prep": prep}
        return prep

    def run_prepared(
        self,
        prep: SolvePrep,
        count=None,
        warm_carry=None,
        repair_plan=None,
        n_slots: int = 0,
        donate_carry=None,
    ) -> solve_ops.SolveOutputs:
        """Run the kernel on a SolvePrep.  ``count`` overrides the class-count
        vector (the repair solve passes only the delta pods; shape must match
        the padded class axis); ``warm_carry`` resumes from a previous solve's
        final carry (ops.solve.WarmCarry); ``repair_plan`` carries the freed-
        hole planes the repair's fills refill first plus the out-of-window
        topology bases of a bounded repair (ops.solve.RepairPlan).
        Returns raw SolveOutputs — device-resident futures (dispatch is
        asynchronous); decode is the caller's step, and ``begin_fetch``
        splits its device→host copy from the completion barrier so a
        pipelined caller overlaps the next dispatch with this one's fetch.

        Warm dispatches DONATE the carry's device buffers when the pipeline
        is armed (utils.pipeline, KC_PIPELINE=0 disarms): the caller must
        not read ``warm_carry`` after this call (the ``donated-read``
        kcanalyze rule).  An enabled policy objective keeps donation off —
        its decode stage re-reads the final state planes on device after
        the dispatch (ops.objective.select_for_state), and those planes
        alias the donated memory one tick later.  ``donate_carry`` overrides
        the auto decision (the incremental session passes False for
        dispatches routed through the service coalescer, whose batched
        executable stacks member carries and cannot donate them); an enabled
        policy still forces donation off."""
        from karpenter_core_tpu.utils import compilecache

        cls = prep.cls
        if count is not None:
            cls = cls._replace(count=np.asarray(count, dtype=np.int32))
        # -- solver-mode dispatch (solver/modes.py, docs/RELAX.md) ------------
        # Cold solves only: a warm-carry repair resumes SCAN state and a
        # repair_plan means this call IS the relax family's own cleanup pass
        # (relax.solve.run_relax re-enters run_prepared with both set, which
        # is also what makes this hook non-recursive).
        if warm_carry is None and repair_plan is None:
            mode = modes_mod.resolve_mode(self.policy)
            if mode != modes_mod.MODE_SCAN:
                n_pods = int(np.asarray(cls.count, dtype=np.int64).sum())
                if modes_mod.relax_selected(mode, n_pods):
                    from karpenter_core_tpu.relax import solve as relax_solve
                    from karpenter_core_tpu.solver.incremental import SOLVE_MODE

                    with tracing.span("solve.mode", mode=mode,
                                      pods=n_pods) as sp:
                        try:
                            out = relax_solve.run_relax(
                                self, prep, cls=cls, n_slots=n_slots
                            )
                        except relax_solve.RelaxFallback as fb:
                            # the scan below runs as if relax never existed;
                            # only the structured reason is left behind
                            sp.set(selected="relax-fallback", reason=fb.reason)
                            SOLVE_MODE.labels("relax-fallback").inc()
                            self.last_solve_mode = f"relax-fallback:{fb.reason}"
                        else:
                            sp.set(selected="relax")
                            SOLVE_MODE.labels("relax").inc()
                            self.last_solve_mode = "relax"
                            return out
                else:
                    self.last_solve_mode = "scan"
            else:
                self.last_solve_mode = "scan"
        ex_static = prep.ex_static
        if warm_carry is not None and ex_static is None:
            # the warm variant always takes the ex-static planes (its tol/vol
            # rows are per-class); synthesize the empty ones the full solve
            # built internally so the repair sees identical semantics
            # (shape reads only — the prep's planes may be device-resident)
            n_res = prep.cls.requests.shape[-1]
            n_classes = cls.count.shape[0]
            g1 = prep.statics_arrays.grp_skew.shape[0]
            ex_static = solve_ops.empty_existing_static(n_res, n_classes, g1)
        donate = "auto" if donate_carry is None else bool(donate_carry)
        if self.policy is not None and getattr(self.policy, "enabled", False):
            donate = False
        from karpenter_core_tpu.utils import pipeline as pipeline_mod
        from karpenter_core_tpu.utils import watchdog

        # deadline-bounded dispatch (utils/watchdog.py): keyed on the same
        # identity the compile cache keys its executable on — static config,
        # mesh topology AND the planes' shape signature — so a program that
        # has yet to compile always gets the cold budget, warm latencies of
        # different programs budget separately, and a hung device surfaces
        # as a structured SolveTimeout, not a wedged worker.  (The shape
        # signature is not optional: on the TPU one compile outlasts the
        # warm floor, and two catalogs can share every static below.)
        ex_state = None if warm_carry is not None else prep.ex_state
        return watchdog.run(
            "solve.dispatch",
            compilecache.run_solve,
            cls, prep.statics_arrays, n_slots or prep.n_slots, prep.key_has_bounds,
            ex_state,
            ex_static,
            key=(
                compilecache.leaf_sig(
                    (cls, prep.statics_arrays, ex_state, ex_static)
                ),
                int(n_slots or prep.n_slots), int(prep.n_passes),
                # SNAPPED features, matching the executable run_solve will
                # actually pick: raw variants that widen to one covering
                # executable must share one deadline budget
                tuple(compilecache.snap_features(prep.features))
                if prep.features is not None else None,
                getattr(prep, "mesh_axes", None),
                warm_carry is not None,
                # executable-variant axes that recompile without moving the
                # shape identity: a flip (KC_PIPELINE, policy toggling
                # donation) must budget as a fresh cold key, not spike a
                # warm EWMA into a spurious timeout
                donate, pipeline_mod.donation_enabled(),
            ),
            n_passes=prep.n_passes,
            features=prep.features,
            warm_carry=warm_carry,
            repair_plan=repair_plan,
            pre_padded=True,
            # the prep's captured topology, NOT "auto": a warm carry's plane
            # layout must keep matching the executable it resumes into even
            # if the live mesh config moves mid-lineage
            mesh_axes=getattr(prep, "mesh_axes", None),
            donate_carry=donate,
        )

    # ``begin_fetch``'s small-plane tuple layout.  The settle/exhaustion
    # checks here and in solver.incremental consume the fetched tuple by
    # these indices — extend the tuple ONLY by appending, and keep this
    # block in lockstep with the tuple construction below.
    FETCH_ASSIGN = 0
    FETCH_ASSIGN_EX = 1
    FETCH_FAILED = 2
    FETCH_SUSPECT = 3
    FETCH_EX_ZONE = 4
    FETCH_POD_COUNT = 5
    FETCH_TMPL_ID = 6
    FETCH_OPEN = 7
    FETCH_N_NEXT = 8

    @classmethod
    def fetch_exhausted(cls, fetched, slots) -> bool:
        """Slot-exhaustion verdict over a fetched begin_fetch tuple: pods
        failed AND the scan consumed every slot it was given.  The ONE
        definition every escalation path shares — solve_encoded's retry,
        the deferred anchor's settle, and the deferred repair's
        window-overflow check (solver.incremental)."""
        return (
            int(np.sum(fetched[cls.FETCH_FAILED])) > 0
            and int(fetched[cls.FETCH_N_NEXT]) >= int(slots)
        )

    def upload_prep(self, prep: SolvePrep) -> SolvePrep:
        """Upload a SolvePrep's padded planes to the device ONCE (with the
        prep's captured mesh shardings) and return the device-resident prep.
        The incremental session adopts this after every full solve: steady
        churn repairs then re-dispatch over the SAME device buffers tick
        after tick — ``device_put`` is a no-op for device-resident leaves,
        so only the fresh per-tick count vector ever crosses the host→device
        boundary again (docs/KERNEL_PERF.md "Layer 7"; the host→device twin
        of the warm carry's donation)."""
        from karpenter_core_tpu.parallel import mesh as mesh_mod

        trees = (prep.cls, prep.statics_arrays, prep.ex_state, prep.ex_static)
        mesh_axes = getattr(prep, "mesh_axes", None)
        if mesh_axes is None:
            up = jax.device_put(trees)
        else:
            up = jax.device_put(
                trees,
                mesh_mod.mesh_shardings(trees, mesh_mod.mesh_for(mesh_axes)),
            )
        return prep._replace(
            cls=up[0], statics_arrays=up[1], ex_state=up[2], ex_static=up[3]
        )

    def begin_fetch(self, outputs: solve_ops.SolveOutputs, ring=None):
        """Split decode's fetch from its dispatch: start non-blocking
        device→host copies of every array decode consumes (the small planes
        first, the big lazy planes behind them) and return the
        utils.pipeline.FetchTicket whose ``wait()`` is the completion
        barrier.  ``decode(..., fetched=ticket)`` then materializes without
        re-touching the device — the seam that lets solve[k+1]'s dispatch
        overlap decode[k]'s copy and host expansion (docs/KERNEL_PERF.md
        "Layer 7").  ``ring`` stages the fetched arrays into reusable host
        buffers (the pipelined session's double-buffer)."""
        from karpenter_core_tpu.utils import pipeline as pipeline_mod

        state = outputs.state
        small = (
            outputs.assign,
            outputs.assign_existing,
            outputs.failed,
            outputs.spread_suspect,
            outputs.ex_state.zone,
            state.pod_count,
            state.tmpl_id,
            state.open_,
            state.n_next,
        )
        ticket = pipeline_mod.FetchTicket(small, ring=ring, label="decode")
        planes = _LazyPlanes(state)
        planes.prefetch()  # big planes ride the link behind the small fetch
        ticket.planes = planes
        return ticket

    def solve_encoded(
        self,
        snapshot: EncodedSnapshot,
        state_nodes: Optional[list] = None,
        bound_pods: Optional[List[Pod]] = None,
        n_slots: int = 0,
    ) -> TPUSolveResults:
        fault = SOLVER_DISPATCH.hit(
            kinds=("error", "timeout"), op="solve", classes=len(snapshot.classes)
        )
        if fault is not None and fault.kind in ("error", "timeout"):
            # surface exactly like a dead backend: a RuntimeError from the
            # first device op, which the provisioning breaker counts
            raise RuntimeError(fault.describe())

        prep = self.prepare_encoded(snapshot, state_nodes, bound_pods, n_slots)
        # ONE ticket serves both the exhaustion check and decode (one
        # device→host round trip instead of fetching n_next/failed twice)
        outputs, ticket = self.grow_until_fits(prep, self.run_prepared(prep))
        return self.decode(snapshot, outputs, state_nodes or [], fetched=ticket)

    def grow_until_fits(self, prep: SolvePrep, outputs, ticket=None, run=None,
                        ring=None, adopt=None):
        """The slot-exhaustion loop every cold solve shares (here, the
        session's anchor and its deferred settle): while the scan failed pods
        with every slot taken (``fetch_exhausted``), solve again at twice the
        slots.  ``estimate_slots`` is optimistic by design, so a wrong
        estimate costs a second solve, never a failed pod; the loop ends at
        the latest once the slots reach the pod count — a node a pod is all
        any batch can open.  Returns ``(outputs, ticket)`` with the ticket's
        barrier passed.  ``ticket`` is the fetch already begun on ``outputs``
        (one is begun here otherwise), ``run`` the dispatch (default
        ``run_prepared``), ``ring`` the staging ring of the retries' tickets,
        and ``adopt(outputs, ticket)`` is called with each retry's pair BEFORE
        its barrier, so a caller that invalidates its ticket on error never
        loses the live one behind a consumed original."""
        run = run or self.run_prepared
        if ticket is None:
            ticket = self.begin_fetch(outputs, ring=ring)
        while True:
            slots = outputs.assign.shape[1]
            # the pod count is summed only once a solve ran out
            if not self.fetch_exhausted(ticket.wait(), slots) or slots >= int(
                np.sum(np.asarray(prep.cls.count))
            ):
                return outputs, ticket
            SOLVER_SLOT_RETRIES.inc()
            log.info("solve ran out of node slots at %d: again at %d",
                     slots, slots * 2)
            outputs = run(prep, n_slots=slots * 2)
            ticket = self.begin_fetch(outputs, ring=ring)
            if adopt is not None:
                adopt(outputs, ticket)

    def decode(
        self,
        snapshot: EncodedSnapshot,
        outputs: solve_ops.SolveOutputs,
        state_nodes: Optional[list] = None,
        fetched=None,
    ) -> TPUSolveResults:
        with tracing.span("decode") as sp:
            results = self._decode_impl(snapshot, outputs, state_nodes, fetched)
            self._apply_policy_selection(snapshot, outputs, results)
            sp.set(
                new_nodes=len(results.new_nodes),
                pods_on_existing=sum(
                    len(placed) for placed in results.existing_assignments.values()
                ),
                failed=len(results.failed_pods),
                residual=len(results.spread_residual_pods),
                # beside ``n_slots`` on ``prepare``: occupancy = used / allocated
                slots_used=results.n_slots_used,
                n_slots=int(outputs.assign.shape[1]),
            )
            return results

    def _apply_policy_selection(self, snapshot, outputs, results) -> None:
        """The policy-objective stage folded into decode: one batched argmin
        over every open slot's feasible (instance type, zone, capacity type)
        cells (ops.objective), stamped onto the node decisions so the launch
        lands on the selected offering.  A no-op (zero device work) unless
        the solver's PolicyConfig enables the objective."""
        config = self.policy
        if config is None or not getattr(config, "enabled", False):
            return
        from karpenter_core_tpu.policy import planes as policy_planes

        planes = policy_planes.planes_of(snapshot)
        if planes is None:
            return
        from karpenter_core_tpu.ops import objective as objective_ops

        with tracing.span("decode.objective", nodes=len(results.new_nodes)):
            selection = objective_ops.select_for_state(
                outputs.state, planes, config, snapshot.capacity_types
            )
        for decision in results.new_nodes:
            n = decision._slot
            if not bool(selection.active[n]):
                continue
            decision.selected = {
                "instance_type": snapshot.it_names[int(selection.sel_it[n])],
                "zone": snapshot.zones[int(selection.sel_zone[n])],
                "capacity_type": snapshot.capacity_types[int(selection.sel_ct[n])],
                "price": float(selection.price[n]),
                "expected": float(selection.expected[n]),
            }
        results.fleet_cost = float(selection.fleet_cost)
        results.fleet_expected_cost = float(selection.fleet_expected)
        from karpenter_core_tpu.metrics.registry import POLICY_FLEET_COST

        POLICY_FLEET_COST.labels("price").set(results.fleet_cost)
        POLICY_FLEET_COST.labels("expected").set(results.fleet_expected_cost)

    def _decode_impl(
        self,
        snapshot: EncodedSnapshot,
        outputs: solve_ops.SolveOutputs,
        state_nodes: Optional[list] = None,
        fetched=None,
    ) -> TPUSolveResults:
        # NOTE: solver.incremental._locate_pods mirrors this walk's pod
        # consumption order (root-shared cursors, existing before new, index
        # order within each) to label pod -> slot for the repair path; a
        # change to the order here must be mirrored there (the tier-1 parity
        # fuzz in tests/test_incremental.py catches drift loudly).
        #
        # Every device→host copy was started at begin_fetch time (at the
        # dispatch site when the caller pipelines; here otherwise) so the
        # transfers overlap whatever host work ran since; everything eager
        # lands in ONE batched device_get — every separate fetch is its own
        # device→host round trip, and the n_next scalar as a bare int()
        # would cost one all by itself.  Big planes stay
        # lazy until consumed (launch path).
        ticket = fetched if fetched is not None else self.begin_fetch(outputs)
        planes = ticket.planes
        # the fetch is its own child span so the decode stage splits into
        # device→host transfer vs host expansion — the boundary the decode
        # pipelining work needs independently visible (docs/KERNEL_PERF.md).
        # ``prefetched`` marks a completion barrier that already ran at the
        # pipelined settle (exposed wait ≈ 0 here); without an upstream sync
        # (ops/solve.sync_outputs) a cold barrier also absorbs any
        # still-running device compute.
        with tracing.span("decode.fetch", prefetched=ticket.done(), staged=ticket.staged):
            (assign, assign_ex, failed, suspect, ex_zone, pod_count, tmpl_id,
             open_, n_next) = ticket.wait()

        results = TPUSolveResults(n_slots_used=int(n_next))
        nodes: Dict[int, TPUNodeDecision] = {}
        provisioner_names = [t.provisioner_name for t in self.templates]
        for n in np.nonzero(open_ & (pod_count > 0))[0]:
            n = int(n)
            nodes[n] = TPUNodeDecision(
                provisioner_names[int(tmpl_id[n])], snapshot, planes, n
            )

        state_nodes = state_nodes or []
        # preference-ladder variants schedule pods from their ROOT's list: all
        # rows of one ladder share a cursor into the root's (identical) pods
        n_classes = len(snapshot.classes)
        if snapshot.cls_root is not None:
            root_of = [int(r) for r in snapshot.cls_root]
        else:
            root_of = list(range(n_classes))
        cursors = [0] * n_classes  # keyed by root index
        assigned_ex_idx: set = set()
        # the [C, E] plane read once, under a span of its own: with a live
        # cluster it is the one piece of decode that grows with the nodes
        # (its bytes came down with the batched fetch above)
        placed_ex: Dict[int, tuple] = {}
        if state_nodes:
            with tracing.span("decode.existing",
                              existing_bytes=int(assign_ex.nbytes)) as sp:
                on_ex = assign_ex[:n_classes] > 0
                for c in np.nonzero(on_ex.any(axis=1))[0].tolist():
                    ex_idx = np.nonzero(on_ex[c])[0]
                    placed_ex[c] = (ex_idx.tolist(), assign_ex[c][ex_idx].tolist())
                sp.set(pods_on_existing=sum(sum(t) for _, t in placed_ex.values()))
        for c, cls in enumerate(snapshot.classes):
            r = root_of[c]
            pods, cursor = snapshot.classes[r].pods, cursors[r]
            # existing-node placements first (they were tried first in-kernel)
            for e, take in zip(*placed_ex.get(c, ((), ()))):
                if e < len(state_nodes):
                    name = state_nodes[e].node.name
                    results.existing_assignments.setdefault(name, []).extend(
                        pods[cursor : cursor + take]
                    )
                    assigned_ex_idx.add(e)
                cursor += take
            node_idx = np.nonzero(assign[c] > 0)[0]
            counts = assign[c][node_idx]
            for n, take in zip(node_idx.tolist(), counts.tolist()):
                nodes[n].pods.extend(pods[cursor : cursor + take])
                cursor += take
            cursors[r] = cursor
        # leftovers: spread_suspect classes (any ladder row) hand their pods to
        # the host re-route instead of failing them outright — the kernel could
        # not prove the water-fill matched the host oracle for those shapes.
        # (Required zonal anti never reaches the kernel: the iterative host
        # retroactively narrows anti nodes' zones as other pods co-locate,
        # which the forward scan cannot replay — classify routes it,
        # models/snapshot.py.)
        suspect_root = [False] * n_classes
        if suspect is not None:
            for c in range(n_classes):
                if bool(suspect[c]):
                    suspect_root[root_of[c]] = True
        for c, cls in enumerate(snapshot.classes):
            if root_of[c] != c:
                continue
            leftover = cls.pods[cursors[c] :]
            if not leftover:
                continue
            scope = cls.selectors.get(cls.zone_spread) if cls.zone_spread else None
            is_member = scope is not None and scope.matches_pod(cls.pods[0])
            if suspect_root[c] and is_member:
                results.spread_residual_pods.extend(leftover)
            else:
                results.failed_pods.extend(leftover)
                if tracing.enabled():
                    # the kernel reports failure per class, not per predicate:
                    # identical pods fail identically, so one audit entry
                    # covers the class (decode cannot see which gate zeroed
                    # the capacity — the host oracle's audit can)
                    tracing.record_unschedulable(
                        leftover[0],
                        engine="kernel",
                        count=len(leftover),
                        error="no viable placement for pod class (kernel solve)",
                    )
        # kernel zone commitments on existing nodes (singleton post-solve
        # masks): the host re-route stamps these onto zone-less nodes
        ex_zone_h = np.asarray(ex_zone, dtype=bool)
        for e in sorted(assigned_ex_idx):
            mask = ex_zone_h[e]
            if int(mask.sum()) == 1:
                z = int(np.argmax(mask))
                if z < len(snapshot.zones):
                    results.existing_committed_zones[state_nodes[e].node.name] = (
                        snapshot.zones[z]
                    )
        results.new_nodes = [nodes[n] for n in sorted(nodes)]
        return results

    def to_launchable(self, decision: TPUNodeDecision) -> LaunchableNode:
        """Convert a kernel node decision into a launch-path object: the
        provisioner's template with zone/capacity-type pinned to the decision's
        surviving domains and the viable instance-type list attached."""
        return self._build_launchable(
            decision.provisioner_name, decision.zones,
            decision.instance_type_names, decision.requests, decision.pods,
            # the pods' merged capacity-type requirement must ride the launch
            # exactly like zones (node.go:62-117 merge): without it the
            # provider's cheapest-offering pick can land an on-demand-required
            # pod on spot (found by testing/validator.py over fuzz seeds)
            capacity_types=decision.capacity_types,
        )

    def launchable_from_wire(self, entry: dict, pods: List[Pod]) -> LaunchableNode:
        """to_launchable for a remote solve: the snapshot channel's newNodes
        entry ({provisioner, instanceTypes, zones, capacityTypes?, requests})
        instead of an in-process decision.  No encode ran locally, so instance
        types resolve against this solver's catalog by name (wire order
        preserved — it is the decision's viability order from the serving
        side)."""
        return self._build_launchable(
            entry["provisioner"], list(entry.get("zones") or ()),
            list(entry.get("instanceTypes") or ()),
            {k: float(v) for k, v in (entry.get("requests") or {}).items()},
            pods,
            capacity_types=list(entry.get("capacityTypes") or ()),
        )

    def _build_launchable(self, provisioner_name, zones, instance_type_names,
                          requests, pods, capacity_types=()) -> LaunchableNode:
        from dataclasses import replace as dc_replace

        from karpenter_core_tpu.apis.objects import OP_IN

        template = next(
            t for t in self.templates if t.provisioner_name == provisioner_name
        )
        requirements = Requirements(*template.requirements.values())
        if zones:
            requirements.add(
                Requirement(labels_api.LABEL_TOPOLOGY_ZONE, OP_IN, list(zones))
            )
        if capacity_types:
            # consolidation's price rules may have pinned spot-only
            requirements.add(
                Requirement(labels_api.LABEL_CAPACITY_TYPE, OP_IN, list(capacity_types))
            )
        options = [
            self._it_by_name[name]
            for name in instance_type_names
            if name in self._it_by_name
        ]
        return LaunchableNode(
            template=dc_replace(template, requirements=requirements),
            instance_type_options=options,
            requests=dict(requests),
            pods=list(pods),
        )


__all__ = ["TPUSolver", "TPUSolveResults", "TPUNodeDecision", "KernelUnsupported"]
