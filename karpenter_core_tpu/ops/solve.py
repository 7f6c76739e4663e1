"""The TPU bin-packing solve kernel.

Re-centers the reference's greedy first-fit-decreasing loop
(/root/reference/pkg/controllers/provisioning/scheduling/scheduler.go:96-219,
node.go:62-159) as a batch tensor program:

  - pods are pre-grouped into equivalence classes (models.snapshot) and the
    kernel scans over *classes* — identical pods commit identically, so the
    sequential dependency that matters is between distinct shapes, not pods
  - each scan step is dense vectorized work over [N] node slots × [I] instance
    types: requirement-mask compatibility rides the MXU as [N,V]x[V,I] matmuls
    per key, capacity checks are [N,I] elementwise min-reductions, offering
    checks flatten zone×capacity-type and matmul too
  - zonal topology spread becomes a closed-form water-fill over per-zone
    counts (the per-pod argmin of topologygroup.go:155-182 telescopes into
    fill-the-lowest-level), then per-zone placement phases
  - hostname spread / anti-affinity become per-node caps on pods-per-class
  - node selection order (existing first, then emptiest new node,
    scheduler.go:174-190) becomes an argsort + prefix-sum fill

Static shapes: N node slots, I instance types, C classes, Z zones, CT capacity
types, K general keys, V+1 mask width, R resources.  Everything under jit; no
data-dependent Python control flow.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_core_tpu.models.snapshot import EncodedSnapshot, UNLIMITED
from karpenter_core_tpu.ops import masks as mask_ops

# plain numpy scalar: a jnp literal here would initialize the device backend
# at import time (and hang any process whose preferred backend is unreachable
# before it can pin itself to CPU — __graft_entry__._ensure_live_backend)
BIG = np.float32(1e30)


class SnapshotFeatures(NamedTuple):
    """Static phase-plan flags: which constraint families the snapshot can
    exercise at all.  Computed host-side in models.snapshot.encode_snapshot
    from the CLASSES (plus bound-pod anti groups and, at solve time, the
    existing-node volume planes) and threaded through solve_core as a static
    jit argument — a False flag means the corresponding phase family is
    provably dead for every class in the snapshot, so the kernel never traces
    it: no compile time, no per-step lax.cond, no dead carry writes.

    Soundness is one-directional: a flag may be True with the feature absent
    from the data (the phases are then runtime no-ops, exactly the pre-flag
    behavior), but must never be False when some class needs the family.
    utils.compilecache.snap_features exploits that monotonicity to widen a
    requested set to an already-built superset executable instead of
    recompiling (and to bound the variant space).
    """

    zone_spread: bool = True  # some class owns a zonal topology-spread slot
    host_spread: bool = True  # ... a hostname spread slot
    zone_affinity: bool = True  # ... a zonal pod-affinity slot
    host_affinity: bool = True  # ... a hostname pod-affinity slot
    zone_anti: bool = True  # ... a zonal anti-affinity slot (soft or required)
    required_zone_anti: bool = True  # ... REQUIRED zonal anti (committal phases)
    host_anti: bool = True  # ... a hostname anti-affinity slot
    # inverse planes: anti GROUPS whose owners can register inverse counts —
    # required class-owned terms or bound-pod terms (extra_anti_groups)
    inv_zone_anti: bool = True
    inv_host_anti: bool = True
    host_ports: bool = True  # some class binds host ports
    volume_limits: bool = True  # existing nodes carry finite CSI attach limits
    # some class owns a zonal spread whose selector its own pods do not match
    zone_spread_nonself: bool = True

    def canonical(self) -> "SnapshotFeatures":
        """Normalize implications so equivalent requests share a cache key:
        required zonal anti implies the zonal-anti family and inverse plane."""
        f = self
        if f.required_zone_anti:
            f = f._replace(zone_anti=True, inv_zone_anti=True)
        return f

    def covers(self, other: "SnapshotFeatures") -> bool:
        """True when an executable traced with ``self`` is sound for a
        snapshot requesting ``other`` (self is a flag superset)."""
        return all(a or not b for a, b in zip(self, other))

    def union(self, other: "SnapshotFeatures") -> "SnapshotFeatures":
        return SnapshotFeatures(*(a or b for a, b in zip(self, other)))


ALL_FEATURES = SnapshotFeatures()


class NodeState(NamedTuple):
    """Per-new-node-slot solver state (all leading dim N)."""

    used: jnp.ndarray  # f32[N, R] accumulated requests incl. daemon overhead
    kmask: jnp.ndarray  # bool[N, K, V+1], or uint32[N, K, W] packed words
    kdef: jnp.ndarray  # bool[N, K]
    kneg: jnp.ndarray  # bool[N, K]
    kgt: jnp.ndarray  # f32[N, K]
    klt: jnp.ndarray  # f32[N, K]
    zone: jnp.ndarray  # bool[N, Z]
    ct: jnp.ndarray  # bool[N, CT]
    viable: jnp.ndarray  # bool[N, I]
    ports: jnp.ndarray  # bool[N, P] bound (port, proto) pairs
    pod_count: jnp.ndarray  # i32[N]
    tmpl_id: jnp.ndarray  # i32[N]
    open_: jnp.ndarray  # bool[N]
    n_next: jnp.ndarray  # i32[] next free slot


class ExistingState(NamedTuple):
    """Per-existing-node solver state (leading dim E).

    Existing (in-flight/real) nodes have fixed capacity and no instance-type
    viability plane — that keeps consolidation sweeps over thousands of nodes
    memory-light (ExistingNode.Add semantics, existingnode.go:77-130).
    """

    used: jnp.ndarray  # f32[E, R] accumulated (starts at remaining daemon overhead)
    kmask: jnp.ndarray  # bool[E, K, V+1]
    kdef: jnp.ndarray  # bool[E, K]
    kneg: jnp.ndarray  # bool[E, K]
    kgt: jnp.ndarray  # f32[E, K]
    klt: jnp.ndarray  # f32[E, K]
    zone: jnp.ndarray  # bool[E, Z]
    ct: jnp.ndarray  # bool[E, CT]
    ports: jnp.ndarray  # bool[E, P] bound (port, proto) pairs
    vol_used: jnp.ndarray  # i32[E, D] distinct PVCs mounted per CSI driver
    pod_count: jnp.ndarray  # i32[E] pods added THIS solve
    open_: jnp.ndarray  # bool[E]


class ExistingStatic(NamedTuple):
    """Trace-time constants for existing nodes."""

    alloc: jnp.ndarray  # f32[E, R] available() at snapshot time
    init: jnp.ndarray  # bool[E] karpenter.sh/initialized
    tol: jnp.ndarray  # bool[C, E] class tolerates node taints
    # bound pods per topology group per node: members (forward counts) and
    # anti-term owners (inverse counts) — count seeds derive from these with
    # the node open-mask applied, so consolidation subsets adjust for free
    grp_node_member: jnp.ndarray  # i32[G1, E]
    grp_node_owner: jnp.ndarray  # i32[G1, E]
    # provisioner-limit accounting (scheduler.go:244-246): open owned nodes
    # consume their template's budget; closed (consolidated) nodes release it
    node_capacity: jnp.ndarray  # f32[E, R]
    node_tmpl: jnp.ndarray  # i32[E] owning template (0 ok when not owned)
    node_owned: jnp.ndarray  # bool[E]
    # volume attach limits (volumeusage.go / existingnode.go:77-130): only
    # existing nodes carry limits — new nodes have no CSINode yet.  Within a
    # class all pods mount the same PVC set, so the per-node increment is
    # count-independent; cross-class PVC sharing routes to the host path
    vol_limit: jnp.ndarray  # i32[E, D] per-driver attach limit (UNLIMITED none)
    cls_vol_add: jnp.ndarray  # i32[C, E, D] distinct new PVCs class c adds to e
    cls_vol_per_pod: jnp.ndarray  # i32[C, D] per-pod claims (disjoint sets mode)


class TopoCounts(NamedTuple):
    """Shared topology-group counts, carried through the class scan.

    Forward counts track selector-matching (member) pods — they gate spread
    skew, affinity targets, and anti-affinity owners.  Inverse counts track
    anti-term *owners* — they gate the pods those owners repel
    (topology.go:44-47 inverse topologies).

    All four planes count pods PER NODE; per-zone counts are DERIVED at each
    class step from the nodes' *current* zone masks (``_class_step``).
    This is the kernel analog of the host recounting domains from live node
    state every push: when a later pod narrows a node's zone set (node.go
    merge), every earlier resident's zone contribution narrows with it —
    in particular a multi-zone anti owner stops poisoning the zones it can no
    longer be in, which is what lets required zonal anti-affinity converge
    inside one batch exactly like the iterative host (r4 fuzzer finding (a);
    accumulating per-zone snapshots at record time could never replay that
    narrowing).

    A class step touches ROWS of these planes, not the planes: it reads the
    rows of the groups the class owns (``cls.groups``) and is a member of
    (``cls.member_idx``) and adds its placements into the member rows and its
    two anti rows, in place in the scan carry.  At G1 = 4 097 the whole-plane
    record was the step's largest op, at its memory roofline; by row the step
    no longer follows G1 (docs/KERNEL_PERF.md).  Where the planes are small
    beside the member lists, or no lists are kept (``member_index``), the
    step takes them whole (``step_goes_by_row``) — same values either way.

    The LAST row is the dummy group every absent slot and every padded list
    entry names.  It is read like any other (a class without a hostname spread
    reads it as its spread row; only the dummy's ``UNLIMITED`` skew makes that
    harmless) and it rides ``SolveOutputs.topo`` and every warm carry, so no
    step may add into it: it stays zero."""

    fwd_ex: jnp.ndarray  # i32[G1, E] member pods per existing node
    inv_ex: jnp.ndarray  # i32[G1, E] anti-owner pods per existing node
    fwd_new: jnp.ndarray  # i32[G1, N] member pods per new slot
    inv_new: jnp.ndarray  # i32[G1, N] anti-owner pods per new slot


class SolveOutputs(NamedTuple):
    assign: jnp.ndarray  # i32[C, N] pods of class c on NEW node n
    assign_existing: jnp.ndarray  # i32[C, E] pods of class c on existing node e
    failed: jnp.ndarray  # i32[C]
    state: NodeState
    ex_state: ExistingState
    # bool[C]: the zone-spread water-fill could not prove host parity for this
    # class (round bound hit with headroom left, or quota unrealized in-phase);
    # failed pods of flagged classes re-route to the host oracle (VERDICT r2 #2)
    spread_suspect: jnp.ndarray = None
    # the rest of the final scan carry, returned so a later repair solve can
    # resume from it (WarmCarry): shared topology counts and the remaining
    # provisioner-limit budget.  Stays device-resident until consumed.
    topo: "TopoCounts" = None
    remaining: jnp.ndarray = None  # f32[T, R]


class WarmCarry(NamedTuple):
    """The previous solve's final scan carry, carried as the initial state of
    a warm-start repair solve (docs/INCREMENTAL.md).

    ``state``/``ex_state`` hold every placement the previous solve committed
    (used capacity, merged requirement masks, zone/ct commitments, ports,
    volume counters); ``topo`` the shared topology-group counts; ``remaining``
    the provisioner-limit budget.  A repair solve re-enters ``solve_core``
    with this carry and a class-count vector holding only the DELTA pods —
    every phase then fills leftover capacity exactly as the full solve's later
    classes would, so the constraint semantics are identical by construction.
    Evictions are applied to the carry first (``repair_free``): capacity and
    counts are returned, but merged requirement masks / zone commitments /
    port claims are NOT un-merged — that one-way pessimism is the optimality
    drift the fallback policy's periodic full-solve audit bounds."""

    state: NodeState
    ex_state: ExistingState
    topo: TopoCounts
    remaining: jnp.ndarray  # f32[T, R]


class RepairPlan(NamedTuple):
    """The dirty-region plan of a warm-start repair solve.

    ``pref_new`` / ``pref_ex`` are the per-class freed-hole planes: how many
    pods of class c were evicted from each new-node slot / existing node since
    the carry was taken.  Every placement fill prefers refilling these holes
    (capped at the freed count — ``_fill_with_pref``) before the normal
    emptiest-first / index order, which is what makes steady-state churn
    repairs land on EXACTLY the slots the departures vacated and keeps the
    lineage's assignments identical to a from-scratch solve.  All-zeros is a
    valid no-preference plan (pure additions).

    The ``base_*`` planes ([G1, Z] i32) carry the topology-count
    contributions of new-node slots OUTSIDE a bounded repair window
    (``gather_repair_window``): the zone derivations in ``_class_step`` add
    them as constants so a windowed repair sees the same zone counts a
    full-width solve would.  All-zeros when the repair runs unwindowed."""

    pref_new: jnp.ndarray  # i32[C, N]
    pref_ex: jnp.ndarray  # i32[C, E]
    base_fwd_sing: jnp.ndarray  # i32[G1, Z] committed-zone forward counts
    base_fwd_full: jnp.ndarray  # i32[G1, Z] pessimistic (anti) forward counts
    base_inv_full: jnp.ndarray  # i32[G1, Z] inverse-ownership counts


# Matmuls whose operands are f32 RESOURCE QUANTITIES (cpu 0.1, memory in odd
# bytes) must say so: at default precision a TPU rounds f32 matmul operands
# to bf16 (~3 significant digits), and capacity accounting done that way
# drifts from what the scan charged elementwise — measured on a v5e: 2e-3
# relative per product, 0.14 cpu / 250 MB per node after three churn ticks,
# lineage no longer identical to a from-scratch solve.  (The 0/1 mask einsums
# cast to bf16 on purpose and are exact; integer einsums are exact.)
_EXACT_F32 = jax.lax.Precision.HIGHEST


def _imax(x: jnp.ndarray, statics: "Statics") -> jnp.ndarray:
    """Finish a reduction over the catalog (instance-type) axis.

    The local ``jnp.max`` already ran; when the solve executes inside a
    ``shard_map`` with the catalog sharded (parallel.mesh dispatch), every
    device holds only its I-shard's partial maximum and this inserts the
    cross-shard ``lax.pmax``.  Unsharded solves pass ``catalog_axis=None``
    and this is the identity — the single-chip path is literally the same
    code (docs/KERNEL_PERF.md "Layer 5").  max over i32/f32 is exactly
    associative, so the sharded solve stays BIT-IDENTICAL to single-device.
    """
    if statics.catalog_axis is not None:
        x = jax.lax.pmax(x, statics.catalog_axis)
    return x


def _isum(x: jnp.ndarray, statics: "Statics") -> jnp.ndarray:
    """Cross-shard ``lax.psum`` over the catalog axis (see ``_imax``).  Only
    used for integer-valued f32 counts (einsum of 0/1 products), whose
    partial sums are exact in f32 — summation order cannot change the bits.
    """
    if statics.catalog_axis is not None:
        x = jax.lax.psum(x, statics.catalog_axis)
    return x


def _water_fill(
    count0: jnp.ndarray,
    allowed: jnp.ndarray,
    m: jnp.ndarray,
    ex_cum: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """i32[Z] quotas: distribute m pods over allowed zones, always filling the
    lowest-count zone first — the telescoped form of the reference's per-pod
    min-domain selection (topologygroup.go:155-182; maxSkew ≥ 1 guarantees the
    min-count zone is always admissible so skew never blocks the min choice).

    Once the filled zones stand level, the pods left over go one each to the
    zones in the order of their counts, then of their index.  With ``ex_cum``
    they go to the zones the reference would reach first: it tries existing
    nodes in index order before any new node (scheduler.go:176-180), so among
    zones tied at the minimum the one whose first existing node with intake
    left comes earliest takes the next pod.  ``ex_cum`` i32[E, Z] is the
    running sum, in node order, of each existing node's intake for the class
    in its zone: a zone's first node with intake left is where the sum passes
    what the level fill gives the zone.  Zones with no such node keep the
    order above among themselves, after the others.
    """
    z = count0.shape[0]
    c = jnp.where(allowed, count0.astype(jnp.float32), BIG)
    order = jnp.argsort(c)
    s = c[order]
    # cost[k] = pods needed to raise the k lowest zones to level s[k]
    idx = jnp.arange(z, dtype=jnp.float32)
    prefix = jnp.cumsum(s) - s
    cost = idx * s - prefix  # cost to reach level s[k] for first k zones
    cost = jnp.where(jnp.isfinite(cost), cost, BIG)
    mf = m.astype(jnp.float32)
    # k* = number of zones that participate in the fill
    k_star = jnp.sum((cost <= mf).astype(jnp.int32)) - 1
    k_star = jnp.clip(k_star, 0, z - 1)
    base_level = s[k_star]
    spent = cost[k_star]
    rem = mf - spent
    k_count = (k_star + 1).astype(jnp.float32)
    level = base_level + jnp.floor(rem / k_count)
    leftover = rem - jnp.floor(rem / k_count) * k_count
    # zones among the k* lowest get filled to `level`; `leftover` of them get
    # one extra: the first in sorted order, or by node order (docstring)
    if ex_cum is None:
        in_fill = jnp.arange(z) <= k_star
        extra = (jnp.arange(z) < leftover).astype(jnp.float32)
        final_sorted = jnp.where(in_fill, jnp.maximum(s, level + extra), s)
        final = jnp.zeros_like(c).at[order].set(final_sorted)
    else:
        pos = jnp.zeros(z, dtype=jnp.int32).at[order].set(jnp.arange(z, dtype=jnp.int32))
        in_fill = pos <= k_star
        base = jnp.where(in_fill, jnp.maximum(c, level), c)
        given = jnp.where(allowed, base - c, 0.0).astype(jnp.int32)
        spare = ex_cum > given[None, :]  # [E, Z]
        first = jnp.where(
            jnp.any(spare, axis=0), jnp.argmax(spare, axis=0), ex_cum.shape[0]
        ).astype(jnp.int32)
        ahead = in_fill[None, :] & (
            (first[None, :] < first[:, None])
            | ((first[None, :] == first[:, None]) & (pos[None, :] < pos[:, None]))
        )  # [Z, Z]: zone j takes a left-over pod before zone i
        extra = in_fill & (jnp.sum(ahead, axis=1) < leftover)
        final = base + extra.astype(jnp.float32)
    quota = jnp.where(allowed, final - c, 0.0)
    return jnp.maximum(quota, 0.0).astype(jnp.int32)


def _key_compat_node_class(state: NodeState, cls, statics) -> jnp.ndarray:
    """bool[N]: Requirements.Compatible(node, class) vectorized over nodes."""
    node_t = mask_ops.ReqTensor(state.kmask, state.kdef, state.kneg, state.kgt, state.klt)
    cls_t = mask_ops.ReqTensor(
        cls.mask[None], cls.defined[None], cls.negative[None], cls.gt[None], cls.lt[None]
    )
    return mask_ops.compatible(
        node_t, cls_t, statics.is_custom, statics.vocab_ints, v=statics.mask_v
    )


def _merge_node_class(state: NodeState, cls, statics) -> mask_ops.ReqTensor:
    node_t = mask_ops.ReqTensor(state.kmask, state.kdef, state.kneg, state.kgt, state.klt)
    cls_t = mask_ops.ReqTensor(
        cls.mask[None], cls.defined[None], cls.negative[None], cls.gt[None], cls.lt[None]
    )
    return mask_ops.add(
        node_t, cls_t, statics.valid, statics.vocab_ints,
        v=statics.mask_v, key_has_bounds=statics.key_has_bounds,
    )


def _it_intersects(merged: mask_ops.ReqTensor, statics) -> jnp.ndarray:
    """bool[N, I]: InstanceType.Requirements.Intersects(nodeReqs) for every
    (node, instance type) pair (node.go:143-145): a word-wide AND + nonzero
    test per key over the packed masks."""
    it = statics.it  # ReqTensor [I, K, W] packed words
    n_keys = it.defined.shape[-1]
    vocab = jnp.asarray(mask_ops.vocab_words(statics.mask_v))
    a_other_all = mask_ops.other_bit(merged.mask, statics.mask_v)  # [N, K]
    b_other_all = mask_ops.other_bit(it.mask, statics.mask_v)  # [I, K]
    ok_all = None
    for k in range(n_keys):  # K is small and static: unrolled
        a_mask = merged.mask[:, k, :]  # [N, W] words
        b_mask = it.mask[:, k, :]  # [I, W] words
        vocab_overlap = jnp.any(
            (a_mask[:, None, :] & vocab & b_mask[None, :, :]) != 0, axis=-1
        )
        both_other = a_other_all[:, k, None] & b_other_all[None, :, k]
        if statics.key_has_bounds[k]:
            gt = jnp.maximum(merged.gt[:, k, None], it.gt[None, :, k])
            lt = jnp.minimum(merged.lt[:, k, None], it.lt[None, :, k])
            n_range = jnp.maximum(jnp.ceil(lt) - jnp.floor(gt) - 1.0, 0.0)
            ints_k = statics.vocab_ints[k]  # [V]
            inside = (ints_k[None, None, :] > gt[..., None]) & (
                ints_k[None, None, :] < lt[..., None]
            )
            n_in = jnp.sum(inside.astype(jnp.float32), axis=-1)
            unseen = both_other & (n_range - n_in >= 1.0)
        else:
            unseen = both_other
        nonempty = vocab_overlap | unseen
        checked = merged.defined[:, k, None] & it.defined[None, :, k]
        both_neg = merged.negative[:, k, None] & it.negative[None, :, k]
        ok = ~checked | nonempty | both_neg
        ok_all = ok if ok_all is None else (ok_all & ok)
    return ok_all


def _capacity(used: jnp.ndarray, size: jnp.ndarray, statics) -> jnp.ndarray:
    """i32[N, I]: how many more pods of the class fit on node n as instance
    type i — min over resources of floor((alloc - used) / size)
    (resources Fits telescoped over identical pods)."""
    n_res = statics.it_alloc.shape[-1]
    count = None
    for r in range(n_res):  # R static: unrolled
        free = statics.it_alloc[None, :, r] - used[:, r, None]  # [N, I]
        per = jnp.where(
            size[r] > 0, jnp.floor((free + 1e-4) / jnp.maximum(size[r], 1e-9)), BIG
        )
        per = jnp.maximum(per, 0.0)
        count = per if count is None else jnp.minimum(count, per)
    return jnp.minimum(count, BIG).astype(jnp.int32)


def _offering_ok(zone_ok: jnp.ndarray, ct_ok: jnp.ndarray, statics) -> jnp.ndarray:
    """bool[N, I]: some available offering lies in the node's allowed
    zone × capacity-type rectangle (node.go:151-159 hasOffering)."""
    n = zone_ok.shape[0]
    zc = (zone_ok[:, :, None] & ct_ok[:, None, :]).reshape(n, -1)  # [N, Z*CT]
    avail2 = statics.it_avail.reshape(statics.it_avail.shape[0], -1)  # [I, Z*CT]
    return (
        jnp.einsum(
            "nz,iz->ni",
            zc.astype(jnp.bfloat16),
            avail2.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        > 0.5
    )


def _any_lane(pred: jnp.ndarray, statics: "Statics") -> jnp.ndarray:
    """The predicate of a "has anything to place" ``cond``.  Under the
    consolidation sweep's ``vmap`` over prefix lanes (``statics.lane_axis``
    names it) it is true where ANY lane's is: one decision for all lanes keeps
    the ``cond`` a ``cond``, where a predicate that differs by lane would turn
    it into a ``select`` that runs both sides in every lane — every phase kind
    of every class, padding rows included.  A lane whose own predicate is
    false then runs the placing side with nothing to place, which leaves its
    state as the skipping side would.  Everywhere else: ``pred``."""
    if statics.lane_axis is None:
        return pred
    return jax.lax.pmax(pred.astype(jnp.int32), statics.lane_axis) > 0


def _fill_by_priority(
    quota: jnp.ndarray, cap: jnp.ndarray, priority: jnp.ndarray
) -> jnp.ndarray:
    """i32[N]: assign up to quota pods to nodes in priority order (ascending),
    each node taking at most cap[n] — the vectorized form of 'sort nodes by
    pod count, first node that accepts wins' (scheduler.go:183-190)."""
    order = jnp.argsort(priority)
    cap_sorted = cap[order]
    before = jnp.cumsum(cap_sorted) - cap_sorted
    assigned_sorted = jnp.clip(quota - before, 0, cap_sorted)
    return jnp.zeros_like(cap).at[order].set(assigned_sorted)


def _fill_in_order(quota: jnp.ndarray, cap: jnp.ndarray) -> jnp.ndarray:
    """``_fill_by_priority`` where the priority is the index itself among the
    nodes with room (existing nodes: the reference takes the first that
    accepts, scheduler.go:176-180): a node without room has ``cap`` 0, adds
    nothing to the running sum and takes nothing, so no sort is needed — the
    same integers, without the argsort, the gather and the scatter over E (on
    the chip a gather of E elements runs element by element; under the
    consolidation sweep's ``vmap`` it was 64 × E of them a call)."""
    before = jnp.cumsum(cap) - cap
    return jnp.clip(quota - before, 0, cap)


def _fill_existing(quota, cap, pref):
    """``_fill_with_pref`` over existing nodes, whose priority is always the
    index among the nodes with room: this class's freed holes first (warm
    repair, ``pref``), then what is left, both rounds ``_fill_in_order``."""
    with jax.named_scope("kc.fill"):
        if pref is None:
            return _fill_in_order(quota, cap)
        refilled = _fill_in_order(quota, jnp.minimum(cap, pref))
        return refilled + _fill_in_order(quota - jnp.sum(refilled), cap - refilled)


def _fill_with_pref(quota, cap, priority, pref):
    """Warm-repair hole refill (docs/INCREMENTAL.md): slots a departed pod of
    THIS class freed since the carry was taken (``pref[n]`` > 0) absorb the
    quota first — each capped at its freed count, so a slot with slack beyond
    its holes cannot siphon a neighbor's refill — then the normal priority
    order sees the remainder.  With steady-state churn (replacements shaped
    like the departures) the holes absorb the whole quota and the repair's
    final placements are IDENTICAL to a from-scratch solve; without holes
    (``pref`` None or zero) this is exactly ``_fill_by_priority``."""
    with jax.named_scope("kc.fill"):
        if pref is None:
            return _fill_by_priority(quota, cap, priority)
        i32max = jnp.iinfo(jnp.int32).max
        idx = jnp.arange(cap.shape[0], dtype=jnp.int32)
        hole_cap = jnp.minimum(cap, pref)
        a0 = _fill_by_priority(quota, hole_cap, jnp.where(hole_cap > 0, idx, i32max))
        cap_rest = cap - a0
        a1 = _fill_by_priority(
            quota - jnp.sum(a0), cap_rest, jnp.where(cap_rest > 0, priority, i32max)
        )
        return a0 + a1


class Statics(NamedTuple):
    """Trace-time constants bundled for the kernel.  The requirement mask
    planes (``it``, ``tmpl``, ``valid``) are uint32 words (ops/masks.py
    pack_mask)."""

    it: mask_ops.ReqTensor
    it_alloc: jnp.ndarray
    it_avail: jnp.ndarray
    tmpl: mask_ops.ReqTensor
    tmpl_zone: jnp.ndarray
    tmpl_ct: jnp.ndarray
    tmpl_it: jnp.ndarray
    tmpl_daemon: jnp.ndarray
    tmpl_limits0: jnp.ndarray  # f32[T, R] initial remaining (limits - usage)
    it_capacity: jnp.ndarray  # f32[I, R]
    valid: jnp.ndarray
    is_custom: jnp.ndarray
    vocab_ints: jnp.ndarray
    grp_skew: jnp.ndarray  # i32[G1]
    grp_is_zone: jnp.ndarray  # bool[G1]
    grp_is_anti: jnp.ndarray  # bool[G1]
    grp_member: jnp.ndarray  # bool[C, G1]
    key_has_bounds: Tuple[bool, ...]  # python tuple -> static per-key branching
    mask_v: int = 0  # semantic slot count V+1 (the word planes cannot recover it)
    # mesh axis name the catalog (I) planes are sharded over inside a
    # shard_map body (parallel.mesh); None = unsharded, no collectives traced
    catalog_axis: "Optional[str]" = None
    # the consolidation sweep's vmap axis over prefix lanes (``_any_lane``)
    lane_axis: "Optional[str]" = None


class StaticArrays(NamedTuple):
    """The array part of Statics (everything but the static key_has_bounds
    tuple) — the pytree prepare_host returns and pad_planes transforms.  Field
    order MUST match Statics so ``Statics(*static_arrays, key_has_bounds=...)``
    stays valid."""

    it: mask_ops.ReqTensor
    it_alloc: jnp.ndarray
    it_avail: jnp.ndarray
    tmpl: mask_ops.ReqTensor
    tmpl_zone: jnp.ndarray
    tmpl_ct: jnp.ndarray
    tmpl_it: jnp.ndarray
    tmpl_daemon: jnp.ndarray
    tmpl_limits0: jnp.ndarray
    it_capacity: jnp.ndarray
    valid: jnp.ndarray
    is_custom: jnp.ndarray
    vocab_ints: jnp.ndarray
    grp_skew: jnp.ndarray
    grp_is_zone: jnp.ndarray
    grp_is_anti: jnp.ndarray
    grp_member: jnp.ndarray


class ClassTensors(NamedTuple):
    mask: jnp.ndarray
    defined: jnp.ndarray
    negative: jnp.ndarray
    gt: jnp.ndarray
    lt: jnp.ndarray
    zone: jnp.ndarray
    ct: jnp.ndarray
    it: jnp.ndarray
    requests: jnp.ndarray
    count: jnp.ndarray
    tol: jnp.ndarray
    ports: jnp.ndarray  # bool[C, P] host ports each pod of the class binds
    groups: jnp.ndarray  # i32[C, 6]: owned group per kind (G = none):
    # [zone_spread, host_spread, zone_aff, host_aff, zone_anti, host_anti]
    relax_next: jnp.ndarray  # i32[C] preference-ladder successor (-1 none):
    # failed counts roll to the successor class between scan passes
    anti_soft: jnp.ndarray  # bool[C, 2] (zone, host) anti slot came from a
    # preferred term: owner seeks zero-count domains but registers no inverse
    # counts (topology.go:203-206 skips inverse tracking for preferences)
    root: jnp.ndarray  # i32[C] ladder root index (self when not a variant):
    # shared-volume adds are once-per-(LADDER, node), tracked at the root
    member_idx: jnp.ndarray  # i32[C, M] the groups with grp_member[c, g], in
    # index order, then the dummy group (``member_index``): the rows of the
    # topology planes a class step reads and records (TopoCounts); M = 0
    # where some class sits in more groups than a step walks


class ExClassPrep(NamedTuple):
    """Per-(class, existing-node) quantities constant across one class step's
    phases: intake capacity, merged requirement tensors, zone/capacity-type
    masks, and the class's volume rows.  Computing them once per step is safe
    because a step's phases touch disjoint existing-node sets: committed-zone
    phases narrow a taken node's live ex.zone to their zone (so later zone
    phases exclude it — _phase_existing checks the LIVE mask), and the other
    phase families run at most one capacity-consuming phase per step."""

    cap: jnp.ndarray  # i32[E] intake for this class; 0 = ineligible node
    merged: mask_ops.ReqTensor  # node ∩ class requirements, per node
    zone_full: jnp.ndarray  # bool[E, Z] node zone ∩ class zone
    ct_ok: jnp.ndarray  # bool[E, C2] node capacity-type ∩ class
    vol_add: jnp.ndarray  # i32[E, D]
    vol_per_pod: jnp.ndarray  # i32[D]


def _prep_existing(
    ex: ExistingState,
    ex_static: ExistingStatic,
    cls: ClassTensors,
    statics: Statics,
    host_cap_vec: jnp.ndarray,
    tol_row: jnp.ndarray,
    vol_add_row: jnp.ndarray,
    vol_per_pod_row: jnp.ndarray,
    ft: SnapshotFeatures = ALL_FEATURES,
) -> ExClassPrep:
    """How many pods of the class each existing node can still take — min over
    resource fit, CSI attach limits, host-port exclusivity, and hostname-group
    caps; 0 for ineligible nodes (closed, key-incompatible, intolerable
    taints, port conflicts, volume-blocked).  The same intake the reference
    derives per pod in existingnode.go:77-130, hoisted to class granularity."""
    node_t = mask_ops.ReqTensor(ex.kmask, ex.kdef, ex.kneg, ex.kgt, ex.klt)
    cls_t = mask_ops.ReqTensor(
        cls.mask[None], cls.defined[None], cls.negative[None], cls.gt[None], cls.lt[None]
    )
    key_ok = mask_ops.compatible(
        node_t, cls_t, statics.is_custom, statics.vocab_ints, v=statics.mask_v
    )
    merged = mask_ops.add(
        node_t, cls_t, statics.valid, statics.vocab_ints,
        v=statics.mask_v, key_has_bounds=statics.key_has_bounds,
    )
    zone_full = ex.zone & cls.zone[None, :]
    ct_ok = ex.ct & cls.ct[None, :]

    # fixed-capacity fit: min over resources of floor((available - used)/size)
    n_res = ex_static.alloc.shape[-1]
    cap = None
    for r in range(n_res):
        free = ex_static.alloc[:, r] - ex.used[:, r]
        per = jnp.where(
            cls.requests[r] > 0,
            jnp.floor((free + 1e-4) / jnp.maximum(cls.requests[r], 1e-9)),
            BIG,
        )
        per = jnp.maximum(per, 0.0)
        cap = per if cap is None else jnp.minimum(cap, per)
    cap = jnp.minimum(cap, BIG).astype(jnp.int32)

    elig = ex.open_ & key_ok & tol_row & jnp.any(zone_full, axis=-1) & jnp.any(ct_ok, axis=-1)
    if ft.host_ports:
        # host ports: conflict blocks the node; identical pods conflict with
        # each other, so a port-bearing class caps at one pod per node
        # (hostportusage.go:31-56)
        has_ports = jnp.any(cls.ports)
        port_conflict = jnp.any(ex.ports & cls.ports[None, :], axis=-1)
        elig = elig & ~port_conflict
        cap = jnp.minimum(cap, jnp.where(has_ports, 1, UNLIMITED))
    if ft.volume_limits:
        # volume attach limits.  Shared-set classes add a fixed count on first
        # placement (count-independent); per-pod classes add per assigned pod
        # (disjoint claim sets), capping the node's intake like a resource
        vol_free = ex_static.vol_limit - ex.vol_used - vol_add_row  # [E, D]
        vol_ok = jnp.all(vol_free >= vol_per_pod_row[None, :], axis=-1)
        cap_vol = jnp.min(
            jnp.where(
                vol_per_pod_row[None, :] > 0,
                vol_free // jnp.maximum(vol_per_pod_row[None, :], 1),
                UNLIMITED,
            ),
            axis=-1,
        ).astype(jnp.int32)
        cap = jnp.minimum(cap, jnp.maximum(cap_vol, 0))
        elig = elig & vol_ok
    cap = jnp.where(elig, jnp.minimum(cap, host_cap_vec), 0)
    return ExClassPrep(
        cap=cap, merged=merged, zone_full=zone_full, ct_ok=ct_ok,
        vol_add=vol_add_row, vol_per_pod=vol_per_pod_row,
    )


def _phase_existing(
    ex: ExistingState,
    prep: ExClassPrep,
    cls: ClassTensors,
    quota: jnp.ndarray,
    zone_restrict: jnp.ndarray,
    extra_elig: Optional[jnp.ndarray] = None,
    single_node: bool = False,
    ft: SnapshotFeatures = ALL_FEATURES,
    pref: Optional[jnp.ndarray] = None,
) -> Tuple[ExistingState, jnp.ndarray, jnp.ndarray]:
    """Place up to ``quota`` pods of the class onto existing nodes, in index
    order (the reference iterates existing nodes first, in order, and takes the
    first that accepts — scheduler.go:176-180).  ``prep`` carries the step-wide
    intake/merge tensors; ``extra_elig`` restricts to a node subset (affinity
    targets / inverse anti-affinity blocks); ``single_node`` pins the whole
    quota to the first eligible node (hostname self-affinity bootstrap);
    ``pref`` (warm repair only) the class's freed-hole counts per node
    (``_fill_with_pref``)."""
    n_ex = ex.used.shape[0]
    merged = prep.merged
    # zone eligibility reads the LIVE state, not the prep snapshot: an
    # unknown-zone node (all-zones mask) that took pods in an earlier
    # committed-zone phase narrowed its ex.zone there, which is what excludes
    # it here — prep.cap would otherwise be stale for it (double-placement)
    zone_ok = ex.zone & cls.zone[None, :] & zone_restrict[None, :]
    cap = jnp.where(jnp.any(zone_ok, axis=-1), prep.cap, 0)
    if extra_elig is not None:
        cap = jnp.where(extra_elig, cap, 0)
    if single_node:
        first = jnp.argmax(cap > 0)
        cap = jnp.where(jnp.arange(n_ex) == first, cap, 0)

    assigned = _fill_existing(quota, cap, pref)
    placed = jnp.sum(assigned)

    took = assigned > 0
    sel = took[:, None]
    new_ex = ExistingState(
        used=ex.used + assigned[:, None].astype(jnp.float32) * cls.requests[None, :],
        kmask=jnp.where(sel[..., None], merged.mask, ex.kmask),
        kdef=jnp.where(sel, merged.defined, ex.kdef),
        kneg=jnp.where(sel, merged.negative, ex.kneg),
        kgt=jnp.where(sel, merged.gt, ex.kgt),
        klt=jnp.where(sel, merged.lt, ex.klt),
        zone=jnp.where(sel, zone_ok, ex.zone),
        ct=jnp.where(sel, prep.ct_ok, ex.ct),
        ports=jnp.where(sel, ex.ports | cls.ports[None, :], ex.ports)
        if ft.host_ports else ex.ports,
        vol_used=jnp.where(
            sel,
            ex.vol_used + prep.vol_add + assigned[:, None] * prep.vol_per_pod[None, :],
            ex.vol_used,
        )
        if ft.volume_limits else ex.vol_used,
        pod_count=ex.pod_count + assigned,
        open_=ex.open_,
    )
    return new_ex, assigned, placed


def _phase(
    state: NodeState,
    cls: ClassTensors,
    statics: Statics,
    quota: jnp.ndarray,
    zone_restrict: jnp.ndarray,
    host_cap_vec: jnp.ndarray,
    fresh_host_cap: jnp.ndarray,
    remaining: jnp.ndarray,
    extra_elig: Optional[jnp.ndarray] = None,
    max_new_nodes: Optional[int] = None,
    ft: SnapshotFeatures = ALL_FEATURES,
    pref: Optional[jnp.ndarray] = None,
    open_restrict: Optional[jnp.ndarray] = None,
) -> Tuple[NodeState, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Place up to ``quota`` pods of the class on nodes whose zone mask meets
    ``zone_restrict`` — first onto open nodes, then fresh nodes from the first
    viable template.  Returns (state, assigned[N], placed).  ``host_cap_vec``
    is the per-slot class cap from hostname groups, ``fresh_host_cap`` the cap
    for newly opened nodes; ``max_new_nodes`` caps node openings (hostname
    self-affinity bootstraps exactly one, target-fill phases open none);
    ``pref`` (warm repair only) the class's freed-hole counts per slot
    (``_fill_with_pref``); ``open_restrict`` ([N, Z]) gives each open node
    a restriction of its own in place of ``zone_restrict``, which fresh nodes
    keep."""
    n_slots = state.used.shape[0]
    n_tmpl = statics.tmpl_it.shape[0]

    merged = _merge_node_class(state, cls, statics)
    key_ok = _key_compat_node_class(state, cls, statics)  # [N]
    node_restrict = zone_restrict[None, :] if open_restrict is None else open_restrict
    zone_ok = state.zone & node_restrict & cls.zone[None, :]  # [N, Z]
    ct_ok = state.ct & cls.ct[None, :]  # [N, CT]
    tol_ok = cls.tol[state.tmpl_id]  # [N]

    it_ok = (
        state.viable
        & cls.it[None, :]
        & _it_intersects(merged, statics)
        & _offering_ok(zone_ok, ct_ok, statics)
    )  # [N, I]
    cap_ni = _capacity(state.used, cls.requests, statics)
    cap_ni = jnp.where(it_ok, cap_ni, 0)
    cap_n = _imax(jnp.max(cap_ni, axis=-1), statics)  # [N]

    elig = (
        state.open_
        & key_ok
        & tol_ok
        & jnp.any(zone_ok, axis=-1)
        & jnp.any(ct_ok, axis=-1)
    )
    if extra_elig is not None:
        elig = elig & extra_elig
    if ft.host_ports:
        has_ports = jnp.any(cls.ports)
        port_conflict = jnp.any(state.ports & cls.ports[None, :], axis=-1)
        elig = elig & ~port_conflict
        cap_n = jnp.minimum(cap_n, jnp.where(has_ports, 1, UNLIMITED))
    cap_n = jnp.where(elig, jnp.minimum(cap_n, host_cap_vec), 0)
    if max_new_nodes is not None and max_new_nodes == 1:
        # hostname self-affinity bootstrap: at most one node hosts the class
        first = jnp.argmax(cap_n > 0)
        cap_n = jnp.where(jnp.arange(n_slots) == first, cap_n, 0)

    # node order: emptiest first (pod count, then slot index); pod_count and
    # slot count both stay far below 2^15 so the packed key fits int32
    priority = state.pod_count * n_slots + jnp.arange(n_slots, dtype=jnp.int32)
    priority = jnp.where(cap_n > 0, priority, jnp.iinfo(jnp.int32).max)
    assigned = _fill_with_pref(quota, cap_n, priority, pref)
    placed_existing = jnp.sum(assigned)

    # -- commit to existing nodes --------------------------------------------
    took = assigned > 0
    add_req = assigned[:, None].astype(jnp.float32) * cls.requests[None, :]
    used = state.used + add_req
    sel = took[:, None]
    kmask = jnp.where(sel[..., None], merged.mask, state.kmask)
    kdef = jnp.where(sel, merged.defined, state.kdef)
    kneg = jnp.where(sel, merged.negative, state.kneg)
    kgt = jnp.where(sel, merged.gt, state.kgt)
    klt = jnp.where(sel, merged.lt, state.klt)
    # the node inherits the pod's zone requirements (incl. anti-affinity
    # exclusions and the phase restriction) exactly as the host merges pod
    # requirements into the node on add (node.go:62-117)
    new_zone = jnp.where(sel, zone_ok, state.zone)
    new_ct = jnp.where(sel, ct_ok, state.ct)
    viable = jnp.where(sel, it_ok & (cap_ni >= assigned[:, None]), state.viable)
    if ft.host_ports:
        ports_plane = jnp.where(sel, state.ports | cls.ports[None, :], state.ports)
    else:
        ports_plane = state.ports
    pod_count = state.pod_count + assigned

    # -- open fresh nodes ----------------------------------------------------
    rem = quota - placed_existing

    # template viability for this class+restriction (scheduler.go:192-217):
    # taints, requirement compat, and a non-empty filtered instance list
    tmpl_t = statics.tmpl
    cls_t = mask_ops.ReqTensor(
        cls.mask[None], cls.defined[None], cls.negative[None], cls.gt[None], cls.lt[None]
    )
    tmpl_key_ok = mask_ops.compatible(
        tmpl_t, cls_t, statics.is_custom, statics.vocab_ints, v=statics.mask_v
    )
    tmpl_merged = mask_ops.add(
        tmpl_t, cls_t, statics.valid, statics.vocab_ints,
        v=statics.mask_v, key_has_bounds=statics.key_has_bounds,
    )
    t_zone = statics.tmpl_zone & zone_restrict[None, :] & cls.zone[None, :]  # [T, Z]
    t_ct = statics.tmpl_ct & cls.ct[None, :]
    # provisioner limits: drop instance types whose launch would breach the
    # remaining budget (scheduler.go:292-309 filterByRemainingResources)
    within_limits = jnp.all(
        statics.it_capacity[None, :, :] <= remaining[:, None, :] + 1e-4, axis=-1
    )  # [T, I]
    t_it_ok = (
        statics.tmpl_it
        & cls.it[None, :]
        & _it_intersects(tmpl_merged, statics)
        & _offering_ok(t_zone, t_ct, statics)
        & within_limits
    )  # [T, I]
    t_cap_ti = _capacity(statics.tmpl_daemon, cls.requests, statics)
    t_cap_ti = jnp.where(t_it_ok, t_cap_ti, 0)
    t_cap = _imax(jnp.max(t_cap_ti, axis=-1), statics)  # [T]
    t_viable = (
        cls.tol
        & tmpl_key_ok
        & jnp.any(t_zone, axis=-1)
        & jnp.any(t_ct, axis=-1)
        & (t_cap > 0)
    )
    t_star = jnp.argmax(t_viable)  # first True (argmax of bool picks first max)
    t_ok = t_viable[t_star]

    per_node = jnp.minimum(t_cap[t_star], fresh_host_cap)
    if ft.host_ports:
        per_node = jnp.minimum(per_node, jnp.where(has_ports, 1, UNLIMITED))
    per_node = jnp.maximum(per_node, 1)
    n_new = jnp.where(t_ok & (rem > 0), -(-rem // per_node), 0)
    free_slots = n_slots - state.n_next
    n_new = jnp.minimum(n_new, free_slots)
    # provisioner-limit budget: opening a node pessimistically consumes the
    # largest surviving instance type (subtractMax), so the batch of openings
    # is capped by floor(remaining / max_capacity) per limited resource
    max_cap_star = _imax(jnp.max(
        jnp.where(t_it_ok[t_star][:, None], statics.it_capacity, 0.0), axis=0
    ), statics)  # [R]
    rem_star = remaining[t_star]  # [R]
    budget_per_r = jnp.where(
        jnp.isfinite(rem_star) & (max_cap_star > 0),
        jnp.floor((rem_star + 1e-4) / jnp.maximum(max_cap_star, 1e-9)),
        BIG,
    )
    budget_nodes = jnp.maximum(jnp.min(budget_per_r), 0.0).astype(jnp.int32)
    n_new = jnp.minimum(n_new, budget_nodes)
    if max_new_nodes is not None:
        # single-node semantics: once the class bootstrapped onto an open
        # slot, the remainder must join it — no fresh node for the overflow
        n_new = jnp.where(placed_existing > 0, 0, jnp.minimum(n_new, max_new_nodes))

    slot_idx = jnp.arange(n_slots)
    is_new = (slot_idx >= state.n_next) & (slot_idx < state.n_next + n_new)
    rank = slot_idx - state.n_next
    a_new = jnp.where(is_new, jnp.clip(rem - rank * per_node, 0, per_node), 0)
    placed_new = jnp.sum(a_new)

    seln = is_new[:, None]
    used = jnp.where(
        seln,
        statics.tmpl_daemon[t_star][None, :]
        + a_new[:, None].astype(jnp.float32) * cls.requests[None, :],
        used,
    )
    kmask = jnp.where(seln[..., None], tmpl_merged.mask[t_star][None], kmask)
    kdef = jnp.where(seln, tmpl_merged.defined[t_star][None], kdef)
    kneg = jnp.where(seln, tmpl_merged.negative[t_star][None], kneg)
    kgt = jnp.where(seln, tmpl_merged.gt[t_star][None], kgt)
    klt = jnp.where(seln, tmpl_merged.lt[t_star][None], klt)
    new_zone = jnp.where(seln, t_zone[t_star][None, :], new_zone)
    new_ct = jnp.where(seln, t_ct[t_star][None, :], new_ct)
    fresh_viable = t_it_ok[t_star][None, :] & (t_cap_ti[t_star][None, :] >= a_new[:, None])
    viable = jnp.where(seln, fresh_viable, viable)
    if ft.host_ports:
        ports_plane = jnp.where(
            seln, (a_new > 0)[:, None] & cls.ports[None, :], ports_plane
        )
    pod_count = jnp.where(is_new, a_new, pod_count)
    tmpl_id = jnp.where(is_new, t_star, state.tmpl_id)
    open_ = state.open_ | is_new
    n_next = state.n_next + n_new

    # pessimistic limit tracking: each opened node may become the largest
    # surviving instance type (scheduler.go:273-290 subtractMax)
    remaining = remaining.at[t_star].add(-n_new.astype(jnp.float32) * max_cap_star)

    new_state = NodeState(
        used, kmask, kdef, kneg, kgt, klt, new_zone, new_ct, viable,
        ports_plane, pod_count, tmpl_id, open_, n_next,
    )
    return new_state, assigned + a_new, placed_existing + placed_new, remaining


def _and_opt(a: Optional[jnp.ndarray], b: Optional[jnp.ndarray]):
    """AND of two optional eligibility masks (None = unrestricted)."""
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def step_goes_by_row(m_padded: int, g1: int) -> bool:
    """Whether a class step reads and records the topology planes by row
    (``_class_step``, TopoCounts docstring): member lists are kept
    (``member_index``: M > 0) and are short beside the group axis.  Two
    shapes decide, so the choice rides the compile key with them."""
    return 0 < 4 * m_padded < g1


def _add_to_rows(plane, rows, n_rows, values):
    """``plane`` with ``values`` [N] added into rows ``rows[:n_rows]`` (a
    traced count, the rows distinct): a loop of that many row updates, which
    XLA:TPU runs in place on a loop-carried plane — about 3 us a row at
    [4 097, 512] where rewriting the plane takes 25 and a ``scatter`` copies
    it first whenever the step also reads it (PERF.md §6, PR 32)."""

    def add_row(k, p):
        row = jax.lax.dynamic_index_in_dim(p, rows[k], keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(p, row + values[None, :], rows[k], 0)

    return jax.lax.fori_loop(0, n_rows, add_row, plane)


def _class_step(
    statics: Statics,
    ex_static: ExistingStatic,
    n_zones: int,
    carry,
    cls_with_index,
    features: SnapshotFeatures = ALL_FEATURES,
    pref: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    topo_base: Optional[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]] = None,
):
    """One scan step: schedule every pod of one class — existing nodes first,
    then new nodes, per phase.  Topology lives in shared group counts (the
    reference's hash-deduped TopologyGroups): forward counts gate spread skew /
    affinity targets / anti owners; inverse counts gate the pods anti owners
    repel.

    ``pref`` (warm repair only) is the class's ``(freed_new[N], freed_ex[E])``
    hole counts: every fill prefers refilling the slots this class's departed
    pods vacated (``_fill_with_pref``) before the normal priority order.
    ``topo_base`` (windowed warm repair only) is the
    ``(fwd_sing, fwd_full, inv_full)`` [G1, Z] zone-count contribution of
    new-node slots outside the repair window (RepairPlan docstring), added as
    constants into the zone derivations below.

    The step reads and records the topology planes by ROW where the member
    lists are kept and short beside the group axis (``by_row`` below: the K
    <= 3 zone rows the class owns, its member rows, its two anti rows), and
    whole otherwise; the dummy row is never written (TopoCounts docstring).

    ``features`` (static) prunes whole phase families the snapshot provably
    cannot exercise — they are never traced, not just runtime-skipped.
    The zone-committal phases (zone spread, required zonal anti) run as one
    batched multi-zone block (``committal_block``) that shares a single dense
    prep and resolves shared-node conflicts by zone order with cumulative
    caps.

    The step's blocks open ``jax.named_scope`` names (``kc.step.*``,
    ``kc.phase.<family>``, ``kc.existing`` / ``kc.new`` / ``kc.committal``,
    ``kc.fill``): one per BLOCK of the program, never per class, group, zone
    or lane, so a program carries the same names whatever its shapes.  They
    add no operation — a profiler capture's op events carry them, and the
    benchmark's ``kernel_*_s`` metrics read device time by them
    (docs/OBSERVABILITY.md "Scopes in a profiler capture")."""
    ft = features
    state, ex, topo, remaining = carry
    cls, cls_index = cls_with_index
    pref_new = pref[0] if pref is not None else None
    pref_ex = pref[1] if pref is not None else None
    m = cls.count
    n_ex = ex.pod_count.shape[0]
    n_new_slots = state.pod_count.shape[0]
    g1 = statics.grp_skew.shape[0]
    g_dummy = g1 - 1

    g_zs, g_hs, g_zaf, g_haf, g_zan, g_han = (cls.groups[i] for i in range(6))
    member_row = statics.grp_member[cls_index]  # [G1]
    # the groups this class is a member of lead its member list; the rest of
    # the list names the dummy group.  Where the lists are kept (M > 0,
    # ``member_index``) and short beside the group axis, the step touches the
    # planes BY ROW, else whole (TopoCounts)
    mem_idx = cls.member_idx  # i32[M]
    by_row = step_goes_by_row(mem_idx.shape[0], g1)
    if by_row:
        n_members = jnp.sum(mem_idx < g_dummy)
    tol_row = ex_static.tol[cls_index]  # [E]
    vol_add_row = ex_static.cls_vol_add[cls_index]  # [E, D]
    vol_per_pod_row = ex_static.cls_vol_per_pod[cls_index]  # [D]

    has_zs = g_zs < g_dummy
    has_zaf = g_zaf < g_dummy
    has_haf = g_haf < g_dummy
    has_zan = g_zan < g_dummy

    with jax.named_scope("kc.step.derive"):
        # -- derived per-zone counts (TopoCounts docstring): positive groups
        # count pods on zone-COMMITTED (singleton-mask) nodes, the committed-zone
        # rule of topology.go:231-276; anti groups count every zone a resident
        # node could still be in (pessimistic).  Reading the CURRENT masks — not
        # record-time snapshots — replays the host's retroactive narrowing.
        any_zone_groups = ft.zone_spread or ft.zone_affinity or ft.zone_anti
        if any_zone_groups or ft.inv_zone_anti:
            ex_zone_i = ex.zone.astype(jnp.int32) * ex.open_.astype(jnp.int32)[:, None]
            new_zone_i = state.zone.astype(jnp.int32) * state.open_.astype(jnp.int32)[:, None]
        # forward counts per zone of the zone groups this class OWNS — the only
        # rows of a [G1, Z] table a step ever read
        zone_fwd = {}
        own_zone = [
            (kind, g) for kind, g, on in (
                ("zs", g_zs, ft.zone_spread),
                ("zaf", g_zaf, ft.zone_affinity),
                ("zan", g_zan, ft.zone_anti),
            ) if on
        ]
        if own_zone:
            # K <= 3 rows, each a dynamic slice (a vector-index gather of three
            # rows costs XLA:TPU several times as much, PERF.md §6 PR 32)
            own_rows = [g for _, g in own_zone]
            own_fwd_ex = jnp.stack([topo.fwd_ex[g] for g in own_rows])  # [K, E]
            own_fwd_new = jnp.stack([topo.fwd_new[g] for g in own_rows])  # [K, N]
            ex_sing_zone = jnp.where(
                jnp.sum(ex_zone_i, axis=-1, keepdims=True) == 1, ex_zone_i, 0
            )
            new_sing_zone = jnp.where(
                jnp.sum(new_zone_i, axis=-1, keepdims=True) == 1, new_zone_i, 0
            )
            own_zone_fwd = jnp.einsum("ke,ez->kz", own_fwd_ex, ex_sing_zone) + jnp.einsum(
                "kn,nz->kz", own_fwd_new, new_sing_zone
            )  # [K, Z]
            if topo_base is not None:
                own_zone_fwd = own_zone_fwd + jnp.stack([topo_base[0][g] for g in own_rows])
            if ft.zone_anti:
                own_zone_full = jnp.einsum("ke,ez->kz", own_fwd_ex, ex_zone_i) + jnp.einsum(
                    "kn,nz->kz", own_fwd_new, new_zone_i
                )
                if topo_base is not None:
                    own_zone_full = own_zone_full + jnp.stack(
                        [topo_base[1][g] for g in own_rows]
                    )
                own_is_anti = jnp.stack([statics.grp_is_anti[g] for g in own_rows])
                own_zone_fwd = jnp.where(own_is_anti[:, None], own_zone_full, own_zone_fwd)
            zone_fwd = {kind: own_zone_fwd[k] for k, (kind, _) in enumerate(own_zone)}

        # -- inverse anti-affinity blocks (topology.go:44-47): members of anti
        # groups avoid every domain the group's owners could occupy
        blocked_z = ok_ex = ok_new = None
        if by_row and (ft.inv_zone_anti or ft.inv_host_anti):
            # one member group a turn: its two inverse rows, nothing wider
            def member_blocks(k, blocks):
                blocked_z, bad_ex, bad_new = blocks
                g = mem_idx[k]
                inv_ex_g, inv_new_g = topo.inv_ex[g], topo.inv_new[g]
                anti_zone = statics.grp_is_anti[g] & statics.grp_is_zone[g]
                anti_host = statics.grp_is_anti[g] & ~statics.grp_is_zone[g]
                if ft.inv_zone_anti:
                    zone_inv_g = inv_ex_g @ ex_zone_i + inv_new_g @ new_zone_i  # [Z]
                    if topo_base is not None:
                        zone_inv_g = zone_inv_g + topo_base[2][g]
                    blocked_z = blocked_z | (anti_zone & (zone_inv_g > 0))
                if ft.inv_host_anti:
                    bad_ex = bad_ex | (anti_host & (inv_ex_g > 0))
                    bad_new = bad_new | (anti_host & (inv_new_g > 0))
                return blocked_z, bad_ex, bad_new

            blocked_z, bad_ex, bad_new = jax.lax.fori_loop(0, n_members, member_blocks, (
                jnp.zeros(n_zones, dtype=bool),
                jnp.zeros(n_ex, dtype=bool),
                jnp.zeros(n_new_slots, dtype=bool),
            ))
            if ft.inv_host_anti:
                ok_ex, ok_new = ~bad_ex, ~bad_new
        else:
            if ft.inv_zone_anti:
                zone_inv_full = jnp.einsum("ge,ez->gz", topo.inv_ex, ex_zone_i) + jnp.einsum(
                    "gn,nz->gz", topo.inv_new, new_zone_i
                )
                if topo_base is not None:
                    zone_inv_full = zone_inv_full + topo_base[2]
                mem_anti_zone = member_row & statics.grp_is_anti & statics.grp_is_zone
                blocked_z = jnp.any(mem_anti_zone[:, None] & (zone_inv_full > 0), axis=0)  # [Z]
            if ft.inv_host_anti:
                mem_anti_host = member_row & statics.grp_is_anti & ~statics.grp_is_zone
                ok_ex = ~jnp.any(mem_anti_host[:, None] & (topo.inv_ex > 0), axis=0)  # [E]
                ok_new = ~jnp.any(mem_anti_host[:, None] & (topo.inv_new > 0), axis=0)  # [N]
        allowed_zone = cls.zone & ~blocked_z if ft.inv_zone_anti else cls.zone

        # -- per-node caps from hostname groups -----------------------------------
        # spread (topologygroup.go:184-188: hostname min-count is 0, so cap=skew):
        # members consume cap; non-members only need count <= skew
        cap_parts_ex = []
        cap_parts_new = []
        fresh_parts = []
        if ft.host_spread:
            skew_hs = statics.grp_skew[g_hs]
            member_hs = member_row[g_hs]
            hs_fwd_ex = topo.fwd_ex[g_hs]
            hs_fwd_new = topo.fwd_new[g_hs]
            cap_parts_ex.append(jnp.where(
                member_hs,
                jnp.maximum(skew_hs - hs_fwd_ex, 0),
                jnp.where(hs_fwd_ex <= skew_hs, UNLIMITED, 0),
            ))
            cap_parts_new.append(jnp.where(
                member_hs,
                jnp.maximum(skew_hs - hs_fwd_new, 0),
                jnp.where(hs_fwd_new <= skew_hs, UNLIMITED, 0),
            ))
            fresh_parts.append(jnp.where(member_hs, skew_hs, UNLIMITED))
        if ft.host_anti:
            # owned hostname anti-affinity: only zero-count nodes; self-members cap 1
            han_fwd_ex = topo.fwd_ex[g_han]
            han_fwd_new = topo.fwd_new[g_han]
            member_han = member_row[g_han]
            cap_parts_ex.append(jnp.where(
                g_han < g_dummy,
                jnp.where(han_fwd_ex == 0, jnp.where(member_han, 1, UNLIMITED), 0),
                UNLIMITED,
            ))
            cap_parts_new.append(jnp.where(
                g_han < g_dummy,
                jnp.where(han_fwd_new == 0, jnp.where(member_han, 1, UNLIMITED), 0),
                UNLIMITED,
            ))
            fresh_parts.append(jnp.where((g_han < g_dummy) & member_han, 1, UNLIMITED))
        if cap_parts_ex:
            host_cap_ex = functools.reduce(jnp.minimum, cap_parts_ex).astype(jnp.int32)
            host_cap_new = functools.reduce(jnp.minimum, cap_parts_new).astype(jnp.int32)
            fresh_host_cap = functools.reduce(jnp.minimum, fresh_parts).astype(jnp.int32)
        else:
            host_cap_ex = jnp.full((n_ex,), UNLIMITED, dtype=jnp.int32)
            host_cap_new = jnp.full((n_new_slots,), UNLIMITED, dtype=jnp.int32)
            fresh_host_cap = jnp.int32(UNLIMITED)

    # step-wide existing-node intake/merge tensors (valid across this step's
    # phases — they touch disjoint node sets; see ExClassPrep)
    with jax.named_scope("kc.step.prep_existing"):
        ex_prep = _prep_existing(
            ex, ex_static, cls, statics, host_cap_ex, tol_row,
            vol_add_row, vol_per_pod_row, ft,
        )

    assigned_total = jnp.zeros_like(state.pod_count)
    assigned_ex_total = jnp.zeros_like(ex.pod_count)
    placed_total = jnp.int32(0)

    def run_phase(state, ex, remaining, quota, restrict, targets_ex=None,
                  targets_new=None, single_node=False, max_new_nodes=None,
                  ex_restrict=None, open_restrict=None):
        """Wrapped in lax.cond so zero-quota phases (most of them: each class
        participates in 1-2 of the surviving phase kinds) cost nothing on
        device.  ``ex_restrict`` / ``open_restrict`` ([N, Z]) stand in for
        ``restrict`` on existing / open nodes; fresh nodes take ``restrict``."""

        def do(operand):
            state_i, ex_i, rem_i = operand
            extra_ex = _and_opt(ok_ex, targets_ex)
            extra_new = _and_opt(ok_new, targets_new)
            with jax.named_scope("kc.existing"):
                ex_o, a_ex, placed_ex = _phase_existing(
                    ex_i, ex_prep, cls, quota,
                    restrict if ex_restrict is None else ex_restrict,
                    extra_elig=extra_ex, single_node=single_node, ft=ft,
                    pref=pref_ex,
                )
            q_new = quota - placed_ex
            if single_node:
                q_new = jnp.where(placed_ex > 0, 0, q_new)
            with jax.named_scope("kc.new"):
                state_o, a_new, placed_new, rem_o = _phase(
                    state_i, cls, statics, q_new, restrict,
                    host_cap_new, fresh_host_cap, rem_i, extra_elig=extra_new,
                    max_new_nodes=max_new_nodes, ft=ft, pref=pref_new,
                    open_restrict=open_restrict,
                )
            return state_o, ex_o, a_new, a_ex, placed_ex + placed_new, rem_o

        def skip(operand):
            state_i, ex_i, rem_i = operand
            return (
                state_i,
                ex_i,
                jnp.zeros_like(state_i.pod_count),
                jnp.zeros_like(ex_i.pod_count),
                jnp.int32(0),
                rem_i,
            )

        return jax.lax.cond(_any_lane(quota > 0, statics), do, skip, (state, ex, remaining))

    def committal_block(state, ex, remaining, quota_z, cap_total):
        """All Z zone-committal phases of one family (zone spread quotas /
        required zonal anti), fused into ONE dense sweep.

        The sequential form runs Z full ``run_phase`` passes, each re-deriving
        the merge/compat/intersect planes and re-writing the whole carry.
        Those planes are IDENTICAL across the block: a node that takes pods in
        zone z narrows its zone mask to {z} and thereby leaves every later
        zone phase, so per-node capacity is consumed at most once and the
        per-class mask merge is idempotent for everyone else.  The fusion
        computes the dense prep once, derives all-Z capacity planes in batch,
        and resolves shared-node conflicts by zone order with cumulative caps
        inside a cheap lax.scan over zones ([N]/[E]-wide fills only); the one
        state commit at the end writes each plane once instead of Z times.
        ``cap_total`` bounds cumulative placement across zones (the required-
        anti family places at most ``m`` pods, one per admissible zone).
        Parity with the sequential path is fuzzed in
        tests/test_kernel_fusion_parity.py."""

        def block(operand):
            state_i, ex_i, rem_i = operand
            i32max = jnp.iinfo(jnp.int32).max
            # ---- dense prep shared by every zone --------------------------
            merged = _merge_node_class(state_i, cls, statics)
            key_ok = _key_compat_node_class(state_i, cls, statics)
            ct_ok = state_i.ct & cls.ct[None, :]
            tol_ok = cls.tol[state_i.tmpl_id]
            it_base = state_i.viable & cls.it[None, :] & _it_intersects(merged, statics)
            cap_ni = _capacity(state_i.used, cls.requests, statics)
            elig = state_i.open_ & key_ok & tol_ok & jnp.any(ct_ok, axis=-1)
            if ok_new is not None:
                elig = elig & ok_new
            if ft.host_ports:
                has_ports = jnp.any(cls.ports)
                port_conflict = jnp.any(state_i.ports & cls.ports[None, :], axis=-1)
                elig = elig & ~port_conflict
            zone_has_new = state_i.zone & cls.zone[None, :]  # [N, Z]
            cap_z_list = []
            viable_z_list = []
            for z in range(n_zones):
                ov = (
                    jnp.einsum(
                        "nc,ic->ni",
                        ct_ok.astype(jnp.bfloat16),
                        statics.it_avail[:, z, :].astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32,
                    )
                    > 0.5
                )
                ok_z = it_base & ov
                viable_z_list.append(ok_z)
                cap_z = jnp.max(jnp.where(ok_z, cap_ni, 0), axis=-1)
                if ft.host_ports:
                    cap_z = jnp.minimum(cap_z, jnp.where(has_ports, 1, UNLIMITED))
                cap_z = jnp.where(
                    elig & zone_has_new[:, z], jnp.minimum(cap_z, host_cap_new), 0
                )
                cap_z_list.append(cap_z)
            # one cross-shard max for the whole [Z, N] block: the clamps above
            # (host-port cap, eligibility mask, host_cap_new) all commute with
            # pmax — replicated operands, monotone ops over nonnegative caps
            cap_open_z = _imax(jnp.stack(cap_z_list), statics)  # [Z, N]
            viable_nzi = jnp.stack(viable_z_list, axis=1)  # [N, Z, I]
            priority = state_i.pod_count * n_new_slots + jnp.arange(
                n_new_slots, dtype=jnp.int32
            )
            # existing-node side: step prep reused, LIVE zone mask at entry
            ex_cap = ex_prep.cap if ok_ex is None else jnp.where(ok_ex, ex_prep.cap, 0)
            zone_has_ex = ex_i.zone & cls.zone[None, :]  # [E, Z]
            # template side: merge/compat/intersect are zone-independent
            cls_t = mask_ops.ReqTensor(
                cls.mask[None], cls.defined[None], cls.negative[None],
                cls.gt[None], cls.lt[None],
            )
            tmpl_key_ok = mask_ops.compatible(
                statics.tmpl, cls_t, statics.is_custom, statics.vocab_ints,
                v=statics.mask_v,
            )
            tmpl_merged = mask_ops.add(
                statics.tmpl, cls_t, statics.valid, statics.vocab_ints,
                v=statics.mask_v, key_has_bounds=statics.key_has_bounds,
            )
            t_ct = statics.tmpl_ct & cls.ct[None, :]
            t_ct_any = jnp.any(t_ct, axis=-1)
            t_base = statics.tmpl_it & cls.it[None, :] & _it_intersects(tmpl_merged, statics)
            t_cap_ti0 = _capacity(statics.tmpl_daemon, cls.requests, statics)
            ovt_z = jnp.stack([
                jnp.einsum(
                    "tc,ic->ti",
                    t_ct.astype(jnp.bfloat16),
                    statics.it_avail[:, z, :].astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32,
                )
                > 0.5
                for z in range(n_zones)
            ])  # [Z, T, I]
            t_zone_cls = statics.tmpl_zone & cls.zone[None, :]  # [T, Z]

            def zone_body(zc, xs):
                (taken_ex, a_ex_acc, zex, taken_new, a_open_acc, zopen,
                 fresh_t, fresh_a, fresh_z, fresh_viable, n_next, rem, placed) = zc
                z, quota, cap_open, ovt, zh_ex, tz = xs
                q = jnp.clip(jnp.minimum(quota, cap_total - placed), 0, None)
                # existing nodes first, in index order (scheduler.go:176-180)
                cap_e = jnp.where(~taken_ex & zh_ex, ex_cap, 0)
                a_ex = _fill_existing(q, cap_e, pref_ex)
                placed_ex = jnp.sum(a_ex)
                took_e = a_ex > 0
                taken_ex = taken_ex | took_e
                a_ex_acc = a_ex_acc + a_ex
                zex = jnp.where(took_e, z, zex)
                # then open slots, emptiest first
                q2 = q - placed_ex
                cap_n = jnp.where(~taken_new, cap_open, 0)
                pri_n = jnp.where(cap_n > 0, priority, i32max)
                a_op = _fill_with_pref(q2, cap_n, pri_n, pref_new)
                placed_op = jnp.sum(a_op)
                took_n = a_op > 0
                taken_new = taken_new | took_n
                a_open_acc = a_open_acc + a_op
                zopen = jnp.where(took_n, z, zopen)
                # then fresh nodes from the first viable template for the zone
                rem_pods = q2 - placed_op
                within = jnp.all(
                    statics.it_capacity[None, :, :] <= rem[:, None, :] + 1e-4, axis=-1
                )
                t_it_ok = t_base & ovt & within
                t_cap_ti = jnp.where(t_it_ok, t_cap_ti0, 0)
                t_cap = _imax(jnp.max(t_cap_ti, axis=-1), statics)
                t_viable = cls.tol & tmpl_key_ok & tz & t_ct_any & (t_cap > 0)
                t_star = jnp.argmax(t_viable)
                t_ok = t_viable[t_star]
                per_node = jnp.minimum(t_cap[t_star], fresh_host_cap)
                if ft.host_ports:
                    per_node = jnp.minimum(per_node, jnp.where(has_ports, 1, UNLIMITED))
                per_node = jnp.maximum(per_node, 1)
                n_new = jnp.where(t_ok & (rem_pods > 0), -(-rem_pods // per_node), 0)
                n_new = jnp.minimum(n_new, n_new_slots - n_next)
                max_cap_star = _imax(jnp.max(
                    jnp.where(t_it_ok[t_star][:, None], statics.it_capacity, 0.0), axis=0
                ), statics)
                rem_star = rem[t_star]
                budget_per_r = jnp.where(
                    jnp.isfinite(rem_star) & (max_cap_star > 0),
                    jnp.floor((rem_star + 1e-4) / jnp.maximum(max_cap_star, 1e-9)),
                    BIG,
                )
                budget_nodes = jnp.maximum(jnp.min(budget_per_r), 0.0).astype(jnp.int32)
                n_new = jnp.minimum(n_new, budget_nodes)
                slot_idx = jnp.arange(n_new_slots)
                is_new = (slot_idx >= n_next) & (slot_idx < n_next + n_new)
                a_fr = jnp.where(
                    is_new,
                    jnp.clip(rem_pods - (slot_idx - n_next) * per_node, 0, per_node),
                    0,
                )
                fresh_t = jnp.where(is_new, t_star, fresh_t)
                fresh_a = fresh_a + a_fr
                fresh_z = jnp.where(is_new, z, fresh_z)
                fv_row = t_it_ok[t_star][None, :] & (
                    t_cap_ti[t_star][None, :] >= a_fr[:, None]
                )
                fresh_viable = jnp.where(is_new[:, None], fv_row, fresh_viable)
                rem = rem.at[t_star].add(-n_new.astype(jnp.float32) * max_cap_star)
                n_next = n_next + n_new
                placed = placed + placed_ex + placed_op + jnp.sum(a_fr)
                return (taken_ex, a_ex_acc, zex, taken_new, a_open_acc, zopen,
                        fresh_t, fresh_a, fresh_z, fresh_viable, n_next, rem,
                        placed), None

            n_it = state_i.viable.shape[-1]
            zc0 = (
                jnp.zeros(n_ex, bool), jnp.zeros(n_ex, jnp.int32),
                jnp.zeros(n_ex, jnp.int32),
                jnp.zeros(n_new_slots, bool), jnp.zeros(n_new_slots, jnp.int32),
                jnp.zeros(n_new_slots, jnp.int32),
                jnp.full(n_new_slots, -1, jnp.int32), jnp.zeros(n_new_slots, jnp.int32),
                jnp.zeros(n_new_slots, jnp.int32),
                jnp.zeros((n_new_slots, n_it), bool),
                state_i.n_next, rem_i, jnp.int32(0),
            )
            xs = (
                jnp.arange(n_zones, dtype=jnp.int32), quota_z.astype(jnp.int32),
                cap_open_z, ovt_z, zone_has_ex.T, t_zone_cls.T,
            )
            (taken_ex, a_ex, zex, taken_new, a_open, zopen, fresh_t, fresh_a,
             fresh_z, fresh_viable, n_next, rem_o, placed), _ = jax.lax.scan(
                zone_body, zc0, xs
            )

            # ---- one-shot commit (each node took pods in at most one zone) --
            took_e = a_ex > 0
            sel_e = took_e[:, None]
            zhot_e = (jnp.arange(n_zones)[None, :] == zex[:, None]) & sel_e
            mex = ex_prep.merged
            ex_o = ExistingState(
                used=ex_i.used + a_ex[:, None].astype(jnp.float32) * cls.requests[None, :],
                kmask=jnp.where(sel_e[..., None], mex.mask, ex_i.kmask),
                kdef=jnp.where(sel_e, mex.defined, ex_i.kdef),
                kneg=jnp.where(sel_e, mex.negative, ex_i.kneg),
                kgt=jnp.where(sel_e, mex.gt, ex_i.kgt),
                klt=jnp.where(sel_e, mex.lt, ex_i.klt),
                zone=jnp.where(sel_e, zhot_e, ex_i.zone),
                ct=jnp.where(sel_e, ex_prep.ct_ok, ex_i.ct),
                ports=jnp.where(sel_e, ex_i.ports | cls.ports[None, :], ex_i.ports)
                if ft.host_ports else ex_i.ports,
                vol_used=jnp.where(
                    sel_e,
                    ex_i.vol_used + ex_prep.vol_add
                    + a_ex[:, None] * ex_prep.vol_per_pod[None, :],
                    ex_i.vol_used,
                )
                if ft.volume_limits else ex_i.vol_used,
                pod_count=ex_i.pod_count + a_ex,
                open_=ex_i.open_,
            )
            took_o = a_open > 0
            is_fresh = fresh_t >= 0
            tmpl_idx = jnp.maximum(fresh_t, 0)
            sel_o = took_o[:, None]
            sel_f = is_fresh[:, None]
            zhot_o = (jnp.arange(n_zones)[None, :] == zopen[:, None]) & sel_o
            zhot_f = (jnp.arange(n_zones)[None, :] == fresh_z[:, None]) & sel_f
            used = state_i.used + a_open[:, None].astype(jnp.float32) * cls.requests[None, :]
            used = jnp.where(
                sel_f,
                statics.tmpl_daemon[tmpl_idx]
                + fresh_a[:, None].astype(jnp.float32) * cls.requests[None, :],
                used,
            )
            kmask = jnp.where(sel_o[..., None], merged.mask, state_i.kmask)
            kmask = jnp.where(sel_f[..., None], tmpl_merged.mask[tmpl_idx], kmask)
            kdef = jnp.where(sel_o, merged.defined, state_i.kdef)
            kdef = jnp.where(sel_f, tmpl_merged.defined[tmpl_idx], kdef)
            kneg = jnp.where(sel_o, merged.negative, state_i.kneg)
            kneg = jnp.where(sel_f, tmpl_merged.negative[tmpl_idx], kneg)
            kgt = jnp.where(sel_o, merged.gt, state_i.kgt)
            kgt = jnp.where(sel_f, tmpl_merged.gt[tmpl_idx], kgt)
            klt = jnp.where(sel_o, merged.lt, state_i.klt)
            klt = jnp.where(sel_f, tmpl_merged.lt[tmpl_idx], klt)
            zone = jnp.where(sel_o, zhot_o, state_i.zone)
            zone = jnp.where(sel_f, zhot_f, zone)
            ct = jnp.where(sel_o, ct_ok, state_i.ct)
            ct = jnp.where(sel_f, t_ct[tmpl_idx], ct)
            v_open = jnp.take_along_axis(
                viable_nzi, jnp.maximum(zopen, 0)[:, None, None], axis=1
            )[:, 0, :]
            viable = jnp.where(
                sel_o, v_open & (cap_ni >= a_open[:, None]), state_i.viable
            )
            viable = jnp.where(sel_f, fresh_viable, viable)
            if ft.host_ports:
                ports_pl = jnp.where(
                    sel_o, state_i.ports | cls.ports[None, :], state_i.ports
                )
                ports_pl = jnp.where(
                    sel_f, (fresh_a > 0)[:, None] & cls.ports[None, :], ports_pl
                )
            else:
                ports_pl = state_i.ports
            pod_count = state_i.pod_count + a_open
            pod_count = jnp.where(is_fresh, fresh_a, pod_count)
            tmpl_id = jnp.where(is_fresh, tmpl_idx, state_i.tmpl_id)
            state_o = NodeState(
                used, kmask, kdef, kneg, kgt, klt, zone, ct, viable, ports_pl,
                pod_count, tmpl_id, state_i.open_ | is_fresh, n_next,
            )
            return state_o, ex_o, a_open + fresh_a, a_ex, placed, rem_o

        def do(operand):
            with jax.named_scope("kc.committal"):
                return block(operand)

        def skip(operand):
            state_i, ex_i, rem_i = operand
            return (
                state_i,
                ex_i,
                jnp.zeros_like(state_i.pod_count),
                jnp.zeros_like(ex_i.pod_count),
                jnp.int32(0),
                rem_i,
            )

        return jax.lax.cond(
            _any_lane(jnp.sum(quota_z) > 0, statics), do, skip, (state, ex, remaining)
        )

    def accumulate(results):
        nonlocal state, ex, remaining, assigned_total, assigned_ex_total, placed_total
        state, ex, assigned, assigned_ex, placed, remaining = results
        assigned_total = assigned_total + assigned
        assigned_ex_total = assigned_ex_total + assigned_ex
        placed_total = placed_total + placed

    # zones some template can actually serve for this class (or an eligible
    # existing node with intake left sits in) — used by spread quotas and the
    # affinity bootstrap below
    if ft.zone_spread or ft.zone_affinity:
        with jax.named_scope("kc.step.zone_intake"):
            # the einsum's i-contraction is partial per catalog shard; psum of the
            # integer-valued f32 partials is exact, so the >0.5 test is unmoved
            tmpl_offers = _isum(jnp.einsum(
                "ti,izc,tz,tc->z",
                statics.tmpl_it.astype(jnp.bfloat16),
                (statics.it_avail & cls.it[:, None, None]).astype(jnp.bfloat16),
                statics.tmpl_zone.astype(jnp.bfloat16),
                (statics.tmpl_ct & cls.ct[None, :]).astype(jnp.bfloat16),
                preferred_element_type=jnp.float32,
            ), statics) > 0.5  # [Z]
            ex_cap_spread = ex_prep.cap if ok_ex is None else jnp.where(ok_ex, ex_prep.cap, 0)
            # per-zone intake for this class: existing nodes contribute their
            # remaining intake; template zones open new nodes on demand (unbounded).
            # A multi-zone (unknown-zone) node's intake deliberately counts into
            # EVERY zone of its mask: the estimate must be optimistic, because an
            # over-grant surfaces as a phase shortfall (the spread_suspect sentinel
            # below routes it to the host oracle), whereas pinning the intake to
            # one zone would under-estimate the others and under-place with no
            # detectable signal — the host can commit such a node to whichever
            # zone the fill needs.
            # (the second bound keeps the running sum over nodes inside int32)
            ex_cap_ez = (
                jnp.minimum(jnp.minimum(ex_cap_spread, m), (2**31 - 1) // n_ex)[:, None]
                * ex_prep.zone_full.astype(jnp.int32)
            )  # i32[E, Z]
            ex_cap_z = jnp.sum(ex_cap_ez, axis=0)  # i32[Z]
            fillable = tmpl_offers | (ex_cap_z > 0)

    # -- zone spread phases (one committed zone per phase) --------------------
    spread_suspect = jnp.array(False)
    if ft.zone_spread:
        with jax.named_scope("kc.phase.zone_spread"):
            counts_zs = zone_fwd["zs"]  # [Z]
            member_zs = member_row[g_zs]
            cap_pods_z = jnp.where(tmpl_offers, UNLIMITED, jnp.minimum(ex_cap_z, UNLIMITED))

            # the reference's per-pod skew check measures against the min over ALL
            # the pod's domains, including zones that cannot take this class —
            # their counts stay frozen, capping every fillable zone at
            # frozen_min + maxSkew (topology_test.go:124-162 "existing pod" case).
            # A zone whose intake runs out MID-fill freezes the same way
            # (nextDomainTopologySpread keeps measuring it,
            # topologygroup.go:155-182), so the water-fill proceeds in rounds:
            # each round fills min-first up to the nearest saturation level, then
            # the saturated zone joins the frozen set and bounds the rest.
            unreachable = allowed_zone & ~fillable
            skew_zs = statics.grp_skew[g_zs]
            BIGI = jnp.int32(1 << 30)
            finite_cap = cap_pods_z < UNLIMITED
            quotas = jnp.zeros(n_zones, dtype=jnp.int32)
            sat = jnp.zeros(n_zones, dtype=bool)
            m_rem = m
            # which tied zone the reference reaches first (_water_fill), in the
            # first round: a later one runs only once a zone that no template
            # offers ran out of existing intake, and keeps the order of the counts
            ex_cum_z = jnp.cumsum(ex_cap_ez, axis=0)
            # worst case: one round per sequentially-saturating finite-cap zone,
            # plus a final redistribution round for the unbounded zones
            for fill_round in range(n_zones + 1):
                counts_now = counts_zs + quotas
                min_frozen = jnp.min(jnp.where(unreachable | sat, counts_now, BIGI))
                skew_cap = jnp.clip(min_frozen + skew_zs - counts_now, 0, UNLIMITED)
                active = allowed_zone & fillable & ~sat
                cap_rem = jnp.clip(cap_pods_z - quotas, 0, UNLIMITED)
                # level where the nearest capacity-bounded active zone saturates;
                # fills stop there so its frozen count bounds the next round
                lvl_sat = jnp.min(jnp.where(active & finite_cap, counts_now + cap_rem, BIGI))
                with jax.named_scope("kc.fill"):
                    q = _water_fill(
                        counts_now, active, m_rem, ex_cum_z if fill_round == 0 else None
                    )
                q = jnp.minimum(q, jnp.clip(lvl_sat - counts_now, 0, UNLIMITED))
                q = jnp.minimum(q, jnp.minimum(skew_cap, cap_rem))
                q = jnp.where(active, q, 0)
                quotas = quotas + q
                m_rem = m_rem - jnp.sum(q)
                sat = sat | (active & finite_cap & (quotas >= cap_pods_z))
            quotas = jnp.where(member_zs, quotas, 0)
            # under-placement sentinel (host-oracle parity,
            # topologygroup.go:155-182): the round bound can exhaust with quota
            # still unallocated while some active zone retains both skew and
            # capacity headroom — the shape ROADMAP gap 5 documented as silent.
            # Flag it; the shell re-routes the class's leftover pods through the
            # host path instead of quietly failing them.
            counts_end = counts_zs + quotas
            min_frozen_end = jnp.min(jnp.where(unreachable | sat, counts_end, BIGI))
            skew_headroom = (counts_end - min_frozen_end) < skew_zs
            cap_headroom = (cap_pods_z - quotas) > 0
            fill_residual = (m_rem > 0) & jnp.any(
                allowed_zone & fillable & ~sat & skew_headroom & cap_headroom
            )
            quotas_gated = jnp.where(has_zs, quotas, 0)
            results_zs = committal_block(
                state, ex, remaining, quotas_gated, jnp.int32(UNLIMITED)
            )
            placed_zs = results_zs[4]
            accumulate(results_zs)
            # quota granted but not realized in-phase: the water-fill's per-zone
            # intake estimate (ex_cap_z) is optimistic — e.g. a multi-zone node's
            # capacity counts into every zone of its mask — so a phase can place
            # fewer pods than its quota with no later round to redistribute them
            quota_shortfall = placed_zs < jnp.sum(quotas)
            spread_suspect = has_zs & member_zs & (fill_residual | quota_shortfall)

            # non-self-selecting zone spread: the pod never increments its own
            # group's counts, so the skew formula (count + 0 - min <= maxSkew,
            # topologygroup.go:155-182) admits a STATIC set of zones — and
            # upstream narrows the pod's node to ONE of them, the least counted
            # of the node's own.  A node left multi-zone could later be
            # committed to a zone the pod was never admitted to (the pod is a
            # member of OTHER groups, which count it there from then on), so
            # each open node takes its least counted admissible zone, a fresh
            # node the least counted of all, an existing node its own
            if ft.zone_spread_nonself:
                min_zs = jnp.min(jnp.where(cls.zone, counts_zs, BIGI))
                admissible_zs = allowed_zone & fillable & (counts_zs - min_zs <= skew_zs)
                z_nm = jnp.argmin(jnp.where(admissible_zs, counts_zs, BIGI))
                q_nm = jnp.where(has_zs & ~member_zs & jnp.any(admissible_zs), m, 0)
                own = state.zone & admissible_zs[None, :]  # [N, Z]
                z_own = jnp.argmin(jnp.where(own, counts_zs[None, :], BIGI), axis=-1)
                results_nm = run_phase(
                    state, ex, remaining, q_nm, jnp.arange(n_zones) == z_nm,
                    ex_restrict=admissible_zs,
                    open_restrict=own & (jnp.arange(n_zones)[None, :] == z_own[:, None]),
                )
                accumulate(results_nm)
                spread_suspect = spread_suspect | (
                    has_zs & ~member_zs & (results_nm[4] < q_nm)
                )

    # -- owned zone anti-affinity: zero-forward-count zones only --------------
    # self-members place one pod per currently-unpoisoned zone, each phase
    # COMMITTING its node to that single zone (the restrict narrows the node
    # mask to a singleton on merge).  This reaches the host's converged
    # fixpoint — one member per admissible zone — in batch one: the host's
    # record-time domain snapshots only get there over batches/retries as
    # co-location luck narrows masks (topology_test.go:1879-1923), so the
    # fuzzer contract is kernel >= host batch-one, equal at the fixpoint.
    # Non-member owners don't repel each other: plain multi-zone phase.
    # soft (preferred) anti keeps the single pessimistic multi-zone phase:
    # the reference relaxes failing preference pods onto existing nodes and
    # never revisits them, so one-per-zone committal would permanently
    # diverge from its packing (topology_test.go:1478 — co-location allowed);
    # required anti commits because the reference CONVERGES to one-per-zone
    # over batches (pods stay pending until zones register)
    if ft.zone_anti:
        with jax.named_scope("kc.phase.zone_anti"):
            zero_zones = allowed_zone & (zone_fwd["zan"] == 0)
            anti_member = member_row[g_zan]
            anti_required = has_zan & anti_member & ~cls.anti_soft[0]
            # the committal phases are only reachable for required-anti members;
            # when the snapshot statically has none (features.required_zone_anti
            # False, from encode_snapshot), they are never traced — formerly the
            # single largest per-class phase block, all compile + per-step cost
            if ft.required_zone_anti:
                anti_quota_z = (anti_required & zero_zones).astype(jnp.int32)
                accumulate(committal_block(state, ex, remaining, anti_quota_z, m))
            anti_quota = jnp.where(
                has_zan & jnp.any(zero_zones),
                jnp.where(
                    anti_member,
                    jnp.where(cls.anti_soft[0], jnp.minimum(m, 1), 0),
                    m,
                ),
                0,
            )
            accumulate(run_phase(state, ex, remaining, anti_quota, zero_zones))

    # -- zone affinity: nonzero-count zones (the selected pods' locations),
    # else self-members bootstrap one allowed zone (topologygroup.go:202-233).
    # The bootstrap must be capacity-aware (the host's per-node bootstrap only
    # lands where a node is viable): restrict to zones some template offers
    # for this class, or where an open existing node sits
    if ft.zone_affinity:
        with jax.named_scope("kc.phase.zone_affinity"):
            bootstrap_allowed = allowed_zone & fillable
            nonzero_zones = allowed_zone & (zone_fwd["zaf"] > 0)
            # the reference tries existing nodes in index order before any new
            # node, and its bootstrap admits the zone of whichever node it is
            # trying (topologygroup.go:210-231): the first existing node with
            # intake for the class names the zone; with none, the first allowed
            ex_zone_ok = ex_prep.zone_full & bootstrap_allowed[None, :]  # [E, Z]
            ex_boot = (ex_cap_spread > 0) & jnp.any(ex_zone_ok, axis=-1)  # [E]
            boot_from = jnp.where(
                jnp.any(ex_boot), ex_zone_ok[jnp.argmax(ex_boot)], bootstrap_allowed
            )
            bootstrap_zone = (
                jnp.zeros(n_zones, dtype=bool)
                .at[jnp.argmax(boot_from)]
                .set(jnp.any(bootstrap_allowed) & member_row[g_zaf])
            )
            zone_aff_restrict = jnp.where(
                jnp.any(nonzero_zones), nonzero_zones, bootstrap_zone
            )
            zone_aff_quota = jnp.where(has_zaf & ~has_haf & jnp.any(zone_aff_restrict), m, 0)
            accumulate(run_phase(state, ex, remaining, zone_aff_quota, zone_aff_restrict))

    # -- hostname affinity: fill target nodes (forward count > 0) on both
    # planes; else self-members bootstrap exactly one node
    all_zones = jnp.ones(n_zones, dtype=bool)
    if ft.host_affinity:
        with jax.named_scope("kc.phase.host_affinity"):
            if ft.zone_affinity:
                host_restrict = jnp.where(has_zaf, zone_aff_restrict, all_zones) & allowed_zone
            else:
                host_restrict = all_zones & allowed_zone
            targets_ex = (topo.fwd_ex[g_haf] > 0) & ex.open_
            targets_new = (topo.fwd_new[g_haf] > 0) & state.open_
            targets_exist = jnp.any(targets_ex) | jnp.any(targets_new)
            host_quota = jnp.where(has_haf, m, 0)
            q_targets = jnp.where(targets_exist, host_quota, 0)
            accumulate(
                run_phase(
                    state, ex, remaining, q_targets, host_restrict,
                    targets_ex=targets_ex, targets_new=targets_new, max_new_nodes=0,
                )
            )
            q_boot = jnp.where(targets_exist | ~member_row[g_haf], 0, host_quota)
            accumulate(
                run_phase(
                    state, ex, remaining, q_boot, host_restrict,
                    single_node=True, max_new_nodes=1,
                )
            )

    # -- unconstrained phase for plain classes --------------------------------
    with jax.named_scope("kc.phase.plain"):
        any_quota = jnp.where(has_zs | has_zan | has_zaf | has_haf, 0, m)
        accumulate(run_phase(state, ex, remaining, any_quota, allowed_zone))

    # -- record (topology.go:120-143): update shared PER-NODE counts ----------
    # zone projections happen at read time from live masks (derivation above),
    # so recording is pure bookkeeping: each placed pod adds its class's
    # membership/ownership to its node's row in every relevant group.
    # No class can own or match a group when no feature family exists, so the
    # whole record step prunes away with them.
    with jax.named_scope("kc.step.record"):
        if (ft.zone_spread or ft.host_spread or ft.zone_affinity or ft.host_affinity
                or ft.zone_anti or ft.host_anti or ft.inv_zone_anti or ft.inv_host_anti):
            a_ex_f = assigned_ex_total.astype(jnp.int32)
            a_new_f = assigned_total.astype(jnp.int32)
            # preferred-anti owners register no inverse counts (the reference skips
            # inverse tracking for preferences, topology.go:203-206)
            anti_rows = jnp.stack([g_zan, g_han])
            anti_on = (anti_rows < g_dummy) & ~cls.anti_soft
            if by_row:
                # the member rows and the two owned anti rows, updated in place in
                # the carry; the dummy row is never among them (TopoCounts)
                fwd_ex = _add_to_rows(topo.fwd_ex, mem_idx, n_members, a_ex_f)
                fwd_new = _add_to_rows(topo.fwd_new, mem_idx, n_members, a_new_f)
                inv_ex, inv_new = topo.inv_ex, topo.inv_new
                if ft.zone_anti or ft.host_anti:
                    anti_first = jnp.where(anti_on[0], anti_rows, anti_rows[::-1])
                    n_anti = jnp.sum(anti_on)
                    inv_ex = _add_to_rows(inv_ex, anti_first, n_anti, a_ex_f)
                    inv_new = _add_to_rows(inv_new, anti_first, n_anti, a_new_f)
                topo = TopoCounts(fwd_ex=fwd_ex, inv_ex=inv_ex, fwd_new=fwd_new, inv_new=inv_new)
            else:
                member_i = member_row.astype(jnp.int32)
                own_inv = jnp.sum(
                    (jnp.arange(g1)[None, :] == anti_rows[:, None]) & anti_on[:, None], axis=0
                ).astype(jnp.int32)
                topo = TopoCounts(
                    fwd_ex=topo.fwd_ex + member_i[:, None] * a_ex_f[None, :],
                    inv_ex=topo.inv_ex + own_inv[:, None] * a_ex_f[None, :],
                    fwd_new=topo.fwd_new + member_i[:, None] * a_new_f[None, :],
                    inv_new=topo.inv_new + own_inv[:, None] * a_new_f[None, :],
                )

        failed = m - placed_total
    return (
        (state, ex, topo, remaining),
        (assigned_total, assigned_ex_total, failed, spread_suspect),
    )


def pack_masks(sa: StaticArrays, class_tensors):
    """The bool mask planes the encoder emits -> the uint32 words the kernel
    runs on (ops/masks.py pack_mask)."""
    sa = sa._replace(
        it=mask_ops.pack_req(sa.it),
        tmpl=mask_ops.pack_req(sa.tmpl),
        valid=mask_ops.pack_mask(sa.valid),
    )
    return sa, class_tensors._replace(mask=mask_ops.pack_mask(class_tensors.mask))


def solve_core(
    class_tensors,
    statics_arrays,
    n_slots: int,
    key_has_bounds,
    existing_state: "Optional[ExistingState]" = None,
    existing_static: "Optional[ExistingStatic]" = None,
    n_passes: int = 1,
    emit_zonal_anti: "Optional[bool]" = None,
    features: "Optional[SnapshotFeatures]" = None,
    warm_carry: "Optional[WarmCarry]" = None,
    repair_plan: "Optional[RepairPlan]" = None,
    catalog_axis: "Optional[str]" = None,
    lane_axis: "Optional[str]" = None,
):
    """Unjitted kernel core — jit/vmap/shard_map-composable (the parallel layer
    vmaps this over snapshot replicas and consolidation subsets;
    __graft_entry__ compile-checks it).

    ``catalog_axis`` (static) names the mesh axis the catalog (instance-type)
    planes are sharded over when this body runs inside a ``shard_map``
    (parallel.mesh dispatch): every I-axis reduction finishes with a
    ``pmax``/``psum`` collective over that axis (``_imax``/``_isum``), all of
    them exact, so the sharded solve is bit-identical to the single-device
    solve.  None (the default; the auto mesh config resolves to it on a
    single device) traces no collectives at all, while a FORCED 1-device
    mesh keeps them as singleton no-ops — the degenerate case is the same
    code either way.

    ``lane_axis`` (static) names the ``vmap`` axis of the consolidation sweep
    (ops.consolidate.sweep: one lane a prefix size): the step's "anything to
    place?" conds then decide once for all lanes (``_any_lane``) and stay
    conds.  None (every other caller) traces nothing.

    ``n_passes`` > 1 re-scans still-failed pods seeded by earlier passes'
    topology counts — the kernel's equivalent of the host queue re-pushing
    failed pods until no progress (scheduler.go:117-123), needed when a
    cross-group affinity follower scans before its target
    (models.snapshot.affinity_scan_passes).

    ``features`` (static) is the snapshot's SnapshotFeatures phase plan —
    pass EncodedSnapshot.features so constraint families no class can
    exercise are never traced (docs/KERNEL_PERF.md).  ``emit_zonal_anti`` is
    the legacy single-flag form (pre-features callers); it maps onto
    features.required_zone_anti.  Requirement masks arrive as bool planes
    (what models/snapshot.py encodes) and are packed here into uint32 words;
    the mask algebra then runs as bitwise AND + popcount (ops/masks.py).

    ``warm_carry`` (traced pytree, shapes fixed) switches the call into a
    warm-start REPAIR solve: the scan resumes from a previous solve's final
    carry instead of empty slots, and ``class_tensors.count`` holds only the
    delta pods to place (docs/INCREMENTAL.md).  The carry's plane shapes must
    match this call's buckets — solver.incremental guarantees that by reusing
    the previous padded tensors verbatim.  ``existing_static`` is still
    required when the carry has real existing nodes (its tol/vol rows are
    per-class); with a warm carry the topology/budget seeding is skipped —
    both already live in the carry.  ``repair_plan`` (warm path only) carries
    the per-class freed-hole planes every fill prefers to refill first plus
    the out-of-window topology bases of a bounded repair (RepairPlan
    docstring)."""
    if features is None:
        ft = ALL_FEATURES
        if emit_zonal_anti is not None:
            ft = ft._replace(required_zone_anti=bool(emit_zonal_anti))
    else:
        ft = SnapshotFeatures(*features)
    ft = ft.canonical()
    sa = StaticArrays(*statics_arrays)
    width = sa.valid.shape[-1]  # semantic slot count V+1, pre-packing
    with jax.named_scope("kc.init"):
        sa, class_tensors = pack_masks(sa, class_tensors)
    statics = Statics(
        *sa, key_has_bounds=key_has_bounds, mask_v=width,
        catalog_axis=catalog_axis, lane_axis=lane_axis,
    )
    n_zones = statics.tmpl_zone.shape[-1]
    n_res = statics.it_alloc.shape[-1]
    n_keys = sa.it.defined.shape[-1]
    n_it = statics.it_alloc.shape[0]
    n_ct = statics.tmpl_ct.shape[-1]
    n_classes = class_tensors.count.shape[0]

    g1 = statics.grp_skew.shape[0]
    n_ports = class_tensors.ports.shape[-1] if n_classes else 1
    with jax.named_scope("kc.init"):
        if warm_carry is not None:
            # warm-start repair: resume from the previous solve's final carry.
            # The carry's planes already went through this function once — masks
            # are packed, topology counts and the limit budget are live — so all
            # of the seeding below is skipped (it would double-count).
            wc = WarmCarry(*warm_carry)
            state = NodeState(*wc.state)
            existing_state = ExistingState(*wc.ex_state)
            n_slots = state.pod_count.shape[0]
            if existing_static is None:
                existing_static = empty_existing_static(n_res, n_classes, g1)
            topo = TopoCounts(*wc.topo)
            remaining0 = wc.remaining
        else:
            kmask0 = jnp.broadcast_to(
                jnp.asarray(mask_ops.full_words(width)),
                (n_slots, n_keys, mask_ops.words_for(width)),
            )
            state = NodeState(
                used=jnp.zeros((n_slots, n_res), dtype=jnp.float32),
                kmask=kmask0,
                kdef=jnp.zeros((n_slots, n_keys), dtype=bool),
                kneg=jnp.zeros((n_slots, n_keys), dtype=bool),
                kgt=jnp.full((n_slots, n_keys), -jnp.inf, dtype=jnp.float32),
                klt=jnp.full((n_slots, n_keys), jnp.inf, dtype=jnp.float32),
                zone=jnp.ones((n_slots, n_zones), dtype=bool),
                ct=jnp.ones((n_slots, n_ct), dtype=bool),
                viable=jnp.ones((n_slots, n_it), dtype=bool),
                ports=jnp.zeros((n_slots, n_ports), dtype=bool),
                pod_count=jnp.zeros(n_slots, dtype=jnp.int32),
                tmpl_id=jnp.zeros(n_slots, dtype=jnp.int32),
                open_=jnp.zeros(n_slots, dtype=bool),
                n_next=jnp.int32(0),
            )
            if existing_state is None:
                existing_state = empty_existing_state(n_res, n_keys, width, n_zones, n_ct, n_ports)
                existing_static = empty_existing_static(n_res, n_classes, g1)
            if existing_state.kmask.dtype != jnp.uint32:
                existing_state = existing_state._replace(
                    kmask=mask_ops.pack_mask(existing_state.kmask)
                )

            # seed topology counts from pre-existing pods (topology.go:231-276
            # countDomains): forward from selector-matching pods, inverse from
            # anti-term owners — closed nodes (consolidation subsets) drop out at
            # derivation time (the zone projection multiplies by the open mask)
            open_i = existing_state.open_.astype(jnp.int32)
            member_open = existing_static.grp_node_member * open_i[None, :]
            owner_open = existing_static.grp_node_owner * open_i[None, :]
            topo = TopoCounts(
                fwd_ex=member_open,
                inv_ex=owner_open,
                fwd_new=jnp.zeros((g1, n_slots), dtype=jnp.int32),
                inv_new=jnp.zeros((g1, n_slots), dtype=jnp.int32),
            )

    def step(carry, cls_with_index):
        # the whole class step is masked behind count > 0: a zero-count class
        # contributes nothing (phases place 0, record adds 0), so skipping it
        # is a pure no-op that saves the step's dense prep on device.  This is
        # what makes the warm-start REPAIR scan cost proportional to the dirty
        # region: clean classes carry count 0 and fall through, while the
        # iteration shape (C steps) stays fixed so the executable is reused
        # across reconciles.  Full solves benefit too — padded bucket rows and
        # ladder-variant rows idle at 0 until a pass rolls counts into them.
        if repair_plan is not None:
            cls, cls_index, pref_new_row, pref_ex_row = cls_with_index
            pref = (pref_new_row, pref_ex_row)
            base = (
                repair_plan.base_fwd_sing,
                repair_plan.base_fwd_full,
                repair_plan.base_inv_full,
            )
        else:
            cls, cls_index = cls_with_index
            pref = None
            base = None

        def do(carry_in):
            return _class_step(
                statics, existing_static, n_zones, carry_in, (cls, cls_index),
                features=ft, pref=pref, topo_base=base,
            )

        def skip(carry_in):
            state_i, ex_i, _, _ = carry_in
            return carry_in, (
                jnp.zeros_like(state_i.pod_count),
                jnp.zeros_like(ex_i.pod_count),
                jnp.int32(0),
                jnp.array(False),
            )

        return jax.lax.cond(_any_lane(cls.count > 0, statics), do, skip, carry)

    with jax.named_scope("kc.init"):
        cls_indices = jnp.arange(n_classes, dtype=jnp.int32)
        if warm_carry is None:
            # charge open owned nodes' capacity against their provisioner's budget
            n_tmpl = statics.tmpl_zone.shape[0]
            tmpl_onehot = (
                existing_static.node_tmpl[:, None] == jnp.arange(n_tmpl)[None, :]
            ) & (existing_static.node_owned & existing_state.open_)[:, None]  # [E, T]
            used_budget = jnp.einsum(
                "et,er->tr", tmpl_onehot.astype(jnp.float32), existing_static.node_capacity,
                precision=_EXACT_F32,
            )
            remaining0 = statics.tmpl_limits0 - used_budget
        carry = (state, existing_state, topo, remaining0)
        assign = jnp.zeros((n_classes, n_slots), dtype=jnp.int32)
        n_ex = existing_state.pod_count.shape[0]
        assign_ex = jnp.zeros((n_classes, n_ex), dtype=jnp.int32)
        count_left = class_tensors.count
        failed = count_left
        suspect = jnp.zeros(n_classes, dtype=bool)
    for p in range(max(n_passes, 1)):
        cls_pass = class_tensors._replace(count=count_left)
        xs = (cls_pass, cls_indices)
        if repair_plan is not None:
            xs = xs + (
                repair_plan.pref_new.astype(jnp.int32),
                repair_plan.pref_ex.astype(jnp.int32),
            )
        with jax.named_scope("kc.scan"):
            carry, (a, a_ex, failed, suspect_p) = jax.lax.scan(step, carry, xs)
        with jax.named_scope("kc.finish"):
            assign = assign + a
            assign_ex = assign_ex + a_ex
            suspect = suspect | suspect_p
            # roll failed counts one step down the preference ladder (the host
            # path's fail -> Preferences.Relax -> re-push round); classes with no
            # successor retry as themselves (late-affinity re-scan)
            roll_to = jnp.where(
                class_tensors.relax_next >= 0, class_tensors.relax_next, cls_indices
            )
            count_left = jnp.zeros_like(failed).at[roll_to].add(failed)
            if p + 1 < n_passes:
                # shared volume adds are once-per-(LADDER, node): ladder rows
                # share one claim profile, so a root placing in pass 1 and its
                # variant landing on the same node in pass 2 must count the claim
                # set once — collapse placements to the root row before the add
                state_c, ex_c, topo_c, rem_c = carry
                placed_any = (assign_ex > 0).astype(jnp.int32)  # [C, E]
                placed_root = (
                    jnp.zeros_like(placed_any).at[class_tensors.root].max(placed_any)
                )
                is_root = (class_tensors.root == cls_indices)[:, None].astype(jnp.int32)
                shared = jnp.sum(
                    (placed_root * is_root)[:, :, None] * existing_static.cls_vol_add,
                    axis=0,
                )
                per_pod = jnp.sum(
                    assign_ex[:, :, None] * existing_static.cls_vol_per_pod[:, None, :],
                    axis=0,
                )
                ex_c = ex_c._replace(vol_used=existing_state.vol_used + shared + per_pod)
                carry = (state_c, ex_c, topo_c, rem_c)
    final_state, final_ex, final_topo, final_remaining = carry
    return SolveOutputs(
        assign=assign,
        assign_existing=assign_ex,
        failed=failed,
        state=final_state,
        ex_state=final_ex,
        spread_suspect=suspect,
        topo=final_topo,
        remaining=final_remaining,
    )


def empty_existing_state(
    n_res, n_keys, width, n_zones, n_ct, n_ports: int = 1, n_drivers: int = 1
) -> ExistingState:
    """A single closed dummy slot (E=0 shapes upset some XLA reductions)."""
    return ExistingState(
        used=jnp.zeros((1, n_res), dtype=jnp.float32),
        kmask=jnp.ones((1, n_keys, width), dtype=bool),
        kdef=jnp.zeros((1, n_keys), dtype=bool),
        kneg=jnp.zeros((1, n_keys), dtype=bool),
        kgt=jnp.full((1, n_keys), -jnp.inf, dtype=jnp.float32),
        klt=jnp.full((1, n_keys), jnp.inf, dtype=jnp.float32),
        zone=jnp.ones((1, n_zones), dtype=bool),
        ct=jnp.ones((1, n_ct), dtype=bool),
        ports=jnp.zeros((1, n_ports), dtype=bool),
        vol_used=jnp.zeros((1, n_drivers), dtype=jnp.int32),
        pod_count=jnp.zeros(1, dtype=jnp.int32),
        open_=jnp.zeros(1, dtype=bool),
    )


def empty_existing_static(
    n_res, n_classes, n_groups1: int = 1, n_drivers: int = 1
) -> ExistingStatic:
    return ExistingStatic(
        alloc=jnp.zeros((1, n_res), dtype=jnp.float32),
        init=jnp.zeros(1, dtype=bool),
        tol=jnp.zeros((n_classes, 1), dtype=bool),
        grp_node_member=jnp.zeros((n_groups1, 1), dtype=jnp.int32),
        grp_node_owner=jnp.zeros((n_groups1, 1), dtype=jnp.int32),
        node_capacity=jnp.zeros((1, n_res), dtype=jnp.float32),
        node_tmpl=jnp.zeros(1, dtype=jnp.int32),
        node_owned=jnp.zeros(1, dtype=bool),
        vol_limit=jnp.full((1, n_drivers), UNLIMITED, dtype=jnp.int32),
        cls_vol_add=jnp.zeros((n_classes, 1, n_drivers), dtype=jnp.int32),
        cls_vol_per_pod=jnp.zeros((n_classes, n_drivers), dtype=jnp.int32),
    )


_solve_jit = functools.partial(
    jax.jit,
    static_argnames=(
        "n_slots", "key_has_bounds", "n_passes", "emit_zonal_anti", "features",
    ),
)(solve_core)


def warm_carry_of(outputs: SolveOutputs) -> Optional[WarmCarry]:
    """Package a solve's final carry for a later repair solve.  All leaves are
    (lazy) device arrays — holding a WarmCarry costs no transfer; None when
    the outputs predate the carry fields (hand-built in tests)."""
    if outputs.topo is None or outputs.remaining is None:
        return None
    return WarmCarry(
        state=outputs.state,
        ex_state=outputs.ex_state,
        topo=outputs.topo,
        remaining=outputs.remaining,
    )


def _repair_free_impl(
    warm_carry: WarmCarry,
    free_new: jnp.ndarray,
    free_ex: jnp.ndarray,
    cls_requests: jnp.ndarray,
    member: jnp.ndarray,
    own_inv: jnp.ndarray,
) -> WarmCarry:
    """Return evicted pods' capacity and topology counts to a warm carry.

    ``free_new`` i32[C, N] / ``free_ex`` i32[C, E] count the pods of class c
    evicted from each slot since the carry was produced; ``cls_requests``
    f32[C, R] is the per-pod request vector, ``member`` / ``own_inv``
    i32[C, G1] the class's topology-group membership and inverse-ownership
    rows (solver.incremental builds them host-side from the snapshot).

    Deliberately one-way: used capacity, pod counts, and group counts are
    returned, but merged requirement masks, zone/ct commitments, port claims,
    and volume counters are NOT reverted — a freed slot keeps every
    requirement its departed residents stamped on it.  That pessimism can
    only under-place (never corrupt), and it is exactly the accumulated
    optimality drift the fallback policy's periodic full-solve audit resets
    (docs/INCREMENTAL.md)."""
    with jax.named_scope("kc.repair.free"):
        wc = WarmCarry(*warm_carry)
        state = NodeState(*wc.state)
        ex = ExistingState(*wc.ex_state)
        topo = TopoCounts(*wc.topo)
        f_new = free_new.astype(jnp.float32)
        f_ex = free_ex.astype(jnp.float32)
        state = state._replace(
            used=state.used - jnp.einsum(
                "cn,cr->nr", f_new, cls_requests, precision=_EXACT_F32
            ),
            pod_count=jnp.maximum(state.pod_count - jnp.sum(free_new, axis=0), 0),
        )
        ex = ex._replace(
            used=ex.used - jnp.einsum(
                "ce,cr->er", f_ex, cls_requests, precision=_EXACT_F32
            ),
            pod_count=jnp.maximum(ex.pod_count - jnp.sum(free_ex, axis=0), 0),
        )
        topo = TopoCounts(
            fwd_ex=jnp.maximum(topo.fwd_ex - jnp.einsum("cg,ce->ge", member, free_ex), 0),
            inv_ex=jnp.maximum(topo.inv_ex - jnp.einsum("cg,ce->ge", own_inv, free_ex), 0),
            fwd_new=jnp.maximum(topo.fwd_new - jnp.einsum("cg,cn->gn", member, free_new), 0),
            inv_new=jnp.maximum(topo.inv_new - jnp.einsum("cg,cn->gn", own_inv, free_new), 0),
        )
        return WarmCarry(state=state, ex_state=ex, topo=topo, remaining=wc.remaining)


repair_free = jax.jit(_repair_free_impl)
# the pipelined loop's twin (utils.pipeline.donation_enabled): the input
# carry's device buffers are DONATED — steady-state churn frees evictions in
# place instead of reallocating the full-width planes every tick.  The caller
# contract matches the donated-read analysis rule (docs/ANALYSIS.md): the
# first positional argument must never be read after this call.
repair_free_donated = jax.jit(_repair_free_impl, donate_argnums=(0,))


@jax.jit
def gather_repair_window(warm_carry: WarmCarry, idx: jnp.ndarray, n_open_w):
    """Gather the repair's dirty slot window out of a full-width carry.

    ``idx`` i32[S] names the global new-node slots the bounded repair may
    touch — the freed-hole slots (in ascending order), any open filler, then
    the fresh tail starting at the carry's ``n_next`` — and ``n_open_w`` is
    how many of them are open.  Returns the windowed WarmCarry (per-slot
    NodeState planes and the new-side topology columns gathered; existing
    planes and the limit budget pass through whole) plus the
    ``(fwd_sing, fwd_full, inv_full)`` [G1, Z] zone-count contribution of
    every EXCLUDED open slot, which the windowed solve adds back as constants
    (RepairPlan).  The per-class-step cost of the repair then scales with the
    window, not the fleet (docs/INCREMENTAL.md)."""
    with jax.named_scope("kc.repair.gather"):
        wc = WarmCarry(*warm_carry)
        state = NodeState(*wc.state)
        topo = TopoCounts(*wc.topo)
        n_slots = state.pod_count.shape[0]
        excl_open = jnp.ones(n_slots, dtype=bool).at[idx].set(False) & state.open_
        zone_i = state.zone.astype(jnp.int32) * excl_open.astype(jnp.int32)[:, None]
        sing = jnp.where(jnp.sum(zone_i, axis=-1, keepdims=True) == 1, zone_i, 0)
        base = (
            jnp.einsum("gn,nz->gz", topo.fwd_new, sing),
            jnp.einsum("gn,nz->gz", topo.fwd_new, zone_i),
            jnp.einsum("gn,nz->gz", topo.inv_new, zone_i),
        )
        w_state = NodeState(
            used=state.used[idx],
            kmask=state.kmask[idx],
            kdef=state.kdef[idx],
            kneg=state.kneg[idx],
            kgt=state.kgt[idx],
            klt=state.klt[idx],
            zone=state.zone[idx],
            ct=state.ct[idx],
            viable=state.viable[idx],
            ports=state.ports[idx],
            pod_count=state.pod_count[idx],
            tmpl_id=state.tmpl_id[idx],
            open_=state.open_[idx],
            n_next=jnp.asarray(n_open_w, dtype=jnp.int32),
        )
        w_topo = TopoCounts(
            fwd_ex=topo.fwd_ex,
            inv_ex=topo.inv_ex,
            fwd_new=topo.fwd_new[:, idx],
            inv_new=topo.inv_new[:, idx],
        )
        return (
            WarmCarry(state=w_state, ex_state=wc.ex_state, topo=w_topo,
                      remaining=wc.remaining),
            base,
        )


def _scatter_repair_window_impl(
    warm_carry: WarmCarry, window_carry: WarmCarry, idx: jnp.ndarray, n_open_w
) -> WarmCarry:
    """Write a windowed repair's final carry back over the full-width carry:
    per-slot planes scatter to their global slots, the existing-node state
    and limit budget are replaced whole (the repair is their only writer),
    and ``n_next`` advances by however many fresh slots the repair opened."""
    with jax.named_scope("kc.repair.scatter"):
        wc = WarmCarry(*warm_carry)
        ww = WarmCarry(*window_carry)
        gs = NodeState(*wc.state)
        ws = NodeState(*ww.state)
        gt = TopoCounts(*wc.topo)
        wt = TopoCounts(*ww.topo)
        state = NodeState(
            used=gs.used.at[idx].set(ws.used),
            kmask=gs.kmask.at[idx].set(ws.kmask),
            kdef=gs.kdef.at[idx].set(ws.kdef),
            kneg=gs.kneg.at[idx].set(ws.kneg),
            kgt=gs.kgt.at[idx].set(ws.kgt),
            klt=gs.klt.at[idx].set(ws.klt),
            zone=gs.zone.at[idx].set(ws.zone),
            ct=gs.ct.at[idx].set(ws.ct),
            viable=gs.viable.at[idx].set(ws.viable),
            ports=gs.ports.at[idx].set(ws.ports),
            pod_count=gs.pod_count.at[idx].set(ws.pod_count),
            tmpl_id=gs.tmpl_id.at[idx].set(ws.tmpl_id),
            open_=gs.open_.at[idx].set(ws.open_),
            n_next=gs.n_next + (ws.n_next - jnp.asarray(n_open_w, dtype=jnp.int32)),
        )
        topo = TopoCounts(
            fwd_ex=wt.fwd_ex,
            inv_ex=wt.inv_ex,
            fwd_new=gt.fwd_new.at[:, idx].set(wt.fwd_new),
            inv_new=gt.inv_new.at[:, idx].set(wt.inv_new),
        )
        return WarmCarry(state=state, ex_state=ww.ex_state, topo=topo,
                         remaining=ww.remaining)


scatter_repair_window = jax.jit(_scatter_repair_window_impl)
# donating twin (utils.pipeline): the FULL-WIDTH carry (first positional
# argument) is donated — the scatter writes the window back into the same
# device memory.  The window carry is NOT donated: its state planes are the
# repair outputs the (possibly still pending) decode reads.  Same caller
# contract as repair_free_donated: never read arg 0 after this call.
scatter_repair_window_donated = jax.jit(
    _scatter_repair_window_impl, donate_argnums=(0,)
)


@jax.jit
def pack_bool(arr: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., ceil(M/8)] bit-packed bools — the big [N, I] planes cross
    the device→host link packed (8× smaller) and unpack host-side with
    np.unpackbits."""
    with jax.named_scope("kc.finish"):
        m = arr.shape[-1]
        pad = (-m) % 8
        if pad:
            arr = jnp.pad(arr, [(0, 0)] * (arr.ndim - 1) + [(0, pad)])
        grouped = arr.reshape(arr.shape[:-1] + (-1, 8)).astype(jnp.uint8)
        weights = jnp.asarray([128, 64, 32, 16, 8, 4, 2, 1], dtype=jnp.uint8)
        return jnp.sum(grouped * weights, axis=-1, dtype=jnp.uint8)


def unpack_bool(packed: np.ndarray, m: int) -> np.ndarray:
    """Host-side inverse of pack_bool."""
    bits = np.unpackbits(packed, axis=-1)
    return bits[..., :m].astype(bool)


def node_prices(state: NodeState, it_price: jnp.ndarray,
                catalog_axis: "Optional[str]" = None) -> jnp.ndarray:
    """f32[N]: min over (viable instance type, allowed zone, allowed ct) of
    offering price; +inf when no offering, 0 for closed slots.

    ``catalog_axis``: inside a shard_map body with the catalog sharded, the
    viable/price planes are local I-shards — the min finishes with an exact
    cross-shard ``pmin`` (parallel.mesh lane sweep)."""
    # price[i, z, ct] -> restrict to node's viable/zone/ct masks
    allowed = (
        state.viable[:, :, None, None]
        & state.zone[:, None, :, None]
        & state.ct[:, None, None, :]
    )
    priced = jnp.where(allowed, it_price[None, :, :, :], jnp.inf)
    best = jnp.min(priced, axis=(1, 2, 3))
    if catalog_axis is not None:
        best = jax.lax.pmin(best, catalog_axis)
    return jnp.where(state.open_ & (state.pod_count > 0), best, 0.0)


def snapshot_features(snapshot) -> SnapshotFeatures:
    """The snapshot's static phase plan, normalized.  Snapshots encoded before
    the features field existed (or built by hand in tests) degrade to the
    all-on plan, optionally narrowed by the legacy has_required_zonal_anti
    flag — widening is always sound (SnapshotFeatures docstring)."""
    f = getattr(snapshot, "features", None)
    if f is None:
        return ALL_FEATURES._replace(
            required_zone_anti=bool(getattr(snapshot, "has_required_zonal_anti", True))
        ).canonical()
    return SnapshotFeatures(*f).canonical()


def features_with_existing(snapshot, ex_static) -> SnapshotFeatures:
    """snapshot_features refined by the existing-node planes: the volume-limit
    family only binds when some node carries a finite CSI attach limit —
    encode_snapshot cannot see the node planes, so solve-time callers that
    have them (TPUSolver, the consolidation sweeps) refine the flag here."""
    f = snapshot_features(snapshot)
    if ex_static is not None and bool(
        np.any(np.asarray(ex_static.vol_limit) < UNLIMITED)
    ):
        f = f._replace(volume_limits=True)
    return f


def solve(snapshot: EncodedSnapshot, n_slots: int = 0,
          mesh_axes="auto") -> SolveOutputs:
    """Run the kernel on an encoded snapshot.  ``n_slots`` defaults to a
    rounded estimate; if slots run out (failed>0 with n_next==n_slots) the
    caller should retry with more (solver.tpu handles this).  ``mesh_axes``
    rides through to compilecache.run_solve: ``"auto"`` (default) follows
    KC_SOLVER_MESH onto the sharded dispatch path, ``None`` pins the
    single-device program (parity baselines)."""
    from karpenter_core_tpu import tracing
    from karpenter_core_tpu.utils import compilecache

    with tracing.span("prepare", classes=len(snapshot.classes)):
        if n_slots <= 0:
            n_slots = estimate_slots(snapshot)
        host_cls, host_statics, key_has_bounds = prepare_host(snapshot)
    return compilecache.run_solve(
        host_cls, host_statics, n_slots, key_has_bounds,
        n_passes=snapshot.scan_passes,
        features=snapshot_features(snapshot),
        mesh_axes=mesh_axes,
    )


def sync_outputs(outputs: SolveOutputs) -> SolveOutputs:
    """Block until the device solve behind ``outputs`` has finished.

    The solve/decode stage split: ``solve()`` returns lazily (device compute
    still in flight) and decode's batched fetch is normally the first sync
    point, so a naive ``t(solve) + t(decode)`` measurement fuses device
    compute into the decode number.  Callers that need the split call this
    between the two so device compute lands in the solve stage and decode
    measures only transfer + host expansion.  Production paths deliberately
    do NOT sync here: skipping it saves one device→host round trip.  The
    barrier runs under the watchdog (utils/watchdog.py): a device that went
    quiet raises a bounded SolveTimeout instead of blocking forever."""
    from karpenter_core_tpu.utils import watchdog

    watchdog.run("solve.sync", jax.block_until_ready, outputs)
    return outputs


def prepare(snapshot: EncodedSnapshot):
    """Device-ready kernel inputs: (class_tensors, statics_arrays,
    key_has_bounds)."""
    cls, statics_arrays, key_has_bounds = prepare_host(snapshot)
    cls, statics_arrays = jax.device_put((cls, statics_arrays))
    return cls, statics_arrays, key_has_bounds


def prepare_host(snapshot: EncodedSnapshot):
    """Kernel input pytrees still on host (numpy) — same shapes/dtypes as
    prepare().  Callers that want to overlap the device upload with the
    compile pass these to
    compilecache.solve_callable and device_put on a separate thread."""
    cls = ClassTensors(
        mask=snapshot.cls_mask,
        defined=snapshot.cls_defined,
        negative=snapshot.cls_negative,
        gt=snapshot.cls_gt,
        lt=snapshot.cls_lt,
        zone=snapshot.cls_zone,
        ct=snapshot.cls_ct,
        it=snapshot.cls_it,
        requests=snapshot.cls_requests,
        count=snapshot.cls_count,
        tol=snapshot.cls_tol,
        ports=snapshot.cls_ports,
        groups=snapshot.cls_groups,
        relax_next=snapshot.cls_relax_next,
        anti_soft=snapshot.cls_anti_soft,
        root=snapshot.cls_root,
        member_idx=member_index(snapshot.grp_member),
    )
    it_t = mask_ops.ReqTensor(
        snapshot.it_mask,
        snapshot.it_defined,
        snapshot.it_negative,
        snapshot.it_gt,
        snapshot.it_lt,
    )
    tmpl_t = mask_ops.ReqTensor(
        snapshot.tmpl_mask,
        snapshot.tmpl_defined,
        snapshot.tmpl_negative,
        snapshot.tmpl_gt,
        snapshot.tmpl_lt,
    )
    statics_arrays = StaticArrays(
        it=it_t,
        it_alloc=snapshot.it_alloc,
        it_avail=snapshot.it_avail,
        tmpl=tmpl_t,
        tmpl_zone=snapshot.tmpl_zone,
        tmpl_ct=snapshot.tmpl_ct,
        tmpl_it=snapshot.tmpl_it,
        tmpl_daemon=snapshot.tmpl_daemon,
        tmpl_limits0=snapshot.tmpl_limits,
        it_capacity=snapshot.it_capacity,
        valid=snapshot.valid,
        is_custom=snapshot.is_custom,
        vocab_ints=snapshot.vocab_ints,
        grp_skew=snapshot.grp_skew,
        grp_is_zone=snapshot.grp_is_zone,
        grp_is_anti=snapshot.grp_is_anti,
        grp_member=snapshot.grp_member,
    )
    key_has_bounds = tuple(
        bool(np.isfinite(snapshot.cls_gt[:, k]).any() or np.isfinite(snapshot.cls_lt[:, k]).any()
             or np.isfinite(snapshot.it_gt[:, k]).any() or np.isfinite(snapshot.it_lt[:, k]).any()
             or np.isfinite(snapshot.tmpl_gt[:, k]).any() or np.isfinite(snapshot.tmpl_lt[:, k]).any())
        for k in range(snapshot.valid.shape[0])
    )
    return cls, statics_arrays, key_has_bounds


# the longest member list a class step walks row by row.  On a v5e a member
# costs a step 6 us (its row of fwd_ex and of fwd_new, each an in-place
# update), the whole-plane record and derivation 70 us at [4 097, 512]: with
# every class in twelve groups the two forms cost the same (PERF.md §6, PR 32)
ROW_LIST_MAX = 12


def member_index(grp_member: np.ndarray) -> np.ndarray:
    """i32[C, M]: row c lists the groups g with ``grp_member[c, g]`` in index
    order, then the dummy group G1 - 1.  M is ``bucket`` of the largest member
    count (floor 8) — a shape, so it rides the compile key by itself and
    nearby batches share an executable — or 0 where that passes
    ``ROW_LIST_MAX``: no lists are kept, and the step takes the planes whole
    (``_class_step``)."""
    member = np.asarray(grp_member, dtype=bool)
    n_classes, g1 = member.shape
    no_lists = np.zeros((n_classes, 0), dtype=np.int32)
    if np.count_nonzero(member) > n_classes * ROW_LIST_MAX:
        return no_lists  # some class must pass the limit: skip the index pass
    # row-major, so each class's groups come out ascending (one flat pass:
    # np.nonzero on the 2-D plane is ten times slower at [5 467, 3 121])
    flat = np.flatnonzero(member)
    rows = flat // g1
    counts = np.bincount(rows, minlength=n_classes)
    width = bucket(int(counts.max(initial=0)))
    if width > ROW_LIST_MAX:
        return no_lists
    idx = np.full((n_classes, width), g1 - 1, dtype=np.int32)
    idx[rows, np.arange(rows.size) - np.repeat(np.cumsum(counts) - counts, counts)] = (
        flat - rows * g1
    )
    return idx


def _distinct_rows(*planes: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(ids i64[C], first i64[U]): rows that are byte-identical in every given
    [C, ...] plane share an id, numbered in order of first appearance, and
    ``first[u]`` is the first row of id ``u``."""
    n = planes[0].shape[0]
    rows = np.concatenate(
        [np.ascontiguousarray(p).reshape(n, -1).view(np.uint8) for p in planes],
        axis=1,
    )
    seen: dict = {}
    ids = np.fromiter(
        (seen.setdefault(r.tobytes(), len(seen)) for r in rows),
        dtype=np.int64, count=n,
    )
    return ids, np.unique(ids, return_index=True)[1]


def estimate_slots(snapshot: EncodedSnapshot) -> int:
    """The node slots N the scan is compiled for: the nodes this batch can
    open, read off the snapshot's planes, rounded up to a power of two.

    A scan step rewrites the whole NodeState, so it costs in proportion to N
    whether a slot is used or not; an N too small costs one more solve (the
    callers double N while ``TPUSolver.fetch_exhausted``), never a pod.  So
    the terms follow what ``_phase`` / ``committal_block`` can open and no
    more.  With ``share[c] = count[c] / pods of c that fit its first viable
    template's roomiest type``:

    - **resources**: classes whose requirement planes are identical certainly
      merge onto one another's nodes, and every fill takes the emptiest open
      node first, so such a *pool* opens ``ceil(sum of its shares)`` nodes —
      one round-up a pool, not one a class.  Classes with different planes
      may be unable to share a node (disjoint selectors, taints): each such
      pool rounds up on its own.  That is read from the planes, not a flag.
    - **zone phases**: a phase restricted to zone z fills the open nodes whose
      zone mask still admits z — whichever class or group committed them —
      emptiest first, and a fresh node it opens is the first candidate of the
      next class placed in z.  So what zone committal strands is one partial
      node a zone *per pool*, not per class nor per topology group: ``Z`` for
      each pool that holds a class with a zone phase (spread, affinity, anti).
    - **hostname caps**: a hostname spread (``skew`` a node) or anti-affinity
      (one a node) caps the group's own members (classes that own the group
      and match its selector), so group g needs ``ceil(members / cap)`` nodes
      however small the pods; host ports cap a port's users at one a node the
      same way.  Groups that can share nodes take the **largest** need, not
      the sum: the nodes the largest group opened (one pod each) are open to
      every other group and to every uncapped class.  Groups that **exclude**
      one another — some class capped by one carries the label an
      anti-affinity term of a class capped by the other selects — cannot
      share a node, and their needs **add**.
    - the three **add**: full nodes cannot take the capped pods, so a group
      that arrives after R nodes have filled still opens its own.

    Allocatable is taken before daemonset overhead; the constant 16 and the
    power of two are the headroom.  Existing nodes are NOT counted (unchanged
    by PR 27, which first measured it): the estimate sizes the new-node slots
    as if the cluster were empty, so a backlog that a live cluster absorbs
    whole still scans over every slot an empty one would open — at 10 000
    pending pods against 5 000 nodes 60 % full, 512 asked and 0 used (the
    ``n_slots`` / ``slots_used`` counters on ``prepare`` / ``decode``).  Safe
    (too many slots cost time, never a pod); subtracting the intake of the
    existing nodes is a ``perf_opt`` of its own, with that cell to claim in.  A
    one-class or few-pod wobble moves the sum by a node or two, well inside
    the power of two, and ``compilecache.snap_slots`` absorbs a fall."""
    count = np.asarray(snapshot.cls_count, dtype=np.int64)
    n_classes = count.shape[0]
    total = 16
    if n_classes and snapshot.tmpl_it.shape[0] and count.any():
        rows = np.arange(n_classes)
        live = count > 0
        groups = np.asarray(snapshot.cls_groups)
        member = np.asarray(snapshot.grp_member)
        n_groups = member.shape[1] - 1  # the last row is the dummy "none"

        # pods of a class on a fresh node: the first template that tolerates
        # it and has a type it fits (``_phase``'s t_star), at its best type
        kind, first = _distinct_rows(snapshot.cls_requests, snapshot.cls_it)
        size = snapshot.cls_requests[first][:, None, :]  # [U, 1, R]
        with np.errstate(divide="ignore", invalid="ignore"):
            fit = np.where(
                size > 0,
                np.floor((snapshot.it_alloc[None] + 1e-4) / np.maximum(size, 1e-9)),
                np.inf,
            ).min(axis=-1)  # [U, I]
        fit = np.where(snapshot.cls_it[first], np.minimum(fit, UNLIMITED), 0)
        catalog, first_tmpl = _distinct_rows(snapshot.tmpl_it)  # mostly one
        per_catalog = np.stack(
            [np.where(snapshot.tmpl_it[t], fit, 0).max(axis=1) for t in first_tmpl],
            axis=1,
        )  # [U, catalogs]
        per_tmpl = np.where(snapshot.cls_tol, per_catalog[kind][:, catalog], 0)
        best = per_tmpl[rows, np.argmax(per_tmpl > 0, axis=1)]  # 0: fits nowhere
        share = np.where(best > 0, count / np.maximum(best, 1), 0.0)

        pool, _ = _distinct_rows(
            snapshot.cls_mask, snapshot.cls_defined, snapshot.cls_negative,
            snapshot.cls_gt, snapshot.cls_lt, snapshot.cls_zone,
            snapshot.cls_ct, snapshot.cls_it, snapshot.cls_tol,
        )
        total += int(np.ceil(np.bincount(pool, weights=share) - 1e-6).sum())
        zoned = live & (groups[:, [0, 2, 4]] < n_groups).any(axis=1)
        total += snapshot.cls_zone.shape[1] * np.unique(pool[zoned]).size

        need = np.zeros(n_groups + 1)
        capped = np.zeros((n_classes, n_groups + 1), dtype=bool)
        for slot, cap in ((1, np.maximum(snapshot.grp_skew, 1)), (5, 1)):
            own = groups[:, slot]
            self_member = member[rows, own] & live  # the dummy row matches none
            capped[rows, own] |= self_member
            need += np.ceil(
                np.bincount(own, weights=self_member * count, minlength=n_groups + 1)
                / cap
            )
        need, capped = need[:n_groups], capped[:, :n_groups]
        exclusive = np.zeros(n_groups, dtype=bool)
        anti = groups[:, 5]
        owners = live & (anti < n_groups)
        if owners.any():
            # repels[a, b]: b carries the label a's hostname anti term selects
            repels = member[:, anti].T & owners[:, None] & live[None, :]
            apart = (repels | repels.T).astype(np.float32)
            units = capped.astype(np.float32)
            clash = units.T @ apart @ units > 0  # [G, G]
            np.fill_diagonal(clash, False)  # within a group: its own cap
            exclusive = clash.any(axis=1)
        ports = count @ np.asarray(snapshot.cls_ports, dtype=np.int64)
        total += int(
            need[exclusive].sum()
            + max(need[~exclusive].max(initial=0), ports.max(initial=0))
        )
    estimate = int(2 ** np.ceil(np.log2(max(total, 16))))
    # hysteresis at the shared derivation point so every caller (provisioning
    # solve, consolidation sweep, mesh studies) reuses covering executables
    from karpenter_core_tpu.utils import compilecache

    return compilecache.snap_slots(estimate)

# -- shape-bucket padding -----------------------------------------------------
#
# The compile cache keys on every input shape, so a one-class change in the
# pod mix (or one node joining the cluster) would recompile an identical
# program.  Steady-state reconciles instead pad the variable axes -- C classes,
# E existing nodes, G topology groups, P port pairs, K keys, V vocabulary
# values, D CSI drivers -- up to a bucket grid (powers of two and 1.5x powers
# of two, <=33% waste).  Padding is semantically invisible:
#
#   - padded classes have count=0: every phase is a lax.cond no-op and the
#     record step adds zero to all topology counts
#   - padded existing nodes are closed (open_=False): never eligible, never
#     seed counts
#   - padded groups clone the dummy "none" row (skew=UNLIMITED, no members);
#     the class sentinel index is remapped to the new last row
#   - padded keys are undefined on every side: Compatible/Intersects skip them
#   - padded value slots sit before the "unseen" slot with mask=False and
#     valid=False: no real value maps to them, no reduction counts them
#   - padded drivers have vol_limit=UNLIMITED and zero usage
#
# The reference has no analog (Go recompiles nothing); this is TPU operational
# parity, same motive as utils.compilecache.


def pad_catalog(cls, statics_arrays, multiple: int, it_price=None):
    """Pad the instance-type (I) axis of prepared host planes to a multiple of
    the mesh's catalog axis with INERT types: no availability, zero
    allocatable/capacity, excluded from every template and class mask, and
    (when a price sheet rides along) +inf price.  Padded columns can never be
    viable, so the padded solve is bit-identical to the unpadded one on the
    real columns — the shard_map dispatcher (parallel.mesh) requires the
    sharded axis to divide evenly.  Production snapshots are already encoded
    shard-aligned (models.snapshot.encode_snapshot ``catalog_pad_multiple``);
    this is the safety net for planes prepared outside that path.

    Returns (cls, statics_arrays[, it_price]) unchanged when the axis already
    divides."""
    sa = StaticArrays(*statics_arrays)
    i0 = np.asarray(sa.it_alloc).shape[0]
    i_new = -(-max(i0, 1) // max(multiple, 1)) * max(multiple, 1)
    if i_new == i0:
        return (cls, sa) if it_price is None else (cls, sa, it_price)
    it = sa.it
    it_p = mask_ops.ReqTensor(
        mask=_pad_axis(np.asarray(it.mask), 0, i_new, False),
        defined=_pad_axis(np.asarray(it.defined), 0, i_new, False),
        negative=_pad_axis(np.asarray(it.negative), 0, i_new, False),
        gt=_pad_axis(np.asarray(it.gt), 0, i_new, -np.inf),
        lt=_pad_axis(np.asarray(it.lt), 0, i_new, np.inf),
    )
    sa = sa._replace(
        it=it_p,
        it_alloc=_pad_axis(np.asarray(sa.it_alloc), 0, i_new, 0.0),
        it_avail=_pad_axis(np.asarray(sa.it_avail), 0, i_new, False),
        tmpl_it=_pad_axis(np.asarray(sa.tmpl_it), 1, i_new, False),
        it_capacity=_pad_axis(np.asarray(sa.it_capacity), 0, i_new, 0.0),
    )
    cls = cls._replace(it=_pad_axis(np.asarray(cls.it), 1, i_new, False))
    if it_price is None:
        return cls, sa
    return cls, sa, _pad_axis(np.asarray(it_price), 0, i_new, np.inf)


def bucket(n: int, floor: int = 8) -> int:
    """Smallest grid value >= max(n, floor); the grid is the powers of two
    and 1.5x powers of two starting at 2 (2, 3, 4, 6, 8, 12, ...)."""
    target = max(int(n), int(floor), 2)
    b = 2
    while b < target:
        b = b * 3 // 2 if (b & (b - 1)) == 0 else (b // 3) * 4
    return b


def _pad_axis(a: np.ndarray, axis: int, target: int, value) -> np.ndarray:
    cur = a.shape[axis]
    if cur >= target:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, target - cur)
    return np.pad(a, widths, constant_values=value)


def _widen_mask(mask: np.ndarray, v_new: int) -> np.ndarray:
    """Insert always-False value slots before the trailing "unseen" slot."""
    v = mask.shape[-1] - 1
    if v >= v_new:
        return mask
    block = np.zeros(mask.shape[:-1] + (v_new - v,), dtype=mask.dtype)
    return np.concatenate([mask[..., :v], block, mask[..., v:]], axis=-1)


def _pad_req(t: mask_ops.ReqTensor, k_new: int, v_new: int) -> mask_ops.ReqTensor:
    """Pad a ReqTensor's K axis (undefined keys, mask=ones) and vocabulary
    width (False slots before "unseen")."""
    mask = _widen_mask(np.asarray(t.mask), v_new)
    mask = _pad_axis(mask, -2, k_new, True)
    return mask_ops.ReqTensor(
        mask=mask,
        defined=_pad_axis(np.asarray(t.defined), -1, k_new, False),
        negative=_pad_axis(np.asarray(t.negative), -1, k_new, False),
        gt=_pad_axis(np.asarray(t.gt), -1, k_new, -np.inf),
        lt=_pad_axis(np.asarray(t.lt), -1, k_new, np.inf),
    )


def pad_planes(cls, statics_arrays, key_has_bounds, ex_state=None, ex_static=None):
    """Bucket-pad kernel inputs (host numpy pytrees from prepare_host /
    TPUSolver.encode_existing).  Returns (cls, statics_arrays, key_has_bounds,
    ex_state, ex_static) with stable shapes across nearby problem sizes."""
    sa = StaticArrays(*statics_arrays)

    c_old = cls.count.shape[0]
    k_old = sa.valid.shape[0]
    v_old = sa.valid.shape[1] - 1
    g1_old = sa.grp_skew.shape[0]
    p_old = cls.ports.shape[-1]

    c_new = bucket(c_old)
    k_new = bucket(k_old)
    v_new = bucket(v_old)
    g1_new = bucket(g1_old - 1, floor=4) + 1
    p_new = bucket(p_old, floor=4)

    groups = np.asarray(cls.groups)
    groups = np.where(groups >= g1_old - 1, g1_new - 1, groups)
    member_idx = np.asarray(cls.member_idx)
    member_idx = np.where(member_idx >= g1_old - 1, g1_new - 1, member_idx).astype(np.int32)
    cls_t = _pad_req(
        mask_ops.ReqTensor(cls.mask, cls.defined, cls.negative, cls.gt, cls.lt),
        k_new, v_new,
    )
    cls = ClassTensors(
        mask=_pad_axis(cls_t.mask, 0, c_new, True),
        defined=_pad_axis(cls_t.defined, 0, c_new, False),
        negative=_pad_axis(cls_t.negative, 0, c_new, False),
        gt=_pad_axis(cls_t.gt, 0, c_new, -np.inf),
        lt=_pad_axis(cls_t.lt, 0, c_new, np.inf),
        zone=_pad_axis(np.asarray(cls.zone), 0, c_new, True),
        ct=_pad_axis(np.asarray(cls.ct), 0, c_new, True),
        it=_pad_axis(np.asarray(cls.it), 0, c_new, True),
        requests=_pad_axis(np.asarray(cls.requests), 0, c_new, 0),
        count=_pad_axis(np.asarray(cls.count), 0, c_new, 0),
        tol=_pad_axis(np.asarray(cls.tol), 0, c_new, False),
        ports=_pad_axis(_pad_axis(np.asarray(cls.ports), -1, p_new, False), 0, c_new, False),
        groups=_pad_axis(groups, 0, c_new, g1_new - 1),
        relax_next=_pad_axis(np.asarray(cls.relax_next), 0, c_new, -1),
        anti_soft=_pad_axis(np.asarray(cls.anti_soft), 0, c_new, False),
        # padded rows never place (count 0), so any root value is inert
        root=_pad_axis(np.asarray(cls.root), 0, c_new, 0),
        member_idx=_pad_axis(member_idx, 0, c_new, g1_new - 1),
    )

    statics_arrays = sa._replace(
        it=_pad_req(sa.it, k_new, v_new),
        tmpl=_pad_req(sa.tmpl, k_new, v_new),
        valid=_pad_axis(_widen_mask(np.asarray(sa.valid), v_new), 0, k_new, False),
        is_custom=_pad_axis(np.asarray(sa.is_custom), 0, k_new, False),
        vocab_ints=_pad_axis(
            _pad_axis(np.asarray(sa.vocab_ints), -1, v_new, np.inf), 0, k_new, np.inf
        ),
        grp_skew=_pad_axis(np.asarray(sa.grp_skew), 0, g1_new, UNLIMITED),
        grp_is_zone=_pad_axis(np.asarray(sa.grp_is_zone), 0, g1_new, False),
        grp_is_anti=_pad_axis(np.asarray(sa.grp_is_anti), 0, g1_new, False),
        grp_member=_pad_axis(
            _pad_axis(np.asarray(sa.grp_member), -1, g1_new, False), 0, c_new, False
        ),
    )
    key_has_bounds = tuple(key_has_bounds) + (False,) * (k_new - k_old)

    if ex_state is not None:
        e_old = ex_state.pod_count.shape[0]
        d_old = ex_state.vol_used.shape[-1]
        # floor 8: node churn below eight existing nodes must not change the
        # plane shape (the bucket grid's 4->6->8 steps are too fine there)
        e_new = bucket(e_old, floor=8)
        d_new = bucket(d_old, floor=2)
        ex_req = _pad_req(
            mask_ops.ReqTensor(
                ex_state.kmask, ex_state.kdef, ex_state.kneg, ex_state.kgt, ex_state.klt
            ),
            k_new, v_new,
        )
        ex_state = ExistingState(
            used=_pad_axis(np.asarray(ex_state.used), 0, e_new, 0),
            kmask=_pad_axis(ex_req.mask, 0, e_new, True),
            kdef=_pad_axis(ex_req.defined, 0, e_new, False),
            kneg=_pad_axis(ex_req.negative, 0, e_new, False),
            kgt=_pad_axis(ex_req.gt, 0, e_new, -np.inf),
            klt=_pad_axis(ex_req.lt, 0, e_new, np.inf),
            zone=_pad_axis(np.asarray(ex_state.zone), 0, e_new, True),
            ct=_pad_axis(np.asarray(ex_state.ct), 0, e_new, True),
            ports=_pad_axis(_pad_axis(np.asarray(ex_state.ports), -1, p_new, False), 0, e_new, False),
            vol_used=_pad_axis(_pad_axis(np.asarray(ex_state.vol_used), -1, d_new, 0), 0, e_new, 0),
            pod_count=_pad_axis(np.asarray(ex_state.pod_count), 0, e_new, 0),
            open_=_pad_axis(np.asarray(ex_state.open_), 0, e_new, False),
        )
        ex_static = ExistingStatic(
            alloc=_pad_axis(np.asarray(ex_static.alloc), 0, e_new, 0),
            init=_pad_axis(np.asarray(ex_static.init), 0, e_new, False),
            tol=_pad_axis(_pad_axis(np.asarray(ex_static.tol), -1, e_new, False), 0, c_new, False),
            grp_node_member=_pad_axis(
                _pad_axis(np.asarray(ex_static.grp_node_member), -1, e_new, 0), 0, g1_new, 0
            ),
            grp_node_owner=_pad_axis(
                _pad_axis(np.asarray(ex_static.grp_node_owner), -1, e_new, 0), 0, g1_new, 0
            ),
            node_capacity=_pad_axis(np.asarray(ex_static.node_capacity), 0, e_new, 0),
            node_tmpl=_pad_axis(np.asarray(ex_static.node_tmpl), 0, e_new, 0),
            node_owned=_pad_axis(np.asarray(ex_static.node_owned), 0, e_new, False),
            vol_limit=_pad_axis(
                _pad_axis(np.asarray(ex_static.vol_limit), -1, d_new, UNLIMITED), 0, e_new, UNLIMITED
            ),
            cls_vol_add=_pad_axis(
                _pad_axis(
                    _pad_axis(np.asarray(ex_static.cls_vol_add), -1, d_new, 0), -2, e_new, 0
                ),
                0, c_new, 0,
            ),
            cls_vol_per_pod=_pad_axis(
                _pad_axis(np.asarray(ex_static.cls_vol_per_pod), -1, d_new, 0), 0, c_new, 0
            ),
        )
    return cls, statics_arrays, key_has_bounds, ex_state, ex_static
