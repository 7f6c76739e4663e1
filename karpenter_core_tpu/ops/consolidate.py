"""Consolidation search as a parallel subset sweep on TPU.

The reference's multi-node consolidation binary-searches the first-N prefix of
disruption-sorted candidates, one full scheduling simulation per probe
(multinodeconsolidation.go:74-114).  Here every prefix size is evaluated
simultaneously: the simulation (a solve with the subset's nodes closed and
their pods re-injected) is vmapped over the prefix axis, so one device pass
answers "what is the largest set of nodes we can delete/replace" — and, unlike
binary search, it does not assume monotonic feasibility.  This is the
pmap-over-candidate-subsets search of BASELINE.json config 3.

The host wrapper (solver.consolidation) applies the price/spot validity rules
to each lane's decoded replacement and picks the largest valid prefix.
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.utils import compilecache


LANES = "sweep_lanes"  # the vmap axis over prefix sizes (solve_core's lane_axis)


class SweepOutputs(NamedTuple):
    """Per-lane (prefix size) results; leading dim S."""

    n_new: jnp.ndarray  # i32[S] new nodes the simulation opened
    failed: jnp.ndarray  # i32[S] pods that failed to schedule
    used_uninitialized: jnp.ndarray  # bool[S] relied on an uninitialized node
    new_viable: jnp.ndarray  # bool[S, M, I] replacement instance viability
    new_zone: jnp.ndarray  # bool[S, M, Z]
    new_ct: jnp.ndarray  # bool[S, M, CT]
    new_used: jnp.ndarray  # f32[S, M, R]
    new_tmpl: jnp.ndarray  # i32[S, M]
    # fleet cost of the lane's replacement nodes: sum over opened slots of
    # the cheapest surviving offering price (ops.solve.node_prices) — the
    # in-kernel half of policy-aware cost-delta consolidation (docs/POLICY.md)
    new_cost: jnp.ndarray  # f32[S]


def sweep(
    class_tensors,
    statics_arrays,
    key_has_bounds,
    ex_state: solve_ops.ExistingState,
    ex_static: solve_ops.ExistingStatic,
    candidate_rank: jnp.ndarray,  # i32[E]: position in disruption order, big=not candidate
    ex_cls_count: jnp.ndarray,  # i32[C, E]: candidate pods per class per node
    prefix_sizes: jnp.ndarray,  # i32[S]
    it_price: jnp.ndarray,  # f32[I, Z, CT] offering price sheet
    n_slots: int = 16,
    n_passes: int = 1,
    features=None,
    catalog_axis=None,
) -> SweepOutputs:
    """Simulate closing the first-k candidates for every k in prefix_sizes.

    ``catalog_axis`` (static): inside the mesh dispatcher's shard_map body
    the catalog planes are local I-shards — the per-simulation solve and the
    price reduction finish their I-axis reductions with exact collectives
    over that axis (parallel.mesh; bit-identical to unsharded)."""

    def one_prefix(k):
        with jax.named_scope("kc.sweep.seed"):
            subset = candidate_rank < k  # bool[E]
            # close the subset's nodes; the topology count seeds derive from
            # grp_node_member/owner masked by open_, so pre-existing pods on
            # removed nodes stop counting automatically (excludedPods semantics)
            ex = ex_state._replace(open_=ex_state.open_ & ~subset)
            # displaced pods join their classes
            displaced = jnp.sum(
                ex_cls_count * subset[None, :].astype(jnp.int32), axis=-1
            )  # [C]
            cls = class_tensors._replace(count=class_tensors.count + displaced)
        out = solve_ops.solve_core(
            cls, statics_arrays, n_slots, key_has_bounds, ex, ex_static,
            n_passes=n_passes, features=features, catalog_axis=catalog_axis,
            lane_axis=LANES,
        )
        with jax.named_scope("kc.sweep.reduce"):
            n_new = out.state.n_next
            failed = jnp.sum(out.failed)
            uninit = jnp.any(
                (out.assign_existing > 0) & ~ex_static.init[None, :]
            )
            prices = solve_ops.node_prices(out.state, it_price, catalog_axis)
            cost = jnp.sum(jnp.where(jnp.isfinite(prices), prices, 0.0))
        return (
            n_new,
            failed,
            uninit,
            out.state.viable,
            out.state.zone,
            out.state.ct,
            out.state.used,
            out.state.tmpl_id,
            cost,
        )

    results = jax.vmap(one_prefix, axis_name=LANES)(prefix_sizes)
    return SweepOutputs(*results)


@functools.lru_cache(maxsize=16)
def lane_sweep_fn(mesh_axes, key_has_bounds, n_slots: int, n_passes: int,
                  features, cls_specs, statics_specs):
    """Cached jit(shard_map(...)) sweep over the 2D (catalog × lane) mesh:
    the prefix-lane axis splits across ``lane`` while each lane group shards
    the catalog planes over ``catalog`` — the production topology
    (parallel.mesh.lane_mesh_axes).  A fresh wrapper per call would defeat
    JAX's compile cache (keyed on callable identity), so the builder is
    memoized on the topology + static config (``cls_specs`` /
    ``statics_specs`` are the planes' shape structs: hashable and
    shape-identifying); ``compilecache.sweep_callable`` memoizes and counts
    the result like every other executable."""
    from jax.sharding import PartitionSpec as P

    from karpenter_core_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.mesh_for(mesh_axes)
    lane, cat = mesh_mod.LANE_AXIS, mesh_mod.CATALOG_AXIS

    def body(sizes_arg, cls_arg, statics_arg, ex_state_arg, ex_static_arg,
             rank_arg, counts_arg, price_arg):
        return sweep(
            cls_arg, statics_arg, key_has_bounds, ex_state_arg, ex_static_arg,
            rank_arg, counts_arg, sizes_arg, price_arg, n_slots=n_slots,
            n_passes=n_passes, features=features, catalog_axis=cat,
        )

    in_specs = (
        P(lane), mesh_mod.partition_specs(cls_specs),
        mesh_mod.partition_specs(statics_specs), P(), P(), P(), P(), P(cat),
    )
    out_specs = SweepOutputs(
        n_new=P(lane), failed=P(lane), used_uninitialized=P(lane),
        new_viable=P(lane, None, cat), new_zone=P(lane), new_ct=P(lane),
        new_used=P(lane), new_tmpl=P(lane), new_cost=P(lane),
    )
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        # lane outputs are genuinely sharded and the catalog collectives
        # inside the body are exact — the mesh parity suite pins every
        # lane-sweep plane bit-identical to unsharded except new_cost, a
        # f32 sum whose reduction order XLA reassociates per program
        # (last-ulp only; the summands themselves are pinned exact)
        check_vma=False,
    ))


# The lane counts a sweep is compiled for.  A pass's prefix sizes are padded
# up to the next rung with repeats of the last size (a repeated lane is the
# same simulation; its outputs are dropped), so a cluster's sweeps use at most
# these two executables whatever bracket the search leaves: the coarse pass
# and every wide re-grid run the top rung, a narrow bracket the low one.  72
# at the top, not 64: a grid of 72 sizes over n candidates leaves a bracket of
# under n / 71, which one more pass of 72 closes for any n up to 5 184 — a
# cluster of 5 000 nodes is searched in exactly two passes whatever its
# answer, where 64 lanes took three or four.
LANE_LADDER = (8, 72)
NOT_A_CANDIDATE = 1 << 30  # candidate_rank of a node no prefix closes


def lane_rung(n_sizes: int, multiple: int = 1) -> int:
    """The padded lane count for ``n_sizes`` prefix sizes: the ladder's first
    rung that holds them (their own count past the top rung — a library
    caller's), rounded up to the mesh's lane axis."""
    lanes = next((r for r in LANE_LADDER if n_sizes <= r), n_sizes)
    return -(-lanes // max(multiple, 1)) * max(multiple, 1)


class SweepPlanes(NamedTuple):
    """One request's sweep inputs: padded on the ladder ``/SolveClasses``
    pads a solve with existing nodes on (``ops.solve.pad_planes``) and
    device-resident, so that every pass of the search ships only its lane
    sizes."""

    args: tuple  # (cls, statics_arrays, ex_state, ex_static, rank, counts, it_price)
    key_has_bounds: tuple
    n_passes: int
    features: tuple
    mesh_axes: Optional[tuple]
    sig: tuple  # compilecache.leaf_sig(args): the shape half of every key


def _resolve_lane_mesh(mesh, mesh_axes, n_it: int):
    from karpenter_core_tpu.parallel import mesh as mesh_mod

    if mesh is not None:
        # legacy dryrun callers pass a Mesh: shard lanes over all its devices.
        # An EXPLICIT mesh wins over the env auto-config — the dryrun must
        # test the topology it asked for, not whatever the env resolves to
        return ((mesh_mod.CATALOG_AXIS, 1),
                (mesh_mod.LANE_AXIS, int(mesh.devices.size)))
    if mesh_axes == "auto":
        mesh_axes = mesh_mod.lane_mesh_axes()
    if mesh_axes is None:
        return None
    # the catalog split must divide I (encode pads production snapshots
    # shard-aligned; anything else falls back to lanes-only — LOUDLY,
    # because a sweep quietly idling most of the mesh is a perf bug)
    cat_size = int(dict(mesh_axes)[mesh_mod.CATALOG_AXIS])
    if n_it % max(cat_size, 1) != 0:
        import logging

        logging.getLogger(__name__).warning(
            "lane sweep: catalog extent %d not divisible by mesh axis "
            "%r; degrading to lanes-only (catalog unsharded)",
            n_it, mesh_axes,
        )
        return ((mesh_mod.CATALOG_AXIS, 1),
                (mesh_mod.LANE_AXIS, dict(mesh_axes)[mesh_mod.LANE_AXIS]))
    return tuple(mesh_axes)


def prepare_sweep(
    snapshot,
    ex_state,
    ex_static,
    candidate_rank: np.ndarray,
    ex_cls_count: np.ndarray,
    mesh=None,
    mesh_axes="auto",
) -> SweepPlanes:
    """Pad and upload a sweep's planes once per request.  C, E (and K, V, G,
    P, D) go on ``pad_planes``' bucket ladder — a padded class row has count 0
    and no displaced pods, a padded node is closed and no candidate — so a
    cluster that gains or loses a few nodes keeps its executables."""
    cls, statics_arrays, key_has_bounds = solve_ops.prepare_host(snapshot)
    rank = np.asarray(candidate_rank, dtype=np.int32)
    counts = np.asarray(ex_cls_count, dtype=np.int32)
    if os.environ.get("KC_TPU_SHAPE_BUCKETS", "1") != "0":
        cls, statics_arrays, key_has_bounds, ex_state, ex_static = solve_ops.pad_planes(
            cls, statics_arrays, key_has_bounds, ex_state, ex_static
        )
        c_new, e_new = ex_static.tol.shape
        rank = solve_ops._pad_axis(rank, 0, e_new, NOT_A_CANDIDATE)
        counts = solve_ops._pad_axis(
            solve_ops._pad_axis(counts, 1, e_new, 0), 0, c_new, 0
        )
    features = compilecache.snap_features(
        solve_ops.features_with_existing(snapshot, ex_static)
    )
    mesh_axes = _resolve_lane_mesh(
        mesh, mesh_axes, int(np.asarray(snapshot.it_alloc).shape[0])
    )
    args = (cls, statics_arrays, ex_state, ex_static, rank, counts,
            np.asarray(snapshot.it_price))
    sig = compilecache.leaf_sig(args)
    return SweepPlanes(
        args=jax.device_put(args), key_has_bounds=tuple(key_has_bounds),
        n_passes=int(snapshot.scan_passes), features=tuple(features),
        mesh_axes=mesh_axes, sig=sig,
    )


def run_sweep(
    snapshot,
    ex_state,
    ex_static,
    candidate_rank: np.ndarray,
    ex_cls_count: np.ndarray,
    prefix_sizes: np.ndarray,
    n_slots: int = 16,
    mesh=None,
    mesh_axes="auto",
) -> SweepOutputs:
    """One pass from unprepared inputs: ``prepare_sweep`` + ``sweep_pass``.

    On the mesh path (``mesh_axes``: a topology descriptor, ``"auto"`` =
    KC_SOLVER_MESH env via parallel.mesh.lane_mesh_axes, None = off) the
    prefix lanes shard across the mesh's ``lane`` axis AND each lane group
    shards the catalog — each device simulates its share of the subsets over
    its catalog shard, with one result gather plus the kernel's tiny exact
    collectives as the only cross-device traffic.  ``mesh`` (a legacy Mesh
    object) is honored as a lanes-only topology for the dryrun entry points."""
    planes = prepare_sweep(
        snapshot, ex_state, ex_static, candidate_rank, ex_cls_count,
        mesh=mesh, mesh_axes=mesh_axes,
    )
    return sweep_pass(planes, prefix_sizes, n_slots)


def sweep_key(planes: SweepPlanes, lanes: int, n_slots: int) -> tuple:
    """The identity of one sweep executable — shapes, lanes, static config,
    mesh: what the watchdog keys a pass's dispatch and fetch deadlines on."""
    return (planes.sig, int(lanes), int(n_slots), planes.n_passes,
            planes.features, planes.mesh_axes)


def sweep_pass(planes: SweepPlanes, prefix_sizes: np.ndarray,
               n_slots: int = 16) -> SweepOutputs:
    """The production sweep entry: one pass over ``prefix_sizes``, its planes
    fetched to the host (one lane per size asked for).

    Dispatched as a solve is: the executable comes from ``compilecache``
    (``sweep_callable`` — exported, memoized, counted in ``builds``) at the
    planes' bucketed shapes and a lane count off ``LANE_LADDER``; dispatch and
    fetch each run under the watchdog, keyed by the executable's identity
    (shapes, lanes, slots, mesh), so a two-lane refine pass never sets a
    72-lane pass's deadline and a program yet to compile gets the cold budget;
    the ``dispatch`` / ``solve`` / ``decode.fetch`` spans are a solve's."""
    from karpenter_core_tpu import tracing
    from karpenter_core_tpu.parallel import mesh as mesh_mod
    from karpenter_core_tpu.utils import pipeline as pipeline_mod
    from karpenter_core_tpu.utils import watchdog

    sizes = np.asarray(prefix_sizes, dtype=np.int32)
    n_sizes = len(sizes)
    lanes = lane_rung(
        n_sizes,
        int(dict(planes.mesh_axes)[mesh_mod.LANE_AXIS]) if planes.mesh_axes else 1,
    )
    sizes = np.concatenate([sizes, np.repeat(sizes[-1:], lanes - n_sizes)])
    key = sweep_key(planes, lanes, n_slots)

    def dispatch():
        # "dispatch" covers the executable lookup (a build on first use) and
        # the async launch; "solve" blocks on the outputs (tracing only) so
        # device compute is the solve's, as in compilecache.run_solve
        with tracing.span("dispatch", n_passes=planes.n_passes, lanes=lanes,
                          mesh=repr(planes.mesh_axes) if planes.mesh_axes else None):
            fn = compilecache.sweep_callable(
                planes.args, lanes, n_slots, planes.key_has_bounds,
                planes.n_passes, planes.features, planes.mesh_axes,
            )
            return fn(sizes, *planes.args)

    out = watchdog.run("consolidate.dispatch", dispatch, key=key)
    if tracing.enabled():
        with tracing.span("solve", sync=out):
            pass
    # ONE batched device→host fetch of every sweep plane (async copies
    # started up front); the barrier budgets under its own watchdog site (a
    # hung lane sweep must not wedge the deprovisioner — it surfaces as a
    # SolveTimeout the breaker counts)
    with tracing.span("decode.fetch"):
        out = pipeline_mod.fetch_tree(out, site="consolidate.sweep", key=key)
    return SweepOutputs(*(np.asarray(plane)[:n_sizes] for plane in out))
