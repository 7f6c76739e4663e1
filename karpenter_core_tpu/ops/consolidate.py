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
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.utils import compilecache


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
        )
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

    results = jax.vmap(one_prefix)(prefix_sizes)
    return SweepOutputs(*results)


_sweep_jit = functools.partial(
    jax.jit, static_argnames=("key_has_bounds", "n_slots", "n_passes", "features")
)(sweep)


@functools.lru_cache(maxsize=16)
def _lane_sweep_fn(mesh_axes, key_has_bounds, n_slots: int, n_passes: int,
                   features, cls_specs, statics_specs):
    """Cached jit(shard_map(...)) sweep over the 2D (catalog × lane) mesh:
    the prefix-lane axis splits across ``lane`` while each lane group shards
    the catalog planes over ``catalog`` — the production topology
    (parallel.mesh.lane_mesh_axes).  A fresh wrapper per call would defeat
    JAX's compile cache (keyed on callable identity), so the builder is
    memoized on the topology + static config (the spec pytrees are hashable
    and shape-identifying)."""
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
        P(lane), cls_specs, statics_specs, P(), P(), P(), P(), P(cat),
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
    """The production sweep entry.  On the mesh path (``mesh_axes``: a
    topology descriptor, ``"auto"`` = KC_SOLVER_MESH env via
    parallel.mesh.lane_mesh_axes, None = off) the prefix lanes shard across
    the mesh's ``lane`` axis AND each lane group shards the catalog — each
    device simulates its share of the subsets over its catalog shard, with
    one result gather plus the kernel's tiny exact collectives as the only
    cross-device traffic.  ``mesh`` (a legacy Mesh object) is honored as a
    lanes-only topology for the dryrun entry points."""
    from karpenter_core_tpu.parallel import mesh as mesh_mod

    cls, statics_arrays, key_has_bounds = solve_ops.prepare(snapshot)
    sizes = jnp.asarray(prefix_sizes)
    it_price = jnp.asarray(snapshot.it_price)
    features = compilecache.snap_features(
        solve_ops.features_with_existing(snapshot, ex_static)
    )
    if mesh is not None:
        # legacy dryrun callers pass a Mesh: shard lanes over all its devices.
        # An EXPLICIT mesh wins over the env auto-config — the dryrun must
        # test the topology it asked for, not whatever the env resolves to
        mesh_axes = ((mesh_mod.CATALOG_AXIS, 1),
                     (mesh_mod.LANE_AXIS, int(mesh.devices.size)))
    elif mesh_axes == "auto":
        mesh_axes = mesh_mod.lane_mesh_axes()
    if mesh_axes is not None:
        # the catalog split must divide I (encode pads production snapshots
        # shard-aligned; anything else falls back to lanes-only — LOUDLY,
        # because a sweep quietly idling most of the mesh is a perf bug)
        n_it = int(np.asarray(snapshot.it_alloc).shape[0])
        cat_size = int(dict(mesh_axes)[mesh_mod.CATALOG_AXIS])
        if n_it % max(cat_size, 1) != 0:
            import logging

            logging.getLogger(__name__).warning(
                "lane sweep: catalog extent %d not divisible by mesh axis "
                "%r; degrading to lanes-only (catalog unsharded)",
                n_it, mesh_axes,
            )
            mesh_axes = ((mesh_mod.CATALOG_AXIS, 1),
                         (mesh_mod.LANE_AXIS, dict(mesh_axes)[mesh_mod.LANE_AXIS]))
    if mesh_axes is not None:
        lanes = int(dict(mesh_axes)[mesh_mod.LANE_AXIS])
        pad = (-len(prefix_sizes)) % max(lanes, 1)
        if pad:
            sizes = jnp.concatenate([sizes, jnp.repeat(sizes[-1:], pad)])
        fn = _lane_sweep_fn(
            tuple(mesh_axes), key_has_bounds, n_slots, snapshot.scan_passes,
            features,
            mesh_mod.partition_specs(cls),
            mesh_mod.partition_specs(statics_arrays),
        )
        out = fn(
            sizes, cls, statics_arrays, ex_state, ex_static,
            jnp.asarray(candidate_rank), jnp.asarray(ex_cls_count),
            it_price,
        )
        if pad:
            out = SweepOutputs(*(np.asarray(plane)[: len(prefix_sizes)] for plane in out))
        return out
    return _sweep_jit(
        cls,
        statics_arrays,
        key_has_bounds,
        ex_state,
        ex_static,
        jnp.asarray(candidate_rank),
        jnp.asarray(ex_cls_count),
        sizes,
        it_price,
        n_slots=n_slots,
        n_passes=snapshot.scan_passes,
        features=features,
    )
