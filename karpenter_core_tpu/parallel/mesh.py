"""Device-mesh parallelism for the solver — the PRODUCTION dispatch layer.

The reference has no collective layer (its "distributed backend" is the
kube-apiserver watch plane, SURVEY.md §2.11/§5.8); the TPU-native design adds
one wherever the problem is data-parallel:

  - **Sharded production solve** (the default path on >1 device,
    ``KC_SOLVER_MESH``): every provisioning/repair solve runs as a
    ``shard_map`` over the device mesh with the CATALOG (instance-type) axis
    sharded and the pod/class planes replicated.  Per class step the hot
    planes are [N slots, I types] with per-I independence; the kernel's few
    I-axis reductions finish with exact pmax/psum collectives
    (ops.solve._imax/_isum), so the sharded solve is BIT-IDENTICAL to the
    single-device solve — and a 1-device mesh is the degenerate case running
    literally the same code.  ``partition_specs`` assigns specs to the solve
    pytrees by regex over leaf paths (the partition-rule pattern).
  - **Consolidation lane sweep**: the subset-prefix simulations
    (ops.consolidate.sweep) split across the mesh's second ``lane`` axis
    while each lane group shards the catalog — one 2D shard_map answers the
    whole largest-valid-prefix search.
  - **Monte-Carlo what-if** (BASELINE config 5): vmap the solve kernel over
    perturbed snapshot replicas (spot-interruption scenarios), sharded across
    the mesh's ``replica`` axis; cost statistics reduce over ICI with psum.

Multi-slice scaling note: the replica/lane axes are embarrassingly parallel,
so lay them over DCN; the catalog axis's per-step collectives are tiny
([N]-vector max/sum) and ride ICI.

Flags (docs/KERNEL_PERF.md "Layer 5"):

    KC_SOLVER_MESH=1|0       force the sharded path on/off; unset = auto
                             (on when the backend exposes >1 device)
    KC_SOLVER_MESH_DEVICES   cap the devices the solve mesh uses
    KC_SOLVER_MESH_SHAPE     "CxL" catalog×lane split for the sweep mesh
                             (default: lanes=2 when the count allows)
"""

from __future__ import annotations

import functools
import os
import re
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from karpenter_core_tpu.models.snapshot import EncodedSnapshot
from karpenter_core_tpu.ops import solve as solve_ops
from karpenter_core_tpu.utils import compilecache

CATALOG_AXIS = "catalog"
LANE_AXIS = "lane"
TENANT_AXIS = "tenant"

# partition rules: leaf-path regex -> the axis index the catalog shards.
# Applied to every solve pytree (ClassTensors/StaticArrays/NodeState/
# WarmCarry/SolveOutputs...) — unmatched leaves replicate.
# ``.it.<field>`` is the catalog ReqTensor ([I, K, ...]); bare ``.it`` is
# ClassTensors.it ([C, I]); ``.viable`` covers NodeState in carries and
# outputs alike, which is what lets the warm-start repair reuse the same
# rule set for its carry pytrees.  (The lane sweep's per-lane outputs add a
# leading lane axis and build their specs by hand —
# ops.consolidate.lane_sweep_fn.)
CATALOG_PARTITION_RULES: Tuple[Tuple[str, int], ...] = (
    (r"\.it\.(mask|defined|negative|gt|lt)$", 0),
    (r"\.(it_alloc|it_avail|it_capacity|it_price)$", 0),
    (r"\.tmpl_it$", 1),
    (r"\.it$", 1),
    (r"\.viable$", 1),
)

# tenant-batch partition rules (the multi-tenant coalesced solve,
# service/tenant.py): the coalescer stacks EVERY solve pytree leaf with a
# leading tenant axis, so one catch-all rule shards axis 0 of every leaf —
# each device holds T/D whole tenants and no collectives cross them (tenant
# solves are independent by construction).  The catch-all is what makes the
# rule set closed under new fused variants: the repair batch's extra
# positional pytrees (WarmCarry, RepairPlan, the synthesized ExistingStatic)
# and the ex-plane batch's ExistingState/ExistingStatic all stack with the
# same leading tenant axis and shard under this one rule, no per-variant
# additions needed (docs/SERVICE.md "Solve fusion").  Same rule-by-regex
# machinery as the catalog rules above, just a different axis and rule set.
TENANT_PARTITION_RULES: Tuple[Tuple[str, int], ...] = (
    (r".", 0),
)


def named_tree_map(fn, tree, path: str = ""):
    """tree_map with dotted field paths for namedtuple pytrees (the named
    partition-rule pattern): ``fn(path, leaf) -> leaf'``.  None subtrees pass
    through (optional ex/warm planes)."""
    if tree is None:
        return None
    if isinstance(tree, tuple) and hasattr(tree, "_fields"):
        return type(tree)(*(
            named_tree_map(fn, getattr(tree, f), f"{path}.{f}")
            for f in tree._fields
        ))
    if isinstance(tree, (list, tuple)):
        return type(tree)(
            named_tree_map(fn, v, f"{path}[{i}]") for i, v in enumerate(tree)
        )
    return fn(path, tree)


def _spec_for(path: str, axis_name: str, rules=CATALOG_PARTITION_RULES):
    for pattern, axis in rules:
        if re.search(pattern, path):
            return P(*([None] * axis), axis_name)
    return P()


def partition_specs(tree, axis_name: str = CATALOG_AXIS):
    """PartitionSpec pytree for a solve pytree: catalog-indexed leaves shard
    over ``axis_name`` (CATALOG_PARTITION_RULES), the rest replicate."""
    return named_tree_map(lambda p, _leaf: _spec_for(p, axis_name), tree)


def mesh_shardings(tree, mesh: Mesh, axis_name: str = CATALOG_AXIS):
    """NamedSharding pytree mirroring ``partition_specs`` — the device_put
    layout for uploading solve inputs onto the mesh."""
    return named_tree_map(
        lambda p, _leaf: NamedSharding(mesh, _spec_for(p, axis_name)), tree
    )


# -- production mesh configuration -------------------------------------------


def _env_tristate(name: str) -> Optional[bool]:
    raw = os.environ.get(name)
    # empty string = unset = AUTO (the chart's documented "" default rides
    # through as an env var set to ""), not forced-off
    if raw is None or raw == "":
        return None
    return raw not in ("0", "false", "False")


def _mesh_device_count() -> int:
    """Devices the solve mesh may use.  Reads ``jax.devices()`` — callers gate
    on the env kill switch first so KC_SOLVER_MESH=0 never initializes a
    backend."""
    n = len(jax.devices())
    cap = os.environ.get("KC_SOLVER_MESH_DEVICES")
    if cap:
        try:
            n = max(1, min(n, int(cap)))
        except ValueError:
            pass
    return n


def solve_mesh_axes() -> Optional[Tuple[Tuple[str, int], ...]]:
    """The production solve mesh topology, or None for the unsharded path.

    ``KC_SOLVER_MESH=0`` → None; ``=1`` → a catalog mesh over the available
    devices (1-device degenerate mesh included — same code, singleton
    collectives); unset → AUTO, on exactly when the backend exposes more
    than one device.  The returned hashable descriptor — not a Mesh — is
    what rides the compile-cache key (one warm executable per topology);
    ``mesh_for`` reconstructs the Mesh deterministically from it."""
    forced = _env_tristate("KC_SOLVER_MESH")
    if forced is False:
        return None
    n = _mesh_device_count()
    if forced is None and n <= 1:
        return None
    return ((CATALOG_AXIS, n),)


def lane_mesh_axes() -> Optional[Tuple[Tuple[str, int], ...]]:
    """The 2D (catalog × lane) sweep mesh topology, or None.  Enabled by the
    same switch as the solve mesh; ``KC_SOLVER_MESH_SHAPE=CxL`` pins the
    split, default peels a lane axis of 2 off an even device count ≥ 4 so
    consolidation prefixes evaluate in parallel WITH catalog sharding.

    A pinned shape's catalog axis must DIVIDE the solve mesh size: the
    encode pads the catalog to multiples of the solve mesh
    (``catalog_pad_multiple``), so any other split would fail the even-split
    check on every snapshot and silently degrade the sweep to lanes-only —
    reject it up front and fall back to the default split instead."""
    axes = solve_mesh_axes()
    if axes is None:
        return None
    n = axes[0][1]
    shape = os.environ.get("KC_SOLVER_MESH_SHAPE", "")
    if shape:
        try:
            c, lanes = (int(v) for v in shape.lower().split("x"))
            if c * lanes <= n and c >= 1 and lanes >= 1 and n % c == 0:
                return ((CATALOG_AXIS, c), (LANE_AXIS, lanes))
        except ValueError:
            pass
        import logging

        logging.getLogger(__name__).warning(
            "KC_SOLVER_MESH_SHAPE=%r rejected (needs CxL with C*L <= %d and "
            "C dividing %d); using the default split", shape, n, n,
        )
    lanes = 2 if n >= 4 and n % 2 == 0 else 1
    return ((CATALOG_AXIS, n // lanes), (LANE_AXIS, lanes))


def tenant_partition_specs(tree):
    """PartitionSpec pytree for a tenant-stacked solve pytree: every leaf
    shards its leading (tenant) axis (TENANT_PARTITION_RULES)."""
    return named_tree_map(
        lambda p, _leaf: _spec_for(p, TENANT_AXIS, TENANT_PARTITION_RULES), tree
    )


def tenant_mesh_shardings(tree, mesh: Mesh):
    """NamedSharding pytree for a tenant-stacked solve pytree — the
    device_put layout for a coalesced batch's inputs."""
    return named_tree_map(
        lambda p, _leaf: NamedSharding(
            mesh, _spec_for(p, TENANT_AXIS, TENANT_PARTITION_RULES)
        ),
        tree,
    )


def tenant_mesh_axes(n_tenants: int) -> Optional[Tuple[Tuple[str, int], ...]]:
    """Mesh topology for one coalesced tenant batch, or None for the
    vmap-only (single-device) path.  Gated by the same KC_SOLVER_MESH switch
    as the solve mesh; the device count must divide the batch size so every
    shard holds whole tenants — otherwise the batch runs unsharded (always
    correct, the batched executable is the same vmap body)."""
    forced = _env_tristate("KC_SOLVER_MESH")
    if forced is False:
        return None
    n = _mesh_device_count()
    if forced is None and n <= 1:
        return None
    if n < 1 or n_tenants % n != 0:
        return None
    return ((TENANT_AXIS, n),)


def tenant_solve_callable(mesh_axes, base_plain, structs):
    """jit(shard_map(vmap(solve))) for one coalesced tenant batch: the batch
    splits over the mesh's tenant axis, each device vmaps its local tenants
    through the plain (collective-free) solve body.  ``structs`` are the
    tenant-STACKED positional arg pytrees (ShapeDtypeStructs or arrays);
    the caller memoizes (utils.compilecache.batched_solve_callable)."""
    mesh = mesh_for(mesh_axes)
    vmapped = jax.vmap(base_plain)
    in_specs = tuple(tenant_partition_specs(s) for s in structs)
    out_specs = tenant_partition_specs(jax.eval_shape(vmapped, *structs))
    return jax.jit(jax.shard_map(
        vmapped, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        # every output leaf is sharded over the tenant axis (no replicated
        # outputs to verify) and tenants never exchange data; the coalesced
        # parity tests (tests/test_tenant_service.py) pin bit-identity
        check_vma=False,
    ))


def catalog_pad_multiple() -> int:
    """The multiple the encode pads the instance-type axis to so every mesh
    topology in play divides it (models.snapshot.encode_snapshot).  The lane
    mesh's catalog axis divides the solve mesh's, so the solve mesh size is
    the binding constraint."""
    axes = solve_mesh_axes()
    return axes[0][1] if axes is not None else 1


@functools.lru_cache(maxsize=8)
def mesh_for(mesh_axes: Tuple[Tuple[str, int], ...]) -> Mesh:
    """Deterministic Mesh for a topology descriptor: the first prod(sizes)
    devices of ``jax.devices()`` reshaped to the axis sizes.  Cached so every
    consumer of one topology shares one Mesh object (and jit caches key
    consistently on it)."""
    names = tuple(name for name, _ in mesh_axes)
    sizes = tuple(size for _, size in mesh_axes)
    total = int(np.prod(sizes))
    devices = jax.devices()
    if total > len(devices):
        raise ValueError(
            f"mesh {mesh_axes} needs {total} devices, have {len(devices)}"
        )
    return Mesh(np.array(devices[:total]).reshape(sizes), names)


def sharded_solve_callable(mesh_axes, base_with_axis, base_plain, structs,
                           donate_argnums=()):
    """jit(shard_map(...)) over the solve pytrees for one mesh topology.

    ``base_with_axis`` is the solve_core partial with
    ``catalog_axis=CATALOG_AXIS`` (collectives traced); ``base_plain`` the
    axis-free twin used only to eval_shape the output structure (outside the
    mesh no axis name is bound).  ``structs`` are the positional arg pytrees
    (ShapeDtypeStructs or arrays).  Returns the jitted callable; the caller
    memoizes (utils.compilecache keys it by topology + leaf signatures).

    ``donate_argnums`` threads buffer donation through the sharded build —
    the pipelined loop's warm-carry variant (utils.pipeline) donates the
    carry argument so mesh-sharded churn repairs reuse the carry's sharded
    device buffers in place; the sharding layout is unchanged (the carry's
    partition specs cover inputs AND outputs, CATALOG_PARTITION_RULES)."""
    mesh = mesh_for(mesh_axes)
    in_specs = tuple(partition_specs(s) for s in structs)
    out_specs = partition_specs(jax.eval_shape(base_plain, *structs))
    return jax.jit(jax.shard_map(
        base_with_axis, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        # replicated out_specs are guaranteed by construction (every
        # cross-shard reduction is an exact collective inside the body);
        # the varying-manual-axes check cannot see through the class scan,
        # so the static claim stands in for it — the mesh parity fuzz
        # (tests/test_mesh_dispatch.py) pins the guarantee at runtime
        check_vma=False,
    ), donate_argnums=tuple(donate_argnums))


def default_mesh(n_devices: Optional[int] = None, axis: str = "replica") -> Mesh:
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return Mesh(np.array(devices), (axis,))


def default_mesh_2d(
    shape: Optional[Tuple[int, int]] = None, axes: Tuple[str, str] = ("replica", "lane")
) -> Mesh:
    """(replica × lane) mesh for crossed studies: Monte-Carlo scenarios on one
    axis, consolidation prefix lanes on the other.  Both axes are
    embarrassingly parallel, so on multi-slice hardware lay ``replica`` over
    DCN and keep ``lane`` within a slice — the only cross-device traffic is
    the result gather."""
    devices = jax.devices()
    if shape is None:
        n = len(devices)
        lanes = 1
        for candidate in range(int(np.sqrt(n)), 0, -1):
            if n % candidate == 0:
                lanes = candidate
                break
        shape = (n // lanes, lanes)
    r, l = shape
    return Mesh(np.array(devices[: r * l]).reshape(r, l), axes)


def solve_catalog_sharded(
    snapshot: EncodedSnapshot,
    mesh: Optional[Mesh] = None,
    axis: str = "lane",
    n_slots: int = 0,
):
    """The PROVISIONING solve with the catalog (instance-type) axis sharded
    across the mesh (VERDICT r4 #7 / BASELINE config 4) — now a thin wrapper
    over the PRODUCTION shard_map dispatcher (utils.compilecache.run_solve
    with ``mesh_axes``), kept as the named dryrun entry __graft_entry__ and
    the parity suites call.

    Why the catalog axis: class dedup collapses the pod axis to ~a dozen
    classes regardless of pod count (models/snapshot.py docstring), and the
    class scan's carry is inherently sequential — but per class step the hot
    planes are [N slots, I instance types] with per-I independence
    (_it_intersects, _capacity, _offering_ok) and only max/any/or reductions
    over I.  The shard_map body computes per-device [N, I/D] planes and
    finishes each reduction with one exact collective
    (ops.solve._imax/_isum), so the result is BIT-IDENTICAL to the
    single-device solve; bit-packed masks compose transparently (packing is
    elementwise over the trailing slot axis, per catalog row).

    The catalog pads to a device multiple with inert instance types
    (ops.solve.pad_catalog) — the padded I tail is never viable, so decode
    sees the same placements."""
    if mesh is None:
        mesh = default_mesh(axis=axis)
    if axis not in mesh.axis_names:
        axis = mesh.axis_names[-1]
    # shard as many ways as the given mesh's sharding axis — on a 2D mesh
    # P(axis) only splits the catalog that many ways
    axis_size = int(mesh.shape[axis])
    if n_slots <= 0:
        n_slots = solve_ops.estimate_slots(snapshot)

    cls, statics_arrays, key_has_bounds = solve_ops.prepare_host(snapshot)
    cls, statics_arrays = solve_ops.pad_catalog(cls, statics_arrays, axis_size)
    out = compilecache.run_solve(
        cls, statics_arrays, n_slots, key_has_bounds,
        n_passes=snapshot.scan_passes,
        features=solve_ops.snapshot_features(snapshot),
        mesh_axes=((CATALOG_AXIS, axis_size),),
    )
    from karpenter_core_tpu.utils import watchdog

    watchdog.run("solve.sync", jax.block_until_ready, out)
    return out


def perturb_spot_availability(
    snapshot: EncodedSnapshot, n_replicas: int, seed: int = 0, interruption_rate: float = 0.3
) -> jnp.ndarray:
    """bool[REP, I, Z, CT]: per-replica offering availability with spot
    offerings randomly interrupted — the scenario axis for the what-if sweep."""
    key = jax.random.PRNGKey(seed)
    avail = jnp.asarray(snapshot.it_avail)  # [I, Z, CT]
    is_spot = jnp.asarray(
        np.array([ct == "spot" for ct in snapshot.capacity_types], dtype=bool)
    )  # [CT]
    interrupted = (
        jax.random.uniform(key, (n_replicas,) + avail.shape) < interruption_rate
    ) & is_spot[None, None, None, :]
    return avail[None] & ~interrupted


def monte_carlo_solve(
    snapshot: EncodedSnapshot,
    n_replicas: int,
    mesh: Optional[Mesh] = None,
    seed: int = 0,
    interruption_rate: float = 0.3,
    n_slots: int = 0,
) -> dict:
    """Solve ``n_replicas`` perturbed snapshots in parallel across the mesh.

    Returns summary statistics (per-replica scheduled/failed/node counts and
    total cost, plus mean/min/max cost) — the cost-vs-disruption Pareto input.
    """
    if mesh is None:
        mesh = default_mesh()
    if n_slots <= 0:
        n_slots = solve_ops.estimate_slots(snapshot)

    cls, statics_arrays, key_has_bounds = solve_ops.prepare(snapshot)
    avail_r = perturb_spot_availability(snapshot, n_replicas, seed, interruption_rate)
    it_price = jnp.asarray(snapshot.it_price)

    # patch the availability plane by field name so a reordering of the
    # Statics tuple can't silently perturb the wrong tensor
    avail_idx = solve_ops.Statics._fields.index("it_avail")

    fn = _monte_carlo_fn(
        mesh, key_has_bounds, n_slots, snapshot.scan_passes, avail_idx,
        compilecache.snap_features(solve_ops.snapshot_features(snapshot)),
    )
    from karpenter_core_tpu.utils import watchdog

    with mesh:
        scheduled, failed, nodes, cost = fn(
            avail_r, cls, statics_arrays, it_price
        )
        # monte-carlo replicas block on one batched fetch: deadline-bounded
        # like every device→host barrier (utils/watchdog.py)
        scheduled, failed, nodes, cost = watchdog.run(
            "mesh.monte_carlo", jax.device_get,
            (scheduled, failed, nodes, cost), key=n_replicas,
        )
    return {
        "replicas": n_replicas,
        "scheduled": np.asarray(scheduled),
        "failed": np.asarray(failed),
        "nodes": np.asarray(nodes),
        "cost": np.asarray(cost),
        "cost_mean": float(np.mean(cost)),
        "cost_min": float(np.min(cost)),
        "cost_max": float(np.max(cost)),
        "failed_mean": float(np.mean(failed)),
    }


@functools.lru_cache(maxsize=16)
def _monte_carlo_fn(mesh, key_has_bounds, n_slots: int, n_passes: int,
                    avail_idx: int, features=None):
    """Cached jitted Monte-Carlo sweep.  The per-replica closure takes the
    snapshot tensors as ARGUMENTS (not captured values) so the cache key is
    the static config alone — a fresh closure per call would defeat JAX's
    compile cache and retrace every study."""

    def one_replica(avail, cls, statics_arrays, it_price):
        arrays = list(statics_arrays)
        arrays[avail_idx] = avail
        out = solve_ops.solve_core(
            cls, tuple(arrays), n_slots, key_has_bounds,
            n_passes=n_passes, features=features,
        )
        scheduled = jnp.sum(out.assign)
        failed = jnp.sum(out.failed)
        nodes = jnp.sum((out.state.pod_count > 0).astype(jnp.int32))
        prices = solve_ops.node_prices(out.state, it_price)
        cost = jnp.sum(jnp.where(jnp.isfinite(prices), prices, 0.0))
        return scheduled, failed, nodes, cost

    sharded = NamedSharding(mesh, P("replica"))
    return jax.jit(
        jax.vmap(one_replica, in_axes=(0, None, None, None)),
        in_shardings=(sharded, None, None, None),
        out_shardings=(sharded, sharded, sharded, sharded),
    )


def perturb_offering_availability(
    snapshot: EncodedSnapshot, risk, n_replicas: int, seed: int = 0
) -> jnp.ndarray:
    """bool[REP, I, Z, CT]: per-replica offering availability with every
    offering cell interrupted with ITS OWN prior probability — the policy
    risk planes (policy.planes) instead of ``perturb_spot_availability``'s
    one uniform spot rate.  Offerings with zero risk never drop, so a
    risk-free catalog reproduces the unperturbed solve in every replica."""
    key = jax.random.PRNGKey(seed)
    avail = jnp.asarray(snapshot.it_avail)  # [I, Z, CT]
    risk_arr = jnp.asarray(risk, dtype=jnp.float32)
    interrupted = (
        jax.random.uniform(key, (n_replicas,) + avail.shape) < risk_arr[None]
    )
    return avail[None] & ~interrupted


def policy_monte_carlo(
    snapshot: EncodedSnapshot,
    n_replicas: int,
    mesh: Optional[Mesh] = None,
    seed: int = 0,
    n_slots: int = 0,
) -> dict:
    """Risk-weighted policy variants over the Monte-Carlo replica machinery:
    sample one interruption OUTCOME per replica from the snapshot's
    per-offering risk priors (``pol_risk``), solve every outcome in parallel
    across the mesh, and pick the replica assignment minimizing risk-adjusted
    cost — fleet price plus an unschedulable-pod penalty that dominates any
    price difference, so a cheap fleet that strands pods under interruption
    never wins (docs/POLICY.md "Risk-weighted variants").

    Returns per-replica ``cost``/``failed``/``nodes`` arrays plus
    ``expected_cost`` (the mean risk-adjusted cost — the number a
    risk-averse objective reports for this fleet) and ``best_replica``."""
    if mesh is None:
        mesh = default_mesh()
    if n_slots <= 0:
        n_slots = solve_ops.estimate_slots(snapshot)
    risk = getattr(snapshot, "pol_risk", None)
    if risk is None:
        risk = np.zeros_like(np.asarray(snapshot.it_price))
    price = getattr(snapshot, "pol_price", None)
    if price is None:
        price = snapshot.it_price

    cls, statics_arrays, key_has_bounds = solve_ops.prepare(snapshot)
    avail_r = perturb_offering_availability(snapshot, risk, n_replicas, seed)
    it_price = jnp.asarray(price)
    avail_idx = solve_ops.Statics._fields.index("it_avail")

    fn = _monte_carlo_fn(
        mesh, key_has_bounds, n_slots, snapshot.scan_passes, avail_idx,
        compilecache.snap_features(solve_ops.snapshot_features(snapshot)),
    )
    from karpenter_core_tpu.utils import watchdog

    with mesh:
        scheduled, failed, nodes, cost = watchdog.run(
            "mesh.monte_carlo", jax.device_get,
            fn(avail_r, cls, statics_arrays, it_price), key=n_replicas,
        )
    cost = np.asarray(cost, dtype=np.float64)
    failed = np.asarray(failed, dtype=np.int64)
    # the penalty per unplaced pod dominates any achievable fleet price —
    # every open slot costs at most the max offering price, so max_price ×
    # n_slots bounds any replica's fleet cost and feasibility strictly
    # outranks price in the risk-adjusted ordering
    finite = np.asarray(price)[np.isfinite(price)]
    penalty = float(finite.max() if finite.size else 1.0) * max(n_slots, 1)
    adjusted = cost + failed * (penalty + 1.0)
    best = int(np.argmin(adjusted)) if len(adjusted) else 0
    return {
        "replicas": n_replicas,
        "scheduled": np.asarray(scheduled),
        "failed": failed,
        "nodes": np.asarray(nodes),
        "cost": cost,
        "adjusted_cost": adjusted,
        "expected_cost": float(np.mean(adjusted)) if len(adjusted) else 0.0,
        "cost_mean": float(np.mean(cost)) if len(cost) else 0.0,
        "cost_max": float(np.max(cost)) if len(cost) else 0.0,
        "best_replica": best,
        "best_cost": float(cost[best]) if len(cost) else 0.0,
        "feasible_replicas": int(np.sum(failed == 0)),
    }


@functools.lru_cache(maxsize=16)
def _crossed_grid_fn(mesh, key_has_bounds, n_slots: int, n_passes: int, avail_idx: int,
                     features=None):
    """Cached jitted crossed grid — a fresh closure per call would defeat
    JAX's compile cache (keyed on callable identity) and recompile the whole
    vmap-of-vmap solve every study (same pattern as
    ops.consolidate.lane_sweep_fn)."""
    rep, lane = mesh.axis_names

    def one_cell(avail, k, cls, statics_arrays, ex_state, ex_static, rank, counts):
        arrays = list(statics_arrays)
        arrays[avail_idx] = avail
        subset = rank < k
        ex = ex_state._replace(open_=ex_state.open_ & ~subset)
        displaced = jnp.sum(counts * subset[None, :].astype(jnp.int32), axis=-1)
        cls_k = cls._replace(count=cls.count + displaced)
        out = solve_ops.solve_core(
            cls_k, tuple(arrays), n_slots, key_has_bounds, ex, ex_static,
            n_passes=n_passes, features=features,
        )
        return jnp.sum(out.failed), out.state.n_next

    batch_none = (None,) * 6
    grid = jax.vmap(
        jax.vmap(one_cell, in_axes=(None, 0) + batch_none),
        in_axes=(0, None) + batch_none,
    )
    return jax.jit(
        grid,
        in_shardings=(NamedSharding(mesh, P(rep)), NamedSharding(mesh, P(lane)))
        + (None,) * 6,
        out_shardings=(
            NamedSharding(mesh, P(rep, lane)),
            NamedSharding(mesh, P(rep, lane)),
        ),
    )


def crossed_consolidation_study(
    snapshot: EncodedSnapshot,
    ex_state,
    ex_static,
    candidate_rank: np.ndarray,  # i32[E] disruption order, big = not candidate
    ex_cls_count: np.ndarray,  # i32[C, E] candidate pods per class per node
    prefix_sizes: np.ndarray,  # i32[S]
    n_replicas: int,
    mesh: Optional[Mesh] = None,
    seed: int = 0,
    interruption_rate: float = 0.3,
    n_slots: int = 16,
) -> dict:
    """Risk-aware consolidation: every (spot-interruption scenario r,
    consolidation prefix k) pair is one simulation — close the first-k
    candidates AND apply replica r's perturbed offering availability, then
    re-schedule.  The [R, S] grid shards over a 2D (replica × lane) mesh
    (vmap∘vmap; XLA partitions both batch axes, no collectives until the
    result gather).

    Returns the failed/new-node grids plus ``safe_prefix``: per replica, the
    largest prefix whose simulation fully re-schedules — min over replicas is
    the consolidation depth that is safe under every sampled interruption
    scenario (the 1D sweep in ops.consolidate answers only the rate-0 row)."""
    if mesh is None:
        mesh = default_mesh_2d()
    n_rep_axis, n_lane_axis = (mesh.shape[name] for name in mesh.axis_names)

    cls, statics_arrays, key_has_bounds = solve_ops.prepare(snapshot)
    avail_r = perturb_spot_availability(snapshot, n_replicas, seed, interruption_rate)
    avail_idx = solve_ops.Statics._fields.index("it_avail")

    sizes = jnp.asarray(prefix_sizes, dtype=jnp.int32)
    pad_s = (-len(prefix_sizes)) % n_lane_axis
    if pad_s:
        sizes = jnp.concatenate([sizes, jnp.repeat(sizes[-1:], pad_s)])
    pad_r = (-n_replicas) % n_rep_axis
    if pad_r:
        avail_r = jnp.concatenate([avail_r, avail_r[-1:].repeat(pad_r, axis=0)])

    fn = _crossed_grid_fn(
        mesh, key_has_bounds, n_slots, snapshot.scan_passes, avail_idx,
        compilecache.snap_features(
            solve_ops.features_with_existing(snapshot, ex_static)
        ),
    )
    from karpenter_core_tpu.utils import watchdog

    with mesh:
        failed, n_new = watchdog.run(
            "mesh.monte_carlo", jax.device_get,
            fn(
                avail_r, sizes, cls, statics_arrays, ex_state, ex_static,
                jnp.asarray(candidate_rank), jnp.asarray(ex_cls_count),
            ),
            key="crossed",
        )
    failed = np.asarray(failed)[:n_replicas, : len(prefix_sizes)]
    n_new = np.asarray(n_new)[:n_replicas, : len(prefix_sizes)]

    feasible = failed == 0  # [R, S]
    sizes_np = np.asarray(prefix_sizes)
    # rows with no feasible prefix reduce to 0 (sizes are >= 1)
    safe_prefix = np.max(np.where(feasible, sizes_np[None, :], 0), axis=1)
    return {
        "failed": failed,
        "n_new": n_new,
        "safe_prefix": safe_prefix,  # per replica
        "safe_prefix_all": int(safe_prefix.min()) if len(safe_prefix) else 0,
    }
