"""Cold-start elimination: persistent compile + export caches.

The solve kernel's first run in a process pays trace + lower + XLA compile.
Two disk caches cut a process restart short:

  - the XLA persistent compilation cache (jax_compilation_cache_dir) reuses
    the compiled executable across processes
  - an exported-StableHLO cache skips the Python trace + MLIR lowering
    entirely: the first boot serializes the jitted program (jax.export),
    restarts deserialize it and go straight to the (cached) compile

Entries key on the input shapes/dtypes, the kernel's static config, the
backend platform, and a hash of ops/solve.py — editing the kernel invalidates
automatically.  A stale or corrupt cache ENTRY is rebuilt in place; a build,
lower or compile error propagates to the caller — there is no second solve
path to hide it behind.

Placement (``cache_dir``): everything this package persists — exported
modules, the XLA cache, the session journal, the lease file — defaults under
ONE fixed directory inside the checkout (``.kc_cache/``, git-ignored; a path
that moved would never hit).  ``KC_TPU_COMPILE_CACHE`` relocates that root
(the chart mounts a volume there).  The XLA cache alone follows
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it: JAX reads the
variable itself and this module then sets no directory in code.

The XLA cache is on exactly when the RESOLVED backend is not CPU (named or
auto-detected), decided lazily at the first executable lookup so that
``enable()`` — called from Operator.start — never initializes a backend.
XLA:CPU executables are AOT-compiled for the build machine's feature set and
their cache stays off.
"""

from __future__ import annotations

import hashlib
import logging
import os
import threading
from typing import Dict

log = logging.getLogger(__name__)

_lock = threading.Lock()
_registered = False
_xla_cache_decided = False
_memo: Dict[tuple, object] = {}
_in_flight: Dict[tuple, threading.Event] = {}
# in-process executable reuse accounting: "builds" counts solve_callable
# misses that had to lower+compile (even if the disk caches made it cheap),
# "memo_hits" counts solves served by an already-built executable.  The
# steady-state contract (tests/test_compile_reuse.py) is builds==constant
# across varied reconcile batches within the same shape buckets.
_stats = {"builds": 0, "memo_hits": 0}

# -- batch-occupancy / padding accounting -------------------------------------
#
# The shape-bucket padding (ops/solve.pad_planes) and the coalesced tenant
# batching both trade wasted rows for executable reuse; this ledger makes the
# trade measurable per (bucket, mesh): real class rows the snapshot shipped
# vs padded rows the kernel ran, and a padded-work proxy in "flops"
# (wasted rows × slots × passes × tenants — a relative yardstick for fusion
# tuning, not a hardware FLOP count).  Exported on /metrics.
from karpenter_core_tpu.metrics import REGISTRY

BATCH_OCCUPANCY = REGISTRY.gauge(
    "karpenter_batch_occupancy_ratio",
    "Real rows / padded rows of the latest solve dispatch, by shape bucket "
    "(padded class-row count) and mesh topology.",
    ("bucket", "mesh"),
)
PADDED_FLOPS = REGISTRY.counter(
    "karpenter_padded_flops_total",
    "Padded-row work dispatched to the kernel (wasted rows x slots x passes "
    "x tenants; a relative padding-cost proxy, not hardware FLOPs), by shape "
    "bucket and mesh topology.",
    ("bucket", "mesh"),
)

_occupancy: Dict[tuple, dict] = {}


def record_batch_occupancy(real_rows, padded_rows, n_slots, n_passes=1,
                           mesh_axes=None, tenants=1) -> None:
    """Record one dispatch's real-vs-padded class rows (one call per device
    dispatch — never on the traced hot path inside an executable).
    ``real_rows`` is per batch element (a float mean for coalesced batches);
    ``tenants`` scales the cumulative row/flops ledger."""
    real_rows = float(real_rows)
    padded_rows = max(int(padded_rows), 1)
    tenants = max(int(tenants), 1)
    bucket = str(padded_rows)
    mesh = repr(tuple(mesh_axes)) if mesh_axes else "none"
    ratio = min(real_rows / padded_rows, 1.0)
    wasted = max(padded_rows - real_rows, 0.0) * int(n_slots) * max(int(n_passes), 1) * tenants
    BATCH_OCCUPANCY.labels(bucket, mesh).set(ratio)
    PADDED_FLOPS.labels(bucket, mesh).inc(float(wasted))
    with _lock:
        entry = _occupancy.setdefault(
            (bucket, mesh),
            {"dispatches": 0, "real_rows": 0.0, "padded_rows": 0,
             "padded_flops": 0.0, "tenant_rows": 0},
        )
        entry["dispatches"] += 1
        entry["real_rows"] += real_rows * tenants
        entry["padded_rows"] += padded_rows * tenants
        entry["tenant_rows"] += tenants
        entry["padded_flops"] += float(wasted)


def occupancy_stats() -> Dict[str, dict]:
    """Cumulative per-(bucket, mesh) occupancy: ``{"<bucket>|<mesh>":
    {dispatches, real_rows, padded_rows, occupancy_ratio, padded_flops}}``."""
    with _lock:
        snapshot = {k: dict(v) for k, v in _occupancy.items()}
    out: Dict[str, dict] = {}
    for (bucket, mesh), entry in snapshot.items():
        entry["occupancy_ratio"] = (
            entry["real_rows"] / entry["padded_rows"]
            if entry["padded_rows"] else 0.0
        )
        out[f"{bucket}|{mesh}"] = entry
    return out


def reset_occupancy() -> None:
    with _lock:
        _occupancy.clear()


_slots_seen: set = set()

# feature-set hysteresis (mirror of _slots_seen): the SnapshotFeatures static
# flags key distinct trace variants, so naive keying would recompile whenever
# one reconcile batch happens to drop a constraint family the previous batch
# used.  Widening a requested set to an already-built superset executable is
# always sound (ops/solve.SnapshotFeatures docstring: enabled-but-unused
# phase families are runtime no-ops), so snap_features reuses the smallest
# covering variant and caps the variant count — past the cap every new set
# widens to all-on, bounding compilation at MAX_FEATURE_VARIANTS + 1
# executables per shape bucket (tests/test_compilecache.py asserts this).
_features_seen: set = set()
MAX_FEATURE_VARIANTS = 8


def snap_features(features):
    """Stabilize the solve's static feature set across nearby batches."""
    from karpenter_core_tpu.ops.solve import ALL_FEATURES, SnapshotFeatures

    if features is None:
        return ALL_FEATURES
    f = SnapshotFeatures(*features).canonical()
    with _lock:
        if f in _features_seen:
            return f
        covering = [g for g in _features_seen if g.covers(f)]
        if covering:
            # fewest extra flags = least superfluous traced work
            return min(covering, key=lambda g: (sum(g), tuple(g)))
        if len(_features_seen) >= MAX_FEATURE_VARIANTS:
            _features_seen.add(ALL_FEATURES)
            return ALL_FEATURES
        _features_seen.add(f)
        return f


def snap_slots(estimate: int, max_waste: int = 4) -> int:
    """Stabilize the solve's static slot count across nearby batches.

    n_slots is a compile-time constant; a batch whose estimate lands just past
    a power-of-two boundary would recompile even though an already-built
    executable has room.  Reuse the smallest previously-used slot count that
    covers the estimate within ``max_waste``x (slots cost solve compute, so
    unbounded reuse would trade a compile for a permanently slower solve)."""
    with _lock:
        covering = [s for s in _slots_seen if estimate <= s <= max_waste * estimate]
        if covering:
            return min(covering)
        _slots_seen.add(estimate)
        return estimate


def stats() -> Dict[str, int]:
    with _lock:
        return dict(_stats)


def reset_stats() -> None:
    with _lock:
        _stats.update(builds=0, memo_hits=0)


def reset_memo() -> None:
    """Simulate a process restart for tests: clear the executable memo AND the
    slot-count/feature-set hysteresis — a stale _slots_seen entry with no
    backing executable would snap later solves to a permanently oversized
    shape (and a stale feature set to a permanently wider trace)."""
    with _lock:
        _memo.clear()
        _slots_seen.clear()
        _features_seen.clear()
        _stats.update(builds=0, memo_hits=0)


# the one fixed default root (module docstring "Placement"): no temp name,
# pid, time or hostname in it — the path is part of the XLA cache key
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".kc_cache",
)


def cache_dir() -> str:
    return os.environ.get("KC_TPU_COMPILE_CACHE") or _CHECKOUT_CACHE


def enable() -> None:
    """Idempotently register the kernel pytree types for jax.export
    serialization.  Touches no backend: the persistent XLA cache is decided
    lazily (``_resolved_backend``)."""
    global _registered
    import jax

    with _lock:
        if _registered:
            return
        from karpenter_core_tpu.ops import consolidate as consolidate_ops
        from karpenter_core_tpu.ops import masks as mask_ops
        from karpenter_core_tpu.ops import solve as solve_ops

        for t in (
            consolidate_ops.SweepOutputs,
            solve_ops.ClassTensors,
            solve_ops.Statics,
            solve_ops.StaticArrays,
            solve_ops.NodeState,
            solve_ops.ExistingState,
            solve_ops.ExistingStatic,
            solve_ops.SolveOutputs,
            solve_ops.TopoCounts,
            solve_ops.WarmCarry,
            solve_ops.RepairPlan,
            mask_ops.ReqTensor,
        ):
            try:
                jax.export.register_namedtuple_serialization(
                    t, serialized_name=f"kc.{t.__name__}"
                )
            except ValueError:
                pass  # already registered
        _registered = True


def _resolved_backend() -> str:
    """``jax.default_backend()`` for the executable cache keys.  The first
    call also decides the persistent XLA cache from what was actually
    resolved: on for any non-CPU backend, at ``JAX_COMPILATION_CACHE_DIR``
    when the environment names one (JAX reads it; nothing is set here), else
    under ``cache_dir()``."""
    global _xla_cache_decided
    import jax

    backend = jax.default_backend()
    with _lock:
        if not _xla_cache_decided:
            _xla_cache_decided = True
            if backend != "cpu":
                if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
                    directory = os.path.join(cache_dir(), "xla")
                    os.makedirs(directory, exist_ok=True)
                    jax.config.update("jax_compilation_cache_dir", directory)
                # persist the fast helper compiles too: a restart should
                # compile nothing it compiled before
                jax.config.update(
                    "jax_persistent_cache_min_compile_time_secs", 0.0
                )
    return backend


_kernel_hash: Dict[tuple, str] = {}


def _kernel_src_hash(*also) -> str:
    """Hash of every module traced into solve_core — and of ``also``, the
    modules a family traces around it — so an edit invalidates the cache."""
    key = tuple(m.__name__ for m in also)
    if key not in _kernel_hash:
        from karpenter_core_tpu.ops import masks as mask_ops
        from karpenter_core_tpu.ops import solve as solve_ops

        digest = hashlib.sha256()
        for module in (solve_ops, mask_ops) + also:
            with open(module.__file__, "rb") as f:
                digest.update(f.read())
        _kernel_hash[key] = digest.hexdigest()[:16]
    return _kernel_hash[key]


def leaf_sig(tree) -> tuple:
    """The (dtype, shape) of every leaf: the shape identity an executable is
    compiled for — part of every cache key here, and of the watchdog's
    deadline key (solver/tpu.run_prepared)."""
    import jax

    return tuple(
        (str(getattr(leaf, "dtype", type(leaf))), tuple(getattr(leaf, "shape", ())))
        for leaf in jax.tree_util.tree_leaves(tree)
    )


def solve_callable(
    cls,
    statics_arrays,
    n_slots: int,
    key_has_bounds,
    ex_state=None,
    ex_static=None,
    n_passes: int = 1,
    features=None,
    warm_carry=None,
    repair_plan=None,
    mesh_axes=None,
    donate_carry: bool = False,
):
    """An AOT-compiled solve callable served through the export cache.  A
    build, lower or compile error propagates (only a stale or corrupt cache
    entry is recoverable, inside ``_build_and_memo``).

    ``donate_carry`` selects the buffer-donating twin of the warm variant
    (utils.pipeline, docs/KERNEL_PERF.md "Layer 7"): the ``warm_carry``
    argument is donated so a steady-state churn repair reuses the carry's
    device memory in place instead of reallocating the full-width planes
    every tick.  It is part of the cache key — the donating and plain
    executables never share a memo slot — and only meaningful with
    ``warm_carry``.  Donating variants skip the exported-StableHLO disk
    cache (donation is a property of the lowering, not the exported module);
    the in-process memo and XLA's persistent cache still cover them.

    ``mesh_axes`` (hashable topology descriptor, e.g. ``(("catalog", 8),)``
    from parallel.mesh.solve_mesh_axes) selects the SHARDED variant: the
    same solve built as a ``shard_map`` over that mesh with the catalog axis
    partitioned (docs/KERNEL_PERF.md "Layer 5").  The topology is part of the
    cache key — one warm executable per mesh shape — and the degenerate
    1-device topology is its own key too, so flipping KC_SOLVER_MESH never
    silently reuses an executable built for another layout.  Mesh variants
    skip the exported-StableHLO disk cache (a shard_map program embeds its
    mesh; the XLA persistent cache still covers the compile).

    The returned callable takes (cls, statics_arrays[, ex_state, ex_static])
    — or (cls, statics_arrays, ex_static, warm_carry) for the warm-start
    repair variant — matching how it was built; it is memoized in-process so
    warm calls reuse the already-compiled executable.  Inputs may be host
    (numpy) or device pytrees — only shapes/dtypes matter, so callers can
    overlap the device upload with this compile.  A warm carry keys its own
    ``delta`` executable variant
    (``has_warm`` + the carry's leaf signature): the repair program resumes
    the scan from the carry instead of empty slots, and because
    solver.incremental reuses the previous padded tensors verbatim the repair
    shape is FIXED across reconciles — one delta executable stays warm for
    the whole churn regime (docs/INCREMENTAL.md)."""
    enable()
    has_ex = ex_state is not None
    has_warm = warm_carry is not None
    has_repair = repair_plan is not None
    donate_carry = bool(donate_carry) and has_warm
    features = snap_features(features)
    key = (
        _kernel_src_hash(),
        _resolved_backend(),
        n_slots,
        tuple(key_has_bounds),
        n_passes,
        tuple(features),
        has_ex,
        has_warm,
        donate_carry,
        mesh_axes,
        leaf_sig(cls),
        leaf_sig(statics_arrays),
        leaf_sig(ex_state) if has_ex else None,
        leaf_sig(ex_static) if (has_ex or has_warm) else None,
        leaf_sig(warm_carry) if has_warm else None,
        leaf_sig(repair_plan) if has_repair else None,
    )
    if has_warm:
        struct_args = (cls, statics_arrays, ex_static, warm_carry, repair_plan)
    elif has_ex:
        struct_args = (cls, statics_arrays, ex_state, ex_static)
    else:
        struct_args = (cls, statics_arrays)
    base = _base_solve_fn(
        has_warm, has_ex, n_slots, key_has_bounds, n_passes, features,
    )
    # the warm signature's donated argument (see _base_solve_fn)
    donate_argnums = (3,) if donate_carry else ()
    sharded = None
    if mesh_axes is not None:
        def sharded(structs):
            from karpenter_core_tpu.parallel import mesh as mesh_mod

            base_axis = _base_solve_fn(
                has_warm, has_ex, n_slots, key_has_bounds, n_passes, features,
                catalog_axis=mesh_axes[0][0],
            )
            return mesh_mod.sharded_solve_callable(
                mesh_axes, base_axis, base, structs,
                donate_argnums=donate_argnums,
            )
    return _memoized(key, lambda: _build_and_memo(
        key, "solve", base, struct_args, sharded=sharded,
        donate_argnums=donate_argnums,
    ))


def _memoized(key, build):
    """The executable memoized under ``key``, built at most once at a time.
    In-flight dedup: the warmup thread and the first real batch race to build
    the same key; the loser waits on the winner's build instead of
    lowering+compiling the identical program twice (and, if the winner's
    build raised, becomes the builder and raises the same error itself)."""
    while True:
        with _lock:
            fn = _memo.get(key)
            if fn is not None:
                _stats["memo_hits"] += 1
                return fn
            building = _in_flight.get(key)
            if building is None:
                building = _in_flight[key] = threading.Event()
                break  # this thread builds
        building.wait(timeout=600.0)

    try:
        return build()
    finally:
        with _lock:
            _in_flight.pop(key, None)
        building.set()


def _base_solve_fn(has_warm, has_ex, n_slots, key_has_bounds, n_passes,
                   features, catalog_axis=None):
    """The positional-signature solve body for one variant: (cls, statics[,
    ...]) matching how callers invoke the memoized executable.
    ``catalog_axis`` threads the mesh axis name into solve_core's exact
    cross-shard collectives (the shard_map build passes it; every other
    build leaves it None — same code, no collectives traced)."""
    from karpenter_core_tpu.ops import solve as solve_ops

    if has_warm:
        # the delta variant: ex_state rides inside the carry; ex_static is
        # passed separately because its tol/vol rows are per-class
        return lambda c, s, exst, w, rp: solve_ops.solve_core(
            c, s, n_slots, key_has_bounds, None, exst, n_passes=n_passes,
            features=features, warm_carry=w, repair_plan=rp,
            catalog_axis=catalog_axis,
        )
    if has_ex:
        return lambda c, s, exs, exst: solve_ops.solve_core(
            c, s, n_slots, key_has_bounds, exs, exst, n_passes=n_passes,
            features=features, catalog_axis=catalog_axis,
        )
    return lambda c, s: solve_ops.solve_core(
        c, s, n_slots, key_has_bounds, n_passes=n_passes,
        features=features, catalog_axis=catalog_axis,
    )


def _build_and_memo(key, family: str, base, struct_args, sharded=None,
                    donate_argnums=()):
    """Build one executable for ``key``: export-cache load (or trace+export)
    of ``base`` — the family's positional-signature body — at
    ``struct_args``' shapes, then AOT compile, then memoize.  Callers hold the
    key's in-flight slot (``_memoized``).  ``sharded(structs)`` builds the
    mesh variant (a jit(shard_map(...))) instead and skips the export cache —
    the memo (and XLA's persistent cache) keep it warm.  ``donate_argnums``
    variants also skip the export cache and build the jit with those
    arguments donated."""
    import jax

    digest = hashlib.sha256(repr(key).encode()).hexdigest()[:24]
    path = os.path.join(cache_dir(), f"{family}-{digest}.stablehlo")
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), struct_args
    )
    if sharded is not None:
        fn = sharded(structs)
        with _lock:
            _memo[key] = fn
            _stats["builds"] += 1
        return fn
    if donate_argnums:
        # donation is a lowering property, not part of an exported StableHLO
        # module — build the donating jit directly and AOT-compile it; the
        # memo + XLA persistent cache keep it warm
        compiled = jax.jit(base, donate_argnums=donate_argnums).lower(*structs).compile()
        with _lock:
            _memo[key] = compiled
            _stats["builds"] += 1
        return compiled
    fn = None
    if os.path.exists(path):
        try:
            with open(path, "rb") as f:
                exported = jax.export.deserialize(f.read())
            fn = jax.jit(exported.call)
        except Exception as e:  # noqa: BLE001 - stale/corrupt entry
            log.warning("export cache load failed (%s), re-exporting", e)
            fn = None
    if fn is None:
        exported = jax.export.export(jax.jit(base))(*structs)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(exported.serialize())
        os.replace(tmp, path)
        fn = jax.jit(exported.call)
    # AOT-compile from shape structs so no device data is needed — callers
    # overlap the input upload with this compile
    compiled = fn.lower(*structs).compile()
    with _lock:
        _memo[key] = compiled
        _stats["builds"] += 1
    return compiled


def sweep_callable(
    planes,
    lanes: int,
    n_slots: int,
    key_has_bounds,
    n_passes: int = 1,
    features=None,
    mesh_axes=None,
):
    """The consolidation sweep's executable (``ops.consolidate.sweep``:
    ``solve_core`` vmapped over ``lanes`` prefix sizes), served like a solve's:
    keyed on the kernel sources, the backend, the static config, the lane
    count and the planes' shape signature, exported to the StableHLO cache,
    AOT-compiled, memoized with in-flight dedup and counted in ``builds``.
    ``planes`` is the sweep body's argument tuple after the lane sizes —
    ``(cls, statics_arrays, ex_state, ex_static, rank, counts, it_price)``,
    host or device; the callable takes ``(sizes, *planes)``.  ``mesh_axes``
    (``parallel.mesh.lane_mesh_axes``) selects the 2-D catalog × lane
    shard_map variant, memoized under its own key and not exported."""
    import numpy as np

    from karpenter_core_tpu.ops import consolidate as consolidate_ops

    enable()
    features = snap_features(features)
    key_has_bounds = tuple(key_has_bounds)
    key = (
        "sweep",
        _kernel_src_hash(consolidate_ops),
        _resolved_backend(),
        int(lanes),
        int(n_slots),
        key_has_bounds,
        int(n_passes),
        tuple(features),
        mesh_axes,
        leaf_sig(planes),
    )

    def base(sizes, cls, statics_arrays, ex_state, ex_static, rank, counts, price):
        return consolidate_ops.sweep(
            cls, statics_arrays, key_has_bounds, ex_state, ex_static, rank,
            counts, sizes, price, n_slots=int(n_slots), n_passes=int(n_passes),
            features=features,
        )

    sharded = None
    if mesh_axes is not None:
        def sharded(structs):
            return consolidate_ops.lane_sweep_fn(
                tuple(mesh_axes), key_has_bounds, int(n_slots), int(n_passes),
                features, structs[1], structs[2],
            )
    struct_args = (np.zeros(int(lanes), dtype=np.int32),) + tuple(planes)
    return _memoized(key, lambda: _build_and_memo(
        key, "sweep", base, struct_args, sharded=sharded,
    ))


def batched_solve_callable(
    n_tenants: int,
    cls,
    statics_arrays,
    n_slots: int,
    key_has_bounds,
    ex_state=None,
    ex_static=None,
    n_passes: int = 1,
    features=None,
    mesh_axes=None,
    warm_carry=None,
    repair_plan=None,
):
    """The coalesced multi-tenant executable: ``vmap`` of the solve body over
    a leading tenant axis (service/tenant.py stacks N compatible-bucket
    tenants' planes and unstacks the outputs).  Memoized in ``_memo`` like
    every other variant, keyed on the batch size + the per-tenant bucket
    signature, so steady coalescing reuses ONE batched executable per
    (bucket, N).  ``cls``/``statics_arrays``/``ex_*``/``warm_carry``/
    ``repair_plan`` are ONE tenant's (unstacked) pytrees — only shapes/dtypes
    matter.  ``mesh_axes`` (parallel.mesh.tenant_mesh_axes) selects the
    sharded twin: the same vmap body under a shard_map that splits the tenant
    axis across devices.

    ``warm_carry`` selects the fused-REPAIR variant (the vmapped twin of the
    solo delta executable): the positional signature becomes ``(cls, statics,
    ex_static, warm_carry, repair_plan)`` with a leading tenant axis on every
    leaf, exactly mirroring the solo ``delta`` variant key above —
    ``n_slots`` is then the (shared) repair-window width.  Fused repairs
    never donate: member carries are stacked copies, and the per-tenant
    output slices must stay readable after the dispatch.

    Per-element semantics are the solo program's exactly — the coalesced
    parity suite pins every co-batched tenant's outputs bit-identical to its
    solo solve (tests/test_tenant_service.py)."""
    import jax

    features = snap_features(features)
    has_warm = warm_carry is not None
    has_ex = ex_state is not None and not has_warm
    key = (
        "tenant-batch-repair" if has_warm else "tenant-batch",
        int(n_tenants),
        _kernel_src_hash(),
        _resolved_backend(),
        n_slots,
        tuple(key_has_bounds),
        n_passes,
        tuple(features),
        has_ex,
        mesh_axes,
        leaf_sig(cls),
        leaf_sig(statics_arrays),
        leaf_sig(ex_state) if has_ex else None,
        leaf_sig(ex_static) if (has_ex or has_warm) else None,
        leaf_sig(warm_carry) if has_warm else None,
        leaf_sig(repair_plan) if has_warm else None,
    )
    with _lock:
        fn = _memo.get(key)
        if fn is not None:
            _stats["memo_hits"] += 1
            return fn
    base = _base_solve_fn(
        has_warm, has_ex, n_slots, key_has_bounds, n_passes, features,
    )
    if has_warm:
        solo_args = (cls, statics_arrays, ex_static, warm_carry, repair_plan)
    elif has_ex:
        solo_args = (cls, statics_arrays, ex_state, ex_static)
    else:
        solo_args = (cls, statics_arrays)
    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct((int(n_tenants),) + tuple(a.shape), a.dtype),
        solo_args,
    )
    if mesh_axes is not None:
        from karpenter_core_tpu.parallel import mesh as mesh_mod

        fn = mesh_mod.tenant_solve_callable(mesh_axes, base, structs)
    else:
        fn = jax.jit(jax.vmap(base))
    with _lock:
        _memo[key] = fn
        _stats["builds"] += 1
    return fn


def resolve_mesh_axes(mesh_axes, statics_arrays):
    """Resolve run_solve's ``mesh_axes`` argument to a concrete topology or
    None.  ``"auto"`` consults parallel.mesh.solve_mesh_axes (KC_SOLVER_MESH
    env + device count); any topology whose catalog axis does not divide the
    instance-type extent falls back to the unsharded path — production
    snapshots are encoded shard-aligned (models.snapshot), the guard covers
    planes prepared outside that path."""
    if mesh_axes == "auto":
        from karpenter_core_tpu.parallel import mesh as mesh_mod

        mesh_axes = mesh_mod.solve_mesh_axes()
    if mesh_axes is None:
        return None
    n_it = int(statics_arrays.it_alloc.shape[0])
    axis_size = int(mesh_axes[0][1])
    if axis_size < 1 or n_it % axis_size != 0:
        log.debug(
            "mesh dispatch skipped: catalog extent %d not a multiple of the "
            "mesh axis %r", n_it, mesh_axes,
        )
        return None
    return tuple(mesh_axes)


def run_solve(
    cls,
    statics_arrays,
    n_slots: int,
    key_has_bounds,
    ex_state=None,
    ex_static=None,
    n_passes: int = 1,
    features=None,
    warm_carry=None,
    repair_plan=None,
    pre_padded: bool = False,
    mesh_axes="auto",
    donate_carry="auto",
):
    """Solve through the memoized executable (``solve_callable``) — the ONE
    dispatch path: a build or compile error raises, nothing re-runs the
    solve another way.

    Inputs may be host (numpy) pytrees — from ops.solve.prepare_host — or
    device arrays; the device upload runs on a worker thread overlapped with
    the (cache-served) compile, since the two are independent.
    ``features`` is the snapshot's SnapshotFeatures phase plan
    (None = all-on); it may be silently widened to a previously-built
    superset executable (snap_features).

    ``warm_carry`` selects the warm-start repair variant (solve_callable
    docstring); ``pre_padded`` skips the bucket padding for callers that
    already hold padded planes — mandatory with a warm carry, whose device
    arrays must not round-trip through numpy padding (pad_planes would force
    a device→host sync on them).

    ``mesh_axes`` routes the solve through the sharded shard_map dispatcher
    (parallel.mesh): a topology descriptor, None for the unsharded path, or
    ``"auto"`` (the default — KC_SOLVER_MESH env / device count decide, so
    every production entry point inherits the sharded path without threading
    anything).  The sharded solve is bit-identical to the unsharded one
    (docs/KERNEL_PERF.md "Layer 5").

    ``donate_carry``: ``"auto"`` (default) donates the warm carry's device
    buffers whenever the pipeline is armed (utils.pipeline.donation_enabled
    — KC_PIPELINE=0 switches it off, and backends that ignore donation skip
    it); True/False force.  Donation never changes results — only whether
    the repair reuses the carry's device memory in place.  The caller must
    not read the passed ``warm_carry`` after this call when donation is
    possible (the ``donated-read`` kcanalyze rule, docs/ANALYSIS.md)."""
    from concurrent.futures import ThreadPoolExecutor

    import jax

    from karpenter_core_tpu import tracing
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.utils import pipeline as pipeline_mod

    features = snap_features(features)
    mesh_axes = resolve_mesh_axes(mesh_axes, statics_arrays)
    if donate_carry == "auto":
        donate_carry = pipeline_mod.donation_enabled()
    donate_carry = bool(donate_carry) and warm_carry is not None
    # "dispatch" covers pad + upload + executable lookup + async kernel launch;
    # the separate "solve" span blocks on the outputs (tracing only) so device
    # compute is attributed to the solve, not to whichever span first touches
    # the result — the JAX-aware boundary docs/OBSERVABILITY.md describes.
    with tracing.span("dispatch", n_passes=n_passes, warm=warm_carry is not None,
                      mesh=repr(mesh_axes) if mesh_axes else None):
        if (
            not pre_padded
            and warm_carry is None
            and os.environ.get("KC_TPU_SHAPE_BUCKETS", "1") != "0"
        ):
            real_rows = int(cls.count.shape[0])
            cls, statics_arrays, key_has_bounds, ex_state, ex_static = solve_ops.pad_planes(
                cls, statics_arrays, key_has_bounds, ex_state, ex_static
            )
            record_batch_occupancy(
                real_rows, int(cls.count.shape[0]), n_slots,
                n_passes=n_passes, mesh_axes=mesh_axes,
            )

        def _upload(tree):
            if mesh_axes is None:
                return jax.device_put(tree)
            from karpenter_core_tpu.parallel import mesh as mesh_mod

            return jax.device_put(
                tree,
                mesh_mod.mesh_shardings(tree, mesh_mod.mesh_for(mesh_axes)),
            )

        with ThreadPoolExecutor(max_workers=1) as pool:
            upload = pool.submit(_upload, (cls, statics_arrays, ex_state, ex_static))
            fn = solve_callable(
                cls, statics_arrays, n_slots, key_has_bounds, ex_state, ex_static,
                n_passes, features, warm_carry, repair_plan, mesh_axes,
                donate_carry,
            )
            cls, statics_arrays, ex_state, ex_static = upload.result()
        if warm_carry is not None:
            out = fn(cls, statics_arrays, ex_static, warm_carry, repair_plan)
            # donation effectiveness ledger: a donated buffer is consumed at
            # dispatch; a live host view (or an undonated variant) degrades
            # to a realloc (pipeline.stats()["donation_reallocs"])
            probe = getattr(
                getattr(warm_carry, "state", None), "used", None
            )
            pipeline_mod.record_donation(
                donate_carry and bool(getattr(probe, "is_deleted", lambda: False)())
            )
        else:
            out = fn(cls, statics_arrays, ex_state, ex_static) if ex_state is not None else fn(cls, statics_arrays)
    if tracing.enabled():
        with tracing.span("solve", sync=out):
            pass
    return out
