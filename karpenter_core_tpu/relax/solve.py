"""The relax family's host orchestrator: gate, dispatch, audit-repair, merge.

``run_relax`` is the cold-solve twin of ``TPUSolver.run_prepared``'s scan
dispatch (which calls it when solver/modes.py routes a batch here).  The
contract with the caller is all-or-nothing per batch:

  1. HOST GATES — constraint families the relaxation does not model raise
     ``RelaxFallback`` immediately (the scan runs instead, and the reason
     rides the ``solve.mode`` span + relax-fallback counter): no objective
     planes on the prep, existing-node planes, finite provisioner limits, or
     no relax-eligible class at all.  Per-CLASS gates are softer: a class
     with topology groups, host ports, a preference ladder, or soft-anti
     terms is simply not eligible — its pods skip the relaxation and go to
     the exact repair pass with every constraint enforced.
  2. KERNEL — one ``relax_core`` jit (relax/kernel.py) served through
     ``utils.compilecache.relax_callable`` and deadline-bounded by
     ``utils.watchdog`` like every other solve variant; inputs upload with
     the prep's captured mesh shardings so the catalog axis stays sharded
     (parallel/mesh.py partition rules).
  3. VERDICT — non-convergence or a fully-audited-away result raises
     ``RelaxFallback`` (nothing was committed; the scan re-solves from
     scratch).
  4. EXACT REPAIR — leftover pods (ineligible classes, audited-out cells,
     slot spill) run through the existing warm-start repair machinery over
     the relax result's carry: a bounded window when it fits
     (``ops.solve.gather/scatter_repair_window``), the full width otherwise.
     The repair is the exact scan — so every pod the relaxation could not
     place correctly is placed by the kernel that can, or reported failed.

The merged ``SolveOutputs`` is full-width and scan-shaped: decode, the
policy objective stage, and the incremental session's ``warm_carry_of``
anchor all consume it unchanged.
"""

from __future__ import annotations

import logging

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_core_tpu import tracing
from karpenter_core_tpu.ops import masks as mask_ops
from karpenter_core_tpu.ops import solve as solve_ops

log = logging.getLogger(__name__)

# projected-gradient convergence tolerance (max per-class normalized step);
# the iteration halves the step every round, so the default iteration cap
# (solver.modes.relax_max_iters) clears this with a wide margin
RELAX_TOL = np.float32(1e-4)
# deterministic rounding tie-order seed: a constant, so the same snapshot
# rounds identically across processes, replicas, and mesh topologies
RELAX_SEED = 0


class RelaxFallback(Exception):
    """The relax family declines this batch; the scan must run it.

    ``reason`` is the structured label surfaced on the ``solve.mode`` span
    and carried by ``karpenter_solve_mode_total{mode="relax-fallback"}``:
    no-planes | existing-nodes | template-limits | no-eligible-classes |
    non-convergence | no-placements."""

    def __init__(self, reason: str) -> None:
        super().__init__(reason)
        self.reason = reason


def eligible_classes(prep, cls=None) -> np.ndarray:
    """bool[C]: classes the relaxation models EXACTLY (docs/RELAX.md).

    A class qualifies when its constraints are all cell-local — requirement
    masks, zone/ct/instance-type rectangles, per-pod resources — i.e. it owns
    no topology group, is a member of none, binds no host ports, sits on no
    preference ladder, and carries no soft-anti terms.  Everything else keeps
    full pod counts in ``leftover`` and routes to the exact repair."""
    if cls is None:
        cls = prep.cls
    sa = solve_ops.StaticArrays(*prep.statics_arrays)
    g1 = int(np.asarray(sa.grp_skew).shape[0])
    groups = np.asarray(cls.groups)
    member = np.asarray(sa.grp_member)
    idx = np.arange(groups.shape[0], dtype=np.int64)
    return (
        np.all(groups == g1 - 1, axis=1)
        & ~member[:, : max(g1 - 1, 0)].any(axis=1)
        & ~np.asarray(cls.ports).any(axis=1)
        & (np.asarray(cls.relax_next) < 0)
        & (np.asarray(cls.root) == idx)
        & ~np.asarray(cls.anti_soft).any(axis=1)
    )


def _policy_weights(policy) -> np.ndarray:
    """f32[3] (cost_weight, risk_aversion, throughput_weight).  With policy
    off the objective degrades to the raw price sheet — the planes exist on
    every encode (policy.planes.attach_planes), so relax can always price."""
    if policy is not None and getattr(policy, "enabled", False):
        return np.asarray(
            [
                float(getattr(policy, "cost_weight", 1.0)),
                float(getattr(policy, "risk_aversion", 0.0)),
                float(getattr(policy, "throughput_weight", 0.0)),
            ],
            dtype=np.float32,
        )
    return np.asarray([1.0, 0.0, 0.0], dtype=np.float32)


def _empty_carry_planes(prep, cls, n_slots: int):
    """(ex_state, topo, remaining) for a cold relax result — the same inert
    planes solve_core builds internally for a cold scan with no existing
    nodes, so the repair resumes over semantics identical by construction."""
    sa = solve_ops.StaticArrays(*prep.statics_arrays)
    n_res = int(np.asarray(sa.it_alloc).shape[-1])
    n_keys = int(np.asarray(sa.valid).shape[0])
    width = int(np.asarray(sa.valid).shape[-1])
    g1 = int(np.asarray(sa.grp_skew).shape[0])
    n_zones = int(np.asarray(cls.zone).shape[-1])
    n_ct = int(np.asarray(cls.ct).shape[-1])
    n_ports = int(np.asarray(cls.ports).shape[-1])
    ex_state = solve_ops.empty_existing_state(
        n_res, n_keys, width, n_zones, n_ct, n_ports
    )
    ex_state = ex_state._replace(kmask=mask_ops.pack_mask(ex_state.kmask))
    topo = solve_ops.TopoCounts(
        fwd_ex=jnp.zeros((g1, 1), dtype=jnp.int32),
        inv_ex=jnp.zeros((g1, 1), dtype=jnp.int32),
        fwd_new=jnp.zeros((g1, n_slots), dtype=jnp.int32),
        inv_new=jnp.zeros((g1, n_slots), dtype=jnp.int32),
    )
    remaining = jnp.asarray(np.asarray(sa.tmpl_limits0, dtype=np.float32))
    return ex_state, topo, remaining


def _zero_repair_plan(n_classes: int, n_slots_w: int, g1: int, n_zones: int,
                      base=None) -> solve_ops.RepairPlan:
    """A no-preference RepairPlan (pure additions); ``base`` carries the
    out-of-window topology planes from ``gather_repair_window`` when the
    repair is bounded."""
    if base is None:
        zeros_gz = jnp.zeros((g1, n_zones), dtype=jnp.int32)
        base = (zeros_gz, zeros_gz, zeros_gz)
    return solve_ops.RepairPlan(
        pref_new=jnp.zeros((n_classes, n_slots_w), dtype=jnp.int32),
        pref_ex=jnp.zeros((n_classes, 1), dtype=jnp.int32),
        base_fwd_sing=base[0],
        base_fwd_full=base[1],
        base_inv_full=base[2],
    )


def run_relax(solver, prep, cls=None, n_slots: int = 0) -> solve_ops.SolveOutputs:
    """Run one cold solve through the relax family (module docstring).

    ``solver`` is the TPUSolver (policy weights + the repair dispatch);
    ``prep`` a cold SolvePrep (no existing planes, no warm carry); ``cls``
    optionally overrides the prep's class tensors (run_prepared's ``count``
    merge).  Returns full-width scan-shaped SolveOutputs or raises
    ``RelaxFallback``."""
    from karpenter_core_tpu.solver import modes
    from karpenter_core_tpu.utils import compilecache, watchdog

    if cls is None:
        cls = prep.cls
    pol = getattr(prep, "pol", None)
    if pol is None:
        raise RelaxFallback("no-planes")
    if prep.ex_state is not None:
        raise RelaxFallback("existing-nodes")
    sa_host = solve_ops.StaticArrays(*prep.statics_arrays)
    if bool(np.isfinite(np.asarray(sa_host.tmpl_limits0)).any()):
        raise RelaxFallback("template-limits")
    counts = np.asarray(cls.count, dtype=np.int64)
    eligible = eligible_classes(prep, cls)
    if not bool(np.any(eligible & (counts > 0))):
        raise RelaxFallback("no-eligible-classes")

    n_slots = int(n_slots or prep.n_slots)
    n_classes = int(counts.shape[0])
    mesh_axes = getattr(prep, "mesh_axes", None)
    max_iters = modes.relax_max_iters()

    fn = compilecache.relax_callable(
        cls, prep.statics_arrays, pol, n_slots, prep.key_has_bounds,
        mesh_axes=mesh_axes,
    )
    trees = (cls, prep.statics_arrays, pol)
    if mesh_axes is not None:
        from karpenter_core_tpu.parallel import mesh as mesh_mod

        trees = jax.device_put(
            trees, mesh_mod.mesh_shardings(trees, mesh_mod.mesh_for(mesh_axes))
        )
    else:
        trees = jax.device_put(trees)
    cls_d, sa_d, pol_d = trees

    with tracing.span(
        "relax.solve", n_slots=n_slots, classes=n_classes,
        mesh=repr(mesh_axes) if mesh_axes else None,
    ) as sp:
        res = watchdog.run(
            "solve.relax", fn,
            cls_d, sa_d, pol_d.price, pol_d.risk, pol_d.throughput,
            jnp.asarray(eligible), jnp.asarray(_policy_weights(solver.policy)),
            jnp.int32(max_iters), jnp.float32(RELAX_TOL),
            jnp.uint32(RELAX_SEED),
            # the jit compiles inside this call: key on the shapes too, so
            # a program that has yet to compile gets the cold budget
            key=(compilecache.leaf_sig((cls_d, sa_d, pol_d)), n_slots,
                 mesh_axes),
        )
        iters, converged, violations, leftover, placed, n_used = watchdog.run(
            "solve.sync", jax.device_get,
            (res.iters, res.converged, res.violations, res.leftover,
             res.placed, res.state.n_next),
            key="relax",
        )
        sp.set(
            iters=int(iters), converged=bool(converged),
            violations=int(violations), placed=int(placed),
            leftover=int(np.sum(leftover)),
        )
        # test observability: the last relax dispatch's verdict, host data
        # only (mirrors the span attrs)
        solver.last_relax_stats = {
            "iters": int(iters),
            "converged": bool(converged),
            "rounded_violations": int(violations),
            "placed": int(placed),
            "leftover": int(np.sum(leftover)),
        }
    if not bool(converged):
        raise RelaxFallback("non-convergence")
    if int(placed) == 0 and int(np.sum(counts)) > 0:
        raise RelaxFallback("no-placements")

    leftover = np.asarray(leftover, dtype=np.int32)
    total_leftover = int(np.sum(leftover))
    ex_state, topo, remaining = _empty_carry_planes(prep, cls, n_slots)
    g1 = int(topo.fwd_ex.shape[0])
    n_zones = int(np.asarray(cls.zone).shape[-1])

    if total_leftover == 0:
        return solve_ops.SolveOutputs(
            assign=res.assign,
            assign_existing=jnp.zeros((n_classes, 1), dtype=jnp.int32),
            failed=jnp.zeros((n_classes,), dtype=jnp.int32),
            state=res.state,
            ex_state=ex_state,
            spread_suspect=jnp.zeros((n_classes,), dtype=bool),
            topo=topo,
            remaining=remaining,
        )

    # -- exact repair over the relax carry ------------------------------------
    carry = solve_ops.WarmCarry(
        state=res.state, ex_state=ex_state, topo=topo, remaining=remaining
    )
    n_used = int(n_used)
    # bounded window when it fits: the relax-open slots (all open slots are
    # the contiguous prefix [0, n_used)) plus a fresh tail sized for the
    # leftover — contiguous, so idx is a plain prefix range
    window_w = solve_ops.bucket(min(n_used + max(total_leftover, 16), n_slots))
    repaired = None
    if window_w < n_slots:
        idx = jnp.arange(window_w, dtype=jnp.int32)
        win_carry, base = solve_ops.gather_repair_window(carry, idx, n_used)
        plan = _zero_repair_plan(n_classes, window_w, g1, n_zones, base=base)
        rep = solver.run_prepared(
            prep, count=leftover, warm_carry=win_carry, repair_plan=plan,
            n_slots=window_w, donate_carry=False,
        )
        ticket = solver.begin_fetch(rep)
        fetched = ticket.wait()
        if solver.fetch_exhausted(fetched, window_w):
            log.debug(
                "relax repair window %d exhausted; retrying full-width",
                window_w,
            )
        else:
            merged = solve_ops.scatter_repair_window(carry, solve_ops.warm_carry_of(rep), idx, n_used)
            assign = res.assign + jnp.zeros(
                (n_classes, n_slots), dtype=jnp.int32
            ).at[:, idx].set(rep.assign)
            repaired = (rep, merged, assign)
    if repaired is None:
        plan = _zero_repair_plan(n_classes, n_slots, g1, n_zones)
        rep = solver.run_prepared(
            prep, count=leftover, warm_carry=carry, repair_plan=plan,
            n_slots=n_slots, donate_carry=False,
        )
        merged = solve_ops.warm_carry_of(rep)
        repaired = (rep, merged, res.assign + rep.assign)
    rep, merged, assign = repaired
    return solve_ops.SolveOutputs(
        assign=assign,
        assign_existing=rep.assign_existing,
        failed=rep.failed,
        state=merged.state,
        ex_state=merged.ex_state,
        spread_suspect=rep.spread_suspect,
        topo=merged.topo,
        remaining=merged.remaining,
    )
