"""The relaxation kernel: one jit from encoded planes to rounded placements.

Formulation (docs/RELAX.md).  For every relax-eligible class c the decision
variable is a continuous mass vector x[c, i, z] >= 0 over (instance type,
zone) cells with sum_iz x[c, i, z] = count[c] — the class simplex scaled by
its pod count.  The support of each class's simplex is derived from the SAME
exact predicate planes the scan kernel commits with (ops/solve.py):
``mask_ops.compatible``/``add`` against every template, ``_it_intersects``
over the merged requirement tensor, ``_capacity`` for per-pod-per-node
intake, template zone/ct rectangles, and the ``it_avail`` offering sheet.
The linear cost of a cell is the policy objective score (ops/objective
vocabulary: ``cost_weight * price * (1 + risk_aversion * risk) -
throughput_weight * throughput``) of the cheapest allowed capacity type,
divided by the cell's per-node pod intake — i.e. the marginal per-pod price
of landing the class there.

The solve is projected gradient on ``min <cost, x> + mu/2 |x|^2`` with an
exact sort-based simplex projection (Held et al.; the Duchi et al. O(S log S)
form) per step.  The small strongly-convex term gives the iteration a 1/2
contraction factor at ``lr = 1/(2 mu)`` so convergence is geometric and the
iteration count small and data-independent.  After the loop a crossover step
snaps each class to the argmin-cost vertex of the unregularized linear
program (deterministic on plateaus via a rank epsilon) — the linear cost of
that vertex lower-bounds every feasible x, so crossover never loses fleet
cost, and it undoes the quadratic term's mass spreading before rounding.

Rounding is largest-fraction-first with a seeded tie permutation: floors are
kept, the per-class deficit is filled one pod per cell in (fraction desc,
seeded rank asc) order — fully deterministic given (x, seed), and identical
under any input sharding because sorts/cumsums are shape-, not
layout-, defined.  A vectorized audit then re-checks every rounded cell
against the exact predicate planes (independently re-gathered at the chosen
template) and zeroes violating cells — their pods join the leftover vector
the orchestrator (relax/solve.py) hands to the exact repair pass.

Everything below runs under ``_relax_jit`` (module-level, same idiom as
ops.solve._solve_jit); statics are ``n_slots`` and ``key_has_bounds`` —
exactly the compile-cache key fields they correspond to in
utils/compilecache.relax_callable.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from karpenter_core_tpu.ops import masks as mask_ops
from karpenter_core_tpu.ops import solve as solve_ops

# same plain-numpy BIG as ops/solve.py: a module-level jnp literal would
# initialize the backend at import time
BIG = np.float32(1e30)
_HALF_BIG = np.float32(5e29)
# quantization grid for the rounding pass's fraction ordering: fractions are
# compared as floor(frac * 2^20) so the order is exact-integer, not f32-ulp
_FRAC_Q = np.float32(2 ** 20)
# deterministic plateau-breaking epsilon, relative to the class cost scale
_RANK_EPS = np.float32(3e-3)
# curvature of the strongly-convex term, relative to cost scale / class mass.
# Two hard bounds pin this constant.  It cannot be tiny: each projection step
# computes ``x/2 - cost_eff/(2 mu)`` and the f32 cancellation noise of the
# threshold subtraction is ``eps_f32 / (2 _MU0)`` of the class mass — at 1e-6
# that is ~6% of m and the step delta never settles below any usable tol
# (observed as non-convergence at bench scale).  It also need not be small
# enough to concentrate plateau mass by itself: the crossover step below
# snaps each class to the argmin vertex of the UNregularized linear cost
# after the loop, so mu only has to keep the iteration contractive and the
# regularized optimum a faithful convergence witness.  1e-3 gives noise
# ~6e-5 * m per step, comfortably under the 1e-4 tol.
_MU0 = np.float32(1e-3)
# shave floors by one ppm before flooring so f32 simplex-projection error can
# never round a class ABOVE its count (sum(floor(x * (1-1e-6))) < count)
_FLOOR_SHAVE = np.float32(1.0 - 1e-6)


class RelaxResult(NamedTuple):
    """Device outputs of one ``relax_core`` run."""

    assign: jnp.ndarray  # i32[C, N] pods of class c materialized on slot n
    state: solve_ops.NodeState  # full-width slot planes (relax slots + cold tail)
    leftover: jnp.ndarray  # i32[C] pods the exact repair pass must place
    iters: jnp.ndarray  # i32[] projected-gradient iterations run
    converged: jnp.ndarray  # bool[] final step delta <= tol
    violations: jnp.ndarray  # i32[] rounded pods the exact audit rejected
    placed: jnp.ndarray  # i32[] pods materialized onto slots
    spilled: jnp.ndarray  # i32[] rounded pods that overflowed n_slots
    relaxed_cost: jnp.ndarray  # f32[] <cost, x> of the continuous optimum


def _simplex_project(y, support, m, jidx):
    """Euclidean projection of each row of ``y`` onto ``{x >= 0 on support,
    sum x = m}`` — sort-descending / cumulative-sum threshold form.  Rows with
    empty support (or m = 0) project to all-zeros."""
    yy = jnp.where(support, y, -BIG)
    ys = -jnp.sort(-yy, axis=1)  # descending
    css = jnp.cumsum(ys, axis=1)
    # ys_j - (css_j - m)/j > 0, multiplied through by j (> 0)
    cond = ys * jidx[None, :] > css - m[:, None]
    rho = jnp.clip(jnp.sum(cond.astype(jnp.int32), axis=1), 1, ys.shape[1])
    css_rho = jnp.take_along_axis(css, (rho - 1)[:, None], axis=1)[:, 0]
    theta = (css_rho - m) / rho.astype(jnp.float32)
    return jnp.where(support, jnp.maximum(y - theta[:, None], 0.0), 0.0)


def relax_core(
    class_tensors,
    statics_arrays,
    pol_price,
    pol_risk,
    pol_throughput,
    eligible,
    weights,
    max_iters,
    tol,
    seed,
    *,
    n_slots: int,
    key_has_bounds,
) -> RelaxResult:
    """Relax, round, audit, and materialize one snapshot's eligible classes.

    Traced inputs: the padded ``ClassTensors`` / ``StaticArrays`` pytrees the
    scan kernel takes, the padded objective planes (f32[I, Z, CT] price/risk,
    f32[I] throughput), ``eligible`` bool[C] (host-gated: groupless,
    portless, ladderless classes — relax/solve.py), ``weights`` f32[3]
    (cost_weight, risk_aversion, throughput_weight), and the loop knobs
    (``max_iters`` i32, ``tol`` f32, ``seed`` u32 tie-order seed) — all
    runtime values so weight/knob changes never retrace."""
    sa = solve_ops.StaticArrays(*statics_arrays)
    width = sa.valid.shape[-1]  # semantic slot count V+1, pre-packing
    sa, class_tensors = solve_ops.pack_masks(sa, class_tensors)
    statics = solve_ops.Statics(
        *sa, key_has_bounds=key_has_bounds, mask_v=width, catalog_axis=None,
    )
    cls = class_tensors
    n_classes = cls.count.shape[0]
    n_tmpl, n_zones = statics.tmpl_zone.shape
    n_it = statics.it_alloc.shape[0]
    n_ct = statics.tmpl_ct.shape[-1]
    n_keys = cls.defined.shape[-1]
    n_ports = cls.ports.shape[-1]
    n_cells = n_it * n_zones
    n_total = n_classes * n_cells

    counts = jnp.where(eligible, cls.count, 0).astype(jnp.int32)  # [C]

    # -- exact per-(class, template) predicate planes -------------------------
    def tmpl_planes(mask, defined, negative, gt, lt, requests, tol_row):
        cls_t = mask_ops.ReqTensor(
            mask[None], defined[None], negative[None], gt[None], lt[None]
        )
        key_ok = mask_ops.compatible(
            statics.tmpl, cls_t, statics.is_custom, statics.vocab_ints,
            v=statics.mask_v,
        )
        merged = mask_ops.add(
            statics.tmpl, cls_t, statics.valid, statics.vocab_ints,
            v=statics.mask_v, key_has_bounds=statics.key_has_bounds,
        )
        it_int = solve_ops._it_intersects(merged, statics)  # [T, I]
        per_pod = solve_ops._capacity(statics.tmpl_daemon, requests, statics)
        return key_ok & tol_row, merged, it_int, per_pod

    key_ok, merged, it_int, per_pod = jax.vmap(tmpl_planes)(
        cls.mask, cls.defined, cls.negative, cls.gt, cls.lt,
        cls.requests, cls.tol,
    )
    # key_ok bool[C,T]; merged ReqTensor[C,T,...]; it_int bool[C,T,I];
    # per_pod i32[C,T,I]

    t_zone = statics.tmpl_zone[None, :, :] & cls.zone[:, None, :]  # [C,T,Z]
    t_ct = statics.tmpl_ct[None, :, :] & cls.ct[:, None, :]  # [C,T,CT]
    base_ti = (
        statics.tmpl_it[None, :, :] & cls.it[:, None, :]
        & it_int & (per_pod >= 1) & key_ok[:, :, None]
    )  # [C,T,I]

    # -- objective: cheapest allowed capacity type per (c,t,i,z) --------------
    cw, ra, tw = weights[0], weights[1], weights[2]
    score = cw * pol_price * (1.0 + ra * pol_risk) - tw * pol_throughput[:, None, None]
    offer_priced = statics.it_avail & jnp.isfinite(pol_price)  # [I,Z,CT]
    score = jnp.where(offer_priced, score, BIG)
    best = jnp.full((n_classes, n_tmpl, n_it, n_zones), BIG, dtype=jnp.float32)
    for k in range(n_ct):  # CT is tiny and static: unrolled
        sc_k = jnp.where(t_ct[:, :, None, None, k], score[None, None, :, :, k], BIG)
        best = jnp.minimum(best, sc_k)
    feas = base_ti[:, :, :, None] & t_zone[:, :, None, :] & (best < _HALF_BIG)

    pp_f = jnp.clip(per_pod.astype(jnp.float32), 1.0, np.float32(1e6))
    unit = jnp.where(feas, best / pp_f[:, :, :, None], BIG)  # [C,T,I,Z]

    # reduce over templates: cheapest realization of each (c,i,z) cell.
    # argmin takes the FIRST minimum — deterministic template tie order.
    unit_ciz = jnp.min(unit, axis=1)  # [C,I,Z]
    tstar = jnp.argmin(unit, axis=1).astype(jnp.int32)  # [C,I,Z]
    feas_ciz = jnp.any(feas, axis=1)

    # -- projected gradient on the class simplices ----------------------------
    cost = unit_ciz.reshape(n_classes, n_cells)
    support = feas_ciz.reshape(n_classes, n_cells) & (counts > 0)[:, None]
    m = counts.astype(jnp.float32)
    # the class's cost magnitude — the epsilon/curvature yardstick.  NOT
    # ``+ 1``-floored: unit prices are tiny (price / pods-per-node), and an
    # epsilon scaled off an inflated yardstick would overwhelm genuine cost
    # gaps and pick cells by index instead of by price
    scale = jnp.maximum(
        jnp.max(jnp.where(support, jnp.abs(cost), 0.0), axis=1),
        np.float32(1e-20),
    )  # [C]
    cell_rank = jnp.arange(n_cells, dtype=jnp.float32) / np.float32(max(n_cells, 1))
    cost_eff = (
        jnp.where(support, cost, 0.0)
        + (_RANK_EPS * scale)[:, None] * cell_rank[None, :]
    )
    mu = (_MU0 * scale / jnp.maximum(m, 1.0))[:, None]  # [C,1]
    lr = 1.0 / (2.0 * mu)
    jidx = jnp.arange(1, n_cells + 1, dtype=jnp.float32)

    x0 = _simplex_project(
        jnp.zeros((n_classes, n_cells), dtype=jnp.float32), support, m, jidx
    )

    def cond_fn(carry):
        _, it, delta = carry
        return jnp.logical_and(it < max_iters, delta > tol)

    def body_fn(carry):
        x, it, _ = carry
        x1 = _simplex_project(x - lr * (cost_eff + mu * x), support, m, jidx)
        delta = jnp.max(jnp.abs(x1 - x) / jnp.maximum(m, 1.0)[:, None])
        return (x1, it + jnp.int32(1), delta)

    x, iters, delta = jax.lax.while_loop(
        cond_fn, body_fn,
        (x0, jnp.int32(0), jnp.asarray(np.inf, dtype=jnp.float32)),
    )
    converged = delta <= tol

    # -- crossover to a basic solution ----------------------------------------
    # The regularized optimum spreads each class over a ``mu * m``-wide cost
    # neighborhood of its best cell (that spread is what made the iteration
    # contractive).  The underlying LINEAR program is separable per class, so
    # its optimal vertex is the argmin-cost supported cell — move the whole
    # class there.  ``cost_eff`` keeps the argmin deterministic on plateaus
    # (rank epsilon), and the linear cost of the vertex is <= the linear cost
    # of ANY feasible x, so crossover never loses fleet cost; it only undoes
    # the quadratic term's spreading before rounding (spread mass rounds into
    # partially-filled nodes).  Standard LP-relaxation practice: solve the
    # smoothed program for a convergence certificate, cross over to a vertex.
    jstar = jnp.argmin(jnp.where(support, cost_eff, BIG), axis=1)  # i32[C]
    onehot = (
        jnp.arange(n_cells, dtype=jnp.int32)[None, :] == jstar[:, None]
    ).astype(jnp.float32)
    x = jnp.where(
        support.any(axis=1)[:, None], m[:, None] * onehot * support, x
    )
    relaxed_cost = jnp.sum(jnp.where(support, cost * x, 0.0))

    # -- deterministic rounding: floors + largest-fraction-first --------------
    x_r = x * _FLOOR_SHAVE
    n0f = jnp.floor(x_r)
    frac = x_r - n0f
    n0 = n0f.astype(jnp.int32)
    deficit = jnp.clip(counts - jnp.sum(n0, axis=1), 0, None)  # i32[C]
    fq = jnp.floor(frac * _FRAC_Q).astype(jnp.int32)
    fq = jnp.where(support, fq, jnp.int32(-1))  # off-support sorts last
    perm = jax.random.permutation(
        jax.random.PRNGKey(seed.astype(jnp.uint32)), n_cells
    ).astype(jnp.int32)
    # stable two-key sort: permute columns into the seeded tie order, then a
    # stable descending-fraction argsort — ties resolve in seeded-rank order
    fq_p = jnp.take(fq, perm, axis=1)  # [C,S]
    ordb = jnp.argsort(-fq_p, axis=1)  # stable
    cells_sorted = jnp.take(perm, ordb)  # [C,S] cell index at each take rank
    take_sorted = (
        jnp.arange(n_cells, dtype=jnp.int32)[None, :] < deficit[:, None]
    ).astype(jnp.int32)
    add = jnp.zeros_like(n0).at[
        jnp.arange(n_classes, dtype=jnp.int32)[:, None], cells_sorted
    ].add(take_sorted)
    n_round = (n0 + add) * support.astype(jnp.int32)  # i32[C,S]

    # -- exact feasibility audit at the chosen template -----------------------
    # independently recombine the EXACT predicate planes (offering existence
    # from it_avail, not the priced objective sheet) and re-gather at tstar:
    # a placement survives only if the scan kernel's own predicates admit it
    offer_exact = (
        jnp.einsum(
            "ctk,izk->ctiz",
            t_ct.astype(jnp.bfloat16),
            statics.it_avail.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
        > 0.5
    )  # [C,T,I,Z]
    audit_plane = base_ti[:, :, :, None] & t_zone[:, :, None, :] & offer_exact
    tsel = tstar.reshape(n_classes, n_cells)
    audit_at = jnp.take_along_axis(
        audit_plane.reshape(n_classes, n_tmpl, n_cells),
        tsel[:, None, :], axis=1,
    )[:, 0]  # [C,S]
    viol = (n_round > 0) & ~audit_at
    violations = jnp.sum(jnp.where(viol, n_round, 0))
    n_ok = jnp.where(viol, 0, n_round)

    # -- materialize: cells -> node slots -------------------------------------
    pp_cell = jnp.take_along_axis(
        jnp.broadcast_to(
            per_pod[:, :, :, None], (n_classes, n_tmpl, n_it, n_zones)
        ).reshape(n_classes, n_tmpl, n_cells),
        tsel[:, None, :], axis=1,
    )[:, 0]  # i32[C,S]
    ppg = jnp.clip(pp_cell.reshape(n_total), 1, np.int32(10 ** 6))
    # materialize only the pods that fill WHOLE nodes at their cell's
    # per-node intake.  The sub-node tail of each class joins ``leftover``
    # and rides the exact repair pass instead, where the scan kernel can
    # bin-pack the tails of DIFFERENT classes onto shared nodes — a
    # per-class materializer cannot co-locate, and a partially-filled node
    # per class is exactly the fleet-cost gap vs the greedy scan.
    ncell = (n_ok.reshape(n_total) // ppg) * ppg
    nodes_g = ncell // ppg
    cum = jnp.cumsum(nodes_g)
    offs = cum - nodes_g
    total_nodes = jnp.sum(nodes_g)
    used_slots = jnp.minimum(total_nodes, n_slots).astype(jnp.int32)
    avail_nodes = jnp.clip(n_slots - offs, 0, nodes_g)
    placed_g = jnp.minimum(ncell, avail_nodes * ppg)
    placed_c = jnp.sum(placed_g.reshape(n_classes, n_cells), axis=1)
    leftover = jnp.maximum(cls.count - placed_c, 0).astype(jnp.int32)
    spilled = jnp.sum(ncell) - jnp.sum(placed_g)

    slots = jnp.arange(n_slots, dtype=jnp.int32)
    gid = jnp.searchsorted(cum, slots, side="right").astype(jnp.int32)
    sel = slots < used_slots
    gidc = jnp.clip(gid, 0, n_total - 1)
    rank = slots - offs[gidc]
    a = jnp.where(
        sel, jnp.clip(ncell[gidc] - rank * ppg[gidc], 0, ppg[gidc]), 0
    ).astype(jnp.int32)
    c_s = gidc // n_cells
    s_s = gidc - c_s * n_cells
    i_s = s_s // n_zones
    z_s = s_s - i_s * n_zones
    t_s = tsel.reshape(n_total)[gidc]

    km = merged.mask[c_s, t_s]  # [N, K, W]
    kd = merged.defined[c_s, t_s]
    kn = merged.negative[c_s, t_s]
    kg = merged.gt[c_s, t_s]
    kl = merged.lt[c_s, t_s]
    zone_hot = jnp.arange(n_zones, dtype=jnp.int32)[None, :] == z_s[:, None]
    ct_row = t_ct[c_s, t_s]  # [N, CT]
    feas_row = feas[c_s, t_s]  # [N, I, Z]
    feas_z = jnp.take_along_axis(feas_row, z_s[:, None, None], axis=2)[:, :, 0]
    pp_row = per_pod[c_s, t_s]  # [N, I]
    viable_row = feas_z & (pp_row >= a[:, None])
    used_row = statics.tmpl_daemon[t_s] + a[:, None].astype(jnp.float32) * cls.requests[c_s]

    kmask0 = jnp.broadcast_to(
        jnp.asarray(mask_ops.full_words(width)),
        (n_slots, n_keys, mask_ops.words_for(width)),
    )
    state = solve_ops.NodeState(
        used=jnp.where(sel[:, None], used_row, 0.0),
        kmask=jnp.where(sel[:, None, None], km, kmask0),
        kdef=jnp.where(sel[:, None], kd, False),
        kneg=jnp.where(sel[:, None], kn, False),
        kgt=jnp.where(sel[:, None], kg, -jnp.inf).astype(jnp.float32),
        klt=jnp.where(sel[:, None], kl, jnp.inf).astype(jnp.float32),
        zone=jnp.where(sel[:, None], zone_hot, True),
        ct=jnp.where(sel[:, None], ct_row, True),
        viable=jnp.where(sel[:, None], viable_row, True),
        ports=jnp.zeros((n_slots, n_ports), dtype=bool),
        pod_count=a,
        tmpl_id=jnp.where(sel, t_s, 0).astype(jnp.int32),
        open_=sel & (a > 0),
        n_next=used_slots,
    )
    assign = jnp.where(
        (jnp.arange(n_classes, dtype=jnp.int32)[:, None] == c_s[None, :])
        & sel[None, :],
        a[None, :],
        0,
    ).astype(jnp.int32)

    return RelaxResult(
        assign=assign,
        state=state,
        leftover=leftover,
        iters=iters,
        converged=converged,
        violations=violations.astype(jnp.int32),
        placed=jnp.sum(placed_g).astype(jnp.int32),
        spilled=spilled.astype(jnp.int32),
        relaxed_cost=relaxed_cost,
    )


_relax_jit = functools.partial(
    jax.jit,
    static_argnames=("n_slots", "key_has_bounds"),
)(relax_core)
