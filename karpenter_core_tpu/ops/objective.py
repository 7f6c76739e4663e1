"""Batched objective kernel: score and select among feasible offerings.

Runs AFTER ``ops.solve.solve_core`` feasibility: the solve's per-node planes
(viable instance types, surviving zone / capacity-type masks) define each new
node's feasible offering cells, and this kernel scores every cell with the
policy objective and argmin-selects one offering per node in a single
vectorized pass — the batched form of the host helpers that today answer the
same question one node at a time (``Offerings.cheapest``,
``worst_launch_price``).

Objective of one (instance type i, zone z, capacity type ct) cell:

    expected[i,z,ct] = price[i,z,ct] * (1 + risk_aversion * risk[i,z,ct])
    score[i,z,ct]    = cost_weight * expected[i,z,ct]
                       - throughput_weight * throughput[i]

Selection semantics (parity-pinned in tests/test_policy.py):

  - default weights (cost 1, risk 0, throughput 0) reduce the score to the
    offering price, so the selected price equals ``Offerings.cheapest()``
    over the node's feasible offering set — the host oracle, exactly;
  - exact score ties prefer spot when ``spot_preference`` is set (the host
    convention: ``worst_launch_price`` consults spot before on-demand and
    consolidation pins spot when both survive), then break deterministically
    by (instance-type index, zone index, capacity-type index) — the same
    stable order the catalog encode fixed.

Everything here is trace-safe device code; the host-facing entry
(``select_for_state``) builds the weight scalars from a PolicyConfig and
returns numpy-backed selections for decode to stamp onto node decisions.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ObjectiveWeights(NamedTuple):
    """Traced scalar knobs — traced (not static) so weight changes reuse the
    compiled executable; shapes alone key the jit cache."""

    cost_weight: jnp.ndarray  # f32[]
    throughput_weight: jnp.ndarray  # f32[]
    risk_aversion: jnp.ndarray  # f32[]
    spot_preference: jnp.ndarray  # bool[]


class ObjectiveSelection(NamedTuple):
    """Per-new-node-slot argmin selection (leading dim N)."""

    sel_it: jnp.ndarray  # i32[N] selected instance-type index
    sel_zone: jnp.ndarray  # i32[N]
    sel_ct: jnp.ndarray  # i32[N]
    price: jnp.ndarray  # f32[N] raw offering price at the selection
    expected: jnp.ndarray  # f32[N] risk-weighted expected cost
    active: jnp.ndarray  # bool[N] open, pod-carrying, selectable slots
    fleet_cost: jnp.ndarray  # f32[] sum of selected prices over active slots
    fleet_expected: jnp.ndarray  # f32[] risk-weighted fleet cost


def weights_of(config) -> ObjectiveWeights:
    return ObjectiveWeights(
        cost_weight=jnp.float32(config.cost_weight),
        throughput_weight=jnp.float32(config.throughput_weight),
        risk_aversion=jnp.float32(config.risk_aversion),
        spot_preference=jnp.asarray(bool(config.spot_preference)),
    )


def cell_scores(price, risk, throughput, weights: ObjectiveWeights):
    """(expected f32[I,Z,CT], score f32[I,Z,CT]) of every offering cell —
    shared by selection here and by the risk-weighted replica studies in
    parallel.mesh."""
    expected = price * (1.0 + weights.risk_aversion * risk)
    score = (
        weights.cost_weight * expected
        - weights.throughput_weight * throughput[:, None, None]
    )
    return expected, score


@jax.jit
def select_offerings(
    viable: jnp.ndarray,  # bool[N, I]
    zone: jnp.ndarray,  # bool[N, Z]
    ct: jnp.ndarray,  # bool[N, CT]
    open_: jnp.ndarray,  # bool[N]
    pod_count: jnp.ndarray,  # i32[N]
    price: jnp.ndarray,  # f32[I, Z, CT] (+inf no offering)
    risk: jnp.ndarray,  # f32[I, Z, CT]
    throughput: jnp.ndarray,  # f32[I]
    is_spot: jnp.ndarray,  # bool[CT]
    weights: ObjectiveWeights,
) -> ObjectiveSelection:
    with jax.named_scope("kc.finish"):
        n = viable.shape[0]
        n_zct = zone.shape[1] * ct.shape[1]
        n_ct = ct.shape[1]
        expected, score = cell_scores(price, risk, throughput, weights)
        allowed = (
            viable[:, :, None, None]
            & zone[:, None, :, None]
            & ct[:, None, None, :]
            & jnp.isfinite(price)[None, :, :, :]
        )
        scored = jnp.where(allowed, score[None], jnp.inf).reshape(n, -1)
        best = jnp.min(scored, axis=1)
        has_any = jnp.isfinite(best)
        # exact-tie set, then the spot-preference filter: among tied cells keep
        # the spot ones when any exist (and the knob is on); argmax then takes
        # the FIRST tied cell in (it, zone, ct) row-major order — deterministic,
        # and matching the catalog's stable index order on full ties
        is_best = scored == best[:, None]
        spot_flat = jnp.broadcast_to(
            is_spot[None, None, :], price.shape
        ).reshape(-1)
        spot_ties = is_best & spot_flat[None, :]
        use_spot = weights.spot_preference & jnp.any(spot_ties, axis=1)
        candidates = jnp.where(use_spot[:, None], spot_ties, is_best)
        sel = jnp.argmax(candidates, axis=1).astype(jnp.int32)
        sel_it = sel // n_zct
        sel_zone = (sel % n_zct) // n_ct
        sel_ct = sel % n_ct
        sel_price = price.reshape(-1)[sel]
        sel_expected = expected.reshape(-1)[sel]
        active = open_ & (pod_count > 0) & has_any
        zero = jnp.float32(0.0)
        fleet_cost = jnp.sum(jnp.where(active, sel_price, zero))
        fleet_expected = jnp.sum(jnp.where(active, sel_expected, zero))
        return ObjectiveSelection(
            sel_it=sel_it,
            sel_zone=sel_zone,
            sel_ct=sel_ct,
            price=sel_price,
            expected=sel_expected,
            active=active,
            fleet_cost=fleet_cost,
            fleet_expected=fleet_expected,
        )


def select_for_state(state, planes, config, capacity_types) -> ObjectiveSelection:
    """Host entry: run the selection kernel over a solve's final NodeState
    with the snapshot's objective planes, returning host-fetched arrays.
    ``capacity_types`` is the snapshot's CT axis (names), spot-detected by
    the well-known label value."""
    from karpenter_core_tpu.apis import labels as labels_api

    is_spot = np.array(
        [name == labels_api.CAPACITY_TYPE_SPOT for name in capacity_types],
        dtype=bool,
    )
    selection = select_offerings(
        state.viable, state.zone, state.ct, state.open_, state.pod_count,
        jnp.asarray(planes.price), jnp.asarray(planes.risk),
        jnp.asarray(planes.throughput), jnp.asarray(is_spot),
        weights_of(config),
    )
    from karpenter_core_tpu.utils import watchdog

    # the objective stage's device→host fetch blocks like every barrier:
    # watchdog-bounded so a quiet device fails the decode, not the process
    return ObjectiveSelection(
        *watchdog.run(
            "pipeline.fetch", jax.device_get, tuple(selection),
            key="objective",
        )
    )
