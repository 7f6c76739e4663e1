"""The multi-tenant solver plane: admission, coalescing, sessions, isolation.

"Millions of users" is tens of thousands of clusters sharing one solver
fleet (ROADMAP; Tesserae is the scale frame).  This module is the robustness
layer that makes the sharing safe — one tenant's poison snapshot, slow
client, or burst must not take down the other N−1:

  admission     Every tenant request passes an ``AdmissionController``:
                a per-tenant token bucket (``utils/retry.RetryBudget``) plus
                a bounded global in-flight cap.  Past either bound the
                request is SHED — an explicit RESOURCE_EXHAUSTED response
                carrying a retry-after hint (the bucket's exact refill time,
                escalated by a per-tenant ``Backoff`` while the tenant keeps
                hammering) — instead of queueing without bound behind the
                worker pool.

  coalescing    Compatible requests batch into ONE device solve: tenants
                whose prepared planes share a shape bucket (the compile
                cache's padding makes this the common case) stack on a
                leading tenant axis and run a vmapped executable
                (``utils/compilecache.batched_solve_callable``; a mesh
                tenant axis when KC_SOLVER_MESH is on —
                ``parallel/mesh.TENANT_PARTITION_RULES``).  Batch membership
                is fault-contained: a tenant whose snapshot fails validation
                never reaches the batch, and a batch-program fault falls
                back to per-tenant solo runs — so every co-batched tenant's
                outputs are bit-identical to its solo solve, always.

  sessions      A per-tenant ``IncrementalSolveSession`` lineage lives
                server-side under an LRU + TTL eviction policy: steady
                same-supply churn repairs instead of re-solving.  Crash
                recovery is by re-anchor, never by trust: a client claiming
                a lineage this process doesn't hold (server restart, LRU/TTL
                eviction) gets a FULL solve with reason ``session-lost`` —
                no stale lineage ever answers.

  isolation     A per-tenant ``CircuitBreaker``: malformed / oversized
                snapshots and solve faults count against the tenant; past
                the threshold the tenant is isolated (UNAVAILABLE with a
                retry-after) until the breaker's half-open trial readmits
                it.  Other tenants never see the breaker.

Everything observable rides ``/metrics``: per-tenant queue/solve/decode
latency histograms and shed/eject/evict counters (docs/SERVICE.md).  All
timing policy (TTL, breaker windows, bucket refill) goes through the
injected ``utils/clock.Clock`` so FakeClock suites step it deterministically;
latency *measurement* uses the monotonic wall clock (diagnostics, not
policy).
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from karpenter_core_tpu import tracing
from karpenter_core_tpu.metrics import REGISTRY, tenant_label
from karpenter_core_tpu.utils import pipeline as pipeline_mod
from karpenter_core_tpu.utils import retry
from karpenter_core_tpu.utils.clock import Clock

TENANT_QUEUE_LATENCY = REGISTRY.histogram(
    "karpenter_tenant_queue_latency_seconds",
    "Per-tenant time from RPC receipt to solve start (admission + decode).",
    ("tenant",),
)
TENANT_SOLVE_LATENCY = REGISTRY.histogram(
    "karpenter_tenant_solve_latency_seconds",
    "Per-tenant solve time (session decide + device dispatch), coalesced or "
    "solo.",
    ("tenant",),
)
TENANT_DECODE_LATENCY = REGISTRY.histogram(
    "karpenter_tenant_decode_latency_seconds",
    "Per-tenant response decode/assembly time.",
    ("tenant",),
)
TENANT_SHED = REGISTRY.counter(
    "karpenter_tenant_shed_total",
    "Requests shed by admission control, by tenant and reason "
    "(rate / queue / isolated).",
    ("tenant", "reason"),
)
TENANT_EJECTED = REGISTRY.counter(
    "karpenter_tenant_ejected_total",
    "Tenant requests ejected with a structured error, by tenant and reason "
    "(malformed / oversized / solve-fault).",
    ("tenant", "reason"),
)
TENANT_SESSIONS_EVICTED = REGISTRY.counter(
    "karpenter_tenant_sessions_evicted_total",
    "Server-side tenant sessions evicted, by reason (lru / ttl).",
    ("reason",),
)
TENANT_SESSIONS_LIVE = REGISTRY.gauge(
    "karpenter_tenant_sessions_live",
    "Server-side tenant sessions currently resident.",
)
TENANT_BATCHES = REGISTRY.counter(
    "karpenter_tenant_batches_total",
    "Coalesced tenant solves dispatched, by batch size (1 = solo).",
    ("size",),
)
TENANT_ADMITTED = REGISTRY.counter(
    "karpenter_tenant_admitted_total",
    "Tenant requests accepted by admission control, by tenant.",
    ("tenant",),
)
TENANT_RETRY_AFTER = REGISTRY.histogram(
    "karpenter_tenant_retry_after_seconds",
    "Retry-after hints handed to shed tenant requests, by tenant.",
    ("tenant",),
    buckets=[0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60],
)
TENANT_DISPATCH = REGISTRY.counter(
    "karpenter_tenant_dispatch_total",
    "Tenant solve dispatches, by tenant and mode (coalesced / solo).",
    ("tenant", "mode"),
)
TENANT_REPAIR_DISPATCH = REGISTRY.counter(
    "karpenter_tenant_repair_dispatch_total",
    "Tenant delta/repair solve dispatches, by tenant and mode (coalesced = "
    "fused with compatible repair windows from other tenants, solo = "
    "unfused; KC_COALESCE_WINDOW=0 forces solo).",
    ("tenant", "mode"),
)
TENANT_SLO_BURN_RATE = REGISTRY.gauge(
    "karpenter_tenant_slo_burn_rate",
    "Multi-window error-budget burn rate over the declared per-tenant solve "
    "latency SLO (KC_TENANT_SLO_SOLVE_S / KC_TENANT_SLO_OBJECTIVE): the "
    "window's bad-solve fraction divided by the budget (1 - objective); "
    "1.0 = burning exactly the budget.",
    ("tenant", "window"),
)

# the shed/isolated detail string clients parse the hint out of
RETRY_AFTER_PREFIX = "retry-after-s="


def parse_retry_after(details: str) -> Optional[float]:
    """The retry-after hint out of a shed/isolated response's detail string,
    or None when absent/unparseable."""
    for token in (details or "").replace(";", " ").split():
        if token.startswith(RETRY_AFTER_PREFIX):
            try:
                return float(token[len(RETRY_AFTER_PREFIX):])
            except ValueError:
                return None
    return None


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def _env_i(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


class SloTracker:
    """Per-tenant multi-window burn rate over a declared solve-latency SLO.

    The SLO is "fraction ``objective`` of solves finish under ``target_s``";
    the burn rate for a window is the window's observed bad fraction divided
    by the error budget (``1 - objective``) — the standard multi-window
    burn-rate alerting shape, so 1.0 means spending the budget exactly and
    14.4 on the short window is a page.  Samples are bounded per tenant and
    tenants are bounded by the metrics label-cardinality guard (overflow
    tenants pool their samples under ``"_other"``)."""

    WINDOWS = (("5m", 300.0), ("1h", 3600.0))
    MAX_SAMPLES = 4096

    def __init__(self, target_s: Optional[float] = None,
                 objective: Optional[float] = None) -> None:
        self.target_s = (
            target_s if target_s is not None
            else max(_env_f("KC_TENANT_SLO_SOLVE_S", 1.0), 1e-6)
        )
        objective = (
            objective if objective is not None
            else _env_f("KC_TENANT_SLO_OBJECTIVE", 0.99)
        )
        self.objective = min(max(objective, 0.0), 0.9999)
        self._lock = threading.Lock()
        # guarded tenant label -> deque[(monotonic_t, was_bad)]
        self._samples: Dict[str, "deque"] = {}

    def observe(self, tenant: str, solve_s: float,
                now: Optional[float] = None) -> None:
        """Record one solve under the guarded tenant label and refresh the
        tenant's burn-rate gauges for every window."""
        now = monotonic() if now is None else now
        bad = solve_s > self.target_s
        budget = 1.0 - self.objective
        with self._lock:
            samples = self._samples.get(tenant)
            if samples is None:
                samples = deque(maxlen=self.MAX_SAMPLES)
                self._samples[tenant] = samples
            samples.append((now, bad))
            horizon = self.WINDOWS[-1][1]
            while samples and now - samples[0][0] > horizon:
                samples.popleft()
            snapshot = list(samples)
        for window, span_s in self.WINDOWS:
            in_window = [b for (t, b) in snapshot if now - t <= span_s]
            if not in_window:
                burn = 0.0
            else:
                burn = (sum(in_window) / len(in_window)) / budget
            TENANT_SLO_BURN_RATE.labels(tenant, window).set(burn)

    def burn(self, tenant: str, window: str = "5m",
             now: Optional[float] = None) -> float:
        """Read one tenant's current burn rate (0.0 when unobserved) — the
        fleet router's load-aware rebalancing signal (fleet/router.py): a
        replica whose tenants burn hottest sheds placements first."""
        now = monotonic() if now is None else now
        span_s = dict(self.WINDOWS).get(window, self.WINDOWS[0][1])
        budget = 1.0 - self.objective
        with self._lock:
            samples = self._samples.get(tenant)
            snapshot = list(samples) if samples else []
        in_window = [b for (t, b) in snapshot if now - t <= span_s]
        if not in_window:
            return 0.0
        return (sum(in_window) / len(in_window)) / budget

    def reset(self) -> None:
        with self._lock:
            self._samples.clear()


# module singleton: TenantPlane.observe_latencies is static (the handler
# calls it without plumbing the plane through), so the tracker lives here
SLO_TRACKER = SloTracker()


# weighted fair-share bounds: a weight outside this band is someone fat-
# fingering an env var or a client inflating itself — clamp, don't trust
WEIGHT_MIN = 0.01
WEIGHT_MAX = 100.0


def parse_weights(spec: str) -> Dict[str, float]:
    """KC_TENANT_WEIGHTS: ``tenant-a=2.0,tenant-b=0.5`` — unparseable parts
    are skipped (a typo must not take admission down)."""
    out: Dict[str, float] = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part or "=" not in part:
            continue
        key, _, value = part.partition("=")
        try:
            out[key.strip()] = min(max(float(value), WEIGHT_MIN), WEIGHT_MAX)
        except ValueError:
            continue
    return out


@dataclass
class TenantConfig:
    """Knobs for the tenant plane; all env-overridable (docs/SERVICE.md)."""

    # admission: per-tenant token bucket (sustained rate + burst) and the
    # bounded global solve queue
    rate_per_s: float = 10.0
    burst: int = 20
    max_inflight: int = 16
    # weighted fair share: per-tenant multipliers on rate AND burst
    # (KC_TENANT_WEIGHTS; the wire envelope's ``weight`` field covers tenants
    # the operator hasn't pinned — env wins, because the serving side owns
    # fairness policy, not the client claiming its own priority)
    weights: Dict[str, float] = field(default_factory=dict)
    # sessions: LRU capacity + idle TTL
    max_sessions: int = 256
    session_ttl_s: float = 900.0
    # isolation: per-tenant breaker
    breaker_threshold: int = 3
    breaker_reset_s: float = 30.0
    # coalescing: rendezvous window + cap (window 0 disables batching)
    batch_window_s: float = 0.01
    max_batch: int = 8
    # solve fusion (docs/SERVICE.md "Solve fusion"): repair/delta dispatches
    # join the coalescer's rendezvous too; KC_COALESCE_WINDOW=0 restores the
    # repairs-always-solo behavior (anchors keep coalescing)
    coalesce_repairs: bool = True
    # request bound: oversized snapshots count against the tenant's breaker
    max_request_bytes: int = 32 * 1024 * 1024
    # True when KC_TENANT_RATE was set explicitly: an operator pin is an
    # absolute per-process statement, so fleet_scaled() must NOT divide it
    # by the fleet size (docs/FLEET.md "Admission")
    rate_pinned: bool = False

    @classmethod
    def from_env(cls) -> "TenantConfig":
        return cls(
            rate_per_s=max(_env_f("KC_TENANT_RATE", 10.0), 0.001),
            rate_pinned="KC_TENANT_RATE" in os.environ,
            burst=max(_env_i("KC_TENANT_BURST", 20), 1),
            max_inflight=max(_env_i("KC_TENANT_QUEUE", 16), 1),
            max_sessions=max(_env_i("KC_TENANT_SESSIONS", 256), 1),
            session_ttl_s=_env_f("KC_TENANT_SESSION_TTL_S", 900.0),
            breaker_threshold=max(_env_i("KC_TENANT_BREAKER_THRESHOLD", 3), 1),
            breaker_reset_s=_env_f("KC_TENANT_BREAKER_RESET_S", 30.0),
            batch_window_s=_env_f("KC_TENANT_BATCH_WINDOW_S", 0.01),
            max_batch=max(_env_i("KC_TENANT_BATCH_MAX", 8), 1),
            coalesce_repairs=os.environ.get("KC_COALESCE_WINDOW", "1") != "0",
            max_request_bytes=max(
                _env_i("KC_TENANT_MAX_BYTES", 32 * 1024 * 1024), 1024
            ),
            weights=parse_weights(os.environ.get("KC_TENANT_WEIGHTS", "")),
        )

    def resolve_weight(self, tenant_id: str, wire_weight=None) -> float:
        """The tenant's fair-share weight: operator env pin wins, then the
        wire envelope's claim, then 1.0 — always clamped."""
        weight = self.weights.get(tenant_id)
        if weight is None:
            try:
                weight = float(wire_weight) if wire_weight is not None else 1.0
            except (TypeError, ValueError):
                weight = 1.0
        return min(max(weight, WEIGHT_MIN), WEIGHT_MAX)

    def bucket_shape(self, weight: float) -> Tuple[int, float]:
        """(budget, window_s) for a weighted tenant bucket: burst scales with
        the weight, and the window is derived from the SCALED budget so the
        refill rate is exactly ``rate_per_s * weight`` even after the burst
        rounds to an int — shed hints stay exact."""
        budget = max(int(round(self.burst * weight)), 1)
        return budget, budget / (self.rate_per_s * weight)

    def fleet_scaled(self, fleet_size: int) -> "TenantConfig":
        """The per-replica backstop shape for an N-replica fleet: the
        fleet-level buckets at the router already enforce the configured
        rate, so each replica grants 1/N of it — N replicas together can
        never over-admit a tenant that bypasses the router, and the fleetless
        (N<=1) config is returned unchanged.  An explicit ``KC_TENANT_RATE``
        pin wins: the operator said per-process, the fleet must not reshape
        it (satellite fix for the historical N× over-admission)."""
        n = int(fleet_size)
        if n <= 1 or self.rate_pinned:
            return self
        import dataclasses

        return dataclasses.replace(
            self,
            rate_per_s=max(self.rate_per_s / n, 0.001),
            burst=max(int(round(self.burst / n)), 1),
        )


@dataclass
class AdmissionDecision:
    admitted: bool
    reason: str = ""  # rate / queue / isolated when not admitted
    retry_after_s: float = 0.0
    # the tenant's entry (so the handler never re-looks it up) and whether
    # THIS admission latched the breaker's half-open trial — a no-verdict
    # exit must release exactly the trial it was granted, never a
    # concurrent request's
    entry: Optional["TenantEntry"] = None
    trial: bool = False

    def detail(self) -> str:
        """The grpc abort detail string (machine-parseable hint included)."""
        return (
            f"tenant-shed reason={self.reason} "
            f"{RETRY_AFTER_PREFIX}{self.retry_after_s:.3f}"
        )


# -- batch coalescing ---------------------------------------------------------


def bucket_key(prep, kw=None) -> tuple:
    """The shape-bucket identity of a SolvePrep: two preps with equal keys
    run the same solve program, so their batches can stack on a tenant axis.
    Mirrors the compile-cache key's static components (docs/SERVICE.md).

    ``kw`` (the dispatch kwargs of a repair solve) extends the key with the
    repair-window identity — the window width (``n_slots`` override) plus the
    warm-carry/repair-plan leaf signatures, mirroring the solo compile
    cache's ``delta`` variant key — so compatible repair windows from
    different tenants stack on one vmapped dispatch (docs/SERVICE.md
    "Solve fusion").  The per-tick ``count`` vector is values-only (its shape
    is already pinned by the cls signature), so it never splits a bucket."""
    from karpenter_core_tpu.utils import compilecache

    key = (
        compilecache.leaf_sig(prep.cls),
        compilecache.leaf_sig(prep.statics_arrays),
        compilecache.leaf_sig(prep.ex_state) if prep.ex_state is not None else None,
        compilecache.leaf_sig(prep.ex_static) if prep.ex_static is not None else None,
        int(prep.n_slots),
        tuple(prep.key_has_bounds),
        int(prep.n_passes),
        tuple(prep.features) if prep.features is not None else None,
    )
    if kw and kw.get("warm_carry") is not None:
        key += (
            "repair",
            int(kw.get("n_slots") or 0) or int(prep.n_slots),
            compilecache.leaf_sig(kw["warm_carry"]),
            compilecache.leaf_sig(kw["repair_plan"])
            if kw.get("repair_plan") is not None else None,
        )
    return key


class _Member:
    __slots__ = ("prep", "solo", "tenant", "kw", "done", "outputs", "error",
                 "batch_n")

    def __init__(self, prep, solo: Callable[[], object],
                 tenant: Optional[str] = None, kw=None) -> None:
        self.prep = prep
        self.solo = solo
        self.tenant = tenant
        self.kw = kw
        self.done = threading.Event()
        self.outputs = None
        self.error: Optional[BaseException] = None
        self.batch_n = 1


class _Group:
    __slots__ = ("members", "full", "closed")

    def __init__(self) -> None:
        self.members: List[_Member] = []
        self.full = threading.Event()
        self.closed = False


class BatchCoalescer:
    """Rendezvous concurrent compatible-bucket solves into one batched
    dispatch.  ``run(prep, solo)`` blocks until this request's outputs exist
    and returns ``(outputs, batch_size)``; ``solo`` is the caller's
    unbatched dispatch (used for singleton groups and as the per-tenant
    fault-containment fallback when a batch program faults)."""

    def __init__(self, window_s: float = 0.01, max_batch: int = 8) -> None:
        self.window_s = window_s
        self.max_batch = max_batch
        self._lock = threading.Lock()
        self._groups: Dict[tuple, _Group] = {}

    def run(self, prep, solo: Callable[[], object],
            tenant: Optional[str] = None, kw=None) -> Tuple[object, int]:
        if self.window_s <= 0 or self.max_batch <= 1:
            return solo(), 1
        key = bucket_key(prep, kw)
        member = _Member(prep, solo, tenant, kw)
        with self._lock:
            group = self._groups.get(key)
            # a full group is as good as closed: the leader may not have
            # woken from full.wait() yet, and appending past max_batch would
            # dispatch an unexpected batch size (fresh compile, uncapped
            # device cost) — late arrivals start the next group instead
            leader = (
                group is None or group.closed
                or len(group.members) >= self.max_batch
            )
            if leader:
                group = _Group()
                group.members.append(member)
                self._groups[key] = group
            else:
                group.members.append(member)
                if len(group.members) >= self.max_batch:
                    group.full.set()
        if not leader:
            # the leader always resolves every member in its finally block
            member.done.wait()
            if member.error is not None:
                raise member.error
            return member.outputs, member.batch_n
        # leader: hold the window open for co-batchers, then dispatch
        group.full.wait(self.window_s)
        with self._lock:
            group.closed = True
            if self._groups.get(key) is group:
                del self._groups[key]
            members = list(group.members)
        try:
            self._execute(members)
        finally:
            for m in members:
                m.done.set()
        if member.error is not None:
            raise member.error
        return member.outputs, member.batch_n

    def _execute(self, members: List[_Member]) -> None:
        TENANT_BATCHES.labels(str(len(members))).inc()
        if len(members) == 1:
            m = members[0]
            try:
                m.outputs = m.solo()
            except BaseException as e:  # noqa: BLE001 - routed to the caller
                m.error = e
            return
        try:
            outs = self._run_batched(
                [m.prep for m in members],
                tenants=[m.tenant for m in members if m.tenant is not None],
                kws=[m.kw for m in members],
            )
        except BaseException:  # noqa: BLE001 - batch fault: contain per tenant
            # fault containment: the batch PROGRAM faulted (device error,
            # chaos) — nothing tenant-attributable yet.  Re-run each member
            # solo: tenants whose solves are healthy still get their exact
            # answers; the faulty one surfaces its own error.
            for m in members:
                try:
                    m.outputs = m.solo()
                    m.batch_n = 1
                except BaseException as e:  # noqa: BLE001 - per-tenant verdict
                    m.error = e
            return
        for m, out in zip(members, outs):
            m.outputs = out
            m.batch_n = len(members)

    @staticmethod
    def _run_batched(preps, tenants=None, kws=None) -> List[object]:
        """One vmapped device dispatch over the stacked preps; returns
        per-tenant output slices (bit-identical to solo solves).  ``tenants``
        (optional member tenant ids, dispatch order) rides the span so a
        server-side trace names who co-batched.  ``kws`` (per-member dispatch
        kwargs, aligned with ``preps``) carries repair dispatches: members
        with a ``warm_carry`` stack their per-tick count vectors, synthesized
        ex-static planes, warm carries, and repair plans as batch leaves and
        run the vmapped REPAIR executable — the rendezvous key (bucket_key's
        repair extension) guarantees every member of one group agrees on the
        variant and the window width."""
        import jax

        from karpenter_core_tpu.parallel import mesh as mesh_mod
        from karpenter_core_tpu.utils import compilecache

        p0 = preps[0]
        kws = kws if kws is not None else [None] * len(preps)
        kw_of = lambda i: kws[i] or {}  # noqa: E731 - local accessor
        kw0 = kw_of(0)
        has_warm = kw0.get("warm_carry") is not None
        has_ex = p0.ex_state is not None and not has_warm
        n_slots = int(kw0.get("n_slots") or 0) or int(p0.n_slots)

        def stack(trees):
            return jax.tree_util.tree_map(
                lambda *ls: np.stack([np.asarray(x) for x in ls]), *trees
            )

        def member_cls(p, kw):
            count = kw.get("count")
            if count is None:
                return p.cls
            return p.cls._replace(count=np.asarray(count, dtype=np.int32))

        cls_list = [member_cls(p, kw_of(i)) for i, p in enumerate(preps)]
        # coalesced occupancy: the preps arrive bucket-padded, so the real
        # row count is recovered from the count vector (padded rows never
        # carry pods; a repair's count holds only this tick's delta pods) —
        # one ledger entry for the whole stacked dispatch
        padded_rows = int(np.asarray(p0.cls.count).shape[0])
        real_rows = sum(
            int(np.count_nonzero(np.asarray(c.count))) for c in cls_list
        ) / len(preps)
        compilecache.record_batch_occupancy(
            real_rows, padded_rows, n_slots, n_passes=p0.n_passes,
            mesh_axes=mesh_mod.tenant_mesh_axes(len(preps)),
            tenants=len(preps),
        )
        with tracing.span("solve.coalesced", tenants=len(preps),
                          n_slots=n_slots, repair=has_warm,
                          tenant=",".join(tenants) if tenants else None):
            args = [stack(cls_list),
                    stack([p.statics_arrays for p in preps])]
            ex_static0 = p0.ex_static
            if has_warm:
                from karpenter_core_tpu.ops import solve as solve_ops

                # the warm variant always takes the ex-static planes;
                # synthesize the empty ones exactly as the solo
                # run_prepared does for preps that never had a fleet
                ex_statics = []
                for p in preps:
                    if p.ex_static is not None:
                        ex_statics.append(p.ex_static)
                    else:
                        ex_statics.append(solve_ops.empty_existing_static(
                            p.cls.requests.shape[-1], p.cls.count.shape[0],
                            p.statics_arrays.grp_skew.shape[0],
                        ))
                ex_static0 = ex_statics[0]
                args.append(stack(ex_statics))
                args.append(stack([kw_of(i)["warm_carry"]
                                   for i in range(len(preps))]))
                args.append(stack([kw_of(i)["repair_plan"]
                                   for i in range(len(preps))]))
            elif has_ex:
                args.append(stack([p.ex_state for p in preps]))
                args.append(stack([p.ex_static for p in preps]))
            mesh_axes = mesh_mod.tenant_mesh_axes(len(preps))
            fn = compilecache.batched_solve_callable(
                len(preps), cls_list[0], p0.statics_arrays, n_slots,
                p0.key_has_bounds, None if has_warm else p0.ex_state,
                ex_static0, p0.n_passes, p0.features, mesh_axes,
                warm_carry=kw0.get("warm_carry"),
                repair_plan=kw0.get("repair_plan"),
            )
            if mesh_axes is not None:
                mesh = mesh_mod.mesh_for(mesh_axes)
                args = [
                    jax.device_put(a, mesh_mod.tenant_mesh_shardings(a, mesh))
                    for a in args
                ]
            # ONE batched fetch of the stacked outputs, sliced per tenant on
            # the host: decode consumes every plane anyway, and host slicing
            # avoids compiling a per-leaf-per-index gather op on device.
            # The fetch goes through the pipeline helper — async copies on
            # every leaf first, then one device_get — so the NEXT coalesced
            # group's dispatch (another worker thread) overlaps this group's
            # device→host copy instead of queueing behind per-array blocking
            # transfers (docs/KERNEL_PERF.md "Layer 7").
            outs = pipeline_mod.fetch_tree(fn(*args), site="tenant.batch")
            return [
                jax.tree_util.tree_map(lambda a, i=i: a[i], outs)
                for i in range(len(preps))
            ]


# -- per-tenant state ---------------------------------------------------------


@dataclass
class TenantEntry:
    """Everything the plane keeps per tenant."""

    tenant_id: str
    session: object  # solver.incremental.IncrementalSolveSession
    breaker: retry.CircuitBreaker
    bucket: retry.RetryBudget
    shed_backoff: retry.Backoff
    # re-entrant: the serve path holds it across the whole solve, and the
    # dispatch hook / checkpoint plane re-take it for their own field access
    # so every entry-field touch is lexically locked (shared-state pass)
    lock: threading.RLock = field(default_factory=threading.RLock)
    last_seen: float = 0.0
    supply_digest: Optional[str] = None
    last_batched: int = 1
    # weighted fair share: the resolved weight this entry's bucket was shaped
    # for (a change reshapes the bucket in place)
    weight: float = 1.0
    # durable sessions (service/journal.py): per-tenant record sequence (0 at
    # each anchor, +1 per delta) and the one-shot recovery echo ("warm")
    # surfaced on the first post-recovery response
    journal_tseq: int = 0
    recovered: Optional[str] = None
    # fleet checkpoints (fleet/checkpoint.py): the raw wire bytes of the last
    # FULL-solve request and its class-identity digests — what a peer replica
    # re-decodes to rebuild this lineage's snapshot — plus the solves-since-
    # last-checkpoint cadence counter
    anchor_request: Optional[bytes] = None
    anchor_uid_bases: Tuple[str, ...] = ()
    ckpt_ticks: int = 0
    # per-entry coalescer bypass: a recovery/failover replay dispatches THIS
    # tenant's solves solo (no rendezvous waits) without degrading concurrent
    # tenants' batching — the per-request property the dispatch hook reads
    # (the old plane-wide flag was racy under concurrent tenant requests)
    bypass_coalescer: bool = False


class TenantPlane:
    """Admission + sessions + breakers + the coalescer, as one unit the
    service owns.  Thread-safe; ``clock`` drives every timing POLICY (TTL,
    breaker reset, bucket refill) so FakeClock suites are deterministic."""

    def __init__(self, clock: Optional[Clock] = None,
                 config: Optional[TenantConfig] = None) -> None:
        self.clock = clock or Clock()
        self.config = config or TenantConfig.from_env()
        self.coalescer = BatchCoalescer(
            self.config.batch_window_s, self.config.max_batch
        )
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, TenantEntry]" = OrderedDict()
        self._inflight = 0
        self._last_sweep = self.clock.now()
        # graceful drain (docs/SERVICE.md): set, every admission sheds with a
        # retry-after hint while in-flight solves finish
        self._draining = False
        self._drain_hint_s = 5.0
        # session-drop hook: the durable-session journal records evictions so
        # recovery never resurrects a dropped lineage
        self.on_drop: Optional[Callable[[str], None]] = None
        # plane-wide coalescer bypass: only the pre-traffic restart recovery
        # sets it (no concurrent requests exist yet).  Replays DURING traffic
        # use the per-entry TenantEntry.bypass_coalescer instead — a
        # plane-wide flip would race concurrent tenants out of their batches.
        self._bypass_coalescer = False

    # -- session lifecycle -----------------------------------------------------

    def _new_entry(self, tenant_id: str, weight: float = 1.0) -> TenantEntry:
        from karpenter_core_tpu.solver.incremental import (
            FallbackPolicy,
            IncrementalSolveSession,
        )

        cfg = self.config
        budget, window_s = cfg.bucket_shape(weight)
        entry = TenantEntry(
            tenant_id=tenant_id,
            session=None,
            breaker=retry.CircuitBreaker(
                self.clock,
                failure_threshold=cfg.breaker_threshold,
                reset_timeout_s=cfg.breaker_reset_s,
                name=f"tenant:{tenant_id}",
            ),
            bucket=retry.RetryBudget(
                self.clock, budget=budget,
                window_s=window_s,
                name=f"tenant:{tenant_id}",
            ),
            shed_backoff=retry.Backoff(0.25, 30.0),
            last_seen=self.clock.now(),
            weight=weight,
        )
        session = IncrementalSolveSession(
            policy=FallbackPolicy.from_env(),
            run_prepared=lambda prep, **kw: self._dispatch(entry, prep, **kw),
        )
        entry.session = session
        return entry

    def _dispatch(self, entry: TenantEntry, prep, **kw):
        """The session's dispatch hook: plain full solves AND repair/delta
        dispatches (``warm_carry`` + ``repair_plan`` kwargs) are coalescing
        candidates — compatible repair windows from different tenants fuse on
        one vmapped dispatch (docs/SERVICE.md "Solve fusion").  Anything else
        parameterized (the bare slot-exhaustion retry) dispatches solo, as
        does a replaying entry (``bypass_coalescer`` — per entry, so one
        tenant's recovery replay never degrades concurrent tenants)."""
        solver = entry.session.solver
        tenant = tenant_label(entry.tenant_id)
        is_repair = (
            kw.get("warm_carry") is not None
            and kw.get("repair_plan") is not None
        )
        with entry.lock:
            bypass = entry.bypass_coalescer or self._bypass_coalescer
        fusable = not kw or (is_repair and self.config.coalesce_repairs)
        if bypass or not fusable:
            TENANT_DISPATCH.labels(tenant, "solo").inc()
            if is_repair:
                TENANT_REPAIR_DISPATCH.labels(tenant, "solo").inc()
            return solver.run_prepared(prep, **kw)
        outputs, batched = self.coalescer.run(
            prep, lambda: solver.run_prepared(prep, **kw),
            tenant=entry.tenant_id, kw=kw or None,
        )
        with entry.lock:
            entry.last_batched = batched
        mode = "coalesced" if batched > 1 else "solo"
        TENANT_DISPATCH.labels(tenant, mode).inc()
        if is_repair:
            TENANT_REPAIR_DISPATCH.labels(tenant, mode).inc()
        return outputs

    def checkout(self, tenant_id: str, weight: Optional[float] = None) -> TenantEntry:
        """The tenant's entry (created on first sight), LRU-touched; expired
        and over-capacity sessions are evicted on the way.  ``weight`` is the
        resolved fair-share weight (None = default); a change reshapes the
        entry's bucket in place, carrying the current fill proportionally."""
        now = self.clock.now()
        with self._lock:
            self._sweep_locked(now)
            entry = self._entries.get(tenant_id)
            if entry is None:
                entry = self._new_entry(
                    tenant_id, weight if weight is not None else 1.0
                )
                self._entries[tenant_id] = entry
                while len(self._entries) > self.config.max_sessions:
                    evicted_id, evicted = self._entries.popitem(last=False)
                    self._drop_entry(evicted, "lru")
            else:
                self._entries.move_to_end(tenant_id)
                if weight is not None and abs(weight - entry.weight) > 1e-9:
                    budget, window_s = self.config.bucket_shape(weight)
                    entry.bucket.reconfigure(budget, window_s)
                    entry.weight = weight
            entry.last_seen = now
            TENANT_SESSIONS_LIVE.labels().set(float(len(self._entries)))
            return entry

    def restore_entry(self, tenant_id: str) -> TenantEntry:
        """A fresh entry for journal recovery (service/journal.py): no
        admission, no LRU touch beyond registration — the restored lineage is
        attached by the caller after replay verifies."""
        with self._lock:
            entry = self._new_entry(
                tenant_id, self.config.resolve_weight(tenant_id)
            )
            self._entries[tenant_id] = entry
            while len(self._entries) > self.config.max_sessions:
                _evicted_id, evicted = self._entries.popitem(last=False)
                self._drop_entry(evicted, "lru")
            TENANT_SESSIONS_LIVE.labels().set(float(len(self._entries)))
            return entry

    def entries_snapshot(self) -> Dict[str, TenantEntry]:
        """A point-in-time copy of the resident tenant map (drain-time fleet
        checkpointing iterates it without holding the plane lock)."""
        with self._lock:
            return dict(self._entries)

    def discard_entry(self, tenant_id: str) -> None:
        """Remove a tenant whose recovery replay failed verification — the
        next request re-anchors ``session-lost`` exactly as if nothing had
        been journaled."""
        with self._lock:
            entry = self._entries.pop(tenant_id, None)
            if entry is not None:
                retry.BREAKER_STATE.delete_labels(f"tenant:{tenant_id}")
            TENANT_SESSIONS_LIVE.labels().set(float(len(self._entries)))

    def _sweep_locked(self, now: float) -> None:
        ttl = self.config.session_ttl_s
        if ttl <= 0:
            return
        # cadence-bound: a full scan per checkout would serialize every
        # tenant on the plane lock for O(resident sessions) work several
        # times per RPC — expiry only needs to be caught within a fraction
        # of the TTL, not on every access
        if now - self._last_sweep < max(ttl / 8.0, 1.0):
            return
        self._last_sweep = now
        expired = [
            tid for tid, e in self._entries.items() if now - e.last_seen > ttl
        ]
        for tid in expired:
            self._drop_entry(self._entries.pop(tid), "ttl")

    def _drop_entry(self, entry: TenantEntry, reason: str) -> None:
        TENANT_SESSIONS_EVICTED.labels(reason).inc()
        # the breaker gauge would otherwise report a dead tenant forever
        retry.BREAKER_STATE.delete_labels(f"tenant:{entry.tenant_id}")
        # journal the drop (enqueue-only; no lock ordering hazard: the
        # journal never takes plane locks) so recovery cannot resurrect an
        # evicted lineage
        if self.on_drop is not None:
            self.on_drop(entry.tenant_id)

    def sessions(self) -> List[str]:
        with self._lock:
            return list(self._entries)

    # -- admission -------------------------------------------------------------

    def start_draining(self, retry_after_s: float = 5.0) -> None:
        """Graceful drain: every subsequent admission sheds with this
        retry-after hint; in-flight solves finish normally."""
        self._drain_hint_s = max(retry_after_s, 0.1)
        self._draining = True

    def admit(self, tenant_id: str, weight=None) -> AdmissionDecision:
        """Admission gate; an admitted request MUST be paired with
        ``release()``.  Order: draining → isolation (breaker) → global
        in-flight bound → per-tenant rate.  The queue check runs BEFORE the
        token bucket so global pressure caused by OTHER tenants never burns
        this tenant's own tokens (a queue-shed retry must not escalate into
        a rate shed).  ``weight`` is the wire envelope's fair-share claim
        (config.resolve_weight decides; an operator env pin wins)."""
        tenant = tenant_label(tenant_id)
        if self._draining:
            # no checkout: a draining server must not mint fresh sessions
            TENANT_SHED.labels(tenant, "draining").inc()
            TENANT_RETRY_AFTER.labels(tenant).observe(self._drain_hint_s)
            return AdmissionDecision(False, "draining", self._drain_hint_s)
        entry = self.checkout(
            tenant_id, weight=self.config.resolve_weight(tenant_id, weight)
        )
        if not entry.breaker.allow():
            hint = max(entry.breaker.reset_timeout_s, 1.0)
            TENANT_SHED.labels(tenant, "isolated").inc()
            TENANT_RETRY_AFTER.labels(tenant).observe(hint)
            return AdmissionDecision(False, "isolated", hint, entry=entry)
        granted_trial = entry.breaker.state == retry.HALF_OPEN
        with self._lock:
            queued = self._inflight >= self.config.max_inflight
            if not queued:
                self._inflight += 1
        if queued:
            if granted_trial:
                entry.breaker.release_trial()  # shed ≠ a backend verdict
            TENANT_SHED.labels(tenant, "queue").inc()
            hint = max(entry.shed_backoff.next(), 0.25)
            TENANT_RETRY_AFTER.labels(tenant).observe(hint)
            return AdmissionDecision(False, "queue", hint, entry=entry)
        if not entry.bucket.allow():
            with self._lock:
                self._inflight = max(0, self._inflight - 1)
            if granted_trial:
                entry.breaker.release_trial()
            hint = max(entry.bucket.next_token_s(), 0.05)
            # repeated sheds escalate the hint so a hammering client backs
            # off harder each time (reset on the next successful admit)
            hint = max(hint, entry.shed_backoff.next())
            TENANT_SHED.labels(tenant, "rate").inc()
            TENANT_RETRY_AFTER.labels(tenant).observe(hint)
            return AdmissionDecision(False, "rate", hint, entry=entry)
        entry.shed_backoff.reset()
        TENANT_ADMITTED.labels(tenant).inc()
        return AdmissionDecision(True, entry=entry, trial=granted_trial)

    def release(self, tenant_id: str) -> None:
        with self._lock:
            self._inflight = max(0, self._inflight - 1)

    def inflight(self) -> int:
        with self._lock:
            return self._inflight

    # -- fault accounting ------------------------------------------------------

    def record_bad_request(self, entry: TenantEntry, reason: str) -> None:
        """Malformed / oversized snapshot: tenant-attributable, breaker
        counts it toward isolation."""
        TENANT_EJECTED.labels(tenant_label(entry.tenant_id), reason).inc()
        entry.breaker.record_failure()

    def record_fault(self, entry: TenantEntry) -> None:
        """This tenant's solve faulted (ejected from its batch)."""
        TENANT_EJECTED.labels(tenant_label(entry.tenant_id), "solve-fault").inc()
        entry.breaker.record_failure()

    def record_timeout(self, entry: TenantEntry) -> None:
        """This tenant's solve overran its watchdog deadline (a structured
        ejection, docs/SERVICE.md "Timeout ejection"): the abandoned device
        call never wedges the worker, and the tenant breaker counts it — a
        tenant whose snapshots reliably hang the backend isolates exactly
        like one whose snapshots fault it."""
        TENANT_EJECTED.labels(
            tenant_label(entry.tenant_id), "watchdog-timeout"
        ).inc()
        entry.breaker.record_failure()

    def record_ok(self, entry: TenantEntry) -> None:
        entry.breaker.record_success()

    # -- latency observation (diagnostic wall time, not policy) ---------------

    @staticmethod
    def observe_latencies(tenant_id: str, queue_s: float, solve_s: float,
                          decode_s: float) -> None:
        tenant = tenant_label(tenant_id)
        TENANT_QUEUE_LATENCY.labels(tenant).observe(max(queue_s, 0.0))
        TENANT_SOLVE_LATENCY.labels(tenant).observe(max(solve_s, 0.0))
        TENANT_DECODE_LATENCY.labels(tenant).observe(max(decode_s, 0.0))
        SLO_TRACKER.observe(tenant, max(solve_s, 0.0))


def monotonic() -> float:
    """Latency measurement clock (diagnostics only — timing POLICY goes
    through the injected utils/clock.Clock)."""
    return time.perf_counter()
