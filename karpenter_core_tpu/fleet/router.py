"""The fleet router: one thin front door over N solver replicas.

Clients dial the router exactly as they dial a single replica — same
service name, same msgpack wire shapes, same abort grammar.  The router:

  places    each tenant on one replica via the consistent-hash ring
            (fleet/ring.py), liveness-filtered by the lease directory
            (fleet/lease.py), and FORWARDS THE RAW REQUEST BYTES verbatim —
            the PR-12 tenant envelope and the PR-16 trace context cross
            untouched, so replica-side behavior (and the KC_FLEET=0
            byte-identity pin) is preserved by construction
  admits    at fleet level (fleet/admission.py) BEFORE forwarding: the
            router's token buckets are shaped by the unscaled tenant config,
            so N replicas can no longer over-admit N× the configured rate
  fails over on UNAVAILABLE / DEADLINE_EXCEEDED: the placement is dropped,
            the replica's breaker trips, and the request retries on the next
            alive replica of the tenant's arc — which restores the tenant
            WARM from its fleet checkpoint (fleet/checkpoint.py)
  rebalances on a cadence, moving at most ``KC_FLEET_REBALANCE_FRACTION`` of
            tenants per interval off the replica whose tenants burn their
            latency SLO hottest (service/tenant.py SloTracker)

Replica-originated aborts (tenant sheds, precondition failures) pass
through with code AND details intact — retry-after hints survive the hop.
The ``fleet.route`` chaos point injects error / timeout / partial faults on
the forwarding edge for the chaos matrix.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent import futures
from typing import Dict, List, Optional, Set, Tuple

import grpc
import msgpack

from karpenter_core_tpu import chaos, tracing
from karpenter_core_tpu.fleet import FleetLocal
from karpenter_core_tpu.fleet.admission import FleetAdmission
from karpenter_core_tpu.fleet.lease import LeaseDirectory, LeasePlane
from karpenter_core_tpu.fleet.ring import HashRing
from karpenter_core_tpu.metrics import REGISTRY, tenant_label
from karpenter_core_tpu.service import tenant as tenant_mod
from karpenter_core_tpu.utils import retry
from karpenter_core_tpu.utils.clock import Clock

log = logging.getLogger(__name__)

# must match service/snapshot_channel.py SERVICE / CHANNEL_OPTIONS —
# redeclared so a thin router process never imports the solver stack (the
# router forwards the replicas' answers whole, so it needs their size limits)
SERVICE = "karpenter.v1.SnapshotSolver"
CHANNEL_OPTIONS = (
    ("grpc.max_send_message_length", -1),
    ("grpc.max_receive_message_length", 1 << 30),
)

# the router→replica forwarding edge (docs/CHAOS.md): error (replica
# unreachable), timeout (forward deadline), partial (the replica answered
# but the client never saw it — the mid-stream eviction shape)
FLEET_ROUTE = chaos.point("fleet.route")

ROUTED_TOTAL = REGISTRY.counter(
    "karpenter_fleet_routed_total",
    "Router forwarding outcomes: ok, shed (fleet-level rate), failover "
    "(placement moved after an UNAVAILABLE/DEADLINE replica), exhausted "
    "(no alive replica accepted), upstream (replica abort passed through), "
    "chaos-error / chaos-timeout / chaos-partial (injected).",
    ("outcome",),
)
REPLICAS_ALIVE = REGISTRY.gauge(
    "karpenter_fleet_replicas_alive",
    "Fleet replicas currently alive by lease freshness (draining and "
    "lease-expired replicas excluded).",
)
REBALANCED_TOTAL = REGISTRY.counter(
    "karpenter_fleet_rebalanced_total",
    "Tenant placements moved by the router's load-aware rebalancer (at most "
    "KC_FLEET_REBALANCE_FRACTION of tenants per interval).",
)


class FleetRouter(grpc.GenericRpcHandler):
    """grpc generic handler + placement/liveness/rebalance state."""

    def __init__(self, fleet: FleetLocal, *,
                 clock: Optional[Clock] = None,
                 tenant_config: Optional[tenant_mod.TenantConfig] = None) -> None:
        self.fleet = fleet
        self.clock = clock or Clock()
        self.ring = HashRing(fleet.fleet_map)
        self.addresses = fleet.fleet_map.addresses()
        self.lease_plane = LeasePlane(fleet.lease_path())
        self.directory = LeaseDirectory(
            self.lease_plane, clock=self.clock, ttl_s=fleet.lease_ttl_s
        )
        self.admission = FleetAdmission(tenant_config, clock=self.clock)
        self.slo = tenant_mod.SloTracker()
        self.forward_timeout_s = tenant_mod._env_f(
            "KC_FLEET_FORWARD_TIMEOUT_S", 120.0
        )
        self.rebalance_interval_s = tenant_mod._env_f(
            "KC_FLEET_REBALANCE_INTERVAL_S", 30.0
        )
        self.rebalance_fraction = min(max(tenant_mod._env_f(
            "KC_FLEET_REBALANCE_FRACTION", 0.1
        ), 0.0), 1.0)
        self._lock = threading.Lock()
        self._placements: Dict[str, str] = {}
        # channel/stub construction has its own lock: concurrent handler
        # threads race the first use of a replica (duplicate channels, one
        # leaked unclosed), and _lock must stay free for placement scans
        self._stub_lock = threading.Lock()
        self._channels: Dict[str, grpc.Channel] = {}
        self._stubs: Dict[Tuple[str, str], object] = {}
        self._breakers: Dict[str, retry.CircuitBreaker] = {
            rid: retry.CircuitBreaker(
                self.clock, failure_threshold=2,
                reset_timeout_s=max(fleet.heartbeat_s * 2.0, 1.0),
                name=f"fleet-replica:{rid}",
            )
            for rid in fleet.fleet_map.ids()
        }
        self._last_rebalance = self.clock.now()

    # -- plumbing --------------------------------------------------------------

    def service(self, handler_call_details):
        method = handler_call_details.method
        if method == f"/{SERVICE}/SolveClasses":
            return grpc.unary_unary_rpc_method_handler(self._solve_classes)
        if method == f"/{SERVICE}/Health":
            return grpc.unary_unary_rpc_method_handler(self._health)
        if method == f"/{SERVICE}/LeaseGet":
            return grpc.unary_unary_rpc_method_handler(self._lease_get)
        if method == f"/{SERVICE}/LeaseApply":
            return grpc.unary_unary_rpc_method_handler(self._lease_apply)
        if method == f"/{SERVICE}/FleetState":
            return grpc.unary_unary_rpc_method_handler(self._fleet_state)
        return None

    def _lease_get(self, request: bytes, context) -> bytes:
        return self.lease_plane.get_wire(request)

    def _lease_apply(self, request: bytes, context) -> bytes:
        return self.lease_plane.apply_wire(request)

    def _stub(self, rid: str, method: str):
        key = (rid, method)
        with self._stub_lock:
            stub = self._stubs.get(key)
            if stub is None:
                channel = self._channels.get(rid)
                if channel is None:
                    channel = grpc.insecure_channel(
                        self.addresses[rid], options=CHANNEL_OPTIONS
                    )
                    self._channels[rid] = channel
                stub = channel.unary_unary(f"/{SERVICE}/{method}")
                self._stubs[key] = stub
        return stub

    # -- liveness + rebalance (lazy: piggybacked on routed requests) -----------

    def _maintain(self) -> Tuple[Set[str], Set[str]]:
        alive, draining = self.directory.view(self.fleet.fleet_map.ids())
        REPLICAS_ALIVE.labels().set(float(len(alive)))
        with self._lock:
            dead_placements = [
                t for t, rid in self._placements.items() if rid not in alive
            ]
            for t in dead_placements:
                del self._placements[t]
        now = self.clock.now()
        with self._lock:
            # claim the interval under the lock: two handler threads racing
            # the same deadline must not both run a rebalance round
            due = now - self._last_rebalance >= self.rebalance_interval_s
            if due:
                self._last_rebalance = now
        if due:
            self._rebalance(alive)
        return alive, draining

    def _rebalance(self, alive: Set[str]) -> None:
        """Move the hottest-burning replica's hottest tenants to the next
        alive replica on their arc — bounded to ``rebalance_fraction`` of
        ALL placed tenants per interval, so rebalancing converges instead of
        thrashing warm lineages around the fleet."""
        with self._lock:
            placements = dict(self._placements)
        if not placements or len(alive) < 2:
            return
        by_replica: Dict[str, List[Tuple[float, str]]] = {}
        for tenant, rid in placements.items():
            burn = self.slo.burn(tenant_label(tenant), "5m")
            by_replica.setdefault(rid, []).append((burn, tenant))
        hot_rid, hot_tenants = max(
            by_replica.items(),
            key=lambda kv: (max(b for b, _ in kv[1]), sum(b for b, _ in kv[1])),
        )
        hottest_burn = max(b for b, _ in hot_tenants)
        if hottest_burn <= 0.0:
            return  # every tenant inside budget: nothing to move
        budget = max(int(self.rebalance_fraction * len(placements)), 1)
        moved = 0
        for burn, tenant in sorted(hot_tenants, reverse=True):
            if moved >= budget or burn <= 0.0:
                break
            target = next(
                (rid for rid in self.ring.arc(tenant)
                 if rid in alive and rid != hot_rid), None,
            )
            if target is None:
                break
            with self._lock:
                if self._placements.get(tenant) == hot_rid:
                    self._placements[tenant] = target
                    moved += 1
                    REBALANCED_TOTAL.labels().inc()
        if moved:
            log.info("fleet rebalance: moved %d tenant(s) off %s", moved,
                     hot_rid)

    # -- the routed solve ------------------------------------------------------

    def _place(self, tenant: str, alive: Set[str]) -> Optional[str]:
        with self._lock:
            rid = self._placements.get(tenant)
            if rid in alive:
                return rid
            assigned: Dict[str, int] = {}
            for r in self._placements.values():
                assigned[r] = assigned.get(r, 0) + 1
            rid = self.ring.owner(tenant, alive, assigned)
            if rid is not None:
                self._placements[tenant] = rid
            return rid

    def _drop_placement(self, tenant: str, rid: str) -> None:
        with self._lock:
            if self._placements.get(tenant) == rid:
                del self._placements[tenant]

    def _solve_classes(self, request: bytes, context) -> bytes:
        try:
            req = msgpack.unpackb(request)
        except Exception:  # noqa: BLE001 - surface like the replica would
            context.abort(grpc.StatusCode.INVALID_ARGUMENT,
                          "malformed msgpack request")
        envelope = req.get("tenant") or {}
        tenant = str(envelope.get("id") or "")
        alive, _draining = self._maintain()
        if tenant:
            admitted, hint = self.admission.admit(
                tenant, envelope.get("weight")
            )
            if not admitted:
                ROUTED_TOTAL.labels("shed").inc()
                context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED,
                              self.admission.shed_detail(hint))
        fault = FLEET_ROUTE.hit(
            kinds=("error", "timeout", "partial"), tenant=tenant,
        )
        if fault is not None and fault.kind == "error":
            ROUTED_TOTAL.labels("chaos-error").inc()
            context.abort(grpc.StatusCode.UNAVAILABLE, fault.describe())
        if fault is not None and fault.kind == "timeout":
            ROUTED_TOTAL.labels("chaos-timeout").inc()
            context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, fault.describe())
        place_key = tenant or "solo"
        with tracing.span_remote("fleet.route", envelope.get("trace"),
                                 tenant=tenant) as sp:
            first = self._place(place_key, alive)
            tried: List[str] = []
            candidates = [first] if first is not None else []
            candidates += [
                rid for rid in self.ring.arc(place_key)
                if rid in alive and rid != first
            ]
            for rid in candidates:
                breaker = self._breakers[rid]
                if not breaker.allow():
                    continue
                tried.append(rid)
                t0 = time.perf_counter()
                try:
                    response = self._stub(rid, "SolveClasses")(
                        request, timeout=self.forward_timeout_s
                    )
                except grpc.RpcError as e:
                    code = e.code()
                    if code in (grpc.StatusCode.UNAVAILABLE,
                                grpc.StatusCode.DEADLINE_EXCEEDED):
                        # the replica is gone (or wedged): trip its breaker,
                        # drop the placement, walk the arc — the adopting
                        # replica restores the tenant from its checkpoint
                        breaker.record_failure()
                        self._drop_placement(place_key, rid)
                        ROUTED_TOTAL.labels("failover").inc()
                        sp.set(**{"fleet.failover": rid})
                        continue
                    # a replica VERDICT (shed, precondition, bad request):
                    # pass code + details through verbatim — retry-after
                    # hints and eject reasons must survive the hop
                    breaker.record_success()
                    ROUTED_TOTAL.labels("upstream").inc()
                    context.abort(code, e.details() or "")
                breaker.record_success()
                with self._lock:
                    self._placements[place_key] = rid
                if tenant:
                    self.slo.observe(
                        tenant_label(tenant), time.perf_counter() - t0
                    )
                if fault is not None and fault.kind == "partial":
                    # the replica computed and journaled the answer, the
                    # client never receives it — the mid-stream eviction leg
                    ROUTED_TOTAL.labels("chaos-partial").inc()
                    context.abort(grpc.StatusCode.UNAVAILABLE,
                                  fault.describe())
                ROUTED_TOTAL.labels("ok").inc()
                sp.set(**{"fleet.replica": rid})
                return response
            ROUTED_TOTAL.labels("exhausted").inc()
            context.abort(
                grpc.StatusCode.UNAVAILABLE,
                f"fleet-no-replica tried={','.join(tried) or 'none'} "
                f"alive={len(alive)}",
            )

    # -- health + debug --------------------------------------------------------

    def _health(self, request: bytes, context) -> bytes:
        alive, draining = self._maintain()
        replicas: Dict[str, Dict] = {}
        for rid in sorted(self.fleet.fleet_map.ids()):
            if rid not in alive:
                replicas[rid] = {
                    "status": "draining" if rid in draining else "dead"
                }
                continue
            try:
                raw = self._stub(rid, "Health")(request, timeout=2.0)
                replicas[rid] = msgpack.unpackb(raw)
            except grpc.RpcError as e:
                replicas[rid] = {"status": "unreachable",
                                 "code": str(e.code())}
        ok = any(r.get("status") == "ok" for r in replicas.values())
        return msgpack.packb({
            "status": "ok" if ok else "degraded",
            "fleet": {"router": True, "alive": sorted(alive),
                      "replicas": replicas},
        })

    def _fleet_state(self, request: bytes, context) -> bytes:
        alive, draining = self._maintain()
        with self._lock:
            placements = dict(self._placements)
        return msgpack.packb({
            "alive": sorted(alive),
            "draining": sorted(draining),
            "replicas": dict(self.addresses),
            "placements": placements,
        })

    def close(self) -> None:
        with self._stub_lock:
            for channel in self._channels.values():
                channel.close()
            self._channels.clear()
            self._stubs.clear()


def serve_router(fleet: FleetLocal, address: str = "127.0.0.1:0", *,
                 clock: Optional[Clock] = None,
                 tenant_config: Optional[tenant_mod.TenantConfig] = None,
                 max_workers: int = 8):
    """Start the router; returns (server, bound_port).  ``server.kc_router``
    carries the FleetRouter for tests and the soak harness."""
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=max_workers),
        maximum_concurrent_rpcs=max_workers * 4,
        options=CHANNEL_OPTIONS,
    )
    router = FleetRouter(fleet, clock=clock, tenant_config=tenant_config)
    server.add_generic_rpc_handlers((router,))
    port = server.add_insecure_port(address)
    server.start()
    server.kc_router = router
    log.info("fleet router listening on port %d over %d replica(s)",
             port, fleet.fleet_map.size)
    return server, port
