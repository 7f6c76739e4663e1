"""The central KC_* environment-flag registry.

Every ``KC_*`` flag read anywhere in the package MUST have a row here and a
row in the docs table (docs/FLAGS.md) — the ``env-flags`` analysis pass
(docs/ANALYSIS.md) enforces both directions: an unregistered read and a
registry row no code reads are both gate failures.  Harness-side flags
(the KC_CHAOS_SEED / KC_SOAK_SEED seeds read by tests/tools) are deliberately
out of band: this table is the runtime surface operators tune.

The registry is DATA, parsed by the analysis pass without importing this
module; keep ``FLAGS`` a plain dict literal of ``flag -> one-line effect``.
Code does not need to read flags through this module — reads stay at their
point of use; the table exists so the whole surface is auditable in one
place.
"""

from __future__ import annotations

from typing import Dict

FLAGS: Dict[str, str] = {
    # -- solver kernel + encode ------------------------------------------------
    "KC_TPU_SHAPE_BUCKETS": "explicit shape-bucket edges for the compile cache (comma-separated pod counts)",
    "KC_TPU_COMPILE_CACHE": "directory for what the package persists (exported StableHLO, XLA cache, journal, leases)",
    "KC_TPU_KERNEL": "select the operator's solver kernel implementation",
    "KC_TPU_WARMUP": "pre-compile the solver executables at operator startup",
    "KC_SOLVER_MESH": "enable the sharded device-mesh solve path",
    "KC_SOLVER_MESH_DEVICES": "device count override for the solver mesh",
    "KC_SOLVER_MESH_SHAPE": "explicit mesh shape (e.g. '2x4') for the sharded solve",
    "KC_SOLVER_INCREMENTAL": "enable warm-start incremental solve sessions",
    "KC_DELTA_WINDOW": "repair-window width (slots re-opened around churned pods) for delta solves",
    "KC_DELTA_MAX_FRACTION": "churn fraction above which a delta solve falls back to full",
    "KC_DELTA_AUDIT_INTERVAL": "full-solve audit cadence for long delta chains",
    "KC_DEGRADED_MAX_PODS": "pod-count ceiling for the degraded (host fallback) solve path",
    "KC_SOLVER_MODE": "solver family routing: scan | relax | auto (PolicyConfig/provisioner spec wins over env)",
    "KC_RELAX_MAX_ITERS": "projected-gradient iteration cap for the relax solver family",
    "KC_RELAX_MIN_PODS": "pod-count threshold above which auto mode picks the relax family",
    # -- watchdog --------------------------------------------------------------
    "KC_WATCHDOG": "adaptive watchdog over every blocking device interaction (0 = legacy unguarded waits)",
    "KC_WATCHDOG_FLOOR_S": "watchdog deadline floor",
    "KC_WATCHDOG_CEILING_S": "watchdog deadline ceiling",
    "KC_WATCHDOG_MARGIN": "multiplier over the observed p95 used as the adaptive deadline",
    "KC_WATCHDOG_COLD_MULT": "extra deadline multiplier for cold (first-compile) solves",
    "KC_WATCHDOG_CANARY_DEADLINE_S": "deadline for the known-answer canary solve that re-admits a quarantined backend",
    # -- async pipeline --------------------------------------------------------
    "KC_PIPELINE": "double-buffered dispatch/fetch solve loop (0 = serial A/B pin)",
    "KC_PIPELINE_DEPTH": "in-flight dispatch depth of the solve pipeline",
    # -- tracing + metrics -----------------------------------------------------
    "KC_TRACE": "decision-trace capture on/off",
    "KC_TRACE_CAPACITY": "trace ring-buffer capacity",
    "KC_TENANT_LABEL_MAX": "tenant-label cardinality cap for metrics (overflow buckets to 'other')",
    # -- service: admission, sessions, coalescer -------------------------------
    "KC_TENANT_RATE": "per-tenant token-bucket refill rate (solves/s)",
    "KC_TENANT_BURST": "per-tenant token-bucket burst capacity",
    "KC_TENANT_QUEUE": "per-tenant queue depth before shedding",
    "KC_TENANT_MAX_BYTES": "per-request wire-size ceiling",
    "KC_TENANT_SESSIONS": "resident per-tenant session cap (LRU eviction beyond)",
    "KC_TENANT_SESSION_TTL_S": "idle TTL before a tenant session is swept",
    "KC_TENANT_BREAKER_THRESHOLD": "consecutive-failure count that trips a tenant's circuit breaker",
    "KC_TENANT_BREAKER_RESET_S": "circuit-breaker half-open reset timeout",
    "KC_TENANT_BATCH_WINDOW_S": "batch-coalescer rendezvous window",
    "KC_TENANT_BATCH_MAX": "batch-coalescer maximum fused batch size",
    "KC_TENANT_WEIGHTS": "weighted fair-share map 'tenant=weight,...' shaping each bucket",
    "KC_TENANT_SLO_SOLVE_S": "per-solve latency SLO threshold fed to burn-rate accounting",
    "KC_TENANT_SLO_OBJECTIVE": "SLO objective (fraction of solves under threshold)",
    "KC_COALESCE_WINDOW": "repair-window slack allowed when fusing delta repairs across tenants",
    "KC_SERVICE_WORKERS": "gRPC server worker-thread count",
    "KC_SERVICE_QUEUE": "gRPC server max concurrent RPCs",
    "KC_SERVICE_DEADLINE_S": "server-side solve deadline",
    "KC_SERVICE_DRAIN_S": "graceful-drain window on shutdown",
    "KC_DRAIN_RETRY_AFTER_S": "retry-after hint returned while draining",
    # -- durable sessions (journal) -------------------------------------------
    "KC_SESSION_JOURNAL": "durable per-tenant session journal on/off",
    "KC_JOURNAL_DIR": "journal + checkpoint directory",
    "KC_JOURNAL_FSYNC": "fsync discipline for journal appends",
    "KC_JOURNAL_CHECKPOINT_EVERY": "journal compaction cadence (records per checkpoint)",
    "KC_JOURNAL_REPLAY_DEADLINE_S": "recovery replay time budget before degrading to re-anchor",
    "KC_JOURNAL_REPLAY_LOG_EVERY": "progress-log cadence during recovery replay",
    # -- fleet -----------------------------------------------------------------
    "KC_FLEET": "fleet mode master switch (0 = single-replica byte-identity pin)",
    "KC_FLEET_DIR": "shared fleet directory (leases, checkpoints, fleet map)",
    "KC_FLEET_MAP": "replica id -> address map for the consistent-hash ring",
    "KC_FLEET_REPLICA": "this process's replica id",
    "KC_FLEET_ROUTER": "run the fleet router front door",
    "KC_FLEET_BIND": "replica bind address",
    "KC_FLEET_HEARTBEAT_S": "replica lease heartbeat interval",
    "KC_FLEET_LEASE_TTL_S": "lease freshness TTL for liveness",
    "KC_FLEET_FORWARD_TIMEOUT_S": "router -> replica forwarding deadline",
    "KC_FLEET_REBALANCE_INTERVAL_S": "router load-aware rebalance cadence",
    "KC_FLEET_REBALANCE_FRACTION": "max fraction of placements moved per rebalance round",
    "KC_FLEET_CKPT_EVERY": "solves between cadence checkpoints of a tenant lineage",
    "KC_FLEET_CHECKPOINT_KEEP": "checkpoint generations retained per tenant",
    # -- policy layer ----------------------------------------------------------
    "KC_POLICY": "named policy profile selector",
    "KC_POLICY_ENABLED": "policy layer on/off",
    "KC_POLICY_COST_WEIGHT": "fleet-cost term weight in the placement objective",
    "KC_POLICY_THROUGHPUT_WEIGHT": "throughput term weight in the placement objective",
    "KC_POLICY_RISK_AVERSION": "spot-interruption risk aversion factor",
    "KC_POLICY_SPOT_PREFERENCE": "spot vs on-demand preference",
    "KC_POLICY_MAX_RESIZE_FRACTION": "cap on fleet fraction resized per policy round",
    "KC_POLICY_COUNTER_PROPOSALS": "emit ShapeHint counter-proposal events",
    # -- test harness hooks shipped in-package ---------------------------------
    "KC_LOCKCHECK": "run the opt-in suites under the runtime lockset tracer (testing/lockcheck.py)",
    # -- operator / wiring -----------------------------------------------------
    "KC_SOLVER_ADDRESS": "remote solver service address the operator dials",
    "KC_SOLVER_LISTEN": "solver service listen address",
    "KC_KUBE_BACKEND": "cluster-state backend selector (memory | apiserver)",
    "KC_KUBE_APISERVER": "kube-apiserver endpoint for the watch/list backend",
    "KC_LEASE_ENDPOINT": "remote lease-plane endpoint",
    "KC_LEASE_STATE": "lease-plane persistence path",
    "KC_NATIVE_SIG": "native (C) signature-interning twin on/off",
    "KC_FAKE_NODE_TAG": "tag applied to fake cloud-provider nodes",
}
