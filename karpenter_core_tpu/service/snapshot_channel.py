"""The snapshot channel: gRPC service in front of the TPU solver.

The north-star architecture (BASELINE.json) keeps the controller plane where
it is and ships cluster-state snapshots over gRPC to a solver sidecar on the
TPU host — this module is that channel.  Requests carry pods, provisioners,
and existing nodes (apis.codec wire dicts, msgpack-framed); responses carry
node decisions, existing-node nominations, and failures.

Implemented with gRPC generic method handlers (no codegen: the environment has
no protoc python plugin) — the method contract is documented here and stable:

    /karpenter.v1.SnapshotSolver/Solve         unary-unary, msgpack bytes
    /karpenter.v1.SnapshotSolver/SolveClasses  unary-unary, msgpack bytes
    /karpenter.v1.SnapshotSolver/Health        unary-unary, empty → msgpack bytes

SolveClasses is the class-columnar fast path: the controller plane dedups its
pending pods into shape classes (models.columnar.PodIngest) and ships ONE
representative pod + count per class — O(distinct shapes) on the wire instead
of O(pods) — and gets back per-node class counts it expands locally.  At 50k
pods / ~13 shapes that is a ~4000× smaller request than /Solve.

The MULTI-TENANT layer (service/tenant.py, docs/SERVICE.md): a SolveClasses
request carrying a ``tenant`` envelope routes through admission control
(token-bucket rate limits + a bounded global queue, RESOURCE_EXHAUSTED sheds
with a retry-after hint), a per-tenant server-side incremental-solve session
(LRU + TTL, ``session-lost`` re-anchor after restarts), per-tenant circuit
breakers, and the batch coalescer that stacks compatible-shape-bucket
tenants into ONE vmapped device solve.  Requests without the envelope keep
the original stateless contract exactly.

``service.rpc`` is the chaos point on this channel — the one major I/O
boundary the other six points don't cover.  It fires on both sides: the
client wrapper (error/timeout raised before the call leaves) and the server
handlers (error → UNAVAILABLE, timeout → DEADLINE_EXCEEDED, partial →
the solve runs but the response is dropped, latency through the armed
clock) — so chaos suites can flap the whole service and watch the
controller's solver breaker + degraded mode absorb it.
"""

from __future__ import annotations

import copy
import json
import logging
import os
import threading
import time
import weakref
from concurrent import futures
from typing import Dict, List, Optional

import grpc
import msgpack

from karpenter_core_tpu import chaos, tracing
from karpenter_core_tpu import metrics as metrics_mod
from karpenter_core_tpu.apis import codec
from karpenter_core_tpu.models.snapshot import KernelUnsupported
from karpenter_core_tpu.service import collector
from karpenter_core_tpu.service import journal as journal_mod
from karpenter_core_tpu.service import tenant as tenant_mod
from karpenter_core_tpu.solver.tpu import TPUSolver
from karpenter_core_tpu.state.cluster import StateNode
from karpenter_core_tpu.utils.watchdog import SolveTimeout

log = logging.getLogger(__name__)

SERVICE = "karpenter.v1.SnapshotSolver"

# gRPC refuses a message over 4 MiB unless told otherwise, and a SolveClasses
# answer lists every viable instance type of every new node — ~86 MB at the
# north-star size (50k pods × 1k types).  Every endpoint of this service
# (server, client, fleet router) opens with these: what this side produces
# is sent whole; what it receives stays bounded (the tenant plane's
# max_request_bytes is the tighter, per-tenant cap on requests).
CHANNEL_OPTIONS = (
    ("grpc.max_send_message_length", -1),
    ("grpc.max_receive_message_length", 1 << 30),
)

# the gRPC channel's injection point (docs/CHAOS.md): one Point, both
# transports — like kubeapi.put covers both kube backends
SERVICE_RPC = chaos.point("service.rpc")


class _AbortRequest(Exception):
    """Internal: carry a (code, details) abort decision out of helper depth
    to the handler boundary.  ``context.abort`` raises a BARE Exception, so
    calling it under a ``try/except Exception`` would get the abort re-caught
    and re-labeled INTERNAL — helpers raise this instead and the outermost
    handler translates it exactly once."""

    def __init__(self, code, details: str) -> None:
        super().__init__(details)
        self.code = code
        self.details = details


class _WireVolumeResolver:
    """Minimal kube-lookup surface for PVC→CSI-driver resolution
    (scheduling.VolumeUsage), backed by the request's ``claimDrivers`` map —
    the controller plane resolves claims against its apiserver and ships just
    the answers."""

    _PREFIX = "wire://"

    def __init__(self, claim_drivers) -> None:
        self.claim_drivers = dict(claim_drivers or {})

    def get_persistent_volume_claim(self, namespace: str, name: str):
        from karpenter_core_tpu.apis.objects import (
            ObjectMeta,
            PersistentVolumeClaim,
            PersistentVolumeClaimSpec,
        )

        driver = self.claim_drivers.get(f"{namespace}/{name}")
        if driver is None:
            return None
        return PersistentVolumeClaim(
            metadata=ObjectMeta(name=name, namespace=namespace),
            spec=PersistentVolumeClaimSpec(storage_class_name=self._PREFIX + driver),
        )

    def get_persistent_volume(self, name: str):
        return None

    def get_storage_class(self, name: str):
        from karpenter_core_tpu.apis.objects import ObjectMeta, StorageClass

        if name.startswith(self._PREFIX):
            return StorageClass(
                metadata=ObjectMeta(name=name), provisioner=name[len(self._PREFIX):]
            )
        return None


class SnapshotSolverService(grpc.GenericRpcHandler):
    """Solver endpoint: each solve request is one stateless snapshot solve.

    The service additionally hosts the coordination-lease plane
    (/LeaseGet, /LeaseApply): the solver is the deployment's one shared
    singleton (it owns the TPU), so operator replicas elect their leader
    through it — the role the apiserver's Lease object plays for the
    reference (operator.go:111-126).  Lease CAS is monotonic on a
    server-assigned resourceVersion; wall-clock staleness is judged by the
    electors, not here."""

    def __init__(self, cloud_provider, clock=None, tenant_config=None,
                 journal_dir=None, fleet=None) -> None:
        self.cloud_provider = cloud_provider
        # fleet membership (karpenter_core_tpu.fleet, docs/FLEET.md): when
        # this process is one replica of a routed fleet (``fleet`` argument
        # or KC_FLEET=1 + KC_FLEET_DIR), it writes tensor-level session
        # checkpoints to the shared directory, restores adopted tenants from
        # peers' checkpoints, and scales its LOCAL admission buckets to 1/N
        # as a backstop behind the router's fleet-level buckets.  None — the
        # default — leaves every byte of service behavior unchanged.
        from karpenter_core_tpu import fleet as fleet_mod

        self.fleet = fleet if fleet is not None else fleet_mod.FleetLocal.from_env()
        self._ckpt = None
        self._pulse = None  # ReplicaPulse, attached by serve()
        if self.fleet is not None and self.fleet.size > 1:
            tenant_config = (
                tenant_config or tenant_mod.TenantConfig.from_env()
            ).fleet_scaled(self.fleet.size)
        # the multi-tenant plane: admission + sessions + breakers + coalescer
        # (service/tenant.py).  ``clock`` drives every timing policy so
        # FakeClock suites can step TTLs and breaker windows.
        self.tenants = tenant_mod.TenantPlane(clock=clock, config=tenant_config)
        if self.fleet is not None:
            from karpenter_core_tpu.fleet.checkpoint import CheckpointPlane

            self._ckpt = CheckpointPlane(
                self.fleet.checkpoint_dir(), clock=self.tenants.clock,
                replica_id=self.fleet.replica_id,
                every=self.fleet.ckpt_every,
            )
            # a replica journals under the shared fleet root so peers can
            # replay its chains when a checkpoint is stale (the failover
            # ladder's middle rung); an explicit journal_dir or
            # KC_JOURNAL_DIR still wins
            if (
                journal_dir is None
                and os.environ.get("KC_SESSION_JOURNAL", "0") == "1"
                and not os.environ.get("KC_JOURNAL_DIR")
                and self.fleet.replica_id
            ):
                journal_dir = self.fleet.journal_dir()
        # durable sessions (service/journal.py, docs/SERVICE.md): when a
        # journal directory is configured, every completed tenant solve is
        # journaled and a restart replays the per-tenant chains back into
        # WARM lineages before the server takes traffic.  KC_SESSION_JOURNAL
        # enables it env-side; an explicit journal_dir argument always wins.
        self.journal = None
        if journal_dir is None and os.environ.get("KC_SESSION_JOURNAL", "0") == "1":
            journal_dir = os.environ.get("KC_JOURNAL_DIR", "")
            if not journal_dir:
                from karpenter_core_tpu.utils import compilecache

                journal_dir = os.path.join(
                    compilecache.cache_dir(), "session-journal"
                )
        if journal_dir:
            self.journal = journal_mod.SessionJournal(
                journal_dir,
                clock=self.tenants.clock,
                checkpoint_every=tenant_mod._env_i(
                    "KC_JOURNAL_CHECKPOINT_EVERY", 64
                ),
                fsync=os.environ.get("KC_JOURNAL_FSYNC", "1") != "0",
            )
            self._recover_sessions()
            self.journal.start()
            self.tenants.on_drop = self.journal.append_drop
        if self._ckpt is not None:
            # a dropped tenant's checkpoint must not resurrect it on a peer
            journal_drop = self.tenants.on_drop

            def _drop_everywhere(tenant_id: str, _j=journal_drop) -> None:
                if _j is not None:
                    _j(tenant_id)
                self._ckpt.drop(tenant_id)

            self.tenants.on_drop = _drop_everywhere
        # server-side per-RPC deadline: an abandoned/slow client cannot pin a
        # worker past this (0 disables); checked at the solve stage
        # boundaries, the coarsest-grained units of handler work
        self.deadline_s = tenant_mod._env_f("KC_SERVICE_DEADLINE_S", 120.0)
        self._leases: Dict[tuple, Dict] = {}
        self._lease_lock = threading.Lock()
        # best-effort durability: a solver restart that wiped the lease map
        # would let both electors race the re-create (a ~retry_period
        # dual-leader window even with the electors' conflict-demote); the
        # compile-cache volume the deployment already mounts carries the
        # lease state across restarts for free
        self._lease_path = os.environ.get("KC_LEASE_STATE", "")
        if not self._lease_path:
            from karpenter_core_tpu.utils import compilecache

            self._lease_path = os.path.join(compilecache.cache_dir(), "leases.json")
        self._load_leases()

    def _load_leases(self) -> None:
        try:
            with open(self._lease_path) as f:
                for entry in json.load(f):
                    self._leases[(entry.get("namespace", ""), entry["name"])] = entry
            log.info("lease plane restored %d lease(s) from %s",
                     len(self._leases), self._lease_path)
        except FileNotFoundError:
            pass
        except Exception as e:  # noqa: BLE001 - durability is best-effort
            log.warning("lease state load failed (%s), starting empty", e)

    def _persist_leases(self) -> None:
        """Write-through under the lease lock; atomic replace."""
        try:
            os.makedirs(os.path.dirname(self._lease_path), exist_ok=True)
            tmp = f"{self._lease_path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(list(self._leases.values()), f)
            os.replace(tmp, self._lease_path)
        except Exception as e:  # noqa: BLE001 - durability is best-effort
            log.debug("lease state persist failed: %s", e)

    # -- durable-session recovery (service/journal.py, docs/SERVICE.md) -------

    def _recover_sessions(self) -> None:
        """Replay the journal's per-tenant chains into warm server-side
        lineages, with never-trust verification: a replayed lineage must
        reproduce the journaled ``lineage_state`` exactly or the tenant is
        downgraded to the existing ``session-lost`` re-anchor.  Runs before
        the server accepts traffic."""
        chains, broken, stats = self.journal.recover()
        # outcome semantics: "corrupt" = the frame STREAM broke (torn tail /
        # CRC failure), counted per damaged file; a structurally broken
        # chain (tseq gap from a dropped append, version skew) is a
        # "reanchor" — the disk is fine, the tenant just re-anchors
        for status in (stats.get("checkpoint"), stats.get("journal")):
            if status in (journal_mod.STATUS_TORN, journal_mod.STATUS_CORRUPT):
                journal_mod.SESSION_RECOVERED.labels("corrupt").inc()
        for _tenant in sorted(broken):
            journal_mod.SESSION_RECOVERED.labels("reanchor").inc()
        if not chains:
            return
        plane = self.tenants
        # most-recent chains win the session cap (the LRU the crash erased)
        ordered = sorted(
            chains.items(), key=lambda kv: int(kv[1][-1].get("seq", 0))
        )
        if len(ordered) > plane.config.max_sessions:
            ordered = ordered[-plane.config.max_sessions:]
        warm = 0
        # warm-restart watchdog (docs/SERVICE.md): each tenant's replay runs
        # under a wall-clock deadline with a progress log every N frames —
        # one pathological chain (or a quiet device mid-replay; each solve
        # inside is already watchdog-bounded) downgrades THAT tenant to the
        # session-lost re-anchor instead of stalling the whole restart.
        # KC_JOURNAL_REPLAY_DEADLINE_S (default 30, 0 disables) bounds one
        # tenant; KC_JOURNAL_REPLAY_LOG_EVERY (default 16) paces the log.
        replay_deadline_s = tenant_mod._env_f(
            "KC_JOURNAL_REPLAY_DEADLINE_S", 30.0
        )
        log_every = max(tenant_mod._env_i("KC_JOURNAL_REPLAY_LOG_EVERY", 16), 1)
        plane._bypass_coalescer = True  # replay is solo: no rendezvous waits
        try:
            for tenant_id, chain in ordered:
                entry = plane.restore_entry(tenant_id)
                t_replay = time.perf_counter()
                # fleet checkpoint rung: when this tenant's published
                # checkpoint is EXACTLY as fresh as the journal tail (full
                # lineage-state equality, never timestamps), one deserialize
                # replaces the whole chain replay.  Stale or damaged
                # checkpoints fall through to replay below.
                if (
                    self._ckpt is not None
                    and self._fleet_restore_chain(entry, tenant_id, chain)
                ):
                    last = chain[-1]
                    entry.supply_digest = last.get("client_supply")
                    entry.journal_tseq = int(last.get("tseq", 0))
                    entry.recovered = "warm"
                    warm += 1
                    journal_mod.SESSION_RECOVERED.labels("warm").inc()
                    journal_mod.SESSION_REPLAY_DURATION.labels(
                        metrics_mod.tenant_label(tenant_id)
                    ).observe(time.perf_counter() - t_replay)
                    continue
                # trace linkage across the restart: the most recent journaled
                # record carrying a trace context names the originating trace
                # — the replay's spans adopt it (span_remote), so /debug/
                # traces shows the crashed solve and its warm replay as one
                # tree.  Old journals without the field replay untraced-
                # linked, exactly as before (schema-additive).
                trace_ctx = next(
                    (rec.get("trace") for rec in reversed(chain)
                     if rec.get("trace")), None,
                )
                try:
                    with tracing.span_remote("session.recover", trace_ctx,
                                             tenant=tenant_id,
                                             records=len(chain)):
                        for i, rec in enumerate(chain):
                            if (
                                replay_deadline_s > 0
                                and time.perf_counter() - t_replay
                                > replay_deadline_s
                            ):
                                raise journal_mod.RecoveryMismatch(
                                    f"replay exceeded its "
                                    f"{replay_deadline_s:.0f}s deadline at "
                                    f"frame {i}/{len(chain)}"
                                )
                            self._replay_record(entry, rec)
                            if (i + 1) % log_every == 0:
                                log.info(
                                    "session recovery: tenant %s frame "
                                    "%d/%d (%.1fs elapsed)", tenant_id,
                                    i + 1, len(chain),
                                    time.perf_counter() - t_replay,
                                )
                        state = entry.session.lineage_state()
                        want = chain[-1].get("state") or {}
                        if state != want:
                            raise journal_mod.RecoveryMismatch(
                                f"replayed lineage state diverged "
                                f"(have version {state.get('version')}, "
                                f"journal {want.get('version')})"
                            )
                except Exception as e:  # noqa: BLE001 - downgrade, never trust
                    log.warning(
                        "session recovery for tenant %s downgraded to "
                        "re-anchor: %s", tenant_id, e,
                    )
                    plane.discard_entry(tenant_id)
                    self.journal.append_drop(tenant_id)
                    journal_mod.SESSION_RECOVERED.labels("reanchor").inc()
                else:
                    last = chain[-1]
                    entry.supply_digest = last.get("client_supply")
                    entry.journal_tseq = int(last.get("tseq", 0))
                    entry.recovered = "warm"
                    warm += 1
                    journal_mod.SESSION_RECOVERED.labels("warm").inc()
                finally:
                    journal_mod.SESSION_REPLAY_DURATION.labels(
                        metrics_mod.tenant_label(tenant_id)
                    ).observe(time.perf_counter() - t_replay)
        finally:
            plane._bypass_coalescer = False
        log.info(
            "session journal recovery: %d/%d lineage(s) warm, %d broken "
            "chain(s) (checkpoint=%s journal=%s)",
            warm, len(ordered), len(broken),
            stats.get("checkpoint"), stats.get("journal"),
        )

    def _replay_record(self, entry, rec: dict) -> None:
        """Re-run one journaled solve from its stored wire request.  Solves
        are deterministic, so replaying the anchor + deltas reconstructs the
        crashed process's lineage bit for bit; the store version is seeded so
        the restored lineage answers to the exact version the client was
        last told."""
        from karpenter_core_tpu.policy import PolicyConfig
        from karpenter_core_tpu.solver.incremental import MODE_FULL

        req = msgpack.unpackb(rec["request"])
        (classes, uid_class, provisioners, daemonset_pods, state_nodes,
         bound, resolver) = self._decode_tenant_classes(req)
        solver = TPUSolver(
            self.cloud_provider, provisioners, daemonset_pods,
            kube_client=resolver,
            policy=PolicyConfig.from_wire(req.get("policy")),
        )
        session = entry.session
        session.rebind(solver)
        if rec.get("kind") == journal_mod.KIND_ANCHOR:
            session.reset()
            session.store.seed_version(int(rec.get("version", 1)) - 1)
            # fleet checkpoints serialize the lineage's anchor request so an
            # adopting peer can re-encode without this journal — capture it
            # on replay too, so a recovered replica checkpoints complete
            entry.anchor_request = bytes(rec["request"])
            entry.anchor_uid_bases = tuple(uid_class)
        session.solve(classes, state_nodes or None, bound)
        want_full = rec.get("kind") == journal_mod.KIND_ANCHOR
        if (session.last_mode == MODE_FULL) != want_full:
            raise journal_mod.RecoveryMismatch(
                f"replayed {rec.get('kind')} record resolved as "
                f"{session.last_mode}"
            )

    # -- fleet failover (karpenter_core_tpu.fleet, docs/FLEET.md) --------------

    @staticmethod
    def _reset_session_store(entry) -> None:
        """A failed warm restore can leave the session's store committed at
        the checkpoint's version; the next rung (replay, or the session-lost
        full solve) must start from a store that has never committed, or
        ``seed_version`` refuses."""
        from karpenter_core_tpu.models.store import SnapshotStore

        entry.session.reset()
        entry.session.store = SnapshotStore()

    def _fleet_restore_chain(self, entry, tenant_id: str, chain) -> bool:
        """Checkpoint rung of journal recovery: restore this tenant's fleet
        checkpoint in one deserialize iff its lineage state equals the
        journal chain's tail exactly.  Returns False (with the entry's
        session left fresh) on miss, staleness, or any restore failure."""
        from karpenter_core_tpu.fleet import checkpoint as ckpt_mod

        ckpt, _status = self._ckpt.load(tenant_id)
        if ckpt is None:
            return False
        want = chain[-1].get("state") or {}
        if ckpt.state != want:
            log.info(
                "fleet checkpoint for tenant %s is stale (version %s, "
                "journal %s): replaying the chain", tenant_id,
                ckpt.version, want.get("version"),
            )
            return False
        try:
            ckpt_mod.restore_session(ckpt, entry.session, self.cloud_provider)
        except Exception as e:  # noqa: BLE001 - downgrade, never trust
            log.warning(
                "fleet checkpoint restore for tenant %s failed (%s); "
                "replaying the chain", tenant_id, e,
            )
            self._reset_session_store(entry)
            return False
        entry.anchor_request = bytes(ckpt.anchor)
        entry.anchor_uid_bases = tuple(
            str(b) for b in ckpt.header.get("uid_bases", [])
        )
        entry.ckpt_ticks = 0
        return True

    def _fleet_adopt(self, tenant_id: str, entry, claimed: int) -> bool:
        """Cross-replica failover ladder: a client claiming a warm lineage
        this replica doesn't hold may be a routed-over tenant whose previous
        replica died or drained.  Rungs, cheapest first:

          warm      one checkpoint deserialize + never-trust verify
          replay    re-run the tenant's chain from a PEER's journal directory
          reanchor  give up; the caller runs the session-lost full solve

        Every rung must land the lineage at EXACTLY the version the client
        claims — anything else would answer session-lost anyway.  Outcomes
        count on ``karpenter_fleet_failover_total``."""
        from karpenter_core_tpu import fleet as fleet_mod
        from karpenter_core_tpu.fleet import checkpoint as ckpt_mod

        # the entry may carry a committed store from an earlier reset
        # lineage (reset drops the warm state, not the store) — both rungs
        # seed_version, which demands a never-committed store
        if entry.session.store.current is not None:
            self._reset_session_store(entry)
        ckpt, _status = self._ckpt.load(tenant_id)
        if ckpt is not None and ckpt.version == claimed:
            try:
                ckpt_mod.restore_session(
                    ckpt, entry.session, self.cloud_provider
                )
                entry.anchor_request = bytes(ckpt.anchor)
                entry.anchor_uid_bases = tuple(
                    str(b) for b in ckpt.header.get("uid_bases", [])
                )
                entry.ckpt_ticks = 0
                entry.supply_digest = ckpt.header.get("client_supply")
                entry.journal_tseq = int(ckpt.header.get("tseq", 0))
                fleet_mod.FAILOVER_TOTAL.labels("warm").inc()
                log.info(
                    "fleet failover: tenant %s adopted warm at version %d",
                    tenant_id, claimed,
                )
                return True
            except Exception as e:  # noqa: BLE001 - fall to the next rung
                log.warning(
                    "fleet failover: checkpoint restore for tenant %s "
                    "failed (%s); trying peer journals", tenant_id, e,
                )
                self._reset_session_store(entry)
        elif ckpt is not None:
            log.info(
                "fleet failover: checkpoint for tenant %s is at version %d, "
                "client claims %d; trying peer journals", tenant_id,
                ckpt.version, claimed,
            )
        if self._fleet_peer_replay(tenant_id, entry, claimed):
            fleet_mod.FAILOVER_TOTAL.labels("replay").inc()
            log.info(
                "fleet failover: tenant %s adopted by journal replay at "
                "version %d", tenant_id, claimed,
            )
            return True
        fleet_mod.FAILOVER_TOTAL.labels("reanchor").inc()
        return False

    @staticmethod
    def _read_peer_chain(directory: str, tenant_id: str):
        """Assemble one tenant's live chain from a peer replica's journal
        files, READ-ONLY — the peer's writer (if it still runs) owns the
        files; ``read_frames`` tolerates a torn tail by design."""
        ck, _ = journal_mod.read_frames(
            os.path.join(directory, "checkpoint.wal")
        )
        j, _ = journal_mod.read_frames(os.path.join(directory, "journal.wal"))
        if not ck and not j:
            return None
        mirror = journal_mod.ChainMirror()
        for rec in ck + j:
            mirror.apply(rec)
        if tenant_id in mirror.broken:
            return None
        return mirror.chains.get(tenant_id)

    def _fleet_peer_replay(self, tenant_id: str, entry, claimed: int) -> bool:
        """Replay rung: scan the shared fleet root for a PEER journal whose
        chain for this tenant ends at the claimed version, and replay it."""
        root = self.fleet.journal_root()
        try:
            peers = sorted(os.listdir(root))
        except OSError:
            return False
        plane = self.tenants
        for rid in peers:
            if rid == self.fleet.replica_id:
                continue
            chain = self._read_peer_chain(
                os.path.join(root, rid), tenant_id
            )
            if not chain or int(chain[-1].get("version", 0)) != claimed:
                continue
            # replay is solo by nature: the bypass is PER ENTRY, so only
            # this tenant's replayed solves skip the rendezvous — concurrent
            # tenants keep coalescing (the old plane-wide flag's save/
            # restore raced them out of their batches)
            entry.bypass_coalescer = True
            try:
                for rec in chain:
                    self._replay_record(entry, rec)
                state = entry.session.lineage_state()
                want = chain[-1].get("state") or {}
                if state != want:
                    raise journal_mod.RecoveryMismatch(
                        f"peer-replayed lineage diverged (have version "
                        f"{state.get('version')}, journal "
                        f"{want.get('version')})"
                    )
                last = chain[-1]
                entry.supply_digest = last.get("client_supply")
                entry.journal_tseq = int(last.get("tseq", 0))
                entry.ckpt_ticks = 0
                return True
            except Exception as e:  # noqa: BLE001 - next peer, never trust
                log.warning(
                    "fleet failover: peer %s journal replay for tenant %s "
                    "failed: %s", rid, tenant_id, e,
                )
                self._reset_session_store(entry)
            finally:
                entry.bypass_coalescer = False
        return False

    def _journal_solve(self, entry, tenant_id: str, mode: str,
                       supply_digest, request: bytes,
                       trace_ctx=None) -> None:
        """Append one completed tenant solve to the journal.  Called with the
        entry lock held — the verification state must snapshot the lineage
        the response was computed from; the actual I/O is enqueued.
        ``trace_ctx`` is the serving span's wire context: replay after a
        restart links back to the trace that originally produced the
        record (docs/OBSERVABILITY.md)."""
        version = entry.session.lineage_version()
        if self.journal is None or version <= 0:
            return  # nothing warm to recover (carry-less solve)
        if mode == "full":
            entry.journal_tseq = 0
            kind = journal_mod.KIND_ANCHOR
        else:
            entry.journal_tseq += 1
            kind = journal_mod.KIND_DELTA
        self.journal.append_solve(
            tenant=tenant_id,
            kind=kind,
            tseq=entry.journal_tseq,
            version=version,
            client_supply=supply_digest,
            state=entry.session.lineage_state(),
            request=bytes(request),
            trace_ctx=trace_ctx,
        )

    # -- graceful drain (SIGTERM path, docs/SERVICE.md) ------------------------

    def drain(self, timeout_s: Optional[float] = None,
              retry_after_s: Optional[float] = None) -> bool:
        """Stop admitting (sheds carry a retry-after hint), let in-flight
        solves finish, then flush + checkpoint the journal.  Returns True
        when the plane fully quiesced inside the timeout.  The caller stops
        the gRPC server afterwards."""
        if timeout_s is None:
            timeout_s = tenant_mod._env_f("KC_SERVICE_DRAIN_S", 30.0)
        if retry_after_s is None:
            retry_after_s = tenant_mod._env_f("KC_DRAIN_RETRY_AFTER_S", 5.0)
        if self._pulse is not None:
            # advertise the drain at the router FIRST (lease duration 0):
            # its next maintenance pass remaps this replica's arc, and the
            # adopting peers find the final checkpoints written below
            self._pulse.mark_draining()
        self.tenants.start_draining(retry_after_s)
        deadline = tenant_mod.monotonic() + max(timeout_s, 0.0)
        import time as _time

        while self.tenants.inflight() > 0 and tenant_mod.monotonic() < deadline:
            _time.sleep(0.02)
        drained = self.tenants.inflight() == 0
        if self._ckpt is not None:
            written = self._ckpt.write_all(self.tenants.entries_snapshot())
            if written:
                log.info(
                    "fleet drain: %d session checkpoint(s) published", written
                )
        if self.journal is not None:
            self.journal.close(checkpoint=True)
        if self._pulse is not None:
            self._pulse.stop()
        log.info("service drained (quiesced=%s)", drained)
        return drained

    def shutdown(self) -> None:
        """Non-drain teardown (tests, soak): release the journal cleanly
        without forcing a final checkpoint."""
        if self.journal is not None:
            self.journal.close(checkpoint=False)

    # -- grpc plumbing --------------------------------------------------------

    def service(self, handler_call_details):
        # resolved per request (an instance attribute may wrap a handler), and
        # every kind ends in one request boundary of the collector policy
        prefix, _, method = handler_call_details.method.rpartition("/")
        handler = {
            "Solve": self._solve,
            "SolveClasses": self._solve_classes,
            "Health": self._health,
            "Consolidate": self._consolidate,
            "LeaseGet": self._lease_get,
            "LeaseApply": self._lease_apply,
        }.get(method) if prefix == f"/{SERVICE}" else None
        if handler is None:
            return None
        return grpc.unary_unary_rpc_method_handler(collector.POLICY.paced(handler))

    # -- handlers -------------------------------------------------------------

    def _health(self, request: bytes, context) -> bytes:
        if self.fleet is None:
            # KC_FLEET off: byte-identical to the pre-fleet response
            return msgpack.packb({"status": "ok"})
        # fleet replicas self-describe: the router's health fan-out and the
        # soak's cross-process leak audit read these
        machines = 0
        created = getattr(self.cloud_provider, "created_machines", None)
        if callable(created):
            machines = len(created())
        return msgpack.packb({
            "status": "ok",
            "fleet": {
                "replica": self.fleet.replica_id,
                "sessions": len(self.tenants.entries_snapshot()),
                "machines": machines,
            },
        })

    def _rpc_chaos(self, context, method: str):
        """Server-transport leg of the ``service.rpc`` chaos point.  error →
        UNAVAILABLE now, timeout → DEADLINE_EXCEEDED now, latency applied by
        the plane (armed clock); a ``partial`` fault is RETURNED so the
        handler can do its full work and then drop the response — the
        wasted-work shape real partial failures have."""
        fault = SERVICE_RPC.hit(
            kinds=("error", "timeout", "partial"), side="server", method=method
        )
        if fault is None:
            return None
        if fault.kind == "partial":
            return fault
        if fault.kind == "timeout":
            context.abort(grpc.StatusCode.DEADLINE_EXCEEDED, fault.describe())
        context.abort(grpc.StatusCode.UNAVAILABLE, fault.describe())

    def _deadline_guard(self, context, t0: float) -> None:
        """Server-side per-RPC deadline (KC_SERVICE_DEADLINE_S): checked at
        the solve-stage boundaries so an abandoned or glacial client cannot
        pin a worker forever; also drops work for clients that already
        disconnected.  Raises _AbortRequest (translated at the handler
        boundary)."""
        if context is None:
            return
        if not context.is_active():
            raise _AbortRequest(grpc.StatusCode.CANCELLED, "client disconnected")
        if self.deadline_s and tenant_mod.monotonic() - t0 > self.deadline_s:
            raise _AbortRequest(
                grpc.StatusCode.DEADLINE_EXCEEDED,
                f"server-side deadline {self.deadline_s:.1f}s exceeded",
            )

    def _consolidate(self, request: bytes, context) -> bytes:
        """Multi-node consolidation sweep on the device: every prefix of the
        disruption-sorted candidate list simulated in parallel
        (solver.consolidation.TPUConsolidationSearch).  Candidates reference
        nodes shipped in the ``nodes`` envelope by name; replacements come
        back as launchable entries whose pods are (nodeName, podIndex) refs
        into the shipped per-node pod lists."""
        partial = self._rpc_chaos(context, "Consolidate")
        # the handler's root span; below it one span per PHASE (decode, the
        # search's encode / split / per-pass sweep and decode, payload, pack),
        # never per node or pod (docs/OBSERVABILITY.md)
        with tracing.span("service.consolidate",
                          request_bytes=len(request)) as root:
            try:
                payload = self._consolidate_request(request, root)
            except KernelUnsupported as e:
                context.abort(grpc.StatusCode.FAILED_PRECONDITION, f"kernel unsupported: {e}")
            except Exception as e:  # noqa: BLE001 - surface as INTERNAL
                log.exception("consolidate request failed")
                context.abort(grpc.StatusCode.INTERNAL, str(e))
        if partial is not None:
            context.abort(grpc.StatusCode.UNAVAILABLE, partial.describe())
        return payload

    def _consolidate_request(self, request: bytes, root) -> bytes:
        from karpenter_core_tpu.apis import labels as labels_api
        from karpenter_core_tpu.controllers.deprovisioning import CandidateNode
        from karpenter_core_tpu.policy import PolicyConfig
        from karpenter_core_tpu.solver.consolidation import TPUConsolidationSearch
        from karpenter_core_tpu.utils import pod as pod_util

        with tracing.span("service.decode", request_bytes=len(request)) as sp:
            req = msgpack.unpackb(request)
            provisioners, daemonset_pods, state_nodes, bound, resolver, node_pods = (
                self._decode_common(req)
            )
            pending = [codec.pod_from_dict(p) for p in req.get("pendingPods", [])]
            by_name = {sn.node.name: sn for sn in state_nodes}
            prov_by_name = {p.name: p for p in provisioners}
            its = {it.name: it for it in self.cloud_provider.get_instance_types(None)}

            def reschedulable(pods):
                # node_util.get_node_pods parity: the envelope ships ALL pods
                # (node utilization needs them) but CandidateNode.pods must be
                # only what a deletion would actually displace
                return [
                    p for p in pods
                    if not (
                        pod_util.is_owned_by_node(p)
                        or pod_util.is_owned_by_daemon_set(p)
                        or pod_util.is_terminal(p)
                        or pod_util.is_terminating(p)
                    )
                ]

            candidates = []
            for c in req.get("candidates", []):
                sn = by_name.get(c["name"])
                provisioner = prov_by_name.get(c["provisioner"])
                if sn is None or provisioner is None:
                    continue
                candidates.append(CandidateNode(
                    node=sn.node,
                    state_node=sn,
                    instance_type=its.get(c["instanceType"]),
                    capacity_type=c.get("capacityType", ""),
                    zone=c.get("zone", ""),
                    provisioner=provisioner,
                    disruption_cost=float(c.get("disruptionCost", 0.0)),
                    pods=reschedulable(node_pods.get(c["name"], [])),
                ))
            search = TPUConsolidationSearch(
                self.cloud_provider, provisioners,
                # the requesting replica's resolved policy config rides the
                # wire (PolicyConfig.to_wire) so remote sweeps score lanes by
                # fleet-cost delta exactly like in-process ones; absent =
                # pre-policy behavior, serving-side KC_POLICY=0 still wins
                policy=PolicyConfig.from_wire(req.get("policy")),
            )
            sp.set(candidates=len(candidates), state_nodes=len(state_nodes),
                   pods=len(bound))

        cmd = search.compute_command(
            candidates, pending_pods=pending,
            state_nodes=state_nodes, bound_pods=bound,
        )

        def domain_of(replacement, key) -> list:
            requirements = replacement.requirements
            if requirements.has(key):
                return list(requirements.get(key).values_list())
            return []

        with tracing.span("service.payload"):
            # a replacement's pods are its candidates' — named by where they
            # were shipped: (node, index in that node's shipped list)
            pod_ref = {
                id(pod): (name, i)
                for r in cmd.replacement_nodes
                for name in {p.spec.node_name for p in r.pods}
                for i, pod in enumerate(node_pods.get(name, ()))
            }
            response = {
                "action": cmd.action.value,
                "nodesToRemove": [n.name for n in cmd.nodes_to_remove],
                "replacements": [
                    {
                        "provisioner": r.provisioner_name,
                        "instanceTypes": [it.name for it in r.instance_type_options],
                        "zones": domain_of(r, labels_api.LABEL_TOPOLOGY_ZONE),
                        # the sweep's price rules may pin spot-only
                        # (consolidation.go:227-267 parity) — the launch must
                        # keep that narrowing or an on-demand machine could
                        # cost more than the nodes it replaces
                        "capacityTypes": domain_of(r, labels_api.LABEL_CAPACITY_TYPE),
                        "requests": {k: float(v) for k, v in r.requests.items()},
                        "podRefs": [
                            pod_ref[id(p)] for p in r.pods if id(p) in pod_ref
                        ],
                    }
                    for r in cmd.replacement_nodes
                ],
            }
        root.set(candidates=len(candidates), nodes=len(state_nodes),
                 pods=len(bound), passes=search.last_passes,
                 action=cmd.action.value, removed=len(cmd.nodes_to_remove))
        return self._pack_reply(response)

    def _lease_get(self, request: bytes, context) -> bytes:
        req = msgpack.unpackb(request)
        with self._lease_lock:
            stored = self._leases.get((req.get("namespace", ""), req["name"]))
            return msgpack.packb({"lease": dict(stored) if stored else None})

    def _lease_apply(self, request: bytes, context) -> bytes:
        """Create/update with compare-and-swap on resourceVersion.

        expectedVersion absent/None = create (conflict if the lease exists);
        otherwise the update only lands if the stored version still matches.
        Returns {ok, conflict, lease} — on conflict the stored lease rides
        along so the caller sees who won without a second round trip."""
        req = msgpack.unpackb(request)
        lease = dict(req["lease"])
        key = (lease.get("namespace", ""), lease["name"])
        expected = req.get("expectedVersion")
        with self._lease_lock:
            stored = self._leases.get(key)
            if expected is None:
                if stored is not None:
                    return msgpack.packb(
                        {"ok": False, "conflict": True, "lease": dict(stored)}
                    )
                lease["resourceVersion"] = 1
            else:
                if stored is None or stored["resourceVersion"] != expected:
                    return msgpack.packb({
                        "ok": False, "conflict": True,
                        "lease": dict(stored) if stored else None,
                    })
                lease["resourceVersion"] = stored["resourceVersion"] + 1
            self._leases[key] = lease
            self._persist_leases()
            return msgpack.packb({"ok": True, "conflict": False, "lease": dict(lease)})

    @staticmethod
    def _decode_common(req):
        """(provisioners, daemonset_pods, state_nodes, bound_pods, resolver)
        from the request envelope shared by /Solve and /SolveClasses.

        ``claimDrivers`` ({"<ns>/<claim>": csi-driver}) lets the controller
        plane ship its PVC→driver resolution so volume attach limits bind on
        this side of the wire too; node entries may carry ``volumeLimits``
        ({driver: allocatable count}) from their CSINode."""
        # no claimDrivers → volumes stay unconstrained (the pre-existing wire
        # contract); a provided map makes every referenced claim resolvable
        # and unresolved ones route to FAILED_PRECONDITION like other
        # kernel-unsupported shapes
        claim_drivers = req.get("claimDrivers")
        resolver = _WireVolumeResolver(claim_drivers) if claim_drivers else None
        provisioners = [
            codec.provisioner_from_dict(p) for p in req.get("provisioners", [])
        ]
        daemonset_pods = [
            codec.pod_from_dict(p) for p in req.get("daemonsetPods", [])
        ]
        state_nodes = []
        bound = []
        node_pods = {}
        nodes = req.get("nodes") or []
        if nodes:
            # the cluster as shipped: a StateNode per node, a Pod and an
            # update_for_pod per pod bound to it — one span whatever the
            # size, and none where no cluster is shipped
            with tracing.span("service.decode_nodes", state_nodes=len(nodes)) as sp:
                for n in nodes:
                    state_node = StateNode(codec.node_from_dict(n["node"]), resolver)
                    for driver, limit in (n.get("volumeLimits") or {}).items():
                        state_node._volume_limits[driver] = int(limit)
                    pods_here = []
                    for p in n.get("pods", []):
                        pod = codec.pod_from_dict(p)
                        state_node.update_for_pod(pod)
                        bound.append(pod)
                        pods_here.append(pod)
                    node_pods[state_node.node.name] = pods_here
                    state_nodes.append(state_node)
                sp.set(bound_pods=len(bound))
        return provisioners, daemonset_pods, state_nodes, bound, resolver, node_pods

    @staticmethod
    def _classes_payload(results, class_counts) -> Dict:
        """The SolveClasses response body for one TPUSolveResults;
        ``class_counts(pods) -> [(class_index, count)]`` supplies the
        caller's pod→request-class mapping (identity-based on the stateless
        path, uid-based on the tenant path)."""
        return {
            "newNodes": [
                {
                    "provisioner": n.provisioner_name,
                    "instanceTypes": n.instance_type_names,
                    "zones": n.zones,
                    "capacityTypes": n.capacity_types,
                    "requests": n.requests,
                    "classCounts": class_counts(n.pods),
                }
                for n in results.new_nodes
            ],
            "existingAssignments": {
                name: class_counts(placed)
                for name, placed in results.existing_assignments.items()
            },
            "failedClassCounts": class_counts(results.failed_pods),
            # spread residuals: classes the kernel may have under-placed
            # vs the host oracle — the controller plane re-routes them
            # through its host scheduler with seeded topology counts
            # (provisioning._solve_host_remainder), so the wire path keeps
            # the same no-shape-schedules-fewer guarantee as in-process
            "residualClassCounts": class_counts(results.spread_residual_pods),
            # zone commitments the solve stamped onto zone-less existing
            # nodes: the re-route must see the same pins
            "existingCommittedZones": dict(results.existing_committed_zones),
        }

    def _solve_classes(self, request: bytes, context) -> bytes:
        t0 = tenant_mod.monotonic()
        # the handler's root span, stateless and tenant alike; below it one
        # span per PHASE (decode, materialize, payload, pack), never per
        # class, so a request opens the same number whatever its size
        with tracing.span("service.solve_classes",
                          request_bytes=len(request)) as root:
            full0, full_s0 = collector.POLICY.full_passes()
            partial = self._rpc_chaos(context, "SolveClasses")
            try:
                with tracing.span("service.decode", request_bytes=len(request)):
                    req = msgpack.unpackb(request)
            except Exception as e:  # noqa: BLE001 - not even msgpack
                context.abort(grpc.StatusCode.INVALID_ARGUMENT, f"malformed request: {e}")
            try:
                if isinstance(req, dict) and req.get("tenant"):
                    response = self._solve_classes_tenant(req, context, request, t0)
                else:
                    response = self._solve_classes_stateless(req, context, t0)
            except _AbortRequest as a:
                context.abort(a.code, a.details)
            if partial is not None:
                context.abort(grpc.StatusCode.UNAVAILABLE, partial.describe())
            # full collector passes that began inside this request: 0 while
            # the policy paces them (docs/OBSERVABILITY.md)
            full, full_s = collector.POLICY.full_passes()
            root.set(reply_bytes=len(response), gc_full=full - full0,
                     gc_full_s=full_s - full_s0)
            return response

    @staticmethod
    def _pack_reply(response: Dict) -> bytes:
        with tracing.span("service.pack") as sp:
            reply = msgpack.packb(response)
            sp.set(reply_bytes=len(reply))
        return reply

    def _solve_classes_stateless(self, req, context, t0: float) -> bytes:
        """The original stateless contract: every request is one snapshot
        solve, no admission, no session."""
        from karpenter_core_tpu.models.snapshot import build_pod_ladder

        try:
            with tracing.span("service.decode") as sp:
                entries = req.get("podClasses", [])
                reps = [codec.pod_from_dict(e["pod"]) for e in entries]
                classes = []
                for rep, entry in zip(reps, entries):
                    cls = build_pod_ladder(rep)
                    cls.pods = [rep] * int(entry["count"])
                    classes.append(cls)
                req_idx = {id(rep): i for i, rep in enumerate(reps)}
                provisioners, daemonset_pods, state_nodes, bound, resolver, _ = (
                    self._decode_common(req)
                )

                from karpenter_core_tpu.policy import PolicyConfig

                solver = TPUSolver(
                    self.cloud_provider, provisioners, daemonset_pods,
                    kube_client=resolver,
                    # policy over the wire (regression: a CPU controller
                    # replica with the objective enabled previously fell back
                    # SILENTLY to first-fit selection on remote solves — the
                    # field never crossed the channel)
                    policy=PolicyConfig.from_wire(req.get("policy")),
                )
                sp.set(classes=len(classes),
                       pods=sum(len(cls.pods) for cls in classes),
                       state_nodes=len(state_nodes))
            self._deadline_guard(context, t0)
            snapshot = solver.encode_classes(
                classes, state_nodes=state_nodes or None, bound_pods=bound
            )
            results = solver.solve_encoded(snapshot, state_nodes or None, bound)
            self._deadline_guard(context, t0)

            def class_counts(pods) -> list:
                counts: Dict[int, int] = {}
                for p in pods:
                    i = req_idx[id(p)]
                    counts[i] = counts.get(i, 0) + 1
                return sorted(counts.items())

            with tracing.span("service.payload") as sp:
                response = self._classes_payload(results, class_counts)
                sp.set(nodes=len(response["newNodes"]))
            return self._pack_reply(response)
        except KernelUnsupported as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, f"kernel unsupported: {e}")
        except _AbortRequest:
            raise
        except Exception as e:  # noqa: BLE001 - surface as INTERNAL
            log.exception("solve-classes request failed")
            context.abort(grpc.StatusCode.INTERNAL, str(e))

    # -- the tenant path (docs/SERVICE.md) ------------------------------------

    @staticmethod
    def _materialize_class(rep, count: int, uid_base: str) -> List:
        """``count`` bookkeeping copies of the class representative with
        distinct, REQUEST-STABLE uids (``<class-digest>#<j>``).  Pods within
        an equivalence class are fungible, so synthetic member identities
        capture count deltas exactly — the per-tenant incremental session
        diffs successive requests' memberships without per-pod uids ever
        crossing the wire (the O(classes) win stays)."""
        pods = []
        for j in range(count):
            pod = copy.copy(rep)
            pod.metadata = copy.copy(rep.metadata)
            pod.metadata.uid = f"{uid_base}#{j}"
            pods.append(pod)
        return pods

    @classmethod
    def _decode_tenant_classes(cls_, req):
        """(classes, uid_base -> request class index, decode_common tail).

        A classmethod so the fleet checkpoint restore (fleet/checkpoint.py
        restore_session) can re-decode a checkpointed anchor request without
        a service instance — the adopting replica re-derives classes and
        synthetic uids from the same bytes the serving replica encoded."""
        from karpenter_core_tpu.models.snapshot import build_pod_ladder
        from karpenter_core_tpu.models.store import class_key, stable_digest

        entries = req.get("podClasses", [])
        classes = []
        copies: List[tuple] = []  # (count, uid base) per class, for pass two
        uid_class: Dict[str, int] = {}
        # two passes, so that ONE span covers every class's copies: the
        # representatives, ladders, digests and the duplicate check first
        for i, entry in enumerate(entries):
            rep = codec.pod_from_dict(entry["pod"])
            cls = build_pod_ladder(rep)
            cls.pods = [rep]  # class_key derives from the representative
            # class identity digest: CROSS-PROCESS stable (stable_digest
            # canonicalizes the key's frozensets), so a fleet checkpoint's
            # membership bookkeeping — keyed by these synthetic uids — reads
            # back identically on the replica that adopts the tenant
            # (fleet/checkpoint.py); same-process lineages see no change
            uid_base = stable_digest(class_key(cls))[:16]
            if uid_base in uid_class:
                raise ValueError(f"duplicate pod class at index {i}")
            uid_class[uid_base] = i
            copies.append((int(entry["count"]), uid_base))
            classes.append(cls)
        with tracing.span("service.materialize", classes=len(classes),
                          copies=sum(count for count, _ in copies)):
            for cls, (count, uid_base) in zip(classes, copies):
                cls.pods = cls_._materialize_class(cls.pods[0], count, uid_base)
        provisioners, daemonset_pods, state_nodes, bound, resolver, _ = (
            cls_._decode_common(req)
        )
        return classes, uid_class, provisioners, daemonset_pods, state_nodes, bound, resolver

    def _solve_classes_tenant(self, req, context, request: bytes, t0: float) -> bytes:
        from karpenter_core_tpu.policy import PolicyConfig

        nbytes = len(request)
        envelope = req.get("tenant") or {}
        tid = str(envelope.get("id") or "")
        if not tid:
            context.abort(grpc.StatusCode.INVALID_ARGUMENT, "tenant.id required")
        # baggage on the handler's root: its phases carry the tenant as the
        # session's spans do, so a per-tenant trace filter finds both
        tracing.set_attrs(tenant=tid)
        plane = self.tenants
        decision = plane.admit(tid, weight=envelope.get("weight"))
        if not decision.admitted:
            if decision.reason == "draining":
                # graceful drain: the server is going away — an explicit
                # UNAVAILABLE with the hint, so clients re-dial elsewhere
                context.abort(
                    grpc.StatusCode.UNAVAILABLE,
                    "tenant-draining "
                    f"{tenant_mod.RETRY_AFTER_PREFIX}{decision.retry_after_s:.3f}",
                )
            if decision.reason == "isolated":
                context.abort(
                    grpc.StatusCode.UNAVAILABLE,
                    "tenant-isolated "
                    f"{tenant_mod.RETRY_AFTER_PREFIX}{decision.retry_after_s:.3f}",
                )
            context.abort(grpc.StatusCode.RESOURCE_EXHAUSTED, decision.detail())
        # a half-open breaker trial latched by THIS admit must be freed on
        # every exit that reaches no verdict (kernel-unsupported, deadline)
        # or the tenant would wedge half-open forever — record_* calls are
        # the verdicts, everything else releases in the finally.  Only the
        # trial this request was granted (decision.trial) is ever released:
        # a concurrent request's latch is not ours to free.
        entry = decision.entry
        verdict = False
        try:
            if nbytes > plane.config.max_request_bytes:
                verdict = True
                plane.record_bad_request(entry, "oversized")
                context.abort(
                    grpc.StatusCode.INVALID_ARGUMENT,
                    f"tenant-ejected reason=oversized bytes={nbytes} "
                    f"limit={plane.config.max_request_bytes}",
                )
            with tracing.span("service.decode") as sp:
                try:
                    (classes, uid_class, provisioners, daemonset_pods,
                     state_nodes, bound, resolver) = self._decode_tenant_classes(req)
                except Exception as e:  # noqa: BLE001 - tenant-attributable
                    verdict = True
                    plane.record_bad_request(entry, "malformed")
                    context.abort(
                        grpc.StatusCode.INVALID_ARGUMENT,
                        f"tenant-ejected reason=malformed: {e}",
                    )
                solver = TPUSolver(
                    self.cloud_provider, provisioners, daemonset_pods,
                    kube_client=resolver,
                    policy=PolicyConfig.from_wire(req.get("policy")),
                )
                sp.set(classes=len(classes), state_nodes=len(state_nodes))
            claimed = int(envelope.get("sessionVersion") or 0)
            supply_digest = envelope.get("supplyDigest")
            self._deadline_guard(context, t0)
            with entry.lock:
                have = entry.session.lineage_version()
                if claimed != have:
                    # the client's lineage and ours diverged: a restarted /
                    # evicted server (claimed > 0, have == 0 — the
                    # ``session-lost`` re-anchor), a restarted client
                    # (claimed == 0, have > 0), or plain version skew.  The
                    # answer is always the same: drop the lineage, full
                    # solve, never a stale delta.  In a fleet, the first
                    # shape gets one more chance: the tenant may have been
                    # routed here after its previous replica died — adopt
                    # the lineage warm from the shared checkpoints (or a
                    # peer's journal) before giving up.
                    adopted = (
                        bool(claimed) and not have
                        and self._ckpt is not None
                        and self._fleet_adopt(tid, entry, claimed)
                    )
                    if adopted:
                        entry.recovered = entry.recovered or "warm"
                        if (
                            supply_digest is not None
                            and entry.supply_digest is not None
                            and supply_digest != entry.supply_digest
                        ):
                            entry.session.force_full("supply-digest")
                    else:
                        entry.session.reset()
                        entry.session.force_full(
                            "session-lost" if claimed else "client-reanchor"
                        )
                elif (
                    have
                    and supply_digest is not None
                    and entry.supply_digest is not None
                    and supply_digest != entry.supply_digest
                ):
                    # versions agree but the client's view of its supply
                    # moved in a way our decode may not capture: trust the
                    # digest, re-anchor.  force_full OVERWRITES any leftover
                    # forced reason — a journal-recovered session whose
                    # earlier owed re-anchor never ran must report
                    # ``supply-digest`` here, not echo a stale
                    # ``session-lost`` into the mode counter and span
                    entry.session.force_full("supply-digest")
                entry.session.rebind(solver)
                # last_batched is written by the coalescer hook — full
                # solves AND fused repairs reach it (docs/SERVICE.md "Solve
                # fusion") — reset so a solve that short-circuits before the
                # hook doesn't echo a stale batch size
                entry.last_batched = 1
                t_solve = tenant_mod.monotonic()
                # the envelope's optional trace context stitches this
                # server-side segment into the client's trace tree; the
                # serving span's own wire context is captured for the
                # journal so replay-after-restart links back to it
                trace_ctx = envelope.get("trace")
                server_ctx = None
                try:
                    with tracing.span_remote("solve.tenant", trace_ctx,
                                             tenant=tid,
                                             classes=len(classes)):
                        server_ctx = tracing.wire_context()
                        results = entry.session.solve(
                            classes, state_nodes or None, bound
                        )
                except KernelUnsupported as e:
                    # a host-routable batch shape, not abuse: no breaker
                    # verdict (the finally frees any half-open trial)
                    tenant_mod.TENANT_EJECTED.labels(tid, "unsupported").inc()
                    context.abort(
                        grpc.StatusCode.FAILED_PRECONDITION,
                        f"kernel unsupported: {e}",
                    )
                except SolveTimeout as e:
                    # the device went quiet under this tenant's solve: a
                    # STRUCTURED timeout ejection, never a wedged worker —
                    # the watchdog already abandoned the stuck call and the
                    # session canceled/re-anchored its pipeline state
                    # (solver/incremental), so the worker thread is free the
                    # moment this returns.  The timeout is a backend
                    # verdict, not tenant abuse, but it still counts on the
                    # tenant breaker: a tenant whose snapshots reliably hang
                    # the device is indistinguishable from poison and must
                    # isolate (docs/SERVICE.md "Timeout ejection").
                    verdict = True
                    plane.record_timeout(entry)
                    log.warning(
                        "tenant %s solve timed out at %s (deadline %.2fs); "
                        "ejected with watchdog-timeout", tid, e.site,
                        e.deadline_s,
                    )
                    return msgpack.packb({
                        "error": {
                            "kind": "ejected",
                            "reason": str(e),
                            "timeout": {
                                "site": e.site,
                                "deadlineS": round(e.deadline_s, 3),
                            },
                        },
                        "tenant": {
                            "id": tid,
                            "sessionVersion": entry.session.lineage_version(),
                        },
                    })
                except Exception as e:  # noqa: BLE001 - eject, batch survives
                    verdict = True
                    plane.record_fault(entry)
                    log.warning("tenant %s solve ejected: %s", tid, e)
                    return msgpack.packb({
                        "error": {"kind": "ejected", "reason": str(e)},
                        "tenant": {
                            "id": tid,
                            "sessionVersion": entry.session.lineage_version(),
                        },
                    })
                solve_s = tenant_mod.monotonic() - t_solve
                entry.supply_digest = supply_digest
                mode, reason = entry.session.last_mode, entry.session.last_reason
                version = entry.session.lineage_version()
                batched = entry.last_batched
                # one-shot recovery echo: the first response after a warm
                # journal restore tells the client its lineage survived.
                # Captured here, CONSUMED only when the response actually
                # returns — a deadline/disconnect abort past this point must
                # not eat the marker (the client never saw it)
                recovered = entry.recovered
                # durable sessions: journal the completed solve (enqueue
                # only; framing/fsync ride the writer thread off this path)
                self._journal_solve(entry, tid, mode, supply_digest, request,
                                    trace_ctx=server_ctx or trace_ctx)
                if self._ckpt is not None:
                    if mode == "full":
                        # the anchor request is what an adopting peer
                        # re-decodes: a full solve re-anchors the lineage,
                        # so it replaces the captured anchor wholesale
                        entry.anchor_request = bytes(request)
                        entry.anchor_uid_bases = tuple(uid_class)
                    # cadence checkpoint: full solves always, deltas every
                    # KC_FLEET_CKPT_EVERY ticks; never raises (counted on
                    # karpenter_fleet_checkpoint_total instead)
                    self._ckpt.after_solve(tid, entry, mode)
            self._deadline_guard(context, t0)

            t_decode = tenant_mod.monotonic()

            def class_counts(pods) -> list:
                counts: Dict[int, int] = {}
                for p in pods:
                    i = uid_class[p.uid.rsplit("#", 1)[0]]
                    counts[i] = counts.get(i, 0) + 1
                return sorted(counts.items())

            with tracing.span("service.payload") as sp:
                response = self._classes_payload(results, class_counts)
                response["tenant"] = {
                    "id": tid,
                    "solveMode": mode,
                    "reason": reason,
                    "sessionVersion": version,
                    "batched": batched,
                }
                if recovered:
                    response["tenant"]["recovered"] = recovered
                    with entry.lock:
                        entry.recovered = None
                sp.set(nodes=len(response["newNodes"]))
            verdict = True
            plane.record_ok(entry)
            plane.observe_latencies(
                tid,
                queue_s=t_solve - t0,
                solve_s=solve_s,
                decode_s=tenant_mod.monotonic() - t_decode,
            )
            return self._pack_reply(response)
        finally:
            if not verdict and decision.trial:
                entry.breaker.release_trial()
            plane.release(tid)

    def _solve(self, request: bytes, context) -> bytes:
        t0 = tenant_mod.monotonic()
        partial = self._rpc_chaos(context, "Solve")
        try:
            req = msgpack.unpackb(request)
            pods = [codec.pod_from_dict(p) for p in req.get("pods", [])]
            provisioners, daemonset_pods, state_nodes, bound, resolver, _ = (
                self._decode_common(req)
            )

            from karpenter_core_tpu.policy import PolicyConfig

            solver = TPUSolver(
                self.cloud_provider, provisioners, daemonset_pods,
                kube_client=resolver,
                policy=PolicyConfig.from_wire(req.get("policy")),
            )
            self._deadline_guard(context, t0)
            results = solver.solve(pods, state_nodes=state_nodes or None, bound_pods=bound)
            self._deadline_guard(context, t0)

            pod_index = {p.uid: i for i, p in enumerate(pods)}
            response = {
                "newNodes": [
                    {
                        "provisioner": n.provisioner_name,
                        "instanceTypes": n.instance_type_names,
                        "zones": n.zones,
                        "capacityTypes": n.capacity_types,
                        "requests": n.requests,
                        "podIndices": [pod_index[p.uid] for p in n.pods if p.uid in pod_index],
                    }
                    for n in results.new_nodes
                ],
                "existingAssignments": {
                    name: [pod_index[p.uid] for p in placed if p.uid in pod_index]
                    for name, placed in results.existing_assignments.items()
                },
                "failedPodIndices": [
                    pod_index[p.uid] for p in results.failed_pods if p.uid in pod_index
                ],
                "residualPodIndices": [
                    pod_index[p.uid]
                    for p in results.spread_residual_pods if p.uid in pod_index
                ],
                "existingCommittedZones": dict(results.existing_committed_zones),
            }
            payload = msgpack.packb(response)
        except KernelUnsupported as e:
            context.abort(grpc.StatusCode.FAILED_PRECONDITION, f"kernel unsupported: {e}")
        except _AbortRequest as a:
            context.abort(a.code, a.details)
        except Exception as e:  # noqa: BLE001 - surface as INTERNAL
            log.exception("solve request failed")
            context.abort(grpc.StatusCode.INTERNAL, str(e))
        if partial is not None:
            context.abort(grpc.StatusCode.UNAVAILABLE, partial.describe())
        return payload


def service_capacity(max_workers: Optional[int] = None) -> tuple:
    """(workers, max_concurrent_rpcs) for ``serve``: KC_SERVICE_WORKERS sizes
    the solver pool (no more hardcoded 4), KC_SERVICE_QUEUE bounds how many
    additional RPCs may WAIT behind the busy workers — anything past that is
    rejected by the transport with RESOURCE_EXHAUSTED instead of piling up
    unboundedly (the admission controller's token buckets shed per-tenant
    load far earlier; this is the transport backstop)."""
    workers = (
        max_workers if max_workers is not None
        else max(tenant_mod._env_i("KC_SERVICE_WORKERS", 4), 1)
    )
    queue = max(tenant_mod._env_i("KC_SERVICE_QUEUE", 32), 0)
    return workers, workers + queue


def install_drain_handler(server, service, grace_s: float = 1.0) -> bool:
    """SIGTERM → graceful drain (stop admitting with retry-after hints,
    finish in-flight solves, flush + checkpoint the journal) → server stop.
    Main-thread only (signal module restriction); returns False when the
    handler could not be installed."""
    import signal

    if threading.current_thread() is not threading.main_thread():
        return False

    def _drain_and_stop() -> None:
        service.drain()
        server.stop(grace=grace_s)

    def _on_term(signum, frame) -> None:
        threading.Thread(
            target=_drain_and_stop, name="kc-service-drain", daemon=True
        ).start()

    try:
        signal.signal(signal.SIGTERM, _on_term)
    except ValueError:
        return False
    return True


def serve(
    cloud_provider,
    address: str = "127.0.0.1:0",
    max_workers: Optional[int] = None,
    clock=None,
    tenant_config=None,
    metrics_port: Optional[int] = None,
    journal_dir: Optional[str] = None,
    drain_on_sigterm: bool = False,
    fleet=None,
):
    """Start the sidecar; returns (server, bound_port).

    ``max_workers`` None reads KC_SERVICE_WORKERS (default 4); the request
    queue is bounded (KC_SERVICE_QUEUE) and per-RPC work is deadlined
    (KC_SERVICE_DEADLINE_S).  ``clock``/``tenant_config`` thread into the
    multi-tenant plane (service/tenant.py).  ``metrics_port`` (0 = ephemeral)
    additionally serves the process /metrics — the per-tenant latency
    histograms and shed/eject/evict counters — over HTTP; the started
    OperatorHTTP rides ``server.kc_http``.

    ``journal_dir`` (or KC_SESSION_JOURNAL=1 + KC_JOURNAL_DIR) enables the
    durable-session journal: recovery replay runs HERE, before the port
    binds, so the first request a client lands already sees warm lineages.
    ``drain_on_sigterm`` installs the graceful-drain SIGTERM handler
    (main-thread processes only).

    While it is up the sidecar paces the process's full garbage collections
    (service/collector.py: one installation shared by every sidecar of the
    process); ``server.stop()`` of the last one puts the collector back as
    found."""
    from karpenter_core_tpu.utils import compilecache

    compilecache.enable()  # sidecar restarts reuse compiled solve kernels
    workers, max_rpcs = service_capacity(max_workers)
    server = grpc.server(
        futures.ThreadPoolExecutor(max_workers=workers),
        maximum_concurrent_rpcs=max_rpcs,
        options=CHANNEL_OPTIONS,
    )
    service = SnapshotSolverService(
        cloud_provider, clock=clock, tenant_config=tenant_config,
        journal_dir=journal_dir, fleet=fleet,
    )
    server.add_generic_rpc_handlers((service,))
    port = server.add_insecure_port(address)
    server.start()
    # the sidecar owns its process's collector while it is up: full passes
    # are paced by the server, not started by an allocation inside a request
    # (service/collector.py); stop() hands the collector back
    release_collector = collector.POLICY.acquire()
    grpc_stop = server.stop

    def stop(grace=None):
        try:
            return grpc_stop(grace)
        finally:
            release_collector()

    server.stop = stop
    weakref.finalize(server, release_collector)  # a server dropped unstopped
    # the service (and its tenant plane) stays reachable for operators/tests
    server.kc_service = service
    server.kc_http = None
    server.kc_pulse = None
    fleet_local = service.fleet
    if (
        fleet_local is not None
        and fleet_local.replica_id
        and fleet_local.router_address
    ):
        # fleet replica: heartbeat this process's lease at the router so the
        # ring routes here (and remaps the moment the lease goes stale)
        from karpenter_core_tpu.fleet.lease import ReplicaPulse

        pulse = ReplicaPulse(
            RemoteLeaseStore(fleet_local.router_address),
            fleet_local.replica_id,
            clock=service.tenants.clock,
            heartbeat_s=fleet_local.heartbeat_s,
            ttl_s=fleet_local.lease_ttl_s,
        )
        pulse.start()
        service._pulse = pulse
        server.kc_pulse = pulse
    if drain_on_sigterm:
        install_drain_handler(server, service)
    if metrics_port is not None:
        from karpenter_core_tpu.operator.httpserver import OperatorHTTP

        server.kc_http = OperatorHTTP(
            metrics_port=metrics_port, health_port=0
        ).start()
    log.info(
        "snapshot solver listening on port %d (%d workers, %d max rpcs)",
        port, workers, max_rpcs,
    )
    return server, port


def _policy_wire(policy) -> Dict:
    """Normalize a client ``policy`` argument (PolicyConfig, wire dict, or
    None) to the request's wire entry.  {} for None keeps the msgpack schema
    stable while decoding as "no policy" on the serving side."""
    if policy is None:
        return {}
    if isinstance(policy, dict):
        return dict(policy)
    return policy.to_wire()


class SnapshotSolverClient:
    """Controller-plane client for the channel."""

    def __init__(self, address: str) -> None:
        self.channel = grpc.insecure_channel(address, options=CHANNEL_OPTIONS)
        self._solve = self.channel.unary_unary(f"/{SERVICE}/Solve")
        self._solve_classes = self.channel.unary_unary(f"/{SERVICE}/SolveClasses")
        self._health = self.channel.unary_unary(f"/{SERVICE}/Health")
        self._consolidate = self.channel.unary_unary(f"/{SERVICE}/Consolidate")
        self._lease_get = self.channel.unary_unary(f"/{SERVICE}/LeaseGet")
        self._lease_apply = self.channel.unary_unary(f"/{SERVICE}/LeaseApply")

    @staticmethod
    def _client_chaos(method: str) -> None:
        """Client-transport leg of the ``service.rpc`` point: error/timeout
        faults surface as a raised InjectedFault BEFORE the call leaves —
        exactly what a dead/black-holed channel looks like to the caller
        (the provisioning solver breaker counts it); latency rides the armed
        clock inside the plane."""
        fault = SERVICE_RPC.hit(
            kinds=("error", "timeout"), side="client", method=method
        )
        if fault is not None:
            raise chaos.InjectedFault(fault)

    def health(self) -> Dict:
        return msgpack.unpackb(self._health(msgpack.packb({})))

    def consolidate(
        self,
        candidates: List[Dict],
        pending_pods: List,
        provisioners: List,
        nodes: Optional[List[Dict]] = None,
        claim_drivers: Optional[Dict[str, str]] = None,
        policy=None,
        timeout: float = 120.0,
    ) -> Dict:
        """Remote multi-node consolidation sweep.

        ``candidates``: [{name, instanceType, capacityType, zone, provisioner,
        disruptionCost}] in disruption order, referencing ``nodes`` entries by
        name.  ``policy`` (policy.PolicyConfig or a wire dict) makes the
        remote sweep score lanes by fleet-cost delta like an in-process one.
        Returns the raw response: {action, nodesToRemove: [name],
        replacements: [{provisioner, instanceTypes, zones, capacityTypes,
        requests, podRefs: [[nodeName, podIndex]]}]}."""
        self._client_chaos("Consolidate")
        # the client's phases, as /SolveClasses names them
        with tracing.span("client.pack", candidates=len(candidates)) as sp:
            request = msgpack.packb(
                {
                    "candidates": candidates,
                    "pendingPods": [codec.pod_to_dict(p) for p in pending_pods],
                    "provisioners": [codec.provisioner_to_dict(p) for p in provisioners],
                    "nodes": nodes or [],
                    "claimDrivers": claim_drivers or {},
                    "policy": _policy_wire(policy),
                }
            )
            sp.set(request_bytes=len(request))
        with tracing.span("client.rpc", request_bytes=len(request)) as sp:
            reply = self._consolidate(request, timeout=timeout)
            sp.set(reply_bytes=len(reply))
        with tracing.span("client.unpack", reply_bytes=len(reply)):
            return msgpack.unpackb(reply)

    def lease_get(self, name: str, namespace: str = "", timeout: float = 5.0):
        response = msgpack.unpackb(
            self._lease_get(msgpack.packb({"name": name, "namespace": namespace}),
                            timeout=timeout)
        )
        return response["lease"]

    def lease_apply(self, lease: Dict, expected_version=None, timeout: float = 5.0) -> Dict:
        response = msgpack.unpackb(
            self._lease_apply(
                msgpack.packb({"lease": lease, "expectedVersion": expected_version}),
                timeout=timeout,
            )
        )
        return response

    def solve(
        self,
        pods: List,
        provisioners: List,
        nodes: Optional[List[Dict]] = None,
        daemonset_pods: Optional[List] = None,
        claim_drivers: Optional[Dict[str, str]] = None,
        policy=None,
        timeout: float = 60.0,
    ) -> Dict:
        """nodes: [{"node": node_dict, "pods": [...], "volumeLimits": {...}}];
        claim_drivers: {"<ns>/<claim>": csi-driver} resolved by this plane so
        volume attach limits bind on the solver side; policy: the replica's
        resolved policy.PolicyConfig (or wire dict) so the remote objective
        stage selects offerings exactly like an in-process solve."""
        self._client_chaos("Solve")
        request = msgpack.packb(
            {
                "pods": [codec.pod_to_dict(p) for p in pods],
                "provisioners": [codec.provisioner_to_dict(p) for p in provisioners],
                "daemonsetPods": [codec.pod_to_dict(p) for p in daemonset_pods or []],
                "nodes": nodes or [],
                "claimDrivers": claim_drivers or {},
                "policy": _policy_wire(policy),
            }
        )
        return msgpack.unpackb(self._solve(request, timeout=timeout))

    def solve_classes(
        self,
        pods: List,
        provisioners: List,
        nodes: Optional[List[Dict]] = None,
        daemonset_pods: Optional[List] = None,
        claim_drivers: Optional[Dict[str, str]] = None,
        members: Optional[List[List[int]]] = None,
        policy=None,
        timeout: float = 60.0,
    ) -> Dict:
        """Class-columnar solve: dedup ``pods`` into shape classes locally,
        ship one representative + count per class, and expand the per-node
        class counts back into this caller's pod objects.  Returns the same
        dict shape as solve() (podIndices refer to the ``pods`` argument).

        ``members`` — precomputed class membership (lists of indices into
        ``pods``), for callers that already classified the batch (the
        provisioning controller's split does) so the O(pods) signature pass
        doesn't run twice on the hot path.  ``policy`` — the replica's
        resolved policy.PolicyConfig (or wire dict); without it a remote
        solve silently ran first-fit selection while the replica believed
        the objective was on."""
        self._client_chaos("SolveClasses")
        if members is None:
            from karpenter_core_tpu.models.columnar import group_by_signature

            with tracing.span("client.classify", pods=len(pods)) as sp:
                by_sig, fast_keys, punted = group_by_signature(pods)
                members = list(by_sig.values())
                sp.set(classes=len(members), fast_keys=fast_keys, punted=punted)
        with tracing.span("client.pack", classes=len(members)) as sp:
            request = msgpack.packb(
                {
                    "podClasses": [
                        {"pod": codec.pod_to_dict(pods[idxs[0]]), "count": len(idxs)}
                        for idxs in members
                    ],
                    "provisioners": [codec.provisioner_to_dict(p) for p in provisioners],
                    "daemonsetPods": [codec.pod_to_dict(p) for p in daemonset_pods or []],
                    "nodes": nodes or [],
                    "claimDrivers": claim_drivers or {},
                    "policy": _policy_wire(policy),
                }
            )
            sp.set(request_bytes=len(request))
        response = self._classes_rpc(request, timeout)
        with tracing.span("client.expand", classes=len(members)) as sp:
            cursors = [0] * len(members)

            def take(counts) -> List[int]:
                indices: List[int] = []
                for c, n in counts:
                    start = cursors[c]
                    indices.extend(members[c][start : start + n])
                    cursors[c] = start + n
                return indices

            expanded = {
                "newNodes": [
                    {
                        "provisioner": n["provisioner"],
                        "instanceTypes": n["instanceTypes"],
                        "zones": n["zones"],
                        "requests": n["requests"],
                        "podIndices": take(n["classCounts"]),
                    }
                    for n in response["newNodes"]
                ],
                "existingAssignments": {
                    name: take(counts)
                    for name, counts in response["existingAssignments"].items()
                },
                "failedPodIndices": take(response["failedClassCounts"]),
                "residualPodIndices": take(response.get("residualClassCounts", [])),
                "existingCommittedZones": response.get("existingCommittedZones", {}),
            }
            sp.set(nodes=len(expanded["newNodes"]), pods=sum(cursors))
        return expanded

    def _classes_rpc(self, request: bytes, timeout: float) -> Dict:
        """One ``/SolveClasses`` round trip: the gRPC call (hop, framing and
        the server's handler inside it), then the reply's msgpack."""
        with tracing.span("client.rpc", request_bytes=len(request)) as sp:
            reply = self._solve_classes(request, timeout=timeout)
            sp.set(reply_bytes=len(reply))
        with tracing.span("client.unpack", reply_bytes=len(reply)):
            return msgpack.unpackb(reply)

    def solve_tenant_classes(
        self,
        pod_classes: List[tuple],
        provisioners: List,
        tenant: Dict,
        nodes: Optional[List[Dict]] = None,
        daemonset_pods: Optional[List] = None,
        claim_drivers: Optional[Dict[str, str]] = None,
        policy=None,
        timeout: float = 60.0,
    ) -> Dict:
        """The multi-tenant protocol (docs/SERVICE.md): ship
        ``pod_classes`` ([(representative Pod, count)]) with a ``tenant``
        envelope ({id, sessionVersion, supplyDigest}) and get the RAW
        class-count response back, plus its ``tenant`` echo ({id, solveMode,
        reason, sessionVersion, batched}).  Delta responses carry only the
        delta's placements, so no client-side pod expansion happens here —
        the caller owns the count→pod mapping.  A response carrying
        ``error`` is this tenant's structured ejection (its co-batched
        tenants were answered normally); sheds/isolation surface as
        RESOURCE_EXHAUSTED / UNAVAILABLE RpcErrors whose details carry a
        ``retry-after-s=`` hint (service.tenant.parse_retry_after)."""
        self._client_chaos("SolveClasses")
        envelope = dict(tenant)
        # read BEFORE any span of this method opens: the context stamped
        # into the envelope is the CALLER's.  Under a span of our own the
        # server's solve.tenant would adopt a trace no caller holds and
        # leave the trace of whoever wraps the handler.
        ctx = tracing.wire_context()
        if ctx is not None and "trace" not in envelope:
            # stamp the caller's active span so the server-side segment
            # joins the same trace tree (schema-additive; SCHEMA.md)
            envelope["trace"] = ctx
        with tracing.span("client.pack", classes=len(pod_classes)) as sp:
            request = msgpack.packb(
                {
                    "podClasses": [
                        {"pod": codec.pod_to_dict(pod), "count": int(count)}
                        for pod, count in pod_classes
                    ],
                    "provisioners": [codec.provisioner_to_dict(p) for p in provisioners],
                    "daemonsetPods": [codec.pod_to_dict(p) for p in daemonset_pods or []],
                    "nodes": nodes or [],
                    "claimDrivers": claim_drivers or {},
                    "policy": _policy_wire(policy),
                    "tenant": envelope,
                }
            )
            sp.set(request_bytes=len(request))
        return self._classes_rpc(request, timeout)

    def close(self) -> None:
        self.channel.close()


class RemoteLeaseStore:
    """Lease store backed by the solver service's lease plane.

    Exposes the same get/create/update_with_version surface the in-process
    KubeClient gives LeaderElector, so an operator replica can elect through
    the shared solver instead of its private in-memory store — which is what
    makes the two-replica deployment's HA story real (VERDICT r2 #4; the
    reference's analog is the apiserver-hosted Lease, operator.go:111-126).
    """

    def __init__(self, client: "SnapshotSolverClient | str") -> None:
        self.client = (
            SnapshotSolverClient(client) if isinstance(client, str) else client
        )

    @staticmethod
    def _to_wire(lease) -> Dict:
        return {
            "name": lease.metadata.name,
            "namespace": lease.metadata.namespace,
            "holderIdentity": lease.spec.holder_identity,
            "leaseDurationSeconds": lease.spec.lease_duration_seconds,
            "acquireTime": lease.spec.acquire_time,
            "renewTime": lease.spec.renew_time,
            "leaseTransitions": lease.spec.lease_transitions,
        }

    @staticmethod
    def _from_wire(wire: Dict):
        from karpenter_core_tpu.apis.objects import Lease, LeaseSpec, ObjectMeta

        lease = Lease(
            metadata=ObjectMeta(
                name=wire["name"], namespace=wire.get("namespace", "")
            ),
            spec=LeaseSpec(
                holder_identity=wire.get("holderIdentity", ""),
                lease_duration_seconds=wire.get("leaseDurationSeconds", 15),
                acquire_time=wire.get("acquireTime", 0.0),
                renew_time=wire.get("renewTime", 0.0),
                lease_transitions=wire.get("leaseTransitions", 0),
            ),
        )
        lease.metadata.resource_version = wire.get("resourceVersion", 0)
        return lease

    def get(self, kind, name: str, namespace: str = ""):
        wire = self.client.lease_get(name, namespace or "")
        return self._from_wire(wire) if wire is not None else None

    def create(self, lease):
        from karpenter_core_tpu.operator.kubeclient import ConflictError

        response = self.client.lease_apply(self._to_wire(lease), expected_version=None)
        if not response["ok"]:
            raise ConflictError(f"lease {lease.metadata.name} already exists")
        return self._from_wire(response["lease"])

    def update_with_version(self, lease, expected_resource_version):
        from karpenter_core_tpu.operator.kubeclient import ConflictError

        response = self.client.lease_apply(
            self._to_wire(lease), expected_version=expected_resource_version
        )
        if not response["ok"]:
            raise ConflictError(f"lease {lease.metadata.name} version conflict")
        return self._from_wire(response["lease"])
