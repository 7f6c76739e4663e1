"""Tensor-level session checkpoints: one deserialize restores a warm lineage.

The durable-session journal (service/journal.py) already makes a tenant's
solve lineage recoverable — by REPLAYING the anchor + every delta request,
one device solve each.  At fleet scale that rung is too slow for failover: a
peer adopting a 64-delta tenant would re-run 64 solves before answering.
This module serializes the lineage's *state* instead of its *history*:

  frame 1  header   — tenant identity, lineage ``lineage_state()`` (the
                      never-trust verification summary), synthetic-uid bases,
                      writer replica + clock time
  frame 2  anchor   — the RAW wire bytes of the last FULL-solve request
  frame 3  tensors  — the padded SolvePrep planes, the warm scan carry, the
                      cumulative assignment planes, and the host bookkeeping
                      (IncrementalSolveSession.export_lineage), msgpack-coded
                      through a small typed codec (ndarrays, NamedTuples)
  frame 4  trailer  — sha256 over frames 1-3's payload bytes
                      (models.store.content_digest)

Frames reuse the journal's exact crc32c framing (``read_frames(magic=...)``)
under a distinct file magic, so torn/corrupt tails are detected by the same
discipline; the whole-file trailer digest catches anything subtler.  A
checkpoint that fails ANY check is treated as missing — the restore ladder
(service/snapshot_channel.py ``_fleet_adopt``) falls to journal replay, then
to the session-lost re-anchor.  Never a stale answer.

Restore (``restore_session``) is never-trust end to end: the adopting
replica re-decodes the checkpointed anchor request with its OWN decoder,
re-encodes and re-commits the snapshot, and requires the fresh commit's
plane digests — and, after ``adopt_restored``, the full ``lineage_state()``
— to equal the checkpointed ones bit for bit before the lineage serves.
"""

from __future__ import annotations

import hashlib
import logging
import os
import re
import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import msgpack
import numpy as np

from karpenter_core_tpu.metrics import REGISTRY
from karpenter_core_tpu.models.store import content_digest
from karpenter_core_tpu.service import journal as journal_mod
from karpenter_core_tpu.utils.clock import Clock

log = logging.getLogger(__name__)

MAGIC = b"KCFC1\n"
FORMAT = 1

STATUS_OK = journal_mod.STATUS_OK
STATUS_MISSING = journal_mod.STATUS_MISSING
# the whole-file trailer digest did not match (or the frame set was
# structurally wrong): framing survived, content cannot be trusted
STATUS_DIGEST = "digest"

CHECKPOINT_TOTAL = REGISTRY.counter(
    "karpenter_fleet_checkpoint_total",
    "Fleet session-checkpoint writes by result: written (fsynced and "
    "atomically published), skipped (no warm lineage / no anchor bytes to "
    "stamp), error (export or I/O failed — the tenant keeps its previous "
    "checkpoint, the journal stays the fallback rung), gc (an older "
    "generation removed by the KC_FLEET_CHECKPOINT_KEEP retention sweep).",
    ("result",),
)
CHECKPOINT_BYTES = REGISTRY.histogram(
    "karpenter_fleet_checkpoint_bytes",
    "Published fleet session-checkpoint file sizes in bytes (header + "
    "anchor request + tensor planes + trailer).",
    (),
    buckets=[4096.0, 65536.0, 262144.0, 1048576.0, 4194304.0,
             16777216.0, 67108864.0],
)


class FleetRestoreError(Exception):
    """A checkpoint failed never-trust verification at restore — callers
    degrade to the journal-replay rung."""


# -- typed msgpack codec ------------------------------------------------------
#
# export_lineage's value tree holds numpy/jax ndarrays and the kernel's
# NamedTuples (SolvePrep and its members).  msgpack sees none of those, so
# each is wrapped in a ``{"__kc__": tag, ...}`` dict; everything else must
# already be a msgpack scalar/list/dict.  Decoding resolves NamedTuple class
# names through an explicit registry — never arbitrary import — so a crafted
# checkpoint cannot name a class into existence (and the registry doubles as
# the format's schema: an unknown name means an incompatible writer).


def _nt_registry() -> Dict[str, type]:
    from karpenter_core_tpu.ops import masks as mask_ops
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.solver.tpu import SolvePrep

    classes = (
        SolvePrep,
        mask_ops.ReqTensor,
        solve_ops.ClassTensors,
        solve_ops.StaticArrays,
        solve_ops.SnapshotFeatures,
        solve_ops.NodeState,
        solve_ops.ExistingState,
        solve_ops.ExistingStatic,
        solve_ops.TopoCounts,
        solve_ops.WarmCarry,
    )
    return {c.__name__: c for c in classes}


def _dtype_of(name: str):
    try:
        return np.dtype(name)
    except TypeError:
        # extension dtypes (bfloat16) register through ml_dtypes — jax
        # imports it, but resolve explicitly so decode order can't matter
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def enc(x):
    """Value tree → msgpack-able tree (see module docstring)."""
    if x is None or isinstance(x, (bool, int, float, str, bytes)):
        return x
    if isinstance(x, np.generic):
        arr = np.asarray(x)
        return {"__kc__": "np", "d": arr.dtype.name, "b": arr.tobytes()}
    if isinstance(x, tuple) and hasattr(x, "_fields"):
        return {"__kc__": "nt", "c": type(x).__name__,
                "f": [enc(v) for v in x]}
    if isinstance(x, tuple):
        return {"__kc__": "tu", "v": [enc(v) for v in x]}
    if isinstance(x, list):
        return [enc(v) for v in x]
    if isinstance(x, dict):
        return {"__kc__": "map", "v": [[enc(k), enc(v)] for k, v in x.items()]}
    if hasattr(x, "dtype") and hasattr(x, "shape"):
        # NOT ascontiguousarray: it promotes 0-d arrays to 1-d, and
        # tobytes() already emits C order for any layout
        arr = np.asarray(x)
        return {"__kc__": "nd", "d": arr.dtype.name,
                "s": list(arr.shape), "b": arr.tobytes()}
    raise TypeError(f"checkpoint codec cannot serialize {type(x).__name__}")


def dec(x, registry: Optional[Dict[str, type]] = None):
    """Inverse of :func:`enc`.  Raises on unknown tags/classes (never-trust:
    an unreadable tree fails the restore, it does not improvise)."""
    if registry is None:
        registry = _nt_registry()
    if isinstance(x, list):
        return [dec(v, registry) for v in x]
    if not isinstance(x, dict):
        return x
    tag = x.get("__kc__")
    if tag == "nd":
        dtype = _dtype_of(x["d"])
        arr = np.frombuffer(x["b"], dtype=dtype)
        return arr.reshape(tuple(x["s"]))
    if tag == "np":
        return np.frombuffer(x["b"], dtype=_dtype_of(x["d"]))[0]
    if tag == "nt":
        cls = registry.get(x["c"])
        if cls is None:
            raise FleetRestoreError(f"unknown checkpoint tuple {x['c']!r}")
        if len(x["f"]) != len(cls._fields):
            # a writer whose tuple had other fields: positional decode would
            # misalign them, or fill the missing ones from the class defaults
            raise FleetRestoreError(
                f"checkpoint tuple {x['c']!r} has {len(x['f'])} fields,"
                f" this reader's has {len(cls._fields)}"
            )
        return cls(*(dec(v, registry) for v in x["f"]))
    if tag == "tu":
        return tuple(dec(v, registry) for v in x["v"])
    if tag == "map":
        return {dec(k, registry): dec(v, registry) for k, v in x["v"]}
    raise FleetRestoreError(f"unknown checkpoint codec tag {tag!r}")


# -- the file -----------------------------------------------------------------


class Checkpoint(NamedTuple):
    """A loaded, digest-verified checkpoint (still codec-encoded: the tensor
    frame decodes lazily at restore, where the NamedTuple registry lives)."""

    header: dict
    anchor: bytes
    tensors: dict
    path: str

    @property
    def version(self) -> int:
        return int(self.header.get("version", 0))

    @property
    def state(self) -> dict:
        return self.header.get("state") or {}


def _frame(payload: bytes) -> bytes:
    return journal_mod._FRAME_HEAD.pack(
        len(payload), journal_mod.crc32c(payload)
    ) + payload


def checkpoint_bytes(header: dict, anchor: bytes, tensors: dict) -> bytes:
    """Assemble the full checkpoint file image (used by write and by the
    round-trip tests): three payload frames + the digest trailer."""
    payloads = [
        msgpack.packb(header),
        msgpack.packb({"t": "anchor", "request": bytes(anchor)}),
        msgpack.packb(tensors),
    ]
    trailer = msgpack.packb({"t": "trailer", "sha256": content_digest(payloads)})
    return MAGIC + b"".join(_frame(p) for p in payloads + [trailer])


def load_checkpoint(path: str) -> Tuple[Optional[Checkpoint], str]:
    """Read + verify a checkpoint file.  NEVER raises: any torn frame, CRC
    failure, digest mismatch, or structural surprise returns (None, status)
    — the caller's ladder treats every non-OK status as "no checkpoint"."""
    try:
        records, status = journal_mod.read_frames(path, magic=MAGIC)
        if status != journal_mod.STATUS_OK:
            if status == journal_mod.STATUS_MISSING:
                return None, STATUS_MISSING
            return None, status
        if len(records) != 4:
            return None, STATUS_DIGEST
        header, anchor_rec, tensors, trailer = records
        if (
            header.get("t") != "header"
            or header.get("format") != FORMAT
            or anchor_rec.get("t") != "anchor"
            or tensors.get("t") != "tensors"
            or trailer.get("t") != "trailer"
        ):
            return None, STATUS_DIGEST
        # whole-file digest: re-pack the three payloads exactly as written
        # (msgpack round-trips our scalar/str/bytes/list/dict trees byte-
        # stably) and compare against the trailer
        digest = content_digest(
            msgpack.packb(rec) for rec in (header, anchor_rec, tensors)
        )
        if digest != trailer.get("sha256"):
            return None, STATUS_DIGEST
        anchor = anchor_rec.get("request")
        if not isinstance(anchor, (bytes, bytearray)):
            return None, STATUS_DIGEST
        return Checkpoint(header, bytes(anchor), tensors, path), STATUS_OK
    except Exception:  # noqa: BLE001 - a checkpoint is always optional
        log.exception("checkpoint load failed for %s", path)
        return None, STATUS_DIGEST


# -- the serving-side plane ---------------------------------------------------


def _safe_name(tenant_id: str) -> str:
    stem = re.sub(r"[^A-Za-z0-9_.-]", "_", tenant_id)[:64]
    suffix = hashlib.sha256(tenant_id.encode()).hexdigest()[:12]
    return f"{stem}-{suffix}.kcfc"


def _retention_keep() -> int:
    """Checkpoint generations retained per tenant (KC_FLEET_CHECKPOINT_KEEP,
    default 2, floor 1).  Keeping >1 lets the restore ladder fall back to the
    previous complete generation when the newest file fails verification —
    e.g. a disk-level corruption that lands AFTER publish — before degrading
    all the way to journal replay."""
    try:
        return max(int(os.environ.get("KC_FLEET_CHECKPOINT_KEEP", "2")), 1)
    except ValueError:
        return 2


class CheckpointPlane:
    """The serving replica's checkpoint writer + the adopting replica's
    reader, over one shared directory (FleetLocal.checkpoint_dir()).

    Writes are atomic (tmp + fsync + os.replace + directory fsync, the
    journal's compaction discipline) and KEYED BY TENANT AND GENERATION:
    each write publishes ``<stem>-<digest>.gNNNNNNNN.kcfc`` and then sweeps
    generations beyond the retention window (``keep``, default from
    KC_FLEET_CHECKPOINT_KEEP), so a reader always sees complete files and
    the newest failing verification still leaves the previous generation to
    restore from.  A pre-retention unsuffixed ``<stem>-<digest>.kcfc`` file
    is treated as generation 0.  ``after_solve`` is the cadence hook — every
    anchor solve, then every ``every``-th solve — and never raises:
    checkpointing is an optimization over the journal, losing one must never
    fail a solve that already answered."""

    def __init__(self, directory: str, *, clock: Optional[Clock] = None,
                 replica_id: str = "", every: int = 8,
                 keep: Optional[int] = None) -> None:
        self.directory = directory
        self.clock = clock or Clock()
        self.replica_id = replica_id
        self.every = max(int(every), 1)
        self.keep = _retention_keep() if keep is None else max(int(keep), 1)
        # serializes list-generations -> publish -> sweep: without it a
        # cadence write (serve path) racing the drain's write_all can compute
        # the SAME next generation number — one os.replace clobbers the
        # other — and sweep against a stale listing
        self._lock = threading.Lock()

    def _generations(self, tenant_id: str) -> List[Tuple[int, str]]:
        """Existing checkpoint generations for a tenant, newest first.  The
        legacy unsuffixed file participates as generation 0 so upgrades GC
        it once ``keep`` newer generations exist."""
        base = _safe_name(tenant_id)
        prefix = base[: -len(".kcfc")]
        pattern = re.compile(re.escape(prefix) + r"\.g(\d{8})\.kcfc\Z")
        found: List[Tuple[int, str]] = []
        try:
            names = os.listdir(self.directory)
        except OSError:
            return []
        for name in names:
            m = pattern.match(name)
            if m:
                found.append((int(m.group(1)),
                              os.path.join(self.directory, name)))
            elif name == base:
                found.append((0, os.path.join(self.directory, name)))
        found.sort(reverse=True)
        return found

    def path_for(self, tenant_id: str) -> str:
        """The current live checkpoint path: the newest published generation,
        or (before any generation exists) the legacy unsuffixed path."""
        gens = self._generations(tenant_id)
        if gens:
            return gens[0][1]
        return os.path.join(self.directory, _safe_name(tenant_id))

    def after_solve(self, tenant_id: str, entry, mode: str) -> None:
        # the cadence counter is entry state shared with the drain's
        # write_all: hold the entry lock (re-entrant — the serve path
        # already owns it) so the read-modify-write is never torn
        with entry.lock:
            entry.ckpt_ticks += 1
            if mode != "full" and entry.ckpt_ticks < self.every:
                return
            entry.ckpt_ticks = 0
        try:
            self.write(tenant_id, entry)
        except Exception:  # noqa: BLE001 - checkpointing must never fail a solve
            log.exception("fleet checkpoint write failed for tenant %s",
                          tenant_id)
            CHECKPOINT_TOTAL.labels("error").inc()

    def write(self, tenant_id: str, entry) -> Optional[str]:
        """Serialize the entry's lineage; returns the published path or None
        when there is nothing to checkpoint (no warm lineage, or the anchor
        request bytes were never captured)."""
        # snapshot the entry fields under its lock: write() also runs from
        # the drain's write_all, concurrently with a serve thread that is
        # mid-solve under this same lock on another tenant's thread
        with entry.lock:
            if not getattr(entry, "anchor_request", None):
                CHECKPOINT_TOTAL.labels("skipped").inc()
                return None
            export = entry.session.export_lineage()
            if export is None:
                CHECKPOINT_TOTAL.labels("skipped").inc()
                return None
            anchor_request = entry.anchor_request
            header = {
                "t": "header",
                "format": FORMAT,
                "tenant": tenant_id,
                "version": export["version"],
                "tseq": int(getattr(entry, "journal_tseq", 0)),
                "client_supply": getattr(entry, "supply_digest", None),
                "state": export["state"],
                "supply": export["supply"],
                "uid_bases": list(
                    getattr(entry, "anchor_uid_bases", ()) or ()
                ),
                "replica": self.replica_id,
                "written_at": float(self.clock.now()),
            }
        tensors = {
            "t": "tensors",
            "prep": enc(export["prep"]),
            "carry": enc(export["carry"]),
            "assign": enc(export["assign"]),
            "assign_ex": enc(export["assign_ex"]),
            "n_next": export["n_next"],
            "members_rows": export["members_rows"],
            "pod_loc": {u: list(v) for u, v in export["pod_loc"].items()},
            "failed_rows": dict(export["failed_rows"]),
            "delta_ticks": export["delta_ticks"],
            "initial_slots_used": export["initial_slots_used"],
            "materialized": list(export["materialized"]),
        }
        blob = checkpoint_bytes(header, anchor_request, tensors)
        os.makedirs(self.directory, exist_ok=True)
        # list -> publish -> sweep is one critical section: two concurrent
        # writers (cadence vs drain) listing the same generations would pick
        # the SAME next number — one publish silently clobbers the other —
        # and each would sweep against the other's stale listing
        with self._lock:
            gens = self._generations(tenant_id)
            gen = (gens[0][0] + 1) if gens else 1
            base = _safe_name(tenant_id)
            path = os.path.join(
                self.directory, f"{base[:-len('.kcfc')]}.g{gen:08d}.kcfc"
            )
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            dfd = os.open(self.directory, os.O_RDONLY)
            try:
                os.fsync(dfd)
            finally:
                os.close(dfd)
            CHECKPOINT_TOTAL.labels("written").inc()
            CHECKPOINT_BYTES.labels().observe(float(len(blob)))
            # retention sweep AFTER the publish fsync: the new generation is
            # durable before any older one disappears, so a crash mid-sweep
            # can only leave extras, never zero
            for _g, old in gens[self.keep - 1:]:
                try:
                    os.remove(old)
                    CHECKPOINT_TOTAL.labels("gc").inc()
                except OSError:
                    pass
        return path

    def write_all(self, entries: Dict[str, object]) -> int:
        """Drain hook: best-effort checkpoint of every resident lineage so
        the adopting replicas restore warm.  Returns the written count."""
        written = 0
        for tenant_id, entry in entries.items():
            try:
                if self.write(tenant_id, entry) is not None:
                    written += 1
            except Exception:  # noqa: BLE001 - drain keeps going
                log.exception("drain checkpoint failed for tenant %s",
                              tenant_id)
                CHECKPOINT_TOTAL.labels("error").inc()
        return written

    def load(self, tenant_id: str) -> Tuple[Optional[Checkpoint], str]:
        """Newest generation that VERIFIES wins: a corrupt newest file falls
        back to the previous retained generation before the caller's ladder
        degrades to journal replay."""
        gens = self._generations(tenant_id)
        if not gens:
            return load_checkpoint(
                os.path.join(self.directory, _safe_name(tenant_id))
            )
        status = STATUS_MISSING
        for i, (_gen, path) in enumerate(gens):
            ckpt, st = load_checkpoint(path)
            if ckpt is not None:
                return ckpt, st
            if i == 0:
                status = st  # report the newest generation's failure mode
        return None, status

    def drop(self, tenant_id: str) -> None:
        """A dropped tenant's checkpoints (every generation) must not
        resurrect it elsewhere."""
        for _gen, path in self._generations(tenant_id):
            try:
                os.remove(path)
            except OSError:
                pass
        try:
            os.remove(os.path.join(self.directory, _safe_name(tenant_id)))
        except OSError:
            pass


# -- restore ------------------------------------------------------------------


def restore_session(ckpt: Checkpoint, session, cloud_provider):
    """Rebuild a warm lineage from a checkpoint into a FRESH session.

    Never-trust, in order: (1) the anchor request re-decodes on THIS replica
    and its class-identity digests match the checkpointed uid bases; (2) the
    re-encoded snapshot commits at the checkpointed version with equal plane
    digests; (3) after ``adopt_restored``, the live ``lineage_state()``
    equals the checkpointed one exactly — aggregates, placement signature,
    supply, delta ticks.  Any failure raises :class:`FleetRestoreError`
    (or the underlying error) and the caller falls to journal replay.

    Returns the bound TPUSolver (callers reuse it for response bookkeeping).
    """
    from karpenter_core_tpu.policy import PolicyConfig
    from karpenter_core_tpu.service.snapshot_channel import (
        SnapshotSolverService,
    )
    from karpenter_core_tpu.solver.tpu import TPUSolver

    header = ckpt.header
    req = msgpack.unpackb(ckpt.anchor)
    (classes, uid_class, provisioners, daemonset_pods, state_nodes,
     bound, resolver) = SnapshotSolverService._decode_tenant_classes(req)
    want_bases = [str(b) for b in header.get("uid_bases", [])]
    if want_bases and list(uid_class) != want_bases:
        raise FleetRestoreError(
            "anchor class identities diverged from the checkpoint header"
        )
    solver = TPUSolver(
        cloud_provider, provisioners, daemonset_pods,
        kube_client=resolver,
        policy=PolicyConfig.from_wire(req.get("policy")),
    )
    session.reset()
    session.rebind(solver)
    version = int(header.get("version", 0))
    if version <= 0:
        raise FleetRestoreError("checkpoint carries no warm version")
    session.store.seed_version(version - 1)
    snapshot = solver.encode_classes(
        classes, state_nodes=state_nodes or None, bound_pods=bound
    )
    versioned = session.store.commit(snapshot, supply=str(header.get("supply")))
    state = ckpt.state
    if int(versioned.version) != version:
        raise FleetRestoreError(
            f"re-encoded anchor committed at version {versioned.version}, "
            f"checkpoint claims {version}"
        )
    if dict(versioned.digests) != dict(state.get("planes") or {}):
        raise FleetRestoreError(
            "re-encoded anchor plane digests diverged from the checkpoint"
        )
    registry = _nt_registry()
    t = ckpt.tensors
    prep = dec(t["prep"], registry)
    carry = dec(t["carry"], registry)
    members = {}
    for row, uids in t.get("members_rows", []):
        row = int(row)
        if row < 0 or row >= len(versioned.rows):
            raise FleetRestoreError(f"member row {row} outside the snapshot")
        members[versioned.rows[row].key] = tuple(str(u) for u in uids)
    session.adopt_restored(
        versioned, prep, carry,
        assign=dec(t["assign"], registry),
        assign_ex=dec(t["assign_ex"], registry),
        n_next=int(t["n_next"]),
        members=members,
        pod_loc={str(u): (int(r), str(k), int(i))
                 for u, (r, k, i) in t.get("pod_loc", {}).items()},
        failed_rows={str(u): int(r)
                     for u, r in t.get("failed_rows", {}).items()},
        supply=str(header.get("supply")),
        state_nodes=state_nodes,
        delta_ticks=int(t.get("delta_ticks", 0)),
        initial_slots_used=int(t.get("initial_slots_used", 0)),
        materialized=[str(u) for u in t.get("materialized", [])],
    )
    live = session.lineage_state()
    if live != state:
        session.reset()
        raise FleetRestoreError(
            f"restored lineage state diverged (have version "
            f"{live.get('version')}, checkpoint {state.get('version')})"
        )
    return solver


def scan_directory(directory: str) -> List[str]:
    """Checkpoint files currently published under ``directory`` (what the
    adopting replica and the soak's leak checks enumerate)."""
    try:
        return sorted(
            os.path.join(directory, name)
            for name in os.listdir(directory)
            if name.endswith(".kcfc")
        )
    except OSError:
        return []
