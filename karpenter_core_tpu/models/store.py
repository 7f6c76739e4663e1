"""Versioned, diffable snapshot store — the substrate of the incremental
warm-start solve path (docs/INCREMENTAL.md).

Every reconcile used to re-encode and re-solve the full cluster snapshot from
scratch; at steady-state churn rates only a handful of pods/nodes change
between ticks, so the right amortization is to version each encode and solve
only the diff.  Three pieces:

  - ``VersionedSnapshot``: one ``encode_snapshot`` output stamped with a
    monotonic version, per-plane content digests (sha256 over the encoded
    tensor bytes — process-independent), and the per-class membership rows
    the diff operates on.  The rows ride ``models.columnar.PodIngest``'s
    existing equivalence-class bookkeeping (``class_members``) — the diff
    never re-derives a pod signature.
  - ``SnapshotDelta``: the structured difference between two versions — new
    and evicted pod rows per class, new/removed classes, which supply-side
    planes changed, the unchanged class-index extents, and the delta
    fraction the fallback policy thresholds on.  ``apply`` replays a delta
    onto the older version's membership summary; ``diff`` then ``apply``
    reproducing the newer summary is the store's core invariant
    (tests/test_incremental.py).
  - ``SnapshotStore``: holds the current version, mints the next
    (``commit``), and diffs (``diff_snapshots``).

Supply-side change detection (``supply_digest``/``catalog_digest``) hashes
the solve INPUTS — state nodes, bound pods, catalog, provisioner templates —
not the encoded planes, because the whole point of a delta reconcile is to
skip the encode when nothing supply-side moved.
"""

from __future__ import annotations

import copy
import hashlib
import operator
from collections.abc import Sequence
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from karpenter_core_tpu.models.snapshot import EncodedSnapshot, _class_signature

# plane groups digested independently, so a delta can name WHICH side moved
# (a catalog refresh invalidates different reuse than a pod-row change)
PLANE_FIELDS: Dict[str, Tuple[str, ...]] = {
    "catalog": (
        "it_mask", "it_defined", "it_negative", "it_gt", "it_lt",
        "it_alloc", "it_avail", "it_price", "it_capacity",
    ),
    "templates": (
        "tmpl_mask", "tmpl_defined", "tmpl_negative", "tmpl_gt", "tmpl_lt",
        "tmpl_zone", "tmpl_ct", "tmpl_it", "tmpl_daemon", "tmpl_limits",
    ),
    "vocab": ("valid", "is_custom", "vocab_ints"),
    "classes": (
        "cls_mask", "cls_defined", "cls_negative", "cls_gt", "cls_lt",
        "cls_zone", "cls_ct", "cls_it", "cls_requests", "cls_count",
        "cls_relax_next", "cls_anti_soft", "cls_root", "cls_tol", "cls_ports",
    ),
    "groups": ("grp_skew", "grp_is_zone", "grp_is_anti", "grp_member", "cls_groups"),
    # the policy-objective planes (policy.planes): the price sheet versions
    # independently of the feasibility catalog so a spot-market move (or a
    # risk/throughput prior change) is its own named escalation reason
    "policy": ("pol_price", "pol_risk", "pol_throughput"),
}


def _digest_arrays(arrays) -> str:
    """sha256 over dtype + shape + raw bytes of each array, in order.  Pure
    content — no id()s, no hash() — so two processes encoding the same input
    produce the same digest (PYTHONHASHSEED-independent)."""
    h = hashlib.sha256()
    for a in arrays:
        if a is None:
            h.update(b"<none>")
            continue
        arr = np.ascontiguousarray(np.asarray(a))
        h.update(str(arr.dtype).encode())
        h.update(repr(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


def snapshot_digests(
    snapshot: EncodedSnapshot,
    prev_snapshot: Optional[EncodedSnapshot] = None,
    prev_digests: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    """Per-plane content digests of one encoded snapshot, plus an ``axes``
    digest covering the name spaces the planes index into.

    ``prev_snapshot``/``prev_digests`` enable the delta-consuming commit: a
    plane group whose every array is the SAME OBJECT as in the previously
    digested snapshot reuses the previous digest instead of re-hashing the
    bytes.  The delta-native encode (models.snapshot ``encode_reused``)
    shares unchanged planes by reference exactly so this identity test
    fires; content digests stay content digests — identity is only ever a
    proof that the content cannot have changed (planes are immutable
    post-encode)."""
    out = {}
    for name, fields in PLANE_FIELDS.items():
        prev = prev_digests.get(name) if prev_digests else None
        if (
            prev is not None
            and prev_snapshot is not None
            and all(
                getattr(snapshot, f, None) is getattr(prev_snapshot, f, None)
                for f in fields
            )
        ):
            out[name] = prev
            continue
        out[name] = _digest_arrays(getattr(snapshot, f, None) for f in fields)
    h = hashlib.sha256()
    for axis in (
        snapshot.resources, snapshot.zones, snapshot.capacity_types,
        snapshot.it_names, [repr(p) for p in (snapshot.ports or [])],
    ):
        h.update(("\x1f".join(axis) + "\x1e").encode())
    h.update(repr(tuple(snapshot.features or ())).encode())
    h.update(str(snapshot.scan_passes).encode())
    out["axes"] = h.hexdigest()
    return out


def stable_canonical(obj):
    """A cross-process canonical form of a (possibly nested) python value:
    sets/frozensets are sorted, dicts are sorted item tuples, everything else
    passes through (or falls back to repr).  The point is PYTHONHASHSEED
    independence — class keys hold frozensets whose iteration (and repr)
    order is hash-randomized, so a digest of a raw repr would differ between
    two processes encoding identical state.  The durable-session journal
    (service/journal.py) verifies restored lineages against digests the
    crashed process wrote, so its verification digests must canonicalize
    through here."""
    if isinstance(obj, (frozenset, set)):
        return ("set", tuple(sorted((stable_canonical(v) for v in obj), key=repr)))
    if isinstance(obj, dict):
        return (
            "map",
            tuple(sorted(
                ((stable_canonical(k), stable_canonical(v)) for k, v in obj.items()),
                key=repr,
            )),
        )
    if isinstance(obj, (list, tuple)):
        return tuple(stable_canonical(v) for v in obj)
    if isinstance(obj, (str, int, float, bool, bytes, type(None))):
        return obj
    return repr(obj)


def stable_digest(obj) -> str:
    """sha256 over the stable canonical form — equal values digest equally in
    any process, whatever PYTHONHASHSEED says."""
    return hashlib.sha256(repr(stable_canonical(obj)).encode()).hexdigest()


def content_digest(chunks) -> str:
    """sha256 hex over an iterable of byte chunks — the whole-file content
    digest the fleet session checkpoints (fleet/checkpoint.py) stamp in their
    trailer frame and re-derive on read (never-trust: a checkpoint whose body
    doesn't hash to its trailer is treated as missing, the restore ladder
    falls to journal replay)."""
    h = hashlib.sha256()
    for chunk in chunks:
        h.update(chunk)
    return h.hexdigest()


def class_key(cls) -> tuple:
    """Version-stable identity of one class row: the equivalence-class
    signature of its representative pod (ladder variants carry the relaxed
    representative, so each rung keys distinctly).  Producers that already
    hold the signature stamp it on the class (``PodClass.interned_sig``,
    contract: equals the derivation exactly) so commits skip the O(C)
    re-derivation."""
    sig = getattr(cls, "interned_sig", None)
    if sig is not None:
        return sig
    return _class_signature(cls.pods[0])


@dataclass(frozen=True)
class ClassRow:
    """One class's membership at one version (roots carry the pod uids;
    ladder variants place rolled-over counts and own no pods)."""

    key: tuple
    count: int
    uids: Tuple[str, ...] = ()


@dataclass
class VersionedSnapshot:
    """One encode output + the version metadata the diff operates on."""

    version: int
    snapshot: EncodedSnapshot
    digests: Dict[str, str]
    rows: Tuple[ClassRow, ...]
    supply: str = ""  # supply_digest at encode time ("" = not tracked)

    def index_of(self) -> Dict[tuple, int]:
        return {row.key: i for i, row in enumerate(self.rows)}

    def summary(self) -> Dict[tuple, Tuple[str, ...]]:
        """class key -> member uids (the diff/apply state space)."""
        return {row.key: row.uids for row in self.rows}


@dataclass
class SnapshotDelta:
    """Structured difference between two snapshot versions."""

    from_version: int
    to_version: int
    # pod-row changes, keyed by class identity
    added: Dict[tuple, Tuple[str, ...]] = field(default_factory=dict)
    evicted: Dict[tuple, Tuple[str, ...]] = field(default_factory=dict)
    new_classes: Tuple[tuple, ...] = ()
    removed_classes: Tuple[tuple, ...] = ()
    # supply-side planes whose digests differ (catalog/templates/vocab/
    # groups/axes — plus "supply" when the node-side input digest moved)
    changed_planes: Tuple[str, ...] = ()
    # class-index extents (start, end) of the NEWER version whose rows are
    # untouched — the regions a masked repair never has to look at
    unchanged_extents: Tuple[Tuple[int, int], ...] = ()
    touched_classes: Tuple[int, ...] = ()
    # requirement-mask words touched by class changes: word count of the
    # packed cls_mask rows of touched classes (0 when only counts moved)
    touched_mask_words: int = 0
    pods_before: int = 0
    pods_after: int = 0
    # how the diff was reached, not what it says: keys diffed from two
    # counted memberships, and keys diffed as uid sets
    counted_classes: int = field(default=0, compare=False)
    uid_classes: int = field(default=0, compare=False)

    @property
    def added_count(self) -> int:
        return sum(len(u) for u in self.added.values())

    @property
    def evicted_count(self) -> int:
        return sum(len(u) for u in self.evicted.values())

    @property
    def delta_fraction(self) -> float:
        """(added + evicted) over the larger population — the fallback
        policy's primary threshold."""
        base = max(self.pods_before, self.pods_after, 1)
        return (self.added_count + self.evicted_count) / base

    @property
    def node_side_changed(self) -> bool:
        return bool(self.changed_planes)

    @property
    def class_shape_changed(self) -> bool:
        """True when the class AXIS itself moved (new/removed classes) —
        tensor reuse is impossible and the repair must re-encode."""
        return bool(self.new_classes or self.removed_classes)

    def apply(self, prev_summary: Dict[tuple, Tuple[str, ...]]) -> Dict[tuple, Tuple[str, ...]]:
        """Replay this delta onto the older version's membership summary.
        ``diff_snapshots(prev, cur)`` then ``apply(prev.summary())`` must
        reproduce ``cur.summary()`` exactly (diff ∘ apply == identity)."""
        out = {key: list(uids) for key, uids in prev_summary.items()}
        for key in self.removed_classes:
            out.pop(key, None)
        for key in self.new_classes:
            out.setdefault(key, [])
        for key, uids in self.evicted.items():
            if key in out:
                gone = set(uids)
                out[key] = [u for u in out[key] if u not in gone]
        for key, uids in self.added.items():
            out.setdefault(key, []).extend(uids)
        return {
            key: tuple(uids) for key, uids in out.items()
            if uids or key in self.new_classes or key not in self.evicted
        }


class CountedUids(Sequence):
    """The member uids ``<base>#<j>`` for ``start <= j < stop``: a class's
    membership carried as its count.  Members of a tenant class are
    fungible copies of one representative, named by their class digest and
    their index (``service.snapshot_channel``), so the membership of a class
    of ``count`` pods IS ``CountedUids(base, count)`` — a prefix — and the
    diff of two counts is a slice of one of them.  Immutable; equal to the
    tuple it stands for, and it builds no string until one is asked for."""

    __slots__ = ("base", "_range")

    def __init__(self, base: str, stop: int, start: int = 0) -> None:
        self.base = base
        self._range = range(start, max(start, stop))

    @property
    def start(self) -> int:
        return self._range.start

    @property
    def stop(self) -> int:
        return self._range.stop

    @property
    def prefix(self) -> bool:
        """The whole membership of a class (``#0 … #n-1``), not a slice."""
        return self._range.start == 0 or not self._range

    def position(self, uid) -> Optional[int]:
        """Where ``uid`` stands in this sequence, or None."""
        if not isinstance(uid, str):
            return None
        base, _, j = uid.rpartition("#")
        if base != self.base or not (j.isascii() and j.isdigit()):
            return None
        n = int(j)
        if str(n) != j or n not in self._range:
            return None
        return n - self._range.start

    def __len__(self) -> int:
        return len(self._range)

    def __getitem__(self, index):
        if isinstance(index, slice):
            r = self._range[index]
            if r.step != 1:
                return tuple(f"{self.base}#{j}" for j in r)
            return CountedUids(self.base, r.stop, r.start)
        return f"{self.base}#{self._range[index]}"

    def __iter__(self):
        base = self.base
        return (f"{base}#{j}" for j in self._range)

    def __eq__(self, other):
        if isinstance(other, CountedUids):
            if not self._range and not other._range:
                return True
            return self.base == other.base and self._range == other._range
        if isinstance(other, tuple):
            return len(other) == len(self) and all(
                a == b for a, b in zip(self, other)
            )
        return NotImplemented

    def __hash__(self) -> int:
        return hash(tuple(self))

    def __repr__(self) -> str:
        return f"CountedUids({self.base!r}, {self.stop}, {self.start})"


def member_copy(rep, uid: str):
    """One member of a class: a copy of the representative with its own
    metadata, differing from it in the uid alone."""
    pod = copy.copy(rep)
    pod.metadata = copy.copy(rep.metadata)
    pod.metadata.uid = uid
    return pod


class CountedPods(Sequence):
    """A class's ``count`` members as a list-like sequence over its
    representative: member ``j`` is ``member_copy(rep, "<base>#<j>")``.  A
    member is copied the first time it is asked for — by index, slice or
    iteration; a slice or an iteration copies them all — and the same object
    is handed out after that.  ``uids`` reads the membership without copying
    anything; ``copied`` counts the copies made."""

    __slots__ = ("rep", "uids", "copied", "_made", "_all")

    def __init__(self, rep, base: str, count: int) -> None:
        self.rep = rep
        self.uids = CountedUids(base, count)
        self.copied = 0
        self._made: Optional[Dict[int, object]] = {}
        self._all: Optional[list] = None

    def _copy(self, uid: str):
        self.copied += 1
        return member_copy(self.rep, uid)

    def materialize(self) -> list:
        """Every member, copied once."""
        if self._all is None:
            made = self._made
            self._all = [
                made[j] if j in made else self._copy(uid)
                for j, uid in enumerate(self.uids)
            ]
            self._made = None
        return self._all

    def __len__(self) -> int:
        return len(self.uids)

    def __getitem__(self, index):
        if self._all is not None or isinstance(index, slice):
            return self.materialize()[index]
        index = operator.index(index)
        uid = self.uids[index]  # IndexError as a list would raise it
        j = index % len(self.uids)
        pod = self._made.get(j)
        if pod is None:
            pod = self._made[j] = self._copy(uid)
        return pod

    def __iter__(self):
        return iter(self.materialize())


def members_without(uids, gone):
    """``uids`` less ``gone``, in order — counted where what is left is
    still a prefix of a counted membership (``gone`` was its tail), a
    tuple otherwise."""
    if isinstance(uids, CountedUids) and uids.prefix:
        at = {uids.position(u) for u in gone} - {None}
        left = len(uids) - len(at)
        if not at or min(at) == left:
            return uids[:left]
    drop = set(gone)
    return tuple(u for u in uids if u not in drop)


def members_with(uids, added):
    """``uids`` then ``added`` — counted where ``added`` continues a counted
    membership (or starts an empty one) from its count, a tuple otherwise."""
    if (
        isinstance(added, CountedUids)
        and added.start == len(uids)
        and (not uids or (isinstance(uids, CountedUids) and uids.prefix
                          and uids.base == added.base))
    ):
        return CountedUids(added.base, added.stop)
    return tuple(uids) + tuple(added)


def _counted_diff(prev, cur):
    """(added, evicted) of one key from two counts, or None where either
    side is not a counted prefix (an absent or empty side counts 0): a
    class going from a to b members gains ``#a … #b-1`` or loses ``#b …
    #a-1`` — what the set arithmetic finds, element for element and in
    order."""
    if not (isinstance(cur, CountedUids) and cur.prefix):
        return None
    if isinstance(prev, CountedUids):
        if not (prev.prefix and (not prev or prev.base == cur.base)):
            return None
    elif len(prev):
        return None
    a, b = len(prev), len(cur)
    return cur[a:], (prev[b:] if a > b else ())


def diff_members(
    prev_members: Dict[tuple, Tuple[str, ...]],
    cur_members: Dict[tuple, Tuple[str, ...]],
    from_version: int = 0,
    to_version: int = 0,
    supply_changed: Tuple[str, ...] = (),
) -> SnapshotDelta:
    """A SnapshotDelta from two membership maps alone — the NO-ENCODE diff a
    delta reconcile uses (class key -> member uids, straight off
    PodIngest.class_members or a prebuilt class list).  Plane-level fields
    (extents, mask words) stay empty: nothing was encoded to measure them;
    ``supply_changed`` carries the input-digest verdict instead.  A key
    whose two sides are counted memberships (``CountedUids``) is diffed from
    its two counts; every other key as two uid sets."""
    added: Dict[tuple, Tuple[str, ...]] = {}
    evicted: Dict[tuple, Tuple[str, ...]] = {}
    new_classes = tuple(k for k in cur_members if k not in prev_members)
    removed_classes = tuple(k for k in prev_members if k not in cur_members)
    counted = 0
    for key, uids in cur_members.items():
        prev = prev_members.get(key, ())
        by_count = _counted_diff(prev, uids)
        if by_count is not None:
            counted += 1
            plus, minus = by_count
        else:
            before = set(prev)
            now = set(uids)
            plus = tuple(u for u in uids if u not in before)
            minus = tuple(u for u in prev if u not in now)
        if plus:
            added[key] = plus
        if minus:
            evicted[key] = minus
    for key in removed_classes:
        if prev_members[key]:
            evicted[key] = prev_members[key]
    return SnapshotDelta(
        from_version=from_version,
        to_version=to_version or from_version + 1,
        added=added,
        evicted=evicted,
        new_classes=new_classes,
        removed_classes=removed_classes,
        changed_planes=tuple(supply_changed),
        pods_before=sum(len(u) for u in prev_members.values()),
        pods_after=sum(len(u) for u in cur_members.values()),
        counted_classes=counted,
        uid_classes=len(cur_members) - counted,
    )


def rows_from_snapshot(snapshot: EncodedSnapshot) -> Tuple[ClassRow, ...]:
    """Membership rows in class order.  Root classes carry their pod uids;
    ladder variants own no pods (counts roll into them in-kernel)."""
    rows: List[ClassRow] = []
    for cls in snapshot.classes:
        uids = () if cls.is_ladder_variant else tuple(p.uid for p in cls.pods)
        rows.append(ClassRow(key=class_key(cls), count=len(uids), uids=uids))
    return tuple(rows)


def diff_snapshots(prev: VersionedSnapshot, cur: VersionedSnapshot) -> SnapshotDelta:
    """The structured delta between two committed versions: the membership
    arithmetic delegated to ``diff_members`` (one implementation of the
    added/evicted/new/removed edge cases), plus the plane-level fields only
    committed versions can measure (digest verdicts, extents, mask words)."""
    delta = diff_members(
        prev.summary(), cur.summary(),
        from_version=prev.version, to_version=cur.version,
    )

    changed = tuple(
        name
        for name in ("catalog", "templates", "vocab", "groups", "axes", "policy")
        if prev.digests.get(name) != cur.digests.get(name)
    )
    if prev.supply != cur.supply:
        changed = changed + ("supply",)

    new_set = set(delta.new_classes)
    touched = tuple(
        i for i, row in enumerate(cur.rows)
        if row.key in delta.added or row.key in new_set or row.key in delta.evicted
    )
    touched_set = set(touched)
    extents: List[Tuple[int, int]] = []
    start: Optional[int] = None
    for i in range(len(cur.rows) + 1):
        clean = i < len(cur.rows) and i not in touched_set
        if clean and start is None:
            start = i
        elif not clean and start is not None:
            extents.append((start, i))
            start = None
    mask_words = 0
    cls_mask = getattr(cur.snapshot, "cls_mask", None)
    if cls_mask is not None and len(touched):
        row_words = int(np.prod(cls_mask.shape[1:])) if cls_mask.ndim > 1 else 0
        # bool planes pack 32 semantic slots per uint32 word in-kernel
        mask_words = len(touched) * -(-row_words // 32)
    delta.changed_planes = changed
    delta.unchanged_extents = tuple(extents)
    delta.touched_classes = touched
    delta.touched_mask_words = mask_words
    return delta


class SnapshotStore:
    """Holds the current snapshot version and mints successors.

    The store is deliberately small: versioning + diffing only.  The warm
    carry, padded tensors, and placement bookkeeping live in
    ``solver.incremental`` — they are solve-path state, not snapshot state.
    """

    def __init__(self) -> None:
        self._version = 0
        self.current: Optional[VersionedSnapshot] = None

    def seed_version(self, version: int) -> None:
        """Pre-position the version counter so the NEXT commit mints
        ``version + 1``.  Journal replay (service/journal.py) uses this to
        restore a recovered lineage at the exact version the crashed process
        last echoed to its client — without it, a replayed anchor would mint
        version 1 and every client claiming the true version would be forced
        into a spurious ``session-lost`` re-anchor.  Only valid on a store
        that has never committed (replay always starts from a fresh
        session)."""
        if self.current is not None:
            raise RuntimeError("seed_version is only valid before the first commit")
        self._version = max(int(version), 0)

    def commit(self, snapshot: EncodedSnapshot, supply: str = "") -> VersionedSnapshot:
        """Stamp one encode output as the next version and make it current.

        Consumes the delta-native encode's reuse: plane groups the encode
        shared by reference from the previous committed snapshot keep their
        digests without re-hashing a byte — on a steady-state churn tick only
        the ``classes`` group (whose cls_count moved) and the recomputed
        ``policy`` planes touch the hasher, so the commit cost scales with
        what changed, not with the fleet."""
        self._version += 1
        prev = self.current
        versioned = VersionedSnapshot(
            version=self._version,
            snapshot=snapshot,
            digests=snapshot_digests(
                snapshot,
                prev_snapshot=prev.snapshot if prev is not None else None,
                prev_digests=prev.digests if prev is not None else None,
            ),
            rows=rows_from_snapshot(snapshot),
            supply=supply,
        )
        self.current = versioned
        return versioned

    def diff(self, cur: VersionedSnapshot) -> Optional[SnapshotDelta]:
        """Delta from the current version to ``cur`` (None when no current)."""
        if self.current is None or cur is self.current:
            return None
        return diff_snapshots(self.current, cur)


def supply_digest(state_nodes, bound_pods) -> str:
    """Content digest of the solve's supply side INPUTS: state nodes (labels,
    available capacity, taints, volume limits/usage) and the bound pods whose
    membership seeds topology counts.  Computed without encoding anything —
    the delta path's whole point is skipping the encode when this is stable.
    O(nodes + bound pods) python, small constants."""
    h = hashlib.sha256()
    for sn in state_nodes or []:
        node = sn.node
        h.update(node.name.encode())
        h.update(repr(sorted(node.metadata.labels.items())).encode())
        h.update(repr(sorted(sn.available().items())).encode())
        h.update(repr(sorted(
            (t.key, t.value, t.effect) for t in sn.taints()
        )).encode())
        h.update(b"1" if sn.initialized() else b"0")
        h.update(repr(sorted(sn.volume_limits().items())).encode())
        h.update(repr(sorted(
            (d, tuple(sorted(ids))) for d, ids in sn.volume_usage().volumes.items()
        )).encode())
        h.update(b"\x1e")
    for pod in bound_pods or []:
        h.update((pod.uid or "").encode())
        h.update((pod.spec.node_name or "").encode())
        h.update((pod.namespace or "").encode())
        h.update(repr(sorted(pod.metadata.labels.items())).encode())
        h.update(b"\x1e")
    return h.hexdigest()


def catalog_digest(provisioners, instance_types) -> str:
    """Content digest of the provisioner/catalog inputs (the template plane's
    upstream).  Provisioner specs are covered via resourceVersion/generation
    plus the weight order; the catalog via names, capacity, and offerings."""
    h = hashlib.sha256()
    for p in provisioners or []:
        h.update(p.name.encode())
        h.update(str(p.metadata.resource_version or "").encode())
        h.update(str(getattr(p.metadata, "generation", "") or "").encode())
        h.update(str(getattr(p.spec, "weight", 0) or 0).encode())
        h.update(b"\x1e")
    for name in sorted(instance_types or {}):
        h.update(name.encode())
        for it in instance_types[name]:
            h.update(it.name.encode())
            h.update(repr(sorted(it.capacity.items())).encode())
            h.update(repr(sorted(
                (o.zone, o.capacity_type, o.available, o.price)
                for o in it.offerings
            )).encode())
        h.update(b"\x1e")
    return h.hexdigest()
