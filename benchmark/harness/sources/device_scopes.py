"""Device time by the program's named scopes, read from the profiler capture.

The kernel opens ``jax.named_scope`` names on its blocks (``kc.scan``,
``kc.step.derive``, ``kc.existing`` ...: docs/OBSERVABILITY.md "Scopes in a
profiler capture"); XLA carries them into every instruction's ``op_name``, and
a v5e capture keeps that path as the ``tf_op`` stat of each op's EVENT
METADATA, whole (``jit(f)/kc.scan/while/body/closed_call/kc.phase.plain/cond/
branch_1_fun/kc.existing/kc.fill/cumsum:``), also with ``enable_hlo_proto``
off and also for an executable that came through the export cache (PERF.md
§3: the probe).  ``jax.profiler.ProfileData`` shows an event's own stats and
not its metadata's, so this module reads the file's wire format itself: the
device planes' metadata tables and their ``XLA Ops`` lines, nothing else.

An op's scope path is the tuple of ``kc.<name>`` tokens of its ``op_name``, in
order, the ``kc.`` cut off; a ``vmap(kc.scan)`` wrapper counts as ``scan``; no
token is ``unscoped``.  XLA:TPU leaves a ``while``, a ``conditional`` and the
copies it inserts itself without an ``op_name`` (the probe again), so a
nameless event takes its path from the events it is nested with IN TIME, which
is the program's own nesting: one that spans others the longest common prefix
of theirs, strays aside (a named event right under it counts where it holds
``STRAY`` of their time or more: the ``while`` of the scan reads ``scan``
though XLA sinks a few ``kc.init`` constants into its body, the per-class
guard reads ``scan``, a phase's ``conditional`` its family); one that spans
nothing named — a copy, a running sum's ``reduce-window`` — the path of the
event around it and nothing deeper: where XLA's schedule put it among the ops
of some block says nothing of the block it belongs to, so it reads as its
family's or the scan's glue.  Such a path is marked inferred (the third member
of a key) and the tool prints the inferred seconds beside the own, by path and
by block; where no event around or inside carries a name nothing is inferred
and the time stays ``unscoped``.  An ``op_name`` that kept only its tail reads
by the components it kept.  Time is SELF time (``xplane.self_times``): a
``while`` or ``conditional`` is charged what its nested events do not cover.
Per traced unit (``bench.unit``), averaged over the chips as ``xplane.reduce``
averages; the median over the units in which an op ran, as ``kernel_device_s``
is.

spec: {"kind": "device_scopes", "any": ["existing", "step.prep_existing"]}
        ops whose path holds any of these components
      {"kind": "device_scopes", "any": ["scan"], "none": ["new", ...]}
        ... and none of those: the scan's glue, under no block
      {"kind": "device_scopes", "scoped_outside": "scan"}
        ops with a scope and no such component
      {"kind": "device_scopes", "ops": "^(cond|conditional|while)"}
        a cross-cut by the instruction's own name, whatever its scope
        ("^copy": the copies XLA inserts, most of them nameless)
      {"kind": "device_scopes", "share": "unscoped"}
        100 x unscoped self time / all op self time of the window

The share also prints the table as one earlier line of the run's output,

    {"device_by_scope": [[path, seconds, top_op], ...]}   the 15 largest paths
                                                          of the window

and is the instrument's own health: an executable loaded from a persistent
cache that was filled before the scopes existed carries no names, reads ~100
here and is said so on standard error, not charged to some block.

Reports nothing in a rehearsal (``facts["peaks"]`` is None there: the README
allows no device time from a rehearsal into any record, and the rehearsal
tests hold a rehearsal's metric names to an exact set).  On a capture with no
scoped op at all — a commit before the scopes — every spec but the share
reports nothing.
"""

import functools
import json
import re
import sys
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark.harness import annotations, stats, xplane

SCOPE = re.compile(r"kc\.([\w.]+)")
OP_NAME_STAT = "tf_op"
UNSCOPED = "unscoped"
TOP = 15
STRAY = 0.01  # of the named time right under a nameless event: less does not vote

Key = Tuple[tuple, str, bool]  # (scope path, instruction name, path inferred)


# -- the .xplane.pb wire format, as far as it is needed ------------------------
# XSpace.planes = 1; XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5
# (both maps: entry.value = 2); XLine: name 2, timestamp_ns 3, events 4;
# XEvent: metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata: id 1,
# name 2, stats 5; XStat: metadata_id 1, str_value 5, ref_value 7 (the id of a
# stat metadata whose name is the string); XStatMetadata: id 1, name 2.

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int) -> Iterator[tuple]:
    """``(field, value)`` of one message: an int for a varint, ``(start,
    end)`` for a length-delimited field; fixed-width fields are skipped."""
    while i < end:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield tag >> 3, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield tag >> 3, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"not an xplane file: wire type {wire}")


def _text(buf: bytes, span: tuple) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span: tuple) -> Optional[tuple]:
    return next((v for f, v in _fields(buf, *span) if f == 2), None)


def _event(buf: bytes, i: int, end: int) -> Tuple[int, int, int]:
    """``(metadata_id, offset_ps, duration_ps)`` of one XEvent, read in place:
    a capture holds millions (its field numbers fit one tag byte)."""
    found = [0, 0, 0, 0]
    while i < end:
        tag = buf[i]
        i += 1
        wire = tag & 7
        if wire == 0:
            value = shift = 0
            while True:
                byte = buf[i]
                i += 1
                value |= (byte & 0x7F) << shift
                if byte < 0x80:
                    break
                shift += 7
            if tag < 0x20:
                found[tag >> 3] = value
        elif wire == 2:
            n, i = _varint(buf, i)
            i += n
        else:
            i += 8 if wire == 1 else 4
    return found[1], found[2], found[3]


def scope_path(op_name: str) -> tuple:
    return tuple(SCOPE.findall(op_name))


def _plane(buf: bytes, span: tuple, window_ps: Optional[tuple] = None,
           details: Optional[dict] = None) -> Optional[List[tuple]]:
    """One device plane's ``XLA Ops`` events as ``(path, instruction,
    start_ps, end_ps)``, the path the event's own; None for any other plane.
    Events that lie wholly outside ``window_ps`` are dropped as they are read
    (one that overlaps it stays: it may enclose events inside).  ``details``
    (the tool's) is filled with ``{instruction: its text}``."""
    name, lines, event_meta, stat_names = "", [], [], {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 3:
            lines.append(v)
        elif f == 4:
            event_meta.append(v)
        elif f == 5:
            meta = dict(_fields(buf, *_map_value(buf, v)))
            stat_names[meta.get(1)] = _text(buf, meta[2]) if 2 in meta else ""
    if not xplane.DEVICE_PLANE.match(name):
        return None
    keys: Dict[int, tuple] = {}
    for entry in event_meta:
        meta_id, text, op_name = None, "", ""
        for f, v in _fields(buf, *_map_value(buf, entry)):
            if f == 1:
                meta_id = v
            elif f == 2:
                text = _text(buf, v)
            elif f == 5:
                stat = dict(_fields(buf, *v))
                if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                    op_name = (_text(buf, stat[5]) if 5 in stat
                               else stat_names.get(stat.get(7), ""))
        match = xplane.OP_NAME.match(text)
        instruction = match.group(1) if match else text[:64]
        keys[meta_id] = (scope_path(op_name), instruction)
        if details is not None:
            details[instruction] = text
    ops = []
    for line in lines:
        line_name, t0_ns, events = "", 0, []
        for f, v in _fields(buf, *line):
            if f == 2:
                line_name = _text(buf, v)
            elif f == 3:
                t0_ns = v
            elif f == 4:
                events.append(v)
        if line_name != xplane.OP_LINE:
            continue
        lo, hi = window_ps or (float("-inf"), float("inf"))
        for span in events:
            meta_id, offset_ps, duration_ps = _event(buf, *span)
            start_ps = t0_ns * 1000 + offset_ps
            if start_ps < hi and start_ps + duration_ps > lo:
                ops.append((*keys.get(meta_id, ((), "?")), start_ps, start_ps + duration_ps))
    return ops


def _shared_prefix(nested: List[tuple]) -> tuple:
    """The longest common prefix of the paths in ``nested`` — ``(path,
    picoseconds)`` of the named events right under a nameless one — those
    aside that hold less than ``STRAY`` of the time."""
    floor = STRAY * sum(ps for _, ps in nested)
    weight: Dict[tuple, int] = {}
    for path, ps in nested:
        weight[path] = weight.get(path, 0) + ps
    voting = [path for path, ps in weight.items() if ps >= floor]
    first, last = min(voting), max(voting)
    n = next((i for i, (a, b) in enumerate(zip(first, last)) if a != b),
             min(len(first), len(last)))
    return first[:n]


def resolve(ops: List[tuple]) -> List[tuple]:
    """``(key, start_s, end_s)`` of one chip's events, the nameless ones
    given the path of the events they are nested with (module docstring)."""
    ops = sorted(ops, key=lambda o: (o[2], -o[3]))
    paths = [o[0] for o in ops]
    parent = [-1] * len(ops)
    stack: List[int] = []
    for i, (_, _, start, _) in enumerate(ops):
        while stack and ops[stack[-1]][3] <= start:
            stack.pop()
        if stack:
            parent[i] = stack[-1]
        stack.append(i)
    nested: Dict[int, List[tuple]] = {}
    for i in range(len(ops) - 1, -1, -1):  # an event's nested events follow it
        if not paths[i] and i in nested:
            paths[i] = _shared_prefix(nested.pop(i))
        if paths[i] and parent[i] >= 0 and not paths[parent[i]]:
            nested.setdefault(parent[i], []).append((paths[i], ops[i][3] - ops[i][2]))
    for i, path in enumerate(paths):  # an event's enclosing event precedes it
        if not path and parent[i] >= 0:  # spans nothing named: the event around it
            paths[i] = paths[parent[i]]
    return [((path, own[1], path != own[0]), own[2] * 1e-12, own[3] * 1e-12)
            for path, own in zip(paths, ops)]


def device_ops(path: str, window: Optional[tuple] = None,
               details: Optional[dict] = None) -> List[List[tuple]]:
    """The op events of every chip that ran one inside ``window`` (seconds;
    everywhere without one), each with its key."""
    with open(path, "rb") as f:
        buf = f.read()
    window_ps = window and (window[0] * 1e12, window[1] * 1e12)
    planes = (_plane(buf, v, window_ps, details)
              for f, v in _fields(buf, 0, len(buf)) if f == 1)
    return [resolve(ops) for ops in planes if ops]


def self_seconds(chips: List[List[tuple]], window: tuple) -> Dict[Key, float]:
    """Self seconds inside ``window`` by key, averaged over the chips."""
    lo, hi = window
    out: Dict[Key, float] = {}
    for ops in chips:
        inside = [o for o in ops if o[2] > lo and o[1] < hi]
        for key, seconds in xplane.self_times(inside, window).items():
            out[key] = out.get(key, 0.0) + seconds / len(chips)
    return out


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    """``{"window": {key: seconds}, "units": [{key: seconds}]}``: the whole
    traced window's table and one per ``bench.unit`` — the reduced tables
    alone are kept, the events go when this returns."""
    units = annotations.load(path)["units"]
    if not units:
        return {"window": {}, "units": []}
    window = (units[0][0], units[-1][1])
    chips = device_ops(path, window)
    return {"window": self_seconds(chips, window) if chips else {},
            "units": [self_seconds(chips, unit) for unit in units] if chips else []}


def matches(spec: dict, key: Key) -> bool:
    path, instruction, _ = key
    if "any" in spec:
        return (not set(spec["any"]).isdisjoint(path)
                and set(spec.get("none", ())).isdisjoint(path))
    if "scoped_outside" in spec:
        return bool(path) and spec["scoped_outside"] not in path
    if "ops" in spec:
        return re.search(spec["ops"], instruction) is not None
    return not path  # the share's: unscoped


def seconds(spec: dict, table: Dict[Key, float]) -> float:
    return sum(s for key, s in table.items() if matches(spec, key))


def by_path(table: Dict[Key, float]) -> List[list]:
    """``[path, seconds, top_op]``, largest first; a path is its components
    joined by ``/``."""
    total: Dict[tuple, float] = {}
    top: Dict[tuple, tuple] = {}
    for (path, instruction, _), s in table.items():
        total[path] = total.get(path, 0.0) + s
        if s > top.get(path, (-1.0, ""))[0]:
            top[path] = (s, instruction)
    return [["/".join(path) or UNSCOPED, s, top[path][1]]
            for path, s in sorted(total.items(), key=lambda kv: -kv[1])]


def value(spec: dict, capture: dict):
    window = capture["window"]
    busy_s = sum(window.values())
    if not busy_s:
        return None
    if "share" in spec:
        share = 100.0 * seconds(spec, window) / busy_s
        print(json.dumps({"device_by_scope": by_path(window)[:TOP]}), flush=True)
        if share > 50.0:
            print(f"device_scopes: {share:.0f} % of the window's op time carries no kc. "
                  "scope: an executable from before the scopes, or out of a compile cache "
                  "filled before them (clear <cache_dir>/xla)", file=sys.stderr)
        return share
    if not any(key[0] for key in window):  # a program without scopes
        return None
    values = [seconds(spec, table) for table in capture["units"] if table]
    return stats.median(values) if values else None


def read(spec: dict, facts: dict):
    if facts.get("peaks") is None:  # a rehearsal: see the docstring
        return None
    path = annotations.newest()
    return value(spec, load(path)) if path else None
