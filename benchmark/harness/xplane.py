"""From a profiler trace (``.xplane.pb``) to device numbers.

``load`` reads the file with nothing but JAX into a plain structure;
``reduce`` is a pure function of that structure, checked in
tests/benchmark_tests against a small recorded trace.  Every PR computes the
same numbers the same way, and none that claims a gain can change how.

What is read (one v5e trace looked at by hand, PERF.md §3):
  planes ``/device:TPU:<n>``   line ``XLA Ops``      one event per HLO op run;
                               a ``while`` spans its body's ops on the same
                               line, so per-op time is SELF time
                               line ``XLA Modules``  one event per program run
  plane  ``/host:CPU``         the benchmark's own TraceAnnotations
                               (``bench.unit`` / ``bench.client`` /
                               ``bench.handler``), on whichever thread ran them

Busy is the union of the intervals in which an op runs on a chip; the traced
window runs from the first ``bench.unit`` to the end of the last; the idle
share is 1 - busy / window.  Idle gaps (no chip busy) are named by what was
going on: inside a running program (``device.between_ops``: the program's own
latency), else by the benchmark annotation the host was under.
"""

import bisect
import re
from typing import Dict, List, Optional, Tuple

from benchmark.harness.sut import CLIENT, HANDLER, UNIT

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
HOST_PLANE = "/host:CPU"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast|psum|pmax|pmin", re.I)
TOP = 10
# the chip names an op event by its whole HLO instruction,
# ``%fusion.12 = f32[8192,3]{...} fusion(...)``: keep the instruction's name
OP_NAME = re.compile(r"^%?([\w.\-]+)")

Interval = Tuple[float, float]


def load(path: str, host_ops: bool = False) -> dict:
    """``{"annotations": {name: [(start_s, end_s)]}, "chips": [{"ops":
    [(name, start_s, end_s)], "modules": [...]}]}``.  ``host_ops`` is for a
    CPU rehearsal only: XLA:CPU runs ops on host threads, so events carrying
    an ``hlo_op`` stat stand in for one chip's op line."""
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    annotations: Dict[str, list] = {UNIT: [], CLIENT: [], HANDLER: []}
    chips: List[dict] = []
    host_chip = {"ops": [], "modules": []}

    def event(e) -> tuple:
        name = OP_NAME.match(e.name)
        return (name.group(1) if name else e.name[:64],
                e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)

    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OP_LINE:
                    chip["ops"] = [event(e) for e in line.events]
                elif line.name == MODULE_LINE:
                    chip["modules"] = [event(e) for e in line.events]
            chips.append(chip)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name in annotations:
                        annotations[e.name].append(event(e)[1:])
                    elif host_ops and e.duration_ns and any(
                            k == "hlo_op" for k, _ in e.stats):
                        host_chip["ops"].append(event(e))
    if host_ops and not chips:
        chips.append(host_chip)
    return {"annotations": annotations, "chips": chips}


def _clip(intervals, window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def _merge(intervals) -> List[Interval]:
    merged: List[list] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def _length(merged: List[Interval]) -> float:
    return sum(end - start for start, end in merged)


def self_times(ops, window: Interval) -> Dict[str, float]:
    """Seconds per op name, each event less the events nested in it."""
    lo, hi = window
    out: Dict[str, float] = {}
    stack: List[list] = []  # [name, end, self_seconds]

    def close(frame) -> None:
        out[frame[0]] = out.get(frame[0], 0.0) + max(frame[2], 0.0)

    for name, start, end in sorted(ops, key=lambda o: (o[1], -o[2])):
        start, end = max(start, lo), min(end, hi)
        if end <= start:
            continue
        while stack and stack[-1][1] <= start:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    while stack:
        close(stack.pop())
    return out


def _covering(intervals: List[Interval], t: float) -> Optional[Interval]:
    """The interval of a sorted, non-overlapping list that holds ``t``."""
    i = bisect.bisect_right(intervals, (t, float("inf"))) - 1
    if i >= 0 and intervals[i][0] <= t < intervals[i][1]:
        return intervals[i]
    return None


def _name_gap(t: float, ann: dict, programs: List[Interval]) -> str:
    if _covering(programs, t):
        return "device.between_ops"
    if _covering(ann[HANDLER], t):
        return "service.handler"
    client = _covering(ann[CLIENT], t)
    if client:
        inside = [h for h in ann[HANDLER] if client[0] <= h[0] < client[1]]
        if not inside:
            return "client.outside_handler"
        return "client.before_handler" if t < inside[0][0] else "client.after_handler"
    if _covering(ann[UNIT], t):
        return "generator.inside_unit"
    return "between_requests"


def reduce(trace: dict) -> Optional[dict]:
    """None when the trace holds no ``bench.unit`` or no device op."""
    # the benchmark's annotations of one kind never overlap: one call at a time
    ann = {k: sorted(v) for k, v in trace["annotations"].items()}
    units = ann[UNIT]
    chips = [c for c in trace["chips"] if c["ops"]]
    if not units or not chips:
        return None
    window = (units[0][0], units[-1][1])
    n = len(chips)
    busy = [_merge(_clip([o[1:] for o in c["ops"]], window)) for c in chips]
    busy_s = sum(_length(b) for b in busy) / n
    if busy_s <= 0:
        return None

    op_s: Dict[str, float] = {}
    for c in chips:
        for name, seconds in self_times(c["ops"], window).items():
            op_s[name] = op_s.get(name, 0.0) + seconds / n
    collective_s = sum(s for name, s in op_s.items() if COLLECTIVE.search(name))

    programs = _merge(_clip(
        [m[1:] for c in chips for m in c["modules"]], window))
    any_busy = _merge([iv for b in busy for iv in b])
    edges = sorted({t for spans in ann.values() for iv in spans for t in iv}
                   | {t for iv in programs for t in iv})
    gaps: Dict[str, float] = {}
    cursor = window[0]
    for start, end in any_busy + [(window[1], window[1])]:
        lo, hi = cursor, start
        cursor = max(cursor, end)
        if hi <= lo:
            continue
        cuts = [lo] + edges[bisect.bisect_right(edges, lo):bisect.bisect_left(edges, hi)] + [hi]
        for a, b in zip(cuts, cuts[1:]):
            name = _name_gap((a + b) / 2, ann, programs)
            gaps[name] = gaps.get(name, 0.0) + (b - a)

    def per_unit(intervals_by_chip) -> List[float]:
        return [
            sum(_length(_clip(iv, unit)) for iv in intervals_by_chip)
            / len(intervals_by_chip)
            for unit in units
        ]

    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {
        "chips": n,
        "window_s": window[1] - window[0],
        "busy_s": busy_s,
        "units": len(units),
        "unit_busy_s": per_unit(busy),
        "unit_program_s": per_unit(
            [_merge([m[1:] for m in c["modules"]]) for c in chips]),
        "collective_s": collective_s,
        "device_ops": top(op_s),
        "idle_gaps": top(gaps),
    }
