"""Arithmetic on the program's spans (``tracing`` span dicts: name, spanId,
parentId, startWall, durationS).  A layer's self time is its span's duration
minus the part of that interval its child spans cover; children may run on
other threads and overlap, so coverage is the UNION of their intervals, never
their sum.
"""

from typing import Iterable, List, Tuple

Interval = Tuple[float, float]


def union_length(intervals: Iterable[Interval]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, reach = 0.0, None
    for start, end in sorted(intervals):
        if reach is None or start > reach:
            total += max(end - start, 0.0)
            reach = end
        elif end > reach:
            total += end - reach
            reach = end
    return total


def _interval(span: dict) -> Interval:
    return span["startWall"], span["startWall"] + (span["durationS"] or 0.0)


def _clipped(span: dict, clip: Interval) -> Interval:
    start, end = _interval(span)
    return max(start, clip[0]), min(end, clip[1])


def covered(spans: List[dict], names) -> float:
    """Seconds covered by the spans named in ``names``; nested or
    overlapping spans count once."""
    return union_length(_interval(s) for s in spans if s["name"] in names)


def self_time(spans: List[dict], names) -> float:
    """Summed self time of the spans named in ``names``: each one's duration
    less what its direct children cover of it."""
    total = 0.0
    for span in spans:
        if span["name"] not in names:
            continue
        mine = _interval(span)
        children = (
            _clipped(s, mine) for s in spans if s["parentId"] == span["spanId"]
        )
        total += max((mine[1] - mine[0]) - union_length(children), 0.0)
    return total
