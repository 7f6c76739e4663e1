"""Seconds per unit under the program's spans named in ``match``, read from
the profiler capture (``harness/annotations.py``: every span of the program
is a ``kc:<name>`` annotation on the capture's clock) and not from the span
store, so spans on the client's thread and spans in other traces count like
any other.  Per traced unit (``bench.unit``), the seconds the ``match`` spans
cover: their union, on any thread.  Median over the capture's units, as
``kernel_device_s`` is.

spec: {"kind": "annotation_total", "match": ["client.rpc"],
       "less": ["service.solve_classes"]}     optional
       "self": true}                          optional

``less``: minus what the ``less`` spans cover of the matched seconds (the hop
is the client's call less the handler inside it).  ``self``: minus every other
``kc:`` span that lies INSIDE a matched one — inside, not overlapping:
``client.rpc`` on the client's thread starts before ``service.solve_classes``
and ends after it, and must not cancel it.  What is left is what the matched
spans' code does under no span of its own.

Reports nothing in a rehearsal (``facts["peaks"]`` is None there).  Two
reasons: the README allows no time from a rehearsal into any record; and the
rehearsal tests hold a rehearsal's metric names to an exact set, which no
later PR may edit — a metric that a rehearsal reported could never be added.
"""

from typing import List, Sequence

from benchmark.harness import annotations, stats, xplane

Interval = tuple


def _overlap(a: List[Interval], b: List[Interval]) -> float:
    """Seconds two merged interval lists have in common."""
    return xplane._length(a) + xplane._length(b) - xplane._length(xplane._merge(a + b))


def seconds(spans: Sequence[annotations.Span], window: Interval, match, less=(),
            self_time: bool = False):
    """The spec's seconds inside ``window``, or None where no matched span
    touches it."""
    matched = [s for s in spans if s.name in match]
    cover = xplane._merge(xplane._clip([(s.start_s, s.end_s) for s in matched], window))
    if not cover:
        return None
    minus = [(s.start_s, s.end_s) for s in spans if s.name in less]
    if self_time:
        minus += [
            (s.start_s, s.end_s) for s in spans if s.name not in match and any(
                m.start_s <= s.start_s and s.end_s <= m.end_s for m in matched)
        ]
    return xplane._length(cover) - _overlap(cover, xplane._merge(xplane._clip(minus, window)))


def per_unit(spec: dict, capture: dict) -> List[float]:
    values = [
        seconds(capture["spans"], unit, set(spec["match"]), set(spec.get("less", ())),
                bool(spec.get("self")))
        for unit in capture["units"]
    ]
    return [v for v in values if v is not None]


def read(spec: dict, facts: dict):
    if facts.get("peaks") is None:  # a rehearsal: see the docstring
        return None
    capture = annotations.capture()
    values = per_unit(spec, capture) if capture else []
    return stats.median(values) if values else None
