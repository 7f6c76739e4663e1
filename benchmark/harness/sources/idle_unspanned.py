"""The share of the chips' idle time that no span of the program covers, in %.

Over the capture's window (first ``bench.unit`` to the end of the last), the
seconds in which no chip runs an op and no program is running — the idle
INSIDE a running program is the program's own latency, ``device.between_ops``
in ``breakdown.idle_gaps`` — each piece charged to the innermost ``kc:`` span
that covers it (``harness/annotations.py``; innermost = latest start, the shorter of two
that start together, on any thread: during a handler the server's span wins over the client's
``client.rpc`` around it), else to ``unspanned``: the benchmark's own generator
and checks, thread hand-offs, whatever the program does under no span.

Prints the whole table as one earlier line of the run's output,

    {"idle_by_span": [[name, seconds], ...]}      the 15 largest, largest first

and returns 100 x unspanned / those idle seconds.  ``breakdown.idle_gaps``
(``harness/xplane.py``) stays what it is and names the same seconds by the
benchmark's own three annotations.

spec: {"kind": "idle_unspanned"}

Reports nothing in a rehearsal (``facts["peaks"]`` is None there).  Two
reasons: the README allows no idle share from a rehearsal into any record; and
the rehearsal tests hold a rehearsal's metric names to an exact set, which no
later PR may edit — a metric that a rehearsal reported could never be added.
"""

import bisect
import json
from typing import Dict, Sequence

from benchmark.harness import annotations, xplane

UNSPANNED = "unspanned"
TOP = 15


def idle_by_span(spans: Sequence[annotations.Span], window, busy, programs) -> Dict[str, float]:
    """Seconds of ``window`` outside ``busy`` and ``programs``, by the
    innermost span covering each piece."""
    occupied = xplane._merge(xplane._clip(list(busy) + list(programs), window))
    edges = sorted({t for s in spans for t in (s.start_s, s.end_s)})
    out: Dict[str, float] = {}
    cursor = window[0]
    for start, end in occupied + [(window[1], window[1])]:
        lo, hi = cursor, start
        cursor = max(cursor, end)
        if hi <= lo:
            continue
        cuts = [lo] + edges[bisect.bisect_right(edges, lo):bisect.bisect_left(edges, hi)] + [hi]
        for a, b in zip(cuts, cuts[1:]):
            mid = (a + b) / 2
            covering = [s for s in spans if s.start_s <= mid < s.end_s]
            name = (max(covering, key=lambda s: (s.start_s, -s.end_s)).name
                    if covering else UNSPANNED)
            out[name] = out.get(name, 0.0) + (b - a)
    return out


def table(capture: dict) -> Dict[str, float]:
    units = capture["units"]
    if not units or not capture["busy"]:
        return {}
    return idle_by_span(capture["spans"], (units[0][0], units[-1][1]),
                        capture["busy"], capture["programs"])


def read(spec: dict, facts: dict):
    if facts.get("peaks") is None:  # a rehearsal: see the docstring
        return None
    capture = annotations.capture()
    by_span = table(capture) if capture else {}
    idle_s = sum(by_span.values())
    # a program without kc: annotations has no span to charge: nothing to report
    if not idle_s or not capture["spans"]:
        return None
    top = sorted(by_span.items(), key=lambda kv: -kv[1])[:TOP]
    print(json.dumps({"idle_by_span": [[k, v] for k, v in top]}), flush=True)
    return 100.0 * by_span.get(UNSPANNED, 0.0) / idle_s
