"""Per-unit sums over the benchmark's own record of each client call
(``sut.Call``), as the median over the window's units.

spec: {"kind": "calls", "field": "outside_s" | "reply_mb"}
``outside_s`` is the client call less the handler inside it: classify and
pack before, unpack and expand after, and the loopback hop.
"""

from benchmark.harness import stats

FIELDS = {
    "outside_s": lambda c: c.client_s - c.handler_s,
    "reply_mb": lambda c: c.reply_bytes / 1e6,
}


def read(spec: dict, facts: dict):
    field = FIELDS[spec["field"]]
    per_unit = [sum(field(c) for c in u.calls) for u in facts["units"] if u.calls]
    return stats.median(per_unit) if per_unit else None
