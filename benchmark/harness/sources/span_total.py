"""Seconds per unit under the program's spans named in ``match``, as the
median over the window's units.  Overlapping or nested spans count once.

spec: {"kind": "span_total", "match": ["decode", "materialize", ...]}
"""

from benchmark.harness import spans, stats


def read(spec: dict, facts: dict):
    names = set(spec["match"])
    per_unit = [
        sum(spans.covered(list(c.spans), names) for c in u.calls)
        for u in facts["units"]
        if any(s["name"] in names for c in u.calls for s in c.spans)
    ]
    return stats.median(per_unit) if per_unit else None
