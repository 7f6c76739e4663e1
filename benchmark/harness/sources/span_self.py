"""Self time per unit of the spans named in ``of``: their duration less what
their child spans cover.  For the benchmark's own root span around the
handler that is what the program does not span at all — request decode, class
materialisation, response build and msgpack.  Median over the window's units.

spec: {"kind": "span_self", "of": ["bench.handler"]}
"""

from benchmark.harness import spans, stats


def read(spec: dict, facts: dict):
    names = set(spec["of"])
    per_unit = [
        sum(spans.self_time(list(c.spans), names) for c in u.calls)
        for u in facts["units"]
        if any(s["name"] in names for c in u.calls for s in c.spans)
    ]
    return stats.median(per_unit) if per_unit else None
