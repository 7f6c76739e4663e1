"""Trace exporter: Chrome trace-event format.

``to_chrome`` emits the trace-event JSON that ``chrome://tracing`` and
Perfetto's legacy loader read: complete events
(``ph: "X"``, microsecond ``ts``/``dur``) per span, instant events
(``ph: "i"``) per span event, one ``tid`` lane per trace so concurrent
solves render side by side.
"""

from __future__ import annotations

from typing import Any, Dict, Iterable, List

from karpenter_core_tpu.tracing.trace import Trace


def to_chrome(traces: Iterable[Trace]) -> Dict[str, Any]:
    """Chrome trace-event JSON object for a set of traces (load the dumped
    file in chrome://tracing or ui.perfetto.dev)."""
    events: List[Dict[str, Any]] = []
    for tid, trace in enumerate(traces, start=1):
        events.append(
            {
                "name": "thread_name",
                "ph": "M",
                "pid": 1,
                "tid": tid,
                "args": {"name": f"{trace.name} {trace.trace_id}"},
            }
        )
        for rec in trace.spans:
            ts_us = rec["startWall"] * 1e6
            events.append(
                {
                    "name": rec["name"],
                    "cat": "solve",
                    "ph": "X",
                    "ts": ts_us,
                    "dur": (rec.get("durationS") or 0.0) * 1e6,
                    "pid": 1,
                    "tid": tid,
                    "args": {
                        "traceId": rec["traceId"],
                        "spanId": rec["spanId"],
                        "parentId": rec.get("parentId"),
                        **(rec.get("attrs") or {}),
                    },
                }
            )
            for event in rec.get("events") or ():
                events.append(
                    {
                        "name": event["name"],
                        "cat": "event",
                        "ph": "i",
                        "s": "t",
                        "ts": event["wall"] * 1e6,
                        "pid": 1,
                        "tid": tid,
                        "args": event.get("attrs") or {},
                    }
                )
    return {"traceEvents": events, "displayTimeUnit": "ms"}
