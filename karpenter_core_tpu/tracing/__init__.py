"""Tracing: spans over the solve pipeline, a ring-buffer trace store,
decision audits, and the Chrome trace-event exporter.

See docs/OBSERVABILITY.md for the operator surface (``/debug/traces``).
"""

from karpenter_core_tpu.tracing.trace import (
    MAX_EVENTS_PER_SPAN,
    Span,
    Trace,
    TraceStore,
    TRACE_STORE,
    add_event,
    current,
    disable,
    enable,
    enabled,
    set_attrs,
    span,
    span_remote,
    traced,
    wire_context,
)
from karpenter_core_tpu.tracing.export import to_chrome
from karpenter_core_tpu.tracing.audit import (
    classify_rejection,
    record_unschedulable,
    rejection,
)

__all__ = [
    "MAX_EVENTS_PER_SPAN",
    "Span",
    "Trace",
    "TraceStore",
    "TRACE_STORE",
    "add_event",
    "classify_rejection",
    "current",
    "disable",
    "enable",
    "enabled",
    "record_unschedulable",
    "rejection",
    "set_attrs",
    "span",
    "span_remote",
    "to_chrome",
    "traced",
    "wire_context",
]
