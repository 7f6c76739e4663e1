"""Span-based solve tracing: contexts, ids, and the in-memory trace store.

The role the reference delegates to controller-runtime's logging/tracing
context (knative logging + the scheduling loop's structured messages) is
re-centered here as explicit spans, because the hot path this repo cares
about is a *pipeline* (ingest → encode → dispatch → solve → decode →
materialize) whose cost attribution is invisible in wall-clock logging.

Design constraints:

  - Near-zero cost when disabled (one module-global check per ``span()``).
    Tracing is opt-in: ``enable()``, or the ``KC_TRACE=1`` environment
    variable at import time.
  - Thread-aware: the current span propagates through a ``contextvars``
    context, so concurrent reconciles interleave without clobbering each
    other.  A span opened on a worker thread with no inherited context
    becomes the root of its own trace.
  - JAX-aware: device work is asynchronously dispatched, so a naive span
    around a kernel call measures dispatch, not compute — and the cost
    folds into whichever later span first touches the result.  A span
    given a ``sync`` target calls ``jax.block_until_ready`` on it at close
    so device time lands in the span that dispatched it.
  - Bounded memory: completed traces land in a thread-safe ring buffer
    (``TraceStore``); old traces fall off the end.

Spans also feed ``metrics.registry.SOLVE_STAGE_DURATION`` (one histogram
time series per span name) with a ``trace_id`` exemplar, so a scrape can
link a latency outlier back to the exact trace that produced it.

Spans also reach the profiler: while tracing is on, every span holds a
``jax.profiler.TraceAnnotation`` named ``kc:<span name>`` for its life, on the
thread that runs it.  Outside a ``jax.profiler`` capture that is a branch in
C++; inside one the span lands on ``/host:CPU`` on the clock of the device's
op events, so an idle gap of the chip can be named by the host span that
covers it.  The prefix keeps a span apart from an annotation of the same name
that a caller wraps around it.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

_enabled = os.environ.get("KC_TRACE", "") == "1"
# completion-order appends can arrive from several threads of one trace
_finish_lock = threading.Lock()
_current: contextvars.ContextVar[Optional["Span"]] = contextvars.ContextVar(
    "kc_tracing_current", default=None
)

# what a span is named on the profiler's host plane: ``kc:<span name>``
ANNOTATION_PREFIX = "kc:"
# jax.profiler.TraceAnnotation, resolved by the first enabled span (this
# module imports without JAX); a process without JAX annotates nothing
_annotation = None

# span-event payloads are debug artifacts, not a database: cap the per-span
# event count so a pathological solve (50k failed pods) cannot balloon a trace
MAX_EVENTS_PER_SPAN = 256


def enabled() -> bool:
    return _enabled


def enable(capacity: Optional[int] = None) -> None:
    global _enabled
    if capacity is not None:
        TRACE_STORE.set_capacity(capacity)
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def _new_id(nbytes: int) -> str:
    return uuid.uuid4().hex[: nbytes * 2]


class Span:
    """One timed operation.  Created by ``span()``; closed spans serialize to
    plain dicts (the exchange format of the exporters and ``/debug/traces``)."""

    __slots__ = (
        "trace_id", "span_id", "parent_id", "name", "attrs", "events",
        "start_wall", "_t0", "duration_s", "_root", "_finished", "_sync",
    )

    def __init__(self, name: str, parent: Optional["Span"], attrs: Dict[str, Any]) -> None:
        self.name = name
        self.attrs = dict(attrs)
        # the tenant attribute is baggage: a span opened under a tenant-owned
        # span belongs to that tenant, so per-tenant trace filtering sees the
        # WHOLE server-side subtree (encode/dispatch/decode), not just the
        # envelope span — only paid when tracing is on
        if parent is not None and "tenant" not in self.attrs:
            tenant = parent.attrs.get("tenant")
            if tenant is not None:
                self.attrs["tenant"] = tenant
        self.events: List[Dict[str, Any]] = []
        self.parent_id = parent.span_id if parent is not None else None
        self.trace_id = parent.trace_id if parent is not None else _new_id(8)
        self.span_id = _new_id(4)
        self._root = parent._root if parent is not None else self
        self._finished: List[Dict[str, Any]] = [] if parent is None else None
        self._sync = None
        self.duration_s = None
        self.start_wall = time.time()
        self._t0 = time.perf_counter()

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def event(self, name: str, **attrs: Any) -> None:
        if len(self.events) >= MAX_EVENTS_PER_SPAN:
            return
        self.events.append({"name": name, "wall": time.time(), "attrs": attrs})

    def sync_on(self, value: Any) -> Any:
        """Register a (possibly still-dispatching) jax pytree to block on at
        span close, so async device work is attributed to THIS span."""
        self._sync = value
        return value

    def to_dict(self) -> Dict[str, Any]:
        return {
            "traceId": self.trace_id,
            "spanId": self.span_id,
            "parentId": self.parent_id,
            "name": self.name,
            "startWall": self.start_wall,
            "durationS": self.duration_s,
            "attrs": self.attrs,
            "events": self.events,
        }

    def _finish(self) -> None:
        if self._sync is not None:
            try:
                import jax

                jax.block_until_ready(self._sync)
            except Exception:  # noqa: BLE001 - tracing must never break the solve
                pass
            self._sync = None
        self.duration_s = time.perf_counter() - self._t0
        record = self.to_dict()
        root = self._root
        with _finish_lock:
            if root._finished is not None:
                root._finished.append(record)
        try:
            from karpenter_core_tpu.metrics.registry import SOLVE_STAGE_DURATION

            SOLVE_STAGE_DURATION.labels(self.name).observe(
                self.duration_s,
                exemplar={"trace_id": self.trace_id, "span_id": self.span_id},
            )
        except Exception:  # noqa: BLE001 - metrics failures must not surface
            pass
        if root is self:
            spans, self._finished = self._finished, None
            TRACE_STORE.add(
                Trace(
                    trace_id=self.trace_id,
                    name=self.name,
                    start_wall=self.start_wall,
                    duration_s=self.duration_s,
                    spans=spans,
                )
            )


def _resolve_annotation():
    global _annotation
    try:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    except Exception:  # noqa: BLE001 - tracing must work without JAX
        _annotation = contextlib.nullcontext
    return _annotation


@contextlib.contextmanager
def _running(sp: Span, sync: Any) -> Iterator[Span]:
    """``sp`` as the current span, under its profiler annotation, until the
    body ends; the annotation covers the close's ``sync`` wait as the span's
    duration does."""
    if sync is not None:
        sp.sync_on(sync)
    with (_annotation or _resolve_annotation())(ANNOTATION_PREFIX + sp.name):
        token = _current.set(sp)
        try:
            yield sp
        except BaseException as e:
            sp.attrs.setdefault("error", f"{type(e).__name__}: {e}"[:200])
            raise
        finally:
            _current.reset(token)
            sp._finish()


class _NoopSpan:
    """The disabled-path span: every method is a cheap no-op."""

    __slots__ = ()
    trace_id = ""
    span_id = ""
    parent_id = None
    name = ""
    duration_s = None

    def set(self, **attrs: Any) -> "_NoopSpan":
        return self

    def event(self, name: str, **attrs: Any) -> None:
        pass

    def sync_on(self, value: Any) -> Any:
        return value


_NOOP = _NoopSpan()


@contextlib.contextmanager
def span(name: str, sync: Any = None, **attrs: Any) -> Iterator[object]:
    """Open a span under the current one (or start a new trace).  ``sync``
    (or a later ``sp.sync_on(x)``) blocks on a jax pytree at close so device
    time is attributed here.  When tracing is disabled this is one branch."""
    if not _enabled:
        yield _NOOP
        return
    with _running(Span(name, _current.get(), attrs), sync) as sp:
        yield sp


def current() -> Optional[Span]:
    """The active span, or None (also None when tracing is disabled)."""
    return _current.get()


def wire_context() -> Optional[Dict[str, str]]:
    """The active span's identity as a wire-portable context dict
    (``{"traceId", "spanId"}``) for stamping into RPC envelopes and journal
    records, or None when tracing is off / no span is active.  The W3C
    traceparent idea without the header spelling: trace id + parent span id
    are all a remote side needs to join the tree."""
    if not _enabled:
        return None
    sp = _current.get()
    if sp is None or not sp.trace_id:
        return None
    return {"traceId": sp.trace_id, "spanId": sp.span_id}


@contextlib.contextmanager
def span_remote(
    name: str, ctx: Optional[Dict[str, Any]], sync: Any = None, **attrs: Any
) -> Iterator[object]:
    """Open a span that ADOPTS a remote trace context: same disabled-path
    contract as ``span()`` (one flag check), but when ``ctx`` carries a
    ``traceId`` the new span joins that trace — it records the remote span as
    its parent while remaining a store-root on THIS side, so its completed
    segment lands in the local ``TRACE_STORE`` under the adopted trace id
    (``TraceStore.tree`` merges the segments back into one tree).  A missing
    or empty ``ctx`` degrades to a plain ``span()``."""
    if not _enabled:
        yield _NOOP
        return
    trace_id = str((ctx or {}).get("traceId") or "")
    if not trace_id:
        with span(name, sync=sync, **attrs) as sp:
            yield sp
        return
    sp = Span(name, None, attrs)
    sp.trace_id = trace_id
    sp.parent_id = str(ctx.get("spanId") or "") or None
    with _running(sp, sync):
        yield sp


def add_event(name: str, **attrs: Any) -> None:
    """Attach a structured event to the active span (no-op without one)."""
    sp = _current.get()
    if sp is not None:
        sp.event(name, **attrs)


def set_attrs(**attrs: Any) -> None:
    """Set attributes on the active span (no-op without one): how a function
    under ``@traced`` records the counts of the work it did."""
    sp = _current.get()
    if sp is not None:
        sp.set(**attrs)


def traced(name: str, **attrs: Any):
    """Decorator form of ``span()`` for controller entry points; the static
    gate (tools/check_instrumented.py) accepts either spelling."""

    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args: Any, **kwargs: Any):
            with span(name, **attrs):
                return fn(*args, **kwargs)

        return wrapper

    return decorate


@dataclass
class Trace:
    """One completed trace: the root span's identity plus every span that
    closed under it, in completion order (sort by ``startWall`` to replay)."""

    trace_id: str
    name: str
    start_wall: float
    duration_s: float
    spans: List[Dict[str, Any]] = field(default_factory=list)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "traceId": self.trace_id,
            "name": self.name,
            "startWall": self.start_wall,
            "durationS": self.duration_s,
            "spans": self.spans,
        }

    def stage_durations(self) -> Dict[str, float]:
        """span name -> summed duration (seconds) across the trace."""
        out: Dict[str, float] = {}
        for rec in self.spans:
            if rec.get("durationS") is not None:
                out[rec["name"]] = out.get(rec["name"], 0.0) + rec["durationS"]
        return out

    def audits(self) -> List[Dict[str, Any]]:
        """Every decision-audit event in the trace (tracing.audit)."""
        out = []
        for rec in self.spans:
            for event in rec.get("events") or ():
                if event.get("name") == "decision.audit":
                    out.append(event.get("attrs") or {})
        return out


class TraceStore:
    """Thread-safe ring buffer of the last N completed traces."""

    def __init__(self, capacity: int = 64) -> None:
        self._lock = threading.Lock()
        self._traces: deque = deque(maxlen=max(capacity, 1))

    def add(self, trace: Trace) -> None:
        with self._lock:
            self._traces.append(trace)

    def last(self, n: Optional[int] = None) -> List[Trace]:
        """The most recent ``n`` traces (all when None), oldest first."""
        with self._lock:
            traces = list(self._traces)
        return traces if n is None or n <= 0 else traces[-n:]

    def find(self, trace_id: str) -> Optional[Trace]:
        with self._lock:
            for trace in reversed(self._traces):
                if trace.trace_id == trace_id:
                    return trace
        return None

    def tree(self, trace_id: str) -> Optional[Trace]:
        """All stored segments of one trace merged into a single tree.

        Cross-boundary propagation (``span_remote``) lands each side's
        segment as its own ``Trace`` entry sharing the trace id — the client
        RPC span, the server session tick, a warm-restart replay.  This
        merges them: spans combined in wall-clock order, the earliest
        segment's root named, duration spanning first start to last end."""
        with self._lock:
            matches = [t for t in self._traces if t.trace_id == trace_id]
        if not matches:
            return None
        if len(matches) == 1:
            return matches[0]
        spans: List[Dict[str, Any]] = []
        for t in matches:
            spans.extend(t.spans)
        spans.sort(key=lambda rec: rec.get("startWall") or 0.0)
        first = min(matches, key=lambda t: t.start_wall)
        end = max(t.start_wall + (t.duration_s or 0.0) for t in matches)
        return Trace(
            trace_id=trace_id,
            name=first.name,
            start_wall=first.start_wall,
            duration_s=end - first.start_wall,
            spans=spans,
        )

    def set_capacity(self, capacity: int) -> None:
        with self._lock:
            self._traces = deque(self._traces, maxlen=max(capacity, 1))

    @property
    def capacity(self) -> int:
        return self._traces.maxlen

    def clear(self) -> None:
        with self._lock:
            self._traces.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)


def _capacity_from_env() -> int:
    try:
        return int(os.environ.get("KC_TRACE_CAPACITY", "64") or 64)
    except ValueError:
        return 64  # a tuning-knob typo must not take the operator down


TRACE_STORE = TraceStore(_capacity_from_env())
