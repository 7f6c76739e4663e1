"""Prometheus-style metrics registry.

Mirror of the role of /root/reference/pkg/metrics/constants.go:41-66 and the
controller-runtime registry: counters/gauges/histograms/summaries with label
sets, a shared default registry, DurationBuckets, and the ``measure`` closure
timer used around scheduling and deprovisioning evaluations.  Exposition is
text-format compatible (``Registry.render``) for scraping.
"""

from __future__ import annotations

import bisect
import os
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

NAMESPACE = "karpenter"

# metrics/constants.go:46-55 DurationBuckets
DURATION_BUCKETS = [
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1, 2.5, 5, 10, 30, 60, 120, 180, 300,
]
# SummaryObjectives p0/p50/p90/p99 (constants.go:57-59)
SUMMARY_OBJECTIVES = [0.0, 0.5, 0.9, 0.99]


class _Metric:
    kind = ""

    def __init__(self, name: str, help_: str, label_names: Iterable[str] = ()) -> None:
        self.name = name
        self.help = help_
        self.label_names = tuple(label_names)
        self._lock = threading.Lock()
        self._children: Dict[Tuple[str, ...], object] = {}

    def labels(self, *values: str, **kwargs: str):
        if kwargs:
            values = tuple(kwargs.get(name, "") for name in self.label_names)
        if len(values) != len(self.label_names):
            raise ValueError(f"{self.name}: expected labels {self.label_names}")
        key = tuple(str(v) for v in values)
        with self._lock:
            child = self._children.get(key)
            if child is None:
                child = self._new_child()
                self._children[key] = child
            return child

    def _new_child(self):
        raise NotImplementedError

    def clear(self) -> None:
        with self._lock:
            self._children.clear()

    def samples(self) -> List[Tuple[str, Dict[str, str], float]]:
        raise NotImplementedError

    def samples_with_exemplars(self):
        """samples() widened with a per-sample exemplar slot (None for metric
        kinds without exemplar support)."""
        return [(name, labels, value, None) for name, labels, value in self.samples()]

    def _label_dicts(self):
        with self._lock:
            return [
                (dict(zip(self.label_names, key)), child)
                for key, child in self._children.items()
            ]


class _CounterChild:
    def __init__(self) -> None:
        self.value = 0.0

    def inc(self, amount: float = 1.0) -> None:
        self.value += amount

    add = inc


class Counter(_Metric):
    kind = "counter"

    def _new_child(self):
        return _CounterChild()

    def inc(self, amount: float = 1.0) -> None:
        self.labels().inc(amount)

    def samples(self):
        return [(self.name, labels, c.value) for labels, c in self._label_dicts()]


class _GaugeChild:
    def __init__(self) -> None:
        self.value = 0.0

    def set(self, value: float) -> None:
        self.value = value

    def inc(self, amount: float = 1.0) -> None:
        self.value += amount

    def dec(self, amount: float = 1.0) -> None:
        self.value -= amount


class Gauge(_Metric):
    kind = "gauge"

    def _new_child(self):
        return _GaugeChild()

    def set(self, value: float) -> None:
        self.labels().set(value)

    def samples(self):
        return [(self.name, labels, g.value) for labels, g in self._label_dicts()]

    def delete_labels(self, *values: str) -> None:
        key = tuple(str(v) for v in values)
        with self._lock:
            self._children.pop(key, None)


class _HistogramChild:
    def __init__(self, buckets: List[float]) -> None:
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        self.total = 0.0
        self.count = 0
        # bucket idx -> (exemplar labels, observed value, wall time): the last
        # observation per bucket that carried an exemplar (e.g. a trace id)
        self.exemplars: Dict[int, Tuple[Dict[str, str], float, float]] = {}
        # exposition emits cumulative buckets that must satisfy +Inf == _count;
        # an unlocked mid-observe scrape would transiently violate it
        self._lock = threading.Lock()

    def observe(self, value: float, exemplar: Optional[Dict[str, str]] = None) -> None:
        idx = bisect.bisect_left(self.buckets, value)
        with self._lock:
            self.counts[idx] += 1
            self.total += value
            self.count += 1
            if exemplar:
                self.exemplars[idx] = (dict(exemplar), float(value), time.time())

    def snapshot(self):
        """(counts, total, count, exemplars) read atomically for exposition."""
        with self._lock:
            return list(self.counts), self.total, self.count, dict(self.exemplars)


def _format_bound(bound: float) -> str:
    return format(bound, "g")


def _escape_label_value(value: str) -> str:
    """Classic Prometheus text-format label-value escaping: backslash, the
    double quote, and line feed are the three characters the grammar reserves
    (https://prometheus.io/docs/instrumenting/exposition_formats/)."""
    return (
        str(value)
        .replace("\\", "\\\\")
        .replace('"', '\\"')
        .replace("\n", "\\n")
    )


class Histogram(_Metric):
    kind = "histogram"

    def __init__(self, name, help_, label_names=(), buckets: Optional[List[float]] = None):
        super().__init__(name, help_, label_names)
        self.buckets = list(buckets or DURATION_BUCKETS)

    def _new_child(self):
        return _HistogramChild(self.buckets)

    def observe(self, value: float, exemplar: Optional[Dict[str, str]] = None) -> None:
        self.labels().observe(value, exemplar=exemplar)

    def samples(self):
        return [(name, labels, value) for name, labels, value, _ in self.samples_with_exemplars()]

    def samples_with_exemplars(self):
        """Prometheus histogram exposition: cumulative ``_bucket{le=...}``
        lines (including ``le="+Inf"``) plus ``_count``/``_sum``.  The fourth
        element carries the bucket's exemplar (or None)."""
        out = []
        for labels, h in self._label_dicts():
            counts, total, count, exemplars = h.snapshot()
            cumulative = 0
            for i, bound in enumerate(h.buckets):
                cumulative += counts[i]
                out.append((
                    self.name + "_bucket",
                    {**labels, "le": _format_bound(bound)},
                    float(cumulative),
                    exemplars.get(i),
                ))
            cumulative += counts[-1]
            out.append((
                self.name + "_bucket",
                {**labels, "le": "+Inf"},
                float(cumulative),
                exemplars.get(len(h.buckets)),
            ))
            out.append((self.name + "_count", labels, float(count), None))
            out.append((self.name + "_sum", labels, total, None))
        return out


class _SummaryChild:
    def __init__(self) -> None:
        self.values: List[float] = []

    def observe(self, value: float) -> None:
        bisect.insort(self.values, value)

    def quantile(self, q: float) -> float:
        if not self.values:
            return float("nan")
        idx = min(int(q * len(self.values)), len(self.values) - 1)
        return self.values[idx]


class Summary(_Metric):
    kind = "summary"

    def _new_child(self):
        return _SummaryChild()

    def observe(self, value: float) -> None:
        self.labels().observe(value)

    def samples(self):
        out = []
        for labels, s in self._label_dicts():
            out.append((self.name + "_count", labels, float(len(s.values))))
            out.append((self.name + "_sum", labels, float(sum(s.values))))
            for q in SUMMARY_OBJECTIVES:
                out.append(
                    (self.name, {**labels, "quantile": str(q)}, s.quantile(q))
                )
        return out


class Registry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._metrics: Dict[str, _Metric] = {}

    def register(self, metric: _Metric) -> _Metric:
        with self._lock:
            existing = self._metrics.get(metric.name)
            if existing is not None:
                return existing
            self._metrics[metric.name] = metric
            return metric

    def counter(self, name, help_="", label_names=()) -> Counter:
        return self.register(Counter(name, help_, label_names))  # type: ignore[return-value]

    def gauge(self, name, help_="", label_names=()) -> Gauge:
        return self.register(Gauge(name, help_, label_names))  # type: ignore[return-value]

    def histogram(self, name, help_="", label_names=(), buckets=None) -> Histogram:
        return self.register(Histogram(name, help_, label_names, buckets))  # type: ignore[return-value]

    def summary(self, name, help_="", label_names=()) -> Summary:
        return self.register(Summary(name, help_, label_names))  # type: ignore[return-value]

    def get(self, name: str) -> Optional[_Metric]:
        with self._lock:
            return self._metrics.get(name)

    def reset(self) -> None:
        with self._lock:
            for metric in self._metrics.values():
                metric.clear()

    def label_set_count(self) -> int:
        """Total live label sets (time series) across every registered
        metric — the number the cardinality guard keeps bounded."""
        with self._lock:
            metrics = list(self._metrics.values())
        total = 0
        for metric in metrics:
            with metric._lock:
                total += len(metric._children)
        return total

    def render(self, exemplars: bool = False) -> str:
        """Prometheus text exposition.  With ``exemplars=True`` bucket lines
        carry their exemplar in OpenMetrics syntax
        (``... # {trace_id="..."} value timestamp``)."""
        lines = []
        with self._lock:
            metrics = list(self._metrics.values())
        for metric in metrics:
            lines.append(f"# HELP {metric.name} {metric.help}")
            lines.append(f"# TYPE {metric.name} {metric.kind}")
            for name, labels, value, exemplar in metric.samples_with_exemplars():
                if labels:
                    rendered = ",".join(
                        f'{k}="{_escape_label_value(v)}"'
                        for k, v in sorted(labels.items())
                    )
                    line = f"{name}{{{rendered}}} {value}"
                else:
                    line = f"{name} {value}"
                if exemplars and exemplar is not None:
                    ex_labels, ex_value, ex_wall = exemplar
                    ex_rendered = ",".join(
                        f'{k}="{_escape_label_value(v)}"'
                        for k, v in sorted(ex_labels.items())
                    )
                    line += f" # {{{ex_rendered}}} {ex_value} {ex_wall:.3f}"
                lines.append(line)
        return "\n".join(lines) + "\n"


REGISTRY = Registry()

# Per-stage solve-pipeline histogram: one series per tracing span name
# (ingest/encode/dispatch/solve/decode/materialize plus the controller
# reconcile spans), observed at span close by tracing/trace.py with a
# trace_id exemplar — a latency outlier on a scrape links straight back to
# the trace that produced it (render(exemplars=True)).
SOLVE_STAGE_DURATION = Histogram(
    NAMESPACE + "_solve_stage_duration_seconds",
    "Duration of solve-pipeline stages, labeled by tracing span name.",
    ("stage",),
)
REGISTRY.register(SOLVE_STAGE_DURATION)

# Soak-subsystem SLO surface (soak/slo.py samples these every simulated
# tick): the live value of each time-series probe and a counter of SLO-rule
# violations, so a long-running soak is watchable on /metrics while the
# structured verdict report is still being accumulated (docs/SOAK.md).
SOAK_SLO_PROBE = Gauge(
    NAMESPACE + "_soak_slo_probe",
    "Latest sampled value of a soak SLO probe, by probe and scenario.",
    ("probe", "scenario"),
)
REGISTRY.register(SOAK_SLO_PROBE)
SOAK_SLO_VIOLATIONS = Counter(
    NAMESPACE + "_soak_slo_violations_total",
    "Soak SLO rules that failed evaluation, by probe and scenario.",
    ("probe", "scenario"),
)
REGISTRY.register(SOAK_SLO_VIOLATIONS)

# Slot-exhaustion retries (solver/tpu.py ``grow_until_fits``): the node-slot
# estimate (ops/solve.estimate_slots) is optimistic, and each count here is
# one cold solve run again at twice the slots.  0 is the steady state; a
# rising count says the estimate under-counts this cluster's batches.
SOLVER_SLOT_RETRIES = Counter(
    NAMESPACE + "_solver_slot_retries_total",
    "Cold solves run again at twice the node slots because the scan took "
    "every slot it was given and still failed pods.",
)
REGISTRY.register(SOLVER_SLOT_RETRIES)

# The sidecar's collector policy (service/collector.py): every pass of the
# cyclic collector the process ran while a sidecar was up, by generation, as
# its ``gc.callbacks`` hook saw them.  Generation 2 is paced by the server —
# one or two a minute is the steady state; a rate of several a second says the
# pacing is not installed (or an embedder calls ``gc.collect()`` in a loop).
SOLVER_GC_COLLECTIONS = Counter(
    NAMESPACE + "_solver_gc_collections_total",
    "Passes of the cyclic garbage collector in the solver sidecar's process, "
    "by generation.",
    ("generation",),
)
REGISTRY.register(SOLVER_GC_COLLECTIONS)
SOLVER_GC_SECONDS = Counter(
    NAMESPACE + "_solver_gc_seconds_total",
    "Seconds the solver sidecar's process spent inside passes of the cyclic "
    "garbage collector, by generation.",
    ("generation",),
)
REGISTRY.register(SOLVER_GC_SECONDS)

# Policy-objective surface (docs/POLICY.md): the latest solve's selected
# fleet cost, raw offering prices ({view="price"}) and risk-weighted
# expectation ({view="expected"}), set by TPUSolver decode when the
# objective stage runs.
POLICY_FLEET_COST = Gauge(
    NAMESPACE + "_policy_fleet_cost",
    "Fleet cost of the latest policy-objective selection, by view "
    "(price = raw offering prices, expected = risk-weighted).",
    ("view",),
)
REGISTRY.register(POLICY_FLEET_COST)


class LabelCardinalityGuard:
    """Bounds the distinct values a high-cardinality label may take.

    The tenant id is the first unbounded-by-construction label value this
    registry carries (every other label is a small closed vocabulary).  The
    guard admits the first ``cap`` distinct values verbatim; every later
    value maps to the overflow bucket (``"_other"``), so a 10k-tenant churn
    holds /metrics to a bounded series count while the busiest (earliest)
    tenants keep per-tenant resolution.  Admission is for the process
    lifetime — releasing on session eviction would let churn re-admit
    forever, which is exactly the cardinality leak being prevented.
    """

    OVERFLOW = "_other"

    def __init__(self, cap: int) -> None:
        self._cap = max(int(cap), 1)
        self._lock = threading.Lock()
        self._seen: set = set()
        self.overflowed = 0

    def admit(self, value: str) -> str:
        value = str(value)
        with self._lock:
            if value in self._seen:
                return value
            if len(self._seen) < self._cap:
                self._seen.add(value)
                return value
            self.overflowed += 1
            return self.OVERFLOW

    @property
    def cap(self) -> int:
        return self._cap

    def seen(self) -> int:
        with self._lock:
            return len(self._seen)

    def reset(self, cap: Optional[int] = None) -> None:
        with self._lock:
            self._seen.clear()
            self.overflowed = 0
            if cap is not None:
                self._cap = max(int(cap), 1)


def _tenant_label_cap_from_env() -> int:
    try:
        return int(os.environ.get("KC_TENANT_LABEL_MAX", "64") or 64)
    except ValueError:
        return 64  # a tuning-knob typo must not take the operator down


TENANT_LABEL_GUARD = LabelCardinalityGuard(_tenant_label_cap_from_env())


def tenant_label(tenant_id: str) -> str:
    """The guarded spelling of a tenant id for metric labels: the id itself
    while the process-wide cap (``KC_TENANT_LABEL_MAX``) has room, the
    ``"_other"`` overflow bucket after.  Every ``{tenant=...}`` call site
    must route through this."""
    return TENANT_LABEL_GUARD.admit(tenant_id)


def measure(observer, clock=None):
    """Closure timer (constants.go:60-66): ``done = measure(hist.labels(...))``
    then ``done()`` observes the elapsed seconds."""
    start = time.perf_counter()

    def done() -> float:
        elapsed = time.perf_counter() - start
        observer.observe(elapsed)
        return elapsed

    return done
