"""The injection plane: process-global, default-off named fault points.

Production modules register points at import time —

    CLOUD_CREATE = chaos.point("cloud.create")

— and fire them on the guarded operation:

    fault = CLOUD_CREATE.hit(kinds=(chaos.KIND_ERROR, ...), instance_type=it.name)
    if fault is not None:
        ...interpret the fault (raise the site's native error type)...

``kinds`` declares which fault kinds the site can interpret.  A scenario
kind the site cannot act on is discarded BEFORE it is counted, traced, or
logged — otherwise a misconfigured scenario (e.g. kind="partial" on
``kubeapi.put``) would report full injected-fault coverage while injecting
nothing.  Latency is implicitly supported whenever an armed clock exists,
because the plane applies the sleep itself.

A hit is a zero-cost no-op (one global load + is-None check) unless a
``Scenario`` is armed, so the points can live on hot paths.  When armed, the
scenario decides — deterministically from its seed and the point's hit index
— whether this hit faults; a triggered fault increments
``karpenter_chaos_faults_injected_total{point,kind}`` and lands a
``chaos.fault`` event on the active tracing span, so a decision audit shows
*which* injected fault caused *which* decision.  Latency-kind faults are
applied here (sleep through the armed clock); every other kind is returned
for the call site to interpret, because only the site knows its native error
surface (ConflictError vs ApiServerError vs RuntimeError).

Registration is exactly-once per name (enforced at runtime here and
statically by the kcanalyze ``chaos-hygiene`` pass); call sites that share a
point import the registered ``Point`` object.  See docs/CHAOS.md for the
point catalog and how to add one.
"""

from __future__ import annotations

import logging
import threading
from dataclasses import dataclass, field
from typing import Dict, Optional

from karpenter_core_tpu import tracing
from karpenter_core_tpu.metrics import REGISTRY

log = logging.getLogger(__name__)

CHAOS_FAULTS_INJECTED = REGISTRY.counter(
    "karpenter_chaos_faults_injected_total",
    "Faults injected by the chaos plane, by point and fault kind.",
    ("point", "kind"),
)
CHAOS_ARMED = REGISTRY.gauge(
    "karpenter_chaos_armed",
    "1 while a chaos scenario is armed in this process.",
)

# fault kinds (scenario.py validates against this set)
KIND_ERROR = "error"
KIND_LATENCY = "latency"
KIND_TIMEOUT = "timeout"
KIND_PARTIAL = "partial"
KIND_DUPLICATE = "duplicate"
KIND_SKEW = "skew"
# a silent stall (a device that hangs instead of erroring): interpreted only by the
# watchdog's monitored dispatch sites (utils/watchdog.py, point solver.hang)
# — the call blocks for delay_s (0 = until abandoned) instead of erroring
KIND_HANG = "hang"
FAULT_KINDS = (
    KIND_ERROR, KIND_LATENCY, KIND_TIMEOUT, KIND_PARTIAL, KIND_DUPLICATE,
    KIND_SKEW, KIND_HANG,
)


@dataclass
class Fault:
    """One injected fault, as decided by the armed scenario."""

    point: str
    index: int  # 0-based hit index at this point within the armed scenario
    kind: str = KIND_ERROR
    code: int = 0  # HTTP-ish status for error kinds (409, 410, 500, ...)
    message: str = ""
    delay_s: float = 0.0  # latency kinds; also skew offset for clock.skew
    data: dict = field(default_factory=dict)  # site-specific knobs

    def describe(self) -> str:
        detail = f" code={self.code}" if self.code else ""
        return f"chaos[{self.point}#{self.index}] {self.kind}{detail}: {self.message}"


class InjectedFault(Exception):
    """Raised by call sites that have no more specific error surface."""

    def __init__(self, fault: Fault) -> None:
        super().__init__(fault.describe())
        self.fault = fault


_lock = threading.Lock()
_points: Dict[str, "Point"] = {}
_armed = None  # Optional[Scenario]; module-global for the fast no-op path
_armed_clock = None


class Point:
    """A named injection point.  ``hit()`` is the only hot-path surface."""

    def __init__(self, name: str) -> None:
        self.name = name

    def hit(self, kinds=None, **ctx) -> Optional[Fault]:
        scenario = _armed
        if scenario is None:
            return None
        return self._hit_armed(scenario, kinds, ctx)

    def _hit_armed(self, scenario, kinds, ctx: dict) -> Optional[Fault]:
        # the effective filter: kinds the site interprets, plus latency when
        # the plane can apply it (armed clock), never latency when it can't —
        # a kind nobody can act on must not be reported as injected
        supported = set(kinds) if kinds is not None else set(FAULT_KINDS)
        if _armed_clock is not None:
            supported.add(KIND_LATENCY)
        else:
            supported.discard(KIND_LATENCY)
        fault = scenario.decide(self.name, supported)
        if fault is None:
            return None
        CHAOS_FAULTS_INJECTED.labels(self.name, fault.kind).inc()
        tracing.add_event(
            "chaos.fault",
            point=self.name,
            kind=fault.kind,
            index=fault.index,
            code=fault.code,
            scenario=scenario.name,
            seed=scenario.seed,
            **{k: v for k, v in ctx.items() if isinstance(v, (str, int, float, bool))},
        )
        log.info(
            "chaos: injecting %s (scenario=%s seed=%s)",
            fault.describe(), scenario.name, scenario.seed,
        )
        if fault.kind == KIND_LATENCY and fault.delay_s > 0:
            clock = _armed_clock
            if clock is not None:
                clock.sleep(fault.delay_s)
        return fault


def point(name: str) -> Point:
    """Register (exactly once) and return the named injection point."""
    with _lock:
        if name in _points:
            raise ValueError(f"chaos point {name!r} registered twice")
        p = _points[name] = Point(name)
        return p


def registered_points() -> Dict[str, Point]:
    with _lock:
        return dict(_points)


def arm(scenario, clock=None) -> None:
    """Arm the scenario process-wide.  ``clock`` (utils/clock.Clock) drives
    latency faults and lets FakeClock suites absorb injected delays."""
    global _armed, _armed_clock
    with _lock:
        scenario.reset_counters()
        _armed = scenario
        _armed_clock = clock
    CHAOS_ARMED.labels().set(1.0)
    log.info(
        "chaos: armed scenario=%s seed=%s points=%s — replay with this "
        "(scenario, seed) pair", scenario.name, scenario.seed,
        sorted(scenario.points),
    )


def disarm() -> None:
    global _armed, _armed_clock
    with _lock:
        _armed = None
        _armed_clock = None
    CHAOS_ARMED.labels().set(0.0)


def armed_scenario():
    return _armed


class armed:
    """``with chaos.armed(scenario, clock):`` — arm for the block only."""

    def __init__(self, scenario, clock=None) -> None:
        self.scenario = scenario
        self.clock = clock

    def __enter__(self):
        arm(self.scenario, self.clock)
        return self.scenario

    def __exit__(self, *exc) -> None:
        disarm()


def current_skew_s() -> float:
    """The armed scenario's clock-skew offset (0.0 unarmed) — read by
    utils/clock.Clock on every ``now()``.  Skew is a standing offset rather
    than a per-hit fault: clocks are read far too often to count usefully,
    so the fault counter is bumped once at first application instead."""
    scenario = _armed
    if scenario is None:
        return 0.0
    return scenario.clock_skew_s()
