"""The measured window: a closed loop with one client.

The next unit of traffic is sent when the last has returned and been settled
(cheap checks only).  The window ends on a CYCLE boundary — a cycle is
``kind.group`` units, the traffic's whole repeating pattern — so every run
does whole cycles of the same seeded work and throughput never depends on
where in a cycle the clock ran out.  It ends on the boundary NEAREST to
``seconds``: a traffic whose cycles happen to divide the window evenly (17
churn cycles take 25.7 s, twice that is 51.4 s) would otherwise run two cycles
or three on the toss of a coin.  Nothing is rounded; the window's length is
what the clock read.
"""

import contextlib
import glob
import os
import time
from typing import NamedTuple, Optional

from benchmark.harness.sut import UNIT


class Unit(NamedTuple):
    start_s: float  # since the window began
    wall_s: float  # the unit's latency sample
    pods: int  # pods whose decision was asked for, 0 where the unit failed
    calls: tuple  # sut.Call, one per client call
    failures: tuple  # one message per failed call


class Profiler:
    """A ``jax.profiler`` capture over a short sub-window: one whole cycle of
    the traffic, at least 2 and at most 8 units, from the window's second
    unit on.  A solve is some 60 000 device events and 3 MB of trace, so a
    capture counted in units stays readable where one counted in seconds
    would not.  Python-level tracing is off — the host path here is Python
    building an 86 MB answer, and a tracer on every call would measure
    itself."""

    FIRST = 1

    def __init__(self, directory: str, group: int) -> None:
        self.directory = directory
        self.last = self.FIRST + min(max(group, 2), 8) - 1
        self.started = False
        self.done = False

    def before(self, i: int) -> None:
        if i == self.FIRST:
            import jax.profiler

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.enable_hlo_proto = False
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self.started = True

    def unit(self):
        if not self.started or self.done:
            return contextlib.nullcontext()
        import jax.profiler

        return jax.profiler.TraceAnnotation(UNIT)

    def after(self, i: int) -> None:
        if i == self.last:
            self.stop()

    def stop(self) -> None:
        if self.started and not self.done:
            import jax.profiler

            jax.profiler.stop_trace()
            self.done = True

    def trace_file(self) -> Optional[str]:
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def run(kind, sidecar, seconds: float, profiler: Optional[Profiler] = None) -> tuple:
    """``(units, window_s)``; ``window_s`` runs from the first send to the
    last reply."""
    units = []
    sidecar.drain_calls()
    t_begin = time.perf_counter()
    t_end = t_begin
    i = 0
    while True:
        if i % kind.group == 0 and (profiler is None or profiler.done):
            elapsed = t_end - t_begin
            half_cycle = 0.5 * elapsed * kind.group / i if i else 0.0
            if i and elapsed + half_cycle >= seconds:
                break
        if profiler is not None:
            profiler.before(i)
        t0 = time.perf_counter()
        with profiler.unit() if profiler is not None else contextlib.nullcontext():
            out = kind.unit(i)
        t_end = time.perf_counter()
        pods, failures = kind.settle(i, out)
        units.append(Unit(t0 - t_begin, t_end - t0, pods,
                          tuple(sidecar.drain_calls()), tuple(failures)))
        if profiler is not None:
            profiler.after(i)
        i += 1
    return units, t_end - t_begin
