"""The program's own spans on the profiler's clock.

With tracing on, every ``tracing.span`` of the program also holds a
``jax.profiler.TraceAnnotation`` named ``kc:<span name>`` for its life, on the
thread that runs it, so a traced run's capture carries them on ``/host:CPU``
beside the benchmark's own ``bench.unit`` and on the clock of the device's op
events.  This module finds the newest capture where ``loop.Profiler`` left it
(``<ROOT>/.kc_cache/bench_trace``), loads it once per process, and returns

    {"spans":    [Span(name, start_s, end_s, thread)]   every ``kc:`` interval,
                                                        the prefix cut off
     "units":    [(start_s, end_s)]                     ``bench.unit``, in order
     "busy":     [(start_s, end_s)]                     some chip runs an op
     "programs": [(start_s, end_s)]                     some chip runs a program}

The chips' side is ``xplane.load`` and its interval arithmetic, not a copy.
A program without ``kc:`` annotations (any commit before they existed) gives
an empty ``spans`` list, and every reader over it finds nothing.
"""

import functools
import glob
import os
from typing import List, NamedTuple, Optional

from benchmark.harness import manifest, xplane
from benchmark.harness.sut import UNIT

PREFIX = "kc:"
TRACE_DIR = os.path.join(manifest.ROOT, ".kc_cache", "bench_trace")


class Span(NamedTuple):
    name: str  # the program's span name, without the prefix
    start_s: float
    end_s: float
    thread: str  # the host plane's line that carried it: one per thread


def newest(directory: Optional[str] = None) -> Optional[str]:
    """The newest capture under ``directory``, as ``loop.Profiler.trace_file``
    finds it."""
    found = sorted(glob.glob(os.path.join(
        directory or TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


@functools.lru_cache(maxsize=1)
def load(path: str) -> dict:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    spans: List[Span] = []
    on_device = False
    for plane in data.planes:
        on_device = on_device or bool(xplane.DEVICE_PLANE.match(plane.name))
        if plane.name != xplane.HOST_PLANE:
            continue
        # Python's threads all carry the process's name: tell them apart by
        # the line's place in the plane
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIX):
                    spans.append(Span(e.name[len(PREFIX):], e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9,
                                      f"{line.name}/{i}"))
    # a capture without a device plane is a CPU rehearsal's: XLA:CPU's host
    # threads stand in for one chip, as they do in run.py
    trace = xplane.load(path, host_ops=not on_device)
    chips = [c for c in trace["chips"] if c["ops"]]
    return {
        "spans": sorted(spans, key=lambda s: (s.start_s, -s.end_s)),
        "units": sorted(trace["annotations"][UNIT]),
        "busy": xplane._merge(o[1:] for c in chips for o in c["ops"]),
        "programs": xplane._merge(m[1:] for c in chips for m in c["modules"]),
    }


def capture() -> Optional[dict]:
    """The newest traced run's capture, or None where there is none."""
    path = newest()
    return load(path) if path else None
