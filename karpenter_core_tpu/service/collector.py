"""The sidecar paces its own full collections.

CPython 3.12 starts a generation-2 pass of the cyclic collector by an
allocation count: whenever the objects that survived young passes since the
last full one exceed a quarter of those it found alive.  Inside a large
request that rule fires several times — the unpacked message and the decoded
pods are millions of containers, all alive until the reply and all freed by
reference count after it — and every pass walks all of them and frees none
(PERF.md §6, PR 29 and PR 30: 203 passes, 14.4 s of a 56 s churn window).

A server that owns its process chooses when those passes run.  While a
sidecar is up (``serve()`` … ``server.stop()``):

  - the automatic generation-2 trigger is out of a request's reach
    (``gc.set_threshold``, the first two thresholds left as found):
    generations 0 and 1 run as before and free young cycles;
  - the full pass is run HERE, at a handler's exit (its frame gone, so the
    request's own objects are already freed) or by a housekeeping thread
    while no handler is in flight, once ``FULL_INTERVAL_S`` have passed since
    the last full pass or resident memory has grown ``RSS_GROWTH_FACTOR``-fold
    since it — never by an allocation in the middle of a decode or an encode;
  - a ``gc.callbacks`` hook counts every pass by generation, for
    ``karpenter_solver_gc_collections_total`` / ``_gc_seconds_total`` and the
    ``gc_full`` / ``gc_full_s`` attributes of ``service.solve_classes``
    (docs/OBSERVABILITY.md).

The collector is the process's, so the policy is too: every sidecar in a
process shares ``POLICY`` — the first ``acquire()`` installs, the last release
puts the third threshold back and removes the hook and the thread.  No flag,
no environment variable: the operator's knob is ``utils/memlimit.apply``, in
its own process.
"""

from __future__ import annotations

import gc
import logging
import threading
import time
from typing import Callable, Optional

from karpenter_core_tpu.metrics.registry import SOLVER_GC_COLLECTIONS, SOLVER_GC_SECONDS
from karpenter_core_tpu.utils import memlimit

log = logging.getLogger(__name__)

# A full pass over the heap a tenant plane keeps alive (50 000 pods) takes
# 0.05–0.33 s on the chip's host (PERF.md §6, PR 29).  One every 30 s is under
# 1 % of the process's time where the allocation-count rule spent 25 %, and it
# bounds how long cyclic garbage that outlived the young generations can stay.
FULL_INTERVAL_S = 30.0
# ... or sooner, when resident memory has grown by half since the last full
# pass: cyclic garbage is then at most half of what was live, whatever the
# traffic.  Relative to the last pass and not to a limit, because the allocator
# seldom hands memory back: after one large request the new level is the
# baseline and does not trigger again.
RSS_GROWTH_FACTOR = 1.5
# the housekeeping thread's tick: how soon after its last request an idle
# sidecar notices that a pass is due
IDLE_POLL_S = 5.0
# generation 2's threshold counts generation-1 passes; a brown-field request
# runs ~220 of them (ISSUE 30's sizing), so this is out of any request's reach
FULL_THRESHOLD_OUT_OF_REACH = 1 << 30

FULL = 2  # the oldest generation


class CollectorPolicy:
    """Thresholds, hook, clock of the last full pass and housekeeping thread.
    ``clock`` and ``rss`` are the two things it observes; tests inject both.

    One lock guards everything but the hook's tallies: the hook runs inside
    whatever allocated last — possibly under this lock, or a metric's — so it
    takes none and only counts; the pacing reads its counts from outside."""

    def __init__(self, clock: Callable[[], float] = time.monotonic,
                 rss: Callable[[], int] = memlimit.rss_bytes) -> None:
        self.clock = clock
        self.rss = rss
        # re-entrant: the paced pass runs under it, and a pass may finalize a
        # sidecar that was dropped unstopped, whose release takes it again
        self._lock = threading.RLock()
        self._holders = 0
        self._inflight = 0
        self._found_full_threshold = 0
        self._stop: Optional[threading.Event] = None
        self._full_noted = 0  # full passes the pacing has taken note of
        self._last_full = 0.0
        self._rss_after_full = 0
        self._published = ([0, 0, 0], [0.0, 0.0, 0.0])
        # the hook's own: passes and seconds by generation, full passes begun
        self.passes = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self.full_begun = 0
        self._pass_began = 0.0

    # -- installation ---------------------------------------------------------

    def acquire(self) -> Callable[[], None]:
        """Install on the first holder; returns this holder's one-shot release."""
        with self._lock:
            self._holders += 1
            if self._holders == 1:
                self._install()
        held = True

        def release() -> None:
            nonlocal held
            with self._lock:
                if not held:
                    return
                held = False
                self._holders -= 1
                if self._holders == 0:
                    self._uninstall()
                self._publish()

        return release

    @property
    def installed(self) -> bool:
        with self._lock:
            return self._holders > 0

    def _install(self) -> None:
        young, middle, self._found_full_threshold = gc.get_threshold()
        gc.set_threshold(young, middle, FULL_THRESHOLD_OUT_OF_REACH)
        gc.callbacks.append(self._on_pass)
        self._mark_full()
        self._stop = threading.Event()
        threading.Thread(
            target=self._housekeeping, args=(self._stop,),
            name="kc-collector", daemon=True,
        ).start()

    def _uninstall(self) -> None:
        self._stop.set()
        gc.callbacks.remove(self._on_pass)
        # only what this policy set: someone else's young thresholds stay
        young, middle, _ = gc.get_threshold()
        gc.set_threshold(young, middle, self._found_full_threshold)

    # -- the hook -------------------------------------------------------------

    def _on_pass(self, phase: str, info: dict) -> None:
        generation = info["generation"]
        if phase == "start":
            self._pass_began = time.perf_counter()
            if generation == FULL:
                self.full_begun += 1
            return
        self.passes[generation] += 1
        self.seconds[generation] += time.perf_counter() - self._pass_began

    def full_passes(self) -> tuple:
        """(full passes begun, seconds spent in finished ones) so far."""
        return self.full_begun, self.seconds[FULL]

    # -- pacing (all under the lock) -------------------------------------------

    def _mark_full(self) -> None:
        self._full_noted = self.passes[FULL]
        self._last_full = self.clock()
        self._rss_after_full = self.rss()

    def _collect_if_due(self) -> None:
        if self._holders == 0:
            return
        if self.passes[FULL] != self._full_noted:
            # someone else's (an embedder's gc.collect()): it is the last one
            self._mark_full()
        due = self.clock() - self._last_full >= FULL_INTERVAL_S or (
            0 < RSS_GROWTH_FACTOR * self._rss_after_full <= self.rss())
        if due:
            gc.collect()
            if self.passes[FULL] != self._full_noted:  # else: still due
                self._mark_full()

    def paced(self, handler):
        """``handler`` with a request boundary at its exit: by then its frame
        is gone and with it the request's objects, so a pass due now walks
        what the server keeps, not what the request built."""

        def run(request, context):
            with self._lock:
                self._inflight += 1
            try:
                return handler(request, context)
            finally:
                with self._lock:
                    self._inflight -= 1
                    self._collect_if_due()
                    self._publish()

        return run

    def _housekeeping(self, stop: threading.Event) -> None:
        while not stop.wait(IDLE_POLL_S):
            with self._lock:
                if self._inflight == 0:
                    self._collect_if_due()
                self._publish()

    def _publish(self) -> None:
        """The hook's tallies onto /metrics, from outside the collector: at
        every handler exit and every tick of the housekeeping thread."""
        passes, seconds = list(self.passes), list(self.seconds)
        was_passes, was_seconds = self._published
        if passes == was_passes:
            return
        for generation in range(3):
            label = str(generation)
            SOLVER_GC_COLLECTIONS.labels(label).inc(
                passes[generation] - was_passes[generation])
            SOLVER_GC_SECONDS.labels(label).inc(
                seconds[generation] - was_seconds[generation])
        self._published = (passes, seconds)


POLICY = CollectorPolicy()
