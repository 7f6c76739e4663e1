"""Event recorder with dedupe + rate limiting.

Mirror of /root/reference/pkg/events/recorder.go:44-79: events identical in
(involved object, reason, message) are deduped within a 2-minute window, and
event types may carry their own token-bucket rate limiter.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

DEDUPE_TTL_SECONDS = 120.0


@dataclass
class Event:
    involved_object: object
    type: str  # Normal | Warning
    reason: str
    message: str
    dedupe_values: List[str] = field(default_factory=list)
    # events per second allowed for this reason; None = unlimited
    rate_limit_qps: Optional[float] = None

    def dedupe_key(self) -> tuple:
        if self.dedupe_values:
            return (self.reason, *self.dedupe_values)
        obj = self.involved_object
        meta = getattr(obj, "metadata", None)
        name = getattr(meta, "name", str(obj))
        namespace = getattr(meta, "namespace", "")
        return (self.type, self.reason, namespace, name, self.message)


class _TokenBucket:
    def __init__(self, qps: float, burst: int = 10, clock: Callable[[], float] = time.monotonic):
        self.qps = qps
        self.burst = burst
        self.tokens = float(burst)
        self.last = clock()
        self.clock = clock

    def allow(self) -> bool:
        now = self.clock()
        self.tokens = min(self.burst, self.tokens + (now - self.last) * self.qps)
        self.last = now
        if self.tokens >= 1.0:
            self.tokens -= 1.0
            return True
        return False


class Recorder:
    """Sink is any callable taking an Event; the operator wires this to logging
    and the controllers' test harnesses capture it directly."""

    def __init__(self, sink: Optional[Callable[[Event], None]] = None, clock=time.monotonic):
        self.sink = sink
        self.clock = clock
        # dedupe key -> the stamp of its last publish, oldest stamp first
        self._seen: "OrderedDict[tuple, float]" = OrderedDict()
        self._limiters: Dict[str, _TokenBucket] = {}
        self.events: List[Event] = []
        self._lock = threading.Lock()

    # retain at most this many events for test inspection; older are dropped
    MAX_RETAINED_EVENTS = 10_000

    def publish(self, event: Event) -> None:
        # publishers are concurrent (launch_machines fans out over a thread
        # pool): the dedupe map, limiter registry, and retained-event list
        # mutate under one lock — the 100k sharded soak's launch storms
        # crashed the unlocked sweep with "dictionary changed size during
        # iteration".  The sink call stays OUTSIDE the lock (it is arbitrary
        # user code and may publish re-entrantly).
        key = event.dedupe_key()
        now = self.clock()
        with self._lock:
            last = self._seen.get(key)
            if last is not None and now - last < DEDUPE_TTL_SECONDS:
                return
            if event.rate_limit_qps is not None:
                limiter = self._limiters.setdefault(
                    event.reason,
                    _TokenBucket(event.rate_limit_qps, clock=self.clock),
                )
                if not limiter.allow():
                    return
            # a key published again after its TTL goes back to the end, so
            # the map stays in the order of the stamps it holds
            self._seen[key] = now
            self._seen.move_to_end(key)
            self._expire(now)
            self.events.append(event)
            if len(self.events) > self.MAX_RETAINED_EVENTS:
                del self.events[: len(self.events) - self.MAX_RETAINED_EVENTS]
        if self.sink is not None:
            self.sink(event)

    def _expire(self, now: float) -> None:
        """Evict dedupe entries past the TTL (the reference uses a 120s TTL
        cache with a janitor; we expire opportunistically on publish).  The
        map is in stamp order, so the expired entries are its front: the
        cost follows what expires, not what the map holds.
        Caller holds ``_lock``."""
        seen = self._seen
        while seen:
            oldest = next(iter(seen))
            if now - seen[oldest] < DEDUPE_TTL_SECONDS:
                return
            del seen[oldest]

    def reset(self) -> None:
        with self._lock:
            self.events.clear()
            self._seen.clear()
