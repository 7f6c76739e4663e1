"""Hang-proof device interaction: watchdog-deadlined, cancelable dispatch.

An accelerator can fail by HANGING, not by erroring — a dispatch or a
device→host copy that never returns — and the pipelined loop
(utils/pipeline.py) keeps in-flight device state (deferred ticks, donated
carries, fetch tickets) that one such silent call pins permanently.  The circuit breaker only
trips on *errors*; this module converts "device went quiet" into a bounded,
structured ``SolveTimeout`` the existing degraded-mode machinery can act on:

  MonitoredDispatch   runs one device interaction on a reusable worker
                      thread under an adaptive deadline.  Overrun abandons
                      the worker (the stuck XLA call is never joined on the
                      hot path — the poisoned thread parks as a daemon and
                      exits on its own if the call ever returns) and raises
                      ``SolveTimeout``.  ``run(site, fn, *args)`` is the
                      module-level convenience every call site uses.

  adaptive deadlines  per ``(site, key)`` — key carries the compile-cache
                      identity (shape bucket + mesh topology), so a 100k-pod
                      sharded solve and an 8-pod canary budget separately.
                      The deadline is an EWMA of observed *warm* latencies
                      times a safety margin, clamped to a floor/ceiling; a
                      cold key (compile not yet paid) gets the cold budget
                      instead.  Knobs (docs/KERNEL_PERF.md):

                        KC_WATCHDOG=0             disable (bit-for-bit the
                                                  pre-watchdog behavior:
                                                  calls run inline, no
                                                  threads, no chaos hits)
                        KC_WATCHDOG_FLOOR_S       min deadline (default 10 —
                                                  doubles as the spurious-
                                                  recompile guard)
                        KC_WATCHDOG_CEILING_S     max deadline (default 120)
                        KC_WATCHDOG_MARGIN        EWMA multiplier (default 8)
                        KC_WATCHDOG_COLD_MULT     floor multiplier for cold
                                                  keys (default 120 — i.e.
                                                  cold = ceiling by default)

  chaos               ``solver.hang`` (kind ``hang``) is the deterministic
                      stall-injection point, hit at every monitored dispatch
                      — ``delay_s`` bounds the stall (0 = hang until
                      abandoned), so a seeded scenario reproduces a silent
                      stall at dispatch or fetch sites.

  quarantine          ``BackendQuarantine`` closes the re-admission loop the
                      breaker leaves open: while the solver breaker is open
                      the backend is quarantined, and each half-open window
                      runs a deadline-bounded *canary* solve (tiny fixed
                      fleet, known answer) instead of risking a real batch —
                      only a verified canary re-admits the device path; a
                      canary with no backend evidence (no provisioners,
                      shape routing) releases the trial without a verdict.

Observability: ``karpenter_watchdog_timeouts_total{site}``, the
``karpenter_watchdog_deadline_headroom_ratio{site}`` gauge (how much of the
deadline the last completed call left unused), canary outcomes, and a
``solve.watchdog`` event on the active tracing span for every timeout.
"""

from __future__ import annotations

import contextvars
import logging
import os
import threading
import time
from typing import Callable, Dict, List, Optional

from karpenter_core_tpu import chaos, tracing
from karpenter_core_tpu.metrics import REGISTRY

log = logging.getLogger(__name__)

# the deterministic stall-injection point (docs/CHAOS.md): hit once per
# monitored dispatch/fetch; kind "hang" stalls the monitored call past its
# deadline (delay_s bounds the stall; 0 hangs until the watchdog abandons it)
SOLVER_HANG = chaos.point("solver.hang")
KIND_HANG = "hang"

WATCHDOG_TIMEOUTS = REGISTRY.counter(
    "karpenter_watchdog_timeouts_total",
    "Monitored device interactions abandoned past their watchdog deadline, "
    "by site.",
    ("site",),
)
WATCHDOG_HEADROOM = REGISTRY.gauge(
    "karpenter_watchdog_deadline_headroom_ratio",
    "Fraction of the adaptive deadline left unused by the last completed "
    "monitored call at each site (1.0 = instant, 0.0 = finished at the "
    "deadline).",
    ("site",),
)
WATCHDOG_CANARY = REGISTRY.counter(
    "karpenter_watchdog_canary_total",
    "Quarantine canary solves by outcome (verified / wrong-answer / timeout "
    "/ error).",
    ("outcome",),
)


class SolveTimeout(RuntimeError):
    """A monitored device interaction overran its watchdog deadline.

    Subclasses RuntimeError deliberately: every existing backend-fault
    consumer (the provisioning solver breaker, the tenant plane's fault
    accounting) already treats an unexpected RuntimeError from the device
    path as a backend verdict, so a timeout feeds degraded mode without new
    plumbing — while structured consumers can still match the type."""

    def __init__(self, site: str, deadline_s: float, key=None) -> None:
        super().__init__(
            f"watchdog: {site} exceeded its {deadline_s:.2f}s deadline "
            f"(key={key!r}); the stuck call was abandoned"
        )
        self.site = site
        self.deadline_s = deadline_s
        self.key = key


def watchdog_enabled() -> bool:
    """Process-wide switch, read per call so tests/benches toggle it live.
    KC_WATCHDOG=0 restores the pre-watchdog behavior bit-for-bit: monitored
    calls run inline on the caller thread and the ``solver.hang`` point is
    never hit."""
    return os.environ.get("KC_WATCHDOG", "1") != "0"


def _env_f(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


def floor_s() -> float:
    # the floor is the spurious-timeout guard for a WARM key: latency jitter
    # (and, on CPU, an unexpected recompile of an already-seen program) must
    # not be abandoned, while a genuine hang stays bounded to seconds.  It
    # does NOT absorb a compile on the TPU (tens of seconds): callers key
    # each site on the executable's full identity, so a program that has
    # yet to compile is a cold key and gets the cold budget instead
    return max(_env_f("KC_WATCHDOG_FLOOR_S", 10.0), 0.001)


def ceiling_s() -> float:
    return max(_env_f("KC_WATCHDOG_CEILING_S", 120.0), floor_s())


def margin() -> float:
    return max(_env_f("KC_WATCHDOG_MARGIN", 8.0), 1.0)


def cold_mult() -> float:
    return max(_env_f("KC_WATCHDOG_COLD_MULT", 120.0), 1.0)


# EWMA smoothing for warm-latency observations (policy constant, not a knob:
# the margin/floor/ceiling band absorbs tuning)
_EWMA_ALPHA = 0.3

_lock = threading.Lock()
# (site, key) -> EWMA of warm latencies.  A key's FIRST completion (the cold
# run: XLA compile + export-cache population contaminate it) only marks the
# key seen; the EWMA seeds at the second completion.
_ewma: Dict[tuple, float] = {}
_seen: set = set()
_timeouts: Dict[str, int] = {}
_last_headroom: Dict[str, float] = {}


def reset_stats() -> None:
    """Forget every observation, deadline, and counter (tests/bench)."""
    with _lock:
        _ewma.clear()
        _seen.clear()
        _timeouts.clear()
        _last_headroom.clear()


def stats() -> Dict[str, object]:
    """Snapshot for bench detail / tests: per-site timeout counts and the
    last deadline-headroom ratio per site."""
    with _lock:
        return {
            "timeouts": dict(_timeouts),
            "headroom": {k: round(v, 4) for k, v in _last_headroom.items()},
        }


def deadline_for(site: str, key=None) -> float:
    """The adaptive deadline for one monitored call: EWMA × margin clamped
    to [floor, ceiling] once the key is warm; the cold budget
    (floor × cold_mult, clamped) before that."""
    lo, hi = floor_s(), ceiling_s()
    with _lock:
        ewma = _ewma.get((site, key))
    if ewma is None:
        return min(max(lo * cold_mult(), lo), hi)
    return min(max(ewma * margin(), lo), hi)


def _observe(site: str, key, elapsed_s: float, deadline_s: float) -> None:
    with _lock:
        k = (site, key)
        if k not in _seen:
            _seen.add(k)  # cold run: compile-contaminated, not a warm sample
        else:
            prev = _ewma.get(k)
            _ewma[k] = (
                elapsed_s if prev is None
                else prev + _EWMA_ALPHA * (elapsed_s - prev)
            )
        headroom = max(1.0 - elapsed_s / deadline_s, 0.0) if deadline_s > 0 else 0.0
        _last_headroom[site] = headroom
    WATCHDOG_HEADROOM.labels(site).set(headroom)


# -- the worker pool ----------------------------------------------------------
# Reusable daemon workers.  A timed-out worker is POISONED: it is dropped
# from the pool and never joined on the hot path — if the stuck call ever
# returns, the worker sees its poison flag and exits on its own.

class _Job:
    __slots__ = ("fn", "args", "kwargs", "ctx", "done", "abandoned",
                 "result", "error")

    def __init__(self, fn, args, kwargs, ctx) -> None:
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.ctx = ctx  # caller's contextvars (tracing span propagation)
        self.done = threading.Event()
        # set when the watchdog gives up on this job: an injected stall (and
        # any cooperative waiter) unblocks promptly instead of leaking a
        # sleeping thread per timeout
        self.abandoned = threading.Event()
        self.result = None
        self.error: Optional[BaseException] = None


class _Worker:
    __slots__ = ("_cond", "_job", "poisoned", "thread")

    def __init__(self) -> None:
        self._cond = threading.Condition()
        self._job: Optional[_Job] = None
        self.poisoned = False
        self.thread = threading.Thread(
            target=self._loop, name="kc-watchdog-worker", daemon=True
        )
        self.thread.start()

    def submit(self, job: _Job) -> None:
        with self._cond:
            self._job = job
            self._cond.notify()

    def _loop(self) -> None:
        while True:
            with self._cond:
                while self._job is None:
                    self._cond.wait()
                job, self._job = self._job, None
            try:
                job.result = job.ctx.run(job.fn, *job.args, **job.kwargs)
            except BaseException as e:  # noqa: BLE001 - routed to the caller
                job.error = e
            job.done.set()
            with _pool_lock:
                if self.poisoned:
                    return  # abandoned mid-call: retire quietly
                _idle.append(self)


_pool_lock = threading.Lock()
_idle: List[_Worker] = []


def _checkout() -> _Worker:
    with _pool_lock:
        if _idle:
            return _idle.pop()
    return _Worker()


def _poison(worker: _Worker, job: _Job) -> None:
    with _pool_lock:
        worker.poisoned = True
    job.abandoned.set()
    # drop the job's own references to the call: the stuck frame inside the
    # worker still pins whatever the hung call holds (unavoidable — the call
    # itself owns those refs until it returns), but nothing ELSE should.
    # An injected stall releases promptly (it waits on `abandoned` before
    # ever touching the arrays).
    job.fn = None
    job.args = ()
    job.kwargs = {}
    job.ctx = None


def _stalled(fn, fault, job: _Job):
    """Wrap ``fn`` with the injected stall: wait out ``delay_s`` (0 = until
    abandoned), then run normally — a stall shorter than the deadline is
    pure latency, a longer one becomes a SolveTimeout and the abandoned
    worker exits promptly instead of sleeping forever."""
    delay_s = float(fault.delay_s or 0.0)

    def stalled(*args, **kwargs):
        if delay_s > 0:
            job.abandoned.wait(delay_s)
        else:
            job.abandoned.wait()
        if job.abandoned.is_set():
            raise RuntimeError(fault.describe())  # never seen: job abandoned
        return fn(*args, **kwargs)

    return stalled


class MonitoredDispatch:
    """One site's deadline-bounded dispatch wrapper.

    ``run(fn, *args, key=..., **kwargs)`` executes ``fn`` on a pooled worker
    under the (site, key) adaptive deadline; ``deadline_s`` overrides it.
    Disabled (KC_WATCHDOG=0) it calls ``fn`` inline — zero threads, zero
    chaos hits, bit-for-bit today's behavior."""

    def __init__(self, site: str, deadline_s: Optional[float] = None) -> None:
        self.site = site
        self.deadline_s = deadline_s

    def run(self, fn: Callable, *args, key=None,
            deadline_s: Optional[float] = None, **kwargs):
        if not watchdog_enabled():
            return fn(*args, **kwargs)
        deadline = deadline_s or self.deadline_s or deadline_for(self.site, key)
        job = _Job(fn, args, kwargs, contextvars.copy_context())
        fault = SOLVER_HANG.hit(kinds=(KIND_HANG,), site=self.site)
        if fault is not None and fault.kind == KIND_HANG:
            job.fn = _stalled(fn, fault, job)
        worker = _checkout()
        t0 = time.perf_counter()
        worker.submit(job)
        if not job.done.wait(deadline):
            _poison(worker, job)
            with _lock:
                _timeouts[self.site] = _timeouts.get(self.site, 0) + 1
            WATCHDOG_TIMEOUTS.labels(self.site).inc()
            WATCHDOG_HEADROOM.labels(self.site).set(0.0)
            tracing.add_event(
                "solve.watchdog", site=self.site,
                deadline_s=round(deadline, 3), outcome="timeout",
                key=repr(key) if key is not None else None,
            )
            log.warning(
                "watchdog: %s overran its %.2fs deadline (key=%r); call "
                "abandoned", self.site, deadline, key,
            )
            raise SolveTimeout(self.site, deadline, key)
        elapsed = time.perf_counter() - t0
        if job.error is not None:
            # failed completions are NOT latency observations: a burst of
            # instant backend errors must not drag the warm EWMA (and with
            # it the deadline) toward the floor, or the first healthy
            # post-recovery call would spuriously time out
            raise job.error
        _observe(self.site, key, elapsed, deadline)
        return job.result


def run(site: str, fn: Callable, *args, key=None,
        deadline_s: Optional[float] = None, **kwargs):
    """Module-level MonitoredDispatch: the one-liner every device-touching
    call site wraps itself in (the kcanalyze ``unbounded-block`` rule flags
    raw blocking device calls that bypass it)."""
    return MonitoredDispatch(site).run(
        fn, *args, key=key, deadline_s=deadline_s, **kwargs
    )


# -- backend quarantine -------------------------------------------------------


class BackendQuarantine:
    """The re-admission ladder over an existing solver-backend breaker.

    The breaker (utils/retry.CircuitBreaker) already converts repeated
    faults into an open state and a periodic half-open trial — but the trial
    is a *real* workload batch, so re-admission risks production pods on an
    unproven device, and a backend that hangs (rather than errors) wedges
    the trial itself.  This wrapper makes the trial a deadline-bounded
    canary: a tiny fixed-fleet solve with a known answer, run through the
    watchdog.  Only a verified canary closes the breaker; anything else
    (wrong answer, timeout, error) re-opens it and the backend stays
    quarantined serving degraded host solves.

    ``canary`` is the probe callable: () -> True (the device answered AND
    the answer verified), False (answered wrong), or None (NO backend
    evidence — e.g. no provisioners to solve against, shape routing): a
    no-verdict releases the trial slot instead of re-opening the breaker,
    the same contract the legacy real-batch trial keeps for precondition
    errors.  It runs through a monitored dispatch at site ``solve.canary``
    so a hung canary is itself bounded."""

    def __init__(self, breaker, canary: Callable[[], bool],
                 deadline_s: Optional[float] = None) -> None:
        self.breaker = breaker
        self.canary = canary
        self.deadline_s = deadline_s

    def _deadline(self) -> Optional[float]:
        """Explicit ctor deadline, else KC_WATCHDOG_CANARY_DEADLINE_S (read
        per canary so tests/operators retune between windows), else the
        adaptive (site, key) deadline."""
        return self.deadline_s or _env_f(
            "KC_WATCHDOG_CANARY_DEADLINE_S", 0.0
        ) or None

    def quarantined(self) -> bool:
        from karpenter_core_tpu.utils import retry

        return self.breaker.state == retry.OPEN

    def try_readmit(self) -> bool:
        """Run one deadline-bounded canary against the quarantined backend.
        True = verified and re-admitted (breaker closed); False = still
        quarantined.  The caller must hold a granted half-open trial (a
        ``breaker.allow()`` that returned True in the half-open state)."""
        outcome = "error"
        try:
            ok = run(
                "solve.canary", self.canary, deadline_s=self._deadline()
            )
            if ok is None:
                outcome = "no-verdict"
            else:
                outcome = "verified" if ok else "wrong-answer"
        except SolveTimeout:
            outcome = "timeout"
        except Exception as e:  # noqa: BLE001 - a canary fault is a verdict
            log.warning("quarantine canary failed: %s", e)
            outcome = "error"
        WATCHDOG_CANARY.labels(outcome).inc()
        tracing.add_event("solve.watchdog", site="solve.canary",
                          outcome=outcome)
        if outcome == "verified":
            self.breaker.record_success()
            log.info(
                "backend quarantine: canary verified — device path "
                "re-admitted"
            )
            return True
        if outcome == "no-verdict":
            # the backend was never exercised (no provisioners, shape
            # routing): not a verdict either way — free the trial slot so a
            # later window can still probe, without burning a fresh
            # reset-timeout on a cluster-config condition
            self.breaker.release_trial()
            log.info(
                "backend quarantine: canary produced no backend evidence — "
                "trial released, backend stays quarantined"
            )
            return False
        self.breaker.record_failure()  # half-open failure re-opens the breaker
        log.warning(
            "backend quarantine: canary %s — backend stays quarantined",
            outcome,
        )
        return False


__all__ = [
    "BackendQuarantine",
    "KIND_HANG",
    "MonitoredDispatch",
    "SOLVER_HANG",
    "SolveTimeout",
    "deadline_for",
    "reset_stats",
    "run",
    "stats",
    "watchdog_enabled",
]
