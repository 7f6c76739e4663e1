"""Async double-buffered solve pipeline primitives (docs/KERNEL_PERF.md
"Layer 7 — the pipelined loop").

The production solve loop used to be serial per tick: dispatch solve[k],
block on the device→host fetch, host-materialize the results, only then
dispatch solve[k+1] — the accelerator idled for the whole fetch+materialize
tail.  This module holds the pieces that overlap those stages:

  FetchTicket       split "dispatch" from "fetch": construction starts
                    non-blocking ``copy_to_host_async`` on every output
                    array; ``wait()`` is the completion barrier (ONE batched
                    ``jax.device_get``).  Everything between construction
                    and the barrier — the next tick's planning, the previous
                    tick's host materialize — overlaps the copy (and, with
                    async dispatch, the device compute itself).  Each wait
                    emits a ``pipeline.overlap`` span: ``hidden_s`` (wall
                    between dispatch and the barrier — fetch+compute time
                    the loop spent doing other work) vs ``exposed_s`` (what
                    the barrier actually blocked).
  HostStagingRing   a small ring (KC_PIPELINE_DEPTH deep) of reusable host
                    staging buffers the ticket lands its arrays in.  Two
                    jobs: steady-state ticks stop allocating fresh host
                    arrays per fetch, and — because staged values are OWNED
                    copies — the zero-copy views a CPU ``device_get`` hands
                    back never pin a device buffer that the next tick wants
                    to donate (a pinned buffer silently degrades donation to
                    a realloc).  Shape drift reallocates and is counted.
  SolvePipeline     the generic depth-N ring driver for tick loops:
                    ``submit(dispatch)`` dispatches now and returns the
                    oldest in-flight tick's results once the ring is full;
                    ``drain()`` retires the tail.  A dispatch that raises
                    leaves the already-dispatched tickets consumable — no
                    wedged slot (the chaos leg in tests/test_pipeline.py).

Buffer donation rides the same switch: ``donation_enabled()`` gates the
``donate_argnums`` solve variants (utils/compilecache, parallel/mesh) that
let steady-state churn repairs reuse the warm carry's device memory instead
of reallocating per tick.  ``record_donation`` keeps the effectiveness
ledger (``stats()["donated"]`` / ``["donation_reallocs"]``).

``KC_PIPELINE=0`` switches all of it off and restores the serial loop
bit-for-bit; ``KC_PIPELINE_DEPTH`` (default 2) sizes the ring.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from karpenter_core_tpu import tracing
from karpenter_core_tpu.metrics import REGISTRY

STAGING_RING_OCCUPANCY = REGISTRY.gauge(
    "karpenter_staging_ring_occupancy",
    "Fraction of host staging-ring slots holding live buffers (filled at "
    "least once since ring construction).",
)
PIPELINE_OVERLAP_RATIO = REGISTRY.gauge(
    "karpenter_pipeline_overlap_ratio",
    "Dispatch/fetch overlap of the latest completed fetch, by ticket label: "
    "hidden_s / (hidden_s + exposed_s); 1.0 = the barrier never blocked.",
    ("label",),
)

_lock = threading.Lock()
_stats = {
    # warm dispatches whose donated carry buffer was actually consumed
    # (device memory reused in place)
    "donated": 0,
    # warm dispatches that re-allocated instead: donation off (KC_PIPELINE=0,
    # policy decode still needs the planes), unsupported by the backend, or
    # silently degraded because a host view still pinned the buffer
    "donation_reallocs": 0,
    # staging-ring slots REBUILT because an array's shape/dtype moved
    # (a slot's first fill is the working set, not drift — uncounted)
    "staging_reallocs": 0,
    # cancellation ledger (utils/watchdog.py): donated dispatches whose tick
    # was invalidated after a watchdog timeout — the donated carry is dead
    # and the lineage re-anchors, so donated == donation_canceled + live
    # donated dispatches at all times (the leak invariant
    # tests/test_watchdog.py pins)
    "donation_canceled": 0,
    # FetchTickets constructed minus tickets retired (first successful wait
    # or invalidate) — a leak-free loop returns this to 0 at quiesce
    "tickets_open": 0,
}
# last completed fetch's overlap record (provisioning surfaces it as the
# soak probe ``tick_overlap_s``)
_last_overlap: Dict[str, float] = {"hidden_s": 0.0, "exposed_s": 0.0}


def pipeline_enabled() -> bool:
    """Process-wide switch: KC_PIPELINE=0 restores the serial solve loop
    (no deferred ticks, no donation, no staging) bit-for-bit."""
    return os.environ.get("KC_PIPELINE", "1") != "0"


def pipeline_depth() -> int:
    """Ring depth (staging slots / in-flight ticks + 1).  Default 2 — the
    double buffer: one tick in flight, one being consumed."""
    try:
        return max(int(os.environ.get("KC_PIPELINE_DEPTH", "2")), 2)
    except ValueError:
        return 2


@functools.lru_cache(maxsize=1)
def backend_supports_donation() -> bool:
    """One-shot runtime probe (memoized): donate a tiny buffer and check it
    was consumed.  Backends that ignore ``donate_argnums`` (older XLA:CPU)
    leave the input alive — donation there would only add warning noise,
    so the solve variants skip it and count reallocs instead."""
    try:
        import warnings

        import jax
        import jax.numpy as jnp

        x = jnp.zeros((8,), jnp.float32)
        probe = jax.jit(lambda a: a + 1.0, donate_argnums=(0,))
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            probe(x).block_until_ready()
        return bool(x.is_deleted())
    except Exception:  # noqa: BLE001 - probe must never break the solve
        return False


def donation_enabled() -> bool:
    """Whether warm-carry dispatches should request buffer donation."""
    return pipeline_enabled() and backend_supports_donation()


def record_donation(engaged: bool) -> None:
    with _lock:
        _stats["donated" if engaged else "donation_reallocs"] += 1


def record_donation_canceled() -> None:
    """A donated dispatch's tick was invalidated (watchdog timeout): the
    donated buffer is dead without its results ever being applied — balance
    the ledger so leak checks can assert donated == canceled + live."""
    with _lock:
        _stats["donation_canceled"] += 1


def stats() -> Dict[str, int]:
    with _lock:
        return dict(_stats)


def reset_stats() -> None:
    with _lock:
        for k in _stats:
            _stats[k] = 0


def last_overlap() -> Dict[str, float]:
    """The most recent FetchTicket.wait() overlap record."""
    with _lock:
        return dict(_last_overlap)


def start_host_copy(tree) -> None:
    """Begin non-blocking device→host copies for every array in ``tree``
    that supports it (jax arrays; numpy/None leaves pass through)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            leaf.copy_to_host_async()
        except AttributeError:
            pass


def fetch_tree(tree, site: str = "pipeline.fetch", key=None):
    """The batched serial-path fetch: start async copies on every leaf, then
    ONE ``jax.device_get`` over the whole tree — no array-by-array blocking
    (the ``decode.fetch`` contract, now shared by the tenant coalescer and
    the consolidation sweep).  The blocking ``device_get`` runs under the
    watchdog (utils/watchdog.py) so a hung device→host copy raises a bounded
    SolveTimeout instead of wedging the caller; ``site`` labels the deadline
    bucket (the consolidation sweep and tenant coalescer pass their own) and
    ``key`` splits it by executable, where one site fetches programs of
    different sizes (the sweep's lane rungs)."""
    import jax

    from karpenter_core_tpu.utils import watchdog

    start_host_copy(tree)
    return watchdog.run(site, jax.device_get, tree, key=key)


class HostStagingRing:
    """A ring of reusable host staging buffer sets.

    ``stage(arrays)`` copies a tuple of host arrays into the next slot's
    persistent buffers (allocating only when a shape/dtype moves — counted
    in ``staging_reallocs``) and returns the buffer views.  Slot ``k`` is
    rewritten only after ``depth-1`` further stage calls, which is exactly
    the double-buffer discipline: a retired tick's consumers are done with
    slot ``k`` before tick ``k+depth`` lands in it."""

    def __init__(self, depth: Optional[int] = None) -> None:
        self.depth = depth or pipeline_depth()
        self._slots: List[List[Optional[np.ndarray]]] = [
            [] for _ in range(self.depth)
        ]
        self._next = 0

    def stage(self, arrays: Tuple) -> Tuple:
        slot = self._slots[self._next]
        self._next = (self._next + 1) % self.depth
        out = []
        for i, a in enumerate(arrays):
            if a is None or not isinstance(a, np.ndarray):
                out.append(a)
                continue
            buf = slot[i] if i < len(slot) else None
            if buf is None or buf.shape != a.shape or buf.dtype != a.dtype:
                # only a REBUILD counts as a realloc — the first fill of a
                # slot is the ring's working set, not shape drift, and
                # counting it would put a false-positive baseline under the
                # ledger every reader checks for steady-state zero
                if buf is not None:
                    with _lock:
                        _stats["staging_reallocs"] += 1
                buf = np.empty_like(a)
            np.copyto(buf, a)
            while len(slot) <= i:
                slot.append(None)
            slot[i] = buf
            out.append(buf)
        STAGING_RING_OCCUPANCY.labels().set(
            sum(1 for s in self._slots if s) / self.depth
        )
        return tuple(out)


class FetchTicket:
    """One solve's device→host fetch, split from its dispatch.

    Construction starts async copies on every array (non-blocking);
    ``wait()`` is the completion barrier — idempotent, one batched
    ``device_get``, optionally staged through a HostStagingRing.  The
    overlap record (``hidden_s`` dispatch→barrier, ``exposed_s`` barrier
    block) lands on the ``pipeline.overlap`` span and ``last_overlap()``."""

    __slots__ = ("_arrays", "_host", "_ring", "_label", "_t_dispatch",
                 "_open", "_invalid", "hidden_s", "exposed_s", "planes")

    def __init__(self, arrays: Tuple, ring: Optional[HostStagingRing] = None,
                 label: str = "solve") -> None:
        self._arrays = arrays
        self._host: Optional[Tuple] = None
        self._ring = ring
        self._label = label
        self._t_dispatch = time.perf_counter()
        self._open = True
        self._invalid = False
        self.hidden_s = 0.0
        self.exposed_s = 0.0
        # decode's lazy big-plane bundle rides the ticket when the solver
        # attaches one (solver.tpu.begin_fetch) so deferred decodes never
        # re-touch possibly-donated device buffers
        self.planes = None
        with _lock:
            _stats["tickets_open"] += 1
        start_host_copy(arrays)

    def done(self) -> bool:
        return self._host is not None

    @property
    def staged(self) -> bool:
        return self._ring is not None

    def _close(self) -> None:
        if self._open:
            self._open = False
            with _lock:
                _stats["tickets_open"] -= 1

    def invalidate(self) -> None:
        """Cancel the ticket after a failed/abandoned barrier: drop the
        TICKET's device refs and retire it from the open ledger; a later
        wait() raises rather than touching the device again.  (A genuinely
        hung ``device_get`` still pins the arrays from its own stuck frame
        until it ever returns — the watchdog drops every reference it
        controls, but the abandoned call's are the call's own.)"""
        self._arrays = ()
        self.planes = None
        self._invalid = True
        self._close()

    def wait(self) -> Tuple:
        if self._invalid:
            raise RuntimeError(
                f"FetchTicket({self._label}) was invalidated after a "
                "watchdog timeout; its tick re-anchors instead"
            )
        if self._host is None:
            import jax

            from karpenter_core_tpu.utils import watchdog

            t_block = time.perf_counter()
            # the completion barrier is the hot path's most likely hang
            # point (a hung device wedges the device→host copy silently):
            # bounded by the watchdog, keyed per ticket label so solve and
            # decode fetches budget separately
            host = watchdog.run(
                "pipeline.fetch", jax.device_get, self._arrays,
                key=self._label,
            )
            t_end = time.perf_counter()
            if self._ring is not None:
                host = self._ring.stage(tuple(host))
            self._host = tuple(host)
            # drop the device refs: a retained zero-copy view would pin the
            # buffers and silently block the next tick's donation
            self._arrays = ()
            self._close()
            self.hidden_s = max(t_block - self._t_dispatch, 0.0)
            self.exposed_s = max(t_end - t_block, 0.0)
            with _lock:
                _last_overlap["hidden_s"] = self.hidden_s
                _last_overlap["exposed_s"] = self.exposed_s
            total = self.hidden_s + self.exposed_s
            PIPELINE_OVERLAP_RATIO.labels(self._label).set(
                self.hidden_s / total if total > 0 else 0.0
            )
            with tracing.span(
                "pipeline.overlap", label=self._label,
                hidden_s=round(self.hidden_s, 6),
                exposed_s=round(self.exposed_s, 6),
                staged=self._ring is not None,
            ):
                pass
        return self._host


class SolvePipeline:
    """Depth-N ring driver for a deferred tick loop.

    ``submit(dispatch)`` calls ``dispatch()`` (which must return a handle
    with a ``result()`` method — e.g. solver.incremental.PendingResults),
    enqueues it, and once the ring holds ``depth - 1`` in-flight handles
    retires the OLDEST by calling its ``result()`` — so tick k's host
    materialize runs after tick k+1's dispatch, overlapped with its device
    compute.  ``drain()`` retires everything left.  A ``dispatch()`` that
    raises enqueues nothing; previously dispatched handles stay consumable
    via ``drain()`` — a mid-pipeline fault cannot wedge a ring slot."""

    def __init__(self, depth: Optional[int] = None) -> None:
        self.depth = depth or pipeline_depth()
        self._inflight: deque = deque()

    def submit(self, dispatch: Callable[[], object]):
        """Returns the oldest in-flight tick's results, or None while the
        ring is filling."""
        handle = dispatch()
        self._inflight.append(handle)
        if len(self._inflight) >= self.depth:
            return self._inflight.popleft().result()
        return None

    def drain(self) -> List[object]:
        out = []
        while self._inflight:
            out.append(self._inflight.popleft().result())
        return out

    def __len__(self) -> int:
        return len(self._inflight)


__all__ = [
    "FetchTicket",
    "HostStagingRing",
    "PIPELINE_OVERLAP_RATIO",
    "STAGING_RING_OCCUPANCY",
    "SolvePipeline",
    "backend_supports_donation",
    "donation_enabled",
    "fetch_tree",
    "last_overlap",
    "pipeline_depth",
    "pipeline_enabled",
    "record_donation",
    "record_donation_canceled",
    "reset_stats",
    "start_host_copy",
    "stats",
]
