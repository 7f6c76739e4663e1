"""Seeded workload-trace generators: the realistic churn mixes the soak
subsystem replays (diurnal waves, deploy storms, batch floods, mass
evictions, mixed multi-provisioner fleets).

Every generator is a pure function ``(seed, **params) -> WorkloadTrace``:
randomness comes exclusively from ``utils/retry.DeterministicRNG``
(splitmix64 — the chaos-plane determinism contract; the kcanalyze
``chaos-hygiene`` gate forbids the ``random`` module here), and timestamps
are emitted monotone so the runner replays list order directly.  Same
``(generator, seed, params)`` ⇒ byte-identical event stream
(``WorkloadTrace.to_jsonl()``), which is what makes every soak verdict
replayable from its printed ``(scenario, seed)`` pair.

See docs/SOAK.md for the add-a-generator guide.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.soak.trace import (
    ACTION_CREATE,
    ACTION_DELETE,
    ACTION_RESIZE,
    TraceEvent,
    WorkloadTrace,
    merge,
    pairs_of,
    sort_events,
)
from karpenter_core_tpu.utils.retry import DeterministicRNG

# the request-size palette
SIZES: Sequence[Dict[str, str]] = (
    {"cpu": "250m", "memory": "256Mi"},
    {"cpu": "500m", "memory": "512Mi"},
    {"cpu": "1", "memory": "2Gi"},
    {"cpu": "2", "memory": "4Gi"},
)


def _exp(rng: DeterministicRNG, mean_s: float) -> float:
    """Exponentially-distributed positive duration (mean ``mean_s``)."""
    # rng.random() is in [0, 1); 1-u is in (0, 1] so log() is defined
    return -mean_s * math.log(1.0 - rng.random())


def _choice(rng: DeterministicRNG, seq: Sequence) -> object:
    return seq[min(int(rng.random() * len(seq)), len(seq) - 1)]


def _size(rng: DeterministicRNG):
    return pairs_of(_choice(rng, SIZES))


def diurnal_wave(
    seed: int,
    duration_s: float = 3600.0,
    period_s: float = 1800.0,
    base_rate_per_s: float = 0.02,
    peak_rate_per_s: float = 0.2,
    mean_lifetime_s: float = 900.0,
    prefix: str = "diurnal",
) -> WorkloadTrace:
    """Sinusoidal arrival rate between base and peak (one full day compressed
    into ``period_s``); each pod lives an exponential lifetime.  Arrivals use
    thinning: candidates at the peak rate, accepted with probability
    rate(t)/peak — exact for a time-varying Poisson process and fully
    deterministic given the seed."""
    rng = DeterministicRNG(seed)
    events: List[TraceEvent] = []
    t, i = 0.0, 0
    while True:
        t += _exp(rng, 1.0 / peak_rate_per_s)
        if t >= duration_s:
            break
        phase = math.sin(2.0 * math.pi * t / period_s - math.pi / 2.0)
        rate = base_rate_per_s + (peak_rate_per_s - base_rate_per_s) * (phase + 1.0) / 2.0
        if rng.random() >= rate / peak_rate_per_s:
            continue
        name = f"{prefix}-{i:05d}"
        i += 1
        events.append(TraceEvent(t, ACTION_CREATE, name, requests=_size(rng)))
        # lifetimes clamp to one mean past the horizon so the trace (and the
        # runner's tick budget) ends rather than trailing an exponential tail
        events.append(TraceEvent(
            min(t + _exp(rng, mean_lifetime_s), duration_s + mean_lifetime_s),
            ACTION_DELETE, name,
        ))
    return WorkloadTrace(
        name=f"{prefix}-wave", seed=seed, events=sort_events(events),
        duration_s=duration_s,
    )


def deploy_storm(
    seed: int,
    waves: int = 3,
    replicas: int = 50,
    wave_interval_s: float = 300.0,
    start_s: float = 10.0,
    rollout: bool = True,
    teardown_lag_s: float = 30.0,
    resize_fraction: float = 0.0,
    prefix: str = "deploy",
) -> WorkloadTrace:
    """Rolling deployments: each wave creates ``replicas`` identical pods in
    a sub-second burst; with ``rollout`` the previous wave is torn down
    ``teardown_lag_s`` after the new one lands (the delete storm that chases
    every deploy).  ``resize_fraction`` of each surviving wave is resized to
    the next size up mid-life — the in-place vertical-scaling churn."""
    rng = DeterministicRNG(seed)
    events: List[TraceEvent] = []
    for w in range(waves):
        at = start_s + w * wave_interval_s
        size = pairs_of(SIZES[w % len(SIZES)])
        bigger = pairs_of(SIZES[(w + 1) % len(SIZES)])
        for r in range(replicas):
            name = f"{prefix}-w{w}-{r:04d}"
            jitter = rng.random() * 0.5
            events.append(TraceEvent(
                at + jitter, ACTION_CREATE, name,
                requests=size,
                labels=pairs_of({"app": prefix, "wave": str(w)}),
                owner_kind="ReplicaSet",
            ))
            if rollout and w + 1 < waves:
                events.append(TraceEvent(
                    start_s + (w + 1) * wave_interval_s + teardown_lag_s
                    + rng.random() * 0.5,
                    ACTION_DELETE, name,
                ))
            elif resize_fraction > 0.0 and rng.random() < resize_fraction:
                events.append(TraceEvent(
                    at + wave_interval_s / 2.0, ACTION_RESIZE, name,
                    requests=bigger,
                ))
    duration = start_s + waves * wave_interval_s + teardown_lag_s
    return WorkloadTrace(
        name=f"{prefix}-storm", seed=seed, events=sort_events(events),
        duration_s=duration,
    )


def batch_flood(
    seed: int,
    jobs: int = 5,
    pods_per_job: int = 40,
    at_s: float = 10.0,
    mean_runtime_s: float = 600.0,
    prefix: str = "batch",
) -> WorkloadTrace:
    """A burst of batch jobs landing near-simultaneously: every job's pods
    arrive inside a few seconds, run an exponential runtime, and complete
    (delete).  The shape that punishes schedulers amortized for trickle
    arrivals."""
    rng = DeterministicRNG(seed)
    events: List[TraceEvent] = []
    for j in range(jobs):
        size = _size(rng)
        job_at = at_s + rng.random() * 5.0
        for p in range(pods_per_job):
            name = f"{prefix}-j{j}-{p:04d}"
            created = job_at + rng.random() * 2.0
            events.append(TraceEvent(
                created, ACTION_CREATE, name,
                requests=size,
                labels=pairs_of({"job": f"{prefix}-{j}"}),
                owner_kind="Job",
            ))
            events.append(TraceEvent(
                created + min(_exp(rng, mean_runtime_s), 4.0 * mean_runtime_s),
                ACTION_DELETE, name,
            ))
    events = sort_events(events)
    return WorkloadTrace(
        name=f"{prefix}-flood", seed=seed, events=events,
        duration_s=events[-1].at_s if events else at_s,
    )


def mass_eviction(
    seed: int,
    standing: int = 60,
    evict_fraction: float = 0.5,
    evict_at_s: float = 600.0,
    recreate_delay_s: float = 30.0,
    prefix: str = "evict",
) -> WorkloadTrace:
    """A standing fleet, then a correlated eviction (node pool rotation, AZ
    drain): a seeded fraction of the fleet is deleted inside one window and
    replacement pods (new names — the controller sees fresh unschedulables)
    arrive ``recreate_delay_s`` later."""
    rng = DeterministicRNG(seed)
    events: List[TraceEvent] = []
    for i in range(standing):
        name = f"{prefix}-{i:05d}"
        events.append(TraceEvent(
            rng.random() * 30.0, ACTION_CREATE, name, requests=_size(rng),
            labels=pairs_of({"app": prefix}),
        ))
        if rng.random() < evict_fraction:
            gone_at = evict_at_s + rng.random() * 10.0
            events.append(TraceEvent(gone_at, ACTION_DELETE, name))
            events.append(TraceEvent(
                gone_at + recreate_delay_s, ACTION_CREATE,
                f"{prefix}-r{i:05d}", requests=_size(rng),
                labels=pairs_of({"app": prefix}),
            ))
    duration = evict_at_s + 10.0 + recreate_delay_s
    return WorkloadTrace(
        name=f"{prefix}-mass", seed=seed, events=sort_events(events),
        duration_s=duration,
    )


def mixed_fleet(
    seed: int,
    provisioners: Sequence[str] = ("fleet-a", "fleet-b"),
    scale: float = 0.5,
    prefix: str = "mixed",
) -> WorkloadTrace:
    """Multi-provisioner fleets under different churn patterns at once: each
    provisioner gets its own sub-workload (round-robin over storm / flood /
    eviction shapes) pinned to it via a node selector on the provisioner-name
    label.  ``scale`` shrinks the standard sub-workload sizes."""
    subtraces: List[WorkloadTrace] = []
    shapes = (
        lambda s, p: deploy_storm(
            s, waves=2, replicas=max(int(24 * scale), 2),
            wave_interval_s=120.0, prefix=p,
        ),
        lambda s, p: batch_flood(
            s, jobs=3, pods_per_job=max(int(20 * scale), 2), prefix=p,
        ),
        lambda s, p: mass_eviction(
            s, standing=max(int(30 * scale), 4), evict_at_s=240.0, prefix=p,
        ),
    )
    for k, prov in enumerate(provisioners):
        sub = shapes[k % len(shapes)](seed + k + 1, f"{prefix}-{prov}")
        selector = pairs_of({labels_api.PROVISIONER_NAME_LABEL_KEY: prov})
        sub.events = [
            TraceEvent(
                e.at_s, e.action, e.pod, requests=e.requests, labels=e.labels,
                node_selector=selector, owner_kind=e.owner_kind,
            )
            for e in sub.events
        ]
        subtraces.append(sub)
    return merge(f"{prefix}-fleet", seed, subtraces)


# generator registry: the names scenarios and tools/soak.py use
GENERATORS = {
    "diurnal": diurnal_wave,
    "deploy-storm": deploy_storm,
    "batch-flood": batch_flood,
    "mass-eviction": mass_eviction,
    "mixed-fleet": mixed_fleet,
}


def generate(kind: str, seed: int, params: Optional[dict] = None) -> WorkloadTrace:
    """Build + validate a trace from the registry (the scenario/CLI entry)."""
    if kind not in GENERATORS:
        raise ValueError(f"unknown generator {kind!r} (have {sorted(GENERATORS)})")
    trace = GENERATORS[kind](seed, **(params or {}))
    trace.validate()
    return trace
