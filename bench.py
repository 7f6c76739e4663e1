"""Headline benchmark: the north-star solve from BASELINE.json.

Runs the 50k-pending-pods × 1k-instance-types × 5-provisioners scheduling solve
on the device JAX finds and prints ONE JSON line:

    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Baseline: the reference's CI throughput floor of 100 pods/sec for the Go
scheduler (scheduling_benchmark_test.go:48,178-182) — the only published
performance number the reference has.  vs_baseline is our pods/sec over that
floor (higher is better).  The measured value is warm end-to-end wall time:
snapshot encode (host) + kernel solve (device) + decode (host).

One process per chip: a process that has touched JAX holds the accelerator,
and a child that needs it then fails or hangs.  So the top level
(``python bench.py``) stays off JAX and runs its measuring children strictly
in sequence — the main run (``--measure``), then the fresh-process restart
probe (``--restart-probe``), then one child per mesh size
(``--sharded-probe K``) — and merges their JSON lines.  Every child stamps
``platform`` / ``device_kind`` / ``device_count`` from its own
``jax.devices()``.  What JAX finds is the device: there is no probe, no
fallback and no re-exec; a CPU run is one the caller asked for with
``JAX_PLATFORMS=cpu``.  Any failed phase makes the exit code non-zero.
"""

import json
import os
import subprocess
import sys
import time


def _device_stamp() -> dict:
    """platform / device_kind / device_count as THIS process's JAX reports
    them — stamped by the process that measured, never by a bystander."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def _machine_tag() -> str:
    """Fingerprint of the host's CPU capability set: tools/perfgate.py widens
    its tolerance when comparing records taken on different machines."""
    import hashlib
    import platform as platform_mod

    basis = platform_mod.machine()
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 spells it "flags", aarch64 "Features"
                if line.startswith(("flags", "Features")):
                    basis += line
                    break
    except OSError:
        pass
    return hashlib.sha256(basis.encode()).hexdigest()[:10]


def _listdir(path: str):
    try:
        return os.listdir(path)
    except OSError:
        return []


def pod_mix(n_pods: int, rng=None) -> list:
    """The reference benchmark's makeDiversePods shape
    (scheduling_benchmark_test.go:185-197): 3/7 generic + 1/7 zonal spread +
    1/7 hostname spread + 2/7 pod (self-)affinity.  ``rng``
    (``random.Random``) draws each generic pod's cpu and memory from the
    reference's own lists (randomCPU / randomMemory: 100m…1500m ×
    100Mi…4Gi — 30 shapes, and 100m is not exact in bf16, which is what lets
    a chip run catch a matmul running below f32 precision) and each affinity
    pod's group, as the reference draws them from its seeded source: 39
    classes.  None keeps the bench's fixed four-size cycle: 13 classes."""
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.apis.objects import (
        LabelSelector,
        PodAffinityTerm,
        TopologySpreadConstraint,
    )
    from karpenter_core_tpu.testing import make_pod

    pods = []
    n_spread = n_pods // 7
    n_host_spread = n_pods // 7
    n_affinity = 2 * n_pods // 7
    n_generic = n_pods - n_spread - n_host_spread - n_affinity
    sizes = [
        {"cpu": "500m", "memory": "512Mi"},
        {"cpu": 1, "memory": "2Gi"},
        {"cpu": 2, "memory": "4Gi"},
        {"cpu": "250m", "memory": "256Mi"},
    ]
    for i in range(n_generic):
        if rng is None:
            size = sizes[i % len(sizes)]
        else:
            size = {
                "cpu": rng.choice(("100m", "250m", "500m", "1000m", "1500m")),
                "memory": rng.choice(
                    ("100Mi", "256Mi", "512Mi", "1024Mi", "2048Mi", "4096Mi")),
            }
        pods.append(make_pod(requests=size))
    for _ in range(n_spread):
        pods.append(
            make_pod(
                labels={"app": "spread"},
                requests={"cpu": "250m", "memory": "256Mi"},
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                        label_selector=LabelSelector(match_labels={"app": "spread"}),
                    )
                ],
            )
        )
    for _ in range(n_host_spread):
        pods.append(
            make_pod(
                labels={"app": "hspread"},
                requests={"cpu": "250m", "memory": "256Mi"},
                topology_spread=[
                    TopologySpreadConstraint(
                        max_skew=1,
                        topology_key=labels_api.LABEL_HOSTNAME,
                        label_selector=LabelSelector(match_labels={"app": "hspread"}),
                    )
                ],
            )
        )
    # zone self-affinity groups over a 7-value label pool — the reference's
    # 2/7 affinity share draws labels/selectors from the same 7 values
    # (scheduling_benchmark_test.go:263-278); self-selecting groups avoid the
    # cross-group scan-order dependency that routes to the host path
    for i in range(n_affinity):
        group = f"g{i % 7 if rng is None else rng.randrange(7)}"
        pods.append(
            make_pod(
                labels={"aff-group": group},
                requests={"cpu": "250m", "memory": "256Mi"},
                pod_affinity=[
                    PodAffinityTerm(
                        topology_key=labels_api.LABEL_TOPOLOGY_ZONE,
                        label_selector=LabelSelector(match_labels={"aff-group": group}),
                    )
                ],
            )
        )
    return pods


def build_inputs(n_pods: int, n_instance_types: int, n_provisioners: int):
    from karpenter_core_tpu.cloudprovider import fake as fake_cp
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_provisioner

    provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(n_instance_types))
    provisioners = [
        make_provisioner(name=f"prov-{i}", weight=n_provisioners - i)
        for i in range(n_provisioners)
    ]
    return TPUSolver(provider, provisioners), pod_mix(n_pods)


def restart_probe(n_pods: int, n_its: int) -> None:
    """First-solve wall time in THIS fresh process with the persistent caches
    warm on disk — the operationally recurring cold start (every operator
    restart); printed as one JSON line for the top-level bench process."""
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.utils import compilecache

    compilecache.enable()
    solver, pods = build_inputs(n_pods, n_its, n_provisioners=5)
    from karpenter_core_tpu.models import columnar as columnar_mod

    columnar_mod._sig_key_impl()  # resolve (maybe build) the fast key untimed
    t0 = time.perf_counter()
    ingest = PodIngest()
    ingest.add_all(pods)
    snapshot = solver.encode(ingest)
    out = solve_ops.solve(snapshot)
    results = solver.decode(snapshot, out)
    elapsed = time.perf_counter() - t0
    scheduled = sum(len(n.pods) for n in results.new_nodes)
    print(json.dumps({
        "restart_cold_s": round(elapsed, 2), "scheduled": scheduled,
        **_device_stamp(),
    }))


def scale_line_100k(n_its: int) -> dict:
    """BASELINE.md scale config: 100k pods × n_its types, cold + warm."""
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops

    solver, pods = build_inputs(100_000, n_its, n_provisioners=5)
    from karpenter_core_tpu.models import columnar as columnar_mod

    columnar_mod._sig_key_impl()  # resolve (maybe build) the fast key untimed
    t0 = time.perf_counter()
    ingest = PodIngest()
    ingest.add_all(pods)
    snapshot = solver.encode(ingest)
    out = solve_ops.solve(snapshot)
    results = solver.decode(snapshot, out)
    cold_s = time.perf_counter() - t0
    warm_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        snapshot = solver.encode(ingest)
        out = solve_ops.solve(snapshot)
        results = solver.decode(snapshot, out)
        warm_s = min(warm_s, time.perf_counter() - t0)
    scheduled = sum(len(n.pods) for n in results.new_nodes)
    return {
        "warm_s": round(warm_s, 4),
        "cold_s": round(cold_s, 2),
        "scheduled": scheduled,
        "failed": len(results.failed_pods),
        "nodes": len(results.new_nodes),
        "pods_per_sec": round(scheduled / warm_s) if warm_s > 0 else 0,
    }


def consolidation_cluster(n_nodes: int, pods_per_node: int, instance_types):
    """(env, candidates): a synthetic consolidatable cluster over
    ``instance_types`` — nodes and bound pods pushed straight through the
    informer plane, no provisioning round trips — and its disruption-sorted
    candidate list, the input of a multi-node consolidation sweep."""
    from karpenter_core_tpu.apis import labels as labels_api
    from karpenter_core_tpu.controllers.deprovisioning import candidate_nodes
    from karpenter_core_tpu.testing import make_node, make_pod, make_provisioner
    from karpenter_core_tpu.testing.harness import make_environment
    from karpenter_core_tpu.utils import resources as resources_util

    env = make_environment(instance_types=instance_types)
    env.kube.create(make_provisioner(name="default", consolidation_enabled=True))
    # a roomy on-demand instance type: bound pods use a sliver of it, so most
    # prefixes consolidate (the interesting, full-cost sweep shape)
    choices = [
        it for it in env.provider.get_instance_types(None)
        if resources_util.parse_quantity(it.capacity.get("cpu", 0)) >= 8
        and any(o.capacity_type == labels_api.CAPACITY_TYPE_ON_DEMAND and o.available
                for o in it.offerings)
    ]
    it = choices[len(choices) // 2]
    offering = next(
        o for o in it.offerings
        if o.capacity_type == labels_api.CAPACITY_TYPE_ON_DEMAND and o.available
    )
    for i in range(n_nodes):
        node = make_node(
            name=f"sweep-node-{i}",
            labels={
                labels_api.PROVISIONER_NAME_LABEL_KEY: "default",
                labels_api.LABEL_INSTANCE_TYPE_STABLE: it.name,
                labels_api.LABEL_TOPOLOGY_ZONE: offering.zone,
                labels_api.LABEL_CAPACITY_TYPE: offering.capacity_type,
                labels_api.LABEL_NODE_INITIALIZED: "true",
            },
            allocatable=it.allocatable(),
            capacity=dict(it.capacity),
            provider_id=f"fake://sweep-node-{i}",
        )
        env.kube.create(node)
        for _ in range(pods_per_node):
            pod = make_pod(requests={"cpu": "100m", "memory": "64Mi"})
            env.kube.create(pod)
            env.bind(pod, node.name)
    env.clock.step(30)
    dep = env.deprovisioning
    candidates = sorted(
        candidate_nodes(
            env.cluster, env.kube, env.clock, env.provider,
            dep.multi_node_consolidation.should_deprovision,
        ),
        key=lambda c: c.disruption_cost,
    )
    return env, candidates


def consolidation_sweep_line(n_nodes: int = 1000, pods_per_node: int = 3) -> dict:
    """1000-candidate multi-node consolidation sweep (BASELINE.md config 4):
    times ``TPUConsolidationSearch.compute_command`` end to end (encode +
    device prefix sweep + re-grid passes + decode), the path the
    deprovisioning controller runs (multinodeconsolidation.go:74-114 analog).
    """
    from karpenter_core_tpu.cloudprovider import fake as fake_cp
    from karpenter_core_tpu.solver.consolidation import TPUConsolidationSearch

    env, candidates = consolidation_cluster(
        n_nodes, pods_per_node, fake_cp.instance_types(64)
    )
    search = TPUConsolidationSearch(env.provider, env.kube.list_provisioners())
    t0 = time.perf_counter()
    cmd = search.compute_command(
        candidates,
        pending_pods=[],
        state_nodes=env.cluster.snapshot_nodes(),
        bound_pods=env.kube.list_pods(),
    )
    sweep_s = time.perf_counter() - t0
    return {
        "sweep_s": round(sweep_s, 3),
        "candidates": len(candidates),
        "action": cmd.action.value,
        "nodes_removed": len(cmd.nodes_to_remove),
    }


def churn_line(solver, ingest, churn_fraction: float = 0.02, ticks: int = 5) -> dict:
    """Steady-state churn benchmark (ISSUE 7 acceptance): the resident pod
    population stays fixed while ``churn_fraction`` of each class is replaced
    per tick, and each tick is solved BOTH ways —

      full re-solve   what every reconcile paid before this PR: encode the
                      whole snapshot from scratch, solve every class, decode
      delta repair    the incremental session: no encode, evictions returned
                      to the warm carry, ONE repair executable over the delta

    Reported: per-tick wall medians (``warm_solve_s`` / ``full_resolve_s``),
    the speedup, the session's full/delta decision counts, and whether the
    delta lineage's final assignments are identical (canonical per-node class
    loads) to the from-scratch solve — the parity the repair claims.
    Deterministic: evictions take each class's oldest members, replacements
    deep-copy the class representative (same shape, fresh identity)."""
    import copy
    import statistics

    from karpenter_core_tpu.apis.objects import new_uid
    from karpenter_core_tpu.models import store as store_mod
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.solver.incremental import (
        FallbackPolicy,
        IncrementalSolveSession,
        node_signature_of,
    )

    session = IncrementalSolveSession(
        solver,
        FallbackPolicy(enabled=True, audit_interval=0, max_delta_fraction=0.5),
    )
    t0 = time.perf_counter()
    session.solve(ingest)
    seed_s = time.perf_counter() - t0

    warm_ticks, full_ticks, delta_ingest_ticks = [], [], []
    churned_per_tick = []
    delta_compile_s = None
    identical = True
    reps = {}  # class signature -> representative pod (shapes to re-mint)
    # O(fleet) ingest yardstick: what a from-scratch re-ingest of the whole
    # resident population costs — the per-tick delta ingest below must scale
    # with the churned subset, not with this number (ISSUE 11 acceptance)
    from karpenter_core_tpu.models.columnar import PodIngest

    resident = ingest.pods()
    t0 = time.perf_counter()
    _full = PodIngest()
    _full.add_all(resident)
    full_ingest_s = time.perf_counter() - t0
    del _full, resident
    # churn concentrates in a rotating subset of classes per tick — the
    # rollout/deployment shape (one workload's pods are replaced while the
    # rest of the fleet idles), which is what makes the dirty REGION small
    # even when the churned pod count is not.  KC_BENCH_CHURN_CLASSES widens
    # it (1.0 = every class churns every tick).
    class_fraction = float(os.environ.get("KC_BENCH_CHURN_CLASSES", "0.25"))
    for tick in range(ticks):
        members = ingest.class_members()
        sigs = sorted(members, key=lambda s: repr(s))
        window = max(int(len(sigs) * class_fraction), 1)
        start = (tick * window) % max(len(sigs), 1)
        dirty = [sigs[(start + i) % len(sigs)] for i in range(window)]
        target = max(int(len(ingest) * churn_fraction), 1)
        pool = sum(len(members[s]) for s in dirty)
        evictions, replacements = [], []
        for sig in dirty:
            uids = members[sig]
            take = min(max(round(target * len(uids) / max(pool, 1)), 1), len(uids))
            rep = reps.setdefault(sig, copy.deepcopy(ingest.get(uids[0])))
            evictions.extend(uids[:take])
            for _ in range(take):
                pod = copy.deepcopy(rep)
                pod.metadata.name = f"churn-{tick}-{len(replacements)}"
                pod.metadata.uid = new_uid()
                pod.spec.node_name = ""
                replacements.append(pod)
        # the delta-tick ingest cost: membership deltas applied to the live
        # store (pod construction above deliberately excluded — it is the
        # workload's cost, not the ingest's); must be O(churned), not O(fleet)
        t0 = time.perf_counter()
        for uid in evictions:
            ingest.remove(uid)
        for pod in replacements:
            ingest.add(pod)
        delta_ingest_ticks.append(time.perf_counter() - t0)
        churned_per_tick.append(len(evictions) + len(replacements))

        import jax

        # the old path: full re-solve of the whole snapshot
        t0 = time.perf_counter()
        snapshot = solver.encode(ingest)
        out_full = solve_ops.solve(snapshot)
        results_full = solver.decode(snapshot, out_full)
        full_ticks.append(time.perf_counter() - t0)

        # fetch the full solve's planes (and thereby drain its device queue)
        # BEFORE the delta timer starts — otherwise the repair's first sync
        # absorbs the full solve's still-in-flight compute and the warm number
        # reads slower than it is
        assign_f, assign_ex_f = jax.device_get(
            (out_full.assign, out_full.assign_existing)
        )
        # label loads by stable class identity, not row index: a fully-churned
        # class re-enters the fresh encode at a different row among
        # equal-request classes, which must not read as divergence
        keys_f = [store_mod.class_key(c) for c in snapshot.classes]
        full_sig = node_signature_of(assign_f, keys_f) + node_signature_of(
            assign_ex_f, keys_f
        )

        # the delta path
        t0 = time.perf_counter()
        session.solve(ingest)
        elapsed = time.perf_counter() - t0
        if tick == 0:
            # first repair pays the delta executable's cold compile; report
            # it separately so the steady-state number is honest
            delta_compile_s = elapsed
        else:
            warm_ticks.append(elapsed)

        identical = identical and (full_sig == session.node_signature())

    agg = session.aggregates()
    warm_s = statistics.median(warm_ticks) if warm_ticks else float("inf")
    full_s = statistics.median(full_ticks)
    delta_ingest_s = statistics.median(delta_ingest_ticks) if delta_ingest_ticks else 0.0
    churned = round(statistics.mean(churned_per_tick)) if churned_per_tick else 0
    return {
        "pods": len(ingest),
        "churn_fraction": churn_fraction,
        "ticks": ticks,
        # per-tick membership-delta ingest vs the O(fleet) from-scratch
        # yardstick: the O(churned) acceptance evidence (ISSUE 11)
        "delta_ingest_s": round(delta_ingest_s, 5),
        "churned_pods_per_tick": churned,
        "full_ingest_s": round(full_ingest_s, 4),
        "delta_ingest_fraction_of_full": round(
            delta_ingest_s / full_ingest_s, 4
        ) if full_ingest_s > 0 else None,
        "seed_full_solve_s": round(seed_s, 4),
        "delta_compile_s": round(delta_compile_s, 4) if delta_compile_s else None,
        "warm_solve_s": round(warm_s, 4),
        "full_resolve_s": round(full_s, 4),
        "speedup": round(full_s / warm_s, 2) if warm_s > 0 else 0.0,
        "modes": dict(session.mode_counts),
        "identical_assignments": identical,
        # capacity accounting: the carry's used plane vs an exact recount
        # (IncrementalSolveSession.used_drift; ~1e-7 = f32 rounding)
        "used_drift_max_rel": session.used_drift(),
        "scheduled": agg["scheduled"],
        "failed": agg["failed"],
        "nodes": agg["nodes"],
    }


def pipeline_line(n_pods: int = 100_000, n_its: int = 2000,
                  churn_fraction: float = 0.02, ticks: int = 6) -> dict:
    """Pipelined solve loop benchmark (ISSUE 14 acceptance): the SAME
    deterministic churn-tick sequence driven two ways over the incremental
    session in its ANCHOR regime — FallbackPolicy(materialized=True), the
    in-process provisioning controller's policy, where every tick's solve
    re-anchors full because the previous solve's decisions became real
    nodes.  That is the loop whose fetch+materialize tail the pipeline
    exists to hide: per tick the device re-solves the whole fleet while the
    host mints the next churn wave, decodes the previous anchor, and
    materializes its launch-path reads.

      serial     KC_PIPELINE=0: dispatch, block on the fetch, decode,
                 materialize, only then the next tick (the pre-pipeline
                 loop bit-for-bit, per-tick host plane re-upload included)
      pipelined  KC_PIPELINE=1 + solve(deferred=True): tick k+1's dispatch
                 overlaps tick k's device->host copy and host materialize;
                 the completion barrier surfaces only the device time the
                 host work could not cover (docs/KERNEL_PERF.md "Layer 7")

    Reported: warm per-tick means (tick 0 excluded), the speedup,
    ``overlap_efficiency`` = median hidden/(hidden+exposed) per
    pipeline.overlap record (the hidden-fetch fraction), and whether the two
    legs' final assignments are identical.  The donation ledger
    (``donated`` / ``donation_reallocs``) comes from a short steady-churn
    REPAIR segment appended to the pipelined leg — carry donation is the
    warm path's device-memory story and the anchor loop never dispatches
    warm."""
    import copy
    import statistics

    from karpenter_core_tpu.apis.objects import new_uid
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.solver.incremental import (
        FallbackPolicy,
        IncrementalSolveSession,
    )
    from karpenter_core_tpu.utils import pipeline as pipeline_mod

    solver, pods = build_inputs(n_pods, n_its, n_provisioners=5)

    def churn(ingest, reps, tick: int) -> None:
        members = ingest.class_members()
        sigs = sorted(members, key=repr)
        target = max(int(len(ingest) * churn_fraction), 1)
        pool = sum(len(members[s]) for s in sigs)
        evictions, replacements = [], []
        for sig in sigs:
            uids = members[sig]
            take = min(
                max(round(target * len(uids) / max(pool, 1)), 1), len(uids)
            )
            rep = reps.setdefault(sig, copy.deepcopy(ingest.get(uids[0])))
            evictions.extend(uids[:take])
            for _ in range(take):
                pod = copy.deepcopy(rep)
                pod.metadata.name = f"churn-{tick}-{len(replacements)}"
                pod.metadata.uid = new_uid()
                pod.spec.node_name = ""
                replacements.append(pod)
        for uid in evictions:
            ingest.remove(uid)
        for pod in replacements:
            ingest.add(pod)

    def consume(results) -> int:
        # the launch path's reads: every decision materializes its offering
        # lists and request vector
        touched = 0
        for d in results.new_nodes:
            touched += len(d.instance_type_names[:4]) + len(d.zones)
            touched += len(d.requests)
        return touched

    def anchor_leg(pipelined: bool, n_ticks=None) -> dict:
        n_ticks = ticks if n_ticks is None else n_ticks
        ingest = PodIngest()
        ingest.add_all(pods)  # pods are read-only to the solve: legs share
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0,
                           max_delta_fraction=0.5, materialized=True),
        )
        handle = session.solve(ingest, deferred=pipelined)
        if pipelined:
            consume(handle.result())
        else:
            consume(handle)
        reps: dict = {}
        tick_walls, overlaps = [], []
        ring = pipeline_mod.SolvePipeline()  # KC_PIPELINE_DEPTH deep
        for tick in range(n_ticks + 1):  # tick 0 warms; excluded from stats
            t_tick = time.perf_counter()
            churn(ingest, reps, tick)
            if pipelined:
                retired = ring.submit(
                    lambda: session.solve(ingest, deferred=True)
                )
                if retired is not None:
                    consume(retired)
                    rec = pipeline_mod.last_overlap()
                    total = rec["hidden_s"] + rec["exposed_s"]
                    if total > 0:
                        overlaps.append(rec["hidden_s"] / total)
            else:
                consume(session.solve(ingest))
            if tick > 0:
                tick_walls.append(time.perf_counter() - t_tick)
        for results in ring.drain():
            consume(results)
        return {
            "tick_s": statistics.mean(tick_walls) if tick_walls else 0.0,
            "overlap_efficiency": (
                round(statistics.median(overlaps), 3) if overlaps else None
            ),
            "signature": session.node_signature(),
            "modes": dict(session.mode_counts),
            "aggregates": session.aggregates(),
        }

    def repair_segment(n_ticks: int = 3) -> dict:
        """Steady-churn repairs through the pipelined loop: the donation
        ledger's measurement segment (and a warm-path sanity check)."""
        ingest = PodIngest()
        ingest.add_all(pods)
        session = IncrementalSolveSession(
            solver,
            FallbackPolicy(enabled=True, audit_interval=0,
                           max_delta_fraction=0.5),
        )
        session.solve(ingest, deferred=True).result()
        reps: dict = {}
        stats0 = pipeline_mod.stats()
        ring = pipeline_mod.SolvePipeline()
        for tick in range(n_ticks):
            churn(ingest, reps, tick)
            retired = ring.submit(
                lambda: session.solve(ingest, deferred=True)
            )
            if retired is not None:
                consume(retired)
        for results in ring.drain():
            consume(results)
        stats1 = pipeline_mod.stats()
        return {
            "donated": stats1["donated"] - stats0["donated"],
            "donation_reallocs": (
                stats1["donation_reallocs"] - stats0["donation_reallocs"]
            ),
            "modes": dict(session.mode_counts),
        }

    saved = os.environ.get("KC_PIPELINE")
    saved_wd = os.environ.get("KC_WATCHDOG")
    try:
        os.environ["KC_PIPELINE"] = "1"
        pipe = anchor_leg(True)
        repairs = repair_segment()
        # watchdog-overhead segment (tools/perfgate.py report_watchdog): the
        # same pipelined anchor loop, SAME tick count, with KC_WATCHDOG=0 —
        # the per-tick delta is what the monitored dispatch/fetch wrappers
        # cost the hot path (advisory budget: <2% of pipeline_warm_tick_s;
        # equal-length legs so tick variance doesn't masquerade as overhead)
        os.environ["KC_WATCHDOG"] = "0"
        unmonitored = anchor_leg(True)
        os.environ.pop("KC_WATCHDOG", None)
        # telemetry-overhead segment (tools/perfgate.py report_telemetry):
        # the same pipelined anchor loop with tracing FULLY enabled — the
        # per-tick delta against the trace-off ``pipe`` leg above (the
        # KC_TRACE=0 baseline of the A/B) is what span bookkeeping plus the
        # occupancy/overlap gauges cost the hot path (advisory: <2% of
        # pipeline_warm_tick_s; equal-length legs, same rationale as the
        # watchdog segment)
        from karpenter_core_tpu import tracing as tracing_mod
        was_tracing = tracing_mod.enabled()
        tracing_mod.enable()
        try:
            traced = anchor_leg(True)
        finally:
            if not was_tracing:
                tracing_mod.disable()
        os.environ["KC_PIPELINE"] = "0"
        serial = anchor_leg(False)
    finally:
        if saved is None:
            os.environ.pop("KC_PIPELINE", None)
        else:
            os.environ["KC_PIPELINE"] = saved
        if saved_wd is None:
            os.environ.pop("KC_WATCHDOG", None)
        else:
            os.environ["KC_WATCHDOG"] = saved_wd

    identical = serial["signature"] == pipe["signature"]
    serial_s, pipe_s = serial["tick_s"], pipe["tick_s"]
    unmon_s = unmonitored["tick_s"]
    # clamped at 0: a faster monitored leg is measurement noise, not
    # negative overhead
    watchdog_overhead = (
        round(max((pipe_s - unmon_s) / unmon_s, 0.0), 4) if unmon_s > 0
        else 0.0
    )
    traced_s = traced["tick_s"]
    telemetry_overhead = (
        round(max((traced_s - pipe_s) / pipe_s, 0.0), 4) if pipe_s > 0
        else 0.0
    )
    return {
        "pods": n_pods,
        "instance_types": n_its,
        "churn_fraction": churn_fraction,
        "ticks": ticks,
        "serial_tick_s": round(serial_s, 4),
        "pipelined_tick_s": round(pipe_s, 4),
        "speedup": round(serial_s / pipe_s, 2) if pipe_s > 0 else 0.0,
        "overlap_efficiency": pipe["overlap_efficiency"],
        "unmonitored_tick_s": round(unmon_s, 4),
        "watchdog_overhead_frac": watchdog_overhead,
        "traced_tick_s": round(traced_s, 4),
        "telemetry_overhead_frac": telemetry_overhead,
        "donated": repairs["donated"],
        "donation_reallocs": repairs["donation_reallocs"],
        "repair_modes": repairs["modes"],
        "identical_assignments": identical,
        "serial_modes": serial["modes"],
        "pipelined_modes": pipe["modes"],
        "scheduled": pipe["aggregates"]["scheduled"],
        "failed": pipe["aggregates"]["failed"],
        "nodes": pipe["aggregates"]["nodes"],
    }


def policy_line(n_pods: int = 2000, n_its: int = 24) -> dict:
    """Policy-objective benchmark (ISSUE 9 acceptance): the SAME feasibility
    solve decoded twice on a mixed spot/on-demand demo fleet with a skewed
    price sheet —

      first-fit    policy off: the launch hands the provider the full
                   viable set and lands on the FIRST compatible available
                   offering of the cheapest type (today's behavior),
                   emulated host-side per decision
      objective    policy on: ops.objective argmin-selects the cheapest
                   feasible (type, zone, capacity-type) cell per node and
                   pins the launch to it

    Feasibility is identical by construction (one solve, two decodes);
    reported are the two fleet costs, their delta (> 0 on this fleet: the
    cheap offerings hide in zones/capacity-types first-fit never reaches),
    and ``objective_s`` — the warm wall cost of the scoring stage itself,
    gated per-round by tools/perfgate.py."""
    from karpenter_core_tpu.cloudprovider import fake as fake_cp
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import objective as objective_ops
    from karpenter_core_tpu.policy import PolicyConfig
    from karpenter_core_tpu.policy import planes as policy_planes
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_pod, make_provisioner

    provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(n_its))
    # the spot market moved: zone-2 spot is cheap, but the provider's
    # first-compatible walk lands on zone-1 (listed first) at full price
    for it in provider.get_instance_types(None):
        provider.set_price(it.name, it.offerings[0].price * 0.6,
                           capacity_type="spot", zone="test-zone-2")
    provisioners = [make_provisioner(name="default")]
    config = PolicyConfig(enabled=True)
    solver = TPUSolver(provider, provisioners, policy=config)
    sizes = [{"cpu": "500m", "memory": "512Mi"}, {"cpu": 1, "memory": "2Gi"},
             {"cpu": "250m", "memory": "256Mi"}]
    ingest = PodIngest()
    ingest.add_all([make_pod(requests=sizes[i % len(sizes)]) for i in range(n_pods)])

    snapshot = solver.encode(ingest)
    prep = solver.prepare_encoded(snapshot)
    outputs = solver.run_prepared(prep)
    results_on = solver.decode(snapshot, outputs)

    # warm cost of the objective stage alone (first call pays its compile)
    planes = policy_planes.planes_of(snapshot)
    objective_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        objective_ops.select_for_state(
            outputs.state, planes, config, snapshot.capacity_types
        )
        objective_s = min(objective_s, time.perf_counter() - t0)

    # the first-fit decode of the SAME outputs: policy off, then emulate the
    # provider's landing per decision (cheapest type by its cheapest
    # in-requirements offering, then the first compatible available offering)
    solver.policy = None
    results_off = solver.decode(snapshot, outputs)
    it_by_name = {it.name: it for it in provider.get_instance_types(None)}

    def landed_price(decision) -> float:
        zones, cts = set(decision.zones), set(decision.capacity_types)

        def cheapest(it) -> float:
            prices = [
                o.price for o in it.offerings.available()
                if o.zone in zones and o.capacity_type in cts
            ]
            return min(prices) if prices else float("inf")

        options = sorted(
            (it_by_name[name] for name in decision.instance_type_names
             if name in it_by_name),
            key=cheapest,
        )
        for it in options:
            for off in it.offerings.available():
                if off.zone in zones and off.capacity_type in cts:
                    return off.price
        return 0.0

    firstfit_cost = sum(landed_price(d) for d in results_off.new_nodes)
    policy_cost = results_on.fleet_cost or 0.0
    pods_on = sorted(p.uid for d in results_on.new_nodes for p in d.pods)
    pods_off = sorted(p.uid for d in results_off.new_nodes for p in d.pods)
    return {
        "pods": n_pods,
        "instance_types": n_its,
        "nodes": len(results_on.new_nodes),
        "objective_s": round(objective_s, 4),
        "fleet_cost_firstfit": round(firstfit_cost, 4),
        "fleet_cost_policy": round(policy_cost, 4),
        "fleet_cost_delta": round(firstfit_cost - policy_cost, 4),
        # one solve, two decodes: placements must match exactly
        "identical_placements": pods_on == pods_off,
    }


def relax_line(n_pods: int = 4000, n_its: int = 24) -> dict:
    """Relax-vs-scan solver family benchmark (ISSUE 20 acceptance): the SAME
    large skewed-price fleet solved by both families —

      scan    the exact greedy-by-priority kernel (KC_SOLVER_MODE=scan)
      relax   the convex-relaxation family (karpenter_core_tpu/relax):
              projected-gradient placement + deterministic rounding + exact
              audit + scan repair (docs/RELAX.md)

    Reported: both warm solve walls (``relax_solve_s`` gated as its own
    perfgate stage), both policy fleet costs and their delta
    (``fleet_cost_delta`` = scan − relax, the acceptance yardstick: the
    relaxation must never cost MORE than greedy on this fleet), and
    ``rounded_violations`` — placements the exact audit rejected (always
    repaired or fallen back, never shipped).  ``report_relax`` warns when the
    delta goes negative.  Env: KC_BENCH_RELAX=0 skips; KC_BENCH_RELAX_PODS /
    KC_BENCH_RELAX_ITS size it."""
    from karpenter_core_tpu.cloudprovider import fake as fake_cp
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.policy import PolicyConfig
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_pod, make_provisioner

    def leg(mode: str):
        provider = fake_cp.FakeCloudProvider(fake_cp.instance_types(n_its))
        # the skew: zone-2 spot is 40% off — the optimum hides off the
        # provider's first-listed offerings
        for it in provider.get_instance_types(None):
            provider.set_price(it.name, it.offerings[0].price * 0.6,
                               capacity_type="spot", zone="test-zone-2")
        config = PolicyConfig(enabled=True, solver_mode=mode)
        solver = TPUSolver(
            provider, [make_provisioner(name="default")], policy=config
        )
        # ONE pod size: the bench isolates the price-skew dimension (what the
        # relaxation is for).  Mixed sizes shift the comparison onto greedy's
        # cross-class bin packing, where a per-class LP concedes O(1 tail
        # node) by construction (docs/RELAX.md "what relax does not model") —
        # tests/test_relax.py covers mixed-size CORRECTNESS instead.
        ingest = PodIngest()
        ingest.add_all(
            [make_pod(requests={"cpu": "500m", "memory": "512Mi"})
             for _ in range(n_pods)]
        )
        snapshot = solver.encode(ingest)
        prep = solver.prepare_encoded(snapshot)
        solve_s = float("inf")
        outputs = None
        for _ in range(3):  # first lap pays the compile; report the warm min
            t0 = time.perf_counter()
            outputs = solve_ops.sync_outputs(solver.run_prepared(prep))
            solve_s = min(solve_s, time.perf_counter() - t0)
        results = solver.decode(snapshot, outputs)
        return solver, results, solve_s

    scan_solver, scan_results, scan_solve_s = leg("scan")
    relax_solver, relax_results, relax_solve_s = leg("relax")
    relax_stats = getattr(relax_solver, "last_relax_stats", None) or {}
    scan_cost = scan_results.fleet_cost or 0.0
    relax_cost = relax_results.fleet_cost or 0.0
    return {
        "pods": n_pods,
        "instance_types": n_its,
        "relax_solve_s": round(relax_solve_s, 4),
        "scan_solve_s": round(scan_solve_s, 4),
        # the routed outcome ("relax", or "relax-fallback:<reason>" when a
        # gate declined the batch — the numbers below then measure the scan
        # twice, which report_relax surfaces)
        "relax_mode": getattr(relax_solver, "last_solve_mode", "scan"),
        "fleet_cost_scan": round(scan_cost, 4),
        "fleet_cost_relax": round(relax_cost, 4),
        # acceptance yardstick (policy layer's convention: positive = the
        # relaxation found a fleet at least as cheap as greedy)
        "fleet_cost_delta": round(scan_cost - relax_cost, 4),
        "rounded_violations": int(relax_stats.get("rounded_violations", 0)),
        "relax_iters": int(relax_stats.get("iters", 0)),
        "relax_leftover": int(relax_stats.get("leftover", 0)),
        "scan_nodes": len(scan_results.new_nodes),
        "relax_nodes": len(relax_results.new_nodes),
        "relax_failed": len(relax_results.failed_pods),
    }


def sharded_probe(n_pods: int, n_its: int, mesh_devices: int) -> None:
    """Child of ``sharded_line``: solve ONE fleet at ONE mesh size and print
    a JSON line.  Runs in its own process because the virtual device count
    (XLA_FLAGS --xla_force_host_platform_device_count) is fixed at backend
    init — the top level pins the env before spawning.  ``mesh_devices`` <= 1
    measures the production single-device path (mesh off), the scaling
    baseline the sharded sizes compare against."""
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.utils import compilecache

    compilecache.enable()
    solver, pods = build_inputs(n_pods, n_its, n_provisioners=5)
    from karpenter_core_tpu.models import columnar as columnar_mod

    columnar_mod._sig_key_impl()  # resolve (maybe build) the fast key untimed
    ingest = PodIngest()
    ingest.add_all(pods)
    snapshot = solver.encode(ingest)
    t0 = time.perf_counter()
    out = solve_ops.sync_outputs(solve_ops.solve(snapshot))
    cold_s = time.perf_counter() - t0
    solve_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        out = solve_ops.sync_outputs(solve_ops.solve(snapshot))
        solve_s = min(solve_s, time.perf_counter() - t0)
    results = solver.decode(snapshot, out)
    print(json.dumps({
        "mesh_devices": mesh_devices,
        **_device_stamp(),
        "solve_s": round(solve_s, 4),
        "cold_s": round(cold_s, 2),
        "scheduled": sum(len(n.pods) for n in results.new_nodes),
        "failed": len(results.failed_pods),
        "nodes": len(results.new_nodes),
    }))


def tenant_line(n_tenants: int = 8, pods_per_tenant: int = 256) -> dict:
    """Multi-tenant coalescing benchmark (ISSUE 12, docs/SERVICE.md): N
    synthetic tenants whose snapshots share one shape bucket (the production
    regime — many clusters, few distinct pod shapes), solved two ways:

      serial    N solo dispatches of the same warm executable, one per
                tenant — what N uncoalesced requests cost the device
      batched   ONE vmapped dispatch over the tenant-stacked planes
                (service.tenant.BatchCoalescer._run_batched)

    Reports both throughputs, the speedup, and the serial path's p99
    per-solve latency; tools/perfgate.py prints an advisory report and warns
    when batching stops paying (speedup <= 1).  Env: KC_BENCH_TENANTS,
    KC_BENCH_TENANT_PODS; KC_BENCH_TENANT=0 skips the line."""
    from karpenter_core_tpu.cloudprovider import fake as fake_cp
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.service.tenant import BatchCoalescer, bucket_key
    from karpenter_core_tpu.soak.slo import percentile
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_pod, make_provisioner

    provider = fake_cp.FakeCloudProvider()
    provisioners = [make_provisioner()]
    sizes = [{"cpu": "500m"}, {"cpu": "250m"}, {"cpu": 1, "memory": "1Gi"}]
    preps = []
    solvers = []
    for t in range(n_tenants):
        solver = TPUSolver(provider, provisioners)
        ingest = PodIngest()
        ingest.add_all([
            make_pod(requests=sizes[(t + i) % len(sizes)])
            for i in range(pods_per_tenant)
        ])
        snapshot = solver.encode(ingest)
        preps.append(solver.prepare_encoded(snapshot))
        solvers.append(solver)
    buckets = {bucket_key(p) for p in preps}
    # warm both executables: compiles stay outside the timed region
    solve_ops.sync_outputs(solvers[0].run_prepared(preps[0]))
    BatchCoalescer._run_batched(preps)

    serial_s = float("inf")
    lat: list = []
    for _ in range(3):
        lats = []
        t0 = time.perf_counter()
        for solver, prep in zip(solvers, preps):
            t1 = time.perf_counter()
            solve_ops.sync_outputs(solver.run_prepared(prep))
            lats.append(time.perf_counter() - t1)
        total = time.perf_counter() - t0
        if total < serial_s:
            serial_s, lat = total, lats
    from karpenter_core_tpu.utils import compilecache

    compilecache.reset_occupancy()  # isolate the timed coalesced dispatches
    batched_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        BatchCoalescer._run_batched(preps)  # device_gets internally: synced
        batched_s = min(batched_s, time.perf_counter() - t0)
    occupancy = compilecache.occupancy_stats()
    p99 = percentile(lat, 0.99)  # the soak SLO engine's nearest-rank

    # durable-session overhead (ISSUE-13, docs/SERVICE.md): the serial loop
    # again, with a per-solve journal append exactly like the tenant handler
    # issues (enqueue on the hot path, framing/fsync on the writer thread).
    # perfgate report_recovery warns past 5% added p99.
    import tempfile

    import msgpack

    from karpenter_core_tpu.apis import codec
    from karpenter_core_tpu.service.journal import SessionJournal

    req_bytes = msgpack.packb({
        "podClasses": [{
            "pod": codec.pod_to_dict(make_pod(requests=sizes[0])),
            "count": pods_per_tenant,
        }],
        "tenant": {"id": "bench"},
    })
    state = {
        "version": 1, "supply": "0" * 64, "planes": {},
        "aggregates": {"scheduled": pods_per_tenant, "failed": 0, "nodes": 1},
        "signature": "0" * 64, "delta_ticks": 0,
    }
    lat_j: list = []
    with tempfile.TemporaryDirectory() as journal_dir:
        journal = SessionJournal(journal_dir, checkpoint_every=0)
        journal.start()
        serial_journal_s = float("inf")
        for _ in range(3):
            lats = []
            t0 = time.perf_counter()
            for tseq, (solver, prep) in enumerate(zip(solvers, preps)):
                t1 = time.perf_counter()
                solve_ops.sync_outputs(solver.run_prepared(prep))
                journal.append_solve(
                    tenant=f"bench-{tseq}", kind="anchor", tseq=0, version=1,
                    client_supply=None, state=state, request=req_bytes,
                )
                lats.append(time.perf_counter() - t1)
            total = time.perf_counter() - t0
            if total < serial_journal_s:
                serial_journal_s, lat_j = total, lats
        journal.close(checkpoint=False)
    p99_j = percentile(lat_j, 0.99)
    return {
        "tenants": n_tenants,
        "pods_per_tenant": pods_per_tenant,
        "shape_buckets": len(buckets),
        "serial_s": round(serial_s, 4),
        "batched_s": round(batched_s, 4),
        "speedup": round(serial_s / batched_s, 2) if batched_s > 0 else None,
        "serial_solves_per_s": round(n_tenants / serial_s, 2),
        "batched_solves_per_s": round(n_tenants / batched_s, 2),
        "p99_serial_solve_s": round(p99, 4),
        "p99_serial_journal_s": round(p99_j, 4),
        "journal_overhead_fraction": (
            round(p99_j / p99 - 1.0, 4) if p99 > 0 else None
        ),
        "batch_occupancy": occupancy,
    }


def fusion_line(tenant_counts=(2, 4, 8), pods_per_tenant: int = 256) -> dict:
    """Generalized solve fusion benchmark (PR 18, docs/SERVICE.md "Solve
    fusion"): REPAIR dispatches from N tenants under steady churn, solved
    two ways —

      serial   N solo warm-carry repair dispatches, one per tenant
      fused    ONE vmapped dispatch over the tenant-stacked repair planes
               (warm_carry + repair_plan leaves batched alongside the class
               planes)

    at each count in ``tenant_counts``, with a bit-level parity check of the
    fused per-tenant slices against the solo outputs at the deepest count.
    Capture runs with KC_DELTA_WINDOW=0 (full-width repairs) so every
    tenant's repair lands in ONE shape bucket regardless of which rows
    churned; windowed-fusion parity is pinned by tests/test_solve_fusion.py.

    Also sweeps KC_BUCKET_QUANTIZE over a mixed-size tenant population:
    distinct executable buckets and batch occupancy vs padded FLOPs, default
    ladder against the coarser power-of-two ladder.  tools/perfgate.py gates
    ``fusion_repair_solve_s`` and warns when fused throughput drops under
    2x serial at the deepest count.  Env: KC_BENCH_FUSION=0 skips,
    KC_BENCH_FUSION_TENANTS, KC_BENCH_FUSION_PODS."""
    import copy as copy_mod
    import random

    import numpy as np

    from karpenter_core_tpu.apis.objects import new_uid
    from karpenter_core_tpu.cloudprovider import fake as fake_cp
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.service.tenant import BatchCoalescer, bucket_key
    from karpenter_core_tpu.solver.incremental import (
        MODE_DELTA,
        FallbackPolicy,
        IncrementalSolveSession,
    )
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_pod, make_provisioner
    from karpenter_core_tpu.utils import compilecache

    sizes = [{"cpu": "500m"}, {"cpu": "250m"}, {"cpu": 1, "memory": "1Gi"}]
    n_max = max(tenant_counts)
    provider = fake_cp.FakeCloudProvider()
    provisioners = [make_provisioner()]

    def churn(ingest, rng, fraction=0.05):
        members = ingest.class_members()
        uids = [u for us in members.values() for u in us]
        for i, uid in enumerate(
            rng.sample(uids, max(int(len(uids) * fraction), 1))
        ):
            rep = copy_mod.deepcopy(ingest.get(uid))
            ingest.remove(uid)
            rep.metadata.name = f"churn-{i}"
            rep.metadata.uid = new_uid()
            rep.spec.node_name = ""
            ingest.add(rep)

    saved_window = os.environ.get("KC_DELTA_WINDOW")
    os.environ["KC_DELTA_WINDOW"] = "0"
    captured = []  # (solver, prep, kw) of each tenant's repair dispatch
    try:
        for t in range(n_max):
            solver = TPUSolver(provider, provisioners)
            holder = {}

            def hook(prep, _solver=solver, _holder=holder, **kw):
                # the tenant service's dispatch shape: the session already
                # passes donate_carry=False to hooked repairs (the coalescer
                # may stack copies of the carry)
                if kw.get("warm_carry") is not None:
                    _holder["repair"] = (prep, dict(kw))
                return _solver.run_prepared(prep, **kw)

            session = IncrementalSolveSession(
                solver,
                FallbackPolicy(enabled=True, audit_interval=0,
                               max_delta_fraction=0.9),
                run_prepared=hook,
            )
            ingest = PodIngest()
            ingest.add_all([
                make_pod(requests=sizes[(t + i) % len(sizes)])
                for i in range(pods_per_tenant)
            ])
            session.solve(ingest)
            churn(ingest, random.Random(17 + t))
            session.solve(ingest)
            if session.last_mode != MODE_DELTA or "repair" not in holder:
                raise RuntimeError(
                    f"tenant {t} repair not captured "
                    f"({session.last_mode}/{session.last_reason})"
                )
            prep, kw = holder["repair"]
            captured.append((solver, prep, kw))
    finally:
        if saved_window is None:
            os.environ.pop("KC_DELTA_WINDOW", None)
        else:
            os.environ["KC_DELTA_WINDOW"] = saved_window

    buckets = {bucket_key(p, kw) for _s, p, kw in captured}
    if len(buckets) != 1:
        raise RuntimeError(
            f"repair dispatches split {len(buckets)} shape buckets"
        )

    def run_solo(solver, prep, kw):
        # the captured kw carries donate_carry=False from the hooked session
        return solver.run_prepared(prep, **kw)

    # bit-level parity at the deepest count before anything is timed
    import jax

    solo_outputs = [run_solo(*c) for c in captured]
    fused_outputs = BatchCoalescer._run_batched(
        [p for _s, p, _kw in captured], kws=[kw for *_ , kw in captured]
    )
    for t, (solo_out, fused_out) in enumerate(
        zip(solo_outputs, fused_outputs)
    ):
        solo_leaves = jax.tree_util.tree_leaves(jax.device_get(solo_out))
        fused_leaves = jax.tree_util.tree_leaves(jax.device_get(fused_out))
        for a, b in zip(solo_leaves, fused_leaves):
            if not np.array_equal(np.asarray(a), np.asarray(b)):
                raise RuntimeError(f"fused repair diverged for tenant {t}")

    compilecache.reset_occupancy()
    repair = {}
    for n in sorted(tenant_counts):
        sub = captured[:n]
        preps = [p for _s, p, _kw in sub]
        kws = [kw for *_ , kw in sub]
        BatchCoalescer._run_batched(preps, kws=kws)  # compile outside timing
        serial_s = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for c in sub:
                solve_ops.sync_outputs(run_solo(*c))
            serial_s = min(serial_s, time.perf_counter() - t0)
        fused_s = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            BatchCoalescer._run_batched(preps, kws=kws)
            fused_s = min(fused_s, time.perf_counter() - t0)
        repair[str(n)] = {
            "serial_s": round(serial_s, 4),
            "fused_s": round(fused_s, 4),
            "speedup": round(serial_s / fused_s, 2) if fused_s > 0 else None,
        }
    occupancy = compilecache.occupancy_stats()

    # KC_BUCKET_QUANTIZE sweep: tenants with MIXED distinct-class counts
    # (the class axis is what actually varies across real tenants — pod
    # counts collapse into classes), distinct executable buckets +
    # occupancy-vs-padded-FLOPs under each padding ladder.  Pairs like
    # (10, 14) straddle a default 1.5x rung (12) and its next power of two
    # (16), so the coarser power-of-two ladder provably merges buckets.
    mixed = [5, 7, 10, 14, 20, 28]

    def quant_leg(enabled: bool) -> dict:
        saved_q = os.environ.get("KC_BUCKET_QUANTIZE")
        os.environ["KC_BUCKET_QUANTIZE"] = "1" if enabled else "0"
        try:
            groups: dict = {}
            for t, n_classes in enumerate(mixed):
                solver = TPUSolver(provider, provisioners)
                ingest = PodIngest()
                ingest.add_all([
                    make_pod(requests={"cpu": f"{100 + 25 * j}m"})
                    for j in range(n_classes)
                    for _ in range(12)
                ])
                prep = solver.prepare_encoded(solver.encode(ingest))
                groups.setdefault(bucket_key(prep), []).append(prep)
            compilecache.reset_occupancy()
            for preps in groups.values():
                BatchCoalescer._run_batched(preps)
            stats = compilecache.occupancy_stats()
            padded_flops = sum(s["padded_flops"] for s in stats.values())
            real = sum(s["real_rows"] for s in stats.values())
            padded = sum(s["padded_rows"] for s in stats.values())
            dispatches = sum(s["dispatches"] for s in stats.values())
            return {
                "buckets": len(groups),
                # batch occupancy: how many tenants each vmapped dispatch
                # carries — the number quantization exists to raise
                "tenants_per_dispatch": (
                    round(len(mixed) / dispatches, 2) if dispatches else None
                ),
                # row-level padding waste inside those dispatches — the
                # FLOPs price paid for the coarser ladder
                "occupancy_ratio": (
                    round(real / padded, 4) if padded else None
                ),
                "padded_flops": round(padded_flops, 1),
            }
        finally:
            if saved_q is None:
                os.environ.pop("KC_BUCKET_QUANTIZE", None)
            else:
                os.environ["KC_BUCKET_QUANTIZE"] = saved_q

    quant_default = quant_leg(False)
    quant_on = quant_leg(True)

    deepest = repair[str(n_max)]
    return {
        "tenant_counts": sorted(tenant_counts),
        "pods_per_tenant": pods_per_tenant,
        "repair": repair,
        "fusion_repair_solve_s": deepest["fused_s"],
        "fusion_repair_serial_s": deepest["serial_s"],
        "fusion_speedup": deepest["speedup"],
        "parity_ok": True,
        "batch_occupancy": occupancy,
        "quantize": {
            "mixed_pod_counts": mixed,
            "default": quant_default,
            "quantized": quant_on,
            "bucket_reduction": quant_default["buckets"] - quant_on["buckets"],
        },
    }


def fleet_line(chains=(1, 8, 64), pods: int = 128) -> dict:
    """Fleet failover cost (ISSUE-17, docs/FLEET.md): how fast an adopting
    replica restores an evicted tenant's warm lineage, measured both ways at
    1/8/64-delta chain depths:

      checkpoint  ONE deserialize of the tensor-level session checkpoint
                  (fleet/checkpoint.py) + the never-trust digest verify
      replay      the peer-journal fallback rung: re-solving the anchor and
                  every delta from the dead replica's journal chain

    A real replica serves the chain over the wire, then two fresh services
    adopt it via the actual failover ladder (``_fleet_adopt``) — one with
    the checkpoint present, one with it dropped.  Both restored lineages
    must answer the NEXT delta bit-identically; tools/perfgate.py gates
    ``fleet_restore_64_s`` and report_fleet warns when the checkpoint path
    stops beating replay by ≥5x at 64 deltas.  Env: KC_BENCH_FLEET=0 skips,
    KC_BENCH_FLEET_CHAINS / KC_BENCH_FLEET_PODS size it."""
    import hashlib
    import shutil
    import tempfile

    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.fleet import FleetLocal, FleetMap
    from karpenter_core_tpu.service.snapshot_channel import (
        SnapshotSolverClient,
        serve,
    )
    from karpenter_core_tpu.service.tenant import TenantConfig
    from karpenter_core_tpu.testing import make_pod, make_provisioner

    config = TenantConfig(
        rate_per_s=1000.0, burst=1000, max_inflight=16,
        batch_window_s=0.0, max_batch=4,
    )
    fleet_map = FleetMap.parse("r1=pending:0,r2=pending:0,r3=pending:0")

    def canon(resp: dict) -> str:
        """Canonical digest of a response body; the one-shot recovery echo
        and coalescing flag are load-dependent, everything else must match."""
        body = dict(resp)
        echo = dict(body.get("tenant") or {})
        echo.pop("recovered", None)
        echo.pop("batched", None)
        body["tenant"] = echo
        return hashlib.sha256(
            json.dumps(body, sort_keys=True, default=repr).encode()
        ).hexdigest()

    def solve(client, count: int, version: int) -> dict:
        return client.solve_tenant_classes(
            [(make_pod(requests={"cpu": "500m"}), count)],
            [make_provisioner()],
            tenant={"id": "bench", "sessionVersion": version},
        )

    rows = []
    for n_deltas in chains:
        directory = tempfile.mkdtemp(prefix="kc-bench-fleet-")
        servers, clients = [], []

        def boot(rid: str):
            fleet = FleetLocal(
                directory=directory, replica_id=rid, fleet_map=fleet_map,
                ckpt_every=1,
            )
            server, port = serve(
                FakeCloudProvider(), tenant_config=config, fleet=fleet,
                journal_dir=os.path.join(directory, "journals", rid),
            )
            servers.append(server)
            return server, port

        try:
            # replica r1 serves anchor + N deltas, then dies (SIGKILL shape:
            # no drain checkpoint — shutdown() only flushes the wal, so the
            # replay rung sees exactly what a dead process leaves on disk)
            server_a, port_a = boot("r1")
            client_a = SnapshotSolverClient(f"127.0.0.1:{port_a}")
            clients.append(client_a)
            version = 0
            for tick in range(n_deltas + 1):
                version = solve(
                    client_a, pods + tick, version
                )["tenant"]["sessionVersion"]
            server_a.stop(grace=0)
            server_a.kc_service.shutdown()

            # r2 adopts WARM: one checkpoint deserialize + digest verify
            server_b, port_b = boot("r2")
            svc_b = server_b.kc_service
            entry_b = svc_b.tenants.restore_entry("bench")
            t0 = time.perf_counter()
            warm_ok = svc_b._fleet_adopt("bench", entry_b, version)
            ckpt_restore_s = time.perf_counter() - t0

            # r3 adopts with the checkpoint gone: the peer-journal replay
            # rung re-solves the whole chain (run BEFORE r2's next solve so
            # r2's journal holds no competing chain for the tenant)
            server_c, port_c = boot("r3")
            svc_c = server_c.kc_service
            svc_c._ckpt.drop("bench")
            entry_c = svc_c.tenants.restore_entry("bench")
            t0 = time.perf_counter()
            replay_ok = svc_c._fleet_adopt("bench", entry_c, version)
            replay_restore_s = time.perf_counter() - t0

            # both restored lineages answer the next delta bit-identically
            bit_identical = None
            if warm_ok and replay_ok:
                client_b = SnapshotSolverClient(f"127.0.0.1:{port_b}")
                client_c = SnapshotSolverClient(f"127.0.0.1:{port_c}")
                clients += [client_b, client_c]
                next_count = pods + n_deltas + 1
                bit_identical = canon(
                    solve(client_b, next_count, version)
                ) == canon(solve(client_c, next_count, version))
            rows.append({
                "deltas": n_deltas,
                "checkpoint_restore_s": round(ckpt_restore_s, 4),
                "replay_restore_s": round(replay_restore_s, 4),
                "speedup": (
                    round(replay_restore_s / ckpt_restore_s, 2)
                    if ckpt_restore_s > 0 else None
                ),
                "warm_ok": bool(warm_ok),
                "replay_ok": bool(replay_ok),
                "bit_identical": bit_identical,
            })
        finally:
            for client in clients:
                client.close()
            for server in servers:
                server.stop(grace=0)
                try:
                    server.kc_service.shutdown()
                except Exception:  # noqa: BLE001 - already shut down
                    pass
            shutil.rmtree(directory, ignore_errors=True)
    return {"pods": pods, "restores": rows}


def run_child(args, env=None, timeout_s: float = 1800.0) -> dict:
    """Run one measuring child of this bench to completion and parse the JSON
    line it prints last.  The ONLY place this file starts a process, and only
    a caller that never touches JAX may use it (the top level here;
    tools/perfgate.py runs the whole bench through it) — children run
    strictly one after another, so the chip has one owner at a time.  A
    dead, hung or garbled child raises."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + [str(a) for a in args],
        capture_output=True, text=True, timeout=timeout_s, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    sys.stderr.write(proc.stderr)
    lines = proc.stdout.strip().splitlines()
    try:
        rec = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        rec = None
    if not isinstance(rec, dict):
        raise RuntimeError(
            f"bench child {' '.join(map(str, args))!r} printed no JSON line "
            f"(rc={proc.returncode})"
        )
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench child {' '.join(map(str, args))!r} failed "
            f"(rc={proc.returncode}): {json.dumps(rec)[:300]}"
        )
    return rec


def sharded_line(platform: str, device_count: int) -> dict:
    """The mesh scaling study (docs/KERNEL_PERF.md "Layer 5"): the SAME fleet
    solved at mesh sizes 1/2/4/8 (KC_BENCH_SHARDED_SIZES, trimmed to what the
    host allows), one child per size run in sequence by the top level,
    reporting per-size ``solve_s`` and scaling efficiency (t1 / (k * tk)).
    ``platform`` / ``device_count`` are the main run's stamp: on an
    accelerator each child meshes the first K of its devices
    (KC_SOLVER_MESH_DEVICES); on CPU each gets a virtual device pool (fixed at
    backend init, hence a process per size).  Fleet: KC_BENCH_SHARDED_PODS
    (default 100k) pods × KC_BENCH_SHARDED_ITS (default 2k) instance types —
    the ROADMAP scale point where the catalog stops fitting one device's
    comfortable working set.  Placements are asserted identical across sizes
    (the sharded solve's bit-parity contract), so a scaling win can never
    hide a behavior drift."""
    sizes = []
    for raw in os.environ.get("KC_BENCH_SHARDED_SIZES", "1,2,4,8").split(","):
        try:
            sizes.append(max(int(raw), 1))
        except ValueError:
            continue
    sizes = sorted(set(sizes))
    n_pods = int(os.environ.get("KC_BENCH_SHARDED_PODS", "100000"))
    n_its = int(os.environ.get("KC_BENCH_SHARDED_ITS", "2000"))

    env = dict(os.environ)
    if platform == "cpu":
        flags = [
            f for f in env.get("XLA_FLAGS", "").split()
            if not f.startswith("--xla_force_host_platform_device_count")
        ]
        env["XLA_FLAGS"] = " ".join(
            flags + [f"--xla_force_host_platform_device_count={max(sizes)}"]
        )
    else:
        # real accelerator: the device pool is whatever the backend exposes —
        # trim oversized sizes instead of letting KC_SOLVER_MESH_DEVICES cap
        # them silently (a k=8 row measured on 4 devices would report a
        # wrong-by-2x efficiency in the gated scaling line)
        dropped = [k for k in sizes if k > device_count]
        sizes = [k for k in sizes if k <= device_count] or [1]
        if dropped:
            print(
                f"bench: sharded_line dropping mesh sizes {dropped} — the "
                f"backend exposes {device_count} device(s)", file=sys.stderr,
            )

    per_size = []
    signature = None
    for k in sizes:
        child = dict(env)
        child["KC_SOLVER_MESH"] = "1" if k > 1 else "0"
        child["KC_SOLVER_MESH_DEVICES"] = str(k)
        try:
            rec = run_child(
                [n_pods, n_its, "--sharded-probe", k], env=child
            )
        except Exception as e:  # noqa: BLE001 - reported per size; main() exits non-zero
            rec = {"mesh_devices": k, "error": f"{type(e).__name__}: {e}"[:300]}
        per_size.append(rec)
        if "error" not in rec:
            sig = (rec["scheduled"], rec["failed"], rec["nodes"])
            if signature is None:
                signature = sig
            elif sig != signature:
                rec["placement_drift"] = True

    ok = {r["mesh_devices"]: r for r in per_size if "error" not in r}
    line = {
        "n_pods": n_pods,
        "n_instance_types": n_its,
        "sizes": per_size,
        "identical_placements": all(
            not r.get("placement_drift") for r in per_size if "error" not in r
        ),
    }
    failed = [r["mesh_devices"] for r in per_size if "error" in r]
    if failed:
        line["error"] = f"mesh sizes {failed} failed"
    if 1 in ok:
        t1 = ok[1]["solve_s"]
        line["solve_s_1dev"] = t1
        for k, rec in ok.items():
            if k > 1:
                rec["speedup"] = round(t1 / rec["solve_s"], 2) if rec["solve_s"] else 0.0
                rec["efficiency"] = round(t1 / (k * rec["solve_s"]), 3) if rec["solve_s"] else 0.0
        best = min((rec["solve_s"], k) for k, rec in ok.items())
        line["solve_s_best"] = best[0]
        line["best_devices"] = best[1]
        line["speedup_best"] = round(t1 / best[0], 2) if best[0] else 0.0
    return line


def _traced_solve(solver, pods) -> dict:
    """One fully-traced ingest → encode → dispatch → solve → decode →
    materialize pass; returns {"trace_id", "stages"} for the bench line."""
    from karpenter_core_tpu import tracing
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops

    was_enabled = tracing.enabled()
    tracing.enable()
    try:
        with tracing.span("bench.solve", pods=len(pods)):
            ingest = PodIngest()
            ingest.add_all(pods)
            snapshot = solver.encode(ingest)
            out = solve_ops.solve(snapshot)
            results = solver.decode(snapshot, out)
            if results.new_nodes:
                results.new_nodes[0].instance_type_names  # noqa: B018 - materialize
        trace = tracing.TRACE_STORE.last(1)[-1]
        dump_path = os.environ.get("KC_BENCH_TRACE", "")
        if dump_path:
            with open(dump_path, "w") as f:
                json.dump(tracing.to_chrome([trace]), f)
        return {
            "trace_id": trace.trace_id,
            "stages": {
                name: round(duration, 4)
                for name, duration in sorted(trace.stage_durations().items())
            },
        }
    finally:
        if not was_enabled:
            tracing.disable()


def _register_compile_counter() -> dict:
    """Count XLA backend compiles for the life of this process (the runtime
    side of kcanalyze's retrace-budget pass: the manifest records how many
    compiles a cold bench is EXPECTED to pay, and the observed count ties
    the static budget to the measured trajectory in the bench records)."""
    import jax.monitoring

    counter = {"n": 0}

    def _on_event(event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            counter["n"] += 1

    jax.monitoring.register_event_duration_secs_listener(_on_event)
    return counter


def _retrace_manifest() -> dict:
    from karpenter_core_tpu.analysis.manifest import load_retrace_manifest

    return load_retrace_manifest()


def _phase(failed: list, name: str, fn):
    """Run one optional bench phase.  A failure is reported in place (the
    rest of the run is still worth printing) AND recorded in ``failed`` —
    the bench exits non-zero when any phase failed."""
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 - reported, then fails the exit code
        import traceback

        traceback.print_exc(file=sys.stderr)
        failed.append(name)
        return {"error": f"{type(e).__name__}: {e}"[:300]}


def measure(n_pods: int, n_its: int) -> int:
    """The main measuring run (``--measure``): every in-process phase, in the
    one process that holds the chip.  Starts no child.  Prints the bench
    line; returns the exit code (non-zero when a phase failed)."""
    compile_counter = _register_compile_counter()
    failed: list = []

    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.utils import compilecache

    compilecache.enable()
    # honesty check for the first-boot number: a prior run's disk caches turn
    # this process's "first boot" into a restart, so record which it was
    cache_warm_at_start = any(
        f.endswith(".stablehlo") for f in _listdir(compilecache.cache_dir())
    )
    solver, pods = build_inputs(n_pods, n_its, n_provisioners=5)
    from karpenter_core_tpu.models import columnar as columnar_mod

    columnar_mod._sig_key_impl()  # resolve (maybe build) the fast key untimed

    # first-boot cold: informer ingestion (per-pod, once per pod lifetime) +
    # encode + trace + compile + solve + decode, with empty or stale caches.
    # ingest_s is the classification leg alone (the O(pods) host loop);
    # classify_s/planes_s/upload_s split the whole host ingest pipeline below.
    # hang coverage: the cold solve (and every later stage, via the monitored
    # run_prepared/fetch sites) rides the watchdog; timeouts land in
    # ``detail.watchdog_timeouts`` and fail the run instead of a silent
    # stuck bench
    from karpenter_core_tpu.utils import watchdog as watchdog_mod

    watchdog_mod.reset_stats()
    t0 = time.perf_counter()
    ingest = PodIngest()
    ingest.add_all(pods)
    ingest_s = time.perf_counter() - t0
    classify_s = ingest_s
    snapshot = solver.encode(ingest)
    out = watchdog_mod.run("bench.solve", solve_ops.solve, snapshot)
    results = solver.decode(snapshot, out)
    first_boot_cold_s = time.perf_counter() - t0

    # warm end-to-end (compile cached): the steady-state reconcile cost —
    # classes come from the incrementally-maintained ingest, as the informer
    # path maintains them in production; best of 3 to absorb link jitter
    # no explicit device sync between solve and decode: decode's batched
    # fetch is the natural synchronization point, so the pipeline pays one
    # device→host round trip instead of two.  t2-t1 is therefore dispatch only;
    # t3-t2 (solve_decode_s) carries device compute + transfer + expansion.
    warm_s = encode_s = dispatch_s = solve_decode_s = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        snapshot = solver.encode(ingest)
        t1 = time.perf_counter()
        out = watchdog_mod.run("bench.solve", solve_ops.solve, snapshot)
        t2 = time.perf_counter()
        results = solver.decode(snapshot, out)
        t3 = time.perf_counter()
        if t3 - t0 < warm_s:
            warm_s = t3 - t0
            encode_s, dispatch_s, solve_decode_s = t1 - t0, t2 - t1, t3 - t2
    # deferred decode cost: first touch of a node's planes pulls them across
    # the device link (launch path); reported so the lazy split is honest
    t0 = time.perf_counter()
    if results.new_nodes:
        results.new_nodes[0].instance_type_names  # noqa: B018 - forces the fetch
    materialize_s = time.perf_counter() - t0

    # ingest sub-stage split (ISSUE 11): classify_s (the per-pod O(pods)
    # classification, == ingest_s), planes_s (warm plane construction — the
    # delta-consuming encode path), upload_s (warm prepare: bucket pad +
    # upload staging, prep-reuse active).  Each gates independently in
    # tools/perfgate.py so a classify regression can't hide inside a flat
    # ingest number (and vice versa).
    planes_s = upload_s = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        snapshot = solver.encode(ingest)
        planes_s = min(planes_s, time.perf_counter() - t0)
        t0 = time.perf_counter()
        solver.prepare_encoded(snapshot)
        upload_s = min(upload_s, time.perf_counter() - t0)

    # solve vs decode split: solve_decode_s above is deliberately fused (no
    # sync between solve and decode saves a round trip on the headline
    # path), which hides how the time divides.  ONE extra pass with an explicit device sync between the
    # stages attributes device compute to solve_s and transfer + host
    # expansion to decode_s; tools/perfgate.py gates each independently so
    # the pipelining work has a stable baseline.
    t0 = time.perf_counter()
    out = watchdog_mod.run("bench.solve", solve_ops.solve, snapshot)
    solve_ops.sync_outputs(out)
    t1 = time.perf_counter()
    solver.decode(snapshot, out)
    t2 = time.perf_counter()
    solve_s, decode_s = t1 - t0, t2 - t1

    # per-stage trace: ONE extra solve with tracing on (span close syncs the
    # device, so stage attribution is exact) — run OUTSIDE the timed loop so
    # the sync points can't perturb the headline number.  The trace rides the
    # output line; KC_BENCH_TRACE=path additionally dumps Chrome trace-event
    # JSON loadable in chrome://tracing / Perfetto.
    trace_detail = _phase(failed, "trace", lambda: _traced_solve(solver, pods))

    # steady-state churn: the incremental warm-start repair vs the full
    # re-solve, on the SAME resident population (docs/INCREMENTAL.md); the
    # two per-tick stage medians gate independently in tools/perfgate.py.
    # KC_BENCH_CHURN=0 skips; fraction/ticks via KC_BENCH_CHURN_*.
    churn = None
    if os.environ.get("KC_BENCH_CHURN", "1") != "0":
        churn = _phase(failed, "churn", lambda: churn_line(
            solver, ingest,
            churn_fraction=float(os.environ.get("KC_BENCH_CHURN_FRACTION", "0.02")),
            ticks=int(os.environ.get("KC_BENCH_CHURN_TICKS", "5")),
        ))

    # pipelined loop: serial vs double-buffered deferred churn ticks at the
    # 100k × 2k scale config (docs/KERNEL_PERF.md "Layer 7"); the warm
    # per-tick stage gates in tools/perfgate.py and report_pipeline warns
    # when overlap efficiency sags.  KC_BENCH_PIPELINE=0 skips;
    # KC_BENCH_PIPELINE_{PODS,ITS,TICKS,FRACTION} size it.
    pipeline = None
    if os.environ.get("KC_BENCH_PIPELINE", "1") != "0":
        pipeline = _phase(failed, "pipeline", lambda: pipeline_line(
            n_pods=int(os.environ.get("KC_BENCH_PIPELINE_PODS", "100000")),
            n_its=int(os.environ.get("KC_BENCH_PIPELINE_ITS", "2000")),
            churn_fraction=float(
                os.environ.get("KC_BENCH_PIPELINE_FRACTION", "0.02")
            ),
            ticks=int(os.environ.get("KC_BENCH_PIPELINE_TICKS", "6")),
        ))

    # policy objective: the cheapest-fleet-vs-first-fit delta and the warm
    # cost of the scoring stage on a skewed-price demo fleet
    # (docs/POLICY.md); KC_BENCH_POLICY=0 skips.
    policy = None
    if os.environ.get("KC_BENCH_POLICY", "1") != "0":
        policy = _phase(failed, "policy", policy_line)

    # relax solver family: relax vs scan on a large skewed-price fleet —
    # solve walls, fleet-cost delta vs greedy, audited rounding violations
    # (docs/RELAX.md); KC_BENCH_RELAX=0 skips.
    relax = None
    if os.environ.get("KC_BENCH_RELAX", "1") != "0":
        relax = _phase(failed, "relax", lambda: relax_line(
            n_pods=int(os.environ.get("KC_BENCH_RELAX_PODS", "4000")),
            n_its=int(os.environ.get("KC_BENCH_RELAX_ITS", "24")),
        ))

    # multi-tenant coalescing: batched (vmapped tenant axis) vs serial solves
    # over N same-bucket tenants (docs/SERVICE.md); KC_BENCH_TENANT=0 skips.
    tenant = None
    if os.environ.get("KC_BENCH_TENANT", "1") != "0":
        tenant = _phase(failed, "tenant", lambda: tenant_line(
            n_tenants=int(os.environ.get("KC_BENCH_TENANTS", "8")),
            pods_per_tenant=int(os.environ.get("KC_BENCH_TENANT_PODS", "256")),
        ))

    # generalized solve fusion: fused vs serial REPAIR dispatches across
    # tenants + the KC_BUCKET_QUANTIZE occupancy sweep (docs/SERVICE.md
    # "Solve fusion"); KC_BENCH_FUSION=0 skips.
    fusion = None
    if os.environ.get("KC_BENCH_FUSION", "1") != "0":
        fusion = _phase(failed, "fusion", lambda: fusion_line(
            tenant_counts=tuple(
                int(c) for c in
                os.environ.get("KC_BENCH_FUSION_TENANTS", "2,4,8").split(",")
                if c.strip()
            ),
            pods_per_tenant=int(os.environ.get("KC_BENCH_FUSION_PODS", "256")),
        ))

    # fleet failover: checkpoint-restore vs journal-replay adoption cost at
    # 1/8/64-delta chains (docs/FLEET.md); KC_BENCH_FLEET=0 skips.
    fleet = None
    if os.environ.get("KC_BENCH_FLEET", "1") != "0":
        fleet = _phase(failed, "fleet", lambda: fleet_line(
            chains=tuple(
                int(c) for c in
                os.environ.get("KC_BENCH_FLEET_CHAINS", "1,8,64").split(",")
                if c.strip()
            ),
            pods=int(os.environ.get("KC_BENCH_FLEET_PODS", "128")),
        ))

    scheduled = sum(len(n.pods) for n in results.new_nodes)
    pods_per_sec = scheduled / warm_s if warm_s > 0 else 0.0
    detail = {
        "scheduled": scheduled,
        "failed": len(results.failed_pods),
        "nodes": len(results.new_nodes),
        "pods_per_sec": round(pods_per_sec),
        # cold_s (the fresh-process restart cold start) is stamped by the
        # top level from its --restart-probe child
        "first_boot_cold_s": round(first_boot_cold_s, 2),
        "caches_warm_at_start": cache_warm_at_start,
        "ingest_s": round(ingest_s, 3),
        "classify_s": round(classify_s, 4),
        "planes_s": round(planes_s, 4),
        "upload_s": round(upload_s, 4),
        "encode_s": round(encode_s, 4),
        "dispatch_s": round(dispatch_s, 4),
        "solve_decode_s": round(solve_decode_s, 4),
        "solve_s": round(solve_s, 4),
        "decode_s": round(decode_s, 4),
        "materialize_s": round(materialize_s, 4),
        "trace": trace_detail,
        "churn": churn,
        **_device_stamp(),
        "baseline": "reference CI floor: 100 pods/sec (scheduling_benchmark_test.go:48)",
        # CPU-capability fingerprint: tools/perfgate.py widens its tolerance
        # when comparing records from different machines
        "machine": _machine_tag(),
    }
    if churn and "error" not in churn:
        # stage-level mirrors so tools/perfgate.py gates the warm path
        # independently of the cold numbers (a warm-path regression must not
        # hide inside a flat headline)
        detail["churn_warm_solve_s"] = churn["warm_solve_s"]
        detail["churn_full_solve_s"] = churn["full_resolve_s"]
        detail["churn_speedup"] = churn["speedup"]
        # per-tick membership-delta ingest (O(churned) acceptance, ISSUE 11)
        detail["churn_delta_ingest_s"] = churn["delta_ingest_s"]
    detail["pipeline"] = pipeline
    if pipeline and "error" not in pipeline:
        # stage mirrors so tools/perfgate.py gates the pipelined warm tick
        # independently; report_pipeline reads the efficiency + parity
        detail["pipeline_warm_tick_s"] = pipeline["pipelined_tick_s"]
        detail["pipeline_serial_tick_s"] = pipeline["serial_tick_s"]
        detail["pipeline_speedup"] = pipeline["speedup"]
        detail["pipeline_overlap_efficiency"] = pipeline["overlap_efficiency"]
        detail["pipeline_donation_reallocs"] = pipeline["donation_reallocs"]
        # watchdog-overhead mirror (report_watchdog advisory: < 2% of the
        # pipelined warm tick)
        detail["pipeline_watchdog_overhead_frac"] = pipeline[
            "watchdog_overhead_frac"
        ]
        # telemetry-overhead mirror (report_telemetry advisory: < 2% of the
        # pipelined warm tick with tracing fully enabled vs KC_TRACE=0)
        detail["pipeline_telemetry_overhead_frac"] = pipeline[
            "telemetry_overhead_frac"
        ]
    detail["policy"] = policy
    if policy and "error" not in policy:
        # stage mirror for the perfgate objective_s gate + the acceptance
        # fleet-cost delta (must stay > 0 on the demo fleet)
        detail["objective_s"] = policy["objective_s"]
        detail["policy_fleet_cost_delta"] = policy["fleet_cost_delta"]
    detail["relax"] = relax
    if relax and "error" not in relax:
        # stage mirror for the perfgate relax_solve_s gate + the acceptance
        # fleet-cost delta vs greedy (must stay >= 0 on the skewed fleet)
        detail["relax_solve_s"] = relax["relax_solve_s"]
        detail["relax_fleet_cost_delta"] = relax["fleet_cost_delta"]
        detail["relax_rounded_violations"] = relax["rounded_violations"]
    detail["tenant"] = tenant
    if tenant and "error" not in tenant:
        # mirrors for the perfgate advisory report (batched must keep beating
        # serial — coalescing that stops paying is a regression even when
        # the single-tenant headline stays flat)
        detail["tenant_batched_solve_s"] = tenant["batched_s"]
        detail["tenant_serial_solve_s"] = tenant["serial_s"]
        detail["tenant_speedup"] = tenant["speedup"]
        # real-vs-padded rows per (bucket, mesh) for the coalesced
        # dispatches — the padding-waste story at fleet scale (ISSUE 16)
        detail["batch_occupancy"] = tenant.get("batch_occupancy") or {}
    detail["fusion"] = fusion
    if fusion and "error" not in fusion:
        # stage mirrors: perfgate gates the fused repair dispatch time as
        # its own stage and report_fusion warns when fused throughput drops
        # under 2x serial at the deepest tenant count
        detail["fusion_repair_solve_s"] = fusion["fusion_repair_solve_s"]
        detail["fusion_repair_serial_s"] = fusion["fusion_repair_serial_s"]
        detail["fusion_speedup"] = fusion["fusion_speedup"]
    detail["fleet"] = fleet
    if fleet and "error" not in fleet:
        # stage mirrors for the deepest chain: the checkpoint-restore gates
        # in tools/perfgate.py, the replay twin stays advisory (it moves
        # with solve cost and is covered by the solve stages); report_fleet
        # warns when restore stops beating replay ≥5x at 64 deltas
        deepest = max(
            (r for r in fleet.get("restores", []) if r.get("warm_ok")),
            key=lambda r: r["deltas"], default=None,
        )
        if deepest is not None:
            detail["fleet_restore_deltas"] = deepest["deltas"]
            detail["fleet_restore_s"] = deepest["checkpoint_restore_s"]
            detail["fleet_replay_s"] = deepest["replay_restore_s"]
            detail["fleet_restore_speedup"] = deepest["speedup"]
            detail["fleet_restore_bit_identical"] = deepest["bit_identical"]
    # observed compile count vs the retrace-budget manifest's expectation:
    # a bench that suddenly compiles more programs than the manifest says a
    # cold run needs is retracing — the exact failure mode the static
    # trace-safety/retrace-budget gates exist for, caught here on the
    # measured trajectory too
    detail["compiles"] = compile_counter["n"]
    expected_compiles = int(_retrace_manifest().get("bench_cold_compiles", 0) or 0)
    if expected_compiles:
        detail["expected_cold_compiles"] = expected_compiles
        if compile_counter["n"] > expected_compiles:
            detail["compile_budget_exceeded"] = True
            print(
                f"bench: WARNING observed {compile_counter['n']} XLA compiles "
                f"> expected cold-compile count {expected_compiles} "
                "(karpenter_core_tpu/analysis/retrace_budget.json) — a jit "
                "argument stopped being static or a compile-cache key axis "
                "is churning",
                file=sys.stderr,
            )

    # scale lines (BASELINE.md configs 3-4): on by default on a real
    # accelerator, opt-in/out via KC_BENCH_SCALE=1/0 (CPU runs them only on
    # request — minutes of compute that say nothing about the chip)
    scale = os.environ.get("KC_BENCH_SCALE", "auto")
    if scale == "1" or (scale == "auto" and detail["platform"] != "cpu"):
        detail["scale_100k"] = _phase(
            failed, "scale_100k", lambda: scale_line_100k(n_its)
        )
        detail["consolidation_sweep_1000"] = _phase(
            failed, "consolidation_sweep_1000", consolidation_sweep_line
        )

    # per-site watchdog abandonments across the whole run (empty = no hangs);
    # a non-empty map is the bench's structured hang evidence and fails it
    detail["watchdog_timeouts"] = watchdog_mod.stats()["timeouts"]
    if detail["watchdog_timeouts"]:
        failed.append("watchdog")
    detail["failed_phases"] = failed
    line = {
        "metric": f"solve_{n_pods // 1000}k_pods_{n_its}_types_wall_clock",
        "value": round(warm_s, 4),
        "unit": "s",
        "vs_baseline": round(pods_per_sec / 100.0, 1),
        "detail": detail,
    }
    print(json.dumps(line))
    return 1 if failed else 0


def main(n_pods: int, n_its: int) -> int:
    """The top level: stays off JAX (module docstring) and runs the measuring
    children in sequence — main run, restart probe, mesh sizes — merging
    their lines into the ONE bench line.  Returns the exit code: non-zero
    when the main run or any later phase failed."""
    try:
        line = run_child([n_pods, n_its, "--measure"], timeout_s=3600.0)
    except Exception as e:  # noqa: BLE001 - one structured record, not a traceback
        print(json.dumps({
            "metric": "bench_failed",
            "value": None,
            "unit": "s",
            "vs_baseline": 0.0,
            "error": {"type": type(e).__name__, "message": str(e)[:500]},
        }))
        return 1
    detail = line["detail"]
    failed = detail["failed_phases"]

    # restart cold: a fresh process with the persistent caches the main run
    # just populated — the cost every operator restart actually pays
    restart = _phase(failed, "restart_probe", lambda: run_child(
        [n_pods, n_its, "--restart-probe"], timeout_s=600.0
    ))
    detail["restart_probe"] = restart
    if "error" not in restart:
        detail["cold_s"] = restart["restart_cold_s"]

    # mesh scaling: the same fleet at mesh sizes 1/2/4/8, one child per size;
    # tools/perfgate.py gates the 1-device and best-mesh numbers
    # independently.  KC_BENCH_SHARDED=0 skips.
    if os.environ.get("KC_BENCH_SHARDED", "1") != "0":
        # (sharded_line reports a failed size in place, under "error")
        sharded = sharded_line(detail["platform"], detail["device_count"])
        detail["sharded"] = sharded
        if "error" in sharded:
            failed.append("sharded")
        elif "solve_s_1dev" in sharded:
            # stage mirrors so tools/perfgate.py gates the sharded path
            # independently — a sharding regression must not hide inside the
            # (single-device) headline number
            detail["sharded_solve_1dev_s"] = sharded["solve_s_1dev"]
            if "solve_s_best" in sharded:
                detail["sharded_solve_s"] = sharded["solve_s_best"]
                detail["sharded_speedup"] = sharded.get("speedup_best")

    print(json.dumps(line))
    return 1 if failed else 0


if __name__ == "__main__":
    _n_pods = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else None
    _n_its = int(sys.argv[2]) if len(sys.argv) > 2 and sys.argv[2].isdigit() else None
    if "--sharded-probe" in sys.argv:
        # child of sharded_line(): env (device pool + KC_SOLVER_MESH*) was
        # pinned by the top level before this interpreter started
        sharded_probe(
            _n_pods or 100_000, _n_its or 2_000,
            int(sys.argv[sys.argv.index("--sharded-probe") + 1]),
        )
    elif "--restart-probe" in sys.argv:
        restart_probe(_n_pods or 50_000, _n_its or 1_000)
    elif "--measure" in sys.argv:
        sys.exit(measure(_n_pods or 50_000, _n_its or 1_000))
    else:
        sys.exit(main(_n_pods or 50_000, _n_its or 1_000))
