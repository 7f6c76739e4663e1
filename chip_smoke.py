"""Chip smoke: the served solve path, end to end, on the local TPU — or fail.

    python chip_smoke.py [--seed N]

One process — the only one that initializes the accelerator backend — drives
the system's main path once through the entry points a user calls, at the
north-star size (BASELINE.json: 50 000 pods × 1 000 instance types × 5
provisioners, the upstream pod mix), and checks every answer:

  served leg    the solver sidecar composed exactly as cmd/solver.py composes
                it, over a loopback SnapshotSolverClient: one cold and three
                warm /SolveClasses, a tenant session (anchor + two 2 % churn
                delta ticks on the warm carry: departures, then arrivals),
                one /Consolidate sweep
  kernel leg    the same population through the library surface: output
                arrays live on the device (on several chips: catalog shards
                on distinct devices, and bit-identity with the single-device
                program), plus an unhooked incremental session under steady
                churn — every delta tick identical to a from-scratch solve of
                the same population, its repairs DONATING the warm carry
                (the served tenant path never donates: its dispatches ride
                the coalescer hook)
  operator leg  the upstream suite's largest size (5 000 pods × 400 types)
                through Operator(use_tpu_kernel=True), validated by
                testing/validator.py
  oracle leg    a 2 000-pod × 400-type cut against the host oracle
                (solver/scheduler.py)

It exits non-zero on any quiet way off the device (``verdict``): a backend
that is not ``tpu``, a request that raised, no executable built, the plain
jit having run, a watchdog timeout, a kernel fallback or degraded solve, an
open breaker, a relax fallback, a compile inside the warm window.

Exit codes: 0 pass (last stdout line: ``{"ok": true, "device": {...}}``);
1 a check failed; 2 JAX found no TPU (nothing is run, no result is printed);
3 a ``--cpu-dry-run`` finished clean — a CPU run the caller asked for
(``JAX_PLATFORMS=cpu``, tiny sizes, the tier-1 test) that can never pass.
Every time printed is an OBSERVATION of this run, not a benchmark.
"""

import argparse
import json
import logging
import os
import random
import sys
import time
import types

EXIT_CHECK_FAILED, EXIT_NO_CHIP, EXIT_DRY_RUN = 1, 2, 3

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def say(**fields) -> None:
    print(json.dumps(fields, default=repr), flush=True)


class Smoke:
    """What one run observed: failed checks, request times, ledgers."""

    def __init__(self, expect_platform: str) -> None:
        self.expect_platform = expect_platform
        self.failures: list = []
        self.request_errors: list = []
        self.off_device: list = []
        self.solve_modes: set = set()
        self.breaker_states: dict = {}
        self.warm_window_compiles = 0
        # JAX's compile event wraps the persistent-cache lookup, so it counts
        # REQUESTS; backend compiles = requests - persistent hits
        self.counts = {"compile_requests": 0, "persistent_cache_hits": 0,
                       "persistent_cache_writes": 0}

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.failures.append(what)
            say(check="FAILED", what=what)
        return bool(ok)

    def request(self, name: str, fn):
        """Run one request to completion; its wall time is an observation.
        A request that raises is recorded (the run goes on to report what
        else it can) and fails the verdict."""
        t0 = time.perf_counter()
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 - recorded, fails the verdict
            self.request_errors.append(f"{name}: {type(e).__name__}: {e}"[:400])
            say(request=name, error=self.request_errors[-1])
            return None
        say(request=name, observed_wall_s=time.perf_counter() - t0)
        return out


def verdict(obs: dict) -> list:
    """The quiet ways off the device, judged from what the run observed.
    Pure — tests/test_chip_smoke.py injects each condition once."""
    bad = []
    if obs["platform"] != obs["expect_platform"]:
        bad.append(f"backend is {obs['platform']!r}, not {obs['expect_platform']!r}")
    for where in obs["off_device_outputs"]:
        bad.append(f"output array not on a {obs['expect_platform']} device: {where}")
    for err in obs["request_errors"]:
        bad.append(f"request raised: {err}")
    if obs["builds"] == 0:
        bad.append("compilecache built no executable (builds == 0)")
    if obs["plain_jit_runs"]:
        bad.append("the plain-jit solve ran (ops.solve._solve_jit)")
    if obs["watchdog_timeouts"]:
        bad.append(f"watchdog timeouts: {obs['watchdog_timeouts']}")
    for name, moved in obs["fallback_counters"].items():
        if moved:
            bad.append(f"{name} moved: {moved}")
    for name, state in obs["breaker_states"].items():
        if state != "closed":
            bad.append(f"breaker {name} is {state}")
    for mode in sorted(obs["solve_modes"]):
        if mode.startswith("relax-fallback") or mode in ("host", "degraded"):
            bad.append(f"solve mode {mode!r} engaged")
    if obs["warm_window_compiles"]:
        bad.append(
            f"{obs['warm_window_compiles']} executable(s) compiled or loaded "
            "inside the warm window"
        )
    return bad


def _counter_samples(family) -> dict:
    return {
        ",".join(f"{k}={v}" for k, v in sorted(labels.items())): value
        for _name, labels, value in family.samples()
    }


def _moved(before: dict, after: dict) -> dict:
    return {
        k: after[k] - before.get(k, 0.0)
        for k in after if after[k] != before.get(k, 0.0)
    }


def _off_device(tree, platform: str, where: str) -> list:
    import jax

    return [
        f"{where}[{i}] on {sorted(d.platform for d in leaf.devices())}"
        for i, leaf in enumerate(jax.tree_util.tree_leaves(tree))
        if isinstance(leaf, jax.Array)
        and any(d.platform != platform for d in leaf.devices())
    ]


# -- the inputs ----------------------------------------------------------------


def pod_mix(n_pods: int, rng=None) -> list:
    """The reference benchmark's makeDiversePods shape
    (scheduling_benchmark_test.go:185-197): 3/7 generic + 1/7 zonal spread +
    1/7 hostname spread + 2/7 pod (self-)affinity.  ``rng``
    (``random.Random``) draws each generic pod's cpu and memory from the
    reference's own lists (randomCPU / randomMemory: 100m…1500m ×
    100Mi…4Gi — 30 shapes, and 100m is not exact in bf16, which is what lets
    a chip run catch a matmul running below f32 precision) and each affinity
    pod's group, as the reference draws them from its seeded source: 39
    classes.  None keeps a fixed four-size cycle: 13 classes."""
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


def churn_line(solver, ingest, churn_fraction: float = 0.02, ticks: int = 5) -> dict:
    """Steady-state churn (ISSUE 7 acceptance): the resident pod
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
    # churn concentrates in a rotating quarter of the classes per tick — the
    # rollout/deployment shape (one workload's pods are replaced while the
    # rest of the fleet idles), which is what makes the dirty REGION small
    # even when the churned pod count is not
    class_fraction = 0.25
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


# -- the legs ------------------------------------------------------------------


def served_leg(smoke: Smoke, args, pods, provisioners, catalog) -> None:
    """The sidecar over loopback gRPC, composed as the binary composes it."""
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.cmd import solver as solver_cmd
    from karpenter_core_tpu.models.snapshot import _class_signature
    from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverClient

    server, port = solver_cmd.compose(
        FakeCloudProvider(catalog), address="127.0.0.1:0"
    )
    client = SnapshotSolverClient(f"127.0.0.1:{port}")
    try:
        # -- cold, then the warm window ---------------------------------------
        cold = smoke.request("solve_classes.cold", lambda: client.solve_classes(
            pods, provisioners, timeout=args.cold_timeout))
        if cold is None:
            return
        placed = sorted(
            i for n in cold["newNodes"] for i in n["podIndices"]
        ) + sorted(i for idx in cold["existingAssignments"].values() for i in idx)
        failed = cold["failedPodIndices"]
        residual = cold["residualPodIndices"]
        say(leg="served", pods=len(pods), nodes=len(cold["newNodes"]),
            scheduled=len(placed), failed=len(failed), residual=len(residual))
        smoke.check(
            sorted(placed + failed + residual) == list(range(len(pods))),
            "solve_classes: scheduled + failed != pods sent (each pod once)",
        )
        smoke.check(not failed and not residual,
                    f"solve_classes: {len(failed)} failed / {len(residual)} "
                    "residual pods on a mix that must fully schedule")
        # an executable compiled OR loaded from the persistent cache inside
        # the warm window means one was not reused
        before = smoke.counts["compile_requests"]
        for k in range(3):
            warm = smoke.request(
                f"solve_classes.warm{k + 1}",
                lambda: client.solve_classes(
                    pods, provisioners, timeout=args.warm_timeout))
            smoke.check(
                warm == cold,
                f"solve_classes.warm{k + 1} differs from the cold answer",
            )
        smoke.warm_window_compiles += smoke.counts["compile_requests"] - before

        # -- tenant session: anchor + two 2% churn delta ticks ----------------
        by_sig: dict = {}
        for pod in pods:
            by_sig.setdefault(_class_signature(pod), []).append(pod)
        reps = [members[0] for members in by_sig.values()]
        counts = [len(members) for members in by_sig.values()]
        plane = server.kc_service.tenants

        def tenant_solve(counts_now, version):
            return client.solve_tenant_classes(
                list(zip(reps, counts_now)), provisioners,
                tenant={"id": "smoke", "sessionVersion": version},
                timeout=args.cold_timeout,
            )

        def settle(name, response, want_mode, population):
            """Checks shared by every tenant answer; returns the lineage's
            (aggregates, version) as the server now holds them."""
            if response is None:
                return None
            if not smoke.check("error" not in response,
                               f"{name}: tenant ejected: {response.get('error')}"):
                return None
            echo = response["tenant"]
            smoke.check(echo["solveMode"] == want_mode,
                        f"{name}: solveMode {echo['solveMode']!r} "
                        f"({echo.get('reason')}), wanted {want_mode!r}")
            session = plane.entries_snapshot()[echo["id"]].session
            agg = session.aggregates()
            smoke.check(
                agg["scheduled"] + agg["failed"] == population
                and agg["failed"] == 0,
                f"{name}: lineage holds {agg} for {population} pods",
            )
            return agg, echo["sessionVersion"]

        anchor = settle(
            "tenant.anchor",
            smoke.request("tenant.anchor", lambda: tenant_solve(counts, 0)),
            "full", sum(counts),
        )
        # 2% of every class departs, then as many arrive.  The wire protocol
        # ships class COUNTS, so a tick is a net count change — never the
        # same-tick evict-and-replace whose refill reproduces a from-scratch
        # packing (docs/INCREMENTAL.md; the kernel leg checks that identity).
        # What must hold here: the warm path answered (mode delta) and the
        # lineage accounts for every pod, exactly like the anchor did.
        version = anchor[1] if anchor else 0
        shrunk = [c - max(c // 50, 1) for c in counts]
        for name, counts_now in (("tenant.delta1", shrunk), ("tenant.delta2", counts)):
            tick = settle(
                name,
                smoke.request(name, lambda: tenant_solve(counts_now, version)),
                "delta", sum(counts_now),
            )
            if tick:
                version = tick[1]
                say(leg="served", tick=name, lineage=tick[0],
                    anchor=anchor[0] if anchor else None)
        for tenant_id, entry in plane.entries_snapshot().items():
            smoke.breaker_states[f"tenant:{tenant_id}"] = entry.breaker.state

        # -- one /Consolidate sweep, through the controller's own envelope ----
        env, candidates = consolidation_cluster(args.sweep_nodes, 3, catalog)
        mnc = env.deprovisioning.multi_node_consolidation
        mnc.solver_endpoint = f"127.0.0.1:{port}"
        cmd = smoke.request(
            "consolidate", lambda: mnc._remote_search(candidates))
        if cmd is not None:
            removed = {n.name for n in cmd.nodes_to_remove}
            say(leg="served", consolidate_action=cmd.action.value,
                candidates=len(candidates), nodes_removed=len(removed),
                replacements=len(cmd.replacement_nodes))
            # every node carries the same three small pods and is roomy: the
            # sweep must consolidate, and what it displaces must fit what
            # stays plus what it launches
            per_node = 3 * 0.1  # cpu
            free_kept = sum(
                float(c.node.status.allocatable["cpu"]) - per_node
                for c in candidates if c.node.name not in removed
            ) + sum(
                float(r.instance_type_options[0].allocatable()["cpu"])
                for r in cmd.replacement_nodes
            )
            smoke.check(
                cmd.action.value in ("delete", "replace") and len(removed) >= 2
                and per_node * len(removed) <= free_kept + 1e-6,
                f"consolidate: {cmd.action.value} removing {len(removed)} of "
                f"{len(candidates)} does not add up",
            )
    finally:
        client.close()
        server.stop(grace=0)
        server.kc_service.shutdown()


def kernel_leg(smoke: Smoke, args, pods, provisioners, catalog) -> None:
    """The same population through the library surface (module docstring)."""
    import jax
    import numpy as np

    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.parallel import mesh as mesh_mod
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.utils import pipeline as pipeline_mod

    solver = TPUSolver(FakeCloudProvider(catalog), provisioners)
    ingest = PodIngest()
    ingest.add_all(pods)
    snapshot = solver.encode(ingest)
    out = smoke.request("kernel.solve", lambda: solve_ops.sync_outputs(
        solve_ops.solve(snapshot)))
    if out is None:
        return
    smoke.off_device = _off_device(out, smoke.expect_platform, "SolveOutputs")
    mesh_axes = mesh_mod.solve_mesh_axes()
    say(leg="kernel", mesh=mesh_axes)
    if len(jax.devices()) > 1:
        want = ((mesh_mod.CATALOG_AXIS, len(jax.devices())),)
        smoke.check(mesh_axes == want, f"mesh is {mesh_axes}, wanted {want}")
        viable = out.state.viable  # [N, I]: catalog-indexed, sharded on axis 1
        shard_devices = {s.device for s in viable.addressable_shards}
        shard_shapes = {s.data.shape for s in viable.addressable_shards}
        say(leg="kernel", viable_shape=viable.shape,
            shard_shapes=sorted(shard_shapes), shard_devices=len(shard_devices))
        smoke.check(
            len(shard_devices) == len(jax.devices())
            and shard_shapes == {(viable.shape[0], viable.shape[1] // len(jax.devices()))},
            "catalog-indexed output is not sharded one slice per device",
        )
        single = smoke.request("kernel.solve.single_device", lambda:
                               solve_ops.sync_outputs(
                                   solve_ops.solve(snapshot, mesh_axes=None)))
        if single is not None:
            c0 = len(snapshot.classes)
            a, b = np.asarray(single.assign), np.asarray(out.assign)
            n = min(a.shape[1], b.shape[1])
            smoke.check(
                np.array_equal(np.asarray(single.failed)[:c0],
                               np.asarray(out.failed)[:c0])
                and np.array_equal(a[:c0, :n], b[:c0, :n])
                and not a[:c0, n:].any() and not b[:c0, n:].any(),
                "catalog-sharded solve is not bit-identical to mesh_axes=None",
            )

    # an UNHOOKED session: its churn repairs donate the warm carry
    donation0 = pipeline_mod.stats()
    churn = smoke.request("kernel.session_churn", lambda: churn_line(
        solver, ingest, churn_fraction=0.02, ticks=2))
    if churn is not None:
        donation = _moved(donation0, pipeline_mod.stats())
        say(leg="kernel", churn_modes=churn["modes"],
            identical=churn["identical_assignments"],
            used_drift_max_rel=churn["used_drift_max_rel"], donation=donation,
            donation_supported=pipeline_mod.backend_supports_donation())
        smoke.check(churn["identical_assignments"],
                    "session churn: delta lineage differs from a from-scratch solve")
        # f32 accumulation noise is ~1e-7; operands rounded to bf16 (a TPU
        # matmul left at default precision) read ~1e-3
        smoke.check(churn["used_drift_max_rel"] < 1e-5,
                    "session churn: the carry's used plane drifted "
                    f"{churn['used_drift_max_rel']:.2e} from an exact recount")
        smoke.check(churn["modes"].get("delta", 0) == 2 and churn["failed"] == 0,
                    f"session churn: modes {churn['modes']}, failed {churn['failed']}")
        if pipeline_mod.backend_supports_donation():
            smoke.check(donation.get("donated", 0) >= 2,
                        f"session churn: carries not donated ({donation})")


def operator_leg(smoke: Smoke, args, rng) -> None:
    """The upstream suite's largest size through the in-process operator."""
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.operator.operator import Operator
    from karpenter_core_tpu.testing import make_provisioner
    from karpenter_core_tpu.testing.harness import nominations
    from karpenter_core_tpu.testing.validator import validate_placements

    provider = FakeCloudProvider(instance_types(args.operator_types))
    pods = pod_mix(args.operator_pods, rng)
    # the smoke's cluster is this in-memory store, and the pods are ITS
    # workload: they must not queue behind the operator's own client-side
    # write throttle (--kube-client-qps 200 would spread 5 000 creates over
    # 25 s and several batch windows)
    kube = KubeClient()
    operator = Operator(
        cloud_provider=provider, kube_client=kube, use_tpu_kernel=True
    ).with_controllers().start()
    try:
        kube.create(make_provisioner(name="default"))

        def run():
            for pod in pods:
                kube.create(pod)
            deadline = time.monotonic() + args.cold_timeout
            while time.monotonic() < deadline:
                nominated = nominations(operator.recorder)
                if len(nominated) >= len(pods):
                    return nominated
                time.sleep(0.2)
            raise TimeoutError(
                f"{len(nominations(operator.recorder))} of {len(pods)} pods "
                f"nominated after {args.cold_timeout:.0f}s"
            )

        nominated = smoke.request("operator.provision", run)
        smoke.breaker_states["solver-backend"] = (
            operator.provisioning.solver_breaker.state
        )
    finally:
        operator.stop()
    if nominated is None:
        return
    # kube-scheduler emulation, then the independent placement oracle
    for pod in pods:
        pod.spec.node_name = nominated[pod.uid]
        kube.apply(pod)
    violations = validate_placements(
        types.SimpleNamespace(kube=kube, provider=provider), pods
    )
    say(leg="operator", pods=len(pods), nodes=len(kube.list_nodes()),
        violations=len(violations))
    smoke.check(not violations,
                f"operator: placement violations: {violations[:3]}")


def oracle_leg(smoke: Smoke, args, rng) -> None:
    """Kernel vs host oracle on a cut the oracle can hold
    (tests/test_tpu_solver.py ``compare``)."""
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.solver.builder import build_scheduler
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_provisioner

    catalog = instance_types(args.operator_types)
    provisioners = [make_provisioner(name="default")]
    pods = pod_mix(args.oracle_pods, rng)

    def kernel():
        return TPUSolver(FakeCloudProvider(catalog), provisioners).solve(pods)

    def host():
        kube = KubeClient()
        for p in provisioners:
            kube.create(p)
        return build_scheduler(
            kube, FakeCloudProvider(catalog), cluster=None, pods=pods,
            state_nodes=[], daemonset_pods=[],
        ).solve(pods)

    tpu = smoke.request("oracle.kernel", kernel)
    ref = smoke.request("oracle.host", host)
    if tpu is None or ref is None:
        return

    def totals(r):
        return {
            "scheduled": sum(len(n.pods) for n in r.new_nodes),
            "failed": len(r.failed_pods),
            "nodes": len(r.new_nodes),
        }

    say(leg="oracle", kernel=totals(tpu), host=totals(ref),
        residual=len(tpu.spread_residual_pods))
    smoke.check(totals(tpu) == totals(ref) and not tpu.spread_residual_pods,
                f"oracle: kernel {totals(tpu)} vs host {totals(ref)}")


# -- bring-up and verdict ------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pods", type=int, default=50_000)
    ap.add_argument("--types", type=int, default=1_000)
    ap.add_argument("--operator-pods", type=int, default=5_000)
    ap.add_argument("--operator-types", type=int, default=400)
    ap.add_argument("--oracle-pods", type=int, default=2_000)
    ap.add_argument("--sweep-nodes", type=int, default=1_000)
    ap.add_argument("--cold-timeout", type=float, default=600.0,
                    help="client timeout for a request that may compile")
    ap.add_argument("--warm-timeout", type=float, default=120.0)
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="run on the CPU the caller pinned (JAX_PLATFORMS=cpu); "
                         "exits 3 when clean — never a chip pass")
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr,
                        format="%(levelname)s %(name)s %(message)s")

    import jax
    import jax.monitoring

    expect = "cpu" if args.cpu_dry_run else "tpu"
    devices = jax.devices()
    if devices[0].platform != expect:
        print(f"chip_smoke: JAX found platform {devices[0].platform!r} "
              f"({len(devices)} device(s)), not {expect!r}; nothing was run",
              file=sys.stderr)
        return EXIT_NO_CHIP
    smoke = Smoke(expect)

    def on_duration(event, duration, **kw):
        if event == _COMPILE_EVENT:
            smoke.counts["compile_requests"] += 1

    def on_event(event, **kw):
        if event == _CACHE_HIT_EVENT:
            smoke.counts["persistent_cache_hits"] += 1
        elif event == _CACHE_MISS_EVENT:
            smoke.counts["persistent_cache_writes"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    import jaxlib

    from karpenter_core_tpu.cloudprovider.fake import instance_types
    from karpenter_core_tpu.controllers import provisioning as prov_mod
    from karpenter_core_tpu.models import native, nativesig
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.parallel import mesh as mesh_mod
    from karpenter_core_tpu.solver.incremental import SOLVE_MODE
    from karpenter_core_tpu.testing import make_provisioner
    from karpenter_core_tpu.utils import compilecache, watchdog

    from importlib import metadata

    try:
        libtpu_version = metadata.version("libtpu")
    except metadata.PackageNotFoundError:  # a CPU-only install
        libtpu_version = None
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    say(device=device, mesh=mesh_mod.solve_mesh_axes(), seed=args.seed,
        versions={"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                  "libtpu": libtpu_version},
        ingest={"kc_sig": "native" if nativesig.load() is not None else "python",
                "kc_runtime": "native" if native.available() else "numpy"},
        compile_cache={
            "JAX_COMPILATION_CACHE_DIR": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
            "package_root": compilecache.cache_dir()})

    rng = random.Random(args.seed)
    catalog = instance_types(args.types)
    provisioners = [
        make_provisioner(name=f"prov-{i}", weight=5 - i) for i in range(5)
    ]
    pods = pod_mix(args.pods, rng)
    fallback_families = (prov_mod.TPU_KERNEL_FALLBACK, prov_mod.DEGRADED_SOLVES)
    fallbacks0 = {f.name: _counter_samples(f) for f in fallback_families}
    modes0 = _counter_samples(SOLVE_MODE)
    watchdog.reset_stats()

    t0 = time.perf_counter()
    served_leg(smoke, args, pods, provisioners, catalog)
    kernel_leg(smoke, args, pods, provisioners, catalog)
    operator_leg(smoke, args, rng)
    oracle_leg(smoke, args, rng)

    smoke.solve_modes.update(
        k.split("=", 1)[1] for k in _moved(modes0, _counter_samples(SOLVE_MODE))
    )
    stats = devices[0].memory_stats() or {}
    say(observed_total_wall_s=time.perf_counter() - t0,
        compile_counts={
            **smoke.counts,
            "backend_compiles": smoke.counts["compile_requests"]
            - smoke.counts["persistent_cache_hits"],
        },
        compilecache=compilecache.stats(),
        jax_compilation_cache_dir=jax.config.jax_compilation_cache_dir,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        solve_modes=sorted(smoke.solve_modes))
    smoke.failures += verdict({
        "platform": device["platform"],
        "expect_platform": expect,
        "off_device_outputs": smoke.off_device,
        "request_errors": smoke.request_errors,
        "builds": compilecache.stats()["builds"],
        "plain_jit_runs": solve_ops._solve_jit._cache_size(),
        "watchdog_timeouts": watchdog.stats()["timeouts"],
        "fallback_counters": {
            f.name: _moved(fallbacks0[f.name], _counter_samples(f))
            for f in fallback_families
        },
        "breaker_states": smoke.breaker_states,
        "solve_modes": smoke.solve_modes,
        "warm_window_compiles": smoke.warm_window_compiles,
    })
    if smoke.failures:
        say(ok=False, device=device, failures=smoke.failures)
        return EXIT_CHECK_FAILED
    if args.cpu_dry_run:
        say(ok=False, device=device, dry_run="clean on cpu: not a chip pass")
        return EXIT_DRY_RUN
    say(ok=True, device=device)
    return 0


if __name__ == "__main__":
    sys.exit(main())
