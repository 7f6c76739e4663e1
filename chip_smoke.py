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


# -- the legs ------------------------------------------------------------------


def served_leg(smoke: Smoke, args, pods, provisioners, catalog) -> None:
    """The sidecar over loopback gRPC, composed as the binary composes it."""
    import bench
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
        env, candidates = bench.consolidation_cluster(args.sweep_nodes, 3, catalog)
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

    import bench
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
    smoke.solve_modes.add(solver.last_solve_mode)

    # an UNHOOKED session: its churn repairs donate the warm carry
    donation0 = pipeline_mod.stats()
    churn = smoke.request("kernel.session_churn", lambda: bench.churn_line(
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
    import bench
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.operator.operator import Operator
    from karpenter_core_tpu.testing import make_provisioner
    from karpenter_core_tpu.testing.harness import nominations
    from karpenter_core_tpu.testing.validator import validate_placements

    provider = FakeCloudProvider(instance_types(args.operator_types))
    pods = bench.pod_mix(args.operator_pods, rng)
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
    import bench
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.solver.builder import build_scheduler
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_provisioner

    catalog = instance_types(args.operator_types)
    provisioners = [make_provisioner(name="default")]
    pods = bench.pod_mix(args.oracle_pods, rng)

    def kernel():
        solver = TPUSolver(FakeCloudProvider(catalog), provisioners)
        results = solver.solve(pods)
        smoke.solve_modes.add(solver.last_solve_mode)
        return results

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

    import bench
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
    pods = bench.pod_mix(args.pods, rng)
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
