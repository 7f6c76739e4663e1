"""What decides ``correct``: the guarantees every configuration file states,
held as far as one run can show.

  every pod sent is answered exactly once; on these mixes none fails
  a node's pods fit every instance type the node lists (from the API objects
    alone: no line of the solver is consulted)
  the kernel's totals equal the host oracle's on a cut the oracle can hold
  no quiet way off the device (``Ledger`` — a copy of chip_smoke.verdict())

The compile counter and the verdict conditions are copies of chip_smoke.py's,
kept here so that no later PR can change what it is judged by.
"""

import numpy as np

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

# counter families whose movement means a solve left the device path
# (read by name from the program's metrics registry; a family that is not
# registered in this process cannot have moved)
FALLBACK_FAMILIES = ("karpenter_tpu_kernel_fallback", "karpenter_degraded_solves_total")
SOLVE_MODE_FAMILY = "karpenter_solve_mode_total"


class CompileCounter:
    """JAX's compile event wraps the persistent-cache lookup, so it counts
    REQUESTS for an executable; backend compiles = requests - cache hits."""

    def __init__(self) -> None:
        import jax.monitoring

        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw) -> None:
        if event == _COMPILE_EVENT:
            self.requests += 1

    def _on_event(self, event, **kw) -> None:
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    @property
    def backend_compiles(self) -> int:
        return self.requests - self.cache_hits


def _family(name: str) -> dict:
    from karpenter_core_tpu.metrics import REGISTRY

    family = REGISTRY.get(name)
    if family is None:
        return {}
    return {
        ",".join(f"{k}={v}" for k, v in sorted(labels.items())): value
        for _name, labels, value in family.samples()
    }


def _moved(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0.0) for k, v in after.items() if v != before.get(k, 0.0)}


class Ledger:
    """The program's own counts, read before the first request and after the
    last; ``verdict`` lists every quiet way off the device they show."""

    def __init__(self) -> None:
        from karpenter_core_tpu.utils import watchdog

        watchdog.reset_stats()
        self._fallbacks0 = {name: _family(name) for name in FALLBACK_FAMILIES}
        self._modes0 = _family(SOLVE_MODE_FAMILY)

    def observe(self, sidecar, compiles_in_window: int) -> dict:
        from karpenter_core_tpu.ops import solve as solve_ops
        from karpenter_core_tpu.utils import compilecache, watchdog

        plain_jit = getattr(getattr(solve_ops, "_solve_jit", None), "_cache_size", None)
        return {
            "builds": compilecache.stats()["builds"],
            "plain_jit_runs": plain_jit() if plain_jit is not None else 0,
            "watchdog_timeouts": watchdog.stats()["timeouts"],
            "fallback_counters": {
                name: _moved(self._fallbacks0[name], _family(name))
                for name in FALLBACK_FAMILIES
            },
            "breaker_states": {
                f"tenant:{tid}": entry.breaker.state
                for tid, entry in sidecar.service.tenants.entries_snapshot().items()
            },
            "solve_modes": sorted(
                k.split("=", 1)[1]
                for k in _moved(self._modes0, _family(SOLVE_MODE_FAMILY))
            ),
            "compiles_in_window": compiles_in_window,
        }


def verdict(obs: dict) -> list:
    """Pure: the quiet ways off the device, judged from what a run observed."""
    bad = []
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
    for mode in obs["solve_modes"]:
        if mode.startswith("relax-fallback") or mode in ("host", "degraded"):
            bad.append(f"solve mode {mode!r} engaged")
    if obs["compiles_in_window"]:
        bad.append(f"{obs['compiles_in_window']} executable(s) compiled or "
                   "loaded inside the measured window")
    return bad


# -- answers -------------------------------------------------------------------


def counts(reply: dict) -> dict:
    """O(nodes) totals of one ``solve_classes`` answer — cheap enough to run
    between requests inside the window."""
    return {
        "nodes": len(reply["newNodes"]),
        "scheduled": sum(len(n["podIndices"]) for n in reply["newNodes"])
        + sum(len(idx) for idx in reply["existingAssignments"].values()),
        "failed": len(reply["failedPodIndices"]),
        "residual": len(reply["residualPodIndices"]),
    }


def accounting(reply: dict, n_pods: int) -> list:
    """Every pod sent is answered exactly once, and none fails."""
    placed = [i for n in reply["newNodes"] for i in n["podIndices"]]
    placed += [i for idx in reply["existingAssignments"].values() for i in idx]
    failed, residual = reply["failedPodIndices"], reply["residualPodIndices"]
    bad = []
    if sorted(placed + failed + residual) != list(range(n_pods)):
        bad.append(f"scheduled + failed + residual is not each of {n_pods} pods once")
    if failed or residual:
        bad.append(f"{len(failed)} failed / {len(residual)} residual pods "
                   "on a mix that must fully schedule")
    return bad


RESOURCES = ("cpu", "memory", "pods")


def capacity(reply: dict, pods: list, catalog: list) -> list:
    """The summed requests of a node's pods fit the allocatable of every
    instance type the node lists — checked from the API objects alone."""
    from karpenter_core_tpu.utils import resources as resources_util

    row = {it.name: i for i, it in enumerate(catalog)}
    alloc = np.array(
        [[it.allocatable().get(r, 0.0) for r in RESOURCES] for it in catalog]
    )
    need_of = np.array([
        [resources_util.requests_for_pods(p).get(r, 0.0) for r in RESOURCES]
        for p in pods
    ])
    bad = []
    for k, node in enumerate(reply["newNodes"]):
        names = node["instanceTypes"]
        if not names or not node["podIndices"]:
            bad.append(f"node {k} lists {len(names)} types for "
                       f"{len(node['podIndices'])} pods")
            continue
        need = need_of[node["podIndices"]].sum(axis=0)
        smallest = alloc[[row[name] for name in names]].min(axis=0)
        if np.any(need > smallest * (1 + 1e-9) + 1e-9):
            bad.append(f"node {k}: pods need {need.tolist()} of {RESOURCES}, "
                       f"its smallest listed type allows {smallest.tolist()}")
    return bad[:5]


def oracle_totals(pods: list, catalog: list, provisioners: list) -> dict:
    """The plain reference: the host scheduler (solver/scheduler.py, the
    port of the upstream Go scheduler) on the same pods."""
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.operator.kubeclient import KubeClient
    from karpenter_core_tpu.solver.builder import build_scheduler

    kube = KubeClient()
    for provisioner in provisioners:
        kube.create(provisioner)
    results = build_scheduler(
        kube, FakeCloudProvider(catalog), cluster=None, pods=pods,
        state_nodes=[], daemonset_pods=[],
    ).solve(pods)
    return {
        "nodes": len(results.new_nodes),
        "scheduled": sum(len(n.pods) for n in results.new_nodes),
        "failed": len(results.failed_pods),
        "residual": 0,
    }


def oracle(ctx, served) -> list:
    """Kernel vs host oracle on the configuration's cut (``oracle.pods``, a
    size the host oracle can hold).  ``served`` is ``(pods, answer)`` where the
    traffic already has an answer of that size; None sends one more served
    solve of a seeded cut of the same mix."""
    side = ctx.sidecar
    if served is None:
        from benchmark.harness.podmix import pod_mix, seeded

        pods = pod_mix(ctx.config["oracle"]["pods"], seeded(ctx.seed, "oracle"),
                       ctx.config["pod_mix"])
        reply, call = side.call(side.client.solve_classes, pods,
                                side.provisioners, timeout=ctx.timeout)
        if reply is None:
            return [f"oracle cut: the served solve raised: {call.error}"]
    else:
        pods, reply = served
    kernel, host = counts(reply), oracle_totals(pods, side.catalog, side.provisioners)
    return [] if kernel == host else [f"oracle cut: kernel {kernel} vs host {host}"]


def library_solve(pods: list, sidecar, mesh_axes="auto") -> tuple:
    """One solve of ``pods`` through the library surface, synced:
    ``(SolveOutputs, scan passes, bytes of the program's arguments, classes)``.
    The arrays carry the kernel's real shapes (``roofline.kernel_shapes``)."""
    import jax

    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.models.columnar import PodIngest
    from karpenter_core_tpu.ops import solve as solve_ops
    from karpenter_core_tpu.solver.tpu import TPUSolver

    solver = TPUSolver(FakeCloudProvider(sidecar.catalog), sidecar.provisioners)
    ingest = PodIngest()
    ingest.add_all(pods)
    snapshot = solver.encode(ingest)
    outputs = solve_ops.sync_outputs(solve_ops.solve(snapshot, mesh_axes=mesh_axes))
    arguments = solve_ops.prepare_host(snapshot)[:2]
    in_bytes = sum(getattr(x, "nbytes", 0) for x in jax.tree_util.tree_leaves(arguments))
    return outputs, snapshot.scan_passes, in_bytes, len(snapshot.classes)


def bit_identity(pods: list, sidecar, want_axes) -> tuple:
    """Several chips only: the sidecar's own mesh is the one the configuration
    states, a catalog-indexed output really is split one slice a chip, and the
    sharded solve equals the single-device program bit for bit.  Returns
    ``(failures, the sharded library_solve)``."""
    import jax

    from karpenter_core_tpu.parallel import mesh as mesh_mod

    bad = []
    axes = mesh_mod.solve_mesh_axes()
    if axes != want_axes:
        bad.append(f"solve mesh is {axes}, the configuration states {want_axes}")
    solved = library_solve(pods, sidecar)
    sharded, _, _, c0 = solved
    single = library_solve(pods, sidecar, mesh_axes=None)[0]
    viable = sharded.state.viable  # [N, I]: catalog-indexed
    if len({s.device for s in viable.addressable_shards}) != len(jax.devices()):
        bad.append("catalog-indexed output is not split one slice per device")
    a, b = np.asarray(single.assign), np.asarray(sharded.assign)
    n = min(a.shape[1], b.shape[1])
    if not (
        np.array_equal(np.asarray(single.failed)[:c0], np.asarray(sharded.failed)[:c0])
        and np.array_equal(a[:c0, :n], b[:c0, :n])
        and not a[:c0, n:].any() and not b[:c0, n:].any()
    ):
        bad.append("catalog-sharded solve is not bit-identical to mesh_axes=None")
    return bad, solved
