"""Provisioning: pod batching, scheduling, machine launch, nomination.

Mirror of /root/reference/pkg/controllers/provisioning/{controller.go,
provisioner.go,batcher.go,volumetopology.go}: a pod-watch trigger feeds a
batching window; the singleton reconciler snapshots cluster state, collects
pending pods (plus pods on deleting nodes), runs the scheduler, launches
machines in parallel, pre-creates node objects, and nominates nodes for pods.

The solve itself routes to the TPU kernel when the batch is kernel-supported
(models.snapshot) and large enough to beat the host path, else to the exact
host scheduler — the Solver-interface seam described in BASELINE.json.
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import (
    OP_IN,
    Affinity,
    Node,
    NodeAffinity,
    NodeSelector,
    NodeSelectorRequirement,
    NodeSelectorTerm,
    NodeStatus,
    Pod,
)
from karpenter_core_tpu.apis.v1alpha5 import Provisioner as ProvisionerCRD
from karpenter_core_tpu.cloudprovider import CloudProvider
from karpenter_core_tpu.events import events as evt
from karpenter_core_tpu.metrics import REGISTRY, measure
from karpenter_core_tpu.operator.settings import Settings
from karpenter_core_tpu.scheduling import Requirement, Requirements
from karpenter_core_tpu.solver.builder import NoProvisionersError, build_scheduler
from karpenter_core_tpu.solver.scheduler import SchedulerOptions, SchedulingResults
from karpenter_core_tpu.state.cluster import (
    STATE_NODE_REBUILD_PODS,
    STATE_NODE_REBUILDS,
    Cluster,
)
from karpenter_core_tpu.utils import pod as pod_util
from karpenter_core_tpu.utils import retry
from karpenter_core_tpu.utils.clock import Clock

log = logging.getLogger(__name__)

SCHEDULING_DURATION = REGISTRY.histogram(
    "karpenter_provisioner_scheduling_duration_seconds",
    "Duration of the scheduling process in seconds.",
    ("provisioner",),
)
NODES_CREATED = REGISTRY.counter(
    "karpenter_nodes_created", "Number of nodes created in total by Karpenter.", ("reason",)
)
TPU_KERNEL_FALLBACK = REGISTRY.counter(
    "karpenter_tpu_kernel_fallback",
    "Batches that fell back from the TPU kernel to the host scheduler.",
    ("reason",),
)
DEGRADED_SOLVES = REGISTRY.counter(
    "karpenter_degraded_solves_total",
    "Solves served by the bounded host path while the solver-backend "
    "circuit breaker was open.",
    ("controller",),
)
POLICY_COUNTERPROPOSALS = REGISTRY.counter(
    "karpenter_policy_counterproposals_total",
    "ShapeHint counter-proposals emitted for pods a bounded resize would "
    "make schedulable on a strictly cheaper fleet (docs/POLICY.md).",
    ("kind",),
)

# consecutive unexpected kernel failures (backend init/dispatch faults, not
# KernelUnsupported routing) before the solver-backend circuit breaker opens
# and batches route through the degraded host path until the breaker's
# half-open trial re-proves the backend
TPU_KERNEL_MAX_FAILURES = 2
# seconds the solver breaker stays open before half-opening one trial batch
SOLVER_BREAKER_RESET_S = 30.0
# degraded-mode bound: the host path is O(pods x nodes), so while the breaker
# is open only this many pending pods solve per batch (the rest stay pending
# and re-trigger); KC_DEGRADED_MAX_PODS overrides
DEGRADED_MAX_PODS = 512


def _node_write_rejected(e: Exception) -> bool:
    """True when a failed node write provably never reached the store: a
    chaos fault injected before the write, the client-error surface both
    backends map those onto, or the apiserver itself answering 4xx.
    Connection-level deaths (socket timeout reading the response) return
    False — the write may have committed server-side."""
    from karpenter_core_tpu import chaos
    from karpenter_core_tpu.operator.kubeclient import ConflictError, NotFoundError

    if isinstance(e, (chaos.InjectedFault, NotFoundError, ConflictError)):
        return True
    status = getattr(e, "status", None)  # kubeapi.client.ApiServerError
    return isinstance(status, int) and 400 <= status < 500


class Batcher:
    """Idle/max-duration pod batching window (batcher.go:27-74): an idempotent
    one-slot trigger; Wait blocks for the first trigger then extends while
    triggers keep arriving within the idle window, up to the max window."""

    def __init__(self, clock: Clock, settings: Settings) -> None:
        self.clock = clock
        self.settings = settings
        self._trigger = threading.Event()

    def trigger(self) -> None:
        self._trigger.set()

    def wait(self, poll_interval: float = 0.05) -> bool:
        """True when a batch is ready; False when no trigger arrived."""
        if not self._trigger.wait(timeout=0.001):
            return False
        self._trigger.clear()
        start = self.clock.now()
        last_activity = start
        while True:
            self.clock.sleep(poll_interval)
            now = self.clock.now()
            if self._trigger.is_set():
                self._trigger.clear()
                last_activity = now
            if now - last_activity >= self.settings.batch_idle_duration:
                return True
            if now - start >= self.settings.batch_max_duration:
                return True


class VolumeTopology:
    """Rewrites pod node-affinity to AND in PV/StorageClass zone requirements
    so relaxation can't drop them (volumetopology.go:36-173)."""

    def __init__(self, kube_client) -> None:
        self.kube_client = kube_client

    def inject(self, pod: Pod) -> Optional[str]:
        requirements: List[NodeSelectorRequirement] = []
        for volume in pod.spec.volumes:
            reqs, err = self._requirements_for(pod, volume)
            if err is not None:
                return err
            requirements.extend(reqs)
        if not requirements:
            return None
        if pod.spec.affinity is None:
            pod.spec.affinity = Affinity()
        if pod.spec.affinity.node_affinity is None:
            pod.spec.affinity.node_affinity = NodeAffinity()
        if pod.spec.affinity.node_affinity.required is None:
            pod.spec.affinity.node_affinity.required = NodeSelector()
        terms = pod.spec.affinity.node_affinity.required.node_selector_terms
        if not terms:
            terms.append(NodeSelectorTerm())
        # AND into every OR term so relaxation can't drop the volume zone
        for term in terms:
            term.match_expressions.extend(requirements)
        return None

    def _requirements_for(self, pod: Pod, volume) -> Tuple[List[NodeSelectorRequirement], Optional[str]]:
        if volume.persistent_volume_claim is None:
            return [], None
        pvc = self.kube_client.get_persistent_volume_claim(
            pod.namespace, volume.persistent_volume_claim.claim_name
        )
        if pvc is None:
            return [], f"pvc {volume.persistent_volume_claim.claim_name} not found"
        if pvc.spec.volume_name:
            pv = self.kube_client.get_persistent_volume(pvc.spec.volume_name)
            if pv is None:
                return [], f"pv {pvc.spec.volume_name} not found"
            if pv.spec.node_affinity_required and pv.spec.node_affinity_required.node_selector_terms:
                return list(pv.spec.node_affinity_required.node_selector_terms[0].match_expressions), None
            return [], None
        if pvc.spec.storage_class_name:
            sc = self.kube_client.get_storage_class(pvc.spec.storage_class_name)
            if sc is None:
                return [], f"storage class {pvc.spec.storage_class_name} not found"
            if sc.allowed_topologies:
                return [
                    NodeSelectorRequirement(e.key, OP_IN, list(e.values))
                    for e in sc.allowed_topologies[0].match_expressions
                ], None
        return [], None

    def validate(self, pod: Pod) -> Optional[str]:
        """PVC/StorageClass existence validation (volumetopology.go:145-173)."""
        for volume in pod.spec.volumes:
            if volume.persistent_volume_claim is None:
                continue
            pvc = self.kube_client.get_persistent_volume_claim(
                pod.namespace, volume.persistent_volume_claim.claim_name
            )
            if pvc is None:
                return f"pvc {volume.persistent_volume_claim.claim_name} not found"
            if pvc.spec.storage_class_name:
                if self.kube_client.get_storage_class(pvc.spec.storage_class_name) is None:
                    return f"storage class {pvc.spec.storage_class_name} not found"
        return None


class PodController:
    """Pod-watch trigger (controller.go:56-66): provisionable pods trip the
    batcher."""

    name = "provisioning_trigger"

    def __init__(self, provisioner: "ProvisioningController") -> None:
        self.provisioner = provisioner

    @tracing.traced("provisioning_trigger.reconcile")
    def reconcile(self, pod: Pod) -> None:
        if pod_util.is_provisionable(pod):
            self.provisioner.trigger()

    def start(self, kube_client) -> None:
        kube_client.watch(Pod, lambda event, pod: event != "DELETED" and self.reconcile(pod))


class ProvisioningController:
    """The Provisioner singleton (provisioner.go:106-360)."""

    name = "provisioning"

    def __init__(
        self,
        kube_client,
        cloud_provider: CloudProvider,
        cluster: Cluster,
        recorder=None,
        settings: Optional[Settings] = None,
        clock: Optional[Clock] = None,
        use_tpu_kernel: bool = False,
        tpu_kernel_min_pods: int = 256,
        solver_endpoint: Optional[str] = None,
    ) -> None:
        self.kube_client = kube_client
        self.cloud_provider = cloud_provider
        self.cluster = cluster
        self.recorder = recorder
        self.settings = settings or Settings()
        self.clock = clock or Clock()
        self.batcher = Batcher(self.clock, self.settings)
        self.volume_topology = VolumeTopology(kube_client)
        self.use_tpu_kernel = use_tpu_kernel
        self.tpu_kernel_min_pods = tpu_kernel_min_pods
        # deployed topology: device solves ship to the shared solver service
        # (KC_SOLVER_ADDRESS, deploy/manifests) instead of running in-process
        import os

        self.solver_endpoint = (
            solver_endpoint if solver_endpoint is not None
            else os.environ.get("KC_SOLVER_ADDRESS", "")
        )
        try:
            self.degraded_max_pods = int(
                os.environ.get("KC_DEGRADED_MAX_PODS", DEGRADED_MAX_PODS)
            )
        except ValueError:
            self.degraded_max_pods = DEGRADED_MAX_PODS
        if self.degraded_max_pods < 1:
            # a non-positive bound would make every degraded batch solve an
            # empty subset and re-trigger forever — a no-progress livelock
            self.degraded_max_pods = DEGRADED_MAX_PODS
        self._solver_client = None
        # incremental warm-start solve lineage (solver.incremental): survives
        # across reconciles; its fallback policy decides full vs delta per
        # batch and KC_SOLVER_INCREMENTAL=0 disables it entirely
        self._incremental_session = None
        # the solver-backend breaker: counts unexpected kernel/backend faults
        # (not KernelUnsupported routing); open = degraded mode (bounded host
        # solves here, deprovisioning paused), half-open = one trial batch
        # re-proves the device path.  Shared with the deprovisioning
        # controller's consolidation sweep — same backend, one verdict.
        self.solver_breaker = retry.CircuitBreaker(
            self.clock,
            failure_threshold=TPU_KERNEL_MAX_FAILURES,
            reset_timeout_s=SOLVER_BREAKER_RESET_S,
            name="solver-backend",
        )
        # the quarantine ladder over that breaker (utils/watchdog.py): each
        # half-open window runs a deadline-bounded canary solve (tiny fixed
        # fleet, known answer) instead of risking a real batch — only a
        # verified canary re-admits the device path.  Built lazily (needs
        # the watchdog module); inert when KC_WATCHDOG=0.
        self._quarantine = None
        self._requeue_backoff = retry.Backoff(0.5, 60.0, max_exponent=7)
        self.last_reconcile_s: Optional[float] = None
        # host ingest/classification wall seconds of the last batch split —
        # the soak runner's advisory ``ingest_s`` probe reads this
        # (soak/slo.py; docs/KERNEL_PERF.md "Layer 6")
        self.last_ingest_s: float = 0.0
        # hidden device→host fetch wall of the last kernel solve (the
        # ``pipeline.overlap`` record, utils.pipeline): seconds of copy the
        # loop spent doing other work instead of blocking.  The soak
        # runner's advisory ``tick_overlap_s`` probe reads this; ≈0 on this
        # controller's serial per-reconcile path, >0 when a pipelined loop
        # (deferred session ticks) drove the solve
        # (docs/KERNEL_PERF.md "Layer 7")
        self.last_overlap_s: float = 0.0
        # persistent signature/ladder interner: watch events become
        # membership deltas — a pod shape seen in ANY previous batch never
        # pays signature derivation or ladder construction again
        # (models.columnar.SignatureInterner; exact by construction)
        from karpenter_core_tpu.models.columnar import SignatureInterner

        self._sig_interner = SignatureInterner()
        self._warmup_started = False
        self._warmup_lock = threading.Lock()
        self._warmup_thread: Optional[threading.Thread] = None
        from karpenter_core_tpu.utils.pretty import ChangeMonitor

        self._change_monitor = ChangeMonitor(ttl_seconds=3600.0)

    @property
    def _tpu_failures(self) -> int:
        """Consecutive solver-backend failures (the breaker's counter)."""
        return self.solver_breaker.failure_count

    def degraded(self) -> bool:
        """True while the solver-backend breaker is open: provisioning runs
        bounded host solves and deprovisioning pauses."""
        return self.use_tpu_kernel and self.solver_breaker.state == retry.OPEN

    def trigger(self) -> None:
        self.batcher.trigger()
        self._maybe_start_warmup()

    def _maybe_start_warmup(self) -> None:
        """First trigger kicks a background speculative compile of the solve
        executable for the standard shape buckets (TPUSolver.warmup), so the
        first real batch's compile overlaps the batch window instead of
        following it (VERDICT r2 #3).  Once per process; kernel path only;
        KC_TPU_WARMUP=0 opts out (tests do — they meter compiles)."""
        if self._warmup_started or not self.use_tpu_kernel:
            return
        # test-and-set under a lock: trigger() runs concurrently from watch
        # and batcher threads, and an unguarded check-then-set could start two
        # warmup compiles and track (and later join) only one — leaving the
        # other inside an XLA compile at interpreter teardown (ADVICE r4 #3)
        with self._warmup_lock:
            if self._warmup_started:
                return
            if self.solver_endpoint:
                # remote solves: the solver service owns (and persists) its
                # own compiled executables; nothing to warm in this process
                self._warmup_started = True
                return
            import os

            if os.environ.get("KC_TPU_WARMUP", "1") == "0":
                self._warmup_started = True
                return
            if not self.kube_client.list_provisioners():
                return  # nothing to compile against yet; retry later
            self._warmup_started = True

        def run() -> None:
            try:
                from karpenter_core_tpu.solver.tpu import TPUSolver

                provisioners = self.kube_client.list_provisioners()
                if not provisioners:
                    return
                solver = TPUSolver(
                    self.cloud_provider, provisioners,
                    daemonset_pods=self.get_daemonset_pods(),
                    kube_client=self.kube_client,
                )
                pending = max(len(self.get_pending_pods()), self.tpu_kernel_min_pods)
                solver.warmup(
                    n_pods=pending,
                    state_nodes=[n for n in self.cluster.snapshot_nodes() if not n.marked()],
                    bound_pods=self.kube_client.list_pods(),
                )
            except Exception:  # noqa: BLE001 - warmup runs off the solve path
                log.warning("speculative kernel warmup failed", exc_info=True)

        thread = threading.Thread(target=run, name="kc-tpu-warmup", daemon=True)
        self._warmup_thread = thread
        thread.start()
        # interpreter finalization while the thread sits inside an XLA compile
        # aborts the process (native exception during thread teardown); a
        # bounded join at exit lets the compile finish first.  Registered
        # through a weakref so a discarded controller isn't pinned (and its
        # handler becomes a no-op) — Operator.stop() joins explicitly anyway.
        import atexit
        import weakref

        ref = weakref.WeakMethod(self.join_warmup)

        def _backstop() -> None:
            join = ref()
            if join is not None:
                join()

        atexit.register(_backstop)

    def join_warmup(self, timeout: float = 120.0) -> None:
        """Wait out an in-flight speculative compile.  Deployed shutdown paths
        must pass a timeout below the pod's terminationGracePeriodSeconds or
        the kubelet's SIGKILL lands mid-compile anyway (Operator.stop passes
        15 s against the manifest's 30 s grace)."""
        thread = self._warmup_thread
        if thread is not None and thread.is_alive():
            thread.join(timeout)

    # -- reconcile ------------------------------------------------------------

    def reconcile(self, wait_for_batch: bool = True) -> Optional[str]:
        # the span opens after the batch window so idle wait time doesn't
        # masquerade as reconcile latency in the stage histogram
        if wait_for_batch and not self.batcher.wait():
            return None
        t0 = time.perf_counter()
        with tracing.span("provisioning.reconcile"):
            err = self._reconcile_batch()
        # wall seconds of the last batch, solve included — the soak runner's
        # per-reconcile solve-latency probe reads this (soak/slo.py)
        self.last_reconcile_s = time.perf_counter() - t0
        if err is not None:
            # requeue-on-error (controller-runtime semantics): the batcher
            # only wakes on pod events, so a failed launch would otherwise
            # sit unretried until unrelated work arrives.  Exponential
            # backoff on consecutive failures — a deterministic error (e.g.
            # exhausted cloud quota) must not become a 1 Hz hot loop of
            # cloud calls (controller-runtime's rate-limited requeue queue).
            delay = self._requeue_backoff.next()
            log.warning("provisioning reconcile: %s (retry in %.1fs)", err, delay)
            timer = threading.Timer(delay, self.batcher.trigger)
            timer.daemon = True
            timer.start()
        else:
            self._requeue_backoff.reset()
        return err

    def _reconcile_batch(self) -> Optional[str]:
        with tracing.span("provisioning.pending") as sp:
            state_nodes = []
            deleting_nodes = []
            for node in self.cluster.snapshot_nodes():
                if not node.marked():
                    state_nodes.append(node)
                else:
                    deleting_nodes.append(node)

            pods = self.get_pending_pods()
            # pods on deleting (cordoned) nodes also need homes (provisioner.go:137-144)
            deleting_names = {n.node.name for n in deleting_nodes}
            listed = self.kube_client.list_pods()
            for pod in listed:
                if (
                    pod.spec.node_name in deleting_names
                    and not pod_util.is_terminal(pod)
                    and not pod_util.is_terminating(pod)
                    and not pod_util.is_owned_by_daemon_set(pod)
                    and not pod_util.is_owned_by_node(pod)
                ):
                    pods.append(pod)
            sp.set(pods=len(pods), listed=len(listed))
        if not pods:
            return None

        results, err = self.schedule(pods, state_nodes)
        if err is not None:
            return err
        self._emit_counterproposals(results.failed_pods)
        if not results.new_nodes:
            return None

        node_names, launch_err = self.launch_machines(results.new_nodes)
        created = sum(1 for n in node_names if n)
        if created:
            NODES_CREATED.labels("provisioning").inc(created)
        return launch_err

    def get_pending_pods(self) -> List[Pod]:
        pods = []
        for pod in self.kube_client.list_pods(selector=lambda p: not p.spec.node_name):
            if not pod_util.is_provisionable(pod):
                continue
            err = self.volume_topology.validate(pod)
            if err is not None:
                log.debug("ignoring pod %s/%s, %s", pod.namespace, pod.name, err)
                continue
            self._consolidation_warnings(pod)
            pods.append(pod)
        return pods

    def _consolidation_warnings(self, pod: Pod) -> None:
        """Warn (hourly, deduped) about constraints that can block consolidation
        (provisioner.go:216-235)."""
        affinity = pod.spec.affinity
        if (
            affinity is not None
            and affinity.pod_anti_affinity is not None
            and affinity.pod_anti_affinity.preferred
        ):
            if self._change_monitor.has_changed((pod.uid, "pod-antiaffinity"), True):
                log.info(
                    "pod %s/%s has a preferred Anti-Affinity which can prevent consolidation",
                    pod.namespace, pod.name,
                )
        for constraint in pod.spec.topology_spread_constraints:
            if constraint.when_unsatisfiable == "ScheduleAnyway":
                if self._change_monitor.has_changed((pod.uid, "pod-topology-spread"), True):
                    log.info(
                        "pod %s/%s has a preferred TopologySpreadConstraint which can "
                        "prevent consolidation",
                        pod.namespace, pod.name,
                    )
                break

    def schedule(self, pods: List[Pod], state_nodes) -> Tuple[Optional[SchedulingResults], Optional[str]]:
        with tracing.span("schedule", pods=len(pods), state_nodes=len(state_nodes)):
            return self._schedule(pods, state_nodes)

    def _schedule(self, pods: List[Pod], state_nodes) -> Tuple[Optional[SchedulingResults], Optional[str]]:
        done = measure(SCHEDULING_DURATION.labels("default"))
        try:
            for pod in pods:
                err = self.volume_topology.inject(pod)
                if err is not None:
                    return None, err
            if self.use_tpu_kernel and len(pods) >= self.tpu_kernel_min_pods:
                if not self.solver_breaker.allow():
                    # breaker open: degraded mode.  Don't stall on (or even
                    # touch) the dead backend — serve a bounded host solve
                    # now; the breaker's half-open trial re-proves the device
                    # path and promotes batches back automatically.
                    TPU_KERNEL_FALLBACK.labels("degraded").inc()
                    return self._schedule_degraded(pods, state_nodes), None
                was_half_open = self.solver_breaker.state == retry.HALF_OPEN
                if was_half_open and not self.solver_endpoint:
                    # (remote topology excluded: a CPU controller replica
                    # must never initialize a device backend, and an
                    # in-process canary would probe the wrong thing — the
                    # half-open trial there stays the real remote batch)
                    from karpenter_core_tpu.utils import watchdog as watchdog_mod

                    if watchdog_mod.watchdog_enabled():
                        # quarantine re-admission: prove the backend on a
                        # deadline-bounded canary BEFORE trusting it with a
                        # real batch.  Verified → the breaker closed and this
                        # batch rides the device path normally; anything else
                        # → the breaker re-opened, serve this batch degraded.
                        if not self._canary_readmit():
                            TPU_KERNEL_FALLBACK.labels("quarantined").inc()
                            return self._schedule_degraded(pods, state_nodes), None
                        was_half_open = False
                try:
                    results = self._schedule_tpu(pods, state_nodes)
                except NoProvisionersError:
                    # precondition error, not a backend verdict: free the
                    # half-open trial slot so a later batch can still probe
                    self.solver_breaker.release_trial()
                    raise
                except Exception as e:  # backend init/dispatch faults, not routing
                    self.solver_breaker.record_failure()
                    TPU_KERNEL_FALLBACK.labels("backend-error").inc()
                    log.warning(
                        "TPU kernel solve failed (%s: %s); falling back to the "
                        "host scheduler (%d/%d consecutive failures, breaker %s)",
                        type(e).__name__, e, self.solver_breaker.failure_count,
                        TPU_KERNEL_MAX_FAILURES, self.solver_breaker.state,
                    )
                    results = None
                else:
                    if results is not None:
                        self.solver_breaker.record_success()
                        if was_half_open:
                            log.info(
                                "solver backend recovered: breaker closed, "
                                "device path restored"
                            )
                    else:
                        # shape routing (unsupported/entangled/under-min): the
                        # batch runs on the host path by design, not by fault —
                        # and it says NOTHING about the backend, so a half-open
                        # trial must not close the breaker on it (the next
                        # eligible batch probes instead); in the closed state
                        # it keeps resetting the failure streak, as before
                        if was_half_open:
                            self.solver_breaker.release_trial()
                        else:
                            self.solver_breaker.record_success()
                        TPU_KERNEL_FALLBACK.labels("unsupported").inc()
                if results is not None:
                    return results, None
            return self._host_solve(pods, state_nodes), None
        except NoProvisionersError as e:
            return None, str(e)
        finally:
            done()

    def _host_solve(self, pods: List[Pod], state_nodes) -> SchedulingResults:
        """The exact host-oracle solve — the normal fallback path and the
        degraded path build it identically so they cannot diverge."""
        from karpenter_core_tpu.solver.incremental import SOLVE_MODE

        SOLVE_MODE.labels("host").inc()
        scheduler = build_scheduler(
            self.kube_client,
            self.cloud_provider,
            self.cluster,
            pods,
            state_nodes,
            daemonset_pods=self.get_daemonset_pods(),
            recorder=self.recorder,
            opts=SchedulerOptions(),
        )
        return scheduler.solve(pods)

    def _schedule_degraded(self, pods: List[Pod], state_nodes) -> SchedulingResults:
        """Bounded host-path greedy solve while the solver breaker is open.

        The host oracle (solver/scheduler.py) is exact but O(pods x nodes);
        degraded mode trades batch size for latency — at most
        ``degraded_max_pods`` pods solve per batch, the remainder stays
        pending and re-triggers shortly, so the cluster keeps converging
        (slowly, correctly) instead of stalling behind a dead backend.
        Everything this path emits carries ``degraded=true``."""
        from karpenter_core_tpu.solver.incremental import SOLVE_MODE

        subset = pods[: self.degraded_max_pods]
        deferred = len(pods) - len(subset)
        DEGRADED_SOLVES.labels("provisioning").inc()
        SOLVE_MODE.labels("degraded").inc()
        with tracing.span(
            "schedule.degraded", degraded=True, pods=len(subset), deferred=deferred
        ):
            log.warning(
                "degraded solve: solver breaker open, host-solving %d/%d "
                "pending pods", len(subset), len(pods),
            )
            results = self._host_solve(subset, state_nodes)
        if deferred:
            # the deferred tail generates no new pod events, so wake the
            # batcher ourselves once this batch's launches land
            timer = threading.Timer(1.0, self.batcher.trigger)
            timer.daemon = True
            timer.start()
        return results

    def _canary_readmit(self) -> bool:
        """One quarantine-ladder rung: a deadline-bounded canary solve
        against the quarantined backend (utils/watchdog.BackendQuarantine).
        True re-admits the device path (breaker closed); False keeps it
        quarantined (breaker re-opened) — the next half-open window retries,
        so a dead backend is probed periodically at zero risk to real
        batches."""
        from karpenter_core_tpu.utils import watchdog as watchdog_mod

        if self._quarantine is None:
            self._quarantine = watchdog_mod.BackendQuarantine(
                self.solver_breaker, self._run_canary
            )
        return self._quarantine.try_readmit()

    def _run_canary(self) -> Optional[bool]:
        """The canary solve itself: a tiny FIXED fleet with a known answer —
        8 identical small pods against the real catalog must all place, on
        any healthy backend, in well under the canary deadline.  Runs the
        full encode → dispatch → fetch → decode path (each leg individually
        watchdog-bounded), so a device that hangs at ANY stage fails the
        canary instead of wedging a worker.  Returns None (no verdict —
        trial slot released, breaker untouched) when the backend was never
        exercised: no provisioners to solve against, or the canary shape
        itself routed off the kernel."""
        from karpenter_core_tpu.apis.objects import (
            Container,
            ObjectMeta,
            PodSpec,
            ResourceRequirements,
        )
        from karpenter_core_tpu.models.snapshot import KernelUnsupported
        from karpenter_core_tpu.solver.tpu import TPUSolver

        provisioners = self.kube_client.list_provisioners()
        if not provisioners:
            return None  # cluster-config condition, not backend evidence
        solver = TPUSolver(
            self.cloud_provider, provisioners,
            daemonset_pods=self.get_daemonset_pods(),
            kube_client=self.kube_client,
        )
        proto = Pod(
            metadata=ObjectMeta(name="watchdog-canary"),
            spec=PodSpec(containers=[Container(
                resources=ResourceRequirements(
                    requests={"cpu": 0.1, "memory": 128 * 2**20}
                )
            )]),
        )
        pods = [proto] * 8
        try:
            results = solver.solve(pods)
        except KernelUnsupported:
            return None  # shape routing: the device was never dispatched
        placed = sum(len(d.pods) for d in results.new_nodes) + sum(
            len(p) for p in results.existing_assignments.values()
        )
        return (
            placed == len(pods)
            and not results.failed_pods
            and not results.spread_residual_pods
        )

    def _schedule_tpu(self, pods: List[Pod], state_nodes) -> Optional[SchedulingResults]:
        """Route the batch through the TPU kernel; None falls back to the host
        path (batch shape unsupported — models.snapshot.classify_pods).

        Mixed batches split: pods whose shape the kernel doesn't model go to
        the host oracle AFTER the kernel pass (with the kernel's existing-node
        placements applied), so one exotic pod no longer drags 50k ordinary
        pods onto the O(pods × nodes) host path.  The split only happens when
        the two sets are topology- and volume-isolated from each other —
        otherwise shared group counts would diverge and the whole batch stays
        on the host path, as before."""
        from karpenter_core_tpu.models.snapshot import KernelUnsupported
        from karpenter_core_tpu.solver.tpu import TPUSolver

        provisioners = self.kube_client.list_provisioners()
        if not provisioners:
            raise NoProvisionersError("no provisioners found")
        split = self._split_batch(pods)
        if split is None:
            return None  # unsupported pods entangled with the rest: whole-batch host
        tpu_classes, tpu_pods, host_pods = split
        if len(tpu_pods) < self.tpu_kernel_min_pods:
            # post-split remainder too small to amortize the kernel's fixed
            # encode/dispatch overhead — same regime the pre-solve gate covers
            return None
        daemonset_pods = self.get_daemonset_pods()
        solver = TPUSolver(
            self.cloud_provider, provisioners,
            daemonset_pods=daemonset_pods,
            kube_client=self.kube_client,
            # the policy objective stage (docs/POLICY.md): scores feasible
            # offerings after the solve and pins each node's launch to the
            # argmin cell; disabled config = pre-policy pipeline exactly
            policy=self.policy_config(provisioners),
        )
        bound_pods = self.kube_client.list_pods()
        if self.solver_endpoint:
            # the deployed topology: CPU controller replicas, one shared TPU
            # solver service — ship the snapshot over the channel
            remote = self._solve_remote(
                solver, tpu_classes, tpu_pods, state_nodes, daemonset_pods,
                provisioners, bound_pods,
            )
            if remote is None:
                return None  # service judged the batch kernel-unsupported
            tpu_results, new_launchables = remote
        else:
            # sharded dispatch (docs/KERNEL_PERF.md "Layer 5"): the in-process
            # solve routes through the shard_map mesh dispatcher whenever
            # KC_SOLVER_MESH enables it (default: on with >1 device) — the
            # encode pads the catalog shard-aligned and prepare_encoded
            # captures the topology, so this controller needs no mesh
            # plumbing of its own; surface the routing on the span for triage.
            # (Deliberately NOT computed on the remote branch above: a CPU
            # controller replica must never initialize a device backend.)
            from karpenter_core_tpu.parallel import mesh as mesh_mod

            mesh_axes = mesh_mod.solve_mesh_axes()
            sp = tracing.current()
            if sp is not None and mesh_axes is not None:
                sp.set(**{"solve.mesh": repr(mesh_axes)})
            try:
                tpu_results = self._solve_in_process(
                    solver, tpu_classes, state_nodes, bound_pods
                )
            except KernelUnsupported as e:
                # batch-level shapes (deep affinity chains, cross-class PVC
                # sharing) surface here rather than per class
                log.debug("TPU kernel unsupported for batch, falling back: %s", e)
                return None
            new_launchables = [
                solver.to_launchable(decision) for decision in tpu_results.new_nodes
            ]

        results = SchedulingResults(failed_pods=list(tpu_results.failed_pods))
        results.new_nodes = new_launchables
        # nominate existing nodes + publish pod nominations
        for node_name, placed in tpu_results.existing_assignments.items():
            self.cluster.nominate_node_for_pod(node_name)
            node = self.kube_client.get_node(node_name)
            if self.recorder is not None and node is not None:
                for pod in placed:
                    self.recorder.publish(evt.nominate_pod(pod, node))
        if self.recorder is not None:
            for pod in results.failed_pods:
                self.recorder.publish(
                    evt.pod_failed_to_schedule(pod, "no capacity (tpu solve)")
                )
        # spread residuals: the kernel flagged these classes as possibly
        # under-placed vs the host oracle (water-fill round bound / intake
        # overestimate) — re-solve their leftover pods on the host with the
        # kernel's placements seeded into the topology counts, so no batch
        # shape schedules fewer pods than the host would (VERDICT r2 #2)
        residual_pods = list(tpu_results.spread_residual_pods)
        if (residual_pods or host_pods) and self._incremental_session is not None:
            # the host remainder places pods the warm carry cannot see — the
            # lineage is no longer the whole truth, so the next batch must
            # re-anchor with a full solve
            self._incremental_session.reset()
        if residual_pods:
            log.info(
                "re-routing %d spread-residual pods to the host oracle",
                len(residual_pods),
            )
        if host_pods or residual_pods:
            if host_pods:
                log.debug(
                    "solving %d kernel-unsupported pods on the host path "
                    "(%d solved on tpu)", len(host_pods), len(tpu_pods),
                )
            remainder = host_pods + residual_pods
            with tracing.span("provisioning.remainder", pods=len(remainder)):
                host_results = self._solve_host_remainder(
                    remainder, state_nodes, tpu_results,
                    results.new_nodes, daemonset_pods,
                    seed_topology=bool(residual_pods),
                )
            results.new_nodes.extend(host_results.new_nodes)
            results.failed_pods.extend(host_results.failed_pods)
            results.errors.update(host_results.errors)
        return results

    def _solve_in_process(self, solver, tpu_classes, state_nodes, bound_pods):
        """One in-process kernel solve, routed through the incremental
        warm-start session (solver.incremental) unless KC_SOLVER_INCREMENTAL=0
        keeps the old full-solve-every-batch path.  The session's fallback
        policy picks full vs delta per batch; the decision rides the
        ``solve.mode`` span attribute and ``karpenter_solve_mode_total``."""
        from karpenter_core_tpu.solver.incremental import (
            SOLVE_MODE,
            FallbackPolicy,
            IncrementalSolveSession,
            incremental_enabled,
        )

        if not incremental_enabled():
            snapshot = solver.encode_classes(
                tpu_classes, state_nodes=state_nodes, bound_pods=bound_pods
            )
            SOLVE_MODE.labels("full").inc()
            sp = tracing.current()
            if sp is not None:
                sp.set(**{"solve.mode": "full", "solve.mode.reason": "disabled"})
            return solver.solve_encoded(snapshot, state_nodes, bound_pods)
        session = self._incremental_session
        if session is None:
            # materialized=True: this session's decisions become real nodes,
            # so repairs additionally require that the previous solve opened
            # no new slots (FallbackPolicy docstring)
            session = self._incremental_session = IncrementalSolveSession(
                policy=FallbackPolicy.from_env(materialized=True)
            )
        session.rebind(solver)
        results = session.solve(tpu_classes, state_nodes, bound_pods)
        # surface the solve's hidden-fetch wall for the soak runner's
        # advisory ``tick_overlap_s`` probe (utils.pipeline, docs/SOAK.md)
        from karpenter_core_tpu.utils import pipeline as pipeline_mod

        self.last_overlap_s = pipeline_mod.last_overlap().get("hidden_s", 0.0)
        return results

    def _solve_remote(self, solver, tpu_classes, tpu_pods, state_nodes,
                      daemonset_pods, provisioners, bound_pods):
        """One snapshot solve over the gRPC channel (service.snapshot_channel,
        SolveClasses — O(distinct shapes) on the wire).

        Returns (tpu_results, launchables) shaped like the in-process path,
        or None when the service judged the batch kernel-unsupported
        (FAILED_PRECONDITION → the caller host-routes the whole batch).
        Transport/backend errors propagate — schedule()'s circuit breaker
        counts them and self-disables the device path after repeated faults.
        """
        import grpc

        from karpenter_core_tpu.apis import codec
        from karpenter_core_tpu.solver.tpu import TPUSolveResults

        # everything this side does to the snapshot before the client has it
        with tracing.span("provisioning.wire") as sp:
            client = self._solver_client
            if client is None:
                from karpenter_core_tpu.service.snapshot_channel import (
                    SnapshotSolverClient,
                )

                client = self._solver_client = SnapshotSolverClient(self.solver_endpoint)

            bound_by_node: Dict[str, List[Pod]] = {}
            for pod in bound_pods:
                if (
                    pod.spec.node_name
                    and not pod_util.is_terminal(pod)
                    and not pod_util.is_terminating(pod)
                ):
                    bound_by_node.setdefault(pod.spec.node_name, []).append(pod)
            nodes = [
                {
                    "node": codec.node_to_dict(sn.node),
                    "pods": [
                        codec.pod_to_dict(p)
                        for p in bound_by_node.get(sn.node.name, [])
                    ],
                    "volumeLimits": dict(sn.volume_limits()),
                }
                for sn in (state_nodes or [])
            ]
            # resolve claims for the BOUND pods too: the server counts existing
            # volume attachments from them, and an unresolvable claim reads as
            # zero attachments (VolumeUsage.add drops resolution errors) — the
            # node would look empty and over-admit new PVC pods
            shipped_bound = [
                p for sn in (state_nodes or [])
                for p in bound_by_node.get(sn.node.name, [])
            ]
            # _split_batch laid tpu_pods out class-by-class: membership is the
            # running offsets, no second O(pods) signature pass
            members: List[List[int]] = []
            offset = 0
            for cls in tpu_classes:
                members.append(list(range(offset, offset + len(cls.pods))))
                offset += len(cls.pods)
            claim_drivers = self._claim_drivers(tpu_pods + shipped_bound)
            sp.set(nodes=len(nodes), bound_pods=len(shipped_bound),
                   claims=len(claim_drivers))
        try:
            response = client.solve_classes(
                tpu_pods, provisioners,
                nodes=nodes,
                daemonset_pods=daemonset_pods,
                claim_drivers=claim_drivers,
                members=members,
                # the replica's resolved policy config rides the wire: the
                # remote objective stage must select offerings exactly like
                # an in-process solve would (it previously fell back
                # silently to first-fit — PolicyConfig never crossed)
                policy=solver.policy,
            )
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.FAILED_PRECONDITION:
                log.debug("remote solver: kernel unsupported (%s)", e.details())
                return None
            raise  # transport/backend fault: the circuit breaker counts it

        tpu_results = TPUSolveResults()
        launchables = []
        catalog_skew_pods: List[Pod] = []
        for entry in response["newNodes"]:
            node = solver.launchable_from_wire(
                entry, [tpu_pods[i] for i in entry["podIndices"]]
            )
            if not node.instance_type_options:
                # catalog skew between this replica and the solver (image
                # rollout): nothing launchable from the wire's instance-type
                # names.  Re-route the pods through the host residual path —
                # the local oracle can still place them with whatever catalog
                # THIS replica has — rather than failing real workload pods
                # every reconcile until the rollout converges (ADVICE r4 #4)
                log.warning(
                    "remote solve returned instance types unknown to this "
                    "catalog (%s); re-routing %d pods to the host oracle",
                    entry.get("instanceTypes", [])[:3], len(node.pods),
                )
                catalog_skew_pods.extend(node.pods)
                continue
            launchables.append(node)
        tpu_results.existing_assignments = {
            name: [tpu_pods[i] for i in indices]
            for name, indices in response["existingAssignments"].items()
        }
        tpu_results.failed_pods.extend(
            tpu_pods[i] for i in response["failedPodIndices"]
        )
        tpu_results.spread_residual_pods = [
            tpu_pods[i] for i in response.get("residualPodIndices", [])
        ] + catalog_skew_pods
        tpu_results.existing_committed_zones = dict(
            response.get("existingCommittedZones", {})
        )
        return tpu_results, launchables

    def _claim_drivers(self, pods: List[Pod]) -> Dict[str, str]:
        """Resolve every PVC the batch references to its CSI driver
        (volumeusage.go:65-90 resolution, done on THIS side of the wire where
        the apiserver lives), keyed "<ns>/<claim>" for the channel."""
        drivers: Dict[str, str] = {}
        for pod in pods:
            for volume in pod.spec.volumes:
                if volume.persistent_volume_claim is None:
                    continue
                claim = volume.persistent_volume_claim.claim_name
                key = f"{pod.namespace}/{claim}"
                if key in drivers:
                    continue
                pvc = self.kube_client.get_persistent_volume_claim(
                    pod.namespace, claim
                )
                if pvc is None:
                    continue
                driver = ""
                if pvc.spec.volume_name:
                    pv = self.kube_client.get_persistent_volume(pvc.spec.volume_name)
                    driver = pv.spec.csi_driver if pv is not None else ""
                elif pvc.spec.storage_class_name:
                    sc = self.kube_client.get_storage_class(pvc.spec.storage_class_name)
                    driver = sc.provisioner if sc is not None else ""
                if driver:
                    drivers[key] = driver
        return drivers

    def _split_batch(self, pods: List[Pod]):
        """(tpu_classes, tpu_pods, host_pods), or None when the unsupported
        pods are not isolated from the supported ones (shared topology
        selectors/labels or shared PVC claims — the split would desynchronize
        shared counts).  The built classes feed TPUSolver.encode_classes so
        classification is not repeated on the hot path.

        Classification rides the controller's PERSISTENT interner
        (models.columnar.SignatureInterner): a shape seen in any previous
        reconcile pays neither signature derivation nor ladder construction
        again, so steady-state batches cost O(pods) cheap fast-key reads plus
        O(new shapes) — trace/watch events become membership deltas, not
        pod-list rebuilds.  The wall cost lands on ``last_ingest_s`` (the
        soak runner's advisory ingest probe)."""
        t0 = time.perf_counter()
        with tracing.span("provisioning.split", pods=len(pods)):
            try:
                return self._split_batch_impl(pods)
            finally:
                self.last_ingest_s = time.perf_counter() - t0

    def _split_batch_impl(self, pods: List[Pod]):
        from dataclasses import replace as dc_replace

        from karpenter_core_tpu.solver.incremental import incremental_enabled

        interner = self._sig_interner
        supported: Dict[tuple, List[Pod]] = {}
        unsupported: Dict[tuple, List[Pod]] = {}
        protos: Dict[tuple, object] = {}
        interned = 0  # shapes of this batch the interner already knew
        # the in-process incremental session keeps no capacity-type counts
        # (models.snapshot.refuse_ct_spread): its batches send such classes
        # to the host, as every path did before the kernel served them
        ct_to_host = not self.solver_endpoint and incremental_enabled()
        for pod in pods:
            sig = interner.sig_of(pod)
            proto = protos.get(sig)
            if proto is None and sig not in protos:
                interned += interner.knows(sig)
                proto, _error = interner.ladder_of(sig, pod)
                if ct_to_host and proto is not None and proto.ct_spread is not None:
                    proto = None
                protos[sig] = proto
            (supported if proto is not None else unsupported).setdefault(
                sig, []
            ).append(pod)

        host_pods = [p for group in unsupported.values() for p in group]
        tpu_classes = []
        tpu_pods: List[Pod] = []
        for sig, group in supported.items():
            # shallow replace, never mutate: the proto is shared across
            # reconciles (and with PodIngest.classes' convention); the
            # interned signature rides along for the encode's reuse key
            cls = dc_replace(protos[sig], pods=group, interned_sig=sig)
            tpu_classes.append(cls)
            tpu_pods.extend(group)
        tracing.set_attrs(
            classes=len(tpu_classes), host_pods=len(host_pods), interned=interned
        )
        if not host_pods:
            return tpu_classes, tpu_pods, []
        if not tpu_pods:
            return None

        # isolation: no topology selector in either set may match labels in
        # the other (label sets are class-invariant, so representatives
        # suffice), and no PVC claim may span both sets (claim identity is
        # NOT class-invariant — check every pod)
        def selectors(pod: Pod):
            for constraint in pod.spec.topology_spread_constraints:
                yield constraint.label_selector
            if pod.spec.affinity is not None:
                for terms in (
                    pod.spec.affinity.pod_affinity,
                    pod.spec.affinity.pod_anti_affinity,
                ):
                    if terms is not None:
                        for term in terms.required + [
                            w.pod_affinity_term for w in terms.preferred
                        ]:
                            yield term.label_selector

        def claims(pod: Pod):
            return {
                (pod.namespace or "", v.persistent_volume_claim.claim_name)
                for v in pod.spec.volumes
                if v.persistent_volume_claim is not None
            }

        host_reps = [group[0] for group in unsupported.values()]
        tpu_reps = [group[0] for group in supported.values()]
        for reps, others in ((host_reps, tpu_reps), (tpu_reps, host_reps)):
            for rep in reps:
                for selector in selectors(rep):
                    if selector is not None and any(
                        selector.matches(o.metadata.labels) for o in others
                    ):
                        return None
        host_claims = set().union(*map(claims, host_pods)) if host_pods else set()
        tpu_claims = set().union(*map(claims, tpu_pods)) if tpu_pods else set()
        if host_claims & tpu_claims:
            return None
        return tpu_classes, tpu_pods, host_pods

    def _solve_host_remainder(
        self, host_pods: List[Pod], state_nodes, tpu_results, tpu_new_nodes,
        daemonset_pods: List[Pod], seed_topology: bool = False,
    ) -> SchedulingResults:
        """Host-oracle solve for the kernel-unsupported remainder, with the
        kernel's existing-node placements applied so capacity is not
        double-booked.  New nodes the kernel opened are not offered to the
        remainder (they are not launched yet); the remainder opens its own,
        but the kernel nodes' pessimistic capacity is charged against the
        provisioner limits first (subtractMax, scheduler.go:273-290) so the
        two solves cannot jointly overspend a limit.

        ``seed_topology`` records every kernel placement into the host
        topology's shared counts first (topology.go:120-143 semantics), which
        spread-residual pods need: unlike the encode-time split (isolated by
        construction), residuals share groups with kernel-placed pods, so the
        host's skew/affinity math must see where those pods landed."""
        from karpenter_core_tpu.solver.scheduler import _subtract_max

        adjusted = []
        for state_node in state_nodes:
            placed = tpu_results.existing_assignments.get(state_node.node.name)
            if placed:
                state_node = state_node.deep_copy()
                for pod in placed:
                    state_node.update_for_pod(pod)
                # a zone-less node the kernel committed (by placing pods under
                # a zone restriction) must read as committed here too — else
                # the two engines could pin the same node to different zones
                committed = tpu_results.existing_committed_zones.get(
                    state_node.node.name
                )
                if committed and labels_api.LABEL_TOPOLOGY_ZONE not in (
                    state_node.node.metadata.labels
                ):
                    state_node.node.metadata.labels[
                        labels_api.LABEL_TOPOLOGY_ZONE
                    ] = committed
            adjusted.append(state_node)
        scheduler = build_scheduler(
            self.kube_client,
            self.cloud_provider,
            self.cluster,
            host_pods,
            adjusted,
            daemonset_pods=daemonset_pods,
            recorder=self.recorder,
            opts=SchedulerOptions(),
        )
        for node in tpu_new_nodes:
            if node.provisioner_name in scheduler.remaining_resources:
                scheduler.remaining_resources[node.provisioner_name] = _subtract_max(
                    scheduler.remaining_resources[node.provisioner_name],
                    node.instance_type_options,
                )
        if seed_topology:
            self._seed_topology_from_kernel(
                scheduler.topology, tpu_results, tpu_new_nodes, adjusted
            )
        return scheduler.solve(host_pods)

    def _seed_topology_from_kernel(
        self, topology, tpu_results, tpu_new_nodes, adjusted_state_nodes
    ) -> None:
        """Commit the kernel's placements into the host topology counts.

        Existing-node placements record under the node's labels; new-node
        placements under the launchable's requirements (zone already pinned by
        decode) plus a synthetic unique hostname per pending node — hostname
        groups then see each kernel node as a frozen-count domain, exactly how
        an already-launched node would read.  Multi-zone nodes skip zone counts
        (domains.len() != 1), matching the reference's record rule
        (topology.go:129-136).  Kernel pods carrying anti-affinity terms also
        register inverse counts so residual pods they repel are blocked
        (topology.go:202-227)."""
        def seed(pod: Pod, requirements: Requirements, domains: dict) -> None:
            topology.record(pod, requirements)
            if pod_util.has_pod_anti_affinity(pod):
                topology._update_inverse_anti_affinity(pod, domains)

        # adjusted nodes carry the kernel's zone stamps — seed from those
        # labels, not the store's, so counts land in the committed zone
        by_name = {n.node.name: n.node for n in adjusted_state_nodes}
        for node_name, placed in tpu_results.existing_assignments.items():
            node = by_name.get(node_name) or self.kube_client.get_node(node_name)
            if node is None:
                continue
            requirements = Requirements.from_labels(node.metadata.labels)
            for pod in placed:
                seed(pod, requirements, node.metadata.labels)
        for i, launchable in enumerate(tpu_new_nodes):
            requirements = Requirements(*launchable.requirements.values())
            hostname = f"tpu-pending-{i}"
            requirements.add(
                Requirement(labels_api.LABEL_HOSTNAME, OP_IN, [hostname])
            )
            domains = {labels_api.LABEL_HOSTNAME: hostname}
            if requirements.has(labels_api.LABEL_TOPOLOGY_ZONE):
                zones = requirements.get(labels_api.LABEL_TOPOLOGY_ZONE)
                if zones.len() == 1:
                    domains[labels_api.LABEL_TOPOLOGY_ZONE] = zones.values_list()[0]
            for pod in launchable.pods:
                seed(pod, requirements, domains)

    def policy_config(self, provisioners=None):
        """The policy-objective config this reconcile runs under: env
        defaults overlaid by the highest-weight provisioner's ``spec.policy``
        block; KC_POLICY=0 kills the stage everywhere (policy.config)."""
        from karpenter_core_tpu.policy import PolicyConfig

        if provisioners is None:
            provisioners = self.kube_client.list_provisioners()
        return PolicyConfig.resolve(provisioners)

    def _emit_counterproposals(self, failed_pods: List[Pod]) -> None:
        """ShapeHint counter-proposals for unschedulable pods (docs/POLICY.md):
        when a bounded resize would fit a strictly cheaper fleet, say so —
        one event per distinct pod shape (not per pod: a 50k-replica batch
        failing identically is ONE proposal), plus
        ``karpenter_policy_counterproposals_total``."""
        if not failed_pods:
            return
        from karpenter_core_tpu.policy import propose_resize
        from karpenter_core_tpu.utils import resources as resources_util

        # one provisioner LIST serves both the config resolve and the catalog
        provisioners = self.kube_client.list_provisioners()
        policy = self.policy_config(provisioners)
        if not (policy.enabled and policy.counter_proposals):
            return
        catalog, seen_types = [], set()
        for provisioner in provisioners:
            for it in self.cloud_provider.get_instance_types(provisioner):
                if it.name not in seen_types:
                    seen_types.add(it.name)
                    catalog.append(it)
        proposed: dict = {}
        for pod in failed_pods:
            requests = resources_util.ceiling(pod)
            shape = tuple(sorted(requests.items()))
            if shape in proposed:
                continue
            proposed[shape] = None
            hint = propose_resize(requests, catalog, policy)
            if hint is None:
                continue
            POLICY_COUNTERPROPOSALS.labels("resize").inc()
            log.info(
                "counter-proposal for pod %s/%s: %s",
                pod.namespace, pod.name, hint.message(),
            )
            if self.recorder is not None:
                self.recorder.publish(evt.shape_hint(pod, hint.message()))

    def get_daemonset_pods(self) -> List[Pod]:
        """Representative daemonset pods for overhead calculation.  The
        reference lists DaemonSet objects (provisioner.go getDaemonSetPods); we
        derive from daemonset-owned pods in the store."""
        seen = {}
        for pod in self.kube_client.list_pods():
            if pod_util.is_owned_by_daemon_set(pod):
                owner = next(
                    (r.name for r in pod.metadata.owner_references if r.kind == "DaemonSet"),
                    pod.name,
                )
                seen.setdefault(owner, pod)
        return list(seen.values())

    # -- launch ---------------------------------------------------------------

    def launch_machines(self, machines) -> Tuple[List[str], Optional[str]]:
        """Parallel machine launches (provisioner.go:169-189)."""
        names: List[Optional[str]] = [None] * len(machines)
        errs: List[Optional[str]] = [None] * len(machines)

        def one(i: int) -> None:
            name, err = self.launch(machines[i])
            names[i] = name or ""
            errs[i] = err

        with tracing.span("provisioning.launch", machines=len(machines)) as sp:
            counted = tracing.enabled()  # off: the span is a flag check, so are its counts
            rebuilds0 = STATE_NODE_REBUILDS.labels().value if counted else 0.0
            rebuild_pods0 = STATE_NODE_REBUILD_PODS.labels().value if counted else 0.0
            if len(machines) == 1:
                one(0)
            else:
                with ThreadPoolExecutor(max_workers=min(len(machines), 32)) as pool:
                    list(pool.map(one, range(len(machines))))
            if counted:
                launched = [m for m, name in zip(machines, names) if name]
                sp.set(
                    created=len(launched),
                    # one Nominated event per pod of a launched machine
                    events=(
                        sum(len(m.pods) for m in launched) if self.recorder is not None else 0
                    ),
                    # every rebuild of a state node while the launch ran, the
                    # informer's and the node controller's beside launch's own
                    state_rebuilds=int(STATE_NODE_REBUILDS.labels().value - rebuilds0),
                    # the pods those rebuilds read from the store: what is
                    # bound to the launched nodes, 0 on a fresh fleet
                    rebuild_pods=int(
                        STATE_NODE_REBUILD_PODS.labels().value - rebuild_pods0
                    ),
                )
        messages = [e for e in errs if e]
        return [n or "" for n in names], ("; ".join(messages) if messages else None)

    def launch(self, machine_node) -> Tuple[Optional[str], Optional[str]]:
        """Launch one machine and pre-create its node (provisioner.go:311-358)."""
        latest = self.kube_client.get(ProvisionerCRD, machine_node.provisioner_name)
        if latest is None:
            return None, f"provisioner {machine_node.provisioner_name} not found"
        if latest.spec.limits is not None:
            err = latest.spec.limits.exceeded_by(latest.status.resources)
            if err is not None:
                return None, err

        template = machine_node.template
        template.instance_type_options = machine_node.instance_type_options
        template.requests = machine_node.requests
        machine = template.to_machine(latest)
        try:
            created = self.cloud_provider.create(machine)
        except Exception as e:  # noqa: BLE001 - cloud errors surface as strings
            return None, f"creating cloud provider instance, {e}"

        # merge the template's node view into the provider's (provisioner.go:
        # 331-335 mergo.Merge): provider-resolved labels win, the template
        # backfills the rest — including single-valued requirement labels
        # (e.g. custom provisioner requirements) and annotations
        template_node = template.to_node()
        node = Node(
            metadata=created.metadata,
            spec=template_node.spec,
            status=NodeStatus(),
        )
        for key, value in template_node.metadata.labels.items():
            node.metadata.labels.setdefault(key, value)
        for key, value in template_node.metadata.annotations.items():
            node.metadata.annotations.setdefault(key, value)
        node.metadata.finalizers = [labels_api.TERMINATION_FINALIZER]
        node.spec.provider_id = created.status.provider_id

        # idempotent node pre-create (provisioner.go:338-348): already-exists
        # is tolerable only when it IS this machine (same provider id).  With
        # the durable apiserver backend, node objects outlive the process
        # while a fresh fake/cloud name sequence restarts — adopting a
        # same-name-different-instance node would corrupt cluster state with
        # a phantom, so that collision fails the launch (the next attempt
        # draws a fresh name)
        from karpenter_core_tpu.operator.kubeclient import ConflictError

        try:
            self.kube_client.create(node)
        except ConflictError:
            # a 409 with no cached object means the conflicting node hasn't
            # reached the watch cache yet (apiserver backend lag) — its
            # identity is unknown, so adopting it would be exactly the
            # corruption this guard exists to prevent; error out and let the
            # requeue retry once the cache catches up
            existing = self.kube_client.get_node(node.name)
            if existing is None or existing.spec.provider_id != node.spec.provider_id:
                self._abandon_machine(created)
                return None, (
                    f"node name {node.name} already taken by "
                    f"{existing.spec.provider_id if existing else 'an unsynced object'}; "
                    f"launch produced {node.spec.provider_id}"
                )
            log.debug("node already registered")
        except Exception as e:  # noqa: BLE001 - surfaced to the caller
            # compensate ONLY when the write provably did not land: the cache
            # read alone cannot distinguish "server doesn't own the node"
            # from "watch cache is behind" (the 409 branch above documents
            # exactly that lag), so deleting the machine on a cache miss
            # after an ambiguous transport death could strand a committed
            # node object on a dead instance — the phantom this guard
            # exists to prevent.  Provably-failed = not visibly ours AND the
            # error says the server never applied the write (a pre-write
            # injected fault, or the server itself answered 4xx).  Anything
            # connection-level is ambiguous: keep the machine — the watch
            # either delivers the node or the machine surfaces as a leak in
            # the audit, both recoverable; a phantom is not.
            try:
                existing = self.kube_client.get_node(node.name)
            except Exception:  # noqa: BLE001 - read failure: stay ambiguous
                existing = None
            visibly_ours = (
                existing is not None
                and existing.spec.provider_id == node.spec.provider_id
            )
            if not visibly_ours:
                if _node_write_rejected(e):
                    self._abandon_machine(created)
                else:
                    log.warning(
                        "node %s create outcome ambiguous (%s: %s); keeping "
                        "machine %s pending the watch",
                        node.name, type(e).__name__, e,
                        created.status.provider_id,
                    )
            return None, f"creating node {node.name}, {e}"
        err = self.cluster.update_node(node)
        if err is not None:
            return None, f"updating cluster state, {err}"
        self.cluster.nominate_node_for_pod(node.name)
        if self.recorder is not None:
            for pod in machine_node.pods:
                self.recorder.publish(evt.nominate_pod(pod, node))
        return node.name, None

    def _abandon_machine(self, created) -> None:
        """Compensate a node pre-create that provably never landed by
        deleting the just-launched cloud instance — otherwise a kubeapi
        fault landing between cloud.create and the node POST strands the
        machine forever (no node object ever points at it, so no termination
        path will).  Best-effort: a failed delete is retried by nothing, but
        the chaos matrix's leak invariant is what surfaced the gap."""
        try:
            self.cloud_provider.delete(created)
        except Exception as e:  # noqa: BLE001 - compensation must not mask the launch error
            log.warning(
                "abandoning machine %s after failed node create: %s",
                created.status.provider_id, e,
            )
