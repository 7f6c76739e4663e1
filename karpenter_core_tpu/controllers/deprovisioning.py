"""Deprovisioning: expiration, drift, emptiness, and consolidation.

Mirror of /root/reference/pkg/controllers/deprovisioning/: a singleton polling
loop runs an ordered method chain — Expiration → Drift → Emptiness →
EmptyNodeConsolidation → MultiNodeConsolidation → SingleNodeConsolidation —
and the first method that acts wins (controller.go:142-193).  Every disruption
is validated by scheduling *simulation* (helpers.go:42-115 simulateScheduling
reuses the solver in simulation mode), re-checked after a 15s TTL
(validation.go), and executed as launch-replacements → cordon → mark →
wait-initialized → delete → wait-deleted (controller.go:219-329).
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from enum import Enum
from typing import Callable, List, Optional, Tuple

from karpenter_core_tpu import tracing
from karpenter_core_tpu.apis import labels as labels_api
from karpenter_core_tpu.apis.objects import Node, Pod, PodDisruptionBudget
from karpenter_core_tpu.apis.v1alpha5 import Provisioner
from karpenter_core_tpu.cloudprovider import CloudProvider, InstanceType
from karpenter_core_tpu.controllers.provisioning import ProvisioningController
from karpenter_core_tpu.events import events as evt
from karpenter_core_tpu.metrics import REGISTRY, measure
from karpenter_core_tpu.scheduling import Requirement, Requirements
from karpenter_core_tpu.solver.builder import build_scheduler
from karpenter_core_tpu.solver.scheduler import SchedulerOptions
from karpenter_core_tpu.state.cluster import Cluster, StateNode
from karpenter_core_tpu.utils import node as node_util
from karpenter_core_tpu.utils import pod as pod_util
from karpenter_core_tpu.utils import retry
from karpenter_core_tpu.utils.clock import Clock

log = logging.getLogger(__name__)

POLLING_PERIOD = 10.0  # controller.go:64
CONSOLIDATION_TTL = 15.0  # consolidation.go:64
WAIT_RETRY_ATTEMPTS = 60  # controller.go:71-76 (~9.5 min)
WAIT_RETRY_DELAY = 2.0
WAIT_RETRY_MAX_DELAY = 10.0

DEGRADED_PAUSES = REGISTRY.counter(
    "karpenter_degraded_pauses_total",
    "Deprovisioning reconciles skipped because the solver-backend circuit "
    "breaker was open (disruption is optional work; a degraded control "
    "plane must not act on stale simulations).",
)

EVALUATION_DURATION = REGISTRY.histogram(
    "karpenter_deprovisioning_evaluation_duration_seconds",
    "Duration of the deprovisioning evaluation process in seconds.",
    ("method",),
)
ACTIONS_PERFORMED = REGISTRY.counter(
    "karpenter_deprovisioning_actions_performed",
    "Number of deprovisioning actions performed.",
    ("action",),
)
REPLACEMENT_INITIALIZED = REGISTRY.histogram(
    "karpenter_deprovisioning_replacement_node_initialized_seconds",
    "Amount of time required for a replacement node to become initialized.",
)
NODES_TERMINATED = REGISTRY.counter(
    "karpenter_nodes_terminated", "Number of nodes terminated in total by Karpenter.", ("reason",)
)


class Result(Enum):
    NOTHING_TO_DO = "nothing-to-do"
    RETRY = "retry"
    FAILED = "failed"
    SUCCESS = "success"


class Action(Enum):
    FAILED = "failed"
    DELETE = "delete"
    REPLACE = "replace"
    RETRY = "retry"
    DO_NOTHING = "do nothing"


@dataclass
class CandidateNode:
    """A node considered for deprovisioning (controller.go:130-139)."""

    node: Node
    state_node: StateNode
    instance_type: InstanceType
    capacity_type: str
    zone: str
    provisioner: Provisioner
    disruption_cost: float
    pods: List[Pod] = field(default_factory=list)


@dataclass
class Command:
    action: Action = Action.DO_NOTHING
    nodes_to_remove: List[Node] = field(default_factory=list)
    replacement_nodes: list = field(default_factory=list)  # SchedulingNode

    def __str__(self) -> str:
        names = ", ".join(n.name for n in self.nodes_to_remove)
        return f"{self.action.value}, terminating {len(self.nodes_to_remove)} nodes {names}"


class CandidateNodeDeleting(Exception):
    pass


# --- helpers (helpers.go) ------------------------------------------------------


def get_pod_eviction_cost(pod: Pod) -> float:
    """Pod-deletion-cost and priority scaled into [-10, 10] (helpers.go:125-146)."""
    cost = 1.0
    deletion_cost = pod.metadata.annotations.get("controller.kubernetes.io/pod-deletion-cost")
    if deletion_cost is not None:
        try:
            cost += float(deletion_cost) / (2.0**27)
        except ValueError:
            log.error("parsing pod-deletion-cost %r", deletion_cost)
    if pod.spec.priority is not None:
        cost += float(pod.spec.priority) / (2.0**25)
    return max(-10.0, min(cost, 10.0))


def disruption_cost(pods: List[Pod]) -> float:
    return sum(get_pod_eviction_cost(p) for p in pods)


def lifetime_remaining(candidate_node: Node, provisioner: Provisioner, clock: Clock) -> float:
    """Fraction of node lifetime remaining; expiring nodes cost less to disrupt
    (helpers.go:276-287)."""
    if provisioner.spec.ttl_seconds_until_expired is None:
        return 1.0
    age = clock.now() - candidate_node.metadata.creation_timestamp
    total = float(provisioner.spec.ttl_seconds_until_expired)
    return max(0.0, min((total - age) / total, 1.0))


def worst_launch_price(offerings, requirements: Requirements) -> float:
    """Spot-preferred worst-case launch price (helpers.go:292-315)."""
    ct = requirements.get(labels_api.LABEL_CAPACITY_TYPE)
    zone = requirements.get(labels_api.LABEL_TOPOLOGY_ZONE)
    if ct.has(labels_api.CAPACITY_TYPE_SPOT):
        spot = [
            o
            for o in offerings
            if o.capacity_type == labels_api.CAPACITY_TYPE_SPOT and zone.has(o.zone)
        ]
        if spot:
            return max(o.price for o in spot)
    if ct.has(labels_api.CAPACITY_TYPE_ON_DEMAND):
        od = [
            o
            for o in offerings
            if o.capacity_type == labels_api.CAPACITY_TYPE_ON_DEMAND and zone.has(o.zone)
        ]
        if od:
            return max(o.price for o in od)
    return float("inf")


def filter_by_price(
    options: List[InstanceType], requirements: Requirements, price: float
) -> List[InstanceType]:
    return [
        it
        for it in options
        if worst_launch_price(it.offerings.available(), requirements) < price
    ]


def instance_types_are_subset(lhs: List[InstanceType], rhs: List[InstanceType]) -> bool:
    return {it.name for it in lhs} <= {it.name for it in rhs}


class PDBLimits:
    """Snapshot of PodDisruptionBudgets (pdblimits.go:28-89)."""

    def __init__(self, kube_client) -> None:
        self.pdbs = kube_client.list(PodDisruptionBudget)

    def can_evict_pods(self, pods: List[Pod]) -> Tuple[Optional[str], bool]:
        for pod in pods:
            for pdb in self.pdbs:
                if pdb.metadata.namespace != pod.namespace:
                    continue
                if pdb.spec.selector is not None and pdb.spec.selector.matches(
                    pod.metadata.labels
                ):
                    if pdb.status.disruptions_allowed == 0:
                        return f"{pdb.metadata.namespace}/{pdb.metadata.name}", False
        return None, True


def pods_prevent_eviction(pods: List[Pod]) -> Tuple[str, bool]:
    """do-not-evict pods block termination (helpers.go:353-367)."""
    for p in pods:
        if pod_util.is_terminating(p) or pod_util.is_terminal(p) or pod_util.is_owned_by_node(p):
            continue
        if pod_util.has_do_not_evict(p):
            return f"pod {p.namespace}/{p.name} has do-not-evict annotation", True
    return "", False


def can_be_terminated(candidate: CandidateNode, pdbs: PDBLimits) -> Tuple[str, bool]:
    if candidate.node.metadata.deletion_timestamp is not None:
        return "in the process of deletion", False
    pdb, ok = pdbs.can_evict_pods(candidate.pods)
    if not ok:
        return f"pdb {pdb} prevents pod evictions", False
    reason, prevented = pods_prevent_eviction(candidate.pods)
    if prevented:
        return reason, False
    return "", True


def candidate_nodes(
    cluster: Cluster,
    kube_client,
    clock: Clock,
    cloud_provider: CloudProvider,
    should_deprovision: Callable,
) -> List[CandidateNode]:
    """Eligibility pipeline (helpers.go:171-249): owned, known instance type /
    zone / capacity type, initialized, not nominated, not marked."""
    provisioners = {p.name: p for p in kube_client.list_provisioners()}
    instance_types = {
        name: {it.name: it for it in cloud_provider.get_instance_types(p)}
        for name, p in provisioners.items()
    }
    out: List[CandidateNode] = []

    def visit(state_node: StateNode) -> bool:
        node = state_node.node
        provisioner_name = node.metadata.labels.get(labels_api.PROVISIONER_NAME_LABEL_KEY)
        provisioner = provisioners.get(provisioner_name or "")
        if state_node.marked():
            return True
        if provisioner is None:
            return True
        it = instance_types[provisioner.name].get(
            node.metadata.labels.get(labels_api.LABEL_INSTANCE_TYPE_STABLE, "")
        )
        if it is None:
            return True
        ct = node.metadata.labels.get(labels_api.LABEL_CAPACITY_TYPE)
        zone = node.metadata.labels.get(labels_api.LABEL_TOPOLOGY_ZONE)
        if not ct or not zone:
            return True
        if not state_node.initialized():
            return True
        if state_node.nominated(clock):
            return True
        pods = node_util.get_node_pods(kube_client, node)
        if not should_deprovision(state_node, provisioner, pods):
            return True
        cost = disruption_cost(pods) * lifetime_remaining(node, provisioner, clock)
        out.append(
            CandidateNode(
                node=node,
                state_node=state_node,
                instance_type=it,
                capacity_type=ct,
                zone=zone,
                provisioner=provisioner,
                pods=pods,
                disruption_cost=cost,
            )
        )
        return True

    cluster.for_each_node(visit)
    return out


def map_nodes(nodes: List[Node], candidates: List[CandidateNode]) -> List[CandidateNode]:
    names = {n.name for n in nodes}
    return [c for c in candidates if c.node.name in names]


def simulate_scheduling(
    kube_client,
    cluster: Cluster,
    provisioning: ProvisioningController,
    *nodes_to_delete: CandidateNode,
) -> Tuple[list, bool]:
    """Snapshot minus candidates; pods = pending + on-candidates + on-deleting;
    solve in simulation mode; fail when results rely on an uninitialized node
    (helpers.go:42-115).  Raises CandidateNodeDeleting on the race."""
    candidate_names = {c.node.name for c in nodes_to_delete}
    state_nodes = []
    deleting_nodes = []
    candidate_is_deleting = False

    def visit(n: StateNode) -> bool:
        nonlocal candidate_is_deleting
        if n.node.name not in candidate_names:
            if not n.marked():
                state_nodes.append(n.deep_copy())
            else:
                deleting_nodes.append(n.deep_copy())
        elif n.marked():
            candidate_is_deleting = True
        return True

    cluster.for_each_node(visit)
    if candidate_is_deleting:
        raise CandidateNodeDeleting()

    pods = provisioning.get_pending_pods()
    for candidate in nodes_to_delete:
        pods.extend(candidate.pods)
    pods.extend(
        node_util.get_node_pods(kube_client, *(n.node for n in deleting_nodes))
    )

    scheduler = build_scheduler(
        kube_client,
        provisioning.cloud_provider,
        cluster,
        pods,
        state_nodes,
        daemonset_pods=provisioning.get_daemonset_pods(),
        opts=SchedulerOptions(simulation_mode=True),
    )
    results = scheduler.solve(pods)

    scheduled = sum(len(n.pods) for n in results.new_nodes) + sum(
        len(n.pods) for n in results.existing_nodes
    )
    # relying on a not-yet-initialized in-flight node is not allowed
    for existing in results.existing_nodes:
        if existing.pods and existing.node.metadata.labels.get(
            labels_api.LABEL_NODE_INITIALIZED
        ) != "true":
            return results.new_nodes, False
    return results.new_nodes, scheduled == len(pods)


def get_node_prices(nodes: List[CandidateNode]) -> Tuple[float, Optional[str]]:
    price = 0.0
    for n in nodes:
        offering = n.instance_type.offerings.get(n.capacity_type, n.zone)
        if offering is None:
            return 0.0, (
                f"unable to determine offering for {n.instance_type.name}/"
                f"{n.capacity_type}/{n.zone}"
            )
        price += offering.price
    return price, None


# --- reporter (reporter.go) ------------------------------------------------------


class Reporter:
    """Dedupes 'why not consolidatable' events (reporter.go:35-53)."""

    def __init__(self, recorder, clock: Clock) -> None:
        self.recorder = recorder
        self.clock = clock
        self._seen = {}

    def record_unconsolidatable(self, node: Node, reason: str) -> None:
        key = (node.name, reason)
        now = self.clock.now()
        if key in self._seen and now - self._seen[key] < 15 * 60:
            return
        self._seen[key] = now
        if self.recorder is not None:
            self.recorder.publish(evt.unconsolidatable(node, reason))


# --- deprovisioners ---------------------------------------------------------------


class Expiration:
    """Delete/replace nodes past TTLSecondsUntilExpired, oldest first
    (expiration.go:56-130)."""

    name = "expiration"

    def __init__(self, clock, kube_client, cluster, provisioning) -> None:
        self.clock = clock
        self.kube_client = kube_client
        self.cluster = cluster
        self.provisioning = provisioning

    def should_deprovision(self, state_node, provisioner, pods) -> bool:
        return self.clock.now() > _expiration_time(state_node.node, provisioner)

    def compute_command(self, candidates: List[CandidateNode]) -> Command:
        candidates = sorted(
            candidates, key=lambda c: _expiration_time(c.node, c.provisioner)
        )
        pdbs = PDBLimits(self.kube_client)
        for candidate in candidates:
            _, ok = can_be_terminated(candidate, pdbs)
            if not ok:
                continue
            try:
                new_nodes, all_scheduled = simulate_scheduling(
                    self.kube_client, self.cluster, self.provisioning, candidate
                )
            except CandidateNodeDeleting:
                continue
            if not all_scheduled:
                log.debug("continuing to expire node %s despite failed simulation", candidate.node.name)
            if not new_nodes:
                return Command(Action.DELETE, [candidate.node])
            return Command(Action.REPLACE, [candidate.node], new_nodes)
        return Command(Action.DO_NOTHING)


def _expiration_time(node: Node, provisioner: Optional[Provisioner]) -> float:
    if provisioner is None or provisioner.spec.ttl_seconds_until_expired is None:
        return float("inf")
    return node.metadata.creation_timestamp + provisioner.spec.ttl_seconds_until_expired


class Drift:
    """Feature-gated; acts on the drifted voluntary-disruption annotation
    (drift.go:50-105)."""

    name = "drift"

    def __init__(self, kube_client, cluster, provisioning, settings) -> None:
        self.kube_client = kube_client
        self.cluster = cluster
        self.provisioning = provisioning
        self.settings = settings

    def should_deprovision(self, state_node, provisioner, pods) -> bool:
        if not self.settings.drift_enabled:
            return False
        return (
            state_node.node.metadata.annotations.get(
                labels_api.VOLUNTARY_DISRUPTION_ANNOTATION_KEY
            )
            == labels_api.VOLUNTARY_DISRUPTION_DRIFTED_ANNOTATION_VALUE
        )

    def compute_command(self, candidates: List[CandidateNode]) -> Command:
        pdbs = PDBLimits(self.kube_client)
        for candidate in candidates:
            _, ok = can_be_terminated(candidate, pdbs)
            if not ok:
                continue
            try:
                new_nodes, all_scheduled = simulate_scheduling(
                    self.kube_client, self.cluster, self.provisioning, candidate
                )
            except CandidateNodeDeleting:
                continue
            if not all_scheduled:
                log.debug("terminating drifted node %s despite failed simulation", candidate.node.name)
            if not new_nodes:
                return Command(Action.DELETE, [candidate.node])
            return Command(Action.REPLACE, [candidate.node], new_nodes)
        return Command(Action.DO_NOTHING)


class Emptiness:
    """TTL-based removal of empty nodes via the emptiness-timestamp annotation
    (emptiness.go:52-90)."""

    name = "emptiness"

    def __init__(self, clock, kube_client, cluster) -> None:
        self.clock = clock
        self.kube_client = kube_client
        self.cluster = cluster

    def should_deprovision(self, state_node, provisioner, pods) -> bool:
        if provisioner is None or provisioner.spec.ttl_seconds_after_empty is None or pods:
            return False
        timestamp = state_node.node.metadata.annotations.get(
            labels_api.EMPTINESS_TIMESTAMP_ANNOTATION_KEY
        )
        if timestamp is None:
            return False
        try:
            emptiness_time = float(timestamp)
        except ValueError:
            log.error("unable to parse emptiness timestamp %r", timestamp)
            return True
        return self.clock.now() > emptiness_time + provisioner.spec.ttl_seconds_after_empty

    def compute_command(self, candidates: List[CandidateNode]) -> Command:
        empty = [c for c in candidates if not c.pods]
        if not empty:
            return Command(Action.DO_NOTHING)
        return Command(Action.DELETE, [c.node for c in empty])


class _ConsolidationBase:
    """Shared consolidation logic (consolidation.go:55-290)."""

    name = "consolidation"

    def __init__(self, clock, cluster, kube_client, provisioning, cloud_provider, reporter) -> None:
        self.clock = clock
        self.cluster = cluster
        self.kube_client = kube_client
        self.provisioning = provisioning
        self.cloud_provider = cloud_provider
        self.reporter = reporter
        self.last_consolidation_state = -1.0

    def record_last_state(self, state: float) -> None:
        self.last_consolidation_state = state

    def should_attempt(self) -> bool:
        return self.last_consolidation_state != self.cluster.cluster_consolidation_state()

    def should_deprovision(self, state_node, provisioner, pods) -> bool:
        annotation = state_node.node.metadata.annotations.get(
            labels_api.DO_NOT_CONSOLIDATE_NODE_ANNOTATION_KEY
        )
        if annotation is not None:
            self.reporter.record_unconsolidatable(
                state_node.node,
                f"{labels_api.DO_NOT_CONSOLIDATE_NODE_ANNOTATION_KEY} annotation exists",
            )
            return annotation != "true"
        if provisioner is None:
            self.reporter.record_unconsolidatable(state_node.node, "provisioner is unknown")
            return False
        if provisioner.spec.consolidation is None or not provisioner.spec.consolidation.enabled:
            self.reporter.record_unconsolidatable(
                state_node.node,
                f"provisioner {provisioner.name} has consolidation disabled",
            )
            return False
        return True

    def sort_and_filter_candidates(self, candidates: List[CandidateNode]) -> List[CandidateNode]:
        pdbs = PDBLimits(self.kube_client)
        filtered = []
        for c in candidates:
            reason, ok = can_be_terminated(c, pdbs)
            if not ok:
                self.reporter.record_unconsolidatable(c.node, reason)
                continue
            filtered.append(c)
        return sorted(filtered, key=lambda c: c.disruption_cost)

    def compute_consolidation(self, *nodes: CandidateNode) -> Command:
        """Simulate → delete if 0 new nodes / replace if exactly 1 cheaper node;
        spot→spot forbidden; OD→[OD,spot] forces spot (consolidation.go:190-290)."""
        done = measure(EVALUATION_DURATION.labels("Replace/Delete"))
        try:
            try:
                new_nodes, all_scheduled = simulate_scheduling(
                    self.kube_client, self.cluster, self.provisioning, *nodes
                )
            except CandidateNodeDeleting:
                return Command(Action.DO_NOTHING)
            if not all_scheduled:
                if len(nodes) == 1:
                    self.reporter.record_unconsolidatable(
                        nodes[0].node, "not all pods would schedule"
                    )
                return Command(Action.DO_NOTHING)
            if not new_nodes:
                return Command(Action.DELETE, [n.node for n in nodes])
            if len(new_nodes) != 1:
                if len(nodes) == 1:
                    self.reporter.record_unconsolidatable(
                        nodes[0].node,
                        f"can't remove without creating {len(new_nodes)} nodes",
                    )
                return Command(Action.DO_NOTHING)

            nodes_price, err = get_node_prices(list(nodes))
            if err is not None:
                log.error("getting offering price from candidate node, %s", err)
                return Command(Action.FAILED)
            replacement = new_nodes[0]
            replacement.instance_type_options = filter_by_price(
                replacement.instance_type_options, replacement.requirements, nodes_price
            )
            if not replacement.instance_type_options:
                if len(nodes) == 1:
                    self.reporter.record_unconsolidatable(
                        nodes[0].node, "can't replace with a cheaper node"
                    )
                return Command(Action.DO_NOTHING)

            all_existing_spot = all(
                n.capacity_type == labels_api.CAPACITY_TYPE_SPOT for n in nodes
            )
            ct_req = replacement.requirements.get(labels_api.LABEL_CAPACITY_TYPE)
            if all_existing_spot and ct_req.has(labels_api.CAPACITY_TYPE_SPOT):
                if len(nodes) == 1:
                    self.reporter.record_unconsolidatable(
                        nodes[0].node, "can't replace a spot node with a spot node"
                    )
                return Command(Action.DO_NOTHING)

            # OD→[OD,spot]: pin to spot so a more expensive OD can't launch
            if ct_req.has(labels_api.CAPACITY_TYPE_SPOT) and ct_req.has(
                labels_api.CAPACITY_TYPE_ON_DEMAND
            ):
                replacement.requirements.add(
                    Requirement(
                        labels_api.LABEL_CAPACITY_TYPE, "In", [labels_api.CAPACITY_TYPE_SPOT]
                    )
                )
            return Command(Action.REPLACE, [n.node for n in nodes], new_nodes)
        finally:
            done()

    def validate_command(self, cmd: Command, candidates: List[CandidateNode]) -> bool:
        """Re-simulation shape check (validation.go:110-172)."""
        nodes_to_delete = map_nodes(cmd.nodes_to_remove, candidates)
        if not nodes_to_delete:
            return False
        try:
            new_nodes, all_scheduled = simulate_scheduling(
                self.kube_client, self.cluster, self.provisioning, *nodes_to_delete
            )
        except CandidateNodeDeleting:
            return False
        if not all_scheduled:
            return False
        if not new_nodes:
            return not cmd.replacement_nodes
        if len(new_nodes) > 1:
            return False
        if not cmd.replacement_nodes:
            return False
        return instance_types_are_subset(
            cmd.replacement_nodes[0].instance_type_options, new_nodes[0].instance_type_options
        )


class Validation:
    """TTL-delayed revalidation (validation.go:36-107)."""

    def __init__(self, period, clock, cluster, kube_client, provisioning, cloud_provider, base) -> None:
        self.period = period
        self.clock = clock
        self.cluster = cluster
        self.kube_client = kube_client
        self.provisioning = provisioning
        self.cloud_provider = cloud_provider
        self.base = base
        self.start: Optional[float] = None
        self.candidates: List[CandidateNode] = []

    def should_deprovision(self, state_node, provisioner, pods) -> bool:
        annotation = state_node.node.metadata.annotations.get(
            labels_api.DO_NOT_CONSOLIDATE_NODE_ANNOTATION_KEY
        )
        if annotation is not None:
            return annotation != "true"
        return (
            provisioner is not None
            and provisioner.spec.consolidation is not None
            and provisioner.spec.consolidation.enabled
        )

    def is_valid(self, cmd: Command) -> bool:
        if self.start is None:
            self.start = self.clock.now()
        wait = self.period - (self.clock.now() - self.start)
        if wait > 0:
            self.clock.sleep(wait)
        if not self.candidates:
            self.candidates = candidate_nodes(
                self.cluster,
                self.kube_client,
                self.clock,
                self.cloud_provider,
                self.should_deprovision,
            )
        for node in cmd.nodes_to_remove:
            if self.cluster.is_node_nominated(node.name):
                return False
        return self.base.validate_command(cmd, self.candidates)


class SingleNodeConsolidation(_ConsolidationBase):
    """Cheapest-disruption-first, first valid delete/replace wins
    (singlenodeconsolidation.go:43-85)."""

    name = "consolidation"

    def compute_command(self, candidates: List[CandidateNode]) -> Command:
        if not self.should_attempt():
            return Command(Action.DO_NOTHING)
        candidates = self.sort_and_filter_candidates(candidates)
        validation = Validation(
            CONSOLIDATION_TTL, self.clock, self.cluster, self.kube_client,
            self.provisioning, self.cloud_provider, self,
        )
        failed_validation = False
        for candidate in candidates:
            cmd = self.compute_consolidation(candidate)
            if cmd.action in (Action.DO_NOTHING, Action.RETRY, Action.FAILED):
                continue
            if not validation.is_valid(cmd):
                failed_validation = True
                continue
            if cmd.action in (Action.REPLACE, Action.DELETE):
                return cmd
        if failed_validation:
            return Command(Action.RETRY)
        return Command(Action.DO_NOTHING)


class MultiNodeConsolidation(_ConsolidationBase):
    """Binary search over the first-N disruption-sorted prefix for the largest
    simultaneously-consolidatable set, m→1 replacement only
    (multinodeconsolidation.go:41-165).  With ``use_tpu_kernel`` the search
    runs as a parallel subset sweep on device (solver.consolidation) and only
    the TTL validation stays on the host path."""

    name = "consolidation"
    use_tpu_kernel = False
    # remote sweep: ship /Consolidate to the solver service instead of
    # compiling in-process (set alongside use_tpu_kernel by the controller)
    solver_endpoint = ""
    _solver_client = None
    # the solver-backend circuit breaker, SHARED with the provisioning
    # controller (set by DeprovisioningController) — one backend, one
    # verdict; None (standalone construction) means no gating
    solver_breaker: Optional[retry.CircuitBreaker] = None

    def compute_command(self, candidates: List[CandidateNode]) -> Command:
        if not self.should_attempt():
            return Command(Action.DO_NOTHING)
        candidates = self.sort_and_filter_candidates(candidates)
        cmd = None
        if self.use_tpu_kernel:
            cmd = self._tpu_search(candidates)
        if cmd is None:
            cmd = self.first_n_consolidation_option(candidates, len(candidates))
        if cmd.action == Action.DO_NOTHING:
            return cmd
        validation = Validation(
            CONSOLIDATION_TTL, self.clock, self.cluster, self.kube_client,
            self.provisioning, self.cloud_provider, self,
        )
        if not validation.is_valid(cmd):
            return Command(Action.RETRY)
        return cmd

    def _tpu_search(self, candidates: List[CandidateNode]) -> Optional[Command]:
        """Device subset sweep — remote over the snapshot channel when a
        solver service is configured, in-process otherwise; None falls back
        to the host binary search."""
        from karpenter_core_tpu.models.snapshot import KernelUnsupported
        from karpenter_core_tpu.solver.consolidation import TPUConsolidationSearch

        if len(candidates) < 2:
            return Command(Action.DO_NOTHING)
        if self.solver_breaker is not None and not self.solver_breaker.allow():
            # breaker open: don't touch the dead backend — host binary search
            return None
        try:
            if self.solver_endpoint:
                cmd = self._remote_search(candidates)
                if cmd is None:
                    # no backend verdict: free a half-open trial slot
                    if self.solver_breaker is not None:
                        self.solver_breaker.release_trial()
                    return None  # service judged the shape kernel-unsupported
            else:
                provisioners = self.kube_client.list_provisioners()
                search = TPUConsolidationSearch(
                    self.cloud_provider, provisioners,
                    # policy objective: lanes score by fleet-cost delta
                    # instead of node count when enabled (docs/POLICY.md);
                    # resolved per sweep like provisioning resolves per batch
                    policy=self._policy_config(provisioners),
                )
                cmd = search.compute_command(
                    candidates,
                    pending_pods=self.provisioning.get_pending_pods(),
                    state_nodes=self.cluster.snapshot_nodes(),
                    bound_pods=self.kube_client.list_pods(),
                )
        except KernelUnsupported as e:
            log.debug("TPU consolidation unsupported for cluster shape, %s", e)
            if self.solver_breaker is not None:
                self.solver_breaker.release_trial()  # shape verdict, not backend
            return None
        except Exception as e:  # backend init/dispatch faults: host binary search
            if self.solver_breaker is not None:
                self.solver_breaker.record_failure()
                state = self.solver_breaker.state
            else:
                state = "unbrokered"
            log.warning(
                "TPU consolidation sweep failed (%s: %s); falling back to the "
                "host binary search (breaker %s)",
                type(e).__name__, e, state,
            )
            return None
        if self.solver_breaker is not None:
            self.solver_breaker.record_success()
        return cmd

    def _policy_config(self, provisioners):
        """The policy-objective config for this sweep: the provisioning
        controller's resolver when it exposes one (one fleet, one objective),
        else env defaults (standalone / stub embeddings)."""
        resolver = getattr(self.provisioning, "policy_config", None)
        if resolver is not None:
            return resolver(provisioners)
        from karpenter_core_tpu.policy import PolicyConfig

        return PolicyConfig.resolve(provisioners)

    def _remote_search(self, candidates: List[CandidateNode]) -> Optional[Command]:
        """Ship the sweep to the solver service (/Consolidate).  Returns None
        on FAILED_PRECONDITION (host binary search takes over); transport
        faults propagate to _tpu_search's failure breaker."""
        import grpc

        from karpenter_core_tpu.apis import codec

        client = self._solver_client
        if client is None:
            from karpenter_core_tpu.service.snapshot_channel import (
                SnapshotSolverClient,
            )

            client = self._solver_client = SnapshotSolverClient(self.solver_endpoint)

        provisioners = self.kube_client.list_provisioners()
        state_nodes = self.cluster.snapshot_nodes()
        bound_pods = self.kube_client.list_pods()
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
                "pods": [codec.pod_to_dict(p) for p in bound_by_node.get(sn.node.name, [])],
                "volumeLimits": dict(sn.volume_limits()),
            }
            for sn in state_nodes
        ]
        pending = self.provisioning.get_pending_pods()
        daemonset_pods = self.provisioning.get_daemonset_pods()
        wire_candidates = [
            {
                "name": c.node.name,
                "instanceType": c.instance_type.name if c.instance_type else "",
                "capacityType": c.capacity_type,
                "zone": c.zone,
                "provisioner": c.provisioner.name,
                "disruptionCost": float(c.disruption_cost),
            }
            for c in candidates
        ]
        try:
            response = client.consolidate(
                wire_candidates, pending, provisioners,
                nodes=nodes,
                claim_drivers=self.provisioning._claim_drivers(bound_pods + pending),
                # same policy the in-process sweep would run under — remote
                # lanes score by fleet-cost delta too (PR 9 leftover: the
                # config previously never crossed the channel)
                policy=self._policy_config(provisioners),
            )
        except grpc.RpcError as e:
            if e.code() == grpc.StatusCode.FAILED_PRECONDITION:
                log.debug("remote consolidation: kernel unsupported (%s)", e.details())
                return None
            raise

        action = Action(response["action"])
        if action == Action.DO_NOTHING:
            return Command(Action.DO_NOTHING)
        nodes_to_remove = [
            node for name in response["nodesToRemove"]
            if (node := self.kube_client.get_node(name)) is not None
        ]
        replacements = []
        if response.get("replacements"):
            # templates + catalogs are only needed to rebuild launchables —
            # the common DELETE outcome skips the construction entirely
            from karpenter_core_tpu.solver.tpu import TPUSolver

            solver = TPUSolver(
                self.cloud_provider, provisioners,
                daemonset_pods=daemonset_pods,
                kube_client=self.kube_client,
            )
            for entry in response["replacements"]:
                pods = [
                    bound_by_node[name][i]
                    for name, i in entry.get("podRefs", [])
                    if name in bound_by_node and i < len(bound_by_node[name])
                ]
                node = solver.launchable_from_wire(entry, pods)
                if not node.instance_type_options:
                    log.warning(
                        "remote consolidation returned instance types unknown "
                        "to this catalog; skipping the command this round"
                    )
                    return Command(Action.DO_NOTHING)
                replacements.append(node)
        return Command(action, nodes_to_remove=nodes_to_remove,
                       replacement_nodes=replacements)

    def first_n_consolidation_option(
        self, candidates: List[CandidateNode], max_parallel: int
    ) -> Command:
        if len(candidates) < 2:
            return Command(Action.DO_NOTHING)
        lo_idx, hi_idx = 1, min(max_parallel, len(candidates) - 1)
        last_saved = Command(Action.DO_NOTHING)
        while lo_idx <= hi_idx:
            mid = (lo_idx + hi_idx) // 2
            subset = candidates[: mid + 1]
            cmd = self.compute_consolidation(*subset)
            if cmd.action == Action.REPLACE:
                cmd.replacement_nodes[0].instance_type_options = self.filter_out_same_type(
                    cmd.replacement_nodes[0], subset
                )
                if not cmd.replacement_nodes[0].instance_type_options:
                    cmd = Command(Action.DO_NOTHING)
            if cmd.action in (Action.REPLACE, Action.DELETE):
                last_saved = cmd
                lo_idx = mid + 1
            else:
                hi_idx = mid - 1
        return last_saved

    @staticmethod
    def filter_out_same_type(new_node, consolidate: List[CandidateNode]) -> List[InstanceType]:
        """Price-sanity filter: a replacement of the same type as a deleted node
        must be cheaper than that node (multinodeconsolidation.go:132-165)."""
        existing_types = set()
        prices_by_type = {}
        for c in consolidate:
            existing_types.add(c.instance_type.name)
            offering = c.instance_type.offerings.get(c.capacity_type, c.zone)
            if offering is None:
                continue
            prices_by_type[c.instance_type.name] = min(
                prices_by_type.get(c.instance_type.name, float("inf")), offering.price
            )
        max_price = float("inf")
        for it in new_node.instance_type_options:
            if it.name in existing_types:
                max_price = min(max_price, prices_by_type.get(it.name, float("inf")))
        return filter_by_price(new_node.instance_type_options, new_node.requirements, max_price)


class EmptyNodeConsolidation(_ConsolidationBase):
    """Batch-delete empty candidates; validation waits the TTL then re-checks
    emptiness + nomination — no simulation (emptynodeconsolidation.go:44-88)."""

    name = "consolidation"

    def compute_command(self, candidates: List[CandidateNode]) -> Command:
        if not self.should_attempt():
            return Command(Action.DO_NOTHING)
        candidates = self.sort_and_filter_candidates(candidates)
        empty = [c for c in candidates if not c.pods]
        if not empty:
            return Command(Action.DO_NOTHING)
        cmd = Command(Action.DELETE, [c.node for c in empty])

        self.clock.sleep(CONSOLIDATION_TTL)
        validation_candidates = candidate_nodes(
            self.cluster, self.kube_client, self.clock, self.cloud_provider, self.should_deprovision
        )
        for candidate in map_nodes(cmd.nodes_to_remove, validation_candidates):
            if candidate.pods and not self.cluster.is_node_nominated(candidate.node.name):
                return Command(Action.RETRY)
        return cmd


# --- the controller ------------------------------------------------------------------


class DeprovisioningController:
    name = "deprovisioning"

    def __init__(
        self,
        clock,
        kube_client,
        provisioning: ProvisioningController,
        cloud_provider: CloudProvider,
        recorder,
        cluster: Cluster,
        settings,
        use_tpu_kernel: bool = False,
    ) -> None:
        self.clock = clock
        self.kube_client = kube_client
        self.provisioning = provisioning
        self.cloud_provider = cloud_provider
        self.recorder = recorder
        self.cluster = cluster
        self.settings = settings
        self.reporter = Reporter(recorder, clock)
        base_args = (clock, cluster, kube_client, provisioning, cloud_provider, self.reporter)
        self.expiration = Expiration(clock, kube_client, cluster, provisioning)
        self.drift = Drift(kube_client, cluster, provisioning, settings)
        self.emptiness = Emptiness(clock, kube_client, cluster)
        self.empty_node_consolidation = EmptyNodeConsolidation(*base_args)
        self.multi_node_consolidation = MultiNodeConsolidation(*base_args)
        # device sweeps follow the provisioning controller's routing: with a
        # solver service configured (KC_SOLVER_ADDRESS / solver_endpoint), the
        # sweep ships over /Consolidate instead of compiling in-process on a
        # CPU-only controller replica
        self.multi_node_consolidation.use_tpu_kernel = use_tpu_kernel
        self.multi_node_consolidation.solver_endpoint = getattr(
            provisioning, "solver_endpoint", ""
        )
        # one backend, one breaker: the sweep shares the provisioning
        # controller's solver-backend verdict.  A stub/embedded provisioning
        # object without a breaker gets a local one — otherwise a dead
        # backend would be re-probed (full timeout + warning) on every sweep
        # for the life of the process, the safeguard the old
        # disable-after-2-failures flag used to provide.
        breaker = getattr(provisioning, "solver_breaker", None)
        if breaker is None:
            from karpenter_core_tpu.controllers.provisioning import (
                SOLVER_BREAKER_RESET_S,
                TPU_KERNEL_MAX_FAILURES,
            )

            breaker = retry.CircuitBreaker(
                clock,
                failure_threshold=TPU_KERNEL_MAX_FAILURES,
                reset_timeout_s=SOLVER_BREAKER_RESET_S,
                name="sweep-solver-backend",
            )
        self.multi_node_consolidation.solver_breaker = breaker
        self.single_node_consolidation = SingleNodeConsolidation(*base_args)
        # test hook: invoked after replacements launch so suites can initialize
        # the nodes that the readiness wait polls for
        self.on_replacements_launched: Optional[Callable[[List[str]], None]] = None
        self._wait_attempts = WAIT_RETRY_ATTEMPTS
        # reconcile requeue backoff (the reference's rate-limited workqueue):
        # 1, 2, 4, 8, then the polling period — pinned by tests/test_retry.py
        self._retry_backoff = retry.Backoff(1.0, POLLING_PERIOD)

    def reconcile(self) -> Tuple[Result, float]:
        """(result, requeue_after_seconds) — controller.go:107-128.  RETRY and
        FAILED back off exponentially (the reference's rate-limited workqueue
        requeue) instead of spinning."""
        with tracing.span("deprovisioning.reconcile") as sp:
            result, requeue = self._reconcile()
            sp.set(result=result.name)
            return result, requeue

    def _reconcile(self) -> Tuple[Result, float]:
        degraded = getattr(self.provisioning, "degraded", None)
        if degraded is not None and degraded():
            # the solver breaker is open: deprovisioning is OPTIONAL work —
            # disrupting nodes against a control plane already in a failure
            # mode risks acting on a stale simulation, so pause entirely and
            # let provisioning's degraded path keep the cluster converging
            DEGRADED_PAUSES.labels().inc()
            tracing.add_event("deprovisioning.paused", degraded=True)
            log.info("deprovisioning paused: solver-backend breaker open")
            return Result.NOTHING_TO_DO, POLLING_PERIOD
        current_state = self.cluster.cluster_consolidation_state()
        result, err = self.process_cluster()
        if result == Result.FAILED:
            log.error("processing cluster, %s", err)
            return result, self._retry_backoff.next()
        if result == Result.RETRY:
            return result, self._retry_backoff.next()
        self._retry_backoff.reset()
        if result == Result.NOTHING_TO_DO:
            self.empty_node_consolidation.record_last_state(current_state)
            self.single_node_consolidation.record_last_state(current_state)
            self.multi_node_consolidation.record_last_state(current_state)
        return result, POLLING_PERIOD

    def process_cluster(self) -> Tuple[Result, Optional[str]]:
        for deprovisioner in (
            self.expiration,
            self.drift,
            self.emptiness,
            self.empty_node_consolidation,
            self.multi_node_consolidation,
            self.single_node_consolidation,
        ):
            candidates = candidate_nodes(
                self.cluster,
                self.kube_client,
                self.clock,
                self.cloud_provider,
                deprovisioner.should_deprovision,
            )
            if not candidates:
                continue
            cmd = deprovisioner.compute_command(candidates)
            if cmd.action == Action.FAILED:
                return Result.FAILED, "computing command"
            if cmd.action == Action.DO_NOTHING:
                continue
            if cmd.action == Action.RETRY:
                return Result.RETRY, None
            result, err = self.execute_command(cmd, deprovisioner)
            if err is not None:
                return Result.FAILED, err
            return result, None
        return Result.NOTHING_TO_DO, None

    def execute_command(self, cmd: Command, deprovisioner) -> Tuple[Result, Optional[str]]:
        ACTIONS_PERFORMED.labels(f"{deprovisioner.name}/{cmd.action.value}").inc()
        log.info("deprovisioning via %s %s", deprovisioner.name, cmd)

        if cmd.action == Action.REPLACE:
            err = self.launch_replacement_nodes(cmd)
            if err is not None:
                return Result.FAILED, f"launching replacement node, {err}"

        for old_node in cmd.nodes_to_remove:
            if self.recorder is not None:
                self.recorder.publish(evt.terminating_node(old_node, str(cmd)))
            try:
                self.kube_client.delete(old_node)
                NODES_TERMINATED.labels(f"{deprovisioner.name}/{cmd.action.value}").inc()
            except Exception as e:  # noqa: BLE001
                log.error("deleting node, %s", e)

        for old_node in cmd.nodes_to_remove:
            self.wait_for_deletion(old_node)
        return Result.SUCCESS, None

    def launch_replacement_nodes(self, cmd: Command) -> Optional[str]:
        """Cordon old → launch → mark → wait initialized; rollback on failure
        (controller.go:274-329)."""
        done = measure(REPLACEMENT_INITIALIZED.labels())
        names_to_remove = [n.name for n in cmd.nodes_to_remove]
        err = self._set_unschedulable(True, *names_to_remove)
        if err is not None:
            return f"cordoning nodes, {err}"

        node_names, launch_err = self.provisioning.launch_machines(cmd.replacement_nodes)
        if launch_err is not None:
            self._set_unschedulable(False, *names_to_remove)
            return launch_err
        from karpenter_core_tpu.controllers.provisioning import NODES_CREATED

        NODES_CREATED.labels("deprovisioning").inc(len(node_names))
        self.cluster.mark_for_deletion(*names_to_remove)

        if self.on_replacements_launched is not None:
            self.on_replacements_launched(node_names)

        # wait for initialization with capped exponential backoff
        failed = []
        for name in node_names:
            if not self._wait_for_initialized(name):
                failed.append(name)
        if failed:
            self.cluster.unmark_for_deletion(*names_to_remove)
            self._set_unschedulable(False, *names_to_remove)
            return f"timed out checking node readiness for {failed}"
        done()
        return None

    def _wait_for_initialized(self, node_name: str) -> bool:
        backoff = retry.Backoff(WAIT_RETRY_DELAY, WAIT_RETRY_MAX_DELAY)
        for attempt in range(self._wait_attempts):
            node = self.kube_client.get_node(node_name)
            if node is not None and labels_api.LABEL_NODE_INITIALIZED in node.metadata.labels:
                return True
            if node is not None and self.recorder is not None:
                self.recorder.publish(evt.waiting_on_readiness(node_name))
            self.clock.sleep(backoff.next())
        return False

    def wait_for_deletion(self, node: Node) -> None:
        backoff = retry.Backoff(WAIT_RETRY_DELAY, WAIT_RETRY_MAX_DELAY)
        for attempt in range(self._wait_attempts):
            if self.kube_client.get_node(node.name) is None:
                return
            self.clock.sleep(backoff.next())
        log.error("waiting on node deletion for %s", node.name)

    def _set_unschedulable(self, unschedulable: bool, *names: str) -> Optional[str]:
        errs = []
        for name in names:
            node = self.kube_client.get_node(name)
            if node is None:
                errs.append(f"getting node {name}")
                continue
            if not unschedulable and node.metadata.deletion_timestamp is not None:
                continue
            node.spec.unschedulable = unschedulable
            self.kube_client.apply(node)
        return "; ".join(errs) if errs else None
