"""Pods created in the API -> machines launched, through the program a
Karpenter user starts: an ``Operator`` composed by ``with_controllers()`` and
started, beside the harness's solver sidecar — the deployed topology
(``deploy/charts``: a CPU controller whose ``KC_SOLVER_ADDRESS`` points at the
one sidecar that owns the chip).  A closed loop of one operator.

| kind | parameters | what it sends |
|---|---|---|
| `operator_cycle` | `sizes`, `warm_sizes` (each a list, or the configuration key that holds one: `timed_sizes`, `batch_sizes`), `batches_per_size` | `batches_per_size` seeded batches of the configuration's `pod_mix` per entry of `sizes`, cycled: created pending in the operator's store, provisioned by ONE pass of its provisioning controller with the batch window already closed (`/SolveClasses` with `members=` over loopback, then cloud create, node pre-create, nominations), then scaled down to an empty cluster; `warm_sizes` are provisioned and checked the same way once each, in set-up |

traffic parameters:
  sizes             the sizes the WINDOW sends, in order.  One entry, so that
                    every timed unit is alike: the harness's
                    ``request_p50_s`` is the median of all unit walls, and
                    over an even number of equally frequent sizes that is the
                    midpoint between the slowest unit of one size and the
                    fastest of the next — two extreme order statistics
                    (PERF.md section 4, the refusal of PR 37).
  warm_sizes        sizes provisioned once each in set-up, ascending, held to
                    every guarantee like a timed unit and never timed.
  batches_per_size  seeded batches drawn per entry of ``sizes``: draw ``r``
                    of entry ``j`` is ``podmix.seeded(seed, f"batch{j}.{r}")``.
                    A cycle sends each draw once.  Six because a unit's wall
                    follows its batch's FLEET — ``launch`` costs nodes x pods,
                    and the nodes a draw needs are the largest of its seven
                    hostname-spread groups, 109-128 at 5 000 pods by draw — so
                    the median unit of a run should sit among the middle
                    fleets of six draws, not three.

**The operator.** ``Operator(cloud_provider=FakeCloudProvider(<the sidecar's
catalog>), kube_client=KubeClient(), use_tpu_kernel=True).with_controllers()``
with ``Options``' and ``Settings``' defaults (leader election on, the memory
store, 1 s / 10 s batch window, the 256-pod kernel gate — set-up checks them
against the configuration's ``operator`` block).  The sidecar's address is
set as the chart sets it: ``KC_SOLVER_ADDRESS`` in this process's environment
while the operator is composed and started, read from the channel the
harness's own client dials (``sut.Sidecar`` keeps no port).  So the
provisioning controller holds a ``SnapshotSolverClient`` of its own, the
deprovisioning controller would send its sweeps there too, and the lease
plane rides the sidecar (``RemoteLeaseStore``), as deployed.  The store is
unthrottled, as ``chip_smoke.py``'s is: the pods are the cluster's workload,
not the operator's writes.

**Every controller the binary starts stays on** — node lifecycle,
termination, counter, the pod trigger, deprovisioning, the scrapers, the
inflight checks — **except the provisioning singleton's LOOP, whose body the
unit calls.**  The kind takes that one ``Singleton`` out of the operator's
list before ``start()``: leader election starts the controllers on its own
thread, so stopping the loop afterwards races with it, and a loop left
running provisions a batch a second time as soon as a unit outlasts the 1 s
idle window (228 nodes where 114 are needed).  Whoever drives this path owns
the batch's close.  No option, flag or environment variable of the program
exists for this, and none is added.

**A unit** is ONE ``sidecar.call(...)`` around
``operator.provisioning.reconcile(wait_for_batch=False)`` — what the
singleton runs once its window has closed: ``get_pending_pods`` ->
``_split_batch`` -> ``_solve_remote`` -> ``launch_machines``.  Its wall is
the time from "batch closed" to "machines launched and pods nominated".  The
window itself is policy, a timer with 50 ms polls, and stays outside.

**Between units** (``settle``: inside the window's seconds, outside the
unit's): read the recorder's events, keep the unit's outcome for ``check()``
(``operator_reference.Outcome``), fail the unit on a reconcile error, a pod
not nominated exactly once, a ``FailedScheduling`` event or any number of
``/SolveClasses`` but one; wait for the watch controllers to drain what the
launch queued; then the scale-down — the batch's pods and the launched nodes
deleted THROUGH THE KUBE CLIENT, so the termination finalizer deletes each
machine — and a wait, with a deadline that fails the unit, until the store
holds no node and no pod, the cluster state no node, the provider no machine
and the watch controllers' queues are empty; the recorder reset; the next
draw created under FRESH names and uids (the same seeded shapes: a
Deployment scaled up again) and waited for until ``get_pending_pods()``
counts it.  Waits poll every 2 ms.  Set-up sends ``warm_sizes`` and then the
cycle once the same way (its first unit is the run's first request) and
leaves draw 0 pending; ``check()`` scales down the draw the last ``settle``
created.

**Two rare passes are settled in a gap, not switched off** (PERF.md section
6, PR 37 and PR 38): each comes round once in many units, costs a good part
of one, and would otherwise land in whichever unit the clock picks.  The
sidecar paces its own full collections, one every 30 s at a handler's exit
or from its housekeeping thread (``service/collector.py``): the gap after a
cycle's LAST unit runs ``gc.collect()``, as ``run.py`` does before the
window, so none falls due inside a unit (a cycle is ~17 s).  The inflight
checks' singleton ticks once a minute over every node: the same gap stops
that loop, runs its tick — over the nodes the unit launched, before the
scale-down — and starts it again, so its minute never runs out inside a
unit.  The lease (2 s), node scraper (5 s) and deprovisioning (10 s) loops
fall where they fall.

``correct`` rests on ``operator_reference``: every warm-up unit (each of
``warm_sizes``, each draw) and the last unit of each draw, the last equal to
the warm-up, no leak after the last tear-down, the oracle batch (the warm-up
size the configuration's ``oracle.pods`` names) equal to the host scheduler.
"""

import copy
import gc
import json
import os
import time

import msgpack

from benchmark.harness.podmix import pod_mix, seeded
from benchmark.traffic.kinds import operator_reference as reference

ADDRESS_ENV = "KC_SOLVER_ADDRESS"
DEADLINE_S = 120.0  # any one wait; a unit that outlasts it fails
POLL_S = 0.002
PHASES = ("provisioning.reconcile", "provisioning.pending", "schedule", "provisioning.split",
          "provisioning.wire", "client.pack", "client.rpc", "client.unpack", "client.expand",
          "provisioning.remainder", "provisioning.launch")


def dialled(client) -> str:
    """The ``host:port`` a ``SnapshotSolverClient`` dials."""
    return client.channel._channel.target().decode().rsplit("/", 1)[-1]


def batches(traffic: dict, config: dict) -> tuple:
    """``(warm_up, cycle)``, each a list of (size, the stream its batch is
    drawn from): what set-up provisions once before the cycle, ascending, and
    the cycle the window repeats."""
    def sizes(key: str) -> list:
        named = traffic.get(key, [])
        return list(config[named] if isinstance(named, str) else named)

    warm_up = [(n, f"warm{k}") for k, n in enumerate(sorted(sizes("warm_sizes")))]
    cycle = [(n, f"batch{j}.{r}") for r in range(int(traffic.get("batches_per_size", 1)))
             for j, n in enumerate(sizes("sizes"))]
    return warm_up, cycle


class Kind:
    def __init__(self, ctx) -> None:
        from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
        from karpenter_core_tpu.operator.kubeclient import KubeClient
        from karpenter_core_tpu.operator.operator import Operator

        self.ctx = ctx
        side = ctx.sidecar
        self.warm_up, self.cycle = batches(ctx.traffic, ctx.config)
        self.group = len(self.cycle)  # units in one cycle
        self.kube = KubeClient()
        self.provider = FakeCloudProvider(side.catalog)
        self.solves = 0  # /SolveClasses requests the sidecar has been sent
        inner = side.service._solve_classes  # the harness's wrapper stays inside

        def counted(request, context):
            self.solves += 1
            return inner(request, context)

        side.service._solve_classes = counted
        before = os.environ.get(ADDRESS_ENV)
        os.environ[ADDRESS_ENV] = dialled(side.client)
        try:
            self.operator = Operator(cloud_provider=self.provider, kube_client=self.kube,
                                     use_tpu_kernel=True).with_controllers()
            # the loop goes, the controller stays: see the docstring
            self.operator._singletons = [
                s for s in self.operator._singletons if s.name != "provisioning"]
            self.kube.create(copy.deepcopy(side.provisioners[0]))
            self.operator.start()
        finally:
            if before is None:
                del os.environ[ADDRESS_ENV]
            else:
                os.environ[ADDRESS_ENV] = before
        self.pending: list = []  # the batch the next unit will find
        self.sized: list = []  # the outcome per warm-up size: (outcome, unit wall)
        self.warm: list = []  # the warm-up outcome per draw of the cycle, likewise
        self.last: list = [None] * self.group
        self.spans: list = []  # traced runs: the last units' phase spans
        if side.traced:
            from karpenter_core_tpu import tracing

            # a launch's node reconciles are a root trace each: keep the
            # unit's own trace in the store until settle has read it
            tracing.TRACE_STORE.set_capacity(max(tracing.TRACE_STORE.capacity, 4096))

    # -- waits -----------------------------------------------------------------

    def _wait(self, what: str, done) -> list:
        end = time.monotonic() + DEADLINE_S
        while not done():
            if time.monotonic() > end:
                return [f"{what}: not within {DEADLINE_S:.0f} s"]
            time.sleep(POLL_S)
        return []

    def _quiet(self) -> bool:
        """No watch controller holds an event it has not taken up."""
        return all(w._queue.empty() and not w._pending for w in self.operator._watchers)

    def _empty(self) -> bool:
        return (not self.kube.list_nodes() and not self.kube.list_pods()
                and not self.provider.created_machines() and self._quiet()
                and not self.operator.cluster.snapshot_nodes())

    def _create(self, batch: tuple) -> list:
        """``batch`` pending in the store under fresh names and uids: the
        same seeded stream draws the same shapes every time."""
        size, stream = batch
        self.pending = pod_mix(size, seeded(self.ctx.seed, stream), self.ctx.config["pod_mix"])
        for pod in self.pending:
            self.kube.create(pod)
        return self._wait(
            f"{stream}: {size} pending pods",
            lambda: self._quiet() and len(self.operator.provisioning.get_pending_pods()) == size)

    def _scale_down(self) -> list:
        """The batch's pods and every node deleted through the kube client;
        the termination finalizer deletes the machines."""
        for pod in self.pending:
            self.kube.delete(pod)
        self.pending = []
        for node in self.kube.list_nodes():
            self.kube.delete(node)
        bad = self._wait("scale-down to an empty cluster", self._empty)
        self.operator.recorder.reset()
        return bad

    def _rare_passes(self) -> None:
        """What comes round once in many units, here and in no unit: the
        inflight checks' tick over the nodes just launched, its minute
        started afresh, and the sidecar's full collection."""
        inflight = next(s for s in self.operator._singletons if s.name == "inflightchecks")
        inflight.stop()
        inflight.tick()
        inflight.start()
        gc.collect()

    # -- the traffic -------------------------------------------------------------

    def _provision(self) -> dict:
        return {"error": self.operator.provisioning.reconcile(wait_for_batch=False)}

    def _send(self):
        self._solves0 = self.solves
        return self.ctx.sidecar.call(self._provision)

    def _outcome(self, what: str, out) -> tuple:
        """``(outcome, failures)`` of the unit that just returned."""
        done, call = out
        if done is None:
            return None, [f"{what}: {call.error}"]
        if done["error"] is not None:
            return None, [f"{what}: reconcile: {done['error']}"]
        nominated, failed = reference.read_events(self.operator.recorder.events)
        outcome = reference.Outcome(
            self.pending, nominated, failed,
            [reference.view(node) for node in self.kube.list_nodes()],
            [m.status.provider_id for m in self.provider.created_machines()],
            self.ctx.sidecar.last_reply)
        bad = [f"{what}: {f}" for f in reference.nominations(outcome)]
        if self.solves - self._solves0 != 1:
            bad.append(f"{what}: {self.solves - self._solves0} /SolveClasses requests, not one")
        if self.ctx.sidecar.traced:
            self.spans.append(self._phase_spans())
        return outcome, bad

    def _phase_spans(self) -> dict:
        """The newest ``provisioning.reconcile`` trace's phases: seconds and
        attributes, for the people's line a traced run prints."""
        from karpenter_core_tpu import tracing

        for trace in reversed(tracing.TRACE_STORE.last()):
            if trace.name == "provisioning.reconcile":
                return {s["name"]: {"s": round(s["durationS"], 5), **s["attrs"]}
                        for s in trace.spans if s["name"] in PHASES}
        return {}

    def _next(self, batch: tuple, cycle_ends: bool) -> list:
        """What lies between two units; ``batch`` is the next one's."""
        bad = self._wait("the watch controllers drain the launch", self._quiet)
        if cycle_ends:
            self._rare_passes()
        return bad + self._scale_down() + self._create(batch)

    def setup(self) -> list:
        config, operator = self.ctx.config["operator"], self.operator
        failures = self._wait("leadership and the controllers", lambda: (
            operator.leader_elector is not None and operator.is_leader()
            and all(c._thread is not None and c._thread.is_alive()
                    for c in operator._watchers + operator._singletons)))
        stated = {
            "solver": "remote" if operator.provisioning.solver_endpoint else "in-process",
            "kernel_min_pods": operator.provisioning.tpu_kernel_min_pods,
            "batch_idle_s": operator.settings.batch_idle_duration,
            "batch_max_s": operator.settings.batch_max_duration,
            "kube_backend": operator.options.kube_backend,
            "leader_election": operator.options.enable_leader_election,
        }
        failures += [f"the operator's {k} is {v!r}, the configuration states {config[k]!r}"
                     for k, v in stated.items() if config[k] != v]
        batches = self.warm_up + self.cycle
        failures += self._create(batches[0])
        for k, (_size, stream) in enumerate(batches):
            out = self._send()
            outcome, bad = self._outcome(f"warm-up {stream}", out)
            (self.sized if k < len(self.warm_up) else self.warm).append((outcome, out[1].client_s))
            last = k == len(batches) - 1
            between = self._next(self.cycle[0] if last else batches[k + 1], cycle_ends=last)
            failures += bad + [f"warm-up {stream}: {f}" for f in between]
        return failures

    def unit(self, i: int):
        return self._send()

    def settle(self, i: int, out) -> tuple:
        """(pods whose machines were asked for, a message per failure)."""
        n, j = len(self.pending), i % self.group
        outcome, bad = self._outcome(f"unit {i}", out)
        if outcome is not None:
            self.last[j] = outcome
        after = (j + 1) % self.group
        bad += [f"unit {i}: {f}" for f in self._next(self.cycle[after], cycle_ends=after == 0)]
        return (0 if bad else n), bad

    def kernel_pods(self):
        return None

    def check(self) -> dict:
        side, failures = self.ctx.sidecar, []
        failures += self._scale_down()
        failures += reference.leaks(self.provider, self.kube, self.operator.cluster)
        self.operator.stop()
        # the operator's own channels to the sidecar, before the harness stops it
        for client in (self.operator.provisioning._solver_client,
                       getattr(self.operator.leader_elector.lease_store, "client", None)):
            if client is not None:
                client.close()
        nodes = placed = 0
        told = []

        def judged(name: str, outcome):
            """``outcome`` with its reply unpacked, every guarantee held."""
            if outcome is None:
                return None
            outcome = outcome._replace(reply=msgpack.unpackb(outcome.reply))
            failures.extend(f"{name}: {f}" for f in reference.check(outcome, side.catalog))
            return outcome

        def tell(outcome, wall_s) -> dict:
            return {"pods": len(outcome.pods), **reference.totals(outcome),
                    "types": len({n.instance_type for n in outcome.nodes}),
                    "warm_up_wall_s": round(wall_s, 5)}

        for (size, stream), (outcome, wall_s) in zip(self.warm_up, self.sized):
            outcome = judged(f"{stream} ({size} pods)", outcome)
            if outcome is None:
                continue
            told.append(tell(outcome, wall_s))
            if size == self.ctx.config["oracle"]["pods"]:
                failures += reference.cut(outcome, side.catalog, side.provisioners)
        if self.ctx.config["oracle"]["pods"] not in [size for size, _ in self.warm_up]:
            failures.append("the oracle cut names a size that is not warmed up")
        for (_size, stream), (warm, wall_s), last in zip(self.cycle, self.warm, self.last):
            warm = judged(f"{stream} (warm-up)", warm)
            last = judged(f"{stream} (last)", last)
            if warm is not None and last is not None:
                failures += [f"{stream}: {f}" for f in reference.same(last, warm)]
            answer = last if last is not None else warm
            if answer is None:
                continue
            told.append(tell(answer, wall_s))
            nodes, placed = nodes + told[-1]["nodes"], placed + told[-1]["scheduled"]
        print(json.dumps({"outcomes": told, "machines_created": len(self.provider.create_calls),
                          "machines_deleted": len(self.provider.delete_calls)}), flush=True)
        if self.spans:
            print(json.dumps({"unit_spans": self.spans[-self.group:]}), flush=True)
        return {"failures": failures, "nodes": nodes, "pods_placed": placed}
