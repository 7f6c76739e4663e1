"""One tenant in steady state on the tenant protocol
(``solve_tenant_classes``: class counts in, a delta's placements out).

The tenant's classes are the benchmark's own: pods for which the generator drew
the same workload (``podmix.draw``) are replicas of one another, and one
``(representative, count)`` pair per workload goes on the wire — whatever
classes the program makes of them inside.

The anchor (a full solve of the configuration's ``pods``) and ``warmup_cycles``
churn cycles happen in set-up.  The timed unit is one CHURN CYCLE of two calls
back to back: one pod in ``churn_one_in`` of the population departs — every
``churn_one_in``-th pod counting through the workloads in the order they were
drawn, so each workload loses its share and never its last pod — then as many
arrive.  A cycle, because a departures tick and an arrivals tick do different
work: timed singly and alternating they put the median on the edge between two
modes.  The wire ships COUNTS, so a tick is a net count
change.  The cycle's work is the pods that moved, twice the departures.

The session audits itself with a full solve every ``audit_period_ticks`` ticks
(the program's ``FallbackPolicy.audit_interval`` delta ticks, then the audit),
so the pattern of work repeats with that period and ``cycles_per_group`` cycles
must hold a whole number of periods: the window ends on a whole group.  An
audit tick answers in mode ``full`` with reason ``audit``; any other tick must
answer ``delta``.  The period is the program's to change (it has a flag), so
``check`` holds the audits it saw against the period this file states: where
they differ, the groups no longer hold equal work and the run is not correct.

traffic parameters: churn_one_in, warmup_cycles, cycles_per_group,
audit_period_ticks, tenant
"""

from benchmark.harness import checks
from benchmark.harness.podmix import draw, seeded


class Kind:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        replicas: dict = {}
        for workload, pod in draw(ctx.config["pods"], seeded(ctx.seed, "tenant"),
                                  ctx.config["pod_mix"]):
            replicas.setdefault(workload, []).append(pod)
        self.reps = [members[0] for members in replicas.values()]
        self.full = [len(members) for members in replicas.values()]
        one_in = int(ctx.traffic["churn_one_in"])
        self.shrunk, counted = [], 0
        for c in self.full:
            leave = (counted + c) // one_in - counted // one_in
            self.shrunk.append(c - min(leave, c - 1))
            counted += c
        self.moved = 2 * (sum(self.full) - sum(self.shrunk))
        self.version = 0
        self.group = int(ctx.traffic["cycles_per_group"])
        self.period = int(ctx.traffic["audit_period_ticks"])
        self.ticks = 0
        self.audits: list = []  # the ticks that answered as the session's audit

    def _tick(self, counts: list):
        side = self.ctx.sidecar
        reply, call = side.call(
            side.client.solve_tenant_classes, list(zip(self.reps, counts)),
            side.provisioners,
            tenant={"id": self.ctx.traffic["tenant"], "sessionVersion": self.version},
            timeout=self.ctx.timeout,
        )
        self.ticks += 1
        if reply is not None and "error" not in reply:
            echo = reply["tenant"]
            self.version = echo["sessionVersion"]
            if echo["solveMode"] == "full" and echo.get("reason") == "audit":
                self.audits.append(self.ticks)
        return reply, call

    @staticmethod
    def _judge(what: str, out, mode: str) -> list:
        reply, call = out
        if reply is None:
            return [f"{what}: {call.error}"]
        if "error" in reply:
            return [f"{what}: tenant ejected: {reply['error']}"]
        echo = reply["tenant"]
        if echo["solveMode"] == "full" and echo.get("reason") == "audit":
            return []  # the session's own periodic drift audit
        if echo["solveMode"] != mode:
            return [f"{what}: solveMode {echo['solveMode']!r} "
                    f"({echo.get('reason')}), wanted {mode!r}"]
        return []

    def setup(self) -> list:
        failures = self._judge("anchor", self._tick(self.full), "full")
        for k in range(int(self.ctx.traffic["warmup_cycles"])):
            _, bad = self.settle(f"warm-up {k}", self.unit(k))
            failures += bad
        return failures

    def unit(self, i):
        return [self._tick(self.shrunk), self._tick(self.full)]

    def settle(self, i, out) -> tuple:
        failures = [f for tick in out for f in self._judge(f"cycle {i}", tick, "delta")]
        return (0 if failures else self.moved), failures

    def kernel_pods(self):
        """None: a churn tick runs the windowed repair programs, whose shapes
        a from-scratch library solve does not show."""
        return None

    def audit_period(self) -> list:
        """The audits seen, against the period the traffic file states."""
        failures = []
        if (2 * self.group) % self.period:
            failures.append(f"a group of {self.group} cycles is not a whole number "
                            f"of audit periods of {self.period} ticks")
        gaps = {b - a for a, b in zip(self.audits, self.audits[1:])}
        late = self.ticks - (self.audits[-1] if self.audits else 0)
        if gaps - {self.period} or late > self.period:
            failures.append(
                f"the session audited at ticks {self.audits} of {self.ticks}; the traffic "
                f"file states one every {self.period}: groups no longer hold equal work")
        return failures

    def check(self) -> dict:
        failures = self.audit_period()
        plane = self.ctx.sidecar.service.tenants
        entry = plane.entries_snapshot().get(self.ctx.traffic["tenant"])
        lineage = entry.session.aggregates() if entry is not None else None
        population = sum(self.full)  # every cycle ends on an arrivals tick
        if not lineage or lineage["failed"] or lineage["scheduled"] != population:
            failures.append(f"the lineage holds {lineage} for {population} pods")
        failures += checks.oracle(self.ctx, None)
        return {"failures": failures}
