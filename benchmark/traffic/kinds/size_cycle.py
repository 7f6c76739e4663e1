"""Stateless ``/SolveClasses`` over a fixed cycle of seeded batches, the next
request when the last returns (one leader that waits for each reply: a closed
loop with one client).

traffic parameters:
  sizes   the batch sizes in the order they are sent — a list, or the name of
          the configuration key that holds one (the suite's ``batch_sizes``;
          a deployment's ``backlogs``, the whole backlog twice).  A fresh
          seeded batch of the configuration's ``pod_mix`` per entry is built
          in set-up.

Set-up sends the cycle once in its own order: ``compilecache.snap_slots``
reuses an earlier slot count within 4x, so which executables serve the window
depends on the order of first use, and the order is part of the traffic.
Several distinct batches, so that no memo keyed on a request's content can
ever be read as a speed-up.
"""

from benchmark.harness import checks
from benchmark.harness.podmix import pod_mix, seeded


class Kind:
    def __init__(self, ctx) -> None:
        self.ctx = ctx
        self.batches = [
            pod_mix(n, seeded(ctx.seed, f"batch{j}"), ctx.config["pod_mix"])
            for j, n in enumerate(self.sizes())
        ]
        self.group = len(self.batches)  # units in one cycle
        self.reference: list = []  # the warm-up answer per batch
        self.last: list = [None] * self.group

    def sizes(self) -> list:
        sizes = self.ctx.traffic["sizes"]
        return list(self.ctx.config[sizes] if isinstance(sizes, str) else sizes)

    def _send(self, j: int):
        side = self.ctx.sidecar
        return side.call(side.client.solve_classes, self.batches[j],
                         side.provisioners, timeout=self.ctx.timeout)

    def setup(self) -> list:
        failures = []
        for j in range(self.group):
            reply, call = self._send(j)
            if reply is None:
                failures.append(f"warm-up of batch {j} raised: {call.error}")
            self.reference.append(reply)
        return failures

    def unit(self, i: int):
        return self._send(i % self.group)

    def settle(self, i: int, out) -> tuple:
        """(pods whose decision was asked for, failure per failed call)."""
        j = i % self.group
        reply, call = out
        if reply is None:
            return 0, [f"unit {i}: {call.error}"]
        self.last[j] = reply
        n = len(self.batches[j])
        got = checks.counts(reply)
        if got["scheduled"] != n or got["failed"] or got["residual"]:
            return 0, [f"unit {i}: {got} for {n} pods"]
        return n, []

    def kernel_pods(self) -> list:
        """The batch whose library solve shows the kernel at this traffic's
        largest shapes."""
        return max(self.batches, key=len)

    def check(self) -> dict:
        side, failures = self.ctx.sidecar, []
        nodes = placed = 0
        for j, (pods, ref, last) in enumerate(zip(self.batches, self.reference, self.last)):
            answer = last if last is not None else ref
            if answer is None:
                continue
            if last is not None and last != ref:
                failures.append(f"batch {j}: the last answer differs from the warm-up answer")
            failures += [f"batch {j}: {f}" for f in checks.accounting(answer, len(pods))]
            failures += [f"batch {j}: {f}" for f in checks.capacity(answer, pods, side.catalog)]
            got = checks.counts(answer)
            nodes, placed = nodes + got["nodes"], placed + got["scheduled"]
        # the oracle cut: a batch of the cycle where one has the cut's size
        # (no shape of its own), else one more served solve
        want = self.ctx.config["oracle"]["pods"]
        served = next(
            ((pods, ref) for pods, ref in zip(self.batches, self.reference)
             if len(pods) == want and ref is not None), None)
        failures += checks.oracle(self.ctx, served)
        return {"failures": failures, "nodes": nodes, "pods_placed": placed}
