"""The system under test: the solver sidecar composed as the deployed binary
composes it (``cmd/solver.compose``), reached through a loopback
``SnapshotSolverClient`` — the client's side of the path a Karpenter operator
waits on.  The benchmark wraps the two calls it can see: the client call, and
the in-process handler the server looks up per request.  From the program it
takes only this surface and, in a traced run, its spans.
"""

import contextlib
import time
from typing import NamedTuple, Optional

HANDLER = "bench.handler"
CLIENT = "bench.client"
UNIT = "bench.unit"


class Call(NamedTuple):
    """One client call as the benchmark saw it."""

    client_s: float  # wall of the client call, classify and expansion included
    handler_s: float  # wall of the server's handler inside it
    reply_bytes: int
    spans: tuple  # traced runs: the program's spans under the handler (dicts)
    error: Optional[str]  # the call raised (shed, deadline, abort)


class Sidecar:
    def __init__(self, n_types: int, n_provisioners: int, traced: bool) -> None:
        from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
        from karpenter_core_tpu.cmd import solver as solver_cmd
        from karpenter_core_tpu.service.snapshot_channel import SnapshotSolverClient
        from karpenter_core_tpu.testing import make_provisioner

        self.traced = traced
        self.catalog = instance_types(n_types)
        # weighted: the highest weight wins, as in BASELINE.json config 4
        self.provisioners = [
            make_provisioner(name=f"prov-{i}", weight=n_provisioners - i)
            for i in range(n_provisioners)
        ]
        self.server, port = solver_cmd.compose(
            FakeCloudProvider(self.catalog), address="127.0.0.1:0"
        )
        self.service = self.server.kc_service
        self.client = SnapshotSolverClient(f"127.0.0.1:{port}")
        self.calls: list = []  # every Call since the last drain, in order
        self.last_reply: bytes = b""  # kept for the msgpack probe after the window
        self._seen = None  # the handler's side of the call in flight
        # the server resolves self._solve_classes per request, so an instance
        # attribute puts the benchmark's span around the whole handler
        self._inner = self.service._solve_classes
        self.service._solve_classes = self._handler

    def _handler(self, request: bytes, context) -> bytes:
        t0 = time.perf_counter()
        spans, reply = (), b""
        try:
            if not self.traced:
                reply = self._inner(request, context)
            else:
                import jax.profiler

                from karpenter_core_tpu import tracing

                with jax.profiler.TraceAnnotation(HANDLER), tracing.span(HANDLER) as root:
                    reply = self._inner(request, context)
                trace = tracing.TRACE_STORE.find(root.trace_id)
                spans = tuple(trace.spans) if trace is not None else ()
            self.last_reply = reply
            return reply
        finally:
            self._seen = (time.perf_counter() - t0, len(reply), spans)

    def call(self, fn, *args, **kwargs):
        """Run one client call; returns ``(reply, Call)``.  A call that raises
        is recorded and returned as ``(None, Call)``: it counts as failed."""
        self._seen = None
        annotate = contextlib.nullcontext()
        if self.traced:
            import jax.profiler

            annotate = jax.profiler.TraceAnnotation(CLIENT)
        reply, error = None, None
        t0 = time.perf_counter()
        try:
            with annotate:
                reply = fn(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 - recorded; the run reports it as failed
            error = f"{type(e).__name__}: {e}"[:300]
        client_s = time.perf_counter() - t0
        call = Call(client_s, *(self._seen or (0.0, 0, ())), error)
        self.calls.append(call)
        return reply, call

    def drain_calls(self) -> list:
        calls, self.calls = self.calls, []
        return calls

    def close(self) -> None:
        self.client.close()
        self.server.stop(grace=0).wait()
        self.service.shutdown()
