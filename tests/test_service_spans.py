"""The host's phase spans (docs/OBSERVABILITY.md "The served path's phases"):
client classify / pack / rpc / unpack / expand, the handler's decode /
materialize / payload / pack under its own root, and the session's diff /
plan / adopt.  One span per PHASE — a request opens the same number whatever
its class count — each carrying the count of the work it did; and the trace
context stamped into the tenant envelope stays the CALLER's."""

import collections
import copy

import msgpack
import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
from karpenter_core_tpu.service.snapshot_channel import (
    SnapshotSolverClient,
    SnapshotSolverService,
    serve,
)
from karpenter_core_tpu.testing import make_pod, make_provisioner

PHASES = ("client.", "service.", "session.")


@pytest.fixture()
def channel():
    server, port = serve(FakeCloudProvider(instance_types(12)))
    client = SnapshotSolverClient(f"127.0.0.1:{port}")
    yield server, client
    client.close()
    server.stop(0)
    server.kc_service.shutdown()


def _class_pods(n_classes: int, per_class: int = 2) -> list:
    """``n_classes`` distinct request shapes, ``per_class`` pods of each."""
    return [
        make_pod(requests={"cpu": f"{100 + 5 * c}m"})
        for c in range(n_classes) for _ in range(per_class)
    ]


def _tenant_classes(n_classes: int, count: int) -> list:
    return [(make_pod(requests={"cpu": f"{100 + 5 * c}m"}), count)
            for c in range(n_classes)]


def _drain() -> list:
    spans = [s for t in tracing.TRACE_STORE.last(None) for s in t.spans]
    tracing.TRACE_STORE.clear()
    return spans


def _phase_counts(spans) -> collections.Counter:
    return collections.Counter(
        s["name"] for s in spans if s["name"].startswith(PHASES))


def _one(spans, name) -> dict:
    (found,) = [s for s in spans if s["name"] == name]
    return found


# what ONE request opens, whatever its class count: every size below is held
# to the same literal, so sizes compare equal through it
STATELESS = {
    "client.classify": 1, "client.pack": 1, "client.rpc": 1, "client.unpack": 1,
    "client.expand": 1, "service.solve_classes": 1,
    "service.decode": 2,  # the request's msgpack, then its objects
    "service.payload": 1, "service.pack": 1,
}
TENANT_FULL = {
    "client.pack": 1, "client.rpc": 1, "client.unpack": 1,
    "service.solve_classes": 1, "service.decode": 2, "service.materialize": 1,
    "service.payload": 1, "service.pack": 1,
    "session.diff": 1, "session.adopt": 1,
}
TENANT_DELTA = {**TENANT_FULL, "session.plan": 1}


@pytest.mark.parametrize("n_classes", [10, 200])
def test_stateless_request_opens_the_same_spans_whatever_its_class_count(
        traced, channel, n_classes):
    _, client = channel
    n_pods = 2 * n_classes
    out = client.solve_classes(_class_pods(n_classes), [make_provisioner()])
    assert not out["failedPodIndices"]
    spans = _drain()
    assert _phase_counts(spans) == STATELESS
    # each phase carries the count of the work it did; classify also how far
    # the fast key carried it: one derivation per distinct key, no pod punted
    assert _one(spans, "client.classify")["attrs"] == {
        "pods": n_pods, "classes": n_classes, "fast_keys": n_classes, "punted": 0}
    request_bytes = _one(spans, "client.pack")["attrs"]["request_bytes"]
    reply_bytes = _one(spans, "service.pack")["attrs"]["reply_bytes"]
    assert _one(spans, "client.rpc")["attrs"] == {
        "request_bytes": request_bytes, "reply_bytes": reply_bytes}
    # gc_full: full collector passes begun inside the request — the sidecar
    # paces them to request boundaries (tests/test_collector_policy.py)
    assert _one(spans, "service.solve_classes")["attrs"] == {
        "request_bytes": request_bytes, "reply_bytes": reply_bytes,
        "gc_full": 0, "gc_full_s": 0.0}
    assert _one(spans, "client.unpack")["attrs"]["reply_bytes"] == reply_bytes
    (objects,) = [s for s in spans
                  if s["name"] == "service.decode" and "classes" in s["attrs"]]
    assert objects["attrs"]["classes"] == n_classes and objects["attrs"]["pods"] == n_pods
    payload = _one(spans, "service.payload")["attrs"]
    nodes = payload["nodes"]
    assert nodes > 0
    # every new node's name lists were written from its plane rows
    assert payload["spliced_nodes"] == nodes
    # the served path's host assembly stands under ``prepare``, as the
    # library path's does: none of it is left to the handler's self time
    prepare = _one(spans, "prepare")
    assert prepare["attrs"]["classes"] >= n_classes and prepare["attrs"]["n_slots"] > 0
    assert _one(spans, "client.expand")["attrs"] == {
        "classes": n_classes, "nodes": nodes, "pods": n_pods}


def test_precomputed_members_skip_the_classify_span(traced, channel):
    _, client = channel
    pods = _class_pods(3)
    client.solve_classes(pods, [make_provisioner()],
                         members=[[0, 1], [2, 3], [4, 5]])
    assert "client.classify" not in _phase_counts(_drain())


@pytest.mark.parametrize("n_classes", [10, 200])
def test_tenant_ticks_open_the_same_spans_whatever_their_class_count(
        traced, channel, n_classes):
    _, client = channel
    anchor = client.solve_tenant_classes(
        _tenant_classes(n_classes, 10), [make_provisioner()],
        tenant={"id": "acme", "sessionVersion": 0})
    assert anchor["tenant"]["solveMode"] == "full"
    full = _drain()
    # one pod in ten leaves every class: a delta tick
    tick = client.solve_tenant_classes(
        _tenant_classes(n_classes, 9), [make_provisioner()],
        tenant={"id": "acme", "sessionVersion": anchor["tenant"]["sessionVersion"]})
    assert tick["tenant"]["solveMode"] == "delta"
    delta = _drain()
    assert _phase_counts(full) == TENANT_FULL
    assert _phase_counts(delta) == TENANT_DELTA
    assert _one(full, "service.materialize")["attrs"] == {
        "classes": n_classes, "copies": 10 * n_classes, "materialized": 0,
        "tenant": "acme"}
    assert _one(delta, "service.materialize")["attrs"]["copies"] == 9 * n_classes
    diff = _one(delta, "session.diff")["attrs"]
    assert (diff["arrivals"], diff["departures"], diff["dirty_classes"]) == (
        0, n_classes, n_classes)
    assert diff["solve.mode"] == "delta" and diff["classes"] == n_classes
    # both ticks' memberships are counts: every class diffed by count
    assert (diff["counted_classes"], diff["uid_classes"]) == (n_classes, 0)
    assert _one(delta, "session.plan")["attrs"]["evictions"] == n_classes
    # departures only: no member was copied for the tick
    assert _one(delta, "session.plan")["attrs"]["resolved"] == 0
    assert _one(delta, "session.adopt")["attrs"]["evicted"] == n_classes
    assert _one(full, "session.adopt")["attrs"]["placed"] == 10 * n_classes
    # the phases nest where the code does: the session's under solve.tenant,
    # the copies inside the decode
    by_id = {s["spanId"]: s for s in delta}
    parent = lambda s: by_id[s["parentId"]]["name"]  # noqa: E731
    assert parent(_one(delta, "session.diff")) == "solve.tenant"
    assert parent(_one(delta, "session.plan")) == "solve.incremental"
    assert parent(_one(delta, "session.adopt")) == "solve.incremental"
    assert parent(_one(delta, "service.materialize")) == "service.decode"
    assert parent(_one(delta, "solve.tenant")) == "service.solve_classes"


def test_envelope_context_stays_the_callers_so_the_handlers_trace_keeps_the_session(
        traced, channel, monkeypatch):
    """A root span around the handler (the benchmark's ``bench.handler``) and
    no caller span on the client's thread: the tenant request stamps NO
    context — the client's own spans must not stand in for a caller — so
    ``solve.tenant`` and its subtree stay in the handler's one trace."""
    server, client = channel
    service = server.kc_service
    inner = service._solve_classes
    roots, requests = [], []

    def handler(request, context):
        requests.append(msgpack.unpackb(request))
        with tracing.span("bench.handler") as root:
            reply = inner(request, context)
        roots.append(root)
        return reply

    monkeypatch.setattr(service, "_solve_classes", handler)
    out = client.solve_tenant_classes(
        _tenant_classes(4, 3), [make_provisioner()],
        tenant={"id": "acme", "sessionVersion": 0})
    assert out["tenant"]["solveMode"] == "full"
    assert "trace" not in requests[0]["tenant"]
    (root,) = roots
    trace = tracing.TRACE_STORE.find(root.trace_id)
    names = {s["name"] for s in trace.spans}
    assert {"bench.handler", "service.solve_classes", "service.decode",
            "solve.tenant", "session.diff", "solve.incremental", "session.adopt",
            "prepare", "dispatch", "solve", "decode", "service.payload", "service.pack"} <= names
    # one trace holds the session: no other stored trace has a server span
    others = [t for t in tracing.TRACE_STORE.last(None) if t.trace_id != root.trace_id]
    assert all(s["name"].startswith("client.") for t in others for s in t.spans)


def test_a_callers_span_still_reaches_the_envelope(traced, channel):
    _, client = channel
    with tracing.span("operator.reconcile") as caller:
        client.solve_tenant_classes(
            _tenant_classes(2, 2), [make_provisioner()],
            tenant={"id": "acme", "sessionVersion": 0})
    tree = tracing.TRACE_STORE.tree(caller.trace_id)
    by_name = {s["name"]: s for s in tree.spans}
    assert by_name["solve.tenant"]["parentId"] == caller.span_id
    # the client's phases are the caller's children, not the server's parents
    for name in ("client.pack", "client.rpc", "client.unpack"):
        assert by_name[name]["parentId"] == caller.span_id


def _materialize_class(rep, count: int, uid_base: str) -> list:
    """The service's copies of a class representative as they were made
    before members became counted: ``count`` copies, uid ``<base>#<j>``."""
    pods = []
    for j in range(count):
        pod = copy.copy(rep)
        pod.metadata = copy.copy(rep.metadata)
        pod.metadata.uid = f"{uid_base}#{j}"
        pods.append(pod)
    return pods


def _one_pass_decode(req):
    """``_decode_tenant_classes`` as it stood before it became two passes,
    every member copied."""
    from karpenter_core_tpu.models.snapshot import build_pod_ladder
    from karpenter_core_tpu.models.store import class_key, stable_digest
    from karpenter_core_tpu.apis import codec

    classes, uid_class = [], {}
    for i, entry in enumerate(req.get("podClasses", [])):
        rep = codec.pod_from_dict(entry["pod"])
        cls = build_pod_ladder(rep)
        cls.pods = [rep]
        uid_base = stable_digest(class_key(cls))[:16]
        if uid_base in uid_class:
            raise ValueError(f"duplicate pod class at index {i}")
        uid_class[uid_base] = i
        cls.pods = _materialize_class(rep, int(entry["count"]), uid_base)
        classes.append(cls)
    return classes, uid_class


def _tenant_request(classes) -> dict:
    from karpenter_core_tpu.apis import codec

    return {
        "podClasses": [{"pod": codec.pod_to_dict(p), "count": n} for p, n in classes],
        "provisioners": [codec.provisioner_to_dict(make_provisioner())],
    }


def test_two_pass_tenant_decode_returns_what_one_pass_did():
    req = _tenant_request(_tenant_classes(7, 3) + [(make_pod(requests={"cpu": "2"}), 0)])
    got_classes, got_uids = SnapshotSolverService._decode_tenant_classes(req)[:2]
    want_classes, want_uids = _one_pass_decode(req)
    assert got_uids == want_uids and list(got_uids) == list(want_uids)
    assert len(got_classes) == len(want_classes) == 8
    for got, want in zip(got_classes, want_classes):
        pods = list(got.pods)
        assert [p.uid for p in pods] == [p.uid for p in want.pods]
        assert got.requests == want.requests
        # copies, never the representative itself, each with its own metadata
        assert len({id(p) for p in pods}) == len(pods)
        assert len({id(p.metadata) for p in pods}) == len(pods)
        assert all(p is not got.pods.rep for p in pods)
    assert list(got_classes[-1].pods) == []


def test_tenant_decode_copies_nothing(traced):
    """The decode stands every class's members for their count: the span
    says how many pods the classes represent, and that it copied none."""
    counts = [3, 0, 5, 1]
    req = _tenant_request([(make_pod(requests={"cpu": f"{100 + c}m"}), n)
                           for c, n in enumerate(counts)])
    classes = SnapshotSolverService._decode_tenant_classes(req)[0]
    assert _one(_drain(), "service.materialize")["attrs"] == {
        "classes": 4, "copies": sum(counts), "materialized": 0}
    assert [len(cls.pods) for cls in classes] == counts
    assert [cls.pods.copied for cls in classes] == [0] * 4


@pytest.mark.parametrize("bad, error", [
    ("duplicate", "duplicate pod class at index 2"),
    ("count", "invalid literal for int()"),
    # the first fault in class order wins, as it did in one pass
    ("count-then-duplicate", "invalid literal for int()"),
])
def test_two_pass_tenant_decode_raises_what_one_pass_did(bad, error):
    classes = _tenant_classes(2, 3)
    req = _tenant_request(classes + [classes[0]])
    if bad == "count":
        req = _tenant_request(classes)
        req["podClasses"][1]["count"] = "many"
    elif bad == "count-then-duplicate":
        req["podClasses"][0]["count"] = "many"
    raised = []
    for decode in (SnapshotSolverService._decode_tenant_classes, _one_pass_decode):
        with pytest.raises(ValueError) as e:
            decode(req)
        raised.append(str(e.value))
    assert raised[0] == raised[1] and error in raised[0]
