"""A /SolveClasses reply writes each new node's name lists from per-name
packed bytes indexed by the node's plane row (service.snapshot_channel
``_NameLists``), and must be byte for byte the reply the plain lists gave:
the dict of ``instance_type_names`` / ``zones`` / ``capacity_types``
through ``msgpack.packb``.  Every reply the handler packs is checked against
that reference, built from the same results at the moment of packing."""

import types

import msgpack
import pytest

from karpenter_core_tpu import tracing
from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider, instance_types
from karpenter_core_tpu.policy import PolicyConfig
from karpenter_core_tpu.service.snapshot_channel import (
    SnapshotSolverClient,
    SnapshotSolverService,
    serve,
)
from karpenter_core_tpu.solver.tpu import TPUNodeDecision, TPUSolver
from karpenter_core_tpu.testing import make_pod, make_provisioner

N_TYPES = 12
CHEAP = f"fake-it-{N_TYPES - 1}"  # the catalog's last type, priced lowest


def _plain_payload(results, class_counts) -> dict:
    """The payload as the plain lists built it: every name a ``str``."""
    return {
        "newNodes": [
            {
                "provisioner": n.provisioner_name,
                "instanceTypes": n.instance_type_names,
                "zones": n.zones,
                "capacityTypes": n.capacity_types,
                "requests": n.requests,
                "classCounts": class_counts(n.pods),
            }
            for n in results.new_nodes
        ],
        "existingAssignments": {
            name: class_counts(placed)
            for name, placed in results.existing_assignments.items()
        },
        "failedClassCounts": class_counts(results.failed_pods),
        "residualClassCounts": class_counts(results.spread_residual_pods),
        "existingCommittedZones": dict(results.existing_committed_zones),
    }


def _plain_reply(results, class_counts, response) -> bytes:
    want = _plain_payload(results, class_counts)
    # what the handler adds after the payload (the tenant envelope), as it is
    want.update((k, v) for k, v in response.items() if k not in want)
    return msgpack.packb(want)


@pytest.fixture()
def replies(monkeypatch):
    """Every reply the handler packs: its bytes, the reference's bytes, and
    the results they were written from."""
    payload = SnapshotSolverService._classes_payload
    pack = SnapshotSolverService._pack_reply
    seen = []

    def recording_payload(results, class_counts):
        seen.append({"results": results, "class_counts": class_counts})
        return payload(results, class_counts)

    def recording_pack(response):
        reply = pack(response)
        rec = seen[-1]
        rec["reply"] = reply
        rec["want"] = _plain_reply(rec["results"], rec["class_counts"], response)
        return reply

    monkeypatch.setattr(SnapshotSolverService, "_classes_payload",
                        staticmethod(recording_payload))
    monkeypatch.setattr(SnapshotSolverService, "_pack_reply",
                        staticmethod(recording_pack))
    return seen


def _mixed_pods(n_per_class: int = 60) -> list:
    return [make_pod(requests={"cpu": cpu})
            for cpu in ("500m", "1", "2", "3") for _ in range(n_per_class)]


def _serve(provider):
    server, port = serve(provider)
    return server, SnapshotSolverClient(f"127.0.0.1:{port}")


def _stop(server, client) -> None:
    client.close()
    server.stop(0)
    server.kc_service.shutdown()


def _stateless(provider, client) -> None:
    client.solve_classes(_mixed_pods(), [make_provisioner()])


def _policy_selected(provider, client) -> None:
    # the last type nearly free: the objective picks it for every node, and
    # its name moves from the end of the list to the front
    provider.set_price(CHEAP, 0.001)
    client.solve_classes(_mixed_pods(20), [make_provisioner()],
                         policy=PolicyConfig(enabled=True))


def _no_new_nodes(provider, client) -> None:
    out = client.solve_classes([make_pod(requests={"cpu": "1000"})] * 3,
                               [make_provisioner()])
    assert out["failedPodIndices"] == [0, 1, 2]


def _tenant_classes(count: int) -> list:
    return [(make_pod(requests={"cpu": cpu}), count)
            for cpu in ("500m", "1", "2")]


def _tenant_full(provider, client) -> None:
    out = client.solve_tenant_classes(_tenant_classes(30), [make_provisioner()],
                                      tenant={"id": "acme", "sessionVersion": 0})
    assert out["tenant"]["solveMode"] == "full"


def _tenant_delta(provider, client) -> None:
    anchor = client.solve_tenant_classes(
        _tenant_classes(30), [make_provisioner()],
        tenant={"id": "acme", "sessionVersion": 0})
    tick = client.solve_tenant_classes(
        _tenant_classes(36), [make_provisioner()],
        tenant={"id": "acme",
                "sessionVersion": anchor["tenant"]["sessionVersion"]})
    assert tick["tenant"]["solveMode"] == "delta"


CASES = {
    "stateless": _stateless,
    "policy-selected": _policy_selected,
    "no-new-nodes": _no_new_nodes,
    "tenant-full": _tenant_full,
    "tenant-delta": _tenant_delta,
}


def _plain_node(pod) -> types.SimpleNamespace:
    """A new node with no plane row: its lists are plain ``str`` lists."""
    return types.SimpleNamespace(
        provisioner_name="default", instance_type_names=["b-type", "a-type"],
        zones=["test-zone-1"], capacity_types=["spot", "on-demand"],
        requests={"cpu": 1.5}, pods=[pod])


@pytest.mark.parametrize("case", [*CASES, "no-plane-row"])
def test_reply_bytes_are_what_the_plain_lists_packed_to(case, replies):
    provider = FakeCloudProvider(instance_types(N_TYPES))
    if case == "no-plane-row":
        pods = _mixed_pods(10)
        results = TPUSolver(provider, [make_provisioner()]).solve(pods)
        extra = make_pod(requests={"cpu": "1"})
        results.new_nodes.append(_plain_node(extra))
        index = {id(p): i for i, p in enumerate([*pods, extra])}

        def class_counts(pods):
            return sorted({index[id(p)]: 1 for p in pods}.items())

        reply = SnapshotSolverService._pack_reply(
            SnapshotSolverService._classes_payload(results, class_counts))
        assert replies[-1]["reply"] == reply
    else:
        server, client = _serve(provider)
        try:
            CASES[case](provider, client)
        finally:
            _stop(server, client)
    assert replies
    for rec in replies:
        assert rec["reply"] == rec["want"]
    nodes = [msgpack.unpackb(rec["reply"])["newNodes"] for rec in replies]
    last = nodes[-1]
    if case == "no-new-nodes":
        assert last == []
    else:
        assert len(last) > 1
    if case == "policy-selected":
        decisions = replies[-1]["results"].new_nodes
        assert all(d.selected["instance_type"] == CHEAP for d in decisions)
        # the chosen type stood last in catalog order: moved, not found first
        assert all(n["instanceTypes"][0] == CHEAP for n in last)
        assert any(len(n["instanceTypes"]) > 1 for n in last)
    if case == "no-plane-row":
        assert last[-1]["instanceTypes"] == ["b-type", "a-type"]
        assert isinstance(replies[-1]["results"].new_nodes[0], TPUNodeDecision)


def test_payload_span_counts_the_spliced_lists(traced):
    server, client = _serve(FakeCloudProvider(instance_types(N_TYPES)))
    try:
        out = client.solve_classes(_mixed_pods(), [make_provisioner()])
    finally:
        _stop(server, client)
    spans = [s for t in tracing.TRACE_STORE.last(None) for s in t.spans]
    (payload,) = [s["attrs"] for s in spans if s["name"] == "service.payload"]
    new_nodes = out["newNodes"]
    assert payload["nodes"] == payload["spliced_nodes"] == len(new_nodes) > 1
    assert payload["type_names"] == sum(len(n["instanceTypes"]) for n in new_nodes)
    # each distinct row joined once: fewer rows than nodes where rows repeat
    distinct = {tuple(n["instanceTypes"]) for n in new_nodes}
    assert payload["type_rows"] == len(distinct) < len(new_nodes)
