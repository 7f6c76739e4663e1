"""``assorted_reference`` — the guarantees of ``assorted-1344.full`` read from
the pods, the answer and the catalog alone — on hand-built answers it must
admit and refuse: each check fails on an answer broken its own way."""

import pytest

from benchmark.traffic.kinds import assorted_reference as reference
from benchmark.traffic.kinds.assorted_cycle import catalog

ZONE, HOSTNAME = "topology.kubernetes.io/zone", "kubernetes.io/hostname"
CT = "karpenter.sh/capacity-type"
OS, ARCH = "kubernetes.io/os", "kubernetes.io/arch"
CATALOG = catalog("assorted")


def _type(zone="test-zone-1", ct="spot", os_="linux", arch="amd64", cpu=2, mem=4) -> str:
    return f"{cpu}-cpu-{mem}-mem-{arch}-{os_}-{zone}-{ct}"


def _pod(name: str, keys=(), os_="linux", arch=None):
    from karpenter_core_tpu.apis.objects import LabelSelector, TopologySpreadConstraint
    from karpenter_core_tpu.testing import make_pod

    selector = {OS: os_, **({ARCH: arch} if arch else {})}
    return make_pod(labels={"name": name}, requests={"cpu": "100m"}, node_selector=selector,
                    topology_spread=[TopologySpreadConstraint(
                        max_skew=1, topology_key=key,
                        label_selector=LabelSelector(match_labels={"name": name}))
                        for key in keys] or None)


def _answer(*nodes) -> dict:
    """``nodes``: ``([type name, ...], [pod index, ...])`` per new node."""
    return {"newNodes": [{"instanceTypes": list(types), "podIndices": list(idx)}
                         for types, idx in nodes],
            "existingAssignments": {}, "failedPodIndices": [], "residualPodIndices": []}


def test_the_catalog_is_upstreams_assorted_one():
    assert len(CATALOG) == 1344 and len({it.name for it in CATALOG}) == 1344
    assert all(len(it.offerings.available()) == 1 for it in CATALOG)
    assert _type() in {it.name for it in CATALOG}


def test_a_valid_answer_is_admitted():
    pods = [_pod("web", (ZONE, HOSTNAME, CT)) for _ in range(4)] + [_pod("win", os_="windows")]
    answer = _answer(
        ([_type("test-zone-1", "spot"), _type("test-zone-1", "spot", cpu=4)], [0]),
        ([_type("test-zone-2", "on-demand")], [1]),
        ([_type("test-zone-3", "spot")], [2]),
        ([_type("test-zone-1", "on-demand")], [3]),
        ([_type(os_="windows"), _type("test-zone-2", "on-demand", os_="windows")], [4]),
    )
    assert reference.guarantees(answer, pods, CATALOG) == []


def test_a_capacity_type_spread_may_leave_the_zone_open():
    pods = [_pod("web", (CT,)) for _ in range(2)]
    answer = _answer(([_type("test-zone-1", "spot"), _type("test-zone-2", "spot")], [0]),
                     ([_type("test-zone-3", "on-demand")], [1]))
    assert reference.guarantees(answer, pods, CATALOG) == []


def test_a_node_with_two_capacity_types_is_refused():
    pods = [_pod("web", (CT,)) for _ in range(2)]
    answer = _answer(([_type("test-zone-1", "spot"), _type("test-zone-1", "on-demand")], [0]),
                     ([_type("test-zone-1", "on-demand")], [1]))
    (bad,) = reference.guarantees(answer, pods, CATALOG)
    assert "node 0" in bad and CT in bad and "on-demand" in bad and "spot" in bad


def test_a_zone_spread_node_in_two_zones_is_refused():
    pods = [_pod("web", (ZONE, CT))]
    answer = _answer(([_type("test-zone-1", "spot"), _type("test-zone-2", "spot")], [0]))
    (bad,) = reference.guarantees(answer, pods, CATALOG)
    assert "node 0" in bad and ZONE in bad


@pytest.mark.parametrize("key, spots", [(CT, 2), (ZONE, 2)])
def test_a_skew_of_two_is_refused(key, spots):
    pods = [_pod("web", (key,)) for _ in range(spots)]
    answer = _answer(*(([_type("test-zone-1", "spot")], [i]) for i in range(spots)))
    (bad,) = reference.guarantees(answer, pods, CATALOG)
    assert bad.startswith(f"{key} spread default/web") and "not within 1" in bad


def test_a_windows_pod_on_a_linux_type_is_refused():
    pods = [_pod("win", os_="windows")]
    answer = _answer(([_type(os_="windows"), _type(os_="linux")], [0]))
    (bad,) = reference.guarantees(answer, pods, CATALOG)
    assert "node 0" in bad and f"{OS}=windows" in bad


def test_an_arm64_pod_on_an_amd64_type_is_refused():
    pods = [_pod("arm", arch="arm64")]
    answer = _answer(([_type(arch="arm64"), _type(arch="amd64")], [0]))
    (bad,) = reference.guarantees(answer, pods, CATALOG)
    assert f"{ARCH}=arm64" in bad


def test_two_pods_of_a_hostname_spread_on_one_node_are_refused():
    pods = [_pod("web", (HOSTNAME,)) for _ in range(2)]
    answer = _answer(([_type()], [0, 1]))
    (bad,) = reference.guarantees(answer, pods, CATALOG)
    assert bad.startswith("hostname spread default/web") and "node 0 holds 2" in bad


def test_the_oracle_holds_the_host_fleet(monkeypatch):
    from benchmark.harness import checks

    pods = [_pod("web", (CT,)) for _ in range(2)]
    answer = _answer(([_type("test-zone-1", "spot")], [0]),
                     ([_type("test-zone-1", "on-demand")], [1]))
    host = {"nodes": 2, "scheduled": 2, "failed": 0, "residual": 0}
    monkeypatch.setattr(checks, "oracle_totals", lambda *a: dict(host))
    assert reference.oracle(answer, pods, CATALOG, []) == []
    for nodes in (1, 3):
        host["nodes"] = nodes
        assert reference.oracle(answer, pods, CATALOG, []) == [
            f"oracle cut: nodes: kernel 2 vs host {nodes}"]
    host.update(nodes=2, failed=1, scheduled=1)
    assert len(reference.oracle(answer, pods, CATALOG, [])) == 2
