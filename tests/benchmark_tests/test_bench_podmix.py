"""The pod mix generator over a configuration's ``pod_mix`` block, the
benchmark's own grouping of pods into workloads, and the churn rule built on
it.  No solver runs here."""

import collections
import json
import os

import pytest

from benchmark.harness import manifest
from benchmark.harness.podmix import draw, pod_mix, seeded
from benchmark.traffic.kinds import tenant_churn

CONFIGS = ("northstar-50k-1k", "northstar-50k-1k-mesh4", "upstream-suite-400")
ZONE, HOSTNAME = "topology.kubernetes.io/zone", "kubernetes.io/hostname"


def _config(name: str) -> dict:
    with open(os.path.join(manifest.BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


MIX = _config("upstream-suite-400")["pod_mix"]


def _constraint(pod):
    """(kind, topology key, selected labels) of the pod's one constraint."""
    spec = pod.spec
    if spec.topology_spread_constraints:
        c = spec.topology_spread_constraints[0]
        return "spread", c.topology_key, c.label_selector.match_labels
    if spec.affinity is not None and spec.affinity.pod_affinity is not None:
        term = spec.affinity.pod_affinity.required[0]
        return "affinity", term.topology_key, term.label_selector.match_labels
    return "generic", None, None


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_sends_the_same_mix_and_names_its_departures(name):
    config = _config(name)
    assert config["pod_mix"] == MIX
    departures = [k for k in config["assumed"] if k.startswith("pod mix: ")]
    assert len(departures) == 4  # three departures and the class count they make
    assert all(len(config["assumed"][k]) > 80 for k in departures)


def test_the_mix_is_the_upstream_suites_five_sevenths_and_a_generic_remainder():
    assert MIX["parts_of"] == 7 and [k["parts"] for k in MIX["kinds"]] == [1] * 5
    assert [k["kind"] for k in MIX["kinds"]] == [
        "generic", "spread", "spread", "affinity", "affinity"]
    assert MIX["cpu"] == ["100m", "250m", "500m", "1000m", "1500m"]
    assert MIX["memory"] == ["100Mi", "256Mi", "512Mi", "1024Mi", "2048Mi", "4096Mi"]
    assert len(MIX["label_values"]) == 7


@pytest.mark.parametrize("n", [1, 50, 100, 500, 1000, 5000])
def test_shares_by_kind(n):
    kinds = collections.Counter(
        _constraint(p)[:2] for p in pod_mix(n, seeded(3, "t"), MIX))
    seventh = n // 7
    assert kinds[("spread", ZONE)] == kinds[("spread", HOSTNAME)] == seventh
    assert kinds[("affinity", ZONE)] == 2 * seventh  # departure: both sevenths zonal
    assert kinds[("generic", None)] == n - 4 * seventh
    assert sum(kinds.values()) == n


def test_every_kind_draws_requests_and_labels_from_the_suites_lists():
    pods = pod_mix(2100, seeded(5, "t"), MIX)
    by_kind = collections.defaultdict(set)
    for pod in pods:
        requests = pod.spec.containers[0].resources.requests
        (label,) = pod.metadata.labels.items()
        by_kind[_constraint(pod)[:2]].add((requests["cpu"], requests["memory"], label))
    assert len(by_kind) == 4
    for kind, seen in by_kind.items():
        assert len({s[:2] for s in seen}) == 30, kind  # 5 cpu x 6 memory
        assert len({s[2] for s in seen}) == 7, kind  # 7 label values


def test_the_same_seed_gives_the_same_pods_and_another_seed_other_pods():
    def shapes(seed):
        return [w for w, _pod in draw(700, seeded(seed, "t"), MIX)]

    assert shapes(11) == shapes(11)
    assert shapes(11) != shapes(12)


@pytest.mark.parametrize("selector, selects_itself", [("own", True), ("drawn", False)])
def test_selector_own_selects_the_pods_own_label_and_drawn_draws_again(
        selector, selects_itself):
    mix = {**MIX, "kinds": [dict(k, selector=selector) if "selector" in k else k
                            for k in MIX["kinds"]]}
    constrained = [p for p in pod_mix(1400, seeded(2, "t"), mix)
                   if _constraint(p)[0] != "generic"]
    own = [_constraint(p)[2] == p.metadata.labels for p in constrained]
    assert all(own) if selects_itself else 0.05 < sum(own) / len(own) < 0.25  # ~1/7


def test_a_workload_is_what_was_drawn_and_equal_workloads_are_replicas():
    groups = collections.defaultdict(list)
    for workload, pod in draw(5000, seeded(9, "t"), MIX):
        groups[workload].append(pod)
    # 210 = 7 labels x 30 shapes per kind; the two affinity sevenths share theirs
    assert 700 < len(groups) <= 840
    for pods in groups.values():
        first = pods[0]
        assert all(p.metadata.labels == first.metadata.labels
                   and p.spec.containers[0].resources.requests
                   == first.spec.containers[0].resources.requests
                   and _constraint(p) == _constraint(first) for p in pods)


class _Ctx:
    def __init__(self, pods, traffic):
        self.config = {"pods": pods, "pod_mix": MIX}
        self.traffic = {"tenant": "t", "churn_one_in": 50, "cycles_per_group": 17,
                        "audit_period_ticks": 17, **traffic}
        self.seed = 4


@pytest.mark.parametrize("pods", [300, 1400, 50000])
def test_churn_moves_one_pod_in_fifty_and_never_a_workloads_last(pods):
    kind = tenant_churn.Kind(_Ctx(pods, {}))
    departed = [full - left for full, left in zip(kind.full, kind.shrunk)]
    assert sum(kind.full) == pods
    assert all(left >= 1 for left in kind.shrunk)
    assert all(0 <= d <= full // 50 + 1 for d, full in zip(departed, kind.full))
    assert pods // 50 - sum(c == 1 for c in kind.full) <= sum(departed) <= pods // 50
    assert kind.moved == 2 * sum(departed)


@pytest.mark.parametrize("audits, ticks, traffic, ok", [
    ([18, 35, 52], 60, {}, True),  # one every 17 ticks
    ([], 5, {}, True),  # too short a run to see one
    ([18, 35], 39, {}, True),
    ([10, 19, 28], 30, {}, False),  # the program audits every 9
    ([], 40, {}, False),  # the program never audits
    ([18, 35], 60, {}, False),  # the audits stopped
    ([18, 35, 52], 60, {"cycles_per_group": 16}, False),  # a group cuts a period
])
def test_the_audit_period_seen_is_held_to_the_one_the_traffic_file_states(
        audits, ticks, traffic, ok):
    kind = tenant_churn.Kind(_Ctx(300, traffic))
    kind.audits, kind.ticks = audits, ticks
    assert (kind.audit_period() == []) is ok
