"""``consolidate_reference.command``: the plain reference accepts a right
answer and refuses planted ones — from the API objects and the catalog alone,
no solve in sight."""

import copy
import json
import os

import pytest

from benchmark.harness.podmix import seeded
from benchmark.traffic.kinds import consolidate_cycle
from benchmark.traffic.kinds import consolidate_reference as reference

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def live():
    from karpenter_core_tpu.cloudprovider.fake import instance_types

    with open(os.path.join(REPO, "benchmark", "configs", "consolidate-5k.json")) as f:
        config = {**json.load(f), "existing_nodes": 12}
    catalog = instance_types(100)
    cluster = consolidate_cycle.build_cluster(config, 11, catalog, "prov-0")
    order = consolidate_cycle.candidates_in_order(cluster, "prov-0", seeded(11, "order0"))
    return cluster, order, catalog


def _replace(live, k: int) -> dict:
    """A right answer: the first ``k`` nodes of the order replaced by the
    catalog's largest type that is still cheaper than all of them."""
    cluster, order, catalog = live
    by_name = {node.name: bound for node, bound in cluster}
    removed = [c["name"] for c in order[:k]]
    total = sum(reference.launch_price(
        next(it for it in catalog if it.name == c["instanceType"]),
        [c["zone"]], [c["capacityType"]]) for c in order[:k])
    mine = {c["instanceType"] for c in order[:k]}
    cheaper = [it for it in catalog
               if it.name not in mine and reference.launch_price(it, [], ["on-demand"]) < total]
    biggest = max(cheaper, key=lambda it: it.allocatable()["cpu"])
    return {
        "action": "replace", "nodesToRemove": removed,
        "replacements": [{
            "provisioner": "prov-0", "instanceTypes": [biggest.name], "zones": [],
            "capacityTypes": ["on-demand"], "requests": {"cpu": 0.5},
            "podRefs": [[name, i] for name in removed for i in range(len(by_name[name]))],
        }],
    }


def test_a_right_answer_passes(live):
    cluster, order, catalog = live
    assert reference.command(_replace(live, 2), order, cluster, catalog) == []
    nothing = {"action": "do nothing", "nodesToRemove": [], "replacements": []}
    assert reference.command(nothing, order, cluster, catalog) == []


def _not_a_prefix(answer, order):
    answer["nodesToRemove"] = [order[0]["name"], order[2]["name"]]


def _one_node(answer, order):
    answer["nodesToRemove"] = answer["nodesToRemove"][:1]


def _over_capacity(answer, order):
    answer.update(action="delete", replacements=[],
                  nodesToRemove=[c["name"] for c in order])


def _dearer_replacement(answer, order):
    answer["replacements"][0]["instanceTypes"] = ["fake-it-99"]


def _same_type_at_no_saving(answer, order):
    answer["replacements"][0]["instanceTypes"].append(order[0]["instanceType"])


def _a_pod_named_twice(answer, order):
    refs = answer["replacements"][0]["podRefs"]
    refs[-1] = refs[0]


def _a_pod_left_out(answer, order):
    answer["replacements"][0]["podRefs"].pop()


def _delete_with_a_replacement(answer, order):
    answer["action"] = "delete"


@pytest.mark.parametrize("plant,says", [
    (_not_a_prefix, "not a prefix"),
    (_one_node, "not a prefix (of two or more)"),
    (_over_capacity, "have room for"),
    (_dearer_replacement, "the removed nodes cost"),
    (_same_type_at_no_saving, "at no saving"),
    (_a_pod_named_twice, "more than once"),
    (_a_pod_left_out, "the removed nodes hold"),
    (_delete_with_a_replacement, "delete with 1 replacements"),
])
def test_a_planted_answer_is_refused(live, plant, says):
    cluster, order, catalog = live
    answer = copy.deepcopy(_replace(live, 2))
    plant(answer, order)
    found = reference.command(answer, order, cluster, catalog)
    assert found and any(says in f for f in found), found
