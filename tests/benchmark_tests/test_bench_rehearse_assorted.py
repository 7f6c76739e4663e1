"""The ``assorted-full`` traffic (kind ``assorted_cycle``) rehearsed end to
end in a fresh process on the CPU: two backlogs of 1 400 pods in 154
Deployments on upstream's assorted catalog, every answer held to
``assorted_reference``, the oracle cut against the host scheduler; the cell
as the manifest states it; and the static flag that keeps the capacity-type
family out of every older cell's program."""

import json

import pytest
from bench_rehearsal import assert_rehearsal, last_line, run_cell

CELL = "assorted-1344.full"
OLDER = ("backlog-50k.full", "backlog-50k.churn", "suite-400.mixed",
         "backlog-50k-mesh4.full", "brownfield-5k.full", "manyshape-50k.full",
         "consolidate-5k.sweep", "suite-400.operator", "suite-400-as-drawn.mixed")


def _lines(proc, key: str) -> list:
    return [json.loads(line)[key] for line in proc.stdout.splitlines()
            if line.startswith('{"%s"' % key)]


def test_assorted_rehearsal_untraced():
    proc = run_cell(CELL, "--trace", "0", "--rehearse")
    result = last_line(proc)
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
    assert result["attempted"] % 2 == 0  # whole cycles of the two backlogs
    # every guarantee held but the one a rehearsal can never meet
    assert not _lines(proc, "failures")
    (backlogs,) = _lines(proc, "backlogs")
    assert [b["pods"] for b in backlogs] == [1400, 1400]
    for b in backlogs:
        assert b["deployments"] == 154 and b["namespaces"] == 2 and b["groups"] == 192
        assert b["by_kind"] == {"generic": 27, "zone-hostname-ct": 43, "ct": 21,
                                "zone-ct-arm64": 21, "windows": 21, "arm64": 21}
    (cut,) = _lines(proc, "oracle_cut")
    assert cut["kernel"]["scheduled"] == cut["host"]["scheduled"] == 140
    assert cut["kernel"]["failed"] == cut["host"]["failed"] == 0
    assert 0 < cut["kernel"]["nodes"] == cut["host"]["nodes"]


def test_the_cell_as_the_manifest_states_it():
    from benchmark.harness import manifest

    mine = manifest.load_cell(CELL)
    assert mine.chips == 1 and mine.traffic["kind"] == "assorted_cycle"
    assert {m["name"] for m in mine.end_to_end} == {"pods_per_s", "request_p50_s", "setup_s"}
    manyshape = manifest.load_cell("manyshape-50k.full")
    # manyshape's per-layer metrics but those whose lists name cells one by
    # one (this cell is on none of them), and the one this cell adds
    assert ({m["name"] for m in mine.per_layer} - {m["name"] for m in manyshape.per_layer}
            == {"kernel_ct_spread_s"})
    assert ({m["name"] for m in manyshape.per_layer} - {m["name"] for m in mine.per_layer}
            == {"encode_groups_s", "client_hop_s", "service_unspanned_s"})
    config = mine.config
    assert (config["pods"], config["types"], config["provisioners"]) == (10_000, 1_344, 1)
    assert config["catalog"] == "assorted" and config["reduced"] == []
    assert config["backlogs"] == [10_000, 10_000] and config["oracle"]["pods"] == 500
    for key in ("namespace_pods", "tiers"):
        assert config[key] == manyshape.config[key], key
    mix = config["pod_mix"]
    for key in ("parts_of", "cpu", "memory"):
        assert mix[key] == manyshape.config["pod_mix"][key], key
    assert [(k["kind"], k["parts"], k["spread"], k["node_selector"]) for k in mix["kinds"]] == [
        ("generic", 1, [], {"kubernetes.io/os": "linux"}),
        ("zone-hostname-ct", 2, ["zone", "hostname", "capacity-type"],
         {"kubernetes.io/os": "linux"}),
        ("ct", 1, ["capacity-type"], {"kubernetes.io/os": "linux"}),
        ("zone-ct-arm64", 1, ["zone", "capacity-type"],
         {"kubernetes.io/os": "linux", "kubernetes.io/arch": "arm64"}),
        ("windows", 1, [], {"kubernetes.io/os": "windows"}),
        ("arm64", 1, [], {"kubernetes.io/os": "linux", "kubernetes.io/arch": "arm64"}),
    ]
    assert sum(k["parts"] for k in mix["kinds"]) == mix["parts_of"]
    assert any("scheduled, failed and new nodes equal" in g for g in config["guarantees"])
    tiny = manifest.load_cell(CELL, rehearse=True).config
    assert tiny["backlogs"] == [1400, 1400] and tiny["oracle"]["pods"] == 140


def test_a_full_backlog_deals_the_stated_deployments():
    from benchmark.harness import manifest
    from benchmark.harness.podmix import seeded
    from benchmark.traffic.kinds.assorted_cycle import deal, summary

    config = manifest.load_cell(CELL).config
    for seed in (1, 2**31 + 5):
        got = summary(deal(10_000, config, seeded(seed, "batch0")), config["pod_mix"]["kinds"])
        assert got["deployments"] == 1094 and got["namespaces"] == 4
        assert got["groups"] == 1398
        assert got["by_kind"] == {"zone-hostname-ct": 311, "ct": 155, "zone-ct-arm64": 155,
                                  "windows": 155, "arm64": 155, "generic": 163}


def _older_pods(cell: str) -> tuple:
    """A batch of ``cell``'s traffic at its rehearsal sizes, and its catalog."""
    from benchmark.harness import manifest
    from benchmark.harness.podmix import pod_mix, seeded
    from benchmark.traffic.kinds import deployment_cycle
    from karpenter_core_tpu.cloudprovider.fake import instance_types

    loaded = manifest.load_cell(cell, rehearse=True)
    config, kind = loaded.config, loaded.traffic["kind"]
    sizes = config.get("backlogs") or config.get("batch_sizes") or [config.get("pods", 500)]
    n = min(max(sizes), 700)
    if kind == "deployment_cycle":
        pods = deployment_cycle.pods_of(deployment_cycle.deal(n, config, seeded(1, "batch0")))
    else:
        pods = pod_mix(n, seeded(1, "batch0"), config["pod_mix"])
    return pods, instance_types(config["types"])


@pytest.mark.parametrize("cell", OLDER)
def test_older_cells_do_not_trace_the_capacity_type_family(cell):
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_provisioner

    pods, catalog = _older_pods(cell)
    snapshot = TPUSolver(FakeCloudProvider(catalog), [make_provisioner()]).encode(pods)
    assert snapshot.features.ct_spread is False
    assert (snapshot.cls_groups[:, 6] == len(snapshot.groups)).all()


def test_the_new_cell_traces_it():
    from benchmark.harness import manifest
    from benchmark.harness.podmix import seeded
    from benchmark.traffic.kinds.assorted_cycle import catalog, deal, pods_of
    from karpenter_core_tpu.cloudprovider.fake import FakeCloudProvider
    from karpenter_core_tpu.solver.tpu import TPUSolver
    from karpenter_core_tpu.testing import make_provisioner

    config = manifest.load_cell(CELL, rehearse=True).config
    pods = pods_of(deal(700, config, seeded(1, "batch0")), config["pod_mix"]["kinds"])
    solver = TPUSolver(FakeCloudProvider(catalog("assorted")), [make_provisioner()])
    assert solver.encode(pods).features.ct_spread is True


@pytest.mark.parametrize("cell", OLDER)
def test_the_capacity_type_metric_stays_out_of_the_older_cells(cell):
    from benchmark.harness import manifest

    assert "kernel_ct_spread_s" not in {m["name"] for m in manifest.load_cell(cell).per_layer}
