"""The ``deployments-full`` traffic (kind ``deployment_cycle``) rehearsed end
to end in a fresh process on the CPU: two backlogs of 1 400 pods in 154
Deployments over two namespaces, every Deployment held to its guarantee, the
oracle cut, and the one per-layer metric the cell adds."""

import json

import pytest
from bench_rehearsal import assert_rehearsal, last_line, run_cell

CELL = "manyshape-50k.full"
OTHERS = ("backlog-50k.full", "backlog-50k.churn", "suite-400.mixed",
          "backlog-50k-mesh4.full", "brownfield-5k.full")


def _lines(proc, key: str) -> list:
    return [json.loads(line)[key] for line in proc.stdout.splitlines()
            if line.startswith('{"%s"' % key)]


def test_manyshape_rehearsal_untraced():
    proc = run_cell(CELL, "--trace", "0", "--rehearse")
    result = last_line(proc)
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
    assert result["attempted"] % 2 == 0  # whole cycles of the two backlogs
    assert not _lines(proc, "failures")
    (backlogs,) = _lines(proc, "backlogs")
    assert [b["pods"] for b in backlogs] == [1400, 1400]
    assert all(b["deployments"] == 154 and b["namespaces"] == 2 and b["groups"] == 84
               for b in backlogs)
    (cut,) = _lines(proc, "oracle_cut")
    assert cut["kernel"]["scheduled"] == cut["host"]["scheduled"] == 140
    assert cut["kernel"]["failed"] == cut["host"]["failed"] == 0
    assert 0 < cut["kernel"]["nodes"] <= cut["host"]["nodes"]


def test_manyshape_rehearsal_traced_reports_the_group_section_of_encode():
    proc = run_cell(CELL, "--trace", "1", "--rehearse")
    result = last_line(proc)
    # the shared metrics a rehearsal reports, and this cell's own one
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "encode_s", "dispatch_s", "compiles_in_window", "first_request_s",
        "backend_compiles", "device_wait_s", "kernel_device_s", "decode_s", "fetch_s",
        "encode_groups_s",
    }, traced=True)
    # every guarantee held but the one a rehearsal can never meet
    assert not _lines(proc, "failures")
    metrics = result["metrics"]
    assert metrics["compiles_in_window"]["value"] == 0
    assert 0 < metrics["encode_groups_s"]["value"] <= metrics["encode_s"]["value"]
    (window,) = _lines(proc, "window")
    assert window["units_per_cycle"] == 2 and window["units"] == result["attempted"]


def test_the_cell_as_the_manifest_states_it():
    from benchmark.harness import manifest

    mine = manifest.load_cell(CELL)
    assert "encode_groups_s" in {m["name"] for m in mine.per_layer}
    assert {m["name"] for m in mine.end_to_end} == {"pods_per_s", "request_p50_s", "setup_s"}
    assert mine.chips == 1 and mine.traffic["kind"] == "deployment_cycle"
    config = mine.config
    assert (config["pods"], config["types"], config["provisioners"]) == (50_000, 1_000, 5)
    assert config["backlogs"] == [50_000, 50_000] and config["namespace_pods"] == 3_000
    assert config["reduced"] == ["chips"] and config["oracle"]["pods"] == 1_000
    assert [(t["name"], t["replicas"], t["share"]) for t in config["tiers"]] == [
        ("big", 250, "1/4"), ("medium", 30, "1/4"), ("small", 5, "1/2")]
    northstar = manifest.load_cell("backlog-50k.full").config
    assert config["pod_mix"] == northstar["pod_mix"] and config["mapping"] == northstar["mapping"]
    # northstar's guarantees, but the cut's node count one-sided and no tenant line
    shared = [g for g in northstar["guarantees"]
              if "oracle cut" not in g and not g.startswith("tenant traffic")]
    assert len(shared) == 5 and set(shared) <= set(config["guarantees"])
    assert any("as many or FEWER, never more" in g for g in config["guarantees"])
    assert "oracle cut: nodes" in config["assumed"]
    tiny = manifest.load_cell(CELL, rehearse=True).config
    assert tiny["backlogs"] == [1400, 1400] and tiny["namespace_pods"] == 700


@pytest.mark.parametrize("cell", OTHERS)
def test_the_group_metric_stays_out_of_the_other_cells(cell):
    from benchmark.harness import manifest

    assert "encode_groups_s" not in {m["name"] for m in manifest.load_cell(cell).per_layer}
