"""The ``cluster-full`` traffic (kind ``cluster_cycle``) rehearsed end to end
in a fresh process on the CPU: a seeded cluster of 40 nodes shipped whole with
every request, two backlogs of 200 pending pods, the oracle cut with state
nodes, and the three per-layer metrics of the existing-node path."""

import json

from bench_rehearsal import assert_rehearsal, last_line, run_cell

CELL = "brownfield-5k.full"


def test_cluster_rehearsal_untraced():
    result = last_line(run_cell(CELL, "--trace", "0", "--rehearse"))
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
    assert result["attempted"] % 2 == 0  # whole cycles of the two backlogs


def test_cluster_rehearsal_traced_reports_the_existing_node_layers():
    proc = run_cell(CELL, "--trace", "1", "--rehearse")
    result = last_line(proc)
    # the shared metrics a rehearsal reports, and this cell's own three
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "encode_s", "dispatch_s", "compiles_in_window", "first_request_s",
        "backend_compiles", "device_wait_s", "kernel_device_s", "decode_s", "fetch_s",
        "service_decode_nodes_s", "encode_existing_s", "decode_existing_s",
    }, traced=True)
    # every guarantee held but the one a rehearsal can never meet
    assert not [line for line in proc.stdout.splitlines() if line.startswith('{"failures"')]
    assert result["metrics"]["compiles_in_window"]["value"] == 0
    window = next(json.loads(line)["window"] for line in proc.stdout.splitlines()
                  if line.startswith('{"window"'))
    assert window["units_per_cycle"] == 2 and window["units"] == result["attempted"]


def test_the_existing_node_metrics_stay_out_of_the_other_cells():
    from benchmark.harness import manifest

    new = {"service_decode_nodes_s", "encode_existing_s", "decode_existing_s"}
    for cell in ("backlog-50k.full", "backlog-50k.churn", "suite-400.mixed",
                 "backlog-50k-mesh4.full"):
        assert not new & {m["name"] for m in manifest.load_cell(cell).per_layer}
    mine = manifest.load_cell(CELL)
    assert new <= {m["name"] for m in mine.per_layer}
    assert {m["name"] for m in mine.end_to_end} == {"pods_per_s", "request_p50_s", "setup_s"}
    assert mine.config["existing_nodes"] == 5000 and mine.config["backlogs"] == [10000, 10000]
    tiny = manifest.load_cell(CELL, rehearse=True).config
    assert tiny["existing_nodes"] == 40 and tiny["backlogs"] == [200, 200]
