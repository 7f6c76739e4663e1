"""The ``sweep`` traffic (kind ``consolidate_cycle``) rehearsed end to end in
a fresh process on the CPU: a seeded cluster of 40 nodes, every one a
candidate, shipped whole with every ``/Consolidate``; two disruption orders
alternating; every answer held to the plain reference and the cut to the
host's own simulation; and the five per-layer metrics the cell adds."""

import json

from bench_rehearsal import assert_rehearsal, last_line, run_cell

CELL = "consolidate-5k.sweep"
OTHERS = ("backlog-50k.full", "backlog-50k.churn", "suite-400.mixed",
          "backlog-50k-mesh4.full", "brownfield-5k.full", "manyshape-50k.full")
NEW = {"consolidate_encode_s", "consolidate_split_s", "consolidate_sweep_s",
       "consolidate_decode_s", "service_consolidate_unspanned_s"}


def _lines(proc, key: str) -> list:
    """``key``'s value from every output line that carries it."""
    found = []
    for line in proc.stdout.splitlines():
        if line.startswith("{") and '"%s"' % key in line:
            found += [json.loads(line)[key]] if key in json.loads(line) else []
    return found


def test_consolidate_rehearsal_untraced():
    proc = run_cell(CELL, "--trace", "0", "--rehearse")
    result = last_line(proc)
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
    assert result["attempted"] % 2 == 0  # whole cycles of the two orders
    assert not _lines(proc, "failures")
    answers = _lines(proc, "answers")
    assert [a["order"] for a in answers] == [0, 1]
    assert all(a["action"] in ("delete", "replace") and 2 <= a["removed"] <= a["of"] == 40
               for a in answers)
    (cut,) = _lines(proc, "oracle_cut")
    assert cut["nodes"] == 16 and cut["host_accepts"] is True
    assert cut["served"][1] >= cut["host"][1] >= 2
    (program,) = _lines(proc, "program")  # rides the window's line
    assert program["builds"] > 0 and program["compiles_in_window"] == 0


def test_consolidate_rehearsal_traced_reports_the_searchs_layers():
    proc = run_cell(CELL, "--trace", "1", "--rehearse")
    result = last_line(proc)
    # the shared metrics a rehearsal reports through the spans the normal
    # dispatch path opens, and this cell's own five
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "encode_s", "dispatch_s", "compiles_in_window", "first_request_s",
        "backend_compiles", "device_wait_s", "kernel_device_s", "decode_s", "fetch_s",
    } | NEW, traced=True)
    assert not _lines(proc, "failures")
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    # the handler's wall is the wrapper's; the search's spans lie inside it
    assert metrics["consolidate_sweep_s"] >= metrics["device_wait_s"] > 0
    assert metrics["encode_s"] <= metrics["consolidate_encode_s"] + metrics["consolidate_split_s"]
    (window,) = _lines(proc, "window")
    assert window["units_per_cycle"] == 2 and window["units"] == result["attempted"]


def test_the_cell_as_the_manifest_states_it():
    from benchmark.harness import manifest

    for cell in OTHERS:
        assert not NEW & {m["name"] for m in manifest.load_cell(cell).per_layer}
    mine = manifest.load_cell(CELL)
    assert NEW <= {m["name"] for m in mine.per_layer}
    # what reads /SolveClasses' own spans finds nothing here, and says so
    assert not {"client_hop_s", "service_unspanned_s", "solve_core_roofline"} & {
        m["name"] for m in mine.per_layer}
    assert {m["name"] for m in mine.end_to_end} == {"pods_per_s", "request_p50_s", "setup_s"}
    assert mine.chips == 1 and mine.traffic["kind"] == "consolidate_cycle"
    config, live = mine.config, manifest.load_cell("brownfield-5k.full").config
    for key in ("existing_nodes", "node_types", "utilisation", "pod_mix", "types",
                "provisioners", "chips"):
        assert config[key] == live[key], key
    assert (config["candidates"], config["pending_pods"], config["orders"]) == ("all", 0, 2)
    assert config["reduced"] == [] and config["existing_nodes"] == 5000
    assert 150 <= config["oracle"]["nodes"] <= 300
    tiny = manifest.load_cell(CELL, rehearse=True).config
    assert (tiny["existing_nodes"], tiny["types"], tiny["oracle"]) == (40, 100, {"nodes": 16})
