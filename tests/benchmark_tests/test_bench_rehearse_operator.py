"""The ``operator-top`` traffic (kind ``operator_cycle``) rehearsed end to end
in a fresh process on the CPU: an ``Operator`` composed and started beside the
harness's sidecar; 300 and 400 pending pods provisioned once each in set-up,
then six seeded draws of 400 cycled, one provisioning pass a unit, scaled down
to an empty cluster between units; every outcome held to the plain reference
and the 300-pod warm-up batch to the host scheduler."""

import json
import os
import shutil

from bench_rehearsal import REPO, assert_rehearsal, last_line, run_cell

CELL = "suite-400.operator"
OTHERS = ("backlog-50k.full", "backlog-50k.churn", "suite-400.mixed", "backlog-50k-mesh4.full",
          "brownfield-5k.full", "manyshape-50k.full", "consolidate-5k.sweep")
NEW = {"operator_pending_s", "operator_split_s", "operator_wire_s", "operator_launch_s",
       "operator_unspanned_s"}


def _lines(proc, key: str) -> list:
    """``key``'s value from every output line that carries it."""
    found = []
    for line in proc.stdout.splitlines():
        if line.startswith("{") and '"%s"' % key in line:
            found += [json.loads(line)[key]] if key in json.loads(line) else []
    return found


def test_operator_rehearsal_untraced():
    proc = run_cell(CELL, "--trace", "0", "--rehearse")
    result = last_line(proc)
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "setup_s"}, traced=False)
    assert result["attempted"] % 6 == 0  # whole cycles of the six draws
    assert not _lines(proc, "failures")
    (told,) = _lines(proc, "outcomes")
    # each warm-up size once, then the six draws of the timed size
    assert [o["pods"] for o in told] == [300, 400] + [400] * 6
    assert all(o["scheduled"] == o["pods"] and not o["failed"] and o["nodes"] > 0 for o in told)
    (window,) = _lines(proc, "window")
    assert window["units_per_cycle"] == 6 and window["units"] == result["attempted"]
    (samples,) = _lines(proc, "samples")
    assert set(samples["pods"]) == {400}  # every timed unit is the timed size
    # a machine a node, every one deleted again: the warm-up sizes once, the
    # draws once in set-up and once a cycle of the window
    (line,) = [json.loads(ln) for ln in proc.stdout.splitlines() if '"machines_created"' in ln]
    cycles = 1 + result["attempted"] // 6
    assert line["machines_created"] == line["machines_deleted"] == sum(
        o["nodes"] for o in told[:2]) + cycles * sum(o["nodes"] for o in told[2:])
    (program,) = _lines(proc, "program")  # rides the window's line
    assert program["builds"] > 0 and program["compiles_in_window"] == 0
    assert program["solve_modes"] == []  # every solve was the sidecar's: none on the host


def test_operator_rehearsal_traced_reports_the_shared_layers_and_its_own_five(tmp_path):
    # from a copy of the benchmark: the five read the capture a traced run
    # leaves under ITS root, and under -n 6 every traced rehearsal of the
    # repo's own root rewrites one .kc_cache/bench_trace (PERF.md section 7 (c))
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    proc = run_cell(CELL, "--trace", "1", "--rehearse", root=str(tmp_path), PYTHONPATH=REPO)
    result = last_line(proc)
    # the shared metrics read the handler's spans; the five operator_* read the
    # capture's kc: annotations through controller_span, in a rehearsal too
    assert_rehearsal(result, {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "encode_s", "dispatch_s", "compiles_in_window", "first_request_s",
        "backend_compiles", "device_wait_s", "kernel_device_s", "decode_s", "fetch_s",
    } | NEW, traced=True)
    assert not _lines(proc, "failures")
    metrics = {name: m["value"] for name, m in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert metrics["client_s"] > 0  # the operator less the sidecar
    assert all(metrics[name] > 0 for name in NEW)
    (samples,) = _lines(proc, "samples")
    assert set(samples["pods"]) == {400}
    # the controller's phases, a unit: one span each, the counts of its work
    (units,) = _lines(proc, "unit_spans")
    assert len(units) == 6  # the last cycle
    for unit in units:
        assert {"provisioning.reconcile", "provisioning.pending", "provisioning.split",
                "provisioning.wire", "provisioning.launch", "client.pack", "client.rpc",
                "client.unpack"} <= set(unit)
        assert "provisioning.remainder" not in unit
        pods = unit["provisioning.pending"]["pods"]
        assert pods == 400 and unit["provisioning.pending"]["listed"] == pods
        split = unit["provisioning.split"]
        assert split["interned"] == split["classes"] and split["host_pods"] == 0
        launch = unit["provisioning.launch"]
        assert launch["created"] == launch["machines"] > 0 and launch["events"] == pods
        assert launch["state_rebuilds"] >= 2 * launch["machines"]


def test_the_cell_as_the_manifest_states_it():
    from benchmark.harness import manifest

    for cell in OTHERS:
        assert not NEW & {m["name"] for m in manifest.load_cell(cell).per_layer}
    mine = manifest.load_cell(CELL)
    names = {m["name"] for m in mine.per_layer}
    assert NEW <= names
    import re

    from benchmark.harness.sources import annotation_total, controller_span

    assert controller_span.per_unit is annotation_total.per_unit  # the same arithmetic
    sources = ""
    for path in ("controllers/provisioning.py", "service/snapshot_channel.py"):
        with open(os.path.join(manifest.ROOT, "karpenter_core_tpu", path)) as f:
            sources += f.read()
    opened = set(re.findall(r'(?:span|span_remote|traced)\(\s*"([\w.]+)"', sources))
    for metric in mine.per_layer:
        if metric["name"] in NEW:
            reader = metric["reader"]
            assert reader["kind"] == "controller_span"
            assert (metric["layer"], metric["moves"]) == ("operator", "request_p50_s")
            # a renamed span would otherwise read as nothing
            assert set(reader["match"] + reader.get("less", [])) <= opened, metric["name"]
    # what lists its cells and reads another path's spans does not list this one
    assert not {"client_classify_s", "client_expand_s", "solve_core_roofline",
                "request_p90_s"} & names
    assert {m["name"] for m in mine.end_to_end} == {"pods_per_s", "request_p50_s", "setup_s"}
    assert mine.chips == 1 and mine.traffic["kind"] == "operator_cycle"
    assert (mine.traffic["sizes"], mine.traffic["warm_sizes"]) == ("timed_sizes", "batch_sizes")
    assert mine.traffic["batches_per_size"] == 6
    config, suite = mine.config, manifest.load_cell("suite-400.mixed").config
    for key in ("pod_mix", "types", "provisioners", "chips"):
        assert config[key] == suite[key], key  # key for key, byte for byte
    assert config["batch_sizes"] == [n for n in suite["batch_sizes"] if n >= 256]
    assert config["timed_sizes"] == [max(suite["batch_sizes"])]
    assert config["reduced"] == ["batch_sizes"] and "batch_sizes" in config["reduced_why"]
    assert config["operator"]["kernel_min_pods"] == 256
    assert {k: v for k, v in config["assumed"].items() if k.startswith("pod mix")} == {
        k: v for k, v in suite["assumed"].items() if k.startswith("pod mix")}
    tiny = manifest.load_cell(CELL, rehearse=True).config
    assert (tiny["batch_sizes"], tiny["types"], tiny["oracle"]) == ([300, 400], 100, {"pods": 300})
    assert tiny["timed_sizes"] == [400]
    assert min(tiny["batch_sizes"]) >= config["operator"]["kernel_min_pods"]


def test_with_one_timed_size_every_unit_of_the_cycle_is_alike():
    from benchmark.harness import manifest
    from benchmark.traffic.kinds.operator_cycle import batches

    cell = manifest.load_cell(CELL)
    warm_up, cycle = batches(cell.traffic, cell.config)
    assert [n for n, _stream in warm_up] == [500, 1000, 2000, 5000]  # ascending, once each
    assert [n for n, _stream in cycle] == [5000] * 6  # group 6, every unit's pods equal
    streams = [stream for _n, stream in warm_up + cycle]
    assert len(set(streams)) == len(streams)  # a draw of its own each
    assert [stream for _n, stream in cycle] == [f"batch0.{r}" for r in range(6)]
    # PR 37's traffic, for contrast: four sizes a pass, so units of four walls
    _none, mixed = batches({"sizes": "batch_sizes", "batches_per_size": 3}, cell.config)
    assert [n for n, _stream in mixed] == [500, 1000, 2000, 5000] * 3
