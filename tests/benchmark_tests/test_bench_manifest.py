"""BENCHMARK.json against the benchmark's contract, and the files it names:
what a driver would refuse before a single run is refused here first."""

import json
import os
import re

import pytest

from benchmark.harness import manifest

ROOT = manifest.ROOT
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
PLAIN_PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _under_paths(bench, path):
    return any(path == p or path.startswith(p + "/") for p in bench["paths"])


def test_keys_command_paths_and_size(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["command"]) <= 32
    assert 1 <= len(bench["paths"]) <= 16
    for path in bench["paths"]:
        assert PLAIN_PATH.match(path) and ".." not in path and not path.startswith("/")
        assert os.path.isdir(os.path.join(ROOT, path))
    files = [a for a in bench["command"] if "/" in a]
    assert files and all(_under_paths(bench, a) for a in files)
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51


def test_a_full_check_fits_its_budget_with_every_cell_the_contract_allows(bench):
    cells = 24
    runs = 2 + 14 * cells
    total = runs * (bench["run_seconds"] + 60) + cells * 2 * 90 + 1200
    assert total <= 43200


def test_names_are_plain_and_used_once(bench):
    for group in ("configs", "workloads"):
        names = [e["name"] for e in bench[group]]
        assert all(NAME.match(n) for n in names) and len(set(names)) == len(names)
    metrics = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert all(NAME.match(n) for n in metrics) and len(set(metrics)) == len(metrics)
    for path in bench["paths"]:
        for folder, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in folder:
                continue
            for name in files:
                rel = os.path.relpath(os.path.join(folder, name), ROOT)
                assert PLAIN_PATH.match(rel), rel


def test_configurations(bench):
    assert 1 <= len(bench["configs"]) <= 24
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(sources)) == len(sources)  # deployments of one suite need sources that differ
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["source"].startswith("https://") and len(c["why"]) <= 200
        assert _under_paths(bench, c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"] and body["reduced"] == c["reduced"]
        for key in ("source", "types", "provisioners", "chips", "mapping",
                    "guarantees", "oracle", "assumed", "rehearse"):
            assert key in body, (c["name"], key)
        cells = [w for w in bench["workloads"] if w["config"] == c["name"]]
        assert all(w["chips"] == body["chips"] for w in cells)


def test_cells(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in bench["configs"]}
    for w in cells:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert 0 < len(w["why"]) <= 200
        traffic = os.path.join(manifest.BENCH_DIR, "traffic", w["traffic"] + ".json")
        with open(traffic) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(manifest.BENCH_DIR, "traffic", "kinds", kind + ".py"))
    four = sum(1 for w in cells if w["chips"] == 4)
    assert four <= max(len(cells) // 2, 1)


def test_end_to_end_metrics(bench):
    metrics = bench["end_to_end"]
    assert 1 <= len(metrics) <= 16
    cells = {w["name"] for w in bench["workloads"]}
    for m in metrics:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["better"] in ("lower", "higher") and m["source"] in E2E_SOURCES
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    setup = next(m for m in metrics if m["name"] == "setup_s")
    assert setup["bound"] == 0.25 and "workloads" not in setup


def test_per_layer_metrics_each_with_a_reader_of_its_own(bench):
    metrics = bench["per_layer"]
    assert 1 <= len(metrics) <= 128
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = {w["name"] for w in bench["workloads"]}
    for m in metrics:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e and m["layer"]
        assert set(m.get("workloads", cells)) <= cells
        assert (m["unit"] == "%") if m["name"].endswith("_roofline") else True
        with open(os.path.join(manifest.BENCH_DIR, "layer_metrics", m["name"] + ".json")) as f:
            reader = json.load(f)
        assert callable(manifest.load_source(reader["kind"]))
    on_disk = {f[:-5] for f in os.listdir(os.path.join(manifest.BENCH_DIR, "layer_metrics"))}
    assert on_disk == {m["name"] for m in metrics}


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer(bench):
    for w in bench["workloads"]:
        cell = manifest.load_cell(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        # a per-layer metric is reported only where the metric it moves is
        assert all(m["moves"] in names for m in cell.per_layer)
        assert cell.chips == w["chips"] == cell.config["chips"]


def test_the_tail_is_one_run_py_computes_and_only_where_the_window_can_hold_it(bench):
    from benchmark.harness import stats

    tails = [m for m in bench["end_to_end"]
             if m["name"].startswith("request_p") and m["name"] != "request_p50_s"]
    assert [t["name"] for t in tails] == ["request_p90_s"]
    assert tails[0]["name"] in {f"request_p{p:g}_s" for p in stats.PERCENTILES[1:]}
    assert tails[0]["workloads"] == ["suite-400.mixed"]


def test_the_command_holds_no_cells_name(bench):
    with open(os.path.join(manifest.BENCH_DIR, "run.py")) as f:
        source = f.read()
    for entry in bench["workloads"] + bench["configs"]:
        assert entry["name"] not in source
    assert "if workload ==" not in source and "args.workload ==" not in source


def test_an_unknown_cell_is_named_with_the_cells_there_are():
    with pytest.raises(KeyError, match="backlog-50k.full"):
        manifest.load_cell("no-such-cell")


def test_rehearsal_sizes_replace_the_real_ones_only_when_asked():
    real = manifest.load_cell("backlog-50k.full").config
    tiny = manifest.load_cell("backlog-50k.full", rehearse=True).config
    assert real["pods"] == 50_000 and real["types"] == 1_000
    assert tiny["pods"] < 5_000 and tiny["types"] <= 100 and tiny["provisioners"] == 5
