"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here by the name in
BENCHMARK.json — so a later PR adds a cell by adding files and one entry,
and edits nothing that exists (benchmark/README.md).
"""

import importlib
import json
import os
from typing import NamedTuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _read(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell(NamedTuple):
    name: str
    chips: int
    config: dict  # benchmark/configs/<config>.json, rehearsal sizes applied
    traffic: dict  # benchmark/traffic/<traffic>.json
    end_to_end: list  # the manifest entries this cell reports
    per_layer: list  # the manifest entries this cell reports, reader attached


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, rehearse: bool = False, root: str = ROOT) -> Cell:
    manifest = _read(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in manifest["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: {known})")
    config_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    config = _read(os.path.join(root, config_entry["file"]))
    if rehearse:
        config = {**config, **config["rehearse"]}
    traffic = _read(os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json"))
    end_to_end = [m for m in manifest["end_to_end"] if applies(m, name)]
    reported = {m["name"] for m in end_to_end}
    per_layer = [
        {**m, "reader": _read(os.path.join(BENCH_DIR, "layer_metrics", m["name"] + ".json"))}
        for m in manifest["per_layer"]
        if applies(m, name) and m["moves"] in reported
    ]
    return Cell(name, int(entry["chips"]), config, traffic, end_to_end, per_layer)


def load_kind(name: str):
    """The traffic generator ``benchmark/traffic/kinds/<name>.py``: its
    ``Kind`` class (README: setup / unit / settle / check)."""
    return importlib.import_module(f"benchmark.traffic.kinds.{name}").Kind


def load_source(name: str):
    """The per-layer reader ``benchmark/harness/sources/<name>.py``: its
    ``read(spec, facts)``, which returns None when it finds nothing."""
    return importlib.import_module(f"benchmark.harness.sources.{name}").read
