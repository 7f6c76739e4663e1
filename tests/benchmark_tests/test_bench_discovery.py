"""Driven by data, as a requirement: a cell, a configuration, a traffic mix,
a generator kind, a per-layer metric and a reader kind dropped into a copy of
the benchmark as NEW files, plus one appended entry each in BENCHMARK.json,
are found and run — and no file that was there is edited."""

import hashlib
import json
import os
import shutil

from bench_rehearsal import REPO, assert_rehearsal, last_line, run_cell

CONFIG = {
    "name": "extra-deployment", "source": "a test", "pods": 300, "types": 24,
    "provisioners": 2, "chips": 1, "mapping": "one sidecar", "guarantees": [],
    "oracle": {"pods": 60}, "reduced": [], "assumed": {},
    "rehearse": {"pods": 300},
}
with open(os.path.join(REPO, "benchmark", "configs", "upstream-suite-400.json")) as f:
    CONFIG["pod_mix"] = json.load(f)["pod_mix"]
TRAFFIC = {"kind": "thirds", "why": "three backlogs of a third of the pods each"}
KIND = '''
from benchmark.traffic.kinds import size_cycle


class Kind(size_cycle.Kind):
    def sizes(self):
        return [self.ctx.config["pods"] // 3] * 3
'''
READER = {"kind": "slowest_call", "field": "client_s"}
SOURCE = '''
def read(spec, facts):
    calls = [c for u in facts["units"] for c in u.calls]
    return max(getattr(c, spec["field"]) for c in calls) if calls else None
'''


def _digests(root):
    out = {}
    for folder, _, files in os.walk(root):
        if "__pycache__" in folder:
            continue
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(f.read()).hexdigest()
    return out


def test_new_files_and_one_appended_entry_each_are_enough(tmp_path):
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(tmp_path / "benchmark")

    bench_dir = tmp_path / "benchmark"
    (bench_dir / "configs" / "extra-deployment.json").write_text(json.dumps(CONFIG))
    (bench_dir / "traffic" / "thirds-cycle.json").write_text(json.dumps(TRAFFIC))
    (bench_dir / "traffic" / "kinds" / "thirds.py").write_text(KIND)
    (bench_dir / "layer_metrics" / "slowest_client_s.json").write_text(json.dumps(READER))
    (bench_dir / "harness" / "sources" / "slowest_call.py").write_text(SOURCE)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "extra-deployment", "source": "https://example.org/extra",
        "file": "benchmark/configs/extra-deployment.json", "reduced": [], "why": "test"})
    bench["workloads"].append({
        "name": "extra.thirds", "config": "extra-deployment",
        "traffic": "thirds-cycle", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "slowest_client_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "client", "moves": "request_p50_s",
        "workloads": ["extra.thirds"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # the program comes from the repo; the benchmark from the copy
    proc = run_cell("extra.thirds", "--trace", "1", "--rehearse",
                    root=str(tmp_path), PYTHONPATH=REPO)
    result = last_line(proc)
    assert result["metrics"]["slowest_client_s"]["value"] > 0
    assert result["attempted"] % 3 == 0
    expected = {
        "client_s", "response_mb", "reply_unpack_s", "service_self_s", "reply_pack_s",
        "encode_s", "dispatch_s", "compiles_in_window", "first_request_s",
        "backend_compiles", "device_wait_s", "kernel_device_s", "decode_s",
        "fetch_s", "slowest_client_s",
    }
    assert_rehearsal(result, expected, traced=True)

    after = _digests(tmp_path / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
    assert len(after) == len(before) + 5
