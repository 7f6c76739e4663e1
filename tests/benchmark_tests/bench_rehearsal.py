"""Shared by the rehearsal tests: run one cell of the benchmark in a fresh
process on the CPU, at the configuration's tiny ``rehearse`` sizes."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def clean_env(devices: int = 1, **extra) -> dict:
    """The program's own defaults, not this suite's pins (conftest.py forces
    8 virtual devices with the mesh and the warm-up off)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "KC_SOLVER_MESH", "KC_TPU_WARMUP")}
    env["JAX_PLATFORMS"] = "cpu"
    if devices > 1:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(extra)
    return env


def manifest_command(root: str = REPO) -> list:
    """The manifest's own command, run by this interpreter: the driver runs
    exactly this list (allocator settings included) from the checkout's root."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    return [sys.executable if part == "python3" else part for part in command]


def run_cell(workload: str, *flags: str, root: str = REPO, devices: int = 1,
             seconds: str = "1", **env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [*manifest_command(root), "--workload", workload, "--seed", "7",
         "--seconds", seconds, *flags],
        capture_output=True, text=True, timeout=420, cwd=root,
        env=clean_env(devices, **env),
    )


def last_line(proc: subprocess.CompletedProcess) -> dict:
    assert proc.returncode == 0, (proc.stdout[-2000:], proc.stderr[-3000:])
    return json.loads(proc.stdout.splitlines()[-1])


def assert_rehearsal(result: dict, metrics: set, traced: bool, devices: int = 1) -> None:
    """The contract's keys and nothing else; a rehearsal can never pass."""
    assert set(result) == RESULT_KEYS | ({"breakdown"} if traced else set())
    assert result["correct"] is False
    assert result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == devices
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert set(result["metrics"]) == metrics
    for name, metric in result["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["value"] is not None, name
    if traced:
        assert result["device"]["busy_s"] > 0
        assert result["device"]["window_s"] >= result["device"]["busy_s"] / devices
        for part in ("device_ops", "idle_gaps"):
            rows = result["breakdown"][part]
            assert 0 < len(rows) <= 10 and all(len(r) == 2 for r in rows)
