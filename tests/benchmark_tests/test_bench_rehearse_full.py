"""The ``full`` traffic rehearsed end to end in a fresh process on the CPU, and
the two ways the command must refuse to run."""

import json
import os
import shutil

from bench_rehearsal import REPO, assert_rehearsal, last_line, run_cell


def test_full_rehearsal_untraced():
    result = last_line(run_cell("backlog-50k.full", "--trace", "0", "--rehearse"))
    assert_rehearsal(result, {"pods_per_s", "request_p50_s", "nodes_per_kpod", "setup_s"},
                     traced=False)
    assert result["attempted"] % 2 == 0  # whole cycles of the two backlogs


def test_the_manifests_command_sets_nothing_the_chart_does_not():
    """The yardstick runs the allocator a deployment runs: the default."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        assert json.load(f)["command"] == ["python3", "benchmark/run.py"]
    proc = run_cell("backlog-50k.full", "--trace", "0", "--rehearse", seconds="0.1")
    setup = json.loads(proc.stdout.splitlines()[0])
    assert setup["allocator"] == {k: v for k, v in os.environ.items()
                                  if k == "PYTHONMALLOC" or k.startswith("MALLOC_")}


def test_without_a_tpu_nothing_runs_and_nothing_is_printed():
    proc = run_cell("backlog-50k.full", "--trace", "0")  # no --rehearse
    assert proc.returncode == 2 and proc.stdout == ""
    assert "nothing was run" in proc.stderr


def test_another_number_of_chips_than_the_cell_asks_for_is_refused():
    proc = run_cell("backlog-50k.full", "--trace", "0", "--rehearse", devices=4)
    assert proc.returncode == 2 and proc.stdout == ""


def test_without_the_program_nothing_runs_and_nothing_is_printed(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under paths."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell("backlog-50k.full", "--trace", "0", "--rehearse", root=str(tmp_path))
    assert proc.returncode != 0 and proc.stdout == ""
