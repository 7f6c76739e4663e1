"""chip_smoke.py end to end, at toy size, on the CPU the caller pinned.

The only way the smoke ever runs off the chip: ``--cpu-dry-run`` under an
explicit ``JAX_PLATFORMS=cpu``.  It must drive every leg, report
``platform: cpu`` — and be impossible to mistake for a chip pass.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# 1 400 pods: the smallest size at which this 39-class mix keeps the churned
# lineage identical to a from-scratch solve (at 700 it does not, on CPU too)
TINY = ["--pods", "1400", "--types", "24", "--operator-pods", "300",
        "--operator-types", "24", "--oracle-pods", "140", "--sweep-nodes", "12"]


@pytest.mark.compile  # compiles every leg's executables: ~30 s
def test_cpu_dry_run_cannot_be_mistaken_for_a_chip_pass():
    # the program's own defaults, not this suite's pins (conftest.py forces
    # 8 virtual devices with the mesh and the warmup off)
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "KC_SOLVER_MESH", "KC_TPU_WARMUP")}
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--cpu-dry-run", *TINY],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env={**env, "JAX_PLATFORMS": "cpu"},
    )
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert proc.returncode == chip_smoke.EXIT_DRY_RUN, proc.stdout[-3000:]
    assert lines[0]["device"]["platform"] == "cpu"
    last = lines[-1]
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert "failures" not in last
    # every leg answered
    requests = {line["request"] for line in lines if "request" in line}
    assert {"solve_classes.cold", "solve_classes.warm3", "tenant.delta2",
            "consolidate", "kernel.session_churn", "operator.provision",
            "oracle.host"} <= requests
