"""chip_smoke.py and the chip-or-fail plumbing around it (PR 21).

The smoke's verdict must fail on every quiet way off the device; the compile
cache must land where the environment (or the fixed in-checkout default)
says; a build error must surface instead of rerouting the solve; the input
builders give what they are asked for; ``make presubmit`` names only what
exists.  (The end-to-end CPU dry run of the smoke itself is
tests/test_smoke_dry_run.py: it compiles every leg, so it sorts after the
cheap suites.)
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from karpenter_core_tpu.utils import compilecache  # noqa: E402

def _clean_observation() -> dict:
    return {
        "platform": "tpu",
        "expect_platform": "tpu",
        "off_device_outputs": [],
        "request_errors": [],
        "builds": 7,
        "plain_jit_runs": 0,
        "watchdog_timeouts": {},
        "fallback_counters": {
            "karpenter_tpu_kernel_fallback": {},
            "karpenter_degraded_solves_total": {},
        },
        "breaker_states": {"solver-backend": "closed", "tenant:smoke": "closed"},
        "solve_modes": {"scan", "full", "delta"},
        "warm_window_compiles": 0,
    }


class TestVerdict:
    def test_clean_run_passes(self):
        assert chip_smoke.verdict(_clean_observation()) == []

    @pytest.mark.parametrize("field,value,needle", [
        ("platform", "cpu", "backend is 'cpu'"),
        ("off_device_outputs", ["SolveOutputs[3] on ['cpu']"], "not on a tpu device"),
        ("request_errors", ["solve_classes.cold: RpcError: boom"], "request raised"),
        ("builds", 0, "built no executable"),
        ("plain_jit_runs", 1, "plain-jit"),
        ("watchdog_timeouts", {"solve.dispatch": 1}, "watchdog timeouts"),
        ("fallback_counters",
         {"karpenter_tpu_kernel_fallback": {"reason=backend-error": 1.0},
          "karpenter_degraded_solves_total": {}},
         "karpenter_tpu_kernel_fallback moved"),
        ("fallback_counters",
         {"karpenter_tpu_kernel_fallback": {},
          "karpenter_degraded_solves_total": {"controller=provisioning": 1.0}},
         "karpenter_degraded_solves_total moved"),
        ("breaker_states", {"solver-backend": "open"}, "breaker solver-backend is open"),
        ("solve_modes", {"scan", "relax-fallback:existing-nodes"}, "relax-fallback"),
        ("solve_modes", {"host"}, "'host' engaged"),
        ("warm_window_compiles", 2, "inside the warm window"),
    ])
    def test_each_quiet_way_off_the_device_fails(self, field, value, needle):
        obs = _clean_observation()
        obs[field] = value
        failures = chip_smoke.verdict(obs)
        assert len(failures) == 1 and needle in failures[0], failures


class TestSmokeEntry:
    def test_refuses_to_run_without_a_tpu(self):
        """No accelerator: non-zero exit, the platform named, NO result."""
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            capture_output=True, text=True, timeout=120, cwd=REPO,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == chip_smoke.EXIT_NO_CHIP
        assert proc.stdout == ""
        assert "'cpu'" in proc.stderr and "nothing was run" in proc.stderr

    def test_dry_run_asked_for_but_not_pinned_is_refused(self, monkeypatch):
        """--cpu-dry-run on a non-CPU backend is the same refusal."""
        import jax

        class FakeTpu:
            platform, device_kind = "tpu", "TPU v5 lite"

        monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()])
        assert chip_smoke.main(["--cpu-dry-run"]) == chip_smoke.EXIT_NO_CHIP


class TestCompileCachePlacement:
    @pytest.fixture()
    def decided(self, monkeypatch):
        """Re-arm the lazy XLA-cache decision on a pretend backend and record
        (never apply) the jax.config updates it makes."""
        import jax

        updates = {}
        monkeypatch.setattr(compilecache, "_xla_cache_decided", False)
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.__setitem__(k, v)
        )
        monkeypatch.delenv("KC_TPU_COMPILE_CACHE", raising=False)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)

        def resolve(backend):
            monkeypatch.setattr(jax, "default_backend", lambda: backend)
            assert compilecache._resolved_backend() == backend
            return updates

        return resolve

    def test_env_placed_cache_is_not_overridden_in_code(self, decided, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        updates = decided("tpu")
        assert "jax_compilation_cache_dir" not in updates
        # on (JAX reads the env var itself), fast compiles persisted too
        assert updates == {"jax_persistent_cache_min_compile_time_secs": 0.0}

    def test_default_is_the_fixed_in_checkout_directory(self, decided):
        updates = decided("tpu")  # unnamed platform: JAX_PLATFORMS is irrelevant
        want = os.path.join(REPO, ".kc_cache", "xla")
        assert updates["jax_compilation_cache_dir"] == want
        assert os.path.isdir(want)
        assert compilecache.cache_dir() == os.path.join(REPO, ".kc_cache")

    def test_kc_tpu_compile_cache_relocates_the_root(self, decided, monkeypatch, tmp_path):
        monkeypatch.setenv("KC_TPU_COMPILE_CACHE", str(tmp_path / "vol"))
        updates = decided("tpu")
        assert updates["jax_compilation_cache_dir"] == str(tmp_path / "vol" / "xla")
        assert os.path.isdir(tmp_path / "vol" / "xla")

    def test_cpu_backend_keeps_the_xla_cache_off(self, decided):
        assert decided("cpu") == {}

    def test_enable_does_not_initialize_a_backend(self, monkeypatch):
        """Operator.start calls enable(): it must stay off the backend."""
        import jax

        def boom():
            raise AssertionError("enable() touched the backend")

        monkeypatch.setattr(jax, "default_backend", boom)
        monkeypatch.setattr(jax, "devices", boom)
        compilecache.enable()


class TestNoHiddenFallback:
    def test_build_error_propagates_from_solve_callable(self, monkeypatch):
        """The seed swallowed this into ``None`` and run_solve quietly ran
        the plain jit instead."""
        import numpy as np

        from karpenter_core_tpu.ops import solve as solve_ops

        def refuse(*a, **kw):
            raise RuntimeError("XLA:TPU refused the program")

        monkeypatch.setattr(compilecache, "_build_and_memo", refuse)
        cls = solve_ops.ClassTensors(*(
            np.zeros((2, 3), np.int32) for _ in solve_ops.ClassTensors._fields
        ))
        with pytest.raises(RuntimeError, match="refused the program"):
            compilecache.solve_callable(cls, (np.zeros(3, np.float32),), 8, (False,))
        # the failed build left no in-flight slot behind: a retry builds again
        with pytest.raises(RuntimeError, match="refused the program"):
            compilecache.solve_callable(cls, (np.zeros(3, np.float32),), 8, (False,))

    def test_run_solve_has_no_second_solve_path(self):
        import inspect

        assert "_solve_jit" not in inspect.getsource(compilecache.run_solve)


class TestBuilders:
    """The input builders the smoke's legs (and two slow tests) share."""

    def test_pod_mix_is_a_pure_function_of_its_seed(self):
        import random

        from karpenter_core_tpu.models.snapshot import classify_pods

        def shapes(pods):
            return [
                (sorted(p.metadata.labels.items()),
                 sorted(p.spec.containers[0].resources.requests.items()),
                 len(p.spec.topology_spread_constraints or ()),
                 p.spec.affinity is not None)
                for p in pods
            ]

        a = chip_smoke.pod_mix(140, random.Random(7))
        b = chip_smoke.pod_mix(140, random.Random(7))
        assert len(a) == len(b) == 140
        assert shapes(a) == shapes(b)
        assert len(classify_pods(a)) == len(classify_pods(b))
        # another seed draws other sizes; no rng is the fixed four-size cycle
        assert shapes(chip_smoke.pod_mix(140, random.Random(8))) != shapes(a)
        assert len(classify_pods(chip_smoke.pod_mix(140))) == 13

    def test_consolidation_cluster_yields_the_asked_nodes_and_pods(self):
        from karpenter_core_tpu.cloudprovider import fake as fake_cp

        env, candidates = chip_smoke.consolidation_cluster(
            6, 3, fake_cp.instance_types(24))
        assert len(env.kube.list_nodes()) == 6
        assert len(candidates) == 6
        assert all(len(c.pods) == 3 for c in candidates)
        costs = [c.disruption_cost for c in candidates]
        assert costs == sorted(costs)

    def test_build_inputs_yields_the_asked_catalog_and_provisioners(self):
        solver, pods = chip_smoke.build_inputs(70, 24, n_provisioners=3)
        assert len(pods) == 70
        assert len(solver.cloud_provider.get_instance_types(None)) == 24
        weights = [p.spec.weight for p in solver.provisioners]
        assert weights == [3, 2, 1]


class TestMakefile:
    def test_presubmit_names_only_targets_and_scripts_that_exist(self):
        """Every prerequisite, down from ``presubmit``, is a target of the
        Makefile, and every script or test file a recipe names exists: a
        gate must have something to gate on."""
        import re

        with open(os.path.join(REPO, "Makefile")) as f:
            text = f.read()
        targets, recipes, current = {}, {}, None
        for line in text.splitlines():
            m = re.match(r"^([A-Za-z][\w-]*):([^#=]*)", line)
            if m:
                current = m.group(1)
                targets[current] = m.group(2).split()
                recipes[current] = []
            elif line.startswith("\t") and current:
                recipes[current].append(line.strip())
        assert "presubmit" in targets
        seen, todo = set(), ["presubmit"]
        while todo:
            name = todo.pop()
            if name in seen:
                continue
            seen.add(name)
            assert name in targets, f"prerequisite {name!r} is no target"
            todo.extend(targets[name])
            for recipe in recipes[name]:
                for word in recipe.split():
                    if re.fullmatch(r"[\w./-]+\.py", word):
                        assert os.path.exists(os.path.join(REPO, word)), (
                            f"{name}: {word} does not exist")
        phony = re.search(r"^\.PHONY:(.*)$", text, re.M).group(1).split()
        assert set(phony) == set(targets), set(phony) ^ set(targets)
