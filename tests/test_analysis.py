"""Tests for the kcanalyze static-analysis framework (docs/ANALYSIS.md).

Each rule gets fixture snippets — bad (must fire, with the exact rule at the
exact file), good (must stay silent), and suppressed (baseline) — plus the
acceptance demonstration: the driver run against a temp tree seeded with one
host-sync, one static-arg mismatch, and one ABBA lock inversion exits
nonzero and names all three, so `make verify` provably fails when any of
these bug classes is introduced.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from karpenter_core_tpu.analysis.core import (
    Baseline,
    BaselineError,
    Finding,
    Project,
    apply_baseline,
    parse_mini_toml,
)
from karpenter_core_tpu.analysis.passes import (
    hygiene,
    instrumented,
    lock_order,
    retrace_budget,
    trace_safety,
    unbounded_block,
)

REPO = Path(__file__).resolve().parent.parent
MANIFEST_PATH = REPO / "karpenter_core_tpu" / "analysis" / "retrace_budget.json"


@pytest.fixture(scope="module")
def repo_project():
    """One Project (and one shared call graph) for every current-tree test —
    rebuilding it per test would re-parse 160+ files five times."""
    return Project(REPO)


@pytest.fixture(scope="module")
def repo_baseline():
    return Baseline.load(
        REPO / "karpenter_core_tpu" / "analysis" / "baseline.toml"
    )


def make_project(tmp_path: Path, files: dict, package: str = "badpkg") -> Project:
    """Write ``files`` (relpath -> source) under a temp package and load it."""
    for rel, src in files.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    pkg_init = tmp_path / package / "__init__.py"
    if not pkg_init.exists():
        pkg_init.parent.mkdir(parents=True, exist_ok=True)
        pkg_init.write_text("")
    return Project(tmp_path, package=package, extra_roots=())


def rules_of(findings) -> set:
    return {f.rule for f in findings}


# -- baseline / mini-toml -----------------------------------------------------


class TestBaseline:
    def test_parse_entries(self):
        entries = parse_mini_toml(
            '# comment\n'
            '[[suppress]]\n'
            'pass = "lock-order"\n'
            'rule = "blocking-under-lock"\n'
            'file = "pkg/mod.py"\n'
            'line = 12\n'
            'reason = "documented false positive"\n'
        )
        assert len(entries) == 1
        assert entries[0]["pass"] == "lock-order"
        assert entries[0]["line"] == 12

    def test_reason_required(self):
        with pytest.raises(BaselineError, match="reason"):
            Baseline(parse_mini_toml('[[suppress]]\nrule = "tabs"\n'))

    def test_inline_comment_after_quoted_value(self):
        entries = parse_mini_toml(
            '[[suppress]]\n'
            'rule = "host-sync"  # documented FP\n'
            'line = 3  # pinned\n'
            'reason = "detail with a # inside"\n'
        )
        assert entries[0]["rule"] == "host-sync"
        assert entries[0]["line"] == 3
        assert entries[0]["reason"] == "detail with a # inside"

    def test_garbage_after_quoted_value_rejected(self):
        with pytest.raises(BaselineError, match="trailing"):
            parse_mini_toml('[[suppress]]\nrule = "x" junk\n')

    def test_match_and_unused(self):
        baseline = Baseline(parse_mini_toml(
            '[[suppress]]\nrule = "tabs"\nfile = "a.py"\nreason = "r"\n'
            '[[suppress]]\nrule = "long-line"\nfile = "b.py"\nreason = "r"\n'
        ))
        hit = Finding("a.py", 3, "tabs", "use spaces", "hygiene")
        miss = Finding("a.py", 3, "trailing-ws", "x", "hygiene")
        kept, suppressed = apply_baseline([hit, miss], baseline)
        assert [f.rule for f in kept] == ["trailing-ws"]
        assert suppressed[0][1] == "r"
        assert [e["rule"] for e in baseline.unused()] == ["long-line"]

    def test_repo_baseline_parses_with_reasons(self):
        path = REPO / "karpenter_core_tpu" / "analysis" / "baseline.toml"
        baseline = Baseline.load(path)
        for entry in baseline.entries:
            assert str(entry.get("reason", "")).strip()


# -- hygiene ------------------------------------------------------------------


class TestHygiene:
    def test_ported_rules_fire(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import os
                import sys

                def f(x=[]):
                    try:
                        return sys.argv
                    except:
                        return f"no field"
            """,
        })
        found = hygiene.run(project)
        assert {"unused-import", "bare-except", "mutable-default",
                "f-string-no-field"} <= rules_of(found)
        # the unused import is os, not sys (used in body)
        unused = [f for f in found if f.rule == "unused-import"]
        assert len(unused) == 1 and "os" in unused[0].detail

    def test_formatting_rules(self, tmp_path):
        src = "x = 1 \ny\t= 2\nz = '" + "a" * 130 + "'\n"
        path = tmp_path / "badpkg" / "fmt.py"
        path.parent.mkdir(parents=True)
        path.write_text(src)
        (tmp_path / "badpkg" / "__init__.py").write_text("")
        found = hygiene.run(Project(tmp_path, package="badpkg", extra_roots=()))
        assert {"trailing-ws", "tabs", "long-line"} <= rules_of(found)

    def test_assert_in_package(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/mod.py": "def f(x):\n    assert x > 0\n    return x\n",
            # the test-harness subtree is exempt
            "badpkg/testing/helper.py": "def g(x):\n    assert x\n    return x\n",
        })
        found = [f for f in hygiene.run(project) if f.rule == "assert-in-package"]
        assert len(found) == 1
        assert found[0].path == "badpkg/mod.py"

    def test_wallclock_in_clocked_dirs_only(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/state/cache.py": """\
                import time

                def now():
                    return time.time()
            """,
            # tracing-style modules outside the reconcile world may read wall
            "badpkg/tracing/span.py": """\
                import time

                def wall():
                    return time.time()
            """,
        })
        found = [f for f in hygiene.run(project) if f.rule == "wallclock"]
        assert len(found) == 1
        assert found[0].path == "badpkg/state/cache.py"

    def test_wallclock_covers_soak(self, tmp_path):
        """soak/ is clock-disciplined: probes and traces live on the
        FakeClock timeline, and a stray wall read would silently break
        verdict seed-replay (ISSUE 6 soak_hygiene satellite)."""
        project = make_project(tmp_path, {
            "badpkg/soak/probe.py": """\
                import time

                def sample():
                    return time.time()
            """,
        })
        found = [f for f in hygiene.run(project) if f.rule == "wallclock"]
        assert len(found) == 1
        assert found[0].path == "badpkg/soak/probe.py"

    def test_clean_module_silent(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ok.py": """\
                import sys

                def f(x=None):
                    if x is None:
                        x = []
                    return (sys.argv, x)
            """,
        })
        assert hygiene.run(project) == []

    def test_current_tree_clean(self, repo_project):
        """The repo itself stays hygiene-clean after the checked-in baseline
        (the make-verify contract) — the only raw findings allowed are the
        documented per-pod-loop suppressions."""
        from karpenter_core_tpu.analysis.core import Baseline, apply_baseline

        baseline = Baseline.load(
            Path(__file__).resolve().parents[1]
            / "karpenter_core_tpu" / "analysis" / "baseline.toml"
        )
        found = hygiene.run(repo_project)
        assert {f.rule for f in found} <= {"per-pod-loop"}, "\n".join(
            f.render() for f in found
        )
        kept, _suppressed = apply_baseline(found, baseline)
        assert kept == [], "\n".join(f.render() for f in kept)

    def test_per_pod_loop_flags_encode_hot_path_only(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/models/columnar.py": """\
                def ingest(pods):
                    out = []
                    for pod in pods:
                        out.append(pod)
                    return out
            """,
            # pod loops OUTSIDE the encode hot path are not this rule's
            # business (the controllers legitimately iterate batches)
            "badpkg/controllers/thing.py": """\
                def count(pods):
                    return len([p for p in pods])
            """,
        })
        found = [f for f in hygiene.run(project) if f.rule == "per-pod-loop"]
        assert len(found) == 1
        assert found[0].path == "badpkg/models/columnar.py"
        assert found[0].symbol == "ingest"

    def test_per_pod_loop_comprehensions_and_attributes(self, tmp_path):
        """Comprehensions count, and so do pod-collection ATTRIBUTES
        (slot.pods.values()) — the loop shape doesn't matter, the O(pods)
        body does; the symbol carries the method qualname so baseline
        entries survive line churn."""
        project = make_project(tmp_path, {
            "badpkg/models/snapshot.py": """\
                class Encoder:
                    def walk(self, slot):
                        return [p.uid for p in slot.pods.values()]
            """,
        })
        found = [f for f in hygiene.run(project) if f.rule == "per-pod-loop"]
        assert len(found) == 1
        assert found[0].symbol == "Encoder.walk"

    def test_per_pod_loop_clean_class_loops_silent(self, tmp_path):
        """Loops over CLASSES (the O(distinct shapes) solve-path unit) never
        trip the rule — only pod collections do."""
        project = make_project(tmp_path, {
            "badpkg/models/snapshot.py": """\
                def encode(classes):
                    return [c.requirements for c in classes]
            """,
        })
        assert [f for f in hygiene.run(project) if f.rule == "per-pod-loop"] == []


# -- trace safety -------------------------------------------------------------

_TRACE_BAD = """\
    import jax
    import jax.numpy as jnp
    import numpy as np

    def helper(x):
        return x.item()

    @jax.jit
    def kernel(x):
        total = jnp.sum(x)
        if jnp.any(x > 0):
            pass
        print("tracing")
        y = np.asarray(total)
        return float(total) + helper(x) + y
"""

_TRACE_GOOD = """\
    import jax
    import jax.numpy as jnp
    import numpy as np

    def host_decode(out):
        # host-side decode: syncs are fine, this is not jit-reachable
        return float(np.asarray(out).sum())

    @jax.jit
    def kernel(x, v=3):
        vocab = jnp.asarray(np.arange(4))  # static host data: constant-folds
        return jnp.where(x > 0, x, 0.0) + vocab[v]
"""


class TestTraceSafety:
    def test_bad_kernel_fires_every_rule(self, tmp_path):
        project = make_project(tmp_path, {"badpkg/ops.py": _TRACE_BAD})
        found = trace_safety.run(project)
        assert {"host-sync", "trace-branch", "host-effect"} <= rules_of(found)
        # reachability: helper's .item() is found through the call edge
        helper_hits = [f for f in found if f.symbol == "helper"]
        assert helper_hits and helper_hits[0].rule == "host-sync"
        # exact anchoring: float(total) on the tainted sum
        casts = [f for f in found if "float()" in f.detail]
        assert casts and casts[0].path == "badpkg/ops.py"

    def test_good_kernel_silent(self, tmp_path):
        project = make_project(tmp_path, {"badpkg/ops.py": _TRACE_GOOD})
        assert trace_safety.run(project) == []

    def test_try_in_trace(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ops.py": """\
                import jax
                import jax.numpy as jnp

                @jax.jit
                def kernel(x):
                    try:
                        return jnp.sum(x)
                    except ValueError:
                        return x
            """,
        })
        assert "try-in-trace" in rules_of(trace_safety.run(project))

    def test_lambda_and_scan_step_reachable(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ops.py": """\
                import jax
                import jax.numpy as jnp

                def step(carry, x):
                    jnp.asarray(x).tolist()
                    return carry, x

                def core(xs):
                    return jax.lax.scan(step, 0, xs)

                solve = jax.jit(lambda xs: core(xs))
            """,
        })
        found = trace_safety.run(project)
        assert any(f.rule == "host-sync" and f.symbol == "step" for f in found)

    def test_current_tree_clean(self, repo_project):
        found = trace_safety.run(repo_project)
        assert found == [], "\n".join(f.render() for f in found)

    def test_shard_map_body_reachable(self, tmp_path):
        """A host sync inside a shard_map body is a static-gate failure even
        when the body never appears at a jax.jit site — sharded bodies seed
        the same reachability as jitted ones (ISSUE 10)."""
        project = make_project(tmp_path, {
            "badpkg/ops.py": """\
                import jax
                import jax.numpy as jnp
                from jax.experimental.shard_map import shard_map

                def body(x):
                    v = jnp.sum(x)
                    return float(v)  # host sync inside the sharded body

                def dispatch(mesh, specs, x):
                    return shard_map(
                        body, mesh=mesh, in_specs=specs, out_specs=specs
                    )(x)
            """,
        })
        found = trace_safety.run(project)
        assert "host-sync" in rules_of(found)
        assert any(f.symbol == "body" or "body" in f.detail for f in found)

    def test_shard_map_decorator_spelling_reachable(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ops.py": """\
                import functools

                import jax
                import jax.numpy as jnp
                from jax.experimental.shard_map import shard_map

                MESH = None

                @functools.partial(shard_map, mesh=MESH, in_specs=(), out_specs=())
                def body(x):
                    if jnp.sum(x) > 0:  # trace-branch inside sharded body
                        return x
                    return x + 1
            """,
        })
        assert "trace-branch" in rules_of(trace_safety.run(project))


# -- retrace budget (static) --------------------------------------------------

_CC_FIXTURE = """\
    import threading

    _lock = threading.Lock()
    _memo = {}

    def solve_callable(cls, n_slots, key_has_bounds, n_passes=1):
        key = (n_slots, tuple(key_has_bounds), n_passes, _leaf_sig(cls))
        return _memo.get(key)

    def _leaf_sig(tree):
        return ()
"""


class TestRetraceBudgetStatic:
    def test_missing_static_flagged(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/utils/compilecache.py": _CC_FIXTURE,
            "badpkg/ops/solve.py": """\
                import functools

                import jax

                def solve_core(cls, n_slots, key_has_bounds, n_passes=1):
                    return cls

                _solve_jit = functools.partial(
                    jax.jit, static_argnames=("n_slots", "key_has_bounds")
                )(solve_core)
            """,
        })
        found = retrace_budget.run(project)
        missing = [f for f in found if f.rule == "static-args"]
        assert len(missing) == 1 and "'n_passes'" in missing[0].detail

    def test_consistent_site_silent(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/utils/compilecache.py": _CC_FIXTURE,
            "badpkg/ops/solve.py": """\
                import functools

                import jax

                def solve_core(cls, n_slots, key_has_bounds, n_passes=1):
                    return cls

                _solve_jit = functools.partial(
                    jax.jit,
                    static_argnames=("n_slots", "key_has_bounds", "n_passes"),
                )(solve_core)
            """,
        })
        assert retrace_budget.run(project) == []

    def test_cache_key_drift_flagged(self, tmp_path):
        # n_passes is a solve_callable param but NOT in its key tuple:
        # declaring it static at a solve_core site must flag the drift
        project = make_project(tmp_path, {
            "badpkg/utils/compilecache.py": """\
                _memo = {}

                def solve_callable(cls, n_slots, key_has_bounds, n_passes=1):
                    key = (n_slots, tuple(key_has_bounds), _leaf_sig(cls))
                    return _memo.get(key)

                def _leaf_sig(tree):
                    return ()
            """,
            "badpkg/ops/solve.py": """\
                import functools

                import jax

                def solve_core(cls, n_slots, key_has_bounds, n_passes=1):
                    return cls

                _solve_jit = functools.partial(
                    jax.jit,
                    static_argnames=("n_slots", "key_has_bounds", "n_passes"),
                )(solve_core)
            """,
        })
        found = retrace_budget.run(project)
        drift = [f for f in found if f.rule == "cache-key-drift"]
        assert drift and "'n_passes'" in drift[0].detail

    def test_unknown_and_unhashable_static(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ops/solve.py": """\
                import jax

                def core(x, cfg=None):
                    return x

                wrapped = jax.jit(core, static_argnames=("cfg", "typo"))

                def caller(x):
                    return wrapped(x, cfg={"a": 1})
            """,
        })
        found = retrace_budget.run(project)
        assert "unknown-static" in rules_of(found)
        unhashable = [f for f in found if f.rule == "unhashable-static"]
        assert unhashable and "'cfg'" in unhashable[0].detail

    def test_uncached_jit_flagged_and_lru_exempt(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ops/solve.py": """\
                import functools

                import jax

                def hot(x):
                    return jax.jit(lambda v: v + 1)(x)

                @functools.lru_cache(maxsize=8)
                def builder(n):
                    return jax.jit(lambda v: v + n)
            """,
        })
        found = [f for f in retrace_budget.run(project)
                 if f.rule == "uncached-jit"]
        assert len(found) == 1 and found[0].symbol == "hot"

    def test_non_literal_static_flagged(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ops/solve.py": """\
                import jax

                NAMES = ("n",)

                def core(x, n=1):
                    return x

                wrapped = jax.jit(core, static_argnames=NAMES)
            """,
        })
        assert "non-literal-static" in rules_of(retrace_budget.run(project))

    def test_uncached_shard_map_flagged_and_lru_exempt(self, tmp_path):
        """shard_map constructed per call retraces exactly like per-call
        jax.jit; a memoized builder whose mesh derives from its parameters
        is the sanctioned shape (parallel.mesh pattern)."""
        project = make_project(tmp_path, {
            "badpkg/ops/solve.py": """\
                import functools

                import jax
                from jax.experimental.shard_map import shard_map

                def body(x):
                    return x

                def hot(mesh, specs, x):
                    return shard_map(
                        body, mesh=mesh, in_specs=specs, out_specs=specs
                    )(x)

                @functools.lru_cache(maxsize=8)
                def builder(mesh_axes, specs):
                    mesh = mesh_for(mesh_axes)
                    return jax.jit(shard_map(
                        body, mesh=mesh, in_specs=specs, out_specs=specs
                    ))

                def mesh_for(axes):
                    return axes
            """,
        })
        found = [f for f in retrace_budget.run(project)
                 if f.rule == "uncached-jit" and "shard_map" in f.detail]
        assert len(found) == 1 and found[0].symbol == "hot"

    def test_unkeyed_mesh_static_flagged(self, tmp_path):
        """A memoized builder whose shard_map captures a module-global mesh
        shares ONE cached executable across topologies — the sharded twin of
        cache-key-drift."""
        project = make_project(tmp_path, {
            "badpkg/ops/solve.py": """\
                import functools

                import jax
                from jax.experimental.shard_map import shard_map

                MESH = object()

                def body(x):
                    return x

                @functools.lru_cache(maxsize=8)
                def builder(specs):
                    return jax.jit(shard_map(
                        body, mesh=MESH, in_specs=specs, out_specs=specs
                    ))
            """,
        })
        found = [f for f in retrace_budget.run(project)
                 if f.rule == "unkeyed-mesh-static"]
        assert len(found) == 1 and found[0].symbol == "builder"

    def test_mesh_derived_from_params_silent(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/ops/solve.py": """\
                import functools

                import jax
                from jax.experimental.shard_map import shard_map

                def body(x):
                    return x

                def mesh_for(axes):
                    return axes

                @functools.lru_cache(maxsize=8)
                def builder(mesh_axes, specs):
                    mesh = mesh_for(mesh_axes)
                    return jax.jit(shard_map(
                        body, mesh=mesh, in_specs=specs, out_specs=specs
                    ))
            """,
        })
        assert "unkeyed-mesh-static" not in rules_of(retrace_budget.run(project))

    def test_donated_read_flagged(self, tmp_path):
        """Reading a buffer after the dispatch that donated it — both the
        ``warm_carry=`` kwarg spelling and the ``*_donated`` helper
        convention (first positional argument)."""
        project = make_project(tmp_path, {
            "badpkg/solver/loop.py": """\
                def tick(solver, prep, carry, counts, plan):
                    out = solver.run_prepared(
                        prep, count=counts, warm_carry=carry, repair_plan=plan
                    )
                    return out, carry  # read after donation

                def free(repair_free_donated, carry, f):
                    freed = repair_free_donated(carry, f)
                    stale = carry.state  # read after donation
                    return freed, stale
            """,
        })
        found = [f for f in retrace_budget.run(project)
                 if f.rule == "donated-read"]
        assert len(found) == 2
        assert "'carry'" in found[0].detail and "'carry'" in found[1].detail

    def test_donated_read_rebind_and_branches_silent(self, tmp_path):
        """The intended idioms stay silent: rebinding the name to the
        dispatch's output clears the taint, and a donation inside one
        if-arm does not taint the sibling arm (it taints the code AFTER
        the branch)."""
        project = make_project(tmp_path, {
            "badpkg/solver/loop.py": """\
                def rebind(repair_free_donated, carry, f):
                    carry = repair_free_donated(carry, f)
                    return carry.state  # the OUTPUT: fine

                def branches(solver, prep, counts, carry, win_carry, plan):
                    if win_carry is not None:
                        keep = carry
                        out = solver.run_prepared(
                            prep, count=counts, warm_carry=win_carry,
                            repair_plan=plan,
                        )
                    else:
                        out = solver.run_prepared(
                            prep, count=counts, warm_carry=carry,
                            repair_plan=plan,
                        )
                    return out, keep  # keep bound BEFORE the donation
            """,
        })
        assert "donated-read" not in rules_of(retrace_budget.run(project))

    def test_donated_read_after_merged_branches_flagged(self, tmp_path):
        """Code AFTER an if/else inherits either arm's donations: a read of
        the else-arm's donated carry past the join is flagged."""
        project = make_project(tmp_path, {
            "badpkg/solver/loop.py": """\
                def tick(solver, prep, counts, carry, windowed, plan):
                    if windowed:
                        out = solver.run_prepared(prep, count=counts)
                    else:
                        out = solver.run_prepared(
                            prep, count=counts, warm_carry=carry,
                            repair_plan=plan,
                        )
                    return out, carry  # may read the donated buffer
            """,
        })
        found = [f for f in retrace_budget.run(project)
                 if f.rule == "donated-read"]
        assert len(found) == 1 and "'carry'" in found[0].detail

    def test_current_tree_only_baselined_findings(self, repo_project,
                                                  repo_baseline):
        kept, _ = apply_baseline(retrace_budget.run(repo_project), repo_baseline)
        assert kept == [], "\n".join(f.render() for f in kept)


# -- lock order ---------------------------------------------------------------

_ABBA = """\
    import threading

    lock_a = threading.Lock()
    lock_b = threading.Lock()

    def forward():
        with lock_a:
            with lock_b:
                return 1

    def backward():
        with lock_b:
            with lock_a:
                return 2
"""


class TestLockOrder:
    def test_abba_inversion(self, tmp_path):
        project = make_project(tmp_path, {"badpkg/locks.py": _ABBA})
        found = lock_order.run(project)
        inversions = [f for f in found if f.rule == "lock-order"]
        assert inversions, found
        assert "lock_a" in inversions[0].detail and "lock_b" in inversions[0].detail

    def test_abba_through_call_chain(self, tmp_path):
        # the synthetic deadlock graph: f holds A and calls g (which takes
        # B); h holds B and calls k (which takes A) — the inversion is only
        # visible interprocedurally
        project = make_project(tmp_path, {
            "badpkg/locks.py": """\
                import threading

                lock_a = threading.Lock()
                lock_b = threading.Lock()

                def takes_b():
                    with lock_b:
                        return 1

                def takes_a():
                    with lock_a:
                        return 2

                def f():
                    with lock_a:
                        return takes_b()

                def h():
                    with lock_b:
                        return takes_a()
            """,
        })
        found = lock_order.run(project)
        assert any(f.rule == "lock-order" for f in found), found

    def test_consistent_order_silent(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/locks.py": """\
                import threading

                lock_a = threading.Lock()
                lock_b = threading.Lock()

                def one():
                    with lock_a:
                        with lock_b:
                            return 1

                def two():
                    with lock_a:
                        with lock_b:
                            return 2
            """,
        })
        assert [f for f in lock_order.run(project) if f.rule == "lock-order"] == []

    def test_blocking_under_lock(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import subprocess
                import threading
                import time

                _lock = threading.Lock()

                def build():
                    with _lock:
                        subprocess.run(["make"])

                def nap_free():
                    time.sleep(0.1)  # no lock held: fine
                    with _lock:
                        return 1
            """,
        })
        found = [f for f in lock_order.run(project)
                 if f.rule == "blocking-under-lock"]
        assert len(found) == 1 and found[0].symbol == "build"

    def test_blocking_through_callee(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import threading
                import time

                _lock = threading.Lock()

                def slow():
                    time.sleep(1.0)

                def holder():
                    with _lock:
                        slow()
            """,
        })
        found = [f for f in lock_order.run(project)
                 if f.rule == "blocking-under-lock"]
        assert found and found[0].symbol == "holder"

    def test_blocking_method_through_callee(self, tmp_path):
        # factoring a .result()/.wait() into a helper must not defeat the gate
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import threading

                _lock = threading.Lock()

                def helper(fut):
                    return fut.result()

                def holder(fut):
                    with _lock:
                        return helper(fut)
            """,
        })
        found = [f for f in lock_order.run(project)
                 if f.rule == "blocking-under-lock"]
        assert found and found[0].symbol == "holder", found

    def test_thread_join_under_lock_flagged(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import threading

                _lock = threading.Lock()

                def stop(worker, parts):
                    with _lock:
                        label = ", ".join(parts)  # str.join: not a stall
                        worker.join(timeout=5)
                        return label
            """,
        })
        found = [f for f in lock_order.run(project)
                 if f.rule == "blocking-under-lock"]
        assert len(found) == 1 and ".join()" in found[0].detail, found

    def test_defining_sleeping_closure_not_blocking(self, tmp_path):
        # DEFINING a closure that sleeps is not sleeping: registering delayed
        # callbacks under a lock must stay clean
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import threading
                import time

                _lock = threading.Lock()

                def makes_closure():
                    def callback():
                        time.sleep(5.0)
                    return callback

                def holder():
                    with _lock:
                        return makes_closure()
            """,
        })
        found = [f for f in lock_order.run(project)
                 if f.rule == "blocking-under-lock"]
        assert found == [], found

    def test_self_deadlock_plain_lock_only(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import threading

                class Plain:
                    def __init__(self):
                        self._mu = threading.Lock()

                    def outer(self):
                        with self._mu:
                            return self.inner()

                    def inner(self):
                        with self._mu:
                            return 1

                class Reentrant:
                    def __init__(self):
                        self._mu = threading.RLock()

                    def outer(self):
                        with self._mu:
                            return self.inner()

                    def inner(self):
                        with self._mu:
                            return 1
            """,
        })
        found = [f for f in lock_order.run(project) if f.rule == "self-deadlock"]
        assert len(found) == 1 and "Plain" in found[0].symbol

    def test_lock_no_with(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/mod.py": """\
                import threading

                _lock = threading.Lock()

                def f():
                    _lock.acquire()
                    try:
                        return 1
                    finally:
                        _lock.release()
            """,
        })
        found = [f for f in lock_order.run(project) if f.rule == "lock-no-with"]
        assert len(found) == 2  # the acquire and the release

    def test_current_tree_clean(self, repo_project, repo_baseline):
        kept, _ = apply_baseline(lock_order.run(repo_project), repo_baseline)
        assert kept == [], "\n".join(f.render() for f in kept)


# -- instrumented -------------------------------------------------------------


class TestInstrumented:
    def test_uninstrumented_controller_fires(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/controllers/thing.py": """\
                class ThingController:
                    name = "thing"

                    def reconcile(self, obj):
                        return None
            """,
        })
        found = instrumented.run(project)
        assert rules_of(found) == {"uninstrumented-reconcile"}

    def test_span_and_traced_accepted(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/controllers/thing.py": """\
                from badpkg import tracing

                class WithSpan:
                    name = "a"

                    def reconcile(self, obj):
                        with tracing.span("a.reconcile"):
                            return None

                class WithDecorator:
                    name = "b"

                    @tracing.traced("b.reconcile")
                    def reconcile(self, obj):
                        return None
            """,
        })
        assert instrumented.run(project) == []


# -- the driver (acceptance demonstration) ------------------------------------

_SEEDED_HOST_SYNC = """\
    import jax
    import jax.numpy as jnp

    @jax.jit
    def kernel(x):
        return float(jnp.sum(x))
"""

_SEEDED_STATIC_MISMATCH = """\
    import functools

    import jax

    def solve_core(cls, n_slots, key_has_bounds, n_passes=1):
        return cls

    _solve_jit = functools.partial(
        jax.jit, static_argnames=("n_slots",)
    )(solve_core)
"""


def run_driver(root: Path, *extra: str):
    return subprocess.run(
        [sys.executable, str(REPO / "tools" / "kcanalyze.py"),
         "--root", str(root), "--package", "badpkg", *extra],
        capture_output=True, text=True, timeout=120,
    )


class TestDriver:
    def test_seeded_tree_fails_with_all_three(self, tmp_path):
        """One host-sync + one static-arg mismatch + one lock inversion in a
        temp tree: the driver (hence `make verify`) exits nonzero and names
        each — introducing any of the three bug classes breaks the build."""
        make_project(tmp_path, {
            "badpkg/ops/kernel.py": _SEEDED_HOST_SYNC,
            "badpkg/ops/solve.py": _SEEDED_STATIC_MISMATCH,
            "badpkg/utils/compilecache.py": _CC_FIXTURE,
            "badpkg/locks.py": _ABBA,
        })
        proc = run_driver(tmp_path)
        assert proc.returncode == 1, proc.stdout + proc.stderr
        assert "trace-safety/host-sync" in proc.stdout
        assert "retrace-budget/static-args" in proc.stdout
        assert "lock-order/lock-order" in proc.stdout
        assert "FAIL" in proc.stdout

    def test_clean_tree_passes_with_timing(self, tmp_path):
        make_project(tmp_path, {
            "badpkg/ok.py": "def f(x):\n    return x\n",
        })
        proc = run_driver(tmp_path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout
        # the satellite contract: a timing line for the suite
        assert "in " in proc.stdout and "s" in proc.stdout
        assert any("pass trace-safety" in ln for ln in proc.stdout.splitlines())

    def test_baseline_suppresses_documented_finding(self, tmp_path):
        make_project(tmp_path, {
            "badpkg/locks.py": _ABBA,
            "badpkg/analysis/baseline.toml": """\
                [[suppress]]
                pass = "lock-order"
                rule = "lock-order"
                file = "badpkg/locks.py"
                reason = "fixture: inversion is intentional in this test tree"
            """,
        })
        proc = run_driver(tmp_path)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "1 suppressed" in proc.stdout

    def test_baseline_without_reason_hard_fails(self, tmp_path):
        make_project(tmp_path, {
            "badpkg/ok.py": "def f(x):\n    return x\n",
            "badpkg/analysis/baseline.toml": (
                '[[suppress]]\nrule = "tabs"\n'
            ),
        })
        proc = run_driver(tmp_path)
        assert proc.returncode == 1
        assert "bad baseline" in proc.stderr

    def test_syntax_error_is_a_finding(self, tmp_path):
        make_project(tmp_path, {
            "badpkg/broken.py": "def f(:\n",
        })
        proc = run_driver(tmp_path)
        assert proc.returncode == 1
        assert "syntax-error" in proc.stdout

    @pytest.mark.slow
    def test_repo_tree_passes(self):
        """`python tools/kcanalyze.py` exits 0 on the final tree (the same
        invocation `make verify` gates on; slow tier because it re-parses
        the whole repo in a subprocess — the in-process current-tree tests
        above cover each pass inside the tier-1 budget)."""
        proc = subprocess.run(
            [sys.executable, str(REPO / "tools" / "kcanalyze.py")],
            capture_output=True, text=True, timeout=300, cwd=str(REPO),
        )
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout


# -- retrace budget (runtime manifest) ----------------------------------------


class TestRetraceManifest:
    def test_manifest_shape(self):
        manifest = json.loads(MANIFEST_PATH.read_text())
        assert int(manifest["default_budget"]) > 0
        assert isinstance(manifest.get("tests", {}), dict)
        for nodeid, budget in manifest.get("tests", {}).items():
            assert nodeid.startswith("tests/"), nodeid
            assert int(budget) > 0

    def test_fixture_fails_over_budget(self, monkeypatch):
        """Drive the conftest fixture by hand: a test that 'compiles' more
        than its budget must fail with the retrace message."""
        import conftest

        monkeypatch.setitem(conftest._MANIFEST, "tests", {})
        monkeypatch.setitem(conftest._MANIFEST, "default_budget", 2)
        monkeypatch.delenv("KC_RETRACE_BUDGET", raising=False)
        monkeypatch.delenv("KC_RETRACE_RECORD", raising=False)

        class _Node:
            nodeid = "tests/test_fake.py::test_over"

        class _Request:
            node = _Node()

        gen = conftest._retrace_budget.__wrapped__(_Request())
        next(gen)
        conftest._compile_count["n"] += 3  # over the budget of 2
        with pytest.raises(pytest.fail.Exception, match="retrace budget"):
            next(gen)

    def test_fixture_passes_within_budget(self, monkeypatch):
        import conftest

        monkeypatch.setitem(conftest._MANIFEST, "tests", {})
        monkeypatch.setitem(conftest._MANIFEST, "default_budget", 10)
        monkeypatch.delenv("KC_RETRACE_RECORD", raising=False)

        class _Node:
            nodeid = "tests/test_fake.py::test_ok"

        class _Request:
            node = _Node()

        gen = conftest._retrace_budget.__wrapped__(_Request())
        next(gen)
        conftest._compile_count["n"] += 1
        with pytest.raises(StopIteration):
            next(gen)

    @pytest.mark.slow
    def test_manifest_matches_reality_on_representative_tests(self, tmp_path):
        """Run two representative tier-1 tests in a fresh process with
        recording on: the observed compile counts must fit the manifest
        (and the budgeted run itself must pass)."""
        record = tmp_path / "counts.jsonl"
        targets = [
            "tests/test_masks.py::test_intersects_parity",
            "tests/test_masks.py::test_add_then_check_parity",
        ]
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", KC_RETRACE_RECORD=str(record))
        proc = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             *targets],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=str(REPO),
        )
        assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
        manifest = json.loads(MANIFEST_PATH.read_text())
        default = int(manifest["default_budget"])
        per_test = manifest.get("tests", {})
        rows = [json.loads(ln) for ln in record.read_text().splitlines()]
        assert rows, "recording produced no rows"
        for row in rows:
            budget = int(per_test.get(row["test"], default))
            assert row["compiles"] <= budget, (
                f"{row['test']}: {row['compiles']} compiles > budget {budget}"
            )


class TestChaosHygienePass:
    """chaos-hygiene: point registration uniqueness + the determinism gate."""

    def _run(self, tmp_path, files):
        from karpenter_core_tpu.analysis.passes import chaos_hygiene

        return chaos_hygiene.run(make_project(tmp_path, files))

    def test_duplicate_registration_fires_at_both_sites(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/a.py": textwrap.dedent("""
                from karpenter_core_tpu import chaos
                P = chaos.point("cloud.create")
            """),
            "badpkg/b.py": textwrap.dedent("""
                from karpenter_core_tpu import chaos
                Q = chaos.point("cloud.create")
            """),
        })
        dups = [f for f in found if f.rule == "point-duplicate"]
        assert len(dups) == 2
        assert {f.path for f in dups} == {"badpkg/a.py", "badpkg/b.py"}

    def test_nonliteral_point_name_fires(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/a.py": textwrap.dedent("""
                from karpenter_core_tpu import chaos
                NAME = "computed"
                P = chaos.point(NAME)
            """),
        })
        assert rules_of(found) == {"point-nonliteral"}

    def test_random_import_in_production_module_fires(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/logic.py": "import random\nx = 1\n",
            "badpkg/sec.py": "import secrets\ny = 2\n",
        })
        rules = [(f.path, f.rule) for f in found]
        assert ("badpkg/logic.py", "nondeterminism") in rules
        assert ("badpkg/sec.py", "nondeterminism") in rules

    def test_chaos_subtree_is_exempt(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/chaos/__init__.py": "",
            "badpkg/chaos/scenario.py": "import random\nx = 1\n",
        })
        assert found == []

    def test_unique_registrations_and_rng_are_clean(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/a.py": textwrap.dedent("""
                from karpenter_core_tpu import chaos
                P = chaos.point("kubeapi.put")
                Q = chaos.point("cloud.create")
            """),
        })
        assert found == []

    def test_current_tree_clean(self, repo_project):
        from karpenter_core_tpu.analysis.passes import chaos_hygiene

        assert chaos_hygiene.run(repo_project) == []


class TestUnboundedBlock:
    """The unbounded-block pass (ISSUE 15): blocking device calls in the
    device-path subtrees must route through utils/watchdog — raw spellings
    are findings, monitored spellings are clean."""

    def _run(self, tmp_path, files):
        return unbounded_block.run(make_project(tmp_path, files))

    def test_raw_device_get_in_ops_is_flagged(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/ops/kernel.py": textwrap.dedent("""
                import jax

                def fetch(outputs):
                    return jax.device_get(outputs)

                def sync(outputs):
                    jax.block_until_ready(outputs)

                def retire(handle):
                    return handle.result()
            """),
        })
        rules = sorted((f.path, f.symbol) for f in found)
        assert rules == [
            ("badpkg/ops/kernel.py", "fetch"),
            ("badpkg/ops/kernel.py", "retire"),
            ("badpkg/ops/kernel.py", "sync"),
        ]

    def test_monitored_spellings_are_clean(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/ops/kernel.py": textwrap.dedent("""
                import jax
                from karpenter_core_tpu.utils import watchdog

                def fetch(outputs):
                    # the callable-argument shape: no raw Call node at all
                    return watchdog.run("site", jax.device_get, outputs)

                def fetch_lambda(outputs):
                    # lexically inside the monitored call expression
                    return watchdog.run("site", lambda: jax.device_get(outputs))

                def fetch_instance(outputs):
                    from karpenter_core_tpu.utils.watchdog import MonitoredDispatch
                    return MonitoredDispatch("site").run(
                        lambda: jax.device_get(outputs)
                    )
            """),
        })
        assert found == []

    def test_unrelated_run_receiver_is_not_a_monitored_scope(self, tmp_path):
        """A generic ``something_dispatch.run(...)`` must NOT exempt the
        blocking calls nested inside it — only watchdog/MonitoredDispatch
        receivers are monitored scopes."""
        found = self._run(tmp_path, {
            "badpkg/ops/kernel.py": textwrap.dedent("""
                import jax

                def sneak(batch_dispatch, outputs):
                    return batch_dispatch.run(jax.device_get(outputs))
            """),
        })
        assert [f.symbol for f in found] == ["sneak"]

    def test_unwatched_subtrees_and_watchdog_module_exempt(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/controllers/loop.py": textwrap.dedent("""
                import jax

                def fetch(outputs):
                    return jax.device_get(outputs)
            """),
            "badpkg/utils/watchdog.py": textwrap.dedent("""
                def run(site, fn, *args):
                    return fn(*args)

                def wait(job):
                    return job.result()
            """),
        })
        assert found == []

    def test_current_tree_only_baselined_sites(self, repo_project):
        from karpenter_core_tpu.analysis.core import Baseline, apply_baseline

        baseline = Baseline.load(
            REPO / "karpenter_core_tpu" / "analysis" / "baseline.toml"
        )
        kept, _suppressed = apply_baseline(
            unbounded_block.run(repo_project), baseline
        )
        assert kept == [], [f.render() for f in kept]


# -- shared-state (lockset inference) -----------------------------------------


class TestSharedState:
    """shared-state: per-method lockset inference over lock-owning classes in
    the concurrency-bearing subtrees.  Fragments live under badpkg/service/
    because the pass scopes itself to the subtrees that actually run
    threaded (service/, fleet/, state/, solver/incremental.py,
    utils/compilecache.py)."""

    def _run(self, tmp_path, files):
        from karpenter_core_tpu.analysis.passes import shared_state

        return shared_state.run(make_project(tmp_path, files))

    def test_unguarded_field_fires_at_the_lock_free_site(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/service/plane.py": """
                import threading

                class Plane:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.count = 0

                    def bump(self):
                        with self._lock:
                            self.count += 1

                    def reset(self):
                        self.count = 0
            """,
        })
        assert rules_of(found) == {"unguarded-field"}
        (f,) = found
        assert f.symbol == "Plane.reset"
        assert "count" in f.detail

    def test_two_lock_field_is_mixed_guard(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/service/plane.py": """
                import threading

                class Plane:
                    def __init__(self):
                        self._a = threading.Lock()
                        self._b = threading.Lock()
                        self.val = 0

                    def left(self):
                        with self._a:
                            self.val += 1

                    def right(self):
                        with self._b:
                            self.val += 1
            """,
        })
        assert rules_of(found) == {"mixed-guard"}
        assert "no single lock" in found[0].detail

    def test_init_only_field_is_silent(self, tmp_path):
        """Escape analysis: a field written only during __init__ (before the
        object is reachable by any other thread) and read lock-free after
        publication is the immutable-config idiom, not a race."""
        found = self._run(tmp_path, {
            "badpkg/service/plane.py": """
                import threading

                class Plane:
                    def __init__(self, cfg):
                        self._lock = threading.Lock()
                        self.cfg = dict(cfg)
                        self.hits = 0

                    def lookup(self, key):
                        return self.cfg[key]

                    def record(self):
                        with self._lock:
                            self.hits += 1
            """,
        })
        assert found == []

    def test_thread_target_makes_private_method_reachable(self, tmp_path):
        """A private method is exempt until something makes it a thread
        entry point: Thread(target=self._pump) seeds it, so its lock-free
        write fires; the never-referenced private twin stays silent."""
        found = self._run(tmp_path, {
            "badpkg/service/plane.py": """
                import threading

                class Plane:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.jobs = 0

                    def start(self):
                        t = threading.Thread(target=self._pump)
                        t.start()

                    def submit(self):
                        with self._lock:
                            self.jobs += 1

                    def _pump(self):
                        self.jobs = 0

                    def _never_called(self):
                        self.jobs = -1
            """,
        })
        assert rules_of(found) == {"unguarded-field"}
        assert [f.symbol for f in found] == ["Plane._pump"]

    def test_lock_free_container_swap_is_unlocked_publication(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/service/plane.py": """
                import threading

                class Plane:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.items = []

                    def add(self, x):
                        with self._lock:
                            self.items.append(x)

                    def clear_all(self):
                        self.items = []
            """,
        })
        assert rules_of(found) == {"unlocked-publication"}
        assert found[0].symbol == "Plane.clear_all"

    def test_helper_inherits_caller_lockset(self, tmp_path):
        """Interprocedural half: a private helper called only from inside
        ``with self._lock:`` runs with that lockset, so its accesses are
        guarded even though the helper itself names no lock."""
        found = self._run(tmp_path, {
            "badpkg/service/plane.py": """
                import threading

                class Plane:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.count = 0

                    def bump(self):
                        with self._lock:
                            self._bump_locked()

                    def drain(self):
                        with self._lock:
                            self._bump_locked()

                    def _bump_locked(self):
                        self.count += 1
            """,
        })
        assert found == []

    def test_out_of_scope_subtree_is_exempt(self, tmp_path):
        """The same unguarded pattern outside the concurrency-bearing
        subtrees (a controller that runs single-threaded) is not scanned."""
        found = self._run(tmp_path, {
            "badpkg/controllers/loop.py": """
                import threading

                class Loop:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.count = 0

                    def bump(self):
                        with self._lock:
                            self.count += 1

                    def reset(self):
                        self.count = 0
            """,
        })
        assert found == []

    def test_current_tree_clean(self, repo_project):
        from karpenter_core_tpu.analysis.passes import shared_state

        kept = shared_state.run(repo_project)
        assert kept == [], [f.render() for f in kept]


# -- env-flags (KC_* registry) ------------------------------------------------


class TestEnvFlags:
    """env-flags: every KC_* environment read must appear in the central
    registry (utils/flags.py FLAGS) and the docs table (docs/FLAGS.md);
    registry rows nothing reads are dead."""

    def _run(self, tmp_path, files):
        from karpenter_core_tpu.analysis.passes import env_flags

        return env_flags.run(make_project(tmp_path, files))

    _REGISTRY = """
        FLAGS = {
            "KC_RATE": "per-tenant refill rate",
        }
    """

    def test_unregistered_read_fires_at_the_read_site(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/svc.py": """
                import os
                TIMEOUT = os.getenv("KC_TIMEOUT_S", "5")
            """,
            "badpkg/utils/flags.py": self._REGISTRY,
        })
        rules = [(f.path, f.rule) for f in found]
        assert ("badpkg/svc.py", "unregistered-read") in rules
        assert any("KC_TIMEOUT_S" in f.detail for f in found)

    def test_dead_entry_and_undocumented_fire_at_the_registry_row(
            self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/utils/flags.py": self._REGISTRY,
        })
        rules = rules_of(found)
        assert "dead-entry" in rules          # nothing reads KC_RATE
        assert "undocumented-flag" in rules   # no docs/FLAGS.md row
        assert all(f.path == "badpkg/utils/flags.py" for f in found)

    def test_registered_documented_read_is_clean(self, tmp_path):
        project = make_project(tmp_path, {
            "badpkg/svc.py": """
                import os
                RATE = os.getenv("KC_RATE", "1")
            """,
            "badpkg/utils/flags.py": self._REGISTRY,
        })
        docs = tmp_path / "docs" / "FLAGS.md"
        docs.parent.mkdir(parents=True, exist_ok=True)
        docs.write_text("| `KC_RATE` | refill rate |\n")
        from karpenter_core_tpu.analysis.passes import env_flags

        assert env_flags.run(project) == []

    def test_helper_indirection_counts_as_a_read(self, tmp_path):
        """Reads through a local env helper (``_env("KC_X")`` where the
        helper's parameter flows into os.getenv) must resolve, in both
        directions: the flag is not dead, and an unregistered flag read
        through the helper still fires."""
        found = self._run(tmp_path, {
            "badpkg/cfg.py": """
                import os

                def _env(name, default=""):
                    return os.getenv(name, default)

                RATE = _env("KC_RATE")
                BURST = _env("KC_BURST")
            """,
            "badpkg/utils/flags.py": self._REGISTRY,
        })
        rules = [(f.rule, f.detail.split()[0]) for f in found]
        assert ("unregistered-read", "KC_BURST") in rules
        assert ("dead-entry", "registry") not in [
            (r, d) for r, d in rules if "KC_RATE" in d
        ]

    def test_environ_subscript_and_membership_are_reads(self, tmp_path):
        found = self._run(tmp_path, {
            "badpkg/svc.py": """
                import os
                A = os.environ["KC_A"]
                B = "KC_B" in os.environ
            """,
            "badpkg/utils/flags.py": self._REGISTRY,
        })
        flagged = {f.detail.split()[0] for f in found
                   if f.rule == "unregistered-read"}
        assert flagged == {"KC_A", "KC_B"}

    def test_current_tree_clean(self, repo_project):
        from karpenter_core_tpu.analysis.passes import env_flags

        kept = env_flags.run(repo_project)
        assert kept == [], [f.render() for f in kept]


# -- driver: --json and --strict ----------------------------------------------


class TestDriverJsonStrict:
    def test_json_report_on_clean_tree(self, tmp_path):
        make_project(tmp_path, {
            "badpkg/ok.py": "def f(x):\n    return x\n",
        })
        proc = run_driver(tmp_path, "--json")
        assert proc.returncode == 0, proc.stdout + proc.stderr
        report = json.loads(proc.stdout)  # stdout must be pure JSON
        assert report["ok"] is True
        assert report["findings"] == []
        assert report["total_s"] > 0
        names = {p["name"] for p in report["passes"]}
        assert {"shared-state", "env-flags", "lock-order"} <= names
        for p in report["passes"]:
            assert p["seconds"] >= 0

    def test_json_report_carries_findings(self, tmp_path):
        make_project(tmp_path, {
            "badpkg/service/plane.py": textwrap.dedent("""
                import threading

                class Plane:
                    def __init__(self):
                        self._lock = threading.Lock()
                        self.count = 0

                    def bump(self):
                        with self._lock:
                            self.count += 1

                    def reset(self):
                        self.count = 0
            """),
        })
        proc = run_driver(tmp_path, "--json")
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert report["ok"] is False
        assert any(
            f["pass"] == "shared-state" and f["rule"] == "unguarded-field"
            for f in report["findings"]
        )

    def test_strict_turns_unused_baseline_into_failure(self, tmp_path):
        files = {
            "badpkg/ok.py": "def f(x):\n    return x\n",
            "badpkg/analysis/baseline.toml": """\
                [[suppress]]
                pass = "hygiene"
                rule = "tabs"
                file = "badpkg/gone.py"
                reason = "stale: the offending file was deleted"
            """,
        }
        make_project(tmp_path, files)
        lax = run_driver(tmp_path)
        assert lax.returncode == 0, lax.stdout + lax.stderr
        assert "WARNING unused baseline entry" in lax.stderr
        strict = run_driver(tmp_path, "--strict")
        assert strict.returncode == 1, strict.stdout + strict.stderr
        assert "ERROR unused baseline entry" in strict.stderr
        assert "FAIL" in strict.stdout
