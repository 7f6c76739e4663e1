"""hygiene: the generic lint rules, ported from the bespoke tools/lint.py
walker onto the framework (tools/lint.py is now a thin CLI over this pass).

Rules carried over unchanged:

  unused-import       imported name never referenced (module ``__init__.py``
                      re-export files and ``__all__`` names are exempt;
                      identifier-boundary matches in string constants count
                      as uses, the documented forward-reference
                      over-approximation)
  bare-except         ``except:`` with no exception class
  mutable-default     list/dict/set literals as parameter defaults
  f-string-no-field   f-string without any substitution
  tabs / trailing-ws  formatting gate
  long-line           > 120 characters

New with the framework:

  assert-in-package   ``assert`` statements in shipped package code —
                      ``python -O`` strips them, so they are not error
                      handling; ``karpenter_core_tpu/testing/`` (the test
                      harness) and tests/ are exempt
  wallclock           ``time.time()`` / ``datetime.now()`` /
                      ``datetime.utcnow()`` in the reconcile world
                      (controllers/, state/, operator/, solver/, kubeapi/,
                      soak/, policy/): TTL logic and soak timelines must go
                      through utils/clock.Clock so suites advance time
                      deterministically (and soak verdicts replay from
                      their seed)
  per-pod-loop        Python ``for`` loops (and comprehensions) iterating a
                      pod collection inside the encode hot path
                      (models/columnar.py, models/snapshot.py): the
                      delta-native ingest (docs/KERNEL_PERF.md "Layer 6")
                      columnarized the per-pod work into interned fast keys
                      and numpy batch ops, and a new O(pods)-body loop would
                      silently regress the million-pod tick budget.  The
                      deliberate residual loops (the bulk-add driver and
                      the cold batch paths' group_by_signature, whose
                      bodies are O(1) dict work against the fast key) carry
                      baseline entries with reasons — the rule exists so
                      NEW ones can't land unexplained.
"""

from __future__ import annotations

import ast
import re
from typing import List

from karpenter_core_tpu.analysis.core import (
    Finding,
    Project,
    SourceModule,
    import_map,
    resolve_call_root,
)

NAME = "hygiene"
MAX_LINE = 120

# package subtrees where wall-clock reads must route through utils/clock.py
# (soak/ is in: its probes and traces live on the FakeClock timeline, and a
# stray wall read would silently break verdict seed-replay; policy/ is in:
# objective decisions and counter-proposals run inside reconciles and soak
# ticks, so a wall read there breaks the same replay guarantees; service/ is
# in: the tenant plane's TTL/lease/breaker/bucket policy must step on
# FakeClock for the multi-tenant suites, and service/journal.py's record
# timestamps ride the injected Clock so durable-session recovery tests run
# on FakeClock — latency MEASUREMENT uses time.perf_counter, which stays
# allowed)
_CLOCKED_DIRS = (
    "controllers", "state", "operator", "solver", "kubeapi", "soak", "policy",
    "service",
)
_WALLCLOCK_CALLS = {
    "time.time", "datetime.now", "datetime.utcnow",
    "datetime.datetime.now", "datetime.datetime.utcnow",
}

# encode-hot-path modules the per-pod-loop rule watches (package-relative
# dotted suffixes) and the identifier names that mark an iterable as a pod
# collection when they appear anywhere inside a loop's iterated expression
_PER_POD_LOOP_MODULES = ("models.columnar", "models.snapshot")
_POD_COLLECTION_NAMES = {
    "pods", "all_pods", "bound_pods", "tpu_pods", "host_pods", "pending_pods",
}


def _iter_mentions_pods(expr: ast.AST) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id in _POD_COLLECTION_NAMES:
            return True
        if isinstance(node, ast.Attribute) and node.attr in _POD_COLLECTION_NAMES:
            return True
    return False


class _PodLoopWalker(ast.NodeVisitor):
    """Collect (line, symbol) of loops/comprehensions over pod collections,
    tracking the enclosing function/class qualname so baseline entries can
    match on ``symbol`` instead of a rot-prone line number."""

    def __init__(self) -> None:
        self.stack: List[str] = []
        self.hits: List[tuple] = []

    def _symbol(self) -> str:
        return ".".join(self.stack)

    def _scoped(self, node, name: str) -> None:
        self.stack.append(name)
        self.generic_visit(node)
        self.stack.pop()

    def visit_ClassDef(self, node: ast.ClassDef) -> None:
        self._scoped(node, node.name)

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._scoped(node, node.name)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._scoped(node, node.name)

    def visit_For(self, node: ast.For) -> None:
        if _iter_mentions_pods(node.iter):
            self.hits.append((node.lineno, self._symbol()))
        self.generic_visit(node)

    def _check_comp(self, node) -> None:
        for gen in node.generators:
            if _iter_mentions_pods(gen.iter):
                self.hits.append((node.lineno, self._symbol()))
                break
        self.generic_visit(node)

    visit_ListComp = _check_comp
    visit_SetComp = _check_comp
    visit_DictComp = _check_comp
    visit_GeneratorExp = _check_comp


class _Walker(ast.NodeVisitor):
    def __init__(self) -> None:
        self.imports: dict = {}  # name -> (line, module)
        self.used: set = set()
        self.findings: List[tuple] = []  # (line, rule, detail)
        self.dunder_all: set = set()

    def visit_Import(self, node: ast.Import) -> None:
        for alias in node.names:
            name = alias.asname or alias.name.split(".")[0]
            self.imports[name] = (node.lineno, alias.name)

    def visit_ImportFrom(self, node: ast.ImportFrom) -> None:
        if node.module == "__future__":
            return
        for alias in node.names:
            if alias.name == "*":
                continue
            name = alias.asname or alias.name
            self.imports[name] = (node.lineno, f"{node.module}.{alias.name}")

    def visit_Name(self, node: ast.Name) -> None:
        if isinstance(node.ctx, ast.Load):
            self.used.add(node.id)
        self.generic_visit(node)

    def visit_Assign(self, node: ast.Assign) -> None:
        for target in node.targets:
            if isinstance(target, ast.Name) and target.id == "__all__":
                for element in ast.walk(node.value):
                    if isinstance(element, ast.Constant) and isinstance(
                        element.value, str
                    ):
                        self.dunder_all.add(element.value)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node: ast.ExceptHandler) -> None:
        if node.type is None:
            self.findings.append(
                (node.lineno, "bare-except", "use `except Exception:`")
            )
        self.generic_visit(node)

    def _check_defaults(self, node) -> None:
        for default in list(node.args.defaults) + [
            d for d in node.args.kw_defaults if d is not None
        ]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                self.findings.append(
                    (default.lineno, "mutable-default", "use None + in-body init")
                )

    def visit_FunctionDef(self, node: ast.FunctionDef) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_AsyncFunctionDef(self, node: ast.AsyncFunctionDef) -> None:
        self._check_defaults(node)
        self.generic_visit(node)

    def visit_JoinedStr(self, node: ast.JoinedStr) -> None:
        if not any(isinstance(v, ast.FormattedValue) for v in node.values):
            self.findings.append(
                (node.lineno, "f-string-no-field", "drop the f prefix")
            )
        # visit interpolated expressions — including those inside dynamic
        # format specs — but never a spec's JoinedStr itself (a field-less
        # inner JoinedStr would false-positive the no-field check)
        def visit_fields(joined: ast.JoinedStr) -> None:
            for value in joined.values:
                if isinstance(value, ast.FormattedValue):
                    self.visit(value.value)
                    if isinstance(value.format_spec, ast.JoinedStr):
                        visit_fields(value.format_spec)

        visit_fields(node)


def check_module(module: SourceModule, project: Project) -> List[Finding]:
    out: List[Finding] = []

    def finding(line: int, rule: str, detail: str) -> None:
        out.append(Finding(module.relpath, line, rule, detail, NAME))

    for i, line in enumerate(module.lines, 1):
        if "\t" in line:
            finding(i, "tabs", "use spaces")
        if line != line.rstrip():
            finding(i, "trailing-ws", "trailing whitespace")
        if len(line) > MAX_LINE:
            finding(i, "long-line", f"{len(line)} > {MAX_LINE}")

    walker = _Walker()
    walker.visit(module.tree)
    # string-annotation references ("Optional[Clock]") count as uses — the
    # documented over-approximation from the original lint.py
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            for name in walker.imports:
                if re.search(rf"\b{re.escape(name)}\b", node.value):
                    walker.used.add(name)
    if module.path.name != "__init__.py":
        for name, (lineno, target) in sorted(walker.imports.items()):
            if name not in walker.used and name not in walker.dunder_all:
                finding(lineno, "unused-import", f"{target} as {name}")
    for lineno, rule, detail in walker.findings:
        finding(lineno, rule, detail)

    # -- assert-in-package -----------------------------------------------------
    in_shipped_package = module.in_package and not module.name.startswith(
        f"{project.package}.testing"
    )
    if in_shipped_package:
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Assert):
                finding(
                    node.lineno, "assert-in-package",
                    "assert in shipped package code disappears under "
                    "`python -O`; raise an exception instead",
                )

    # -- per-pod-loop ----------------------------------------------------------
    if module.in_package and any(
        module.name.endswith(f".{suffix}") for suffix in _PER_POD_LOOP_MODULES
    ):
        pod_walker = _PodLoopWalker()
        pod_walker.visit(module.tree)
        for lineno, symbol in pod_walker.hits:
            out.append(Finding(
                module.relpath, lineno, "per-pod-loop",
                "Python loop over a pod collection in the encode hot path — "
                "columnarize it (interned fast keys / numpy batch ops) or "
                "baseline it with a reason (docs/KERNEL_PERF.md Layer 6)",
                NAME, symbol=symbol,
            ))

    # -- wallclock -------------------------------------------------------------
    parts = module.name.split(".")
    if module.in_package and len(parts) > 1 and parts[1] in _CLOCKED_DIRS:
        imports = import_map(module.tree)
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call):
                root = resolve_call_root(node.func, imports)
                if root in _WALLCLOCK_CALLS:
                    finding(
                        node.lineno, "wallclock",
                        f"{root}() in reconcile-world code defeats FakeClock "
                        "determinism; take a utils/clock.Clock and call "
                        ".now()",
                    )
    return out


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for module in project.all_modules:
        findings.extend(check_module(module, project))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
