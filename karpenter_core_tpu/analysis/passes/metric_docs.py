"""metric-docs: two-way drift gate between the registered metric families
and docs/OBSERVABILITY.md (ISSUE 16).

The observability doc is the fleet-operator contract: dashboards and alert
rules are written against it, not against the source.  Metrics drift out of
it in both directions — a new family lands in code and never reaches the
doc (undocumented-metric), or a family is renamed/removed and the doc keeps
promising it (stale-doc-metric).  Both are findings; deliberate exceptions
carry baseline entries with reasons, like every other pass.

What counts as a registration (package-wide — families are registered where
they are used: tenant.py, journal.py, retry.py, watchdog.py, chaos.py,
compilecache.py, pipeline.py, the controllers — not just
metrics/registry.py):

  REGISTRY.counter("karpenter_...", ...)        # any attr base, any of the
  REGISTRY.gauge/histogram/summary(...)         # four family kinds
  Counter/Gauge/Histogram/Summary(              # direct construction, the
      NAMESPACE + "_...", ...)                  # registry.py idiom

The name operand must be a string literal or ``NAMESPACE + "_..."`` —
anything dynamic is invisible to scrapers' docs too and gets its own
finding (dynamic-metric-name).  Only ``karpenter_*`` families participate:
the ``controller_runtime_*`` compatibility names mirror controller-runtime
and are documented upstream.

Doc-side tokens are ``karpenter_[a-z0-9_]+`` words in
docs/OBSERVABILITY.md.  A token matches a family exactly, via a rendered
sample suffix (``_bucket``/``_sum``/``_count``), or as a line-wrap prefix
(token ends with ``_`` and a family starts with it).  The package-name
token ``karpenter_core_tpu...`` is ignored.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List

from karpenter_core_tpu.analysis.core import Finding, Project

NAME = "metric-docs"

DOC_PATH = "docs/OBSERVABILITY.md"
# metrics/registry.py NAMESPACE — resolved statically; the pass re-reads it
# from the registry module when available so a namespace rename cannot
# silently blind the gate
DEFAULT_NAMESPACE = "karpenter"

_FAMILY_KINDS = {"counter", "gauge", "histogram", "summary"}
_CTOR_NAMES = {"Counter", "Gauge", "Histogram", "Summary"}
_DOC_TOKEN = re.compile(r"karpenter_[a-z0-9_]+")
_SAMPLE_SUFFIXES = ("_bucket", "_sum", "_count")


def _namespace(project: Project) -> str:
    mod = project.get("karpenter_core_tpu.metrics.registry")
    if mod is not None:
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Assign)
                and any(
                    isinstance(t, ast.Name) and t.id == "NAMESPACE"
                    for t in node.targets
                )
                and isinstance(node.value, ast.Constant)
                and isinstance(node.value.value, str)
            ):
                return node.value.value
    return DEFAULT_NAMESPACE


def _is_registration(call: ast.Call) -> bool:
    func = call.func
    if isinstance(func, ast.Attribute) and func.attr in _FAMILY_KINDS:
        return True
    return isinstance(func, ast.Name) and func.id in _CTOR_NAMES


def _literal_name(arg: ast.expr, namespace: str):
    """The family name when the operand is statically resolvable, else
    None.  Handles the two idioms: a plain string literal and the
    ``NAMESPACE + "_suffix"`` concatenation."""
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    if (
        isinstance(arg, ast.BinOp)
        and isinstance(arg.op, ast.Add)
        and isinstance(arg.left, ast.Name)
        and arg.left.id == "NAMESPACE"
        and isinstance(arg.right, ast.Constant)
        and isinstance(arg.right.value, str)
    ):
        return namespace + arg.right.value
    return None


def collect_families(project: Project, namespace: str):
    """{family: (relpath, line)} of every karpenter_* registration in the
    package, plus findings for dynamic (unresolvable) name operands."""
    families: Dict[str, tuple] = {}
    dynamic: List[Finding] = []
    for mod in project.package_modules:
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and _is_registration(node)):
                continue
            if not node.args:
                continue
            name = _literal_name(node.args[0], namespace)
            if name is None:
                if isinstance(node.args[0], ast.Name):
                    # a bare variable is a pass-through wrapper (the
                    # Registry.counter/... factories themselves), not a
                    # registration site
                    continue
                dynamic.append(Finding(
                    path=mod.relpath, line=node.lineno,
                    rule="dynamic-metric-name", pass_name=NAME,
                    detail="metric family name is not a string literal "
                           "(or NAMESPACE + literal) — scrapers and "
                           "docs/OBSERVABILITY.md cannot track it",
                ))
                continue
            if name.startswith(namespace + "_"):
                families.setdefault(name, (mod.relpath, node.lineno))
    return families, dynamic


def doc_tokens(text: str) -> Dict[str, int]:
    """{token: first line number} of karpenter_* words in the doc."""
    out: Dict[str, int] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        for tok in _DOC_TOKEN.findall(line):
            out.setdefault(tok, lineno)
    return out


def run(project: Project) -> List[Finding]:
    namespace = _namespace(project)
    families, findings = collect_families(project, namespace)

    doc_file = project.root / DOC_PATH
    if not doc_file.is_file():
        # a tree that registers no families needs no doc surface (the
        # driver's synthetic fixture trees, downstream forks without
        # telemetry); one registered family makes the doc mandatory
        if families:
            findings.append(Finding(
                path=DOC_PATH, line=1, rule="missing-doc", pass_name=NAME,
                detail=f"{DOC_PATH} not found — the metric contract has no "
                       "documentation surface",
            ))
        return findings
    tokens = doc_tokens(doc_file.read_text(encoding="utf-8"))
    tokens = {
        t: ln for t, ln in tokens.items()
        if not t.startswith("karpenter_core_tpu")
    }

    def documented(family: str) -> bool:
        if family in tokens:
            return True
        for tok in tokens:
            if tok.endswith("_") and family.startswith(tok):
                return True  # line-wrapped name in the doc
            if tok.startswith(family) and tok[len(family):] in _SAMPLE_SUFFIXES:
                return True  # doc shows a rendered sample line
        return False

    for family in sorted(families):
        if not documented(family):
            path, line = families[family]
            findings.append(Finding(
                path=path, line=line, rule="undocumented-metric",
                pass_name=NAME,
                detail=f"{family} is registered but absent from {DOC_PATH}",
            ))

    def registered(tok: str) -> bool:
        if tok in families:
            return True
        if tok.endswith("_") and any(f.startswith(tok) for f in families):
            return True  # line-wrap fragment of a real family
        for family in families:
            if tok.startswith(family) and tok[len(family):] in _SAMPLE_SUFFIXES:
                return True
        return False

    for tok, lineno in sorted(tokens.items()):
        if not registered(tok):
            findings.append(Finding(
                path=DOC_PATH, line=lineno, rule="stale-doc-metric",
                pass_name=NAME,
                detail=f"{tok} is documented but no package registration "
                       "creates it",
            ))
    return findings
