"""env-flags: every KC_* environment flag is registered and documented.

The service grew ~50 ``KC_*`` tuning flags (KC_PIPELINE, KC_WATCHDOG,
KC_COALESCE_WINDOW, KC_FLEET_CHECKPOINT_KEEP, ...) with
no central inventory: a flag you cannot find is a flag you cannot audit,
and a dead registry row is documentation that lies.  This pass closes the
loop in both directions against the central registry
(``karpenter_core_tpu/utils/flags.py`` ``FLAGS`` table) and the docs table
(``docs/FLAGS.md``):

  unregistered-read  a ``KC_*`` read (``os.environ.get`` / ``os.environ[...]``
                     / ``os.getenv`` / ``"KC_X" in os.environ`` / a literal
                     flag name passed to an env-helper like ``_env_f``) whose
                     flag is missing from the registry
  dead-entry         a registry row no package code reads
  undocumented-flag  a registry row missing from the docs/FLAGS.md table

Scope is the package only: tools/tests harness flags (KC_CHAOS_SEED,
KC_SOAK_SEED, ...) are out of band and stay out of the registry.
Helper indirection is inferred, not hard-coded: any package function whose
parameter flows into an environ read is an env-helper, and literal first
arguments at its call sites count as reads of that flag.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Set, Tuple

from karpenter_core_tpu.analysis.core import (
    Finding,
    Project,
    SourceModule,
    dotted,
    import_map,
)

NAME = "env-flags"

_FLAG_RE = re.compile(r"\bKC_[A-Z0-9_]+\b")

_REGISTRY_REL = "utils/flags.py"
_DOCS_REL = "docs/FLAGS.md"


def _norm(expr: ast.expr, imports: Dict[str, str]) -> Optional[str]:
    """Dotted name with the import map applied: ``environ.get`` ->
    ``os.environ.get`` under ``from os import environ``."""
    name = dotted(expr)
    if name is None:
        return None
    head, _, rest = name.partition(".")
    target = imports.get(head, head)
    return f"{target}.{rest}" if rest else target


def _flag_of(expr: ast.expr) -> Optional[str]:
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str) and \
            _FLAG_RE.fullmatch(expr.value):
        return expr.value
    return None


def _param_of(expr: ast.expr, params: Set[str]) -> Optional[str]:
    if isinstance(expr, ast.Name) and expr.id in params:
        return expr.id
    return None


def _env_read_arg(node: ast.AST, imports: Dict[str, str]) -> Optional[ast.expr]:
    """The flag-name expression of an environment read, or None."""
    if isinstance(node, ast.Call):
        root = _norm(node.func, imports)
        if root in ("os.getenv", "os.environ.get", "os.environ.setdefault",
                    "os.environ.pop") and node.args:
            return node.args[0]
    if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        if _norm(node.value, imports) == "os.environ":
            return node.slice
    if isinstance(node, ast.Compare) and len(node.ops) == 1 and isinstance(
        node.ops[0], (ast.In, ast.NotIn)
    ):
        if node.comparators and _norm(
            node.comparators[0], imports
        ) == "os.environ":
            return node.left
    return None


def _load_registry(
    project: Project,
) -> Tuple[Optional[SourceModule], Dict[str, int]]:
    """(registry module, flag -> line in flags.py)."""
    module = project.get(f"{project.package}.utils.flags")
    if module is None:
        return None, {}
    for node in module.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            target: Optional[ast.expr] = node.targets[0]
        elif isinstance(node, ast.AnnAssign):
            target = node.target
        else:
            continue
        if isinstance(target, ast.Name) and target.id == "FLAGS" and \
                isinstance(node.value, ast.Dict):
            out: Dict[str, int] = {}
            for key in node.value.keys:
                flag = _flag_of(key) if key is not None else None
                if flag is not None:
                    out[flag] = key.lineno
            return module, out
    return module, {}


def run(project: Project) -> List[Finding]:
    registry_mod, registry = _load_registry(project)

    # first sweep: find env-helper functions (a param flows into a read)
    helpers: Set[str] = set()  # bare function names, matched by leaf
    for module in project.package_modules:
        imports = import_map(module.tree)
        for fn in ast.walk(module.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            params = {
                a.arg for a in fn.args.posonlyargs + fn.args.args
                + fn.args.kwonlyargs
            }
            for node in ast.walk(fn):
                arg = _env_read_arg(node, imports)
                if arg is not None and _param_of(arg, params) is not None:
                    helpers.add(fn.name)
                    break

    # second sweep: every flag read site in the package
    reads: List[Tuple[str, SourceModule, int]] = []  # (flag, module, line)
    for module in project.package_modules:
        imports = import_map(module.tree)
        for node in ast.walk(module.tree):
            arg = _env_read_arg(node, imports)
            if arg is not None:
                flag = _flag_of(arg)
                if flag is not None:
                    reads.append((flag, module, node.lineno))
                continue
            if isinstance(node, ast.Call) and node.args:
                leaf = None
                if isinstance(node.func, ast.Name):
                    leaf = node.func.id
                elif isinstance(node.func, ast.Attribute):
                    leaf = node.func.attr
                if leaf in helpers:
                    flag = _flag_of(node.args[0])
                    if flag is not None:
                        reads.append((flag, module, node.lineno))

    findings: List[Finding] = []
    registry_path = f"{project.package}/{_REGISTRY_REL}"
    if registry_mod is not None:
        registry_path = registry_mod.relpath

    for flag, module, line in reads:
        if flag not in registry:
            findings.append(Finding(
                module.relpath, line, "unregistered-read",
                f"{flag} is read here but missing from the FLAGS registry "
                f"({registry_path}) — register it with a one-line "
                "description so the flag surface stays auditable",
                NAME,
            ))

    read_flags = {flag for flag, _, _ in reads}
    docs_path = project.root / _DOCS_REL
    try:
        documented = set(_FLAG_RE.findall(docs_path.read_text()))
    except OSError:
        documented = set()
    for flag, line in sorted(registry.items()):
        if flag not in read_flags:
            findings.append(Finding(
                registry_path, line, "dead-entry",
                f"registry entry {flag} is never read by package code — "
                "delete the row (or the dead flag plumbing it described)",
                NAME,
            ))
        if flag not in documented:
            findings.append(Finding(
                registry_path, line, "undocumented-flag",
                f"registry entry {flag} is missing from the {_DOCS_REL} "
                "table — every registered flag needs a documented default "
                "and effect",
                NAME,
            ))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings
