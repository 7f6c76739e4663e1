"""unbounded-block: device blocking reachable outside a watchdog scope.

The watchdog (utils/watchdog.py) is only hang-proof if every blocking
device interaction actually routes through it — one raw
``jax.device_get`` / ``block_until_ready`` / deferred-handle ``.result()``
on the solve path reintroduces exactly the unbounded wait the watchdog
exists to bound.  This rule extends the PR-4 blocking-call machinery
(analysis/passes/lock_order's blocking set) to the device-path subtrees:

  unbounded-block   a blocking device call (``jax.device_get``,
                    ``jax.block_until_ready``, method spellings
                    ``.device_get()``/``.block_until_ready()``, or
                    ``.result()``) in a device-path module, outside any
                    MonitoredDispatch scope — i.e. not lexically inside a
                    ``watchdog.run(...)`` / ``MonitoredDispatch(...).run(...)``
                    call and not in utils/watchdog.py itself.

Passing the blocking callable INTO the watchdog
(``watchdog.run(site, jax.device_get, tree)``) produces no Call node and
is automatically clean — the preferred integration shape.  Deliberate
residual sites (host-thread futures like the compilecache upload overlap,
deferred-handle retirement that settles through the monitored session)
carry baseline entries with reasons; the rule exists so NEW unbounded
blocking can't land unexplained.
"""

from __future__ import annotations

import ast
from typing import List

from karpenter_core_tpu.analysis.core import (
    Finding,
    Project,
    import_map,
    resolve_call_root,
)

NAME = "unbounded-block"

# package-relative dotted prefixes of the device-path subtrees the rule
# watches (controllers/ and models/ never hold device handles directly; the
# watchdog module itself is the monitored scope)
_WATCHED_PREFIXES = (
    "ops.", "solver.", "parallel.", "service.",
)
_WATCHED_MODULES = ("utils.pipeline", "utils.compilecache")
_EXEMPT_MODULES = ("utils.watchdog",)

# dotted roots / method names that block on device values
_BLOCKING_ROOTS = {"jax.device_get", "jax.block_until_ready"}
_BLOCKING_METHODS = {"device_get", "block_until_ready", "result"}

# resolved dotted roots that ARE the monitored scope: any blocking call
# lexically inside one of these call expressions is watchdog-bounded
_MONITORED_CALLS = {
    "karpenter_core_tpu.utils.watchdog.run",
    "watchdog.run",
    "watchdog_mod.run",
}


def _relname(module) -> str:
    """Module name relative to the package root (``utils.pipeline``)."""
    parts = module.name.split(".")
    return ".".join(parts[1:]) if len(parts) > 1 else module.name


def _watched(module) -> bool:
    rel = _relname(module)
    if rel in _EXEMPT_MODULES:
        return False
    return rel in _WATCHED_MODULES or any(
        rel.startswith(p) for p in _WATCHED_PREFIXES
    )


class _Walker(ast.NodeVisitor):
    """Collect blocking calls with their enclosing symbol, tracking how many
    monitored-scope call expressions enclose the current node."""

    def __init__(self, imports) -> None:
        self.imports = imports
        self.stack: List[str] = []
        self.monitored_depth = 0
        self.hits: List[tuple] = []  # (line, desc, symbol)

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

    def _is_monitored(self, node: ast.Call) -> bool:
        root = resolve_call_root(node.func, self.imports)
        if root in _MONITORED_CALLS:
            return True
        # MonitoredDispatch(...).run(...) style, NARROWLY: the receiver must
        # be a MonitoredDispatch construction or a name/attr that literally
        # says "watchdog" — a generic ``something_dispatch.run(...)`` must
        # NOT silently exempt the blocking calls nested inside it
        if isinstance(node.func, ast.Attribute) and node.func.attr == "run":
            recv = node.func.value
            if isinstance(recv, ast.Call):
                recv_root = resolve_call_root(recv.func, self.imports) or ""
                if recv_root.rsplit(".", 1)[-1] == "MonitoredDispatch":
                    return True
            if isinstance(recv, ast.Name) and "watchdog" in recv.id.lower():
                return True
            if isinstance(recv, ast.Attribute) and (
                "watchdog" in recv.attr.lower()
            ):
                return True
        return False

    def visit_Call(self, node: ast.Call) -> None:
        monitored = self._is_monitored(node)
        if monitored:
            self.monitored_depth += 1
        if self.monitored_depth == 0:
            root = resolve_call_root(node.func, self.imports)
            desc = None
            if root in _BLOCKING_ROOTS:
                desc = root
            elif (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _BLOCKING_METHODS
            ):
                desc = f".{node.func.attr}()"
            if desc is not None:
                self.hits.append((node.lineno, desc, self._symbol()))
        self.generic_visit(node)
        if monitored:
            self.monitored_depth -= 1


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for module in project.package_modules:
        if not _watched(module):
            continue
        walker = _Walker(import_map(module.tree))
        walker.visit(module.tree)
        for line, desc, symbol in walker.hits:
            findings.append(Finding(
                module.relpath, line, NAME,
                f"blocking device call {desc} outside a MonitoredDispatch "
                "scope — a quiet device hangs it forever; route it through "
                "utils/watchdog.run (or baseline it with the reason it is "
                "bounded)",
                NAME,
                symbol=symbol,
            ))
    return findings
