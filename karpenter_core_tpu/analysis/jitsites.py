"""Discovery of ``jax.jit`` sites and their static-argument declarations.

Shared by the trace-safety pass (jit targets seed reachability) and the
retrace-budget pass (each site's static_argnums/static_argnames is checked
against the compile-cache key).  Handles the spellings this repo uses:

    @jax.jit
    @functools.partial(jax.jit, static_argnames=(...))
    jax.jit(fn, ...)
    jax.jit(lambda ...: ..., ...)
    jax.jit(jax.vmap(fn), ...)
    functools.partial(jax.jit, ...)(fn)

Targets unwrap through ``vmap``/``partial`` chains to the underlying
function expression.
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from karpenter_core_tpu.analysis.core import (
    SourceModule,
    import_map,
    resolve_call_root,
)

_JIT_NAMES = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}
_SHARD_MAP_NAMES = {
    "jax.experimental.shard_map.shard_map",
    "jax.shard_map",
    "shard_map",
}
_PARTIAL_NAMES = {"functools.partial", "partial"}
_UNWRAP_NAMES = {
    "jax.vmap", "vmap", "jax.checkpoint", "jax.remat",
    # a jitted shard_map unwraps to its body for reachability: host syncs
    # inside sharded bodies are trace hazards exactly like under plain jit
    "jax.experimental.shard_map.shard_map", "jax.shard_map", "shard_map",
}


@dataclass
class JitSite:
    module: SourceModule
    lineno: int
    target: Optional[ast.expr]  # function expression (Name/Attribute/Lambda)
    decorated: Optional[ast.AST] = None  # FunctionDef when a decorator site
    static_argnames: Optional[Tuple[str, ...]] = None
    static_argnums: Optional[Tuple[int, ...]] = None
    non_literal_statics: bool = False  # statics computed, not literal
    enclosing: str = ""  # qualname of the function containing the site ("" = module scope)
    jit_call: Optional[ast.Call] = None
    kwargs: Dict[str, ast.expr] = field(default_factory=dict)


def _literal_names(node: ast.expr) -> Optional[Tuple[str, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant) and isinstance(el.value, str)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


def _literal_nums(node: ast.expr) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for el in node.elts:
            if not (isinstance(el, ast.Constant) and isinstance(el.value, int)):
                return None
            out.append(el.value)
        return tuple(out)
    return None


def _unwrap_target(
    expr: ast.expr, imports: Dict[str, str], tree: Optional[ast.Module] = None
) -> ast.expr:
    """Peel vmap/partial wrappers down to the wrapped function expression.
    A bare Name is chased through (single-assignment) local bindings so
    ``grid = jax.vmap(one_cell); jax.jit(grid)`` still yields ``one_cell``."""
    for _ in range(8):  # bounded: pathological chains just stop resolving
        if isinstance(expr, ast.Call):
            root = resolve_call_root(expr.func, imports)
            if (root in _UNWRAP_NAMES or root in _PARTIAL_NAMES) and expr.args:
                expr = expr.args[0]
                continue
            return expr
        if isinstance(expr, ast.Name) and tree is not None:
            bound = _assignment_value(tree, expr.id)
            if bound is not None and isinstance(bound, ast.Call):
                expr = bound
                continue
        return expr
    return expr


def _assignment_value(tree: ast.Module, name: str) -> Optional[ast.expr]:
    """Value of the single ``name = <expr>`` assignment in the module, or
    None when the name is unassigned or assigned more than once."""
    hits: List[ast.expr] = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if isinstance(t, ast.Name) and t.id == name:
                hits.append(node.value)
    return hits[0] if len(hits) == 1 else None


def _apply_statics(site: JitSite, call: ast.Call) -> None:
    for kw in call.keywords:
        if kw.arg is None:
            continue
        site.kwargs[kw.arg] = kw.value
        if kw.arg == "static_argnames":
            names = _literal_names(kw.value)
            if names is None:
                site.non_literal_statics = True
            else:
                site.static_argnames = names
        elif kw.arg == "static_argnums":
            nums = _literal_nums(kw.value)
            if nums is None:
                site.non_literal_statics = True
            else:
                site.static_argnums = nums


def _is_partial_of_jit(call: ast.Call, imports: Dict[str, str]) -> bool:
    root = resolve_call_root(call.func, imports)
    if root not in _PARTIAL_NAMES or not call.args:
        return False
    return resolve_call_root(call.args[0], imports) in _JIT_NAMES


def _enclosing_map(tree: ast.Module) -> Dict[int, str]:
    """node id -> qualname of the enclosing function ("" = module scope) —
    the per-call-construction checks need to know which function a jit/
    shard_map site lives in.  Shared by find_jit_sites and
    find_shard_map_sites so the tracking can never drift between them."""
    enclosing_of: Dict[int, str] = {}

    def mark(node: ast.AST, qual: List[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                mark(child, qual + [child.name])
            elif isinstance(child, ast.ClassDef):
                mark(child, qual + [child.name])
            else:
                enclosing_of[id(child)] = ".".join(qual)
                mark(child, qual)

    mark(tree, [])
    return enclosing_of


def find_jit_sites(module: SourceModule) -> List[JitSite]:
    imports = import_map(module.tree)
    sites: List[JitSite] = []
    enclosing_of = _enclosing_map(module.tree)

    for node in ast.walk(module.tree):
        # decorator sites
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                root = resolve_call_root(
                    dec.func if isinstance(dec, ast.Call) else dec, imports
                )
                if root in _JIT_NAMES:
                    site = JitSite(
                        module=module, lineno=node.lineno, target=None,
                        decorated=node,
                        enclosing=enclosing_of.get(id(node), ""),
                    )
                    if isinstance(dec, ast.Call):
                        site.jit_call = dec
                        _apply_statics(site, dec)
                    sites.append(site)
                elif isinstance(dec, ast.Call) and _is_partial_of_jit(dec, imports):
                    site = JitSite(
                        module=module, lineno=node.lineno, target=None,
                        decorated=node, jit_call=dec,
                        enclosing=enclosing_of.get(id(node), ""),
                    )
                    _apply_statics(site, dec)
                    sites.append(site)
            continue
        if not isinstance(node, ast.Call):
            continue
        root = resolve_call_root(node.func, imports)
        if root in _JIT_NAMES and node.args:
            site = JitSite(
                module=module, lineno=node.lineno,
                target=_unwrap_target(node.args[0], imports, module.tree),
                jit_call=node,
                enclosing=enclosing_of.get(id(node), ""),
            )
            _apply_statics(site, node)
            sites.append(site)
        elif (
            isinstance(node.func, ast.Call)
            and _is_partial_of_jit(node.func, imports)
            and node.args
        ):
            # partial(jax.jit, ...)(fn)
            site = JitSite(
                module=module, lineno=node.lineno,
                target=_unwrap_target(node.args[0], imports, module.tree),
                jit_call=node.func,
                enclosing=enclosing_of.get(id(node), ""),
            )
            _apply_statics(site, node.func)
            sites.append(site)
    return sites


def _shard_map_kwargs(site: JitSite, call: ast.Call) -> None:
    """Record shard_map's config expressions (mesh/in_specs/out_specs/
    check_vma) on the site.  ``jax.shard_map`` takes them by keyword only;
    the positional ``mesh`` (arg 1) is the retired
    ``jax.experimental.shard_map`` spelling, still recognized so old code
    is analyzed rather than skipped."""
    for kw in call.keywords:
        if kw.arg:
            site.kwargs[kw.arg] = kw.value
    if "mesh" not in site.kwargs and len(call.args) >= 2:
        site.kwargs["mesh"] = call.args[1]


def find_shard_map_sites(module: SourceModule) -> List[JitSite]:
    """``shard_map`` call sites, same spellings as ``find_jit_sites``:

        shard_map(body, mesh=..., in_specs=..., out_specs=...)
        @functools.partial(shard_map, mesh=..., ...)
        functools.partial(shard_map, mesh=...)(body)

    Shared by trace-safety (sharded bodies seed jit reachability — a host
    sync inside one hangs/retraces exactly like under plain jit) and
    retrace-budget (per-call construction + un-keyed mesh statics,
    docs/ANALYSIS.md)."""
    imports = import_map(module.tree)
    sites: List[JitSite] = []
    enclosing_of = _enclosing_map(module.tree)

    def _is_partial_of_shard_map(call: ast.Call) -> bool:
        if resolve_call_root(call.func, imports) not in _PARTIAL_NAMES:
            return False
        return bool(call.args) and (
            resolve_call_root(call.args[0], imports) in _SHARD_MAP_NAMES
        )

    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                if isinstance(dec, ast.Call) and (
                    resolve_call_root(dec.func, imports) in _SHARD_MAP_NAMES
                    or _is_partial_of_shard_map(dec)
                ):
                    site = JitSite(
                        module=module, lineno=node.lineno, target=None,
                        decorated=node, jit_call=dec,
                        enclosing=enclosing_of.get(id(node), ""),
                    )
                    _shard_map_kwargs(site, dec)
                    sites.append(site)
            continue
        if not isinstance(node, ast.Call):
            continue
        root = resolve_call_root(node.func, imports)
        if root in _SHARD_MAP_NAMES and node.args:
            site = JitSite(
                module=module, lineno=node.lineno,
                target=_unwrap_target(node.args[0], imports, module.tree),
                jit_call=node,
                enclosing=enclosing_of.get(id(node), ""),
            )
            _shard_map_kwargs(site, node)
            sites.append(site)
        elif (
            isinstance(node.func, ast.Call)
            and _is_partial_of_shard_map(node.func)
            and node.args
        ):
            site = JitSite(
                module=module, lineno=node.lineno,
                target=_unwrap_target(node.args[0], imports, module.tree),
                jit_call=node.func,
                enclosing=enclosing_of.get(id(node), ""),
            )
            _shard_map_kwargs(site, node.func)
            sites.append(site)
    return sites
