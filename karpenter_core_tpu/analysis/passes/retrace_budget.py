"""retrace-budget: static jit-declaration consistency with the compile cache.

The compile cache (utils/compilecache.py) keys executables on a tuple of
static config fields; ``jax.jit`` keys its own cache on static_argnums /
static_argnames.  The two drift independently, and each direction of drift
is a distinct production bug:

  static-args       a compile-cache key field that is a parameter of a
                    jitted solve entry but is NOT declared static there —
                    jit would trace it as an array (wrong program) or
                    silently key a retrace per value
  cache-key-drift   a static_argname of a solve jit site that is also a
                    ``solve_callable`` parameter but does NOT appear in the
                    compile-cache key — two configs would collide on one
                    memoized executable (silent wrong reuse)
  non-literal-static  static_argnums/static_argnames computed at runtime:
                    unauditable, and typo'd names fail only when the site
                    first runs
  unknown-static    a declared static name that is not a parameter of the
                    jitted target (typo — jax raises only on first call)
  unhashable-static a dict/list/set literal passed for a static parameter
                    at a call site of a known jitted wrapper, or a static
                    parameter whose default is a mutable literal — jit
                    raises ``unhashable type`` at solve time
  uncached-jit      ``jax.jit(...)`` constructed inside a function that is
                    not memoized (lru_cache): every call builds a fresh
                    wrapper with an empty jit cache, so every call retraces
                    (the bug class ops.consolidate.lane_sweep_fn's
                    docstring describes)
  donated-read      a buffer passed to a donating dispatch site is read
                    again afterwards in the same function — the classic
                    use-after-donate footgun of the pipelined solve loop
                    (docs/KERNEL_PERF.md "Layer 7"): the executable consumed
                    the device memory, so the read either raises
                    "buffer deleted" or (with a live host view) silently
                    degrades donation to a realloc.  Donating sites are
                    (a) calls whose callee name ends in ``_donated``
                    (ops.solve.repair_free_donated / scatter_repair_window
                    _donated — by convention their FIRST positional
                    argument is donated) and (b) ``run_prepared`` /
                    ``run_solve`` calls with a ``warm_carry=`` keyword (the
                    carry is donated whenever the pipeline is armed).
                    Branch-aware: donation inside one arm of an if/else
                    taints only that arm and the code after the branch.

The runtime half of this pass lives in tests/conftest.py: a fixture counts
actual XLA compilations per tier-1 test against the checked-in manifest
``karpenter_core_tpu/analysis/retrace_budget.json``.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from karpenter_core_tpu.analysis.callgraph import shared_graph
from karpenter_core_tpu.analysis.core import (
    Finding,
    Project,
    SourceModule,
    import_map,
    resolve_call_root,
)
from karpenter_core_tpu.analysis.jitsites import (
    JitSite,
    _PARTIAL_NAMES,
    find_jit_sites,
    find_shard_map_sites,
)

NAME = "retrace-budget"

_MEMO_DECORATORS = {
    "functools.lru_cache", "lru_cache", "functools.cache", "cache",
}
_MUTABLE_LITERALS = (ast.Dict, ast.List, ast.Set, ast.DictComp, ast.ListComp,
                     ast.SetComp)


def _params(fn: ast.AST) -> List[str]:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        return []
    a = fn.args
    return [p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)]


def _param_defaults(fn: ast.AST) -> Dict[str, ast.expr]:
    if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        return {}
    a = fn.args
    out: Dict[str, ast.expr] = {}
    pos = a.posonlyargs + a.args
    for p, d in zip(pos[len(pos) - len(a.defaults):], a.defaults):
        out[p.arg] = d
    for p, d in zip(a.kwonlyargs, a.kw_defaults):
        if d is not None:
            out[p.arg] = d
    return out


def _is_memoized(fn, imports: Dict[str, str]) -> bool:
    """The function carries a memoizing decorator (lru_cache/cache) — its
    per-call jit/shard_map constructions build once per distinct key."""
    if fn is None or not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
        return False
    for dec in fn.decorator_list:
        droot = resolve_call_root(
            dec.func if isinstance(dec, ast.Call) else dec, imports
        )
        if droot in _MEMO_DECORATORS:
            return True
    return False


def _mesh_derives_from_params(mesh_expr: ast.expr, fn: ast.AST) -> bool:
    """True when a shard_map's mesh expression references (or chases, through
    one local single-assignment, to an expression referencing) at least one
    parameter of the enclosing memoized builder — the mesh topology is then
    part of the memo key by construction (``mesh = mesh_for(mesh_axes)``).
    A mesh pulled from module scope or a closure is NOT keyed: two
    topologies would silently share one cached executable."""
    params = set(_params(fn))
    if not params:
        return False

    def names_of(expr: ast.expr):
        return {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}

    if names_of(mesh_expr) & params:
        return True
    if isinstance(mesh_expr, ast.Name):
        hits = [
            node.value
            for node in ast.walk(fn)
            if isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == mesh_expr.id
        ]
        if len(hits) == 1 and names_of(hits[0]) & params:
            return True
    return False


# donating dispatch sites for the donated-read rule: callees whose
# ``warm_carry=`` keyword argument is donated when the pipeline is armed
# (utils.compilecache.run_solve / solver.tpu.TPUSolver.run_prepared), plus
# the ``*_donated`` helper convention (first positional argument donated —
# ops/solve.py repair_free_donated / scatter_repair_window_donated)
_DONATING_CALLEES = {"run_prepared", "run_solve"}


def _call_donations(node: ast.Call) -> List[str]:
    """Plain names this call donates, per the donating-site conventions."""
    fn = node.func
    name = fn.attr if isinstance(fn, ast.Attribute) else (
        fn.id if isinstance(fn, ast.Name) else ""
    )
    out: List[str] = []
    if name.endswith("_donated"):
        if node.args and isinstance(node.args[0], ast.Name):
            out.append(node.args[0].id)
    elif name in _DONATING_CALLEES:
        for kw in node.keywords:
            if kw.arg == "warm_carry" and isinstance(kw.value, ast.Name):
                out.append(kw.value.id)
    return out


def _donated_read_findings(module: SourceModule) -> List[Finding]:
    """The donated-read rule (module docstring): an intra-procedural,
    branch-aware walk flagging reads of a name after the dispatch that
    donated its buffer.  Rebinding the name clears the taint (``carry =
    repair_free_donated(carry, ...)`` is the intended idiom — the name then
    holds the dispatch's OUTPUT, not the consumed input).  Aliased callees
    (``fn = x_donated; fn(...)``) are not chased — the rule is a tripwire
    for the direct spellings the solve path uses, not an escape-proof
    dataflow analysis."""
    findings: List[Finding] = []

    def flag(name: str, read_line: int, donate_line: int, qual: str) -> None:
        findings.append(Finding(
            module.relpath, read_line, "donated-read",
            f"{name!r} is read after being donated to the dispatch at line "
            f"{donate_line} — the executable consumed its device buffer; "
            "use the dispatch's returned value, or keep an undonated "
            "reference taken before the call",
            NAME, symbol=qual,
        ))

    def check_reads(node: ast.AST, donated: Dict[str, int], qual: str) -> None:
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Name)
                and isinstance(sub.ctx, ast.Load)
                and sub.id in donated
            ):
                flag(sub.id, sub.lineno, donated[sub.id], qual)
                donated.pop(sub.id, None)  # one finding per donation

    def register(node: ast.AST, donated: Dict[str, int]) -> None:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                for nm in _call_donations(sub):
                    donated[nm] = sub.lineno

    def clear_binds(targets, donated: Dict[str, int]) -> None:
        for t in targets:
            for sub in ast.walk(t):
                if isinstance(sub, ast.Name):
                    donated.pop(sub.id, None)

    def scan(stmts, donated: Dict[str, int], qual: str) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # nested defs get their own fresh scope below
            if isinstance(stmt, ast.If):
                check_reads(stmt.test, donated, qual)
                register(stmt.test, donated)
                body_d, else_d = dict(donated), dict(donated)
                scan(stmt.body, body_d, qual)
                scan(stmt.orelse, else_d, qual)
                donated.clear()
                donated.update(body_d)
                donated.update(else_d)
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                head = stmt.iter if hasattr(stmt, "iter") else stmt.test
                check_reads(head, donated, qual)
                register(head, donated)
                if hasattr(stmt, "target"):
                    clear_binds([stmt.target], donated)
                body_d = dict(donated)
                scan(stmt.body, body_d, qual)
                scan(stmt.orelse, body_d, qual)
                donated.update(body_d)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    check_reads(item.context_expr, donated, qual)
                    register(item.context_expr, donated)
                    if item.optional_vars is not None:
                        clear_binds([item.optional_vars], donated)
                scan(stmt.body, donated, qual)
                continue
            if isinstance(stmt, ast.Try):
                scan(stmt.body, donated, qual)
                for handler in stmt.handlers:
                    h_d = dict(donated)
                    scan(handler.body, h_d, qual)
                    donated.update(h_d)
                scan(stmt.orelse, donated, qual)
                scan(stmt.finalbody, donated, qual)
                continue
            # simple statement: reads first (the donating call's own
            # argument is not yet tainted), then new donations, then
            # rebound targets drop their taint
            check_reads(stmt, donated, qual)
            register(stmt, donated)
            if isinstance(stmt, ast.Assign):
                clear_binds(stmt.targets, donated)
            elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                clear_binds([stmt.target], donated)

    def walk_fns(node: ast.AST, qual: List[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = ".".join(qual + [child.name])
                scan(child.body, {}, q)
                walk_fns(child, qual + [child.name])
            elif isinstance(child, ast.ClassDef):
                walk_fns(child, qual + [child.name])
            else:
                walk_fns(child, qual)

    walk_fns(module.tree, [])
    return findings


def _fn_index(module: SourceModule) -> Dict[str, ast.AST]:
    """qualname -> FunctionDef for the module (dotted by nesting)."""
    out: Dict[str, ast.AST] = {}

    def walk(node: ast.AST, qual: List[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out[".".join(qual + [child.name])] = child
                walk(child, qual + [child.name])
            elif isinstance(child, ast.ClassDef):
                walk(child, qual + [child.name])
            else:
                walk(child, qual)

    walk(module.tree, [])
    return out


def _static_key_names(expr: ast.expr) -> Set[str]:
    """Parameter names the cache key STATICALLY keys on.  Names inside
    helper calls other than ``tuple(...)`` are excluded: ``leaf_sig(cls)``
    keys on shapes/dtypes — those stay runtime (traced) arguments, only the
    directly-embedded config values are static."""
    out: Set[str] = set()

    def walk(node: ast.AST) -> None:
        if isinstance(node, ast.Call):
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id == "tuple":
                for a in node.args:
                    walk(a)
            return
        if isinstance(node, ast.Name):
            out.add(node.id)
            return
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(expr)
    return out


def cache_key_fields(project: Project) -> Tuple[Set[str], Optional[SourceModule]]:
    """Parameter names of ``solve_callable`` referenced by its ``key = (...)``
    expression — the compile-cache's static config axis.  Empty when the
    project has no compilecache module (temp trees in tests)."""
    mod = project.get(f"{project.package}.utils.compilecache")
    if mod is None:
        return set(), None
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and (
            node.name == "solve_callable"
        ):
            params = set(_params(node))
            for stmt in ast.walk(node):
                if (
                    isinstance(stmt, ast.Assign)
                    and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and stmt.targets[0].id == "key"
                ):
                    used = _static_key_names(stmt.value)
                    return used & params, mod
    return set(), mod


def solve_callable_params(project: Project) -> Set[str]:
    mod = project.get(f"{project.package}.utils.compilecache")
    if mod is None:
        return set()
    for node in ast.walk(mod.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) and (
            node.name == "solve_callable"
        ):
            return set(_params(node))
    return set()


def _target_binds(site: JitSite, imports: Dict[str, str]) -> Tuple[bool, Set[str]]:
    """(went_through_partial, kwarg names bound by partial wrappers) for the
    site's ORIGINAL (pre-unwrap) target expression."""
    if site.jit_call is None or not getattr(site.jit_call, "args", None):
        return False, set()
    expr = site.jit_call.args[0]
    via_partial = False
    bound: Set[str] = set()
    while isinstance(expr, ast.Call):
        root = resolve_call_root(expr.func, imports)
        if root in _PARTIAL_NAMES and expr.args:
            via_partial = True
            bound |= {kw.arg for kw in expr.keywords if kw.arg}
            expr = expr.args[0]
            continue
        if root in ("jax.vmap", "vmap") and expr.args:
            expr = expr.args[0]
            continue
        break
    return via_partial, bound


def run(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    graph = shared_graph(project)
    key_fields, cc_mod = cache_key_fields(project)
    sc_params = solve_callable_params(project)
    solve_core_key = f"{project.package}.ops.solve:solve_core"

    # wrapper name -> (static names, target params) for unhashable checks
    wrappers: Dict[str, Tuple[Tuple[str, ...], List[str]]] = {}

    for module in project.package_modules:
        imports = import_map(module.tree)
        fn_index = _fn_index(module)
        # use-after-donate tripwire for the pipelined loop's donating
        # dispatch sites (docs/KERNEL_PERF.md "Layer 7")
        findings.extend(_donated_read_findings(module))
        sites = find_jit_sites(module)
        for site in sites:
            statics = tuple(site.static_argnames or ())
            # resolve the jitted function node
            if site.decorated is not None:
                target_node: Optional[ast.AST] = site.decorated
                target_key = graph.key_for_node(site.decorated)
            elif site.target is not None:
                if isinstance(site.target, ast.Lambda):
                    target_node = site.target
                    target_key = graph.key_for_node(site.target)
                else:
                    target_key = graph.resolve(site.target, module)
                    target_node = (
                        graph.functions[target_key].node
                        if target_key in graph.functions
                        else None
                    )
            else:
                target_node, target_key = None, None

            if site.non_literal_statics:
                findings.append(Finding(
                    module.relpath, site.lineno, "non-literal-static",
                    "static_argnums/static_argnames must be literal "
                    "constants so the declaration is auditable",
                    NAME, symbol=site.enclosing,
                ))

            target_params = _params(target_node) if target_node is not None else []
            if target_node is not None and statics:
                for name in statics:
                    if name not in target_params:
                        findings.append(Finding(
                            module.relpath, site.lineno, "unknown-static",
                            f"static_argnames entry {name!r} is not a "
                            "parameter of the jitted function",
                            NAME, symbol=site.enclosing,
                        ))
                defaults = _param_defaults(target_node)
                for name in statics:
                    d = defaults.get(name)
                    if d is not None and isinstance(d, _MUTABLE_LITERALS):
                        findings.append(Finding(
                            module.relpath, site.lineno, "unhashable-static",
                            f"static parameter {name!r} defaults to a "
                            "mutable literal; jit raises 'unhashable type' "
                            "when the default is used",
                            NAME, symbol=site.enclosing,
                        ))

            # consistency with the compile-cache key, both directions
            if key_fields and target_node is not None:
                relevant = target_key == solve_core_key or bool(
                    set(statics) & key_fields
                )
                if relevant:
                    via_partial, bound = _target_binds(site, imports)
                    static_nums = site.static_argnums or ()
                    by_pos = {
                        target_params[i]
                        for i in static_nums
                        if 0 <= i < len(target_params)
                    }
                    declared = set(statics) | by_pos | bound
                    defaults = _param_defaults(target_node)
                    for f in sorted(key_fields & set(target_params)):
                        if f in declared:
                            continue
                        if via_partial and f in defaults:
                            # partial-built wrapper: the field stays at its
                            # python default, which is a trace-time constant
                            continue
                        findings.append(Finding(
                            module.relpath, site.lineno, "static-args",
                            f"compile-cache key field {f!r} is a runtime "
                            "argument at this jit site — declare it in "
                            "static_argnames or bind it via partial",
                            NAME, symbol=site.enclosing,
                        ))
                    if cc_mod is not None:
                        for name in sorted(set(statics) & sc_params - key_fields):
                            findings.append(Finding(
                                module.relpath, site.lineno, "cache-key-drift",
                                f"static arg {name!r} is a solve_callable "
                                "parameter but absent from the compile-cache "
                                "key tuple — distinct configs would share "
                                "one memoized executable "
                                f"({cc_mod.relpath})",
                                NAME, symbol=site.enclosing,
                            ))

            # per-call jit construction
            if site.enclosing:
                if not _is_memoized(fn_index.get(site.enclosing), imports):
                    findings.append(Finding(
                        module.relpath, site.lineno, "uncached-jit",
                        "jax.jit constructed per call inside "
                        f"{site.enclosing!r}: each call gets a fresh wrapper "
                        "with an empty jit cache and retraces — memoize the "
                        "builder (functools.lru_cache) or hoist to module "
                        "scope",
                        NAME, symbol=site.enclosing,
                    ))

            # record module-level wrapper assignments for call-site checks
            if statics and site.decorated is None and not site.enclosing:
                parent = _assign_name_for(module.tree, site)
                if parent:
                    wrappers[f"{module.name}.{parent}"] = (statics, target_params)
            elif statics and site.decorated is not None:
                qual = getattr(site.decorated, "name", "")
                if qual and not site.enclosing:
                    wrappers[f"{module.name}.{qual}"] = (statics, target_params)

        # shard_map sites (the mesh dispatch layer, docs/KERNEL_PERF.md
        # "Layer 5"): same per-call-construction hazard as jax.jit, plus the
        # mesh-keying rule — a memoized builder whose shard_map captures a
        # mesh that does NOT derive from the builder's parameters silently
        # shares one executable across mesh topologies (the sharded twin of
        # cache-key-drift)
        for site in find_shard_map_sites(module):
            if site.enclosing:
                enclosing_fn = fn_index.get(site.enclosing)
                memoized = _is_memoized(enclosing_fn, imports)
                if not memoized:
                    findings.append(Finding(
                        module.relpath, site.lineno, "uncached-jit",
                        "shard_map constructed per call inside "
                        f"{site.enclosing!r}: each call builds a fresh "
                        "sharded wrapper with an empty jit cache and "
                        "retraces — memoize the builder "
                        "(functools.lru_cache) or hoist to module scope",
                        NAME, symbol=site.enclosing,
                    ))
                else:
                    mesh_expr = site.kwargs.get("mesh")
                    if mesh_expr is not None and not _mesh_derives_from_params(
                        mesh_expr, enclosing_fn
                    ):
                        findings.append(Finding(
                            module.relpath, site.lineno, "unkeyed-mesh-static",
                            "shard_map mesh inside memoized builder "
                            f"{site.enclosing!r} does not derive from the "
                            "builder's parameters — distinct mesh topologies "
                            "would share one cached executable; thread the "
                            "topology through the cache key (e.g. "
                            "mesh_for(mesh_axes))",
                            NAME, symbol=site.enclosing,
                        ))

    # unhashable literals at call sites of known jitted wrappers
    for module in project.package_modules:
        imports = import_map(module.tree)
        for node in ast.walk(module.tree):
            if not isinstance(node, ast.Call):
                continue
            root = resolve_call_root(node.func, imports)
            if root is None:
                continue
            hit = wrappers.get(root)
            if hit is None and "." not in root:
                hit = wrappers.get(f"{module.name}.{root}")
            if hit is None:
                continue
            statics, target_params = hit
            for kw in node.keywords:
                if kw.arg in statics and isinstance(kw.value, _MUTABLE_LITERALS):
                    findings.append(Finding(
                        module.relpath, node.lineno, "unhashable-static",
                        f"static arg {kw.arg!r} receives a mutable literal "
                        f"({type(kw.value).__name__.lower()}); jit raises "
                        "'unhashable type' — pass a tuple / frozen value",
                        NAME,
                    ))
            for i, arg in enumerate(node.args):
                if i < len(target_params) and target_params[i] in statics and (
                    isinstance(arg, _MUTABLE_LITERALS)
                ):
                    findings.append(Finding(
                        module.relpath, node.lineno, "unhashable-static",
                        f"static arg {target_params[i]!r} receives a mutable "
                        "literal; jit raises 'unhashable type' — pass a "
                        "tuple / frozen value",
                        NAME,
                    ))
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def _assign_name_for(tree: ast.Module, site: JitSite) -> Optional[str]:
    """Name a module-level ``X = jax.jit(...)`` / ``X = partial(jax.jit,
    ...)(...)`` assignment binds, when the site is such a value."""
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        if not isinstance(node.targets[0], ast.Name):
            continue
        for sub in ast.walk(node.value):
            if sub is site.jit_call or (
                getattr(sub, "lineno", None) == site.lineno
                and isinstance(sub, ast.Call)
                and sub is node.value
            ):
                return node.targets[0].id
    return None
