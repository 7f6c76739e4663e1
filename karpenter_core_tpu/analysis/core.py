"""Framework core: source loading, the finding model, and the baseline.

Everything here is stdlib-only (``ast`` + file IO): the analyses parse the
tree, they never import it, so a pass can run against any directory —
including the temp trees the unit tests seed with known-bad fragments.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple


@dataclass(frozen=True)
class Finding:
    """One analysis result, renderable as ``file:line: pass/rule: detail``."""

    path: str  # root-relative, forward slashes
    line: int
    rule: str
    detail: str
    pass_name: str = ""
    symbol: str = ""  # enclosing function/class qualname when known

    def render(self) -> str:
        where = f" [{self.symbol}]" if self.symbol else ""
        prefix = f"{self.pass_name}/{self.rule}" if self.pass_name else self.rule
        return f"{self.path}:{self.line}: {prefix}: {self.detail}{where}"


@dataclass
class SourceModule:
    """One parsed source file."""

    name: str  # dotted module name ("" for non-package files like __graft_entry__.py)
    path: Path
    relpath: str  # root-relative, forward slashes
    source: str
    tree: ast.Module
    lines: List[str] = field(default_factory=list)

    @property
    def in_package(self) -> bool:
        return bool(self.name)


class Project:
    """The loaded analysis target: a package tree plus auxiliary roots.

    ``root`` is the repository root; ``package`` the importable package
    directory under it.  ``extra_roots`` (tests/, tools/, top-level scripts)
    participate only in passes that opt into ``all_modules`` — the
    call-graph and lock passes look at ``package_modules`` alone.
    """

    DEFAULT_EXTRA_ROOTS = ("tests", "tools", "__graft_entry__.py")

    def __init__(
        self,
        root: Path,
        package: str = "karpenter_core_tpu",
        extra_roots: Optional[Iterable[str]] = None,
    ) -> None:
        self.root = Path(root)
        self.package = package
        self.package_modules: List[SourceModule] = []
        self.extra_modules: List[SourceModule] = []
        self.errors: List[Finding] = []  # syntax errors surface as findings
        self._by_name: Dict[str, SourceModule] = {}

        pkg_dir = self.root / package
        if pkg_dir.is_dir():
            for path in sorted(pkg_dir.rglob("*.py")):
                if "__pycache__" in path.parts:
                    continue
                mod = self._load(path, self._dotted_name(path))
                if mod is not None:
                    self.package_modules.append(mod)
                    self._by_name[mod.name] = mod
        extras = (
            self.DEFAULT_EXTRA_ROOTS if extra_roots is None else tuple(extra_roots)
        )
        for rel in extras:
            p = self.root / rel
            if p.is_file():
                mod = self._load(p, "")
                if mod is not None:
                    self.extra_modules.append(mod)
            elif p.is_dir():
                for path in sorted(p.rglob("*.py")):
                    if "__pycache__" in path.parts:
                        continue
                    mod = self._load(path, "")
                    if mod is not None:
                        self.extra_modules.append(mod)

    @property
    def all_modules(self) -> List[SourceModule]:
        return self.package_modules + self.extra_modules

    def get(self, dotted: str) -> Optional[SourceModule]:
        return self._by_name.get(dotted)

    def relative(self, path: Path) -> str:
        try:
            return path.relative_to(self.root).as_posix()
        except ValueError:
            return path.as_posix()

    def _dotted_name(self, path: Path) -> str:
        rel = path.relative_to(self.root).with_suffix("")
        parts = list(rel.parts)
        if parts[-1] == "__init__":
            parts = parts[:-1]
        return ".".join(parts)

    def _load(self, path: Path, name: str) -> Optional[SourceModule]:
        try:
            source = path.read_text()
        except OSError as e:
            self.errors.append(
                Finding(self.relative(path), 0, "read-error", str(e), "loader")
            )
            return None
        try:
            tree = ast.parse(source, filename=str(path))
        except SyntaxError as e:
            self.errors.append(
                Finding(
                    self.relative(path), e.lineno or 0, "syntax-error",
                    e.msg or "invalid syntax", "loader",
                )
            )
            return None
        return SourceModule(
            name=name, path=path, relpath=self.relative(path),
            source=source, tree=tree, lines=source.splitlines(),
        )


# -- baseline -----------------------------------------------------------------


class BaselineError(Exception):
    """Malformed baseline file (policy violations are hard errors: an
    undocumented suppression must not silently disable a gate)."""


_KV_RE = re.compile(r"^([A-Za-z_][A-Za-z0-9_-]*)\s*=\s*(.+?)\s*$")


def _parse_toml_value(raw: str, path: str, lineno: int):
    if raw.startswith('"') and raw.endswith('"') and len(raw) >= 2:
        return raw[1:-1]
    if raw in ("true", "false"):
        return raw == "true"
    try:
        return int(raw)
    except ValueError:
        raise BaselineError(
            f"{path}:{lineno}: unsupported TOML value {raw!r} "
            "(this parser takes strings, integers, and booleans)"
        )


def parse_mini_toml(text: str, path: str = "<baseline>") -> List[dict]:
    """Parse the ``[[suppress]]`` array-of-tables subset of TOML used by the
    baseline file (Python 3.10 has no ``tomllib``).  Inline comments are
    supported outside strings."""
    entries: List[dict] = []
    current: Optional[dict] = None
    for lineno, line in enumerate(text.splitlines(), 1):
        stripped = line.strip()
        if not stripped or stripped.startswith("#"):
            continue
        if stripped == "[[suppress]]":
            current = {"_line": lineno}
            entries.append(current)
            continue
        if stripped.startswith("["):
            raise BaselineError(
                f"{path}:{lineno}: only [[suppress]] tables are supported"
            )
        m = _KV_RE.match(stripped)
        if m is None:
            raise BaselineError(f"{path}:{lineno}: unparseable line {stripped!r}")
        if current is None:
            raise BaselineError(
                f"{path}:{lineno}: key outside a [[suppress]] table"
            )
        key, raw = m.group(1), m.group(2)
        if raw.startswith('"'):
            # strip a trailing comment after the closing quote (values do
            # not contain escaped quotes in this subset)
            end = raw.find('"', 1)
            if end != -1:
                rest = raw[end + 1:].strip()
                if rest and not rest.startswith("#"):
                    raise BaselineError(
                        f"{path}:{lineno}: trailing characters after string "
                        f"value: {rest!r}"
                    )
                raw = raw[: end + 1]
        else:
            raw = raw.split("#", 1)[0].strip()
        current[key] = _parse_toml_value(raw, path, lineno)
    return entries


class Baseline:
    """Checked-in suppression list.  Every entry names the pass/rule/file it
    covers and MUST carry a ``reason`` — the policy is documented false
    positives, not silenced true positives (docs/ANALYSIS.md)."""

    MATCH_KEYS = ("pass", "rule", "file", "line", "symbol", "contains")

    def __init__(self, entries: List[dict], path: str = "<baseline>") -> None:
        self.path = path
        self.entries = entries
        self.hits = [0] * len(entries)
        for e in entries:
            if not str(e.get("reason", "")).strip():
                raise BaselineError(
                    f"{path}:{e.get('_line', 0)}: suppression without a reason "
                    "(every baseline entry must document why it is a false "
                    "positive or an accepted deviation)"
                )
            unknown = set(e) - set(self.MATCH_KEYS) - {"reason", "_line"}
            if unknown:
                raise BaselineError(
                    f"{path}:{e.get('_line', 0)}: unknown key(s) "
                    f"{sorted(unknown)}"
                )

    @classmethod
    def load(cls, path: Path) -> "Baseline":
        return cls(parse_mini_toml(path.read_text(), str(path)), str(path))

    @classmethod
    def empty(cls) -> "Baseline":
        return cls([], "<empty>")

    def match(self, finding: Finding) -> Optional[str]:
        """The matching entry's reason, or None when the finding stands."""
        for i, e in enumerate(self.entries):
            if e.get("pass") not in (None, finding.pass_name):
                continue
            if e.get("rule") not in (None, finding.rule):
                continue
            if e.get("file") not in (None, finding.path):
                continue
            if e.get("line") not in (None, finding.line):
                continue
            if e.get("symbol") not in (None, finding.symbol):
                continue
            contains = e.get("contains")
            if contains is not None and contains not in finding.detail:
                continue
            self.hits[i] += 1
            return str(e["reason"])
        return None

    def unused(self) -> List[dict]:
        return [e for e, n in zip(self.entries, self.hits) if n == 0]


def apply_baseline(
    findings: List[Finding], baseline: Baseline
) -> Tuple[List[Finding], List[Tuple[Finding, str]]]:
    """(kept, [(suppressed, reason)])."""
    kept: List[Finding] = []
    suppressed: List[Tuple[Finding, str]] = []
    for f in findings:
        reason = baseline.match(f)
        if reason is None:
            kept.append(f)
        else:
            suppressed.append((f, reason))
    return kept, suppressed


# -- shared ast helpers -------------------------------------------------------


def dotted(node: ast.expr) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def import_map(tree: ast.Module) -> Dict[str, str]:
    """local name -> dotted target for every top-level-visible import.
    ``import a.b as c`` maps c->a.b; ``from a import b`` maps b->a.b;
    ``import a.b`` maps a->a (the bound name is the root package)."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.asname:
                    out[alias.asname] = alias.name
                else:
                    out[alias.name.split(".")[0]] = alias.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom):
            if node.module is None or node.level:
                continue  # relative imports are not used in this repo
            for alias in node.names:
                if alias.name == "*":
                    continue
                out[alias.asname or alias.name] = f"{node.module}.{alias.name}"
    return out


def resolve_call_root(call_func: ast.expr, imports: Dict[str, str]) -> Optional[str]:
    """Fully-resolved dotted name of a call target, through the import map:
    ``mask_ops.compatible`` -> ``karpenter_core_tpu.ops.masks.compatible``."""
    name = dotted(call_func)
    if name is None:
        return None
    head, _, rest = name.partition(".")
    target = imports.get(head)
    if target is None:
        return name
    return f"{target}.{rest}" if rest else target
