"""rwlint core: the rule registry, the parsed-package model, and the
suppression-pragma machinery.

Why a framework and not five more greps: the invariants this package
guards (one dispatch per fused epoch, every frame through the
chaos-injectable wire boundary, placement mutated only via the scaling
plane, durable IO only behind the retry wrapper) are *semantic* — they
are statements about call expressions, import aliases, and
reachability, not about byte patterns. A grep false-positives on a
docstring that *mentions* ``PermitChannel(`` and false-negatives on
``from ..stream.dispatch import PermitChannel as PC``; an AST rule with
alias resolution gets both right. See docs/static-analysis.md.

Model
-----
``Module``    — one parsed source file: AST, import map, suppressions.
``Package``   — every module under the package root, plus shared lazy
                analyses (export canonicalisation, the call graph) that
                individual rules request through ``Package.shared``.
``Rule``      — a named check. ``check(package)`` yields ``Finding``s;
                the driver filters them through inline suppressions.

Suppressions
------------
``# rwlint: allow(rule): reason`` on the flagged line (or alone on the
line directly above it) suppresses that rule there. The reason is
MANDATORY — an allow without a justification is itself a finding
(rule ``pragma``), because an unexplained exemption is how invariants
rot. ``# rwlint: allow-file(rule): reason`` anywhere in a file exempts
the whole file. ``allow(*)`` suppresses every rule on that line.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import re
import tokenize
from pathlib import Path
from typing import Callable, Dict, Iterable, Iterator, List, Optional, Tuple

__all__ = [
    "Finding", "Module", "Package", "Rule", "RULES", "register",
    "load_package", "run_rules", "all_rules",
]

_PRAGMA_RE = re.compile(
    r"#\s*rwlint:\s*(allow|allow-file)\(([^)]*)\)\s*(?::\s*(.*))?$")

#: Every rule target is expressed against this package name. Module
#: qualnames are normalised to it regardless of what directory the
#: linted tree happens to be rooted at (a fixture copy, a vendored
#: checkout), so rooting the tree at ``/tmp/copy`` cannot silently
#: disable every boundary rule.
CANONICAL_PKG = "risingwave_tpu"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation, anchored to a source location."""
    rule: str
    path: str          # package-root-relative posix path
    line: int
    col: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: [{self.rule}] " \
               f"{self.message}"

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


class _Pragma:
    __slots__ = ("rules", "reason", "line", "file_wide")

    def __init__(self, rules: Tuple[str, ...], reason: str, line: int,
                 file_wide: bool):
        self.rules = rules
        self.reason = reason
        self.line = line
        self.file_wide = file_wide

    def covers(self, rule: str) -> bool:
        return "*" in self.rules or rule in self.rules


class Module:
    """One parsed source file plus everything rules need per-file."""

    def __init__(self, package: "Package", abspath: Path, rel: str):
        self.package = package
        self.abspath = abspath
        self.rel = rel                      # posix, relative to pkg root
        self.source = abspath.read_text(encoding="utf-8")
        self.lines = self.source.splitlines()
        self.tree = ast.parse(self.source, filename=str(abspath))
        #: every node of the tree in ``ast.walk`` order, walked once: a
        #: dozen rules each walk every module, and the walk dominated
        self._nodes: Optional[List[ast.AST]] = None
        # dotted module qualname: pkgname.sub.mod (pkgname/__init__.py
        # -> pkgname), with the root segment pinned to CANONICAL_PKG —
        # rule targets are written against it, not the root dir name
        parts = [CANONICAL_PKG] + rel[:-3].split("/")
        if parts[-1] == "__init__":
            parts.pop()
        self.qualname = ".".join(parts)
        self.pragmas: List[_Pragma] = []
        self.pragma_findings: List[Finding] = []
        self._scan_pragmas()
        from .imports import ImportMap
        self.imports = ImportMap(self)

    # -- suppressions -----------------------------------------------------

    def _scan_pragmas(self) -> None:
        # Pragmas live in COMMENT tokens only: a docstring that *shows*
        # the pragma syntax (docs, this module's own header) must never
        # register a live suppression, so we tokenize rather than
        # regex raw lines.
        for i, text in self._comment_tokens():
            m = _PRAGMA_RE.search(text)
            if not m:
                if "rwlint: allow" in text:
                    self.pragma_findings.append(Finding(
                        "pragma", self.rel, i, 0,
                        "malformed rwlint pragma (expected "
                        "'# rwlint: allow(rule): reason')"))
                continue
            kind, rules_s, reason = m.group(1), m.group(2), m.group(3)
            rules = tuple(r.strip() for r in rules_s.split(",") if r.strip())
            if not rules:
                self.pragma_findings.append(Finding(
                    "pragma", self.rel, i, 0,
                    "rwlint allow pragma names no rule"))
                continue
            if not (reason or "").strip():
                self.pragma_findings.append(Finding(
                    "pragma", self.rel, i, 0,
                    f"rwlint allow({rules_s}) without a reason — every "
                    "exemption must carry its justification"))
                continue
            self.pragmas.append(_Pragma(rules, reason.strip(), i,
                                        kind == "allow-file"))

    def _comment_tokens(self) -> Iterator[Tuple[int, str]]:
        """(lineno, text) for every ``#`` comment in the source."""
        try:
            for tok in tokenize.generate_tokens(
                    io.StringIO(self.source).readline):
                if tok.type == tokenize.COMMENT:
                    yield tok.start[0], tok.string
        except tokenize.TokenError:
            # ast.parse already succeeded, so this is unreachable in
            # practice; fail open (no pragmas) rather than crash.
            return

    def suppressed(self, rule: str, line: int) -> bool:
        for p in self.pragmas:
            if not p.covers(rule):
                continue
            if p.file_wide:
                return True
            # pragma on the flagged line, or alone on the line above it
            if p.line == line:
                return True
            if p.line == line - 1:
                stripped = self.lines[p.line - 1].lstrip()
                if stripped.startswith("#"):
                    return True
        return False

    # -- AST helpers ------------------------------------------------------

    def walk(self) -> List[ast.AST]:
        if self._nodes is None:
            self._nodes = list(ast.walk(self.tree))
        return self._nodes

    def docstring_linenos(self) -> "set[int]":
        """Line numbers covered by docstrings (module/class/function) —
        the classic grep false-positive surface."""
        covered: "set[int]" = set()
        for node in self.walk():
            if isinstance(node, (ast.Module, ast.ClassDef,
                                 ast.FunctionDef, ast.AsyncFunctionDef)):
                body = node.body
                if body and isinstance(body[0], ast.Expr) and \
                        isinstance(body[0].value, ast.Constant) and \
                        isinstance(body[0].value.value, str):
                    d = body[0].value
                    covered.update(range(d.lineno,
                                         (d.end_lineno or d.lineno) + 1))
        return covered


class Package:
    """Every module under one package root, plus shared lazy analyses."""

    def __init__(self, root: Path):
        self.root = root.resolve()
        self.name = self.root.name
        self.modules: Dict[str, Module] = {}
        self.parse_errors: List[Finding] = []
        for p in sorted(self.root.rglob("*.py")):
            if "__pycache__" in p.parts:
                continue
            rel = p.relative_to(self.root).as_posix()
            try:
                self.modules[rel] = Module(self, p, rel)
            except SyntaxError as e:
                self.parse_errors.append(Finding(
                    "parse", rel, e.lineno or 0, e.offset or 0,
                    f"syntax error: {e.msg}"))
        self._shared: Dict[str, object] = {}
        self._exports: Optional[Dict[str, Dict[str, str]]] = None

    def module(self, rel: str) -> Optional[Module]:
        return self.modules.get(rel)

    def shared(self, key: str, build: Callable[["Package"], object]):
        """Memoize a package-wide analysis (e.g. the call graph) so
        several rules can share one construction."""
        if key not in self._shared:
            self._shared[key] = build(self)
        return self._shared[key]

    # -- export canonicalisation -----------------------------------------

    def _export_table(self) -> Dict[str, Dict[str, str]]:
        """module qualname -> exported name -> source qualified name.

        Covers both definitions (``name`` defined in ``mod`` maps to
        ``mod.name``) and re-exports (``from .dispatch import
        PermitChannel`` in ``stream/__init__.py`` maps
        ``stream.PermitChannel`` back to ``stream.dispatch
        .PermitChannel``), so a rule target stays matchable through any
        alias chain."""
        if self._exports is None:
            table: Dict[str, Dict[str, str]] = {}
            for mod in self.modules.values():
                entry: Dict[str, str] = {}
                for node in mod.tree.body:
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef,
                                         ast.ClassDef)):
                        entry[node.name] = f"{mod.qualname}.{node.name}"
                    elif isinstance(node, ast.Assign):
                        for t in node.targets:
                            if isinstance(t, ast.Name):
                                entry[t.id] = f"{mod.qualname}.{t.id}"
                    elif isinstance(node, ast.AnnAssign) and \
                            isinstance(node.target, ast.Name):
                        entry[node.target.id] = \
                            f"{mod.qualname}.{node.target.id}"
                # imports may shadow/define exported names too
                for name, qn in mod.imports.aliases.items():
                    entry.setdefault(name, qn)
                table[mod.qualname] = entry
            self._exports = table
        return self._exports

    def canonical(self, qualname: Optional[str]) -> Optional[str]:
        """Follow re-export chains to the defining module's name."""
        if qualname is None:
            return None
        table = self._export_table()
        seen = set()
        while qualname not in seen:
            seen.add(qualname)
            head, _, attr = qualname.rpartition(".")
            nxt = table.get(head, {}).get(attr)
            if nxt is None or nxt == qualname:
                break
            qualname = nxt
        return qualname


# -- rule registry --------------------------------------------------------


class Rule:
    """Base class: subclass, set the class attrs, implement check()."""

    #: registry key, used in pragmas and --rule filters
    name: str = ""
    #: one-line summary, shown by --list-rules
    title: str = ""
    #: label used for the per-rule CI OK line (defaults to name)
    ci_label: str = ""
    #: long-form rationale, shown by --explain (markdown-ish)
    doc: str = ""

    def check(self, package: Package) -> Iterator[Finding]:
        raise NotImplementedError


RULES: Dict[str, Rule] = {}


def register(cls):
    """Class decorator adding a rule to the global registry."""
    inst = cls()
    assert inst.name and inst.name not in RULES, inst.name
    RULES[inst.name] = inst
    return cls


def all_rules() -> List[Rule]:
    # import the rule modules for their registration side effect
    from . import (  # noqa: F401
        rules_boundary, rules_purity, rules_state, rules_tick,
    )
    return [RULES[k] for k in sorted(RULES)]


# -- driver ---------------------------------------------------------------


def load_package(root) -> Package:
    return Package(Path(root))


def run_rules(package: Package,
              rules: Optional[Iterable[Rule]] = None
              ) -> Tuple[List[Finding], Dict[str, int]]:
    """Run rules over the package; returns (findings, per-rule counts).

    Findings already filtered through inline suppressions; pragma
    syntax errors and file parse errors ride along under the ``pragma``
    / ``parse`` pseudo-rules so they can never be silently ignored.
    """
    if rules is None:
        rules = all_rules()
    findings: List[Finding] = list(package.parse_errors)
    counts: Dict[str, int] = {}
    for mod in package.modules.values():
        findings.extend(mod.pragma_findings)
    for rule in rules:
        counts[rule.name] = 0
        for f in rule.check(package):
            mod = package.module(f.path)
            if mod is not None and mod.suppressed(f.rule, f.line):
                continue
            findings.append(f)
            counts[rule.name] += 1
    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule))
    return findings, counts
