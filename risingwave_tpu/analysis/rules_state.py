"""State-protocol rules: seqlock-discipline and failpoint-honesty.

seqlock-discipline guards the serving plane's lock-free read path
(PR 8): optimistic readers in frontend/serving.py accept a scan only
when the same EVEN ``_data_version`` spans it, which is sound only if
every writer (a) bumps the version through the two bracket methods and
(b) leaves the odd section on EVERY exit path. A stray increment, or an
``_enter_mutation()`` whose exit is not in a ``finally``, breaks reader
correctness only under races/exceptions — exactly the bugs tests miss.

failpoint-honesty moves the declared⊇executed registry check from
test-time (the old TestFailpointRegistry grep in tests/test_net_faults
.py) to lint-time, and tightens it to declared==executed: a site added
in code but not declared is invisible to the crash-point sweep; a
declared site with no call site is sweep time wasted on a no-op.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from .core import Finding, Module, Package, Rule, register

PKG = "risingwave_tpu"


@register
class SeqlockDiscipline(Rule):
    name = "seqlock-discipline"
    title = "Session seqlock mutated only via the bracket methods"
    ci_label = "seqlock-discipline"
    doc = """The data-version seqlock (frontend/session.py): EVEN =
stores quiescent, ODD = a mutation in flight. Serving readers
(frontend/serving.py) spin on it instead of taking the API lock. The
rule enforces, allowlist-driven: (1) ``_data_version`` /
``_mutation_depth`` are written ONLY inside __init__ /
_enter_mutation / _exit_mutation of Session; (2) any method calling
``_enter_mutation()`` pairs every call with an ``_exit_mutation()``
that sits in a ``finally`` block — an exception escaping the odd
section would otherwise wedge every optimistic reader forever; (3) no
module outside frontend/session.py writes either attribute."""

    SESSION = "frontend/session.py"
    GUARDED = {"_data_version", "_mutation_depth"}
    ALLOWED_METHODS = {"__init__", "_enter_mutation", "_exit_mutation"}

    def check(self, package: Package) -> Iterator[Finding]:
        for rel, mod in package.modules.items():
            yield from self._check_writes(mod, rel)
        sess = package.module(self.SESSION)
        if sess is not None:
            yield from self._check_balance(sess)

    # (1) + (3): direct writes to the seqlock words
    def _check_writes(self, mod: Module, rel: str) -> Iterator[Finding]:
        for node in mod.walk():
            targets: List[ast.expr] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            for t in targets:
                if not (isinstance(t, ast.Attribute) and
                        t.attr in self.GUARDED):
                    continue
                meth = self._method_of(mod, node)
                if rel == self.SESSION and meth in self.ALLOWED_METHODS:
                    continue
                yield Finding(
                    self.name, mod.rel, node.lineno, node.col_offset,
                    f"write to seqlock word .{t.attr} outside the "
                    "bracket methods (_enter_mutation/_exit_mutation) "
                    "— readers infer quiescence from this word")

    # (2): every enter is covered by a finally'd exit. Counting
    # enters/exits per function is not enough — a balanced count says
    # nothing about WHICH finally protects WHICH enter, so a stray
    # try/finally elsewhere in the same method could launder an
    # unprotected odd section. Each enter is checked structurally: it
    # must sit inside a try whose finally exits, or be the statement
    # immediately before one (the canonical
    # ``_enter_mutation(); try: ... finally: _exit_mutation()`` idiom).
    def _check_balance(self, mod: Module) -> Iterator[Finding]:
        for node in mod.walk():
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if node.name in self.ALLOWED_METHODS:
                continue
            enters = self._bracket_calls(node, "_enter_mutation")
            if not enters:
                continue
            parents = {child: parent for parent in ast.walk(node)
                       for child in ast.iter_child_nodes(parent)}
            for call in enters:
                if self._finally_protected(node, call, parents):
                    continue
                yield Finding(
                    self.name, mod.rel, call.lineno, call.col_offset,
                    f"{node.name}: _enter_mutation() not covered by an "
                    "_exit_mutation() in a finally (enclosing it or "
                    "immediately following it) — an exception escaping "
                    "the odd section leaves _data_version odd and every "
                    "optimistic reader spins forever")

    @classmethod
    def _finally_protected(cls, fn: ast.AST, call: ast.Call,
                           parents: Dict[ast.AST, ast.AST]) -> bool:
        # (a) the enter sits inside the BODY of a try whose finally
        # exits (finalbody/handlers/orelse don't count: an enter there
        # runs after/outside the protection)
        node: ast.AST = call
        stmt: Optional[ast.stmt] = None
        while node is not fn:
            parent = parents.get(node)
            if parent is None:
                break
            if stmt is None and isinstance(node, ast.stmt):
                stmt = node
            if isinstance(parent, ast.Try) and \
                    any(node is s for s in parent.body) and \
                    cls._exits_in(parent.finalbody):
                return True
            node = parent
        # (b) canonical idiom: the very next statement is a
        # try/finally that exits
        if stmt is None:
            return False
        holder = parents.get(stmt)
        for lst in cls._stmt_lists(holder):
            for i, s in enumerate(lst):
                if s is stmt:
                    nxt = lst[i + 1] if i + 1 < len(lst) else None
                    return isinstance(nxt, ast.Try) and \
                        cls._exits_in(nxt.finalbody)
        return False

    @staticmethod
    def _stmt_lists(holder: Optional[ast.AST]) -> List[List[ast.stmt]]:
        if holder is None:
            return []
        lists = []
        for attr in ("body", "orelse", "finalbody"):
            val = getattr(holder, attr, None)
            if isinstance(val, list):
                lists.append(val)
        return lists

    @classmethod
    def _exits_in(cls, stmts: List[ast.stmt]) -> bool:
        return any(cls._bracket_calls(s, "_exit_mutation")
                   for s in stmts)

    @staticmethod
    def _bracket_calls(fn: ast.AST, name: str) -> List[ast.Call]:
        out = []
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == name:
                out.append(node)
        return out

    @staticmethod
    def _method_of(mod: Module, node: ast.AST) -> Optional[str]:
        best: Optional[ast.AST] = None
        for fn in mod.walk():
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and fn.lineno <= node.lineno <= \
                    (fn.end_lineno or fn.lineno):
                if best is None or fn.lineno > best.lineno:
                    best = fn
        return best.name if best is not None else None


@register
class FailpointHonesty(Rule):
    name = "failpoint-honesty"
    title = "fail_point() sites == the declared registry"
    ci_label = "failpoint-honesty"
    doc = """The crash-point sweep (sim.py --sweep) and the chaos
plane's coverage claims iterate ``DECLARED_SITES`` in
common/failpoint.py; the sweep only proves what the registry names.
This rule equates the declared set with the set of ``fail_point("...")``
string literals in the package, both directions, at lint time: an
undeclared executed site is chaos coverage silently lost, a declared
never-executed site is a sweep slot that tests nothing. Dynamic
(non-literal) site names are flagged too — they defeat the whole
static accounting. Replaces the test-time regex check that lived in
tests/test_net_faults.py."""

    FAILPOINT_MOD = "common/failpoint.py"
    DECL_NAMES = ("DECLARED_SITES", "KNOWN_SITES")
    CALL = f"{PKG}.common.failpoint.fail_point"
    REGISTER = f"{PKG}.common.failpoint.register_site"

    def declared(self, package: Package
                 ) -> Tuple[Set[str], int, Optional[Module]]:
        mod = package.module(self.FAILPOINT_MOD)
        if mod is None:
            return set(), 0, None
        for node in mod.tree.body:
            names: List[str] = []
            if isinstance(node, ast.Assign):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                value = node.value
            elif isinstance(node, ast.AnnAssign) and \
                    isinstance(node.target, ast.Name):
                names, value = [node.target.id], node.value
            else:
                continue
            if not any(n in self.DECL_NAMES for n in names) or \
                    value is None:
                continue
            sites = {c.value for c in ast.walk(value)
                     if isinstance(c, ast.Constant) and
                     isinstance(c.value, str)}
            return sites, node.lineno, mod
        return set(), 0, mod

    def executed(self, package: Package
                 ) -> Tuple[Dict[str, Tuple[Module, ast.Call]],
                            List[Tuple[Module, ast.Call]]]:
        sites: Dict[str, Tuple[Module, ast.Call]] = {}
        dynamic: List[Tuple[Module, ast.Call]] = []
        for rel, mod in package.modules.items():
            if rel == self.FAILPOINT_MOD:
                continue
            for node in mod.walk():
                if not isinstance(node, ast.Call):
                    continue
                qn = package.canonical(
                    mod.imports.resolve_or_local(node.func))
                if qn not in (self.CALL, self.REGISTER):
                    continue
                # keyword form fail_point(name="x") counts the same as
                # positional — a site must not dodge the accounting by
                # calling convention
                values = list(node.args) + \
                    [kw.value for kw in node.keywords]
                for arg in values:
                    if isinstance(arg, ast.Constant) and \
                            isinstance(arg.value, str):
                        sites.setdefault(arg.value, (mod, node))
                    else:
                        dynamic.append((mod, node))
        return sites, dynamic

    def check(self, package: Package) -> Iterator[Finding]:
        declared, decl_line, decl_mod = self.declared(package)
        executed, dynamic = self.executed(package)
        if decl_mod is None:
            return
        if not declared:
            yield Finding(self.name, decl_mod.rel, 1, 0,
                          "no DECLARED_SITES/KNOWN_SITES literal found "
                          "in common/failpoint.py")
            return
        for mod, call in dynamic:
            yield Finding(
                self.name, mod.rel, call.lineno, call.col_offset,
                "non-literal failpoint site name — the crash-point "
                "sweep cannot account for dynamic sites")
        for site in sorted(set(executed) - declared):
            mod, call = executed[site]
            yield Finding(
                self.name, mod.rel, call.lineno, call.col_offset,
                f'failpoint site "{site}" is not in DECLARED_SITES '
                "(common/failpoint.py) — the crash-point sweep will "
                "never kill here")
        for site in sorted(declared - set(executed)):
            yield Finding(
                self.name, decl_mod.rel, decl_line, 0,
                f'declared failpoint site "{site}" has no '
                "fail_point() call site — stale registry entry wastes "
                "a sweep slot")
