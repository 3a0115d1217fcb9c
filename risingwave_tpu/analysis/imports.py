"""Import/alias resolution — the piece that makes AST lints beat grep.

``from ..stream.dispatch import PermitChannel as PC`` binds the local
name ``PC`` to the qualified name
``risingwave_tpu.stream.dispatch.PermitChannel``; a grep for
``PermitChannel(`` never sees the ``PC(...)`` call, this resolver does.
Conversely a docstring that *mentions* the class never produces a
``Call`` node, so the alias-aware rule stays quiet where grep fired.

Resolution is purely static and per-module: an ``ImportMap`` maps local
names to dotted qualified names, and ``resolve()`` flattens a
``Name``/``Attribute`` chain through it. Cross-module re-export chains
are then collapsed by ``Package.canonical`` (core.py).
"""

from __future__ import annotations

import ast
from typing import Dict, Optional

__all__ = ["ImportMap", "dotted"]


def dotted(node: ast.AST) -> Optional[str]:
    """Flatten ``a.b.c`` Name/Attribute chains to ``"a.b.c"``."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


class ImportMap:
    """Local name -> fully qualified dotted name, for one module."""

    def __init__(self, module) -> None:
        self.module = module
        self.aliases: Dict[str, str] = {}
        # the module's own package ("a.b.c" -> package "a.b" for a
        # plain module, "a.b.c" itself for a package __init__)
        qn = module.qualname
        if module.rel.endswith("__init__.py"):
            self._pkg = qn
        else:
            self._pkg = qn.rpartition(".")[0]
        self._collect(module.walk())

    def _collect(self, nodes) -> None:
        for node in nodes:
            if isinstance(node, ast.Import):
                for a in node.names:
                    local = a.asname or a.name.split(".")[0]
                    target = a.name if a.asname else a.name.split(".")[0]
                    self.aliases[local] = target
            elif isinstance(node, ast.ImportFrom):
                base = self._resolve_from(node)
                if base is None:
                    continue
                for a in node.names:
                    if a.name == "*":
                        continue
                    local = a.asname or a.name
                    self.aliases[local] = f"{base}.{a.name}" if base \
                        else a.name

    def _resolve_from(self, node: ast.ImportFrom) -> Optional[str]:
        if node.level == 0:
            return node.module or ""
        # relative import: level 1 = current package, 2 = parent, ...
        parts = self._pkg.split(".") if self._pkg else []
        up = node.level - 1
        if up > len(parts):
            return None
        base_parts = parts[:len(parts) - up] if up else parts
        if node.module:
            base_parts = base_parts + node.module.split(".")
        return ".".join(base_parts)

    def resolve(self, node: ast.AST) -> Optional[str]:
        """Qualified dotted name for a Name/Attribute chain, through
        this module's import aliases; ``None`` if the head is not an
        imported/module-level name (e.g. a local variable)."""
        d = dotted(node)
        if d is None:
            return None
        head, _, rest = d.partition(".")
        if head in self.aliases:
            base = self.aliases[head]
            return f"{base}.{rest}" if rest else base
        return None

    def resolve_or_local(self, node: ast.AST) -> Optional[str]:
        """Like resolve(), but a bare unimported head falls back to a
        name in the current module (module-level def/class/assign)."""
        qn = self.resolve(node)
        if qn is not None:
            return qn
        d = dotted(node)
        if d is None:
            return None
        return f"{self.module.qualname}.{d}"
