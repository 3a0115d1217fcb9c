"""Deep checkers no grep can express: dispatch-discipline and
trace-purity.

dispatch-discipline is the static twin of the runtime
``common/dispatch_count.py`` guard: the whole performance story of the
fused epochs (docs/performance.md) is ONE dispatch per epoch, and the
ways to silently break it are host↔device transfers
(``jax.device_get``, ``.item()``, ``np.asarray``, scalar coercion) or
a nested ``jax.jit`` inside a function reachable from the epoch-builder
registries. The runtime guard only sees paths a test happened to
execute; this rule covers the full static closure.

trace-purity guards determinism: a ``time.time()`` / ``random.*`` call
or a mutable default argument inside a jit/vmap/shard_map-traced
function is baked in at trace time — the replayable chaos plane and the
bit-exactness contracts (solo vs co-scheduled vs sharded) both rest on
traced code being pure.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set

from .callgraph import Func, FunctionIndex, build_index
from .core import Finding, Module, Package, Rule, register

PKG = "risingwave_tpu"

#: the epoch-builder registries the one-dispatch invariant hangs off
REGISTRIES = (
    ("ops/fused_epoch.py", "EPOCH_BUILDERS"),
    ("ops/fused_sharded.py", "SHARDED_EPOCH_BUILDERS"),
    ("ops/fused_hetero.py", "HETERO_EPOCH_BUILDERS"),
)

#: builders outside the registries that still own a one-dispatch
#: surface: the co-scheduled multi-job epochs (stream/coschedule.py
#: resolves them directly, not via a registry dict)
EXTRA_BUILDERS = (
    ("ops/fused_multi.py", "fused_multi_agg_epoch"),
    ("ops/fused_multi.py", "fused_multi_join_epoch"),
    ("ops/fused_multi.py", "build_group_epoch"),
)

_JIT_WRAPPERS = {"jax.jit", "jax.pmap"}
_TRACE_WRAPPERS = {
    "jax.jit", "jax.pmap", "jax.vmap",
    "jax.shard_map",
    "jax.experimental.pallas.pallas_call",
    f"{PKG}.parallel.sharded_agg.shard_map_compat",
}


def _callee_qn(package: Package, mod: Module,
               node: ast.Call) -> Optional[str]:
    return package.canonical(mod.imports.resolve(node.func))


def registry_builders(package: Package, index: FunctionIndex
                      ) -> Dict[str, Dict[str, Func]]:
    """Statically parse the two builder registries: registry name ->
    {surface key -> builder Func}. The acceptance contract is that
    this sees EXACTLY the entries the runtime dicts hold —
    tests/test_rwlint.py cross-checks it against the imported
    registries, so a builder added to the dict without lint coverage
    fails the tier-1 wiring test."""
    out: Dict[str, Dict[str, Func]] = {}
    for rel, reg_name in REGISTRIES:
        mod = package.module(rel)
        if mod is None:
            continue
        entry: Dict[str, Func] = {}
        for node in mod.tree.body:
            if not isinstance(node, ast.Assign) or \
                    not isinstance(node.value, ast.Dict):
                continue
            names = [t.id for t in node.targets
                     if isinstance(t, ast.Name)]
            if reg_name not in names:
                continue
            for k, v in zip(node.value.keys, node.value.values):
                if not isinstance(k, ast.Constant):
                    continue
                fn = index.lookup(mod.imports.resolve_or_local(v)) \
                    if v is not None else None
                if fn is not None:
                    entry[str(k.value)] = fn
        out[reg_name] = entry
    extra: Dict[str, Func] = {}
    for rel, name in EXTRA_BUILDERS:
        mod = package.module(rel)
        if mod is None:
            continue
        fn = index.by_qualname.get(f"{mod.qualname}.{name}")
        if fn is not None:
            extra[name] = fn
    out["COSCHEDULED_BUILDERS"] = extra
    return out


def _device_region(package: Package, index: FunctionIndex,
                   builders: List[Func]) -> Set[Func]:
    """Everything reachable from the builders, except the builder
    bodies themselves (they run at build time on the host and own the
    ONE legitimate ``jax.jit`` call), plus every ``lax.scan`` body in
    ops/ (scan bodies are traced even when a registry does not reach
    them yet)."""
    region = index.reachable(builders) - set(builders)
    for rel, mod in package.modules.items():
        if not rel.startswith("ops/"):
            continue
        for node in mod.walk():
            if isinstance(node, ast.Call) and \
                    _callee_qn(package, mod, node) == "jax.lax.scan" \
                    and node.args:
                owner = _enclosing_func(index, mod, node)
                if owner is None:
                    continue
                for fn in index.resolve_ref(owner, node.args[0]):
                    region |= index.reachable([fn])
    return region


def _enclosing_func(index: FunctionIndex, mod: Module,
                    node: ast.AST) -> Optional[Func]:
    best: Optional[Func] = None
    for fn in index.by_qualname.values():
        if fn.module is not mod:
            continue
        n = fn.node
        if n.lineno <= node.lineno <= (n.end_lineno or n.lineno):
            if best is None or n.lineno > best.node.lineno:
                best = fn
    return best


@register
class DispatchDiscipline(Rule):
    name = "dispatch-discipline"
    title = "no host transfer / nested jit reachable from epoch builders"
    ci_label = "dispatch-discipline"
    doc = """The fused-epoch contract (PRs 4/6/7, docs/performance.md)
is ONE XLA dispatch per epoch; the runtime dispatch_count guard checks
it on executed paths only. This rule walks the static closure of every
function reachable from EPOCH_BUILDERS / SHARDED_EPOCH_BUILDERS (plus
every lax.scan body in ops/) and flags the constructs that smuggle a
host round-trip or a second dispatch into the traced region:
``jax.device_get`` / ``jax.device_put``, ``.block_until_ready()``,
``np.asarray``, ``.item()``, ``int()/float()`` on an indexed/attribute
device value, and nested ``jax.jit``/``jax.pmap``. Coverage is
cross-checked against the runtime registries by the wiring test."""

    def coverage(self, package: Package) -> Dict[str, Dict[str, list]]:
        index = build_index(package)
        regs = registry_builders(package, index)
        out: Dict[str, Dict[str, list]] = {}
        for reg_name, entries in regs.items():
            out[reg_name] = {
                key: sorted(f.qualname
                            for f in index.reachable([fn]))
                for key, fn in entries.items()}
        return out

    def check(self, package: Package) -> Iterator[Finding]:
        index = build_index(package)
        regs = registry_builders(package, index)
        builders = [fn for entries in regs.values()
                    for fn in entries.values()]
        region = _device_region(package, index, builders)
        for fn in sorted(region, key=lambda f: f.qualname):
            yield from self._check_func(package, index, fn)

    def _check_func(self, package: Package, index: FunctionIndex,
                    fn: Func) -> Iterator[Finding]:
        mod = fn.module
        where = f"in {fn.qualname.removeprefix(PKG + '.')} " \
                "(reachable from the epoch-builder registries)"
        for node in index._own_body_walk(fn):
            if not isinstance(node, ast.Call):
                continue
            qn = _callee_qn(package, mod, node)
            f = node.func
            if qn in ("jax.device_get", "jax.device_put"):
                yield self._f(mod, node,
                              f"host↔device transfer {qn}() {where}")
            elif qn in _JIT_WRAPPERS:
                yield self._f(mod, node,
                              f"nested {qn}() {where} — a second "
                              "dispatch inside the one-dispatch region")
            elif qn in ("numpy.asarray", "numpy.array"):
                yield self._f(mod, node,
                              f"{qn}() forces device→host "
                              f"materialization {where}")
            elif isinstance(f, ast.Attribute) and \
                    f.attr == "block_until_ready":
                yield self._f(mod, node,
                              f".block_until_ready() {where} — host "
                              "sync inside the traced region")
            elif isinstance(f, ast.Attribute) and f.attr == "item" \
                    and not node.args:
                yield self._f(mod, node,
                              f".item() pulls a device scalar {where}")
            elif isinstance(f, ast.Name) and f.id in ("int", "float") \
                    and len(node.args) == 1 and \
                    isinstance(node.args[0],
                               (ast.Subscript, ast.Attribute)):
                yield self._f(mod, node,
                              f"{f.id}() on an indexed/attribute value "
                              f"{where} — device-scalar coercion blocks "
                              "on the dispatch")

    def _f(self, mod: Module, node: ast.AST, msg: str) -> Finding:
        return Finding(self.name, mod.rel, node.lineno,
                       node.col_offset, msg)


@register
class TracePurity(Rule):
    name = "trace-purity"
    title = "no wall-clock/RNG/mutable-default capture in traced code"
    ci_label = "trace-purity"
    doc = """A function traced by jax.jit / vmap / pmap / shard_map
executes its Python body ONCE; a ``time.time()``, ``random.*`` or
``np.random.*`` call inside it bakes one sample into the compiled
artifact, and a mutable default argument is shared trace state. Both
silently break the determinism contracts: seeded chaos replay
(docs/robustness.md) and the solo/co-scheduled/sharded bit-exactness
pins. Device-side randomness belongs to ``jax.random`` with threaded
keys; wall-clock belongs outside the epoch and rides in as data."""

    _IMPURE_PREFIXES = ("random.", "numpy.random.")
    _IMPURE_CALLS = {
        "time.time", "time.monotonic", "time.perf_counter",
        "time.time_ns", "time.monotonic_ns",
        "datetime.datetime.now", "datetime.datetime.utcnow",
    }

    def check(self, package: Package) -> Iterator[Finding]:
        # Purity is a closure property, same as dispatch-discipline: an
        # impure call one helper away from the traced root is frozen at
        # trace time exactly as if it were inline, so we walk the full
        # static reachability of every traced root, not just its
        # lexically nested defs.
        index = build_index(package)
        seen: Set[Func] = set()
        for root in self._traced_roots(package, index):
            for fn in index.reachable([root]):
                if fn in seen:
                    continue
                seen.add(fn)
                yield from self._check_func(package, index, fn)

    def _traced_roots(self, package: Package,
                      index: FunctionIndex) -> List[Func]:
        roots: List[Func] = []
        for fn in index.by_qualname.values():
            mod = fn.module
            for dec in getattr(fn.node, "decorator_list", ()):
                target = dec.func if isinstance(dec, ast.Call) else dec
                qn = package.canonical(mod.imports.resolve(target))
                if qn in _TRACE_WRAPPERS:
                    roots.append(fn)
                elif qn == "functools.partial" and \
                        isinstance(dec, ast.Call) and dec.args and \
                        package.canonical(
                            mod.imports.resolve(dec.args[0])
                        ) in _TRACE_WRAPPERS:
                    # @functools.partial(jax.jit, static_argnames=...)
                    roots.append(fn)
        for rel, mod in package.modules.items():
            for node in mod.walk():
                if not isinstance(node, ast.Call):
                    continue
                if _callee_qn(package, mod, node) not in _TRACE_WRAPPERS:
                    continue
                for arg in node.args[:1]:
                    owner = _enclosing_func(index, mod, node)
                    if owner is not None:
                        roots.extend(index.resolve_ref(owner, arg))
                    else:
                        hit = index.lookup(
                            mod.imports.resolve_or_local(arg))
                        if hit is not None:
                            roots.append(hit)
        return roots

    def _check_func(self, package: Package, index: FunctionIndex,
                    fn: Func) -> Iterator[Finding]:
        mod = fn.module
        short = fn.qualname.removeprefix(PKG + ".")
        args = fn.node.args
        for default in list(args.defaults) + \
                [d for d in args.kw_defaults if d is not None]:
            if isinstance(default, (ast.List, ast.Dict, ast.Set)):
                yield Finding(
                    self.name, mod.rel, default.lineno,
                    default.col_offset,
                    f"mutable default argument on traced function "
                    f"{short} — shared state is captured at trace time")
        for node in index._own_body_walk(fn):
            if not isinstance(node, ast.Call):
                continue
            qn = _callee_qn(package, mod, node)
            if qn is None:
                continue
            impure = qn in self._IMPURE_CALLS or \
                any(qn.startswith(p) for p in self._IMPURE_PREFIXES)
            if impure:
                yield Finding(
                    self.name, mod.rel, node.lineno, node.col_offset,
                    f"impure call {qn}() inside traced function {short} "
                    "— the sample/time is frozen at trace time (use "
                    "jax.random with threaded keys, or pass the value "
                    "in as data)")
