"""sync-fetch-discipline: blocking device→host fetches on the tick
path must go through the async fetch helper.

The pipelined tick (docs/performance.md "Pipelined tick") lives or dies
on the host never synchronizing with the device accidentally: one
``jax.device_get`` in a per-tick method stalls the dispatch queue and
silently reverts the overlap the pipeline bought. The blessed crossing
is ``common/fetch.py`` — ``async_fetch`` starts the copy at dispatch
time, ``FetchFuture.result()``/``fetch()`` resolve it at flush/barrier
time — so this rule walks the closure reachable from
``Session._tick_impl`` through the fused engines' per-tick methods and
flags the raw blocking spellings:

* ``jax.device_get(...)``
* ``.block_until_ready()``
* ``np.asarray(...)`` over a call/attribute expression inside the
  engine-driver modules (the np.asarray-on-a-device-value idiom; a
  plain ``np.asarray(name)`` over host data is not flagged)

``common/fetch.py`` itself is exempt (its ``result()`` IS the one
legitimate device_get), and the grow-retry drain keeps one reasoned
``# rwlint: allow`` — after a routing-overflow replay the packed flags
must validate before anything else dispatches, so that re-fetch is
deliberately synchronous.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Set

from .callgraph import Func, FunctionIndex, build_index
from .core import Finding, Module, Package, Rule, register

PKG = "risingwave_tpu"

#: the tick path's root set: Session's tick drivers, the fused-job
#: registry's driver, and every fused group's per-tick surface (the
#: callgraph cannot statically type ``self._fused.tick(...)`` or
#: ``group.run_epoch(...)`` receivers, so those methods are roots in
#: their own right — "reachable from _tick_impl through the engines").
#: Method-name sets keep checkpoint/recovery/debug surfaces
#: (export_host, merged_group_values) out of scope: they run on the
#: durable path, not per tick.
_GROUP_TICK = ("run_epoch", "flush", "begin_flush", "finish_flush")
TICK_ROOTS = (
    ("frontend/session.py", ("Session",),
     ("_tick_impl", "_complete_oldest_impl")),
    ("stream/fused_jobs.py", ("FusedJobs",), ("tick", "drain")),
    ("stream/coschedule.py", ("JobAxisGroup", "CoGroup"), _GROUP_TICK),
    ("stream/tick_compiler.py", ("PaddedHeteroGroup", "MegaGroup"),
     _GROUP_TICK),
    ("parallel/fused.py", None,      # every engine class in the module
     _GROUP_TICK + ("_settle", "_settled_packed")),
)

#: the one module allowed to call jax.device_get on the tick path
EXEMPT_MODULES = ("common/fetch.py",)

#: modules where a bare np.asarray(<call>/<attr>) is treated as a
#: device-value materialization (the engine drivers); elsewhere
#: np.asarray over host rows is routine
DEVICE_DRIVER_MODULES = ("stream/coschedule.py", "stream/tick_compiler.py",
                         "stream/fused_jobs.py", "parallel/fused.py",
                         "ops/", "frontend/session.py")


def _callee_qn(package: Package, mod: Module, node: ast.Call):
    return package.canonical(mod.imports.resolve(node.func))


def tick_roots(package: Package, index: FunctionIndex) -> List[Func]:
    roots: List[Func] = []
    for rel, classes, methods in TICK_ROOTS:
        mod = package.module(rel)
        if mod is None:
            continue
        for fn in index.by_qualname.values():
            if fn.module is not mod or fn.cls is None:
                continue
            if classes is not None and fn.cls not in classes:
                continue
            if fn.name in methods:
                roots.append(fn)
    return roots


@register
class SyncFetchDiscipline(Rule):
    name = "sync-fetch-discipline"
    title = "tick-path device fetches go through common/fetch.py"
    ci_label = "sync-fetch-discipline"
    doc = """The asynchronous epoch pipeline overlaps device compute
with host flush decode by starting every device→host copy at dispatch
time (common/fetch.py async_fetch) and resolving it at flush/barrier
time. A raw blocking fetch — jax.device_get, .block_until_ready(),
np.asarray on a device value — anywhere in the closure reachable from
Session._tick_impl through the fused engines' per-tick methods stalls
the dispatch queue and silently reverts the overlap. This rule walks
that closure and flags the raw spellings; common/fetch.py is the one
blessed crossing, and the sharded grow-retry drain carries the one
reasoned allow (a replayed epoch must validate synchronously before
anything else dispatches)."""

    def check(self, package: Package) -> Iterator[Finding]:
        index = build_index(package)
        roots = tick_roots(package, index)
        seen: Set[Func] = set()
        for fn in sorted(index.reachable(roots),
                         key=lambda f: f.qualname):
            if fn in seen:
                continue
            seen.add(fn)
            if fn.module.rel in EXEMPT_MODULES:
                continue
            yield from self._check_func(package, index, fn)

    def _check_func(self, package: Package, index: FunctionIndex,
                    fn: Func) -> Iterator[Finding]:
        mod = fn.module
        where = (f"in {fn.qualname.removeprefix(PKG + '.')} "
                 "(tick path — reachable from Session._tick_impl "
                 "through the fused engines)")
        in_driver = mod.rel.startswith(DEVICE_DRIVER_MODULES)
        for node in index._own_body_walk(fn):
            if not isinstance(node, ast.Call):
                continue
            qn = _callee_qn(package, mod, node)
            f = node.func
            if qn == "jax.device_get":
                yield self._f(mod, node,
                              f"blocking jax.device_get() {where} — "
                              "start the copy at dispatch time via "
                              "common/fetch.async_fetch and resolve at "
                              "flush time")
            elif isinstance(f, ast.Attribute) and \
                    f.attr == "block_until_ready":
                yield self._f(mod, node,
                              f".block_until_ready() {where} — host "
                              "sync on the tick path; fetch the value "
                              "through common/fetch.py instead")
            elif qn in ("numpy.asarray", "numpy.array") and in_driver \
                    and node.args and \
                    isinstance(node.args[0], ast.Attribute):
                # np.asarray(self.some_device_state): synchronous
                # materialization. Call args are NOT flagged — the
                # common post-refactor shape is np.asarray over an
                # already-host fetch result (fetch(...)/(...).result())
                yield self._f(mod, node,
                              f"{qn}() over a device value {where} — "
                              "materializes device→host synchronously; "
                              "route it through common/fetch.py")

    def _f(self, mod: Module, node: ast.AST, msg: str) -> Finding:
        return Finding(self.name, mod.rel, node.lineno,
                       node.col_offset, msg)
