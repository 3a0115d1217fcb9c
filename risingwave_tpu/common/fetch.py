"""Async device→host fetch futures — the pipelined tick's transfer seam.

JAX dispatch is asynchronous: a jitted epoch call returns immediately
with futures for its outputs while the device (or the CPU backend's
thread pool) keeps computing. The host tick loop used to throw that
overlap away by calling ``jax.device_get`` the moment an epoch's packed
stats existed — a blocking round trip that serializes host decode
behind device compute. This module is the one blessed crossing:

* ``async_fetch(tree)`` starts the device→host copy *now*
  (``jax.Array.copy_to_host_async``) and returns a ``FetchFuture``;
  the copy streams over DMA/PCIe while Python runs other work (another
  engine's dispatch, gather decode, checkpoint encode).
* ``FetchFuture.result()`` resolves to host numpy values — by the time
  a well-ordered tick calls it, the copy has usually already landed,
  so resolution costs a cache read instead of a round trip.
* ``fetch(tree)`` = ``async_fetch(tree).result()`` — the blocking form
  for call sites with no work to overlap; routing them through here
  keeps the tick path uniform and lets the ``sync-fetch-discipline``
  rwlint rule reason about exactly one module instead of every
  ``device_get`` spelling in the tree.

Profiler honesty rides along: a dispatch's wall time measured at
*enqueue* reads near-zero under async dispatch, so callers pass the
dispatch qualname (``dispatch=``) and ``result()`` reports the
enqueue→host-visible completion latency back to
``common/profiling.GLOBAL_PROFILER`` (``complete_seconds``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

__all__ = ["FetchFuture", "PendingFlush", "async_fetch", "fetch"]


def _start_copy(tree: Any) -> None:
    """Kick off the non-blocking device→host copy on every array leaf.
    Leaves without the async-copy surface (host numpy, scalars) simply
    resolve synchronously at ``result()``."""
    import jax

    def start(x):
        fn = getattr(x, "copy_to_host_async", None)
        if fn is not None:
            try:
                fn()
            except RuntimeError:
                pass        # deleted/donated buffer: result() will raise
        return x

    jax.tree_util.tree_map(start, tree)


class FetchFuture:
    """One in-flight device→host copy of a pytree of arrays."""

    __slots__ = ("_tree", "_result", "_done", "_dispatch")

    def __init__(self, tree: Any, dispatch: Optional[str] = None):
        self._tree = tree
        self._result: Any = None
        self._done = False
        self._dispatch = dispatch
        _start_copy(tree)

    def done(self) -> bool:
        """True when every leaf's producing computation (and copy) has
        finished — never blocks."""
        if self._done:
            return True
        import jax
        ready = True
        for leaf in jax.tree_util.tree_leaves(self._tree):
            is_ready = getattr(leaf, "is_ready", None)
            if is_ready is not None and not is_ready():
                ready = False
                break
        return ready

    def result(self) -> Any:
        """Host numpy values (blocks until the copy lands; idempotent).
        The one legitimate device_get on the tick path lives here."""
        if not self._done:
            import jax
            self._result = jax.device_get(self._tree)
            self._done = True
            self._tree = None            # release device references
            if self._dispatch is not None:
                from .profiling import GLOBAL_PROFILER
                GLOBAL_PROFILER.note_complete(self._dispatch)
        return self._result


@dataclasses.dataclass
class PendingFlush:
    """One fused epoch's in-flight barrier flush — the handle both the
    co-scheduled (stream/coschedule.CoGroup) and the K×S sharded
    (parallel/fused.ShardedCoGroup) engines defer across ticks. The
    probe ran, its packed stats are streaming host-ward (``fetch``),
    and the gathers wait on the resolved counts against ``stacked`` —
    the PRE-finish state, kept alive here so the next epoch's
    (possibly donating) dispatch can launch against the separately
    allocated finished buffer while this flush is still pending."""

    stacked: object
    packed: object
    ranks: object
    fetch: FetchFuture


def async_fetch(tree: Any, dispatch: Optional[str] = None) -> FetchFuture:
    """Start fetching ``tree`` to the host; resolve later with
    ``.result()``. ``dispatch`` names the producing dispatch's profiler
    qualname so completion latency lands in its record."""
    return FetchFuture(tree, dispatch=dispatch)


def fetch(tree: Any, dispatch: Optional[str] = None) -> Any:
    """Blocking fetch through the async helper (start + resolve): the
    uniform spelling for tick-path sites with nothing to overlap."""
    return FetchFuture(tree, dispatch=dispatch).result()
