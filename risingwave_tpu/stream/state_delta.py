"""The checkpoint delta's host half: walk the device's dirty-row windows
(``ops/ckpt_delta.delta_window``), then encode the rows and stage them
into the state table. One routine each for the hash agg, the hash join
and the sharded hash agg; what differs between them — which columns are
gathered, and which dirty rows are deletes — stays with the caller.

Whatever ``*.state_delta`` span the caller has open gets three children
here, one a kind of work: ``delta.fetch_wait`` (the host blocked on the
device's windows), ``delta.encode`` (dirty rows to key / value bytes) and
``delta.stage`` (the packed batch into the state table and its commit). What is
left as the parent's self time is window 0's dispatch, the numpy cut, the
caller's masks and its ``ckpt_dirty`` reset. None carries a ledger stage:
the parent's ``state_delta`` stage already holds their time.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import numpy as np

from ..common.fetch import async_fetch, fetch
from ..common.packed import PackedBatch
from ..common.tracing import CAT_STORAGE, current_span, span
from ..storage.state_table import StateTable

#: rows of one delta window; a smaller state is its own window. A
#: window's device time is linear in its rows, dirty or not (0.24-0.29 us
#: a row of the agg's three columns on a v5e, PERF.md PR 26), so a window
#: far above the delta gathers and moves rows nobody reads.
DELTA_WINDOW_ROWS = 1 << 13


def fetch_delta(window: Callable, capacity: int) -> tuple:
    """Fetch the dirty rows of a state of ``capacity`` flat rows (a
    shard). ``window(lo, G)`` dispatches ``delta_window`` and returns its
    device outputs ``(n_dirty, valid, *columns)``; under ``vmap`` over a
    leading shard axis each output carries that axis and every shard walks
    the same ranks. Window 0 answers every ``n_dirty``; further windows go
    out asynchronously, as many as the fullest shard needs.

    Returns ``(n_rows, columns, counters)``: the columns cut to the dirty
    rows, shard after shard in ascending flat-index order, and what the
    ``*.state_delta`` spans report (``windows``, ``bytes_fetched``)."""
    G = min(capacity, DELTA_WINDOW_ROWS)
    first = window(np.int32(0), G)
    with _child("delta.fetch_wait", wait="device") as wait:
        wins = [fetch(first)]
        n_dirty = np.atleast_1d(wins[0][0])
        more = [async_fetch(window(np.int32(lo), G))
                for lo in range(G, int(n_dirty.max()), G)]
        wins += [f.result() for f in more]
        wait.set(windows=len(wins))
    # a shard's windows are full up to its last one and the valid rows
    # lead, so its first n_dirty rows across the windows are its delta
    keep = np.arange(len(wins) * G) < n_dirty[:, None]
    columns = jax.tree_util.tree_map(
        lambda *xs: np.concatenate(xs, axis=-1).reshape(keep.shape)[keep],
        *(w[2:] for w in wins))
    counters = {"windows": len(wins), "bytes_fetched": sum(
        x.nbytes for x in jax.tree_util.tree_leaves(wins))}
    return int(n_dirty.sum()), columns, counters


def _child(name: str, **kw) -> span:
    """A span under the caller's open ``*.state_delta``, on its track and
    of its epoch."""
    parent = current_span()
    return span(name, epoch=None, cat=CAT_STORAGE,
                tid=parent.tid if parent is not None else "main", **kw)


def stage_delta(table: StateTable, epoch: int, datas: Sequence[np.ndarray],
                masks: Sequence[np.ndarray], puts: np.ndarray,
                dels: np.ndarray) -> int:
    """Stage delta rows — one array a table column, row ``i`` written
    where ``puts[i]``, deleted where ``dels[i]`` — and commit them to
    ``epoch``. Deletes strictly before puts: a join's same-pk update lands
    in two rows of one delta, and the delete must not clobber the freshly
    upserted row. With the native codec that is ONE packed batch
    (common/packed.py), the delete keys first, staged whole. Returns the
    encoded bytes staged (0 where the native codec does not serve: the
    table then encodes at its commit)."""
    from ..native import codec as _native_codec
    put_idx, del_idx = np.flatnonzero(puts), np.flatnonzero(dels)
    types = table.schema.types
    codec = _native_codec()
    native = codec is not None and codec.supports(types)
    staged = 0
    with _child("delta.encode", rows=len(put_idx) + len(del_idx),
                native=int(native)) as encode:
        if native:
            pk = table.pk_indices
            live = np.zeros(len(del_idx) + len(put_idx), np.uint8)
            live[len(del_idx):] = 1
            batch = PackedBatch(
                codec.pack_keys([datas[i] for i in pk],
                                [masks[i] for i in pk],
                                [types[i] for i in pk],
                                np.concatenate([del_idx, put_idx])),
                codec.pack_value_rows(datas, masks, types, put_idx), live)
            staged = batch.nbytes
        else:
            def row_at(r):
                return tuple(d[r].item() if m[r] else None
                             for d, m in zip(datas, masks))

            del_rows = [row_at(r) for r in del_idx]
            put_rows = [row_at(r) for r in put_idx]
        encode.set(bytes=staged)
    with _child("delta.stage", puts=len(put_idx), deletes=len(del_idx)):
        if native:
            table.stage_packed(batch)
        else:
            for row in del_rows:
                table.delete(row)
            for row in put_rows:
                table.insert(row)
        table.commit(epoch)
    return staged
