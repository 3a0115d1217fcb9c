"""The checkpoint delta's host half: walk the device's dirty-row windows
(``ops/ckpt_delta.delta_window``), then encode the rows and stage them
into the state table. One routine each for the hash agg, the hash join
and the sharded hash agg; what differs between them — which columns are
gathered, and which dirty rows are deletes — stays with the caller.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import numpy as np

from ..common.fetch import async_fetch, fetch
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
    wins = [fetch(window(np.int32(0), G))]
    n_dirty = np.atleast_1d(wins[0][0])
    more = [async_fetch(window(np.int32(lo), G))
            for lo in range(G, int(n_dirty.max()), G)]
    wins += [f.result() for f in more]
    # a shard's windows are full up to its last one and the valid rows
    # lead, so its first n_dirty rows across the windows are its delta
    keep = np.arange(len(wins) * G) < n_dirty[:, None]
    columns = jax.tree_util.tree_map(
        lambda *xs: np.concatenate(xs, axis=-1).reshape(keep.shape)[keep],
        *(w[2:] for w in wins))
    counters = {"windows": len(wins), "bytes_fetched": sum(
        x.nbytes for x in jax.tree_util.tree_leaves(wins))}
    return int(n_dirty.sum()), columns, counters


def stage_delta(table: StateTable, epoch: int, datas: Sequence[np.ndarray],
                masks: Sequence[np.ndarray], puts: np.ndarray,
                dels: np.ndarray) -> int:
    """Stage delta rows — one array a table column, row ``i`` written
    where ``puts[i]``, deleted where ``dels[i]`` — and commit them to
    ``epoch``. Deletes strictly before puts: a join's same-pk update lands
    in two rows of one delta, and the delete must not clobber the freshly
    upserted row. Returns the encoded bytes staged (0 where the native
    codec does not serve: the table then encodes at its commit)."""
    from ..native import codec as _native_codec
    put_idx, del_idx = np.flatnonzero(puts), np.flatnonzero(dels)
    types = table.schema.types
    codec = _native_codec()
    staged = 0
    if codec is not None and codec.supports(types):
        pk = table.pk_indices
        pk_d = [datas[i] for i in pk]
        pk_m = [masks[i] for i in pk]
        pk_t = [types[i] for i in pk]
        rows = dict(zip(
            codec.encode_keys(pk_d, pk_m, pk_t, put_idx),
            codec.encode_value_rows(datas, masks, types, put_idx)))
        keys = codec.encode_keys(pk_d, pk_m, pk_t, del_idx)
        table.stage_encoded(rows, keys)
        staged = (sum(map(len, rows)) + sum(map(len, rows.values()))
                  + sum(map(len, keys)))
    else:
        def row_at(r):
            return tuple(d[r].item() if m[r] else None
                         for d, m in zip(datas, masks))

        for r in del_idx:
            table.delete(row_at(r))
        for r in put_idx:
            table.insert(row_at(r))
    table.commit(epoch)
    return staged
