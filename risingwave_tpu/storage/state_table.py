"""StateTable — the relational view over the state store.

Counterpart of the reference's ``StateTable``
(reference: src/stream/src/common/table/state_table.rs:62,520,667-686,783):
pk-addressed row storage with buffered writes that become visible at
``commit(epoch)``. In the TPU design executors keep *hot* state on device and
use the StateTable as the durable tier: they write dirty deltas here on
barriers, and reload on recovery (`scan_all` → device bulk-insert).

Rows are stored as physical-value tuples; pk columns are memcomparable-
encoded so iteration order == pk order.
"""

from __future__ import annotations

from typing import Any, Iterator, Optional, Sequence

from ..common.packed import PackedBatch, dict_view, layer_view
from ..common.row import decode_value_row, encode_key, encode_value_row
from ..common.types import Schema
from .state_store import MemoryStateStore


class StateTable:
    def __init__(
        self,
        store: MemoryStateStore,
        table_id: int,
        schema: Schema,
        pk_indices: Sequence[int],
    ) -> None:
        self.store = store
        self.table_id = table_id
        self.schema = schema
        self.pk_indices = tuple(pk_indices)
        self._pk_types = tuple(schema[i].type for i in self.pk_indices)
        # The epoch buffer, oldest write first: LAYERS in durable form
        # (common/packed.py — a packed batch, or a dict {key: value bytes |
        # None} that the row-at-a-time writers fill), under the raw rows
        # of insert(), which are the newest writes of their keys and are
        # value-encoded into the top dict layer when a packed batch lands
        # on them or at commit().
        self._layers: list = []
        self._puts: dict[bytes, tuple] = {}

    # -- key helpers ----------------------------------------------------------

    def key_of(self, row: Sequence[Any]) -> bytes:
        return encode_key([row[i] for i in self.pk_indices], self._pk_types)

    # -- buffered writes (MemTable semantics) ---------------------------------

    def _top(self) -> dict:
        """The dict layer the row-at-a-time writers write: the last layer,
        or a new one on top of a packed batch."""
        if not self._layers or not isinstance(self._layers[-1], dict):
            self._layers.append({})
        return self._layers[-1]

    def _seal_puts(self) -> None:
        """insert()'s raw rows, value-encoded, into the top dict layer."""
        if self._puts:
            types = self.schema.types
            self._top().update({k: encode_value_row(v, types)
                                for k, v in self._puts.items()})
            self._puts = {}

    def insert(self, row: Sequence[Any]) -> None:
        self._puts[self.key_of(row)] = tuple(row)

    def delete(self, row: Sequence[Any]) -> None:
        k = self.key_of(row)
        self._puts.pop(k, None)
        self._top()[k] = None

    def stage_encoded(self, puts: dict, dels: Sequence[bytes]) -> None:
        """Batch-staged rows already in durable form: keys are
        memcomparable bytes, values are value-encoded bytes. Semantically
        identical to delete() of every ``dels`` and then insert() of every
        ``puts``, row by row."""
        if self._puts:
            for k in (*dels, *puts):
                self._puts.pop(k, None)
        top = self._top()
        top.update(dict.fromkeys(dels))
        top.update(puts)

    def stage_packed(self, batch: PackedBatch) -> None:
        """An ordered batch as the native codec packed it (the checkpoint
        delta of ``stream/state_delta.py``, a barrier's rows of
        ``stream/materialize.py``): a layer of its own, whole — no Python
        statement runs once a row. Semantically identical to insert() /
        delete() of its rows one by one: the last write of a pk wins."""
        if len(batch):
            self._seal_puts()
            self._layers.append(batch)

    def update(self, old_row: Sequence[Any], new_row: Sequence[Any]) -> None:
        ko, kn = self.key_of(old_row), self.key_of(new_row)
        if ko != kn:
            self.delete(old_row)
        self.insert(new_row)

    def commit(self, epoch: int) -> None:
        """Hand the buffered epoch delta to the store (visible after the
        store-level commit of this epoch). Rows cross the table/store
        boundary as value-encoded bytes — the store is an opaque KV tier,
        and the durable backend persists process-independent bytes
        (reference: value encoding at the table layer, state_table.rs:62)."""
        self._seal_puts()
        layers = [layer for layer in self._layers if len(layer)]
        self._layers = []
        if layers:
            self.store.ingest_layers(self.table_id, epoch, layers)

    def is_dirty(self) -> bool:
        return bool(self._puts) or any(map(len, self._layers))

    # -- reads (committed + own uncommitted buffer) ---------------------------

    def _staged(self) -> dict:
        """``{key: value bytes | None}`` of every write the store has not
        committed, the newest winning: the store's pending epochs of this
        table under this buffer's layers. insert()'s raw rows are not in
        it; the readers lay them on top."""
        return dict_view(self.store.pending_layers(self.table_id)
                         + self._layers)

    def get_row(self, pk_values: Sequence[Any]) -> Optional[tuple]:
        k = encode_key(list(pk_values), self._pk_types)
        if k in self._puts:
            return self._puts[k]
        for layer in reversed(self._layers):
            view = layer_view(layer)
            if k in view:
                v = view[k]
                break
        else:
            v = self.store.get(self.table_id, k)
        return None if v is None else decode_value_row(v, self.schema.types)

    def scan_all(self) -> Iterator[tuple]:
        """Committed rows merged with the uncommitted buffer, pk order."""
        types = self.schema.types
        merged = dict(self.store.iter_table(self.table_id))
        merged.update(dict_view(self._layers))
        merged.update(self._puts)
        for k in sorted(merged):
            v = merged[k]
            if v is not None:
                yield v if k in self._puts else decode_value_row(v, types)

    def scan_after(self, after_key: Optional[bytes],
                   limit: int) -> tuple[list[tuple], Optional[bytes]]:
        """Up to ``limit`` rows with encoded pk > ``after_key``, in key
        order, plus the last key read (the resumable backfill cursor —
        reference: snapshot-read chunks, executor/backfill.rs:48-69).
        Reads the CURRENT merged view, so each call observes updates
        committed since the last one — exactly the per-epoch re-read the
        reference's backfill relies on for exactly-once.

        Cost per call: O(log n) bisect into the store's cached sorted
        committed keys + O(batch + staged) merge walk — a backfill over a
        large table never re-sorts the whole table per batch."""
        import bisect
        committed = self.store.committed_view(self.table_id)
        skeys = self.store.sorted_committed_keys(self.table_id)
        # staged overlay (pending epochs + this instance's buffer): small
        # between checkpoints; None = delete
        overlay: dict[bytes, Optional[Any]] = dict(self._staged())
        overlay.update(self._puts)
        raw = self._puts
        okeys = sorted(k for k in overlay
                       if after_key is None or k > after_key)
        i = (bisect.bisect_right(skeys, after_key)
             if after_key is not None else 0)
        j = 0
        out: list[tuple] = []
        last = after_key
        while len(out) < limit and (i < len(skeys) or j < len(okeys)):
            ck = skeys[i] if i < len(skeys) else None
            ok = okeys[j] if j < len(okeys) else None
            if ok is None or (ck is not None and ck < ok):
                k, v = ck, committed[ck]
                is_raw = False
                i += 1
            else:
                if ck == ok:
                    i += 1                 # overlay shadows committed
                k, v = ok, overlay[ok]
                is_raw = k in raw
                j += 1
            last = k
            if v is None:
                continue
            out.append(v if is_raw
                       else decode_value_row(v, self.schema.types))
        return out, last

    def scan_prefix(self, prefix_values: Sequence[Any], n_cols: int) -> Iterator[tuple]:
        """Rows whose encoded pk starts with the first ``n_cols`` pk
        columns' encoding, in key order. O(log n) bisect into the store's
        sorted committed keys + the (small) staged overlay — the join
        cold-tier fault-in path calls this per faulted key."""
        import bisect
        types = self.schema.types
        prefix = encode_key(list(prefix_values), self._pk_types[:n_cols])
        committed = self.store.committed_view(self.table_id)
        skeys = self.store.sorted_committed_keys(self.table_id)
        merged: dict[bytes, Optional[Any]] = {}
        i = bisect.bisect_left(skeys, prefix)
        while i < len(skeys) and skeys[i].startswith(prefix):
            merged[skeys[i]] = committed[skeys[i]]
            i += 1
        for overlay in (self._staged(), self._puts):
            for k, v in overlay.items():
                if k.startswith(prefix):
                    merged[k] = v
        for k in sorted(merged):
            v = merged[k]
            if v is not None:
                yield v if k in self._puts else decode_value_row(v, types)

    def __len__(self) -> int:
        n = self.store.table_len(self.table_id)
        staged = dict_view(self._layers) if self._layers else {}
        if self._puts:
            staged = {**staged, **self._puts}
        for k, v in staged.items():
            before = self.store.get(self.table_id, k) is not None
            n += (v is not None) - before
        return n
