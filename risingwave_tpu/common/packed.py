"""The packed delta: a checkpoint's rows as the row codec wrote them.

A ``PackedColumn`` is ``n`` encoded rows (keys or value rows) back to back
in one ``bytes`` with ``int64[n + 1]`` offsets — what ``rw_encode``
(native/rowcodec.cpp) produces in one call. A ``PackedBatch`` is an ordered
batch of writes to ONE state table: a key column, a ``live`` flag a row and
a value column of the live rows; row ``i`` puts a value under ``keys[i]``
where ``live[i]`` (the ``j``-th live row's is ``values[j]``), else deletes
``keys[i]`` — a tombstone has no value. Rows apply in order: a later row of
an equal key wins.

A batch is made once (``stream/state_delta.stage_delta``,
``stream/materialize.py``) and is what ``StateTable``, the store's pending
epochs and the segment writer hold — nothing between the device's window
and the segment's bytes runs once a row in Python. The one place that
needs Python ``bytes`` a row is the committed dict (``MemoryStateStore.
commit``) or a read of a staged batch: the blobs are cut there, once.

A table's delta is a list of LAYERS in application order, each a
``PackedBatch`` or the dict ``{key: value | None}`` the row-at-a-time
writers fill (``None`` = delete). ``dict_view`` folds them for the readers
that want one dict.
"""

from __future__ import annotations

import operator
from collections import deque
from itertools import compress, repeat
from typing import Optional, Union

import numpy as np


class PackedColumn:
    """``n`` byte strings back to back: row ``i`` is
    ``blob[offsets[i]:offsets[i + 1]]``."""

    __slots__ = ("blob", "offsets")

    def __init__(self, blob: bytes, offsets: np.ndarray):
        self.blob = blob
        self.offsets = offsets

    @classmethod
    def empty(cls) -> "PackedColumn":
        return cls(b"", np.zeros(1, np.int64))

    def __len__(self) -> int:
        return len(self.offsets) - 1

    def lens(self) -> np.ndarray:
        return np.diff(self.offsets)

    def cut(self) -> list:
        """The rows as a list of ``bytes``. A column whose rows all have
        one length (integer / timestamp keys, all-fixed value rows without
        a NULL) is cut by numpy in one call; a ragged one with Python ints
        from one ``tolist()``, never numpy scalars."""
        n = len(self)
        if n == 0:
            return []
        blob = self.blob
        width = len(blob) // n
        if width and np.array_equal(
                self.offsets, np.arange(n + 1, dtype=np.int64) * width):
            return np.frombuffer(blob, np.dtype(("V", width)), n).tolist()
        offs = self.offsets.tolist()
        return [blob[lo:hi] for lo, hi in zip(offs, offs[1:])]


class PackedBatch:
    """An ordered batch of puts and deletes of one table, packed."""

    __slots__ = ("keys", "values", "live", "_view")

    def __init__(self, keys: PackedColumn, values: PackedColumn,
                 live: np.ndarray):
        live = np.ascontiguousarray(live, np.uint8)
        if (len(keys) != len(live)
                or len(values) != np.count_nonzero(live)):
            raise ValueError(
                f"packed batch: {len(keys)} keys, {len(live)} flags, "
                f"{len(values)} values of {np.count_nonzero(live)} puts")
        self.keys = keys
        self.values = values
        self.live = live
        self._view: Optional[dict] = None

    def __len__(self) -> int:
        return len(self.live)

    @property
    def nbytes(self) -> int:
        return len(self.keys.blob) + len(self.values.blob)

    @property
    def viewed(self) -> bool:
        return self._view is not None

    def cut(self) -> tuple:
        """``(keys, values)``: a ``bytes`` a row each, None the value of
        a tombstone."""
        keys, values = self.keys.cut(), self.values.cut()
        if len(values) != len(keys):
            by_row = np.full(len(keys), None, object)
            by_row[self.live.view(np.bool_)] = np.array(values, object)
            values = by_row.tolist()
        return keys, values

    def view(self) -> dict:
        """``{key: value | None}`` of the batch, the last row of a key
        winning; built on the first call and kept."""
        if self._view is None:
            self._view = dict(zip(*self.cut()))
        return self._view


Layer = Union[PackedBatch, dict]


def layer_view(layer: Layer) -> dict:
    return layer if isinstance(layer, dict) else layer.view()


def packed_rows(layers) -> int:
    """Rows of ``layers`` that are held in packed batches."""
    return sum(len(layer) for layer in layers
               if isinstance(layer, PackedBatch))


def dict_view(layers) -> dict:
    """One ``{key: value | None}`` of a table's layers in application
    order (later layers win); a single dict layer is returned as it is."""
    if len(layers) == 1:
        return layer_view(layers[0])
    view: dict = {}
    for layer in layers:
        view.update(layer_view(layer))
    return view


def apply_view(rows: dict, view: dict) -> None:
    """Apply ``{key: value | None}`` to a table ``{key: value}``: puts by
    one ``dict.update``, tombstones popped — no Python statement a row."""
    rows.update(view)
    dead = compress(view, map(operator.is_, view.values(), repeat(None)))
    deque(map(rows.pop, dead, repeat(None)), maxlen=0)


def apply_layer(rows: dict, layer: Layer) -> None:
    """Apply one layer to a committed table. A dict layer, or a packed
    batch a reader has already cut, goes through its dict view. A packed
    batch nobody has read goes in straight from its one cut, with no dict
    of its size between: every put by one ``dict.update`` in order (the
    last put of a key wins), then the keys whose LAST row is a tombstone
    are popped — found among the rows of the tombstones' keys alone."""
    if not isinstance(layer, PackedBatch) or layer.viewed:
        apply_view(rows, layer_view(layer))
        return
    keys, values = layer.keys.cut(), layer.values.cut()
    if len(values) == len(keys):
        rows.update(zip(keys, values))
        return
    flags = layer.live.view(np.bool_).tolist()
    rows.update(zip(compress(keys, flags), values))
    deleted = set(compress(keys, map(operator.not_, flags)))
    last = dict(compress(zip(keys, flags), map(deleted.__contains__, keys)))
    dead = compress(last, map(operator.not_, last.values()))
    deque(map(rows.pop, dead, repeat(None)), maxlen=0)
