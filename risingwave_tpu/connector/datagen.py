"""Datagen source: deterministic per-split field generators.

Counterpart of the reference's datagen connector + field generators
(reference: src/connector/src/source/datagen/,
src/common/src/field_generator/ — sequence and random generators per
column). Every value is a pure function of (column, split, offset), so
``seek`` is O(1) and replay after recovery reproduces the exact stream —
the property the split-state checkpoint contract requires.

Options (WITH clause), mirroring the reference's naming:
  * ``datagen.split.num``       — number of splits (default 1)
  * ``datagen.rows.per.chunk``  — rows per emitted chunk (default 256)
  * ``datagen.max.rows``        — total rows per split (default unbounded)
  * per-field: ``fields.<name>.kind`` = ``sequence`` (default for integral
    types) | ``random``; ``fields.<name>.start``/``end`` bounds.
"""

from __future__ import annotations

import numpy as np
from typing import Dict, List, Optional

from ..common.chunk import HostChunk
from ..common.types import Schema, TypeKind
from .base import SplitReader


def _field_values(field, kind: str, start: int, end: int,
                  split: int, n_splits: int, lo: int, hi: int) -> np.ndarray:
    """Values for rows [lo, hi) of one split — pure function of position.
    Sequence fields interleave across splits (split s gets start + s,
    start + s + n_splits, …) so the union over splits is the contiguous
    sequence, as in the reference's datagen split scheme."""
    idx = np.arange(lo, hi, dtype=np.int64)
    t = field.type
    if kind == "sequence":
        vals = start + split + idx * n_splits
        if end > start:
            vals = start + (vals - start) % (end - start + 1)
        return vals
    # random: splitmix64 of the global position — stable across runs
    x = (idx * np.int64(n_splits) + np.int64(split)).astype(np.uint64)
    x = (x + np.uint64(0x9E3779B97F4A7C15))
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    x = x ^ (x >> np.uint64(31))
    if t.is_float:
        return (x >> np.uint64(11)).astype(np.float64) / float(1 << 53) \
            * (end - start) + start
    span = max(1, end - start + 1)
    return (x % np.uint64(span)).astype(np.int64) + start


class DatagenReader(SplitReader):
    def __init__(self, schema: Schema, options: Optional[dict] = None):
        options = options or {}
        self.schema = schema
        self.n_splits = int(options.get("datagen.split.num", 1))
        self.rows_per_chunk = int(options.get("datagen.rows.per.chunk",
                                              options.get("rows_per_chunk", 256)))
        mr = options.get("datagen.max.rows")
        self.max_rows = int(mr) if mr is not None else None
        self._offsets: Dict[str, int] = {str(s): 0 for s in range(self.n_splits)}
        self._fields = []
        for f in schema:
            kind = str(options.get(f"fields.{f.name}.kind",
                                   "sequence" if f.type.is_integral
                                   else "random"))
            start = int(options.get(f"fields.{f.name}.start", 0))
            end = int(options.get(f"fields.{f.name}.end", 0))
            self._fields.append((f, kind, start, end))

    def splits(self) -> List[str]:
        return list(self._offsets)

    @property
    def offsets(self) -> Dict[str, int]:
        return dict(self._offsets)

    def seek(self, offsets: Dict[str, int]) -> None:
        for s, o in offsets.items():
            if s in self._offsets:
                self._offsets[s] = int(o)

    def next_host_chunk(self) -> Optional[HostChunk]:
        # serve the most-behind split first: deterministic given offsets
        # alone, so seek() needs no extra cursor state
        for split in sorted(range(self.n_splits),
                            key=lambda s: (self._offsets[str(s)], s)):
            sid = str(split)
            lo = self._offsets[sid]
            hi = lo + self.rows_per_chunk
            if self.max_rows is not None:
                hi = min(hi, self.max_rows)
            if hi <= lo:
                continue
            self._offsets[sid] = hi
            n = hi - lo
            arrays = []
            for f, kind, start, end in self._fields:
                vals = _field_values(f, kind, start, end, split,
                                     self.n_splits, lo, hi)
                if f.type.kind == TypeKind.VARCHAR:
                    from ..common.types import GLOBAL_STRING_DICT
                    vals = np.array([GLOBAL_STRING_DICT.intern(
                        f"{f.name}_{int(v)}") for v in vals], np.int32)
                arrays.append(vals)
            # datagen makes no nulls and only inserts
            return HostChunk(self.schema, arrays, n, self.rows_per_chunk)
        return None
