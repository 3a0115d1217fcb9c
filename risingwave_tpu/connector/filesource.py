"""File source: a directory of JSONL/CSV files, one split per file.

Counterpart of the reference's FsSourceExecutor / S3 file source
(reference: src/stream/src/executor/source/fs_source_executor.rs,
src/connector/src/source/filesystem/). Each file is a split; the offset is
the *line number* next to read, so seek is cheap and replay after recovery
re-reads the same lines — files are assumed append-only between
checkpoints, the same contract the reference's fs source has.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

from ..common.chunk import OP_INSERT, HostChunk, host_rows
from ..common.types import Schema
from .base import SplitReader
from .parsers import parse_csv_lines, parse_debezium_line, parse_json_line


class FileSourceReader(SplitReader):
    def __init__(self, schema: Schema, path: str,
                 fmt: str = "jsonl", rows_per_chunk: int = 256,
                 match_pattern: Optional[str] = None):
        self.schema = schema
        self.path = path
        self.fmt = fmt.lower()
        self.rows_per_chunk = rows_per_chunk
        self.match_pattern = match_pattern
        self._offsets: Dict[str, int] = {}
        self.dropped_events = 0      # unparseable debezium lines skipped
        # split → ((mtime_ns, size), line list): re-read only when the
        # file changed, not on every chunk
        self._cache: Dict[str, tuple] = {}
        self._discover()

    def _discover(self) -> None:
        """Split enumeration (reference: SplitEnumerator::list_splits).
        Called lazily per read cycle so files added at runtime are picked
        up, like the reference's periodic enumerator tick."""
        if os.path.isfile(self.path):
            names = [self.path]
        elif os.path.isdir(self.path):
            names = sorted(
                os.path.join(self.path, n) for n in os.listdir(self.path)
                if not n.startswith(".")
                and (self.match_pattern is None
                     or n.endswith(self.match_pattern)))
        else:
            names = []
        for n in names:
            self._offsets.setdefault(n, 0)

    def splits(self) -> List[str]:
        self._discover()
        return list(self._offsets)

    @property
    def offsets(self) -> Dict[str, int]:
        return dict(self._offsets)

    def seek(self, offsets: Dict[str, int]) -> None:
        for s, o in offsets.items():
            self._offsets[s] = int(o)

    def _lines(self, split: str) -> List[str]:
        try:
            st = os.stat(split)
        except OSError:
            return []
        key = (st.st_mtime_ns, st.st_size)
        cached = self._cache.get(split)
        if cached is None or cached[0] != key:
            try:
                with open(split, "r", encoding="utf-8") as f:
                    cached = (key, f.read().splitlines())
            except OSError:
                return []
            self._cache[split] = cached
        return cached[1]

    def _read_split(self, split: str) -> tuple:
        """-> (ops, rows): a changelog slice of the split. JSONL/CSV are
        append-only (all Insert); debezium_json carries the CDC envelope's
        ops (reference: src/connector/src/parser/debezium/)."""
        start = self._offsets[split]
        lines = self._lines(split)
        if self.fmt == "csv":
            # header line is line 0 of every csv split; data offsets start at 1
            if start == 0:
                start = 1
            body = lines[start:start + self.rows_per_chunk]
            header = lines[0] if lines else ""
            rows = parse_csv_lines("\n".join([header] + body), self.schema,
                                   has_header=True)
            ops = [OP_INSERT] * len(rows)
        elif self.fmt in ("debezium", "debezium_json"):
            body = lines[start:start + self.rows_per_chunk]
            ops, rows = [], []
            for ln in body:
                try:
                    entries = parse_debezium_line(ln, self.schema)
                except (ValueError, TypeError, KeyError) as e:
                    # poisoned line: skip, still advance — but LOUDLY:
                    # a dropped changelog event (unlike a dropped insert
                    # line) diverges downstream state from the upstream
                    self.dropped_events += 1
                    import sys
                    sys.stderr.write(
                        f"debezium: dropped unparseable event in "
                        f"{split}: {e}\n")
                    continue
                for op, r in entries:
                    ops.append(op)
                    rows.append(r)
        else:
            body = lines[start:start + self.rows_per_chunk]
            rows = []
            for ln in body:
                try:
                    r = parse_json_line(ln, self.schema)
                except (ValueError, TypeError):
                    # malformed line: skip it but still advance the offset
                    # — a poisoned line must not wedge the whole source
                    continue
                if r is not None:
                    rows.append(r)
            ops = [OP_INSERT] * len(rows)
        if body:
            self._offsets[split] = start + len(body)
        return ops, rows

    def next_host_chunk(self) -> Optional[HostChunk]:
        self._discover()
        # most-behind split first: deterministic given offsets alone
        for split in sorted(self._offsets,
                            key=lambda s: (self._offsets[s], s)):
            ops, rows = self._read_split(split)
            if rows:
                phys = [tuple(f.type.to_physical(v) if v is not None else None
                              for f, v in zip(self.schema, r)) for r in rows]
                return host_rows(self.schema, phys, ops=ops,
                                 capacity=max(self.rows_per_chunk,
                                              len(phys)),
                                 physical=True)
        return None
