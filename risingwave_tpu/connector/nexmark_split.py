"""NEXmark as a seekable split source.

The reference's NEXmark connector partitions the event stream into splits
by ``event_id % n_splits`` (reference:
src/connector/src/source/nexmark/split.rs, source/reader.rs:41). Here the
generator is already vectorized (connector/nexmark.py) and deterministic
given (seed, chunk index), so a single split with offset = number of
emitted chunks suffices for checkpointing; ``seek`` replays the generator's
host columns to the offset (cheap: vectorized generation, no IO, and
nothing of a replayed chunk goes to the device).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from ..common.chunk import HostChunk
from .base import SplitReader
from .nexmark import (
    AUCTION_SCHEMA, BID_SCHEMA, PERSON_SCHEMA, NexmarkConfig, NexmarkGenerator,
)

_SCHEMAS = {"bid": BID_SCHEMA, "auction": AUCTION_SCHEMA,
            "person": PERSON_SCHEMA}


class NexmarkReader(SplitReader):
    def __init__(self, table: str, chunk_capacity: int = 1024,
                 seed: int = 42):
        self.table = table.lower()
        self.schema = _SCHEMAS[self.table]
        self.chunk_capacity = chunk_capacity
        self.seed = seed
        self._gen = NexmarkGenerator(
            NexmarkConfig(chunk_capacity=chunk_capacity), seed=seed)
        self._n = 0

    def _columns(self):
        """The generator's host-column draw of this reader's table."""
        return getattr(self._gen, f"{self.table}_columns")

    def splits(self) -> List[str]:
        return ["0"]

    @property
    def offsets(self) -> Dict[str, int]:
        return {"0": self._n}

    def seek(self, offsets: Dict[str, int]) -> None:
        target = int(offsets.get("0", 0))
        if target < self._n:
            self._gen = NexmarkGenerator(
                NexmarkConfig(chunk_capacity=self.chunk_capacity),
                seed=self.seed)
            self._n = 0
        # only the rng has to advance: draw the host columns, stage none
        columns = self._columns()
        while self._n < target:
            columns(self.chunk_capacity)
            self._n += 1

    def rows_emitted(self) -> int:
        return self._n * self.chunk_capacity

    def next_host_chunk(self) -> Optional[HostChunk]:
        cap = self.chunk_capacity
        arrays = self._columns()(cap)
        self._n += 1
        # no NEXmark column is ever null and the source is append-only:
        # masks, vis and ops all follow from n on the device
        return HostChunk(self.schema, arrays, cap, cap)
