"""Source connector framework: splits, readers, offset state.

Counterpart of the reference's source framework — ``SplitEnumerator`` /
``SplitReader`` traits and the ``SplitImpl`` state enum
(reference: src/connector/src/source/base.rs:295,326,340;
docs/data-source.md). A *split* is the unit of parallel, seekable ingest
(a Kafka partition, a file, a datagen shard); its *offset* is the
checkpointable read position. The runtime persists ``{split_id: offset}``
per source into a split-state table on checkpoint barriers and seeks
readers on recovery — the reference's split-state checkpointing
(src/stream/src/executor/source/state_table_handler.rs).

TPU angle: readers emit fixed-capacity columnar chunks (static shapes for
XLA) as host columns, staged on the device a barrier at a time; ingest-side
string interning happens here so device columns stay integer-typed.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional

from ..common.chunk import (
    HostChunk, RowIdSequence, StagedCounts, StreamChunk, stage_chunks,
)


def feed_chunks(draw: Callable[[], Optional[HostChunk]], k: int,
                push: Callable[[StreamChunk], None],
                counts: Optional[StagedCounts] = None,
                row_ids: Optional[RowIdSequence] = None) -> List[StreamChunk]:
    """One feed's share of a barrier: draw up to ``k`` host chunks, stage
    them together (one transfer per dtype, one dispatch; with the feed's
    ``row_ids`` the hidden ``_row_id`` column too) and push each.

    A draw advances its reader's offsets, and the next checkpoint persists
    them. So what was drawn is pushed even where a later draw raises (a
    broker fetch out of retries, a file read error): offsets, row ids and
    queue agree on every way out, and the tick that is retried goes on
    from there."""
    host: List[HostChunk] = []
    try:
        for _ in range(k):
            chunk = draw()
            if chunk is not None:
                host.append(chunk)
    finally:
        chunks = stage_chunks(host, counts, row_ids)
        for chunk in chunks:
            push(chunk)
    return chunks


class SplitReader:
    """One source instance: a set of splits read round-robin.

    Offsets are *next-to-read* positions: after ``next_host_chunk`` returns rows
    ``[o, o+n)`` of split s, ``offsets[s] == o+n``. ``seek`` must make the
    subsequent chunks identical to a fresh reader fast-forwarded to the
    same offsets — that determinism is what makes source replay after
    recovery exactly-once end to end.
    """

    def splits(self) -> List[str]:
        raise NotImplementedError

    @property
    def offsets(self) -> Dict[str, int]:
        raise NotImplementedError

    def seek(self, offsets: Dict[str, int]) -> None:
        raise NotImplementedError

    def next_host_chunk(self) -> Optional[HostChunk]:
        """Next chunk's host columns, or None when (currently) exhausted.
        Bounded sources return None forever once drained; unbounded ones
        never return None. Whoever drives the reader stages a barrier's
        host chunks together (common/chunk.stage_chunks)."""
        raise NotImplementedError

    def next_chunk(self) -> Optional[StreamChunk]:
        """``next_host_chunk`` staged on the device, one chunk at a time."""
        host = self.next_host_chunk()
        return None if host is None else stage_chunks([host])[0]

    def rows_emitted(self) -> int:
        """Rows emitted through the current offsets — an upper bound is
        acceptable. Used to restart serial row-id assignment above any id
        handed out before a crash (where a feed's ``RowIdSequence`` goes on
        after recovery)."""
        return sum(self.offsets.values())
