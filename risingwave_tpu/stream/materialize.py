"""MaterializeExecutor — terminal sink writing MV rows to a StateTable.

Counterpart of the reference's MaterializeExecutor
(reference: src/stream/src/executor/mview/materialize.rs:52). The egress
boundary handles a chunk as COLUMNS: ``map_chunk`` only starts the chunk's
device→host copy (one ``common.fetch.async_fetch`` over the whole pytree,
nothing blocks between two chunks of an epoch) and the barrier resolves
the epoch's copies, takes the visible rows of all of them as one columnar
batch, encodes keys and value rows with the native codec (one call each a
barrier) and stages them whole, in arrival order, as one packed batch
(common/packed.py). Where the codec is absent, or the schema holds a type
it has no code for, the same fetched host chunks go through Python rows.
Conflict handling is overwrite-on-pk, matching the reference's default
HandleConflictBehavior for MVs.

Visibility: a chunk's rows reach the table's uncommitted buffer at the
barrier that closes their epoch (or when ``rows()`` / ``drain()`` asks),
not when the chunk passes; the store-level commit was always the barrier
conductor's.
"""

from __future__ import annotations

import collections
from typing import Optional, Union

import jax
import numpy as np

from ..common.chunk import (
    OP_INSERT, OP_UPDATE_INSERT, StreamChunk, chunk_to_rows,
)
from ..common.fetch import async_fetch
from ..common.packed import PackedBatch
from ..common.tracing import CAT_STORAGE, conductor_epoch, span
from ..native import codec as native_codec
from ..storage.state_table import StateTable
from .executor import Executor, SingleInputExecutor
from .message import Barrier

#: most chunks of one epoch held as pending fetches; an epoch that sends
#: more (a backfill, a recovery replay) stages its oldest inside map_chunk
MAX_PENDING_FETCHES = 64


class MaterializeExecutor(SingleInputExecutor):
    identity = "Materialize"

    def __init__(self, input: Executor, state_table: StateTable):
        super().__init__(input)
        self.schema = input.schema
        self.table = state_table
        self._pending: collections.deque = collections.deque()
        self._counts = dict.fromkeys(
            ("rows_staged", "bytes_fetched", "fetches"), 0)

    def _codec(self):
        """The native codec where it can encode this schema, else None."""
        codec = native_codec()
        if codec is not None and codec.supports(self.table.schema.types):
            return codec
        return None

    async def map_chunk(self, chunk: StreamChunk):
        if len(self._pending) >= MAX_PENDING_FETCHES:
            self.drain(keep=MAX_PENDING_FETCHES - 1, epoch=conductor_epoch(),
                       parent="barrier.collect")
        self._pending.append(async_fetch(chunk))
        self._counts["fetches"] += 1
        yield chunk

    def drain(self, keep: int = 0, *, epoch: Optional[int] = None,
              parent: Union[None, int, str] = None) -> None:
        """Resolve the oldest pending fetches until ``keep`` are left — the
        one wait for the device — and stage their rows in arrival order."""
        with span("materialize.fetch_wait", epoch=epoch, parent=parent,
                  wait="device", tid=self.identity) as wait:
            chunks = [self._pending.popleft().result()
                      for _ in range(len(self._pending) - keep)]
            wait.set(fetches=len(chunks))
        if chunks:
            self._stage(chunks)

    def _stage(self, chunks: list) -> None:
        """Host chunks in arrival order → the table's buffer, equal to
        insert() / delete() row by row."""
        self._counts["bytes_fetched"] += sum(
            x.nbytes for x in jax.tree_util.tree_leaves(chunks))
        codec = self._codec()
        if codec is None:
            for chunk in chunks:
                for op, phys in chunk_to_rows(chunk, self.schema,
                                              with_ops=True, physical=True):
                    self._counts["rows_staged"] += 1
                    if op in (OP_INSERT, OP_UPDATE_INSERT):
                        self.table.insert(phys)
                    else:
                        self.table.delete(phys)
            return
        # one columnar batch of the visible rows, in arrival order
        visible = [np.nonzero(chunk.vis)[0] for chunk in chunks]
        rows = jax.tree_util.tree_map(
            lambda *leaves: np.concatenate(
                [leaf[idx] for leaf, idx in zip(leaves, visible)]), *chunks)
        datas = [col.data for col in rows.columns]
        masks = [col.mask for col in rows.columns]
        types = self.table.schema.types
        pk = self.table.pk_indices
        is_put = (rows.ops == OP_INSERT) | (rows.ops == OP_UPDATE_INSERT)
        # the ordered batch of the barrier, packed: staged whole
        batch = PackedBatch(
            codec.pack_keys(
                [datas[i] for i in pk], [masks[i] for i in pk],
                [types[i] for i in pk], np.arange(len(is_put))),
            codec.pack_value_rows(datas, masks, types,
                                  np.nonzero(is_put)[0]),
            is_put)
        self.table.stage_packed(batch)
        # the batch's one cut into Python bytes, taken HERE, on the barrier
        # its rows arrive at, and kept for the store's commit: a checkpoint
        # barrier then applies ten barriers' views instead of cutting ten
        # barriers' rows (the segment writer still takes the blobs)
        batch.view()
        self._counts["rows_staged"] += len(is_put)

    def epoch_counts(self) -> dict:
        counts = dict(self._counts, native=int(self._codec() is not None))
        self._counts = dict.fromkeys(self._counts, 0)
        return counts

    async def on_barrier(self, barrier: Barrier):
        # table-level seal only: the STORE-level epoch commit belongs to the
        # barrier conductor (Session.tick) after ALL jobs collected the
        # barrier — an executor-side commit raced concurrent jobs' ingests
        # and could strand them pending forever (reference: HummockManager.
        # commit_epoch is driven by meta after barrier collection, not by
        # materialize).
        epoch = barrier.epoch.curr
        self.drain(epoch=epoch)
        with span(f"{self.identity}.seal", epoch=epoch,
                  cat=CAT_STORAGE, tid=self.identity):
            self.table.commit(epoch)
        if False:
            yield

    # -- query surface (batch scan over the MV) ------------------------------

    def scan_all(self):
        """The table's rows with the open epoch's chunks so far — what a
        reader of the MV's own buffer gets (``StateTable.scan_all`` alone
        would miss the chunks whose fetch is still pending)."""
        if self._pending:
            self.drain()
        return self.table.scan_all()

    def rows(self) -> list[tuple]:
        out = []
        for phys in self.scan_all():
            out.append(tuple(
                None if v is None else self.schema[i].type.to_python(v)
                for i, v in enumerate(phys)
            ))
        return out
