"""MaterializeExecutor — terminal sink writing MV rows to a StateTable.

Counterpart of the reference's MaterializeExecutor
(reference: src/stream/src/executor/mview/materialize.rs:52). The egress
boundary is where device chunks become host rows (one device_get per chunk);
everything upstream stayed on device. Conflict handling is overwrite-on-pk,
matching the reference's default HandleConflictBehavior for MVs.
"""

from __future__ import annotations

from typing import AsyncIterator

from ..common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
    chunk_to_rows,
)
from ..storage.state_table import StateTable
from .executor import Executor, SingleInputExecutor
from .message import Barrier


class MaterializeExecutor(SingleInputExecutor):
    identity = "Materialize"

    def __init__(self, input: Executor, state_table: StateTable):
        super().__init__(input)
        self.schema = input.schema
        self.table = state_table

    async def map_chunk(self, chunk: StreamChunk):
        for op, phys in chunk_to_rows(chunk, self.schema, with_ops=True,
                                      physical=True):
            if op in (OP_INSERT, OP_UPDATE_INSERT):
                self.table.insert(phys)
            else:
                self.table.delete(phys)
        yield chunk

    async def on_barrier(self, barrier: Barrier):
        # table-level seal only: the STORE-level epoch commit belongs to the
        # barrier conductor (Session.tick) after ALL jobs collected the
        # barrier — an executor-side commit raced concurrent jobs' ingests
        # and could strand them pending forever (reference: HummockManager.
        # commit_epoch is driven by meta after barrier collection, not by
        # materialize).
        from ..common.tracing import CAT_STORAGE, span
        with span(f"{self.identity}.seal", epoch=barrier.epoch.curr,
                  cat=CAT_STORAGE, tid=self.identity):
            self.table.commit(barrier.epoch.curr)
        if False:
            yield

    # -- query surface (batch scan over the MV) ------------------------------

    def rows(self) -> list[tuple]:
        out = []
        for phys in self.table.scan_all():
            out.append(tuple(
                None if v is None else self.schema[i].type.to_python(v)
                for i, v in enumerate(phys)
            ))
        return out
