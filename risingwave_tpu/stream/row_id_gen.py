"""RowIdGenExecutor — serial row ids for chunks that reach a source or a
table ALREADY on the device.

Counterpart of the reference's RowIdGenExecutor
(reference: src/stream/src/executor/row_id_gen.rs; RowId layout
src/common/src/util/row_id.rs — vnode-prefixed monotone ids so ids generated
by parallel source actors never collide). A connector's chunks never come
through here: their ``_row_id`` is made where they are staged
(``common/chunk.stage_chunks``, from the feed's ``RowIdSequence``). What a
test pushes into a reader-less source, and a row-id table's INSERT, is on
the device before anyone could count its rows, so the sequence rides as a
device scalar: one fused step a chunk appends and fills the column, no
host sync.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..common.chunk import RowIdSequence, StreamChunk, append_row_ids
from ..common.types import Schema
from .executor import Executor, SingleInputExecutor


class RowIdGenExecutor(SingleInputExecutor):
    identity = "RowIdGen"

    def __init__(self, input: Executor, out_schema: Schema,
                 row_ids: RowIdSequence):
        super().__init__(input)
        self.schema = out_schema     # the input's + the hidden _row_id
        self.next_id = jnp.asarray(row_ids.take(0), jnp.int64)

    async def map_chunk(self, chunk: StreamChunk):
        self.next_id, out = append_row_ids(self.next_id, chunk)
        yield out
