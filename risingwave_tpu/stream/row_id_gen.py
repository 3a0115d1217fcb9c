"""RowIdAppendExecutor + RowIdGenExecutor — make room for, then assign,
serial row ids on source rows.

Counterpart of the reference's RowIdGenExecutor
(reference: src/stream/src/executor/row_id_gen.rs; RowId layout
src/common/src/util/row_id.rs — vnode-prefixed monotone ids so ids generated
by parallel source actors never collide). Here: id = shard_id << 48 | seq,
seq a device counter bumped per visible row — one fused step, no host sync.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..common.chunk import Column, StreamChunk
from .executor import Executor, SingleInputExecutor
from .message import Barrier
from .metrics import ChunkClock, ExecutorStats, barrier_timer


class RowIdAppendExecutor(Executor):
    """Wraps a queue of connector chunks, appending the hidden _row_id
    column's slot (reference: source executors append the row-id column
    before RowIdGen fills it). ``inner`` is no plan edge: the queue under
    it stays bare (its own work is a ``queue.get()``). Timed like any
    executor: ``RowIdAppend.chunks`` / ``.barrier``."""

    identity = "RowIdAppend"

    def __init__(self, inner: Executor, out_schema):
        self.inner = inner
        self.schema = out_schema
        self.stats = ExecutorStats()

    async def execute(self):
        stats = self.stats
        clock = ChunkClock(stats, self.identity)
        async for msg in self.inner.execute():
            if isinstance(msg, StreamChunk):
                stats.chunks_in += 1
                stats.capacity_rows_in += msg.capacity
                with clock:
                    cap = msg.capacity
                    msg = msg.append_columns((Column(
                        jnp.zeros(cap, jnp.int64),
                        jnp.ones(cap, jnp.bool_)),))
                stats.chunks_out += 1
            elif isinstance(msg, Barrier):
                with barrier_timer(stats, self.identity, msg.epoch.curr,
                                   self.node):
                    pass
                clock.emit(msg.epoch.curr, self.node)
            yield msg
            if isinstance(msg, Barrier) and msg.is_stop():
                return


class RowIdGenExecutor(SingleInputExecutor):
    identity = "RowIdGen"

    def __init__(self, input: Executor, row_id_index: int, shard_id: int = 0,
                 start_seq: int = 0):
        super().__init__(input)
        self.schema = input.schema
        self.row_id_index = row_id_index
        self.seq = jnp.asarray(start_seq, jnp.int64)
        base = jnp.int64(shard_id) << 48

        @jax.jit
        def _step(seq, chunk: StreamChunk):
            vis = chunk.vis
            offset = jnp.cumsum(vis) - vis.astype(jnp.int64)
            ids = base | (seq + offset)
            cols = list(chunk.columns)
            cols[row_id_index] = Column(ids, jnp.ones_like(vis))
            return seq + jnp.sum(vis), chunk.with_columns(cols)

        self._step = _step

    async def map_chunk(self, chunk: StreamChunk):
        self.seq, out = self._step(self.seq, chunk)
        yield out
