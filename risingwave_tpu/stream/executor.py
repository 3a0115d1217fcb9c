"""Executor protocol: async stream transformers over Messages.

Counterpart of the reference's ``Executor`` trait
(reference: src/stream/src/executor/mod.rs:170-206): every operator is an
async generator of ``Message`` (chunk / barrier / watermark). Barriers flow
through every executor and *must* be yielded after the executor has applied
all chunks of the closing epoch to its state — that ordering is what makes
the barrier a consistent cut (Chandy-Lamport, docs/checkpoint.md).

The TPU twist: an executor's per-chunk work is a jitted, functionally-pure
step over (device_state, chunk) — the async generator is only the host
control loop. Invariant-checking wrappers mirror the reference's
executor/wrapper/{schema_check,epoch_check,update_check}.rs and are enabled
in tests/sim runs.
"""

from __future__ import annotations

from typing import AsyncIterator, Optional, Sequence

from ..common.chunk import (
    ChunkBatch, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk, chunk_to_rows,
)
from ..common.types import Schema
from .message import Barrier, Message, Watermark


class Executor:
    """Base class. ``schema`` describes the output chunks."""

    schema: Schema
    identity: str = "Executor"
    #: ordinal in its plan (``stream/metrics.number_executors``): the
    #: ``node`` arg of the executor's ``.chunks`` / ``.barrier`` spans
    node: Optional[int] = None

    def execute(self) -> AsyncIterator[Message]:
        raise NotImplementedError


class SingleInputExecutor(Executor):
    """Common shape: transform one upstream, pass barriers/watermarks through.

    Subclasses override ``map_chunk`` (1→0..n chunks) and optionally
    ``on_barrier`` (flush state, emit pending output *before* the barrier)."""

    def __init__(self, input: Executor):
        self.input = input
        from .metrics import ExecutorStats
        self.stats = ExecutorStats()

    async def map_chunk(self, chunk: StreamChunk):
        yield chunk

    async def map_chunk_batch(self, batch: ChunkBatch):
        """Batched ingest. Default: unstack and run per-chunk (correct for
        every executor); override with a scanned/vmapped single-dispatch step
        where throughput matters."""
        for i in range(batch.num_chunks):
            async for out in self.map_chunk(batch.at(i)):
                yield out

    async def on_barrier(self, barrier: Barrier):
        if False:  # pragma: no cover - async generator shape
            yield

    async def on_watermark(self, watermark: Watermark):
        yield watermark

    def epoch_counts(self) -> dict:
        """Further args of the epoch's ``<identity>.chunks`` span: counts
        the operator keeps itself, asked for once a barrier, after
        ``on_barrier``."""
        return {}

    async def execute(self) -> AsyncIterator[Message]:
        from .metrics import ChunkClock, barrier_timer
        stats = self.stats
        clock = ChunkClock(stats, self.identity)
        async for msg in self.input.execute():
            if isinstance(msg, StreamChunk):
                stats.chunks_in += 1
                stats.capacity_rows_in += msg.capacity
                async for out in clock.atimed(self.map_chunk(msg)):
                    stats.chunks_out += 1
                    yield out
            elif isinstance(msg, ChunkBatch):
                stats.batches_in += 1
                stats.batch_chunks_in += msg.num_chunks
                stats.capacity_rows_in += msg.num_chunks * msg.chunk_capacity
                async for out in clock.atimed(self.map_chunk_batch(msg)):
                    stats.chunks_out += 1
                    yield out
            elif isinstance(msg, Barrier):
                with barrier_timer(stats, self.identity, msg.epoch.curr,
                                   self.node):
                    outs = [out async for out in self.on_barrier(msg)]
                clock.emit(msg.epoch.curr, self.node, **self.epoch_counts())
                for out in outs:
                    stats.chunks_out += 1
                    yield out
                yield msg
                if msg.is_stop():
                    return
            elif isinstance(msg, Watermark):
                stats.watermarks += 1
                async for out in self.on_watermark(msg):
                    yield out


# ---------------------------------------------------------------------------
# Invariant wrappers (reference: src/stream/src/executor/wrapper/)
# ---------------------------------------------------------------------------


class EpochCheckExecutor(SingleInputExecutor):
    """Barrier epochs must strictly increase (wrapper/epoch_check.rs)."""

    def __init__(self, input: Executor):
        super().__init__(input)
        self.schema = input.schema
        self.identity = input.identity
        self._last_epoch: Optional[int] = None

    async def on_barrier(self, barrier: Barrier):
        if self._last_epoch is not None and barrier.epoch.curr <= self._last_epoch:
            raise AssertionError(
                f"epoch regression: {barrier.epoch.curr} after {self._last_epoch} "
                f"at {self.identity}"
            )
        self._last_epoch = barrier.epoch.curr
        if False:
            yield


class SchemaCheckExecutor(SingleInputExecutor):
    """Every chunk's column count + physical dtypes must match the
    executor's declared schema (wrapper/schema_check.rs) — catches
    builder wiring bugs before they corrupt downstream state."""

    def __init__(self, input: Executor):
        super().__init__(input)
        self.schema = input.schema
        self.identity = input.identity

    async def map_chunk(self, chunk: StreamChunk):
        if len(chunk.columns) != len(self.schema):
            raise AssertionError(
                f"schema check at {self.identity}: chunk has "
                f"{len(chunk.columns)} columns, schema has "
                f"{len(self.schema)}")
        for i, (col, field) in enumerate(zip(chunk.columns, self.schema)):
            want = field.type.dtype
            import jax.numpy as jnp
            if jnp.dtype(col.data.dtype) != jnp.dtype(want):
                raise AssertionError(
                    f"schema check at {self.identity}: column {i} "
                    f"({field.name}) is {col.data.dtype}, schema says "
                    f"{jnp.dtype(want)}")
        yield chunk


class UpdateCheckExecutor(SingleInputExecutor):
    """UpdateDelete must be immediately followed by UpdateInsert within a
    chunk (wrapper/update_check.rs)."""

    def __init__(self, input: Executor):
        super().__init__(input)
        self.schema = input.schema
        self.identity = input.identity

    async def map_chunk(self, chunk: StreamChunk):
        rows = chunk_to_rows(chunk, self.schema, with_ops=True)
        pending_ud = False
        for op, _ in rows:
            if pending_ud and op != OP_UPDATE_INSERT:
                raise AssertionError(f"U- not followed by U+ at {self.identity}")
            pending_ud = op == OP_UPDATE_DELETE
        if pending_ud:
            raise AssertionError(f"chunk ends with dangling U- at {self.identity}")
        yield chunk


def wrap_debug(executor: Executor) -> Executor:
    """Compose the sanity wrappers (debug/sim runs)."""
    return EpochCheckExecutor(UpdateCheckExecutor(executor))


async def collect_until_barrier(stream, n_barriers: int = 1):
    """Test helper: drain messages until the n-th barrier; returns (chunks,
    barriers, watermarks)."""
    chunks: list[StreamChunk] = []
    barriers: list[Barrier] = []
    watermarks: list[Watermark] = []
    async for msg in stream:
        if isinstance(msg, StreamChunk):
            chunks.append(msg)
        elif isinstance(msg, Barrier):
            barriers.append(msg)
            if len(barriers) >= n_barriers:
                break
        else:
            watermarks.append(msg)
    return chunks, barriers, watermarks
