"""Project and Filter executors (stateless).

Counterparts of the reference's ProjectExecutor / FilterExecutor
(reference: src/stream/src/executor/project.rs, executor/filter.rs). Both are
single jitted device steps; Filter keeps ops consistent for Update pairs the
same way the reference does — if a filter flips visibility across a U-/U+
pair, the pair degrades to a plain Delete/Insert (filter.rs apply logic).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from ..common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, Column,
    StreamChunk,
)
from ..common.types import Field, Schema
from ..expr import Expr
from ..expr.expr import FunctionCall, InputRef, Literal
from .executor import Executor, SingleInputExecutor
from .message import Watermark

# Expressions through which a watermark can be derived: monotone in the
# watermark column (reference: watermark derivation over exprs,
# src/frontend/src/optimizer/property/watermark_columns.rs + stream project's
# watermark derivation). tumble_start is the load-bearing one — it carries
# source watermarks onto window-start group keys for state cleaning.
_MONOTONE_FNS = {"tumble_start"}


def derive_watermark(expr: Expr, wm: Watermark):
    """Map an input watermark through one output expression; None if the
    expression does not preserve the watermark order."""
    if isinstance(expr, InputRef):
        return wm.value if expr.index == wm.col_idx else None
    if (isinstance(expr, FunctionCall) and expr.name in _MONOTONE_FNS
            and expr.args and isinstance(expr.args[0], InputRef)
            and expr.args[0].index == wm.col_idx
            and all(isinstance(a, Literal) for a in expr.args[1:])):
        # evaluate the monotone fn on the watermark value via a 1-row chunk
        # (only the watermark column is ever read by the expression)
        cols = tuple(
            Column(jnp.full(1, wm.value if i == wm.col_idx else 0, jnp.int64),
                   jnp.ones(1, jnp.bool_))
            for i in range(wm.col_idx + 1))
        one = StreamChunk(jnp.zeros(1, jnp.int8), jnp.ones(1, jnp.bool_), cols)
        res = expr.eval(one)
        if bool(res.mask[0]):
            return res.data[0].item()
    return None


class ProjectExecutor(SingleInputExecutor):
    identity = "Project"

    def __init__(self, input: Executor, exprs: Sequence[Expr],
                 names: Sequence[str] = ()):
        super().__init__(input)
        self.exprs = tuple(exprs)
        names = tuple(names) or tuple(f"expr{i}" for i in range(len(exprs)))
        self.schema = Schema(tuple(Field(n, e.type) for n, e in zip(names, self.exprs)))

        def _step(chunk: StreamChunk) -> StreamChunk:
            cols = tuple(e.eval(chunk) for e in self.exprs)
            return chunk.with_columns(cols)

        from ..expr.expr import uses_host_callback
        if any(uses_host_callback(e) for e in self.exprs):
            # string functions compute on the host dictionary over
            # concrete arrays (expr/expr.py) and cannot run inside a
            # trace — run the step eagerly
            self._step = _step
            self._step_batch = None
        else:
            self._step = jax.jit(_step)
            self._step_batch = jax.jit(jax.vmap(_step))

    async def map_chunk(self, chunk: StreamChunk):
        yield self._step(chunk)

    async def map_chunk_batch(self, batch):
        if self._step_batch is None:
            async for out in super().map_chunk_batch(batch):
                yield out
            return
        from ..common.chunk import ChunkBatch
        yield ChunkBatch(self._step_batch(batch.chunk))

    async def on_watermark(self, watermark: Watermark):
        for i, e in enumerate(self.exprs):
            v = derive_watermark(e, watermark)
            if v is not None:
                yield Watermark(i, v)


class FilterExecutor(SingleInputExecutor):
    identity = "Filter"

    def __init__(self, input: Executor, predicate: Expr):
        super().__init__(input)
        self.schema = input.schema
        self.predicate = predicate

        # a name of its own: a profiler's trace and the idle-gap labels
        # tell jit_filter_step from Project's jit__step
        def filter_step(chunk: StreamChunk) -> StreamChunk:
            cond = predicate.eval(chunk)
            keep = cond.data & cond.mask  # NULL -> filtered out (SQL WHERE)
            # Degrade broken update pairs to Insert/Delete: a U- whose U+ was
            # filtered (or vice versa) must not dangle
            # (reference: filter.rs / dispatch.rs:635-650 pairing rules).
            ops = chunk.ops
            is_ud = ops == OP_UPDATE_DELETE
            is_ui = ops == OP_UPDATE_INSERT
            partner_kept = jnp.roll(keep, -1)  # for U- rows: their U+ follows
            partner_kept_prev = jnp.roll(keep, 1)  # for U+ rows: their U- precedes
            new_ops = jnp.where(
                is_ud & ~partner_kept, OP_DELETE,
                jnp.where(is_ui & ~partner_kept_prev, OP_INSERT, ops),
            ).astype(ops.dtype)
            return chunk.replace(ops=new_ops, vis=chunk.vis & keep)

        from ..expr.expr import uses_host_callback
        if uses_host_callback(predicate):
            self._step = filter_step    # eager: see ProjectExecutor note
            self._step_batch = None
        else:
            self._step = jax.jit(filter_step)
            self._step_batch = jax.jit(jax.vmap(filter_step))

    async def map_chunk(self, chunk: StreamChunk):
        yield self._step(chunk)

    async def map_chunk_batch(self, batch):
        if self._step_batch is None:
            async for out in super().map_chunk_batch(batch):
                yield out
            return
        from ..common.chunk import ChunkBatch
        yield ChunkBatch(self._step_batch(batch.chunk))
