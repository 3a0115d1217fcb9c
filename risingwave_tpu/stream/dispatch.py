"""Dispatchers + permit-based exchange channels + merge fan-in.

Counterparts of the reference's actor delivery fabric:
  * dispatchers (reference: src/stream/src/executor/dispatch.rs — Hash
    :532, Broadcast :715, Simple :798, RoundRobin :455), including the
    update-pair rule at dispatch.rs:635-650: an UpdateDelete/UpdateInsert
    pair whose key moves across outputs is degraded to Delete+Insert;
  * permit-based backpressure channels (reference:
    exchange/permit.rs:35-107 — bounded budget for data, barriers always
    admitted so the control stream can never deadlock behind data);
  * merge fan-in with barrier alignment (reference: executor/merge.rs:114
    SelectReceivers — forward data freely, hold each upstream's barrier
    until ALL upstreams produced the epoch's barrier).

TPU angle: the hash split is computed on device for the whole chunk (one
vnode hash + per-output visibility masks — no row loop); only the
channel plumbing is host asyncio.
"""

from __future__ import annotations

import asyncio
from typing import AsyncIterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
)
from ..common.hashing import vnode_of, vnode_to_shard
from ..common.types import Schema
from .executor import Executor
from .message import Barrier, Message, Watermark
from .metrics import task_resumed


class MsgQueue:
    """Single-consumer unbounded message queue.

    Functionally asyncio.Queue(put_nowait/get), minus one asyncio.Queue
    wart this runtime keeps hitting: Queue.get's cleanup path calls
    ``getter.cancel()`` → ``loop.call_soon`` even when finalized by GC
    AFTER the owning loop closed, spraying "Event loop is closed"
    unraisable warnings whenever an abandoned executor generator (job
    stop/reschedule leaves them suspended in get()) is collected late.
    This get() awaits a bare future and only clears it in ``finally`` —
    no loop interaction on finalization, so late GC is silent."""

    def __init__(self) -> None:
        import collections
        self._items: collections.deque = collections.deque()
        self._waiter: Optional[asyncio.Future] = None

    def put_nowait(self, item) -> None:
        self._items.append(item)
        w = self._waiter
        if w is not None and not w.done():
            w.set_result(None)

    async def put(self, item) -> None:
        # unbounded: never blocks (PermitChannel does its own flow
        # control with a semaphore before calling this)
        self.put_nowait(item)

    async def get(self):
        while not self._items:
            self._waiter = asyncio.get_running_loop().create_future()
            try:
                await self._waiter
            finally:
                self._waiter = None
        # the consuming task has a message in hand: the first one after a
        # barrier starts its ``actor.run`` (stream/metrics.TaskClock)
        task_resumed()
        return self._items.popleft()

    def qsize(self) -> int:
        return len(self._items)

    def empty(self) -> bool:
        return not self._items


class PermitChannel:
    """Bounded exchange edge. Data messages consume permits (one per chunk
    of capacity rows — the reference counts cardinality; capacity is the
    host-known stand-in) and block the SENDER when the budget is
    exhausted; barriers and watermarks always pass (control never queues
    behind data)."""

    def __init__(self, permits: int = 32):
        self._sem = asyncio.Semaphore(permits)
        self._q = MsgQueue()
        self.permits = permits

    async def send(self, msg: Message) -> None:
        if isinstance(msg, StreamChunk):
            await self._sem.acquire()
            await self._q.put(("data", msg))
        else:
            await self._q.put(("ctl", msg))

    async def recv(self) -> Message:
        kind, msg = await self._q.get()
        if kind == "data":
            self._sem.release()
        return msg

    def close(self) -> None:
        self._q.put_nowait(("ctl", None))


def open_channel(permits: int = 32) -> PermitChannel:
    """THE way to obtain an exchange channel outside this module. Every
    exchange edge — in-process fragment fabric, worker-local span edges —
    goes through here so flow-control policy stays in one place
    (scripts/check.sh lints direct ``PermitChannel(...)`` construction
    outside the fabric the same way raw object-store opens are linted)."""
    return PermitChannel(permits)


class ChannelSource(Executor):
    """Executor view of a PermitChannel's receiving end."""

    identity = "ChannelSource"

    def __init__(self, channel: PermitChannel, schema: Schema):
        self.channel = channel
        self.schema = schema

    async def execute(self) -> AsyncIterator[Message]:
        while True:
            msg = await self.channel.recv()
            if msg is None:
                return
            yield msg
            if isinstance(msg, Barrier) and msg.is_stop():
                return


class HashDispatcher:
    """Route each row to ``vnode → shard`` output; barriers/watermarks
    broadcast. The whole split is one jitted device step producing one
    visibility mask per output."""

    def __init__(self, outputs: Sequence[PermitChannel],
                 key_cols: Sequence[int], schema: Schema):
        self.outputs = list(outputs)
        self.key_cols = tuple(key_cols)
        n_out = len(self.outputs)

        @jax.jit
        def _split(chunk: StreamChunk):
            cols = [chunk.columns[i] for i in self.key_cols]
            shard = vnode_to_shard(vnode_of(cols), n_out)
            ops = chunk.ops
            # update-pair splitting (dispatch.rs:635-650): if U- and its
            # U+ land on different shards, both degrade to plain ops
            is_ud = ops == OP_UPDATE_DELETE
            is_ui = ops == OP_UPDATE_INSERT
            partner_shard = jnp.roll(shard, -1)       # U- partner follows
            partner_shard_prev = jnp.roll(shard, 1)   # U+ partner precedes
            split_pair = (is_ud & (partner_shard != shard)) | (
                is_ui & (partner_shard_prev != shard))
            new_ops = jnp.where(
                split_pair & is_ud, OP_DELETE,
                jnp.where(split_pair & is_ui, OP_INSERT, ops),
            ).astype(ops.dtype)
            masks = tuple(
                chunk.vis & (shard == o) for o in range(n_out))
            return new_ops, masks

        self._split = _split

    async def dispatch(self, msg: Message) -> None:
        if isinstance(msg, StreamChunk):
            new_ops, masks = self._split(msg)
            rebased = msg.replace(ops=new_ops)
            for out, mask in zip(self.outputs, masks):
                await out.send(rebased.with_vis(mask))
            return
        from ..common.chunk import ChunkBatch
        if isinstance(msg, ChunkBatch):
            # data must be split, never broadcast: unpack the batch
            for i in range(msg.num_chunks):
                await self.dispatch(msg.at(i))
            return
        for out in self.outputs:
            await out.send(msg)


class BroadcastDispatcher:
    def __init__(self, outputs: Sequence[PermitChannel]):
        self.outputs = list(outputs)

    async def dispatch(self, msg: Message) -> None:
        for out in self.outputs:
            await out.send(msg)


class RoundRobinDispatcher:
    """Chunk-granular round robin (reference :455 — used for stateless
    fragments where row placement is free)."""

    def __init__(self, outputs: Sequence[PermitChannel]):
        self.outputs = list(outputs)
        self._i = 0

    async def dispatch(self, msg: Message) -> None:
        if isinstance(msg, StreamChunk):
            out = self.outputs[self._i % len(self.outputs)]
            self._i += 1
            await out.send(msg)
        else:
            for out in self.outputs:
                await out.send(msg)


class SimpleDispatcher(BroadcastDispatcher):
    """1:1 pipe (reference :798 / NoShuffle)."""

    def __init__(self, output: PermitChannel):
        super().__init__([output])


class MergeExecutor(Executor):
    """N-ary fan-in with barrier alignment: chunks forward as they arrive;
    an upstream that produced the epoch's barrier is parked until every
    upstream has. Watermarks are ALIGNED per column: the merge forwards
    the minimum over all upstreams, and only once every live upstream has
    reported one for that column (reference: BufferedWatermarks in
    executor/merge.rs — a fan-in must not let one shard's watermark
    overtake another shard's still-buffered rows below it)."""

    identity = "Merge"

    def __init__(self, channels: Sequence[PermitChannel], schema: Schema):
        self.channels = list(channels)
        self.schema = schema
        # col_idx -> {channel_idx: latest value}; col_idx -> last forwarded
        self._wm: dict[int, dict[int, object]] = {}
        self._wm_sent: dict[int, object] = {}

    def _on_watermark(self, i: int, wm: Watermark,
                      finished: set) -> Optional[Watermark]:
        per_chan = self._wm.setdefault(wm.col_idx, {})
        per_chan[i] = wm.value
        live = [j for j in range(len(self.channels)) if j not in finished]
        if not all(j in per_chan for j in live):
            return None
        lo = min(per_chan[j] for j in live)
        if wm.col_idx in self._wm_sent and lo <= self._wm_sent[wm.col_idx]:
            return None
        self._wm_sent[wm.col_idx] = lo
        return Watermark(wm.col_idx, lo)

    async def execute(self) -> AsyncIterator[Message]:
        n = len(self.channels)
        held: dict[int, Barrier] = {}
        finished: set[int] = set()
        pending: dict[int, asyncio.Task] = {}
        try:
            while True:
                for i, ch in enumerate(self.channels):
                    if i not in pending and i not in finished and i not in held:
                        pending[i] = asyncio.ensure_future(ch.recv())
                if not pending and not held:
                    return
                if pending:
                    done, _ = await asyncio.wait(
                        pending.values(),
                        return_when=asyncio.FIRST_COMPLETED)
                    for i in list(pending):
                        task = pending[i]
                        if task not in done:
                            continue
                        del pending[i]
                        msg = task.result()
                        if msg is None:
                            finished.add(i)
                        elif isinstance(msg, Barrier):
                            held[i] = msg
                        elif isinstance(msg, Watermark):
                            out = self._on_watermark(i, msg, finished)
                            if out is not None:
                                yield out
                        else:
                            yield msg
                live = [i for i in range(n) if i not in finished]
                if live and all(i in held for i in live):
                    epochs = {held[i].epoch.curr for i in live}
                    if len(epochs) != 1:
                        raise AssertionError(
                            f"barrier misalignment at merge: {sorted(epochs)}")
                    barrier = held[next(iter(live))]
                    held.clear()
                    yield barrier
                    if barrier.is_stop():
                        return
                if not live:
                    return
        finally:
            for task in pending.values():
                task.cancel()
