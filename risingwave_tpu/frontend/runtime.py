"""Single-process streaming runtime: jobs, queue sources, changelog buses.

Counterpart of the reference's playground-mode compute runtime
(reference: src/cmd_all/src/playground.rs + LocalStreamManager
src/stream/src/task/stream_manager.rs:96 — one process, real executors,
in-memory state store). Jobs are asyncio tasks draining an executor
pipeline into a MaterializeExecutor; epochs are driven centrally by the
Session (the GlobalBarrierManager stand-in), which pushes chunks + barriers
into every job's QueueSources and awaits barrier completion — the same
inject/collect cycle as the reference's checkpoint loop (SURVEY.md §3.2).

MV-on-MV: each job owns a ChangelogBus republishing its post-materialize
messages; downstream jobs subscribe and receive (snapshot chunks, then live
deltas) — the backfill protocol of executor/backfill.rs reduced to the
between-epochs case (the session only creates jobs at epoch boundaries, so
the snapshot is exactly the upstream state at a barrier cut).
"""

from __future__ import annotations

import asyncio
from typing import AsyncIterator, Optional

from ..common.chunk import StreamChunk, physical_chunk
from ..common.types import Schema
from ..storage.state_table import StateTable
from ..stream.dispatch import MsgQueue
from ..stream.executor import Executor
from ..stream.materialize import MaterializeExecutor
from ..stream.message import Barrier, Message, Watermark
from ..stream.metrics import (
    number_executors, start_task_clock, task_barrier_passed,
)


class QueueSource(Executor):
    """Executor fed externally through an asyncio queue."""

    identity = "QueueSource"

    def __init__(self, schema: Schema):
        self.schema = schema
        self.queue = MsgQueue()

    def push(self, msg: Message) -> None:
        self.queue.put_nowait(msg)

    async def execute(self) -> AsyncIterator[Message]:
        while True:
            msg = await self.queue.get()
            if msg is None:      # hard shutdown
                return
            yield msg
            if isinstance(msg, Barrier) and msg.is_stop():
                return


class ChangelogBus:
    """Fan-out of a job's output messages to subscriber queues."""

    def __init__(self) -> None:
        self.subscribers: list[QueueSource] = []

    def publish(self, msg: Message) -> None:
        for q in self.subscribers:
            q.push(msg)

    def subscribe(self, q: QueueSource) -> None:
        self.subscribers.append(q)

    def unsubscribe(self, q: QueueSource) -> None:
        if q in self.subscribers:
            self.subscribers.remove(q)


class StreamJob:
    """One materialized view job: executor pipeline → Materialize → bus."""

    def __init__(self, name: str, pipeline: MaterializeExecutor,
                 sources: list[QueueSource], actors: list = ()):
        self.name = name
        self.pipeline = pipeline
        self.sources = sources
        # extra fragment actors (multi-fragment builds, frontend/fragments):
        # coroutine factories spawned alongside the root pipeline task
        self.actors = list(actors)
        self.bus = ChangelogBus()
        self.table: StateTable = pipeline.table
        self._barrier_events: dict[int, asyncio.Event] = {}
        self._task: Optional[asyncio.Task] = None
        self._actor_tasks: list[asyncio.Task] = []
        self._failure: Optional[BaseException] = None
        number_executors(pipeline)

    def start(self, loop: Optional[asyncio.AbstractEventLoop] = None) -> None:
        for i, factory in enumerate(self.actors):
            self._actor_tasks.append(asyncio.ensure_future(
                self._run_actor(factory, i + 1), loop=loop))
        self._task = asyncio.ensure_future(self._run(), loop=loop)

    async def _run_actor(self, factory, task: int) -> None:
        # the actor passes its barriers on itself (frontend/fragments.py)
        start_task_clock(self.name, task)
        try:
            await factory()
        except asyncio.CancelledError:
            raise
        except BaseException as e:   # noqa: BLE001 - surfaced on next await
            self._failure = e
            for ev in self._barrier_events.values():
                ev.set()
            raise

    async def _run(self) -> None:
        start_task_clock(self.name)
        try:
            async for msg in self.pipeline.execute():
                self.bus.publish(msg)
                if isinstance(msg, Barrier):
                    ev = self._barrier_events.setdefault(
                        msg.epoch.curr, asyncio.Event())
                    ev.set()
                    task_barrier_passed(msg.epoch.curr)
        except BaseException as e:   # noqa: BLE001 - surfaced on next await
            self._failure = e
            for ev in self._barrier_events.values():
                ev.set()
            raise

    async def wait_barrier(self, epoch: int) -> None:
        if self._failure is not None:
            # already dead: epochs injected after the failure have no event
            # to set — waiting would hang the conductor forever
            raise RuntimeError(
                f"stream job {self.name!r} failed") from self._failure
        ev = self._barrier_events.setdefault(epoch, asyncio.Event())
        await ev.wait()
        self._barrier_events.pop(epoch, None)
        if self._failure is not None:
            raise RuntimeError(
                f"stream job {self.name!r} failed") from self._failure

    def snapshot_messages(self, epoch_barrier: Barrier,
                          capacity: int = 1024) -> list[Message]:
        """Initial feed for a new subscriber: current MV rows as insert
        chunks (the backfill snapshot), before live deltas resume."""
        rows = list(self.pipeline.scan_all())
        msgs: list[Message] = []
        for i in range(0, len(rows), capacity):
            msgs.append(physical_chunk(
                self.pipeline.schema, rows[i:i + capacity], capacity))
        return msgs

    async def stop(self) -> None:
        for t in self._actor_tasks:
            t.cancel()
        for t in self._actor_tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        self._actor_tasks.clear()
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
