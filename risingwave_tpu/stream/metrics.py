"""Per-executor streaming metrics.

Counterpart of the reference's executor counters + barrier-latency
histograms (reference: src/stream/src/executor/monitor/streaming_stats.rs:
27-88 — actor/executor row+barrier counters scraped by Prometheus). Design
constraint the reference does not have: a host sync stalls the device
pipeline, so counters only use host-known quantities — chunk counts, chunk
capacities, batch sizes, and wall-clock time spent in barrier handling.
Row-exact cardinalities would require device syncs and are deliberately
absent from the hot path.
"""

from __future__ import annotations

import contextvars
import dataclasses
from typing import Iterator, Optional

from ..common.tracing import (
    CAT_BARRIER, annotation, conductor_epoch, now_ns, record_span, span,
)


@dataclasses.dataclass
class ExecutorStats:
    chunks_in: int = 0            # single chunks received
    batches_in: int = 0           # ChunkBatch messages received
    batch_chunks_in: int = 0      # chunks carried inside batches
    capacity_rows_in: int = 0     # upper bound on rows (sum of capacities)
    chunks_out: int = 0
    barriers: int = 0
    barrier_seconds: float = 0.0  # wall time inside on_barrier handling
    watermarks: int = 0

    def snapshot(self) -> dict:
        return dataclasses.asdict(self)


class _BarrierTimer(span):
    """The ``<identity>.barrier`` span of one executor's barrier
    handling, also counted into its ``ExecutorStats``. The executor runs
    on its job's task, so the parent is named: the conductor's
    ``barrier.collect`` of the same epoch."""

    __slots__ = ("stats",)

    def __init__(self, stats: ExecutorStats, identity: str,
                 epoch: Optional[int], node: Optional[int]):
        super().__init__(f"{identity}.barrier", epoch=epoch,
                         parent="barrier.collect", cat=CAT_BARRIER,
                         tid=identity, node=node)
        self.stats = stats

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        self.stats.barriers += 1
        self.stats.barrier_seconds += self.dur_ns / 1e9
        return False


def barrier_timer(stats: ExecutorStats, identity: str,
                  epoch: Optional[int] = None,
                  node: Optional[int] = None) -> _BarrierTimer:
    """Time one barrier's handling into ``stats`` and the epoch's span
    tree. ``node`` tells two executors of one identity apart (see
    ``number_executors``)."""
    return _BarrierTimer(stats, identity, epoch, node)


class ChunkClock:
    """Host time one executor spends handling an epoch's chunks, rolled up
    into ONE ``<identity>.chunks`` span at the barrier (a span a chunk
    would flood the ring). Only the executor's OWN steps are timed — each
    resumption of its ``map_chunk`` until it hands a chunk on — so a slow
    consumer does not show up in its producer. The span starts where the
    first step did and lasts the summed busy time: the steps are
    disjoint, so it ends no later than the last of them.

    Each step also runs inside a profiler annotation of the roll-up's name
    (``tracing.annotation``: no ring record), so a profiler's trace holds
    the steps themselves under the name the ring knows them by. A step is
    synchronous from ``begin`` to ``end``: it hands over only between
    steps, never inside one — an annotation left open across a suspension
    would take in another task's work."""

    __slots__ = ("stats", "name", "tid", "first_ns", "busy_ns", "_base",
                 "_t0", "_note")

    def __init__(self, stats: ExecutorStats, identity: str):
        self.stats = stats
        self.name = f"{identity}.chunks"
        self.tid = identity
        self.first_ns = 0
        self.busy_ns = 0
        self._base = (0, 0, 0)

    def begin(self) -> None:
        """A step starts (``with clock:`` does the same)."""
        self._note = annotation(self.name, conductor_epoch())
        self._note.__enter__()
        self._t0 = now_ns()

    def end(self) -> None:
        t0 = self._t0
        if not self.busy_ns:
            self.first_ns = t0
        self.busy_ns += now_ns() - t0
        self._note.__exit__(None, None, None)

    __enter__ = begin

    def __exit__(self, *exc) -> bool:
        self.end()
        return False

    def timed(self, steps):
        """Iterate a synchronous generator of output chunks, timing each
        ``next``."""
        it = iter(steps)
        while True:
            with self:
                try:
                    out = next(it)
                except StopIteration:
                    return
            yield out

    async def atimed(self, steps):
        """The same over an async generator (``map_chunk``)."""
        it = steps.__aiter__()
        while True:
            with self:
                try:
                    out = await it.__anext__()
                except StopAsyncIteration:
                    return
            yield out

    def emit(self, epoch: Optional[int], node: Optional[int] = None,
             **args) -> None:
        """``args``: further counts of the epoch the operator keeps itself
        (the hash join's rows in per side, chunks out, rewinds, grows)."""
        st = self.stats
        now = (st.chunks_in + st.batch_chunks_in, st.batches_in,
               st.capacity_rows_in)
        record_span(self.name,
                    self.first_ns if self.busy_ns else now_ns(),
                    self.busy_ns, epoch=epoch, parent="barrier.collect",
                    cat=CAT_BARRIER, tid=self.tid, node=node,
                    chunks=now[0] - self._base[0],
                    batches=now[1] - self._base[1],
                    capacity_rows=now[2] - self._base[2], **args)
        self._base = now
        self.busy_ns = 0


class TaskClock:
    """The clock of one job task: ONE ``actor.run`` span a task and epoch,
    from the task's first resumption in the epoch (its source stamps it
    when the first message after a barrier arrives: ``task_resumed``) to
    the moment the task has passed the epoch's barrier on. A sibling of
    the executors' spans under ``barrier.collect``: ``barrier.collect``
    minus ``actor.run`` is the conductor and the event loop's entry and
    exit, ``actor.run`` minus every ``.chunks`` / ``.barrier`` of the job
    the generator chain, the queues and whatever has no name yet."""

    __slots__ = ("job", "task", "start_ns", "messages")

    def __init__(self, job: str, task: int = 0):
        self.job = job
        self.task = task        # 0: the job's root task; i + 1: actor i
        self.start_ns = 0
        self.messages = 0


#: the running task's clock; a task made inside it (``barrier_align``
#: polls each input from a task of its own) shares the object
_TASK_CLOCK: contextvars.ContextVar = contextvars.ContextVar(
    "rw_task_clock", default=None)


def start_task_clock(job: str, task: int = 0) -> None:
    """Give the running task its clock (first thing a job task does)."""
    _TASK_CLOCK.set(TaskClock(job, task))


def task_resumed() -> None:
    """A source of the running task has a message in hand: the first one
    after a barrier starts the task's ``actor.run``."""
    clock = _TASK_CLOCK.get()
    if clock is not None:
        if not clock.start_ns:
            clock.start_ns = now_ns()
        clock.messages += 1


def task_barrier_passed(epoch: int) -> None:
    """The running task has handed the epoch's barrier on."""
    clock = _TASK_CLOCK.get()
    if clock is None:
        return
    if clock.start_ns:
        record_span("actor.run", clock.start_ns,
                    now_ns() - clock.start_ns, epoch=epoch,
                    parent="barrier.collect", cat=CAT_BARRIER,
                    tid=clock.job, task=clock.task,
                    messages=clock.messages)
    clock.start_ns = clock.messages = 0


def number_executors(root) -> None:
    """Give every executor of a plan its ``node``: its ordinal in
    ``iter_executors`` order (0 is the root). Two executors of one plan
    may share an identity (``Project`` twice in q5, ``HashAgg`` twice in
    q8); span names are a contract and stay, the ``node`` arg of
    ``.chunks`` / ``.barrier`` tells them apart."""
    for node, ex in enumerate(iter_executors(root)):
        ex.node = node


def iter_executors(root) -> Iterator:
    """Walk an executor pipeline (input / left+right / inputs edges)."""
    seen = set()
    stack = [root]
    while stack:
        ex = stack.pop()
        if id(ex) in seen:
            continue
        seen.add(id(ex))
        yield ex
        for attr in ("input", "left", "right"):
            child = getattr(ex, attr, None)
            if child is not None and hasattr(child, "execute"):
                stack.append(child)
        for child in getattr(ex, "inputs", ()) or ():
            if hasattr(child, "execute"):
                stack.append(child)


def pipeline_metrics(root) -> dict:
    """{'<Identity>#<n>': stats_dict} for every executor with stats."""
    out: dict = {}
    counts: dict = {}
    for ex in iter_executors(root):
        stats: Optional[ExecutorStats] = getattr(ex, "stats", None)
        if stats is None:
            continue
        ident = getattr(ex, "identity", type(ex).__name__)
        n = counts.get(ident, 0)
        counts[ident] = n + 1
        out[f"{ident}#{n}" if n else ident] = stats.snapshot()
    return out


class LatencyRecorder:
    """Session-level barrier latency (inject -> collected), reference's
    barrier_latency histogram. Keeps the last ``window`` samples."""

    def __init__(self, window: int = 1024):
        self.window = window
        self.samples: list[float] = []

    def record(self, seconds: float) -> None:
        self.samples.append(seconds)
        if len(self.samples) > self.window:
            del self.samples[: len(self.samples) - self.window]

    def percentile(self, q: float) -> Optional[float]:
        if not self.samples:
            return None
        s = sorted(self.samples)
        i = min(len(s) - 1, int(q / 100.0 * len(s)))
        return s[i]

    def snapshot(self) -> dict:
        return {
            "count": len(self.samples),
            "p50_ms": None if not self.samples else round(
                1e3 * self.percentile(50), 3),
            "p99_ms": None if not self.samples else round(
                1e3 * self.percentile(99), 3),
            "max_ms": None if not self.samples else round(
                1e3 * max(self.samples), 3),
        }
