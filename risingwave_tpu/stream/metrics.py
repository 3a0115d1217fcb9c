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

import dataclasses
from typing import Iterator, Optional

from ..common.tracing import CAT_BARRIER, now_ns, record_span, span


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
                 epoch: Optional[int]):
        super().__init__(f"{identity}.barrier", epoch=epoch,
                         parent="barrier.collect", cat=CAT_BARRIER,
                         tid=identity)
        self.stats = stats

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        self.stats.barriers += 1
        self.stats.barrier_seconds += self.dur_ns / 1e9
        return False


def barrier_timer(stats: ExecutorStats, identity: str,
                  epoch: Optional[int] = None) -> _BarrierTimer:
    """Time one barrier's handling into ``stats`` and the epoch's span
    tree."""
    return _BarrierTimer(stats, identity, epoch)


class ChunkClock:
    """Host time one executor spends handling an epoch's chunks, rolled up
    into ONE ``<identity>.chunks`` span at the barrier (a span a chunk
    would flood the ring). Only the executor's OWN steps are timed — each
    resumption of its ``map_chunk`` until it hands a chunk on — so a slow
    consumer does not show up in its producer. The span starts where the
    first step did and lasts the summed busy time: the steps are
    disjoint, so it ends no later than the last of them."""

    __slots__ = ("stats", "first_ns", "busy_ns", "_base")

    def __init__(self, stats: ExecutorStats):
        self.stats = stats
        self.first_ns = 0
        self.busy_ns = 0
        self._base = (0, 0, 0)

    def add(self, t0: int) -> None:
        if not self.busy_ns:
            self.first_ns = t0
        self.busy_ns += now_ns() - t0

    def timed(self, steps):
        """Iterate a synchronous generator of output chunks, timing each
        ``next``."""
        it = iter(steps)
        while True:
            t0 = now_ns()
            try:
                out = next(it)
            except StopIteration:
                self.add(t0)
                return
            self.add(t0)
            yield out

    async def atimed(self, steps):
        """The same over an async generator (``map_chunk``)."""
        it = steps.__aiter__()
        while True:
            t0 = now_ns()
            try:
                out = await it.__anext__()
            except StopAsyncIteration:
                self.add(t0)
                return
            self.add(t0)
            yield out

    def emit(self, identity: str, epoch: Optional[int], **args) -> None:
        """``args``: further counts of the epoch the operator keeps itself
        (the hash join's rows in per side, chunks out, rewinds, grows)."""
        st = self.stats
        now = (st.chunks_in + st.batch_chunks_in, st.batches_in,
               st.capacity_rows_in)
        record_span(f"{identity}.chunks",
                    self.first_ns if self.busy_ns else now_ns(),
                    self.busy_ns, epoch=epoch, parent="barrier.collect",
                    cat=CAT_BARRIER, tid=identity,
                    chunks=now[0] - self._base[0],
                    batches=now[1] - self._base[1],
                    capacity_rows=now[2] - self._base[2], **args)
        self._base = now
        self.busy_ns = 0


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
