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
import time
from typing import Iterator, Optional

from ..common.tracing import CAT_BARRIER, GLOBAL_TRACE, Span


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


class _BarrierTimer:
    __slots__ = ("stats", "identity", "epoch", "_t0", "_ts")

    def __init__(self, stats: ExecutorStats, identity: Optional[str] = None,
                 epoch: Optional[int] = None):
        self.stats = stats
        self.identity = identity
        self.epoch = epoch

    def __enter__(self):
        self._ts = time.time()
        self._t0 = time.perf_counter()
        self.stats.barriers += 1
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        self.stats.barrier_seconds += dur
        if self.identity is not None:
            # the tracing seam: every identified barrier timing doubles as
            # a per-executor span in the epoch's trace tree
            GLOBAL_TRACE.record(Span(
                f"{self.identity}.barrier", CAT_BARRIER, self._ts, dur,
                epoch=self.epoch, tid=self.identity))
        return False


def barrier_timer(stats: ExecutorStats, identity: Optional[str] = None,
                  epoch: Optional[int] = None) -> _BarrierTimer:
    """Time one barrier's handling into ``stats``; with ``identity`` (and
    ideally ``epoch``) the timing is also recorded as a tracing span."""
    return _BarrierTimer(stats, identity, epoch)


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
