"""Barrier observatory: per-barrier lifecycle ledger + stage events.

The paper's consistency spine is the Chandy-Lamport barrier (inject at
the conductor, collect across actors, 2PC checkpoint commit), and this
module makes every injected barrier individually accountable: a
cluster-wide waterfall record per epoch — inject → per-worker collect →
checkpoint prepare/settle/commit → sink delivery — kept in a bounded
history ring with p50/p99 per-stage aggregates (reference: the barrier
manager's inflight tracking + rw_catalog barrier tables,
src/meta/src/barrier/mod.rs:152 and
src/frontend/src/catalog/system_catalog/rw_catalog/).

Two pieces:

* ``StageEventLog`` — a process-global, bounded log of epoch-stamped
  stage events, written at the 2PC sites (storage/checkpoint.py,
  worker/host.py handle_barrier, stream/sink.py). In worker processes
  the log is drained onto the existing ``stats`` reply (a
  ``barrier_stages`` key with the same seq/ack outbox discipline as the
  span outbox), so stage events ride frames the session already sends —
  zero added dispatches, zero extra RPCs, nothing on the critical tick
  path beyond a clock delta and a list append. Events are written by
  ``common/tracing.span(..., stage=...)``: a stage IS a span's duration.

* ``BarrierLedger`` — the session-owned history ring. Every stage folds
  in from the stage-event logs (the session's own, synchronously at
  barrier completion — so a stage recorded BEFORE the record opens, the
  source feed or the fused epoch's dispatch, still reaches it; the
  workers', via stats federation — late events find their record in the
  ring and attach there). Only ``pending`` is recorded directly: it is
  the gap between two spans, not a span. A record also carries
  ``tick_ms``, the whole of the ``Session.tick()`` call that injected the
  barrier, and ``compiles``, the XLA compilations that ran inside it.

Stage vocabulary (stable: Prometheus labels and rw_catalog columns key
on it):

    inject            conductor: queue pushes + remote barrier frames
    pending           conductor: injected, waiting its turn to complete
    collect           conductor: awaiting every actor/worker ack
    commit            conductor: cluster checkpoint phase 2
    storage_prepare   any process: DurableStateStore.prepare (phase 1)
    storage_settle    any process: prepared→committed settle
    storage_commit    any process: segment append (epoch encode+publish)
    sink_deliver      sink executor: external delivery inside on_barrier
    worker_collect    worker conductor: its jobs' barrier collection
    source_feed       conductor: every source generates + queues its chunks
    epoch_dispatch    conductor: fused epochs + flush probes enqueued
    epoch_wait        conductor: waiting on the device for the flush fetch
    flush_decode      conductor: flush gathers pushed to the MV queues
    state_delta       any process: an agg's state-table delta (checkpoint)

The last five lie OUTSIDE ``total_ms`` on the co-scheduled path (the
fused epoch runs before the barrier is injected) and inside ``collect``
on the executor path (``state_delta`` only); ``tick_ms`` spans them all.
"""

from __future__ import annotations

import collections
import threading
from typing import Optional

#: conductor-side stages whose sum reconciles with the epoch's total
#: barrier latency (inject is measured before the latency clock starts)
CONDUCTOR_STAGES = ("pending", "collect", "commit")

#: every stage the ledger may see, in waterfall order
ALL_STAGES = ("inject", "pending", "collect", "commit",
              "storage_prepare", "storage_settle", "storage_commit",
              "sink_deliver", "worker_collect",
              "source_feed", "epoch_dispatch", "epoch_wait", "flush_decode",
              "state_delta")


#: per-record counters an event may bump (``StageEventLog.count``)
COUNTERS = ("compiles",)


class StageEventLog:
    """Process-global bounded log of ``{epoch, stage, ms}`` events with a
    seq/ack outbox for cross-process federation (mirrors the tracing-span
    outbox: a drained batch is retained until the session's next stats
    request acknowledges its sequence number)."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self._events: collections.deque = collections.deque(maxlen=capacity)
        self._outbox: list = []
        self.seq = 0
        self._lock = threading.Lock()

    def record(self, epoch: int, stage: str, ms: float) -> None:
        with self._lock:
            self._events.append(
                {"epoch": int(epoch), "stage": stage, "ms": float(ms)})

    def count(self, epoch: int, counter: str, n: int = 1) -> None:
        """One occurrence of something countable inside an epoch (a
        compile): folds into the record's counter of that name."""
        with self._lock:
            self._events.append(
                {"epoch": int(epoch), "counter": counter, "n": int(n)})

    def drain(self) -> list:
        """Take-and-clear — the session consumes its own log this way at
        barrier completion."""
        with self._lock:
            out = list(self._events)
            self._events.clear()
        return out

    def drain_outbox(self, ack: Optional[int] = None) -> tuple:
        """Worker side of federation: move fresh events into the retained
        outbox, clear it when ``ack`` matches the last shipped seq, and
        return ``(seq, events)`` for the stats reply."""
        with self._lock:
            if ack == self.seq:
                self._outbox = []
            fresh = list(self._events)
            self._events.clear()
            if fresh:
                self._outbox.extend(fresh)
                if len(self._outbox) > self.capacity:
                    del self._outbox[:-self.capacity]
                self.seq += 1
            return self.seq, list(self._outbox)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._outbox = []


#: the per-process stage-event log every 2PC site writes to
GLOBAL_STAGES = StageEventLog()


def record_stage(epoch: Optional[int], stage: str, ms: float) -> None:
    """Record one stage duration against an epoch (no-op without one —
    e.g. a store commit outside barrier conduction)."""
    if epoch is None or epoch <= 0:
        return
    GLOBAL_STAGES.record(epoch, stage, ms)


class BarrierLedger:
    """Session-owned bounded history ring of per-barrier waterfall
    records, plus per-stage p50/p99 aggregates.

    A record::

        {"epoch": int, "checkpoint": bool, "injected_at": wall_ts,
         "injected_ns": the same instant on the spans' clock,
         "total_ms": float, "result": "ok" | "failed",
         "tick_ms": float,                   # the injecting tick() call
         "compiles": int,                    # XLA compilations inside it
         "stages": {stage: ms},              # summed across processes
         "workers": {wid: {stage: ms}}}      # per-process detail

    ``workers`` keys: -1 for the session process, worker_id otherwise.
    Late events (federated worker stages, deferred checkpoint encodes)
    find their record in the ring by epoch and attach there."""

    def __init__(self, capacity: int = 256):
        self.capacity = max(1, int(capacity))
        self._ring: collections.deque = collections.deque(
            maxlen=self.capacity)
        self._open: dict[int, dict] = {}
        self._by_epoch: dict[int, dict] = {}
        self.total = {"ok": 0, "failed": 0}
        self._lock = threading.Lock()

    # -- assembly --------------------------------------------------------------

    def begin(self, epoch: int, checkpoint: bool, wall_ts: float,
              mono_ns: Optional[int] = None) -> None:
        with self._lock:
            self._open[epoch] = {
                "epoch": int(epoch), "checkpoint": bool(checkpoint),
                "injected_at": wall_ts, "injected_ns": mono_ns,
                "total_ms": None, "result": None,
                "tick_ms": None, "compiles": 0,
                "stages": {}, "workers": {},
            }

    def _find(self, epoch: int) -> Optional[dict]:
        rec = self._open.get(epoch)
        if rec is None:
            rec = self._by_epoch.get(epoch)
        return rec

    def stage(self, epoch: int, stage: str, ms: float,
              worker: int = -1) -> None:
        """Accumulate one stage duration (summed on repeats: several
        storage commits or sinks in one epoch fold together)."""
        with self._lock:
            rec = self._find(epoch)
            if rec is None:
                return
            st = rec["stages"]
            st[stage] = st.get(stage, 0.0) + float(ms)
            per = rec["workers"].setdefault(int(worker), {})
            per[stage] = per.get(stage, 0.0) + float(ms)

    def count(self, epoch: int, counter: str, n: int = 1) -> None:
        """Bump one of the record's ``COUNTERS``."""
        with self._lock:
            rec = self._find(epoch)
            if rec is not None and counter in COUNTERS:
                rec[counter] += int(n)

    def set_tick_ms(self, epoch: int, ms: float) -> None:
        """The whole ``tick()`` call that injected ``epoch`` (it ends
        after the record is sealed, so it attaches late)."""
        with self._lock:
            rec = self._find(epoch)
            if rec is not None:
                rec["tick_ms"] = round(float(ms), 3)

    def ingest_events(self, events, worker: int = -1) -> None:
        """Fold a batch of stage-event dicts (a drained StageEventLog —
        the session's own, or one federated off a worker's stats
        reply)."""
        for ev in events or ():
            try:
                if "counter" in ev:
                    self.count(int(ev["epoch"]), ev["counter"], ev["n"])
                    continue
                self.stage(int(ev["epoch"]), str(ev["stage"]),
                           float(ev["ms"]), worker=worker)
            except (KeyError, TypeError, ValueError):
                continue          # a malformed event must not fail stats

    def finish(self, epoch: int, total_ms: float,
               result: str = "ok") -> Optional[dict]:
        """Seal the epoch's record into the ring; returns the record."""
        with self._lock:
            rec = self._open.pop(epoch, None)
            if rec is None:
                return None
            rec["total_ms"] = round(float(total_ms), 3)
            rec["result"] = result
            self.total[result] = self.total.get(result, 0) + 1
            if len(self._ring) == self._ring.maxlen:
                old = self._ring[0]
                self._by_epoch.pop(old["epoch"], None)
            self._ring.append(rec)
            self._by_epoch[rec["epoch"]] = rec
            return rec

    def abandon(self, epoch: int) -> None:
        """Drop an open record (recovery discarded the epoch)."""
        with self._lock:
            self._open.pop(epoch, None)

    # -- readers ---------------------------------------------------------------

    def get(self, epoch: int) -> Optional[dict]:
        import copy
        with self._lock:
            rec = self._find(epoch)
            return copy.deepcopy(rec) if rec is not None else None

    def history(self) -> list:
        """Sealed records, oldest first (each a deep copy: callers may
        not mutate ring state)."""
        import copy
        with self._lock:
            return [copy.deepcopy(r) for r in self._ring]

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    @staticmethod
    def _pct(sorted_vals: list, q: float) -> float:
        if not sorted_vals:
            return 0.0
        i = min(len(sorted_vals) - 1, int(q * len(sorted_vals)))
        return sorted_vals[i]

    def stage_percentiles(self) -> dict:
        """{stage: {"p50_ms", "p99_ms", "n"}} over the ring (stages with
        no samples are omitted)."""
        with self._lock:
            samples: dict[str, list] = {}
            for rec in self._ring:
                for stage, ms in rec["stages"].items():
                    samples.setdefault(stage, []).append(ms)
        out = {}
        for stage, vals in samples.items():
            vals.sort()
            out[stage] = {"p50_ms": round(self._pct(vals, 0.5), 3),
                          "p99_ms": round(self._pct(vals, 0.99), 3),
                          "n": len(vals)}
        return out

    def summary(self) -> dict:
        """The metrics()/Prometheus section: result totals + per-stage
        percentiles + ring occupancy."""
        with self._lock:
            total = dict(self.total)
            n = len(self._ring)
        return {"total": total, "history_len": n,
                "history_capacity": self.capacity,
                "stages": self.stage_percentiles()}
