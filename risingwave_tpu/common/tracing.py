"""Epoch-aware structured tracing: ONE span primitive, a bounded ring,
Chrome trace export.

Counterpart of the reference's tracing layer (reference:
src/utils/runtime/src/logger.rs tracing subscribers + the await-tree /
risectl trace surface, src/compute/src/rpc/service/monitor_service.rs:46).
Every barrier cycle produces a small tree of spans that share the
barrier's ``epoch`` id —

    session.tick                   root: Session.tick(), entry to return
      source.feed                  the sources hand over their chunks
      cosched.dispatch / flush_begin / epoch_wait / flush_decode / restack
      barrier.inject               queue pushes + remote inject
      barrier.collect              awaiting every actor's ack
        actor.run                  one job task's time in the epoch
        <Executor>.chunks          roll-up: host time inside map_chunk
        <Executor>.barrier         each executor's on_barrier work
          agg.flush_wait           the flush's one device fetch
          agg.state_delta          the checkpoint's state-table delta
            delta.fetch_wait / delta.encode / delta.stage
      checkpoint.commit            store + worker phase-2 commit
        commit.pending             the epoch's staged deltas merged
        DurableStateStore.commit   segment append inside the store
          segment.encode / segment.put / manifest.write
        store.apply                the in-memory apply

— and every span is recorded through ``span(...)`` below, the only place
that reads a clock for a span:

* start and duration are integer nanoseconds of ONE monotonic clock
  (``now_ns`` = ``time.perf_counter_ns``, CLOCK_MONOTONIC: shared by every
  process of a host, so federated worker spans line up);
* the body runs inside ``jax.profiler.TraceAnnotation(name, epoch=...)``,
  so whenever a profiler session is active (``jax.profiler.start_trace``,
  the dashboard's profiler endpoint, a benchmark's traced run) the same
  span is in the device trace, on the profiler's own clock, next to the
  device's operations. "Tracing on" is exactly "a profiler session is
  active": with none, the annotation is a no-op of about half a
  microsecond;
* the completed span lands in a bounded ring (``GLOBAL_TRACE``) so the last
  few hundred epochs are always inspectable post-hoc (``epoch_spans()``)
  without any collector, and with ``stage=`` its milliseconds fold into the
  barrier ledger's record of that epoch (common/barrier_ledger.py).

``to_chrome_trace`` renders spans as Chrome trace-event JSON ("X" complete
events) loadable in Perfetto / chrome://tracing. Cross-process: worker
processes record into their own ``GLOBAL_TRACE``; the session's stats
federation drains those rings over the control socket and re-ingests the
spans with the worker's pid, so a single export covers the whole cluster.
"""

from __future__ import annotations

import collections
import contextvars
import dataclasses
import itertools
import json
import threading
import time
from typing import Iterable, Optional, Union

from .barrier_ledger import record_stage

#: span categories (Chrome trace "cat" field)
CAT_EPOCH = "epoch"          # whole-epoch + inject/collect conductor spans
CAT_BARRIER = "barrier"      # per-executor on_barrier work
CAT_STORAGE = "storage"      # state-table / store commit work
CAT_DISPATCH = "dispatch"    # jitted-epoch dispatches (common/profiling.py)

#: the one clock of every span: monotonic integer nanoseconds
now_ns = time.perf_counter_ns


@dataclasses.dataclass
class Span:
    """One completed span: ``start_ns`` and ``dur_ns`` on ``now_ns``'s
    clock; ``id`` is unique within its process (``pid``), ``parent`` the
    enclosing span's id; ``wait`` names what the host waited for inside
    it (``"device"``), if anything."""

    name: str
    cat: str
    start_ns: int
    dur_ns: int
    epoch: Optional[int] = None
    tid: str = "main"            # logical track: executor identity etc.
    pid: int = 0                 # 0 = session; worker_id + 1 = worker
    id: int = 0
    parent: Optional[int] = None
    wait: Optional[str] = None
    args: dict = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "Span":
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})


class TraceRecorder:
    """Bounded, thread-safe ring of completed spans.

    Recording must stay cheap enough for the barrier hot path: one lock
    acquisition + deque append per span, no allocation beyond the Span."""

    def __init__(self, capacity: int = 16384):
        self.capacity = capacity
        self._spans: collections.deque[Span] = collections.deque(
            maxlen=capacity)
        self._lock = threading.Lock()
        self.enabled = True
        #: newest epoch the ring has lost a span of (None: lost nothing)
        self._evicted_epoch: Optional[int] = None

    def set_capacity(self, capacity: int) -> None:
        with self._lock:
            self.capacity = capacity
            for lost in itertools.islice(
                    self._spans, max(0, len(self._spans) - capacity)):
                self._note_evicted(lost)
            self._spans = collections.deque(self._spans, maxlen=capacity)

    def _note_evicted(self, lost: Span) -> None:
        if lost.epoch is not None and (self._evicted_epoch is None
                                       or lost.epoch > self._evicted_epoch):
            self._evicted_epoch = lost.epoch

    def record(self, span: Span) -> None:
        if not self.enabled:
            return
        with self._lock:
            if len(self._spans) == self._spans.maxlen and self._spans:
                self._note_evicted(self._spans[0])
            self._spans.append(span)

    def snapshot(self, epoch: Optional[int] = None) -> list[Span]:
        """Copy of the ring, optionally filtered to one epoch's tree."""
        with self._lock:
            spans = list(self._spans)
        if epoch is not None:
            spans = [s for s in spans if s.epoch == epoch]
        return spans

    def drain(self) -> list[Span]:
        """Take-and-clear — the worker side of span federation (each
        session stats poll drains, so no span ships twice)."""
        with self._lock:
            spans = list(self._spans)
            self._spans.clear()
        return spans

    def ingest(self, dicts: Iterable[dict], pid: Optional[int] = None) -> None:
        """Re-record spans shipped from another process (stats reply)."""
        for d in dicts:
            s = Span.from_dict(d)
            if pid is not None:
                s.pid = pid
            self.record(s)

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()
            self._evicted_epoch = None

    def epochs(self) -> list[int]:
        """Distinct epochs currently covered by the ring, ascending."""
        return sorted({s.epoch for s in self.snapshot()
                       if s.epoch is not None})

    def epoch_spans(self) -> dict:
        """``{epoch: [span dict, ...]}`` of every epoch the ring holds
        WHOLE, spans in start order. An epoch the ring has lost a span
        of (and every older one) is left out: a reader gets all of a
        barrier's spans or none of them."""
        with self._lock:
            spans = list(self._spans)
            floor = self._evicted_epoch
        out: dict = {}
        for s in spans:
            if s.epoch is None or (floor is not None and s.epoch <= floor):
                continue
            out.setdefault(s.epoch, []).append(s.to_dict())
        for group in out.values():
            group.sort(key=lambda d: d["start_ns"])
        return out


#: the per-process recorder every instrumentation seam writes to
GLOBAL_TRACE = TraceRecorder()


def epoch_spans() -> dict:
    """The process-global ring by epoch (see ``TraceRecorder.epoch_spans``).
    The ring is the process's, not a Session's: it still answers after
    ``Session.close()``."""
    return GLOBAL_TRACE.epoch_spans()


# -- the span primitive -------------------------------------------------------

_IDS = itertools.count(1)
#: the innermost open span of the running task / thread
_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "rw_current_span", default=None)
#: open spans by (name, epoch) → id, for ``parent="<name>"`` from another
#: task or thread (an operator inside the conductor's ``barrier.collect``)
_OPEN: dict = {}
#: the epoch the conductor is ticking (None between ticks): stamps spans
#: that have no span around them, such as a compile's
_conductor_epoch: Optional[int] = None

#: ``parent=ROOT``: no parent, whatever span encloses the call
ROOT = 0

_annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported on first use (this
    module loads in processes that never touch JAX's profiler)."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


def current_span() -> Optional["span"]:
    """The innermost open span of the running task (None outside any): an
    operator inside its ``<identity>.barrier`` adds the counts it learns
    there with ``current_span().set(...)``."""
    return _CURRENT.get()


def set_conductor_epoch(epoch: Optional[int]) -> None:
    global _conductor_epoch
    _conductor_epoch = epoch


def conductor_epoch() -> Optional[int]:
    return _conductor_epoch


def _resolve_parent(parent: Union[None, int, str], epoch: Optional[int],
                    current: Optional["span"]) -> Optional[int]:
    if parent is None:
        return current.id if current is not None else None
    if parent == ROOT:
        return None
    if isinstance(parent, str):
        return _OPEN.get((parent, epoch))
    return parent


def record_span(name: str, start_ns: int, dur_ns: int, *,
                epoch: Optional[int], stage: Optional[str] = None,
                wait: Optional[str] = None,
                parent: Union[None, int, str] = None,
                cat: str = CAT_BARRIER, tid: str = "main",
                **args) -> None:
    """Record a span whose interval is already known (a roll-up of many
    short pieces, an interval that began in another call, a duration a
    listener is handed): same ring, same ledger fold as ``span`` — but no
    annotation, there is no body to run inside one (the pieces of a
    roll-up get theirs from ``annotation``)."""
    _emit(Span(name, cat, int(start_ns), int(dur_ns), epoch=epoch, tid=tid,
               id=next(_IDS),
               parent=_resolve_parent(parent, epoch, _CURRENT.get()),
               wait=wait, args=args), stage)


def annotation(name: str, epoch: Optional[int] = None, **stats):
    """A profiler annotation WITHOUT a ring record — the counterpart of
    ``record_span``, for the pieces a roll-up sums: each short step runs
    inside ``with annotation("<identity>.chunks", epoch)`` and lands in a
    profiler's trace under the name the ring knows its roll-up by; with no
    profiler session it is a no-op of about half a microsecond. The body
    must not suspend its task (another task's work would fall inside)."""
    if epoch is not None:
        stats["epoch"] = epoch
    return _trace_annotation()(name, **stats)


def _emit(done: Span, stage: Optional[str], min_ns: float = 0) -> None:
    """A completed span into the ring and, with a stage, the ledger."""
    if done.dur_ns >= min_ns:
        GLOBAL_TRACE.record(done)
    if stage is not None:
        record_stage(done.epoch, stage, done.dur_ns / 1e6)


class span:
    """``with span("cosched.dispatch", epoch=e, stage="epoch_dispatch"):``
    — the one way a piece of host work gets a name.

    ``epoch`` is the id every span of one barrier shares (``None``
    inherits the enclosing span's); the enclosing span of the running
    task is the parent, ``parent=`` names another (a span name resolved
    among the open spans of the same epoch, or an id) where the work runs
    on another task or thread; ``stage=`` also folds the duration into the
    barrier ledger's record of the epoch; ``wait=`` says what the host
    waits for inside (``"device"``); ``min_ms`` keeps a shorter span out of
    the ring (its annotation is still in a profiler's trace). Further
    keywords are the span's ``args``; ``set(**args)`` adds counts known
    only inside the body. After the block ``dur_ns`` holds the duration.
    """

    __slots__ = ("name", "epoch", "stage", "wait", "cat", "tid", "args",
                 "min_ms", "id", "parent", "start_ns", "dur_ns", "_prev",
                 "_note")

    def __init__(self, name: str, *, epoch: Optional[int],
                 stage: Optional[str] = None, wait: Optional[str] = None,
                 parent: Union[None, int, str] = None,
                 cat: str = CAT_BARRIER, tid: str = "main",
                 min_ms: float = 0.0, **args):
        self.name = name
        self.epoch = epoch
        self.stage = stage
        self.wait = wait
        self.parent = parent
        self.cat = cat
        self.tid = tid
        self.min_ms = min_ms
        self.args = args
        self.dur_ns = 0

    def set(self, **args) -> None:
        self.args.update(args)

    def __enter__(self) -> "span":
        current = _CURRENT.get()
        if self.epoch is None and current is not None:
            self.epoch = current.epoch
        self.parent = _resolve_parent(self.parent, self.epoch, current)
        self.id = next(_IDS)
        self._prev = current
        _CURRENT.set(self)
        _OPEN[(self.name, self.epoch)] = self.id
        stats = {}
        if self.epoch is not None:
            stats["epoch"] = self.epoch
        if self.wait is not None:
            stats["wait"] = self.wait
        self._note = _trace_annotation()(self.name, **stats)
        self._note.__enter__()
        self.start_ns = now_ns()
        return self

    def __exit__(self, *exc) -> bool:
        self.dur_ns = now_ns() - self.start_ns
        self._note.__exit__(*exc)
        _OPEN.pop((self.name, self.epoch), None)
        _CURRENT.set(self._prev)
        _emit(Span(self.name, self.cat, self.start_ns, self.dur_ns,
                   epoch=self.epoch, tid=self.tid, id=self.id,
                   parent=self.parent, wait=self.wait, args=self.args),
              self.stage, self.min_ms * 1e6)
        return False


# -- Chrome trace-event export ------------------------------------------------

def to_chrome_trace(spans: Iterable[Span],
                    process_names: Optional[dict] = None,
                    barrier_records: Optional[Iterable[dict]] = None,
                    ) -> dict:
    """Spans → Chrome trace-event JSON object (Perfetto-loadable).

    Every span becomes a complete ("X") event; epoch spans live on the
    ``conductor`` track and executor spans on per-identity tracks, so one
    epoch renders as a timeline across executors. Timestamps are
    microseconds relative to the earliest span so the viewer opens at
    t=0; ``id`` / ``parent`` / ``wait`` ride in ``args``.
    ``barrier_records`` (BarrierLedger waterfall records) render as
    flow events ("s"/"t"/"f", one flow id per epoch) arrowing each
    barrier from its conductor injection through every participating
    worker's collect back to completion."""
    spans = sorted(spans, key=lambda s: s.start_ns)
    base = spans[0].start_ns if spans else 0
    events: list[dict] = []
    names = {0: "session"}
    names.update(process_names or {})
    for s in spans:
        if s.pid not in names:
            names[s.pid] = f"worker-{s.pid - 1}"
        args = {"id": s.id, "parent": s.parent, **s.args}
        if s.epoch is not None:
            args["epoch"] = s.epoch
        if s.wait is not None:
            args["wait"] = s.wait
        events.append({
            "name": s.name, "cat": s.cat, "ph": "X",
            "ts": round((s.start_ns - base) / 1e3, 3),
            "dur": round(s.dur_ns / 1e3, 3),
            "pid": s.pid, "tid": s.tid, "args": args,
        })
    events.extend(barrier_flow_events(barrier_records or (), base, names))
    meta: list[dict] = []
    for pid, pname in sorted(names.items()):
        meta.append({"name": "process_name", "ph": "M", "pid": pid,
                     "tid": "", "args": {"name": pname}})
    return {"traceEvents": meta + events, "displayTimeUnit": "ms"}


def barrier_flow_events(records: Iterable[dict], base: int,
                        names: Optional[dict] = None) -> list[dict]:
    """BarrierLedger waterfall records → Chrome flow events.

    One flow per barrier (id = epoch): start ("s") on the conductor
    track at injection, a step ("t") on each participating worker's
    conductor track at its collect, finish ("f") back on the conductor
    at completion — Perfetto draws the barrier's cluster-wide path as
    arrows across process lanes."""
    out: list[dict] = []
    for rec in records:
        t0 = rec.get("injected_ns")      # the spans' clock, not the wall's
        total_ms = rec.get("total_ms")
        if t0 is None or total_ms is None:
            continue          # an in-flight record has no finish yet
        epoch = rec["epoch"]
        common = {"name": f"barrier {epoch}", "cat": CAT_EPOCH,
                  "id": epoch, "tid": "conductor"}
        out.append({**common, "ph": "s", "pid": 0,
                    "ts": round((t0 - base) / 1e3, 3),
                    "args": {"epoch": epoch,
                             "checkpoint": rec.get("checkpoint")}})
        for wid, stages in sorted(rec.get("workers", {}).items()):
            if int(wid) < 0:
                continue      # session-process detail stays on pid 0
            pid = int(wid) + 1
            if names is not None and pid not in names:
                names[pid] = f"worker-{wid}"
            wc = stages.get("worker_collect", 0.0)
            out.append({**common, "ph": "t", "pid": pid,
                        "ts": round((t0 - base) / 1e3 + wc * 1e3, 3),
                        "args": {"epoch": epoch}})
        out.append({**common, "ph": "f", "bp": "e", "pid": 0,
                    "ts": round((t0 - base) / 1e3 + total_ms * 1e3, 3),
                    "args": {"epoch": epoch, "result": rec.get("result")}})
    return out


def export_chrome_trace(spans: Iterable[Span],
                        path: Optional[str] = None, **kw) -> dict:
    """Render and optionally write the Chrome trace JSON."""
    obj = to_chrome_trace(spans, **kw)
    if path is not None:
        with open(path, "w") as f:
            json.dump(obj, f)
    return obj
