"""SinkExecutor + log store: exactly-once changelog delivery, decoupled
from the barrier path.

Counterpart of the reference's SinkExecutor with its LogStore decoupling
(reference: src/stream/src/executor/sink.rs:38;
src/stream/src/common/log_store/mod.rs:57-168 — LogWriter buffers the
epoch's chunks, LogReader delivers them to the external system and
*truncates* up to the delivered offset; sink-decouple: a dead sink
backend degrades one job instead of stalling cluster checkpointing). The
log lives in a StateTable keyed (epoch, seq) so it shares the state
store's atomic epoch commit:

  on chunk      — buffer rows (host decode; sinks are host IO anyway)
  on barrier e  — append buffered rows to the log table (ALWAYS commits
                  with epoch e — this is the barrier-path contract),
                  then ATTEMPT delivery of log rows up to e with bounded
                  retry/backoff; on success, record (delivered_epoch,
                  sink position) in the progress table and truncate the
                  delivered rows — those writes ride the SAME epoch
                  commit.

Failure containment: a delivery failure never fails the epoch. The log
keeps the undelivered rows; after ``degrade_after`` consecutive failed
epochs the job goes DEGRADED (delivery attempts pause, the log keeps
absorbing changes, health is surfaced in Session.metrics()["sinks"]).
``resume()`` (Session.resume_sink — the ALTER SINK ... RESUME shape) or
crash recovery re-arms delivery; every logged row is then delivered
exactly once. The only hard failure is the log cap
(``log_cap_rows``): unbounded log growth is refused loudly.

Exactly-once across crashes AND in-process retries: the sink's byte/row
position is persisted in the SAME epoch commit as the log truncation,
and every delivery attempt first rolls the sink back to the last
successful position (FileSink.truncate_to), so a half-delivered failed
attempt is overwritten by the retry, and after a crash undelivered log
rows (whose truncation never committed) are re-delivered on top of the
committed position.
"""

from __future__ import annotations

from typing import Optional

from ..common.chunk import StreamChunk, chunk_to_rows
from ..common.failpoint import fail_point
from ..common.types import INT64, Field, Schema
from ..connector.sinks import Sink
from ..storage.state_table import StateTable
from .executor import Executor, SingleInputExecutor
from .message import Barrier


def log_table_schema(value_schema: Schema) -> Schema:
    """(epoch, seq, op) ⧺ row values; pk = (epoch, seq) so iteration order
    is delivery order (reference: KvLogStore key layout)."""
    head = (Field("_epoch", INT64), Field("_seq", INT64), Field("_op", INT64))
    return Schema(head + tuple(value_schema))


PROGRESS_SCHEMA = Schema((Field("_id", INT64), Field("_delivered_epoch", INT64),
                          Field("_position", INT64)))


class SinkExecutor(SingleInputExecutor):
    identity = "Sink"

    def __init__(self, input: Executor, sink: Sink,
                 log_table: StateTable, progress_table: StateTable,
                 n_visible: Optional[int] = None, recovering: bool = False,
                 retry_policy=None, degrade_after: int = 3,
                 log_cap_rows: int = 1_000_000):
        super().__init__(input)
        self.schema = input.schema
        self.n_visible = len(self.schema) if n_visible is None else n_visible
        self._recovering = recovering
        self.sink = sink
        self.log = log_table
        self.progress = progress_table
        if retry_policy is None:
            # single source of default numbers: the FaultConfig dataclass
            from ..common.config import FaultConfig
            retry_policy = FaultConfig().sink_retry_policy()
        self._policy = retry_policy
        self.degrade_after = max(1, int(degrade_after))
        self.log_cap_rows = int(log_cap_rows)
        # sink jobs are StreamJobs; .table is the job's "output" table —
        # for a sink that is its progress table (scanned by nothing, but
        # keeps the job protocol uniform)
        self.table = progress_table
        self._pending: list[tuple[int, tuple]] = []
        self._seq = 0
        self.delivered_epoch = 0
        #: last successful sink position (the rollback point every
        #: delivery attempt starts from)
        self._position = 0
        # health (surfaced via sink_health() → Session.metrics()["sinks"])
        self.degraded = False
        self.delivery_failures = 0
        self.consecutive_failures = 0
        self.rows_delivered = 0
        self.last_error: Optional[str] = None
        self._recover()

    def _recover(self) -> None:
        row = self.progress.get_row((0,))
        if row is not None:
            self.delivered_epoch = int(row[1])
            self._position = int(row[2])
            self.sink.truncate_to(self._position)
        elif self._recovering:
            # crashed before the first progress row durably committed:
            # anything already delivered is phantom output — roll the sink
            # back to empty (the committed position is 0)
            self.sink.truncate_to(0)
        # seq continues above any undelivered log rows
        seqs = [int(r[1]) for r in self.log.scan_all()]
        self._seq = max(seqs) + 1 if seqs else 0

    # -- delivery (off the epoch-failure path) --------------------------------

    def resume(self) -> None:
        """Re-arm delivery on a degraded sink (the ALTER SINK resume
        shape; also what a fresh executor after recovery starts as). The
        backlog drains at the next barrier."""
        self.degraded = False
        self.consecutive_failures = 0
        self.last_error = None

    def sink_health(self) -> dict:
        return {
            "degraded": self.degraded,
            "delivered_epoch": self.delivered_epoch,
            "pending_rows": len(self.log),   # O(keys), no row decode
            "delivery_failures": self.delivery_failures,
            "consecutive_failures": self.consecutive_failures,
            "rows_delivered": self.rows_delivered,
            "last_error": self.last_error,
        }

    def _deliver_once(self, typed: list) -> None:
        """One delivery attempt, idempotent under retry: roll the sink
        back to the last committed position first so a previous partial
        attempt's bytes are discarded, then write + flush."""
        fail_point("sink.deliver")
        self.sink.truncate_to(self._position)
        self.sink.write_rows(typed)
        self.sink.flush()

    def _try_deliver(self, epoch: int) -> None:
        to_deliver = [row for row in self.log.scan_all()
                      if int(row[0]) <= epoch]
        if not to_deliver and self.delivered_epoch >= epoch:
            return
        typed = [(int(r[2]), tuple(
            None if v is None else self.schema[i].type.to_python(v)
            for i, v in enumerate(r[3:3 + self.n_visible])))
            for r in to_deliver]
        try:
            self._policy.run("sink.deliver", self._deliver_once, typed)
        except Exception as e:  # noqa: BLE001 - degrade, don't fail the epoch
            self.delivery_failures += 1
            self.consecutive_failures += 1
            self.last_error = f"{type(e).__name__}: {e}"
            if self.consecutive_failures >= self.degrade_after:
                self.degraded = True
            return
        # success: truncate delivered rows + persist (epoch, position) —
        # all staged into the SAME epoch commit below
        for r in to_deliver:
            self.log.delete(r)
        self.delivered_epoch = epoch
        self._position = int(self.sink.position())
        self.rows_delivered += len(typed)
        self.consecutive_failures = 0
        self.last_error = None
        old = self.progress.get_row((0,))
        new = (0, epoch, self._position)
        if old is not None:
            self.progress.update(old, new)
        else:
            self.progress.insert(new)

    async def on_barrier(self, barrier: Barrier):
        epoch = barrier.epoch.curr
        for op, values in self._pending:
            self.log.insert((epoch, self._seq, int(op)) + tuple(values))
            self._seq += 1
        self._pending.clear()
        if not self.degraded:
            from ..common.tracing import span
            with span(f"{self.identity}.deliver", epoch=epoch,
                      stage="sink_deliver", tid=self.identity):
                self._try_deliver(epoch)
        else:
            # degraded: the log absorbs changes up to the cap; bounded-log
            # backpressure is a LOUD failure, not silent truncation
            # (len() counts keys without decoding the backlog)
            n_logged = len(self.log)
            if n_logged > self.log_cap_rows:
                raise RuntimeError(
                    f"sink log exceeded log_cap_rows={self.log_cap_rows} "
                    f"({n_logged} undelivered rows) while degraded; "
                    "resume the sink or raise the cap")
        self.log.commit(epoch)
        self.progress.commit(epoch)
        if False:  # pragma: no cover - async generator shape
            yield

    async def map_chunk(self, chunk: StreamChunk):
        self._pending.extend(
            chunk_to_rows(chunk, self.schema, with_ops=True, physical=True))
        yield chunk
