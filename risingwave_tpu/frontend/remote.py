"""Session-side handles for remote worker processes.

Counterpart of the reference's rpc_client pools + stream client
(reference: src/rpc_client/src/meta_client.rs:92, stream_client.rs — the
frontend/meta side of the compute-node RPC boundary). One
``RemoteWorker`` per worker process: it owns the subprocess, the
multiplexed socket, permit accounting for outbound data channels, and
the per-epoch barrier-completion events. ``RemoteJob`` adapts a
worker-hosted job to the StreamJob surface the Session's conduction loop
drives (wait_barrier / stop / sources / bus).
"""

from __future__ import annotations

import asyncio
import itertools
import os
import signal
import subprocess
import sys
import time
from typing import Optional

from ..common.config import FaultConfig as _FaultConfig
from ..rpc.wire import message_to_wire, read_frame, write_frame
from ..stream.message import Message
from .runtime import ChangelogBus, QueueSource

_FAULT_DEFAULTS = _FaultConfig()


class WorkerDied(RuntimeError):
    pass


class RemoteWorker:
    """Spawn + drive one worker process over a multiplexed socket."""

    SPAWN_TIMEOUT_S = 60.0
    #: default deadline on control-frame request/reply cycles: a worker
    #: wedged before replying (accelerator hang, livelock) used to hang
    #: handle_create_job/scan forever — now it trips WorkerDied and the
    #: recovery machinery. Defaults come from FaultConfig (the single
    #: source of the numbers; configurable via rw_config fault.*).
    REQUEST_TIMEOUT_S = _FAULT_DEFAULTS.worker_request_timeout_s
    #: deadline on barrier collection per epoch: a worker that stops
    #: acking barriers without closing its socket is declared failed
    #: (fail-stop) so the heartbeat-TTL scoped recovery can respawn it
    EPOCH_TIMEOUT_S = _FAULT_DEFAULTS.worker_epoch_timeout_s

    def __init__(self, data_dir: str, worker_id: int, loop,
                 permits: int = 32):
        self.data_dir = data_dir
        self.worker_id = worker_id
        self.loop = loop
        self.permits = permits
        self.request_timeout = self.REQUEST_TIMEOUT_S
        self.epoch_timeout = self.EPOCH_TIMEOUT_S
        self.dead = False
        self.proc: Optional[subprocess.Popen] = None
        #: fault-plane link name of the session→worker direction
        self.link = f"s->w{worker_id}"
        #: session-generation fencing token (ISSUE 9): stamped on every
        #: frame this handle sends; the Session bumps it on every scoped
        #: recovery so a stale pre-recovery worker's barrier acks are
        #: dropped here and its commits are refused worker-side
        self.generation = 1
        self.stale_acks_dropped = 0
        self.dup_replies_dropped = 0
        self.dup_acks_dropped = 0
        self._rid = itertools.count(1)
        self._chan = itertools.count(worker_id * 100_000 + 1)
        self._pending: dict[int, asyncio.Future] = {}
        self._done_rids: "set[int]" = set()
        self._epoch_events: dict[int, asyncio.Event] = {}
        self._epoch_errors: dict[int, str] = {}
        self._init_fut: Optional[asyncio.Future] = None
        self._sems: dict[int, asyncio.Semaphore] = {}
        self._data_seqs: dict[int, int] = {}
        from ..rpc.exchange import AckWatermark
        self._acks: dict[int, AckWatermark] = {}
        self._forwarders: dict[str, list[asyncio.Task]] = {}
        self._wlock: Optional[asyncio.Lock] = None
        self._reader_task: Optional[asyncio.Task] = None
        self._writer = None

    # -- lifecycle -------------------------------------------------------------

    def spawn(self) -> None:
        # the worker runs on THIS process's JAX platform, named
        # explicitly: a worker that cannot get it must die with its own
        # error at startup (worker/host.py initializes the backend before
        # WORKER_READY), never come up on another platform in silence.
        # Multi-process mode is CPU-verified (README "One JAX process per
        # chip"): on a one-chip host this process holds the chip.
        import jax
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = jax.default_backend()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "risingwave_tpu.worker",
             "--data-dir", self.data_dir,
             "--worker-id", str(self.worker_id), "--port", "0"],
            stdout=subprocess.PIPE, stderr=None, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))))
        deadline = time.monotonic() + self.SPAWN_TIMEOUT_S
        port = None
        assert self.proc.stdout is not None
        import select
        buf = b""
        fd = self.proc.stdout.fileno()
        while time.monotonic() < deadline:
            # select-bounded read: a worker that hangs during startup
            # WITHOUT printing (wedged accelerator init) must still trip
            # the timeout instead of blocking readline forever
            ready, _, _ = select.select([fd], [], [],
                                        max(0.05, deadline - time.monotonic()))
            if not ready:
                continue
            chunk = os.read(fd, 4096)
            if not chunk:
                # stdout closed before WORKER_READY: the worker is on its
                # way out. Reap it inside the spawn deadline; one that
                # closed stdout yet lingers is killed, not waited on
                try:
                    rc = self.proc.wait(
                        timeout=max(0.05, deadline - time.monotonic()))
                except subprocess.TimeoutExpired:
                    self.proc.kill()
                    rc = self.proc.wait()
                raise WorkerDied(
                    f"worker {self.worker_id} exited during startup "
                    f"(rc={rc}; its own error is on stderr above)")
            buf += chunk
            for line in buf.decode(errors="replace").splitlines():
                if line.startswith("WORKER_READY"):
                    port = int(line.split()[1])
                    break
            if port is not None:
                break
        if port is None:
            self.proc.kill()
            raise WorkerDied(f"worker {self.worker_id} startup timed out")
        self.port = port
        self.dead = False

    async def connect(self) -> None:
        reader, writer = await asyncio.open_connection("127.0.0.1", self.port)
        self._writer = writer
        self._wlock = asyncio.Lock()
        self._reader_task = asyncio.ensure_future(self._read_loop(reader))

    async def aclose(self) -> None:
        """Tear down the socket INSIDE the loop (cancelled reader awaited,
        writer closed) so no task or transport outlives the session."""
        if self._reader_task is not None:
            self._reader_task.cancel()
            try:
                await self._reader_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._reader_task = None
        if self._writer is not None:
            self._writer.close()
            try:
                await self._writer.wait_closed()
            except Exception:  # noqa: BLE001
                pass
            self._writer = None

    def respawn(self, connect_await) -> None:
        """Fresh process over the SAME durable directory (state + offsets
        recover from the last committed checkpoint)."""
        connect_await(self.aclose())
        self.terminate()
        self._pending.clear()
        self._epoch_events.clear()
        self._epoch_errors.clear()
        self._sems.clear()
        self._data_seqs.clear()
        self._acks.clear()
        # sibling jobs' forwarders feed a process that no longer exists;
        # cancel (not just forget) so they cannot leak across recoveries
        for tasks in self._forwarders.values():
            for t in tasks:
                t.cancel()
        self._forwarders.clear()
        self.spawn()
        connect_await(self.connect())

    def terminate(self) -> None:
        if self._reader_task is not None:   # not yet aclosed
            self._reader_task.cancel()
            self._reader_task = None
        if self.proc is not None and self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait()
        self.dead = True

    def kill9(self) -> None:
        """Chaos hook: SIGKILL the worker process (the madsim node-kill
        analogue across a REAL process boundary)."""
        if self.proc is not None and self.proc.poll() is None:
            os.kill(self.proc.pid, signal.SIGKILL)
            self.proc.wait()

    # -- socket ----------------------------------------------------------------

    async def _read_loop(self, reader) -> None:
        while True:
            frame = await read_frame(reader)
            if frame is None:
                self._mark_dead()
                return
            t = frame.get("type")
            if t == "reply":
                rid = frame.get("rid")
                fut = self._pending.pop(rid, None)
                if fut is not None and not fut.done():
                    fut.set_result(frame)
                    if rid is not None:
                        self._done_rids.add(rid)
                        if len(self._done_rids) > 4096:
                            self._done_rids = set(
                                sorted(self._done_rids)[-2048:])
                elif rid in self._done_rids:
                    # at-least-once reply delivery (duplicated frame on a
                    # faulty link) stays exactly-once at the caller: the
                    # first copy resolved the future, later copies drop
                    self.dup_replies_dropped += 1
            elif t == "ack":
                chan = frame["chan"]
                wm = self._acks.get(chan)
                if wm is not None and not wm.accept(frame.get("seq")):
                    # duplicated data ack: releasing a permit for it
                    # would inflate the channel's credit (reordered
                    # acks are accepted exactly once by the watermark)
                    self.dup_acks_dropped += 1
                    continue
                sem = self._sems.get(chan)
                if sem is not None:
                    sem.release()
            elif t == "barrier_complete":
                gen = frame.get("gen")
                if gen is not None and int(gen) != self.generation:
                    # fencing: a barrier ack carrying a stale generation
                    # (pre-recovery incarnation, or a chaos-delayed
                    # frame) must not count toward the CURRENT graph's
                    # epoch collection
                    self.stale_acks_dropped += 1
                    continue
                # per-JOB failure map: one poisoned or peer-starved job
                # must not read as a whole-worker failure (legacy
                # ok/error frames fold into the wildcard entry)
                failed = dict(frame.get("failed") or {})
                if frame.get("ok", True) is False:
                    failed["*"] = frame.get("error", "worker job failed")
                if failed:
                    self._epoch_errors[frame["epoch"]] = failed
                if frame.get("init") and self._init_fut is not None:
                    if not self._init_fut.done():
                        self._init_fut.set_result(frame)
                else:
                    ev = self._epoch_events.setdefault(
                        frame["epoch"], asyncio.Event())
                    ev.set()

    def _mark_dead(self) -> None:
        self.dead = True
        for ev in self._epoch_events.values():
            ev.set()
        for fut in self._pending.values():
            if not fut.done():
                fut.set_exception(WorkerDied("worker connection lost"))
        self._pending.clear()
        if self._init_fut is not None and not self._init_fut.done():
            self._init_fut.set_exception(WorkerDied("worker connection lost"))
        for sem in self._sems.values():
            sem.release()          # unblock forwarders; send() will raise

    async def send(self, obj: dict, meta: bool = False) -> None:
        if self.dead or self._writer is None:
            raise WorkerDied("worker is down")
        if "gen" not in obj:
            # fencing token on every session→worker frame: the worker
            # records it at job creation and refuses barrier/commit
            # frames older than a job's deployment generation
            obj = {**obj, "gen": self.generation}
        try:
            await write_frame(self._writer, obj, self._wlock,
                              link=self.link, meta=meta)
        except (ConnectionError, BrokenPipeError, OSError):
            self._mark_dead()
            raise WorkerDied("worker connection lost") from None

    async def request(self, obj: dict,
                      timeout: Optional[float] = None,
                      meta: bool = False) -> dict:
        """Request/reply with a DEFAULT deadline (``request_timeout``; a
        worker wedged before replying is declared dead instead of hanging
        the caller forever). Pass ``timeout=0`` to wait unbounded."""
        rid = next(self._rid)
        obj = {**obj, "rid": rid}
        fut = self.loop.create_future()
        self._pending[rid] = fut
        t = self.request_timeout if timeout is None else timeout
        try:
            await self.send(obj, meta=meta)
            if t and t > 0:
                try:
                    resp = await asyncio.wait_for(fut, t)
                except asyncio.TimeoutError:
                    # fail-stop: a worker that missed a control deadline
                    # is indistinguishable from a dead one — mark it so
                    # recovery (respawn over durable state) takes over
                    self._mark_dead()
                    raise WorkerDied(
                        f"worker {self.worker_id} request "
                        f"{obj.get('type')!r} timed out after {t}s") \
                        from None
            else:
                resp = await fut
        finally:
            # a caller-side wait_for timeout cancels ``fut`` but would
            # otherwise leave its rid in _pending forever (the late
            # reply, if any, is discarded by _read_loop's pop)
            self._pending.pop(rid, None)
        if resp.get("ok") is False:
            raise RuntimeError(
                f"worker {self.worker_id}: {resp.get('error')}")
        return resp

    # -- data channels ---------------------------------------------------------

    def alloc_chan(self) -> int:
        from ..rpc.exchange import AckWatermark
        chan = next(self._chan)
        self._sems[chan] = asyncio.Semaphore(self.permits)
        self._data_seqs[chan] = 0
        self._acks[chan] = AckWatermark()
        return chan

    async def send_data(self, chan: int, msg: Message, schema) -> None:
        from ..common.chunk import StreamChunk
        if isinstance(msg, StreamChunk):
            sem = self._sems.get(chan)
            if sem is not None:
                await sem.acquire()
            if self.dead:
                raise WorkerDied("worker is down")
        seq = self._data_seqs.get(chan, 0)
        self._data_seqs[chan] = seq + 1
        await self.send({"type": "data", "chan": chan, "seq": seq,
                         "msg": message_to_wire(msg, schema)})

    def start_forwarder(self, job: str, q: QueueSource, chan: int,
                        schema) -> None:
        """Forward an upstream bus subscription over a data channel —
        the session side of the remote exchange edge."""

        async def run() -> None:
            try:
                async for msg in q.execute():
                    await self.send_data(chan, msg, schema)
            except WorkerDied:
                pass                      # recovery re-wires the edge
            except Exception as e:        # noqa: BLE001 - must be LOUD:
                import sys                # a dead forwarder starves the job
                sys.stderr.write(
                    f"exchange forwarder {job!r}/chan {chan} died: "
                    f"{e!r}\n")
                raise

        self._forwarders.setdefault(job, []).append(
            asyncio.ensure_future(run(), loop=self.loop))

    def stop_forwarders(self, job: str) -> list[asyncio.Task]:
        tasks = self._forwarders.pop(job, [])
        for t in tasks:
            t.cancel()
        return tasks

    # -- barrier conduction ----------------------------------------------------

    async def inject_barrier(self, epoch: int, checkpoint: bool,
                             generate: bool, mutation=None,
                             exclude=None) -> None:
        for old in [e for e in self._epoch_events if e < epoch - 64]:
            self._epoch_events.pop(old, None)
            self._epoch_errors.pop(old, None)
        frame = {"type": "barrier", "epoch": epoch, "checkpoint": checkpoint,
                 "generate": generate}
        if exclude:
            # jobs the session already declared dead (spanning jobs with
            # a killed peer): the worker must not feed or wait on them
            frame["exclude"] = sorted(exclude)
        if mutation is not None:
            frame["mutation"] = mutation.kind.value
            if isinstance(mutation.payload, str):
                frame["mutation_payload"] = mutation.payload
        await self.send(frame)

    async def init_barrier(self, name: str, epoch: int) -> None:
        """Init cut for a just-created job (replaces the local path's
        direct queue push)."""
        self._init_fut = self.loop.create_future()
        await self.send({"type": "barrier", "epoch": epoch,
                         "checkpoint": False, "generate": False,
                         "only": [name], "init": True})
        try:
            if self.epoch_timeout and self.epoch_timeout > 0:
                frame = await asyncio.wait_for(self._init_fut,
                                               self.epoch_timeout)
            else:
                frame = await self._init_fut
        except asyncio.TimeoutError:
            self._mark_dead()
            raise WorkerDied(
                f"worker {self.worker_id} init barrier for {name!r} "
                f"timed out after {self.epoch_timeout}s") from None
        finally:
            self._init_fut = None
        failed = dict(frame.get("failed") or {})
        if frame.get("ok", True) is False:
            failed["*"] = frame.get("error")
        err = failed.get(name) or failed.get("*")
        if err:
            raise RuntimeError(
                f"remote job {name!r} failed at init: {err}")

    def _job_error(self, epoch: int, job: Optional[str]) -> Optional[str]:
        failed = self._epoch_errors.get(epoch)
        if not failed:
            return None
        if isinstance(failed, dict):
            if job is not None:
                return failed.get(job) or failed.get("*")
            return "; ".join(f"{k}: {v}" for k, v in sorted(failed.items()))
        return str(failed)

    async def wait_epoch(self, epoch: int, job: Optional[str] = None) -> bool:
        """True iff the worker collected the epoch cleanly for ``job``
        (all jobs when None). Bounded by ``epoch_timeout``: a worker that
        stops acking barriers while its socket stays open (SIGSTOP,
        accelerator wedge) is declared dead instead of deadlocking the
        conductor — the heartbeat-TTL scoped recovery then respawns it
        over durable state. A ``PEER_LOST`` per-job error (this worker's
        fragment lost its exchange peer) also returns False — it is a
        kill signal for scoped recovery, not a poisoned job."""
        if self.dead:
            return False
        err = self._job_error(epoch, job)
        if err:
            if err.startswith("PEER_LOST"):
                return False
            raise RuntimeError(f"remote job failed: {err}")
        ev = self._epoch_events.setdefault(epoch, asyncio.Event())
        if self.epoch_timeout and self.epoch_timeout > 0:
            try:
                await asyncio.wait_for(ev.wait(), self.epoch_timeout)
            except asyncio.TimeoutError:
                self._mark_dead()
                return False
        else:
            await ev.wait()
        # NOT popped here: several RemoteJobs on this worker wait the same
        # epoch; entries are pruned by inject_barrier's horizon instead
        err = self._job_error(epoch, job)
        if err:
            if err.startswith("PEER_LOST"):
                return False
            raise RuntimeError(f"remote job failed: {err}")
        return not self.dead

    async def commit(self, epoch: int, skip_jobs=None) -> None:
        frame = {"type": "commit", "epoch": epoch}
        if skip_jobs:
            frame["skip_jobs"] = sorted(skip_jobs)
        await self.send(frame)

    async def get_stats(self, timeout: float = 10.0,
                        span_ack: Optional[int] = None,
                        stage_ack: Optional[int] = None) -> dict:
        """Fetch this worker's monitor snapshot (executor trees, counters,
        queue depths, state bytes, tracing spans, barrier stage events).
        ``span_ack``/``stage_ack`` echo the last ``span_seq``/``stage_seq``
        this session processed so the worker can discard its retained
        batches (a timed-out reply is resent, not lost)."""
        req: dict = {"type": "stats"}
        if span_ack is not None:
            req["span_ack"] = span_ack
        if stage_ack is not None:
            req["stage_ack"] = stage_ack
        return await asyncio.wait_for(self.request(req, meta=True),
                                      timeout)

    async def shutdown(self) -> None:
        try:
            await asyncio.wait_for(self.request({"type": "shutdown"}), 5.0)
        except (WorkerDied, RuntimeError, asyncio.TimeoutError):
            pass


class RemoteJob:
    """StreamJob-shaped adapter for a worker-hosted job: the conduction
    loop waits on the worker's epoch acks; ``sources`` are the
    session-side queues subscribed to upstream buses (feeding the
    forwarders); the bus is empty (downstream MVs on remote MVs are not
    supported yet)."""

    def __init__(self, name: str, worker: RemoteWorker):
        self.name = name
        self.worker = worker
        self.sources: list[QueueSource] = []
        self.bus = ChangelogBus()
        self.pipeline = None
        self.table = None
        self._failure: Optional[BaseException] = None
        self._task = None

    async def wait_barrier(self, epoch: int) -> None:
        try:
            ok = await self.worker.wait_epoch(epoch, job=self.name)
        except RuntimeError:
            self._failure = self._failure or RuntimeError("remote job failed")
            raise
        if not ok:
            # worker process died: present as a killed actor so the
            # session's TTL detector + scoped recovery machinery takes over
            self._failure = asyncio.CancelledError()
            raise RuntimeError(f"worker of remote job {self.name!r} died")

    async def stop(self) -> None:
        for t in self.worker.stop_forwarders(self.name):
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass


class SpanningJob:
    """StreamJob-shaped adapter for a job whose FRAGMENT GRAPH spans
    several worker processes: an epoch completes only when EVERY
    participating worker collected it for this job (each worker's ack
    asserts all of ITS fragment actors forwarded the barrier — so the
    epoch's data crossed every remote exchange edge before the session
    may commit: exactly-once across the wire). Any participant's death —
    its socket, its deadline, or a surviving peer's PEER_LOST report —
    presents as a killed actor so the heartbeat-TTL scoped recovery
    rebuilds the job's fragments from their per-worker durable state."""

    def __init__(self, name: str, workers: list[RemoteWorker]):
        self.name = name
        self.workers = list(workers)
        self.sources: list[QueueSource] = []
        self.bus = ChangelogBus()
        self.pipeline = None
        self.table = None
        self._failure: Optional[BaseException] = None
        self._task = None

    async def wait_barrier(self, epoch: int) -> None:
        results = await asyncio.gather(
            *(w.wait_epoch(epoch, job=self.name) for w in self.workers),
            return_exceptions=True)
        hard = [r for r in results if isinstance(r, BaseException)
                and not isinstance(r, (WorkerDied,))]
        if hard:
            self._failure = self._failure or hard[0]
            raise RuntimeError(
                f"spanning job {self.name!r} failed") from hard[0]
        if not all(r is True for r in results):
            self._failure = asyncio.CancelledError()
            raise RuntimeError(
                f"a worker of spanning job {self.name!r} died")

    async def stop(self) -> None:
        return None
