"""Cross-worker fragment execution: exchange endpoints + fragment jobs.

This is the executor-level half of the remote exchange subsystem (the
socket half lives in rpc/exchange.py). One streaming job's fragment graph
spans worker PROCESSES: each worker hosts some of the job's fragments as
a ``FragmentJob``, whose actors drain their fragment subtree and dispatch
into exchange edges — worker-local edges ride ``PermitChannel``s from the
in-process fabric, cross-worker edges ride ``ExchangeOutput``/
``ExchangeInput`` pairs over the multiplexed peer sockets with the SAME
credit semantics (data consumes permits released on consumption, barriers
and watermarks always pass). The consuming side of every edge is a
``MergeExecutor`` fan-in with barrier alignment, so two-phase checkpoints
hold end-to-end across processes: a worker acks a barrier only after
every local actor of the job has seen it flow through, and the session
commits only after every participating worker acked (reference:
dispatch.rs + merge.rs + exchange/permit.rs + stream_service.rs, now
composed ACROSS compute nodes instead of inside one).
"""

from __future__ import annotations

import asyncio
from typing import Dict, List, Optional

from ..common.chunk import StreamChunk
from ..common.types import Schema
from ..rpc.exchange import EdgeStats, ExchangePeerClient, PeerLost
from ..rpc.wire import message_from_wire, message_to_wire, write_frame
from .dispatch import (
    BroadcastDispatcher, HashDispatcher, MergeExecutor, MsgQueue,
    SimpleDispatcher, open_channel,
)
from .message import Barrier, Message


class ExchangeInput:
    """Consuming end of a cross-worker edge: channel-shaped (``recv``)
    so ``MergeExecutor`` treats it exactly like a local ``PermitChannel``
    end. Frames decode lazily and the permit ack goes back over the peer
    socket only when the consumer TAKES a chunk — end-to-end
    consumption-based credit (reference: permit.rs).

    Faulty-delivery hardening (ISSUE 9): frames carry per-channel
    sequence numbers. A duplicate (seq already delivered) is dropped
    WITHOUT an ack — the producer consumed one permit for it, so acking
    twice would inflate the edge's credit. An out-of-order frame (a
    chaos-delayed sibling overtook it) is held and released in seq
    order, so barrier position in the stream — the exactly-once cut —
    survives reordering networks."""

    def __init__(self, chan: int, schema: Schema, capacity: int,
                 stats: EdgeStats, job: str, link: str = ""):
        from ..rpc.exchange import SeqReorderBuffer
        self.chan = chan
        self.schema = schema
        self.capacity = capacity
        self.stats = stats
        self.job = job
        self.link = link              # fault-plane name of the ACK edge
        self._q = MsgQueue()
        self._seqbuf = SeqReorderBuffer()
        self._ack_seq = 0             # per-chan ack sequence (producer
        #                               dedups duplicated acks by it)

    def feed_wire(self, wire_msg: dict, writer, wlock,
                  seq: Optional[int] = None) -> None:
        """Called by the peer-connection read loop for every exg_data
        frame on this channel (the writer is the SAME connection, used to
        send consumption acks back). Dedup + re-order by ``seq`` HERE,
        before the frame enters the delivery queue, so ``recv`` only ever
        sees each frame once, in send order (a dropped duplicate is NOT
        acked — the producer consumed one permit for it)."""
        delivered = self._seqbuf.feed(seq, ("wire", wire_msg, writer,
                                            wlock))
        self.stats.dup_frames = self._seqbuf.dup_frames
        self.stats.reordered = self._seqbuf.reordered
        for item in delivered:
            self._q.put_nowait(item)

    def put_local(self, msg: Optional[Message]) -> None:
        """Locally injected message (stop barriers at drop; None closes)."""
        self._q.put_nowait(("local", msg, None, None))

    def peer_lost(self) -> None:
        """The producing worker's connection dropped: fail the consumer
        instead of starving it (the merge would otherwise wait forever
        for a barrier that can never arrive)."""
        self._q.put_nowait(("peer_lost", None, None, None))

    def qsize(self) -> int:
        return self._q.qsize()

    async def recv(self) -> Optional[Message]:
        kind, payload, writer, wlock = await self._q.get()
        if kind == "peer_lost":
            raise PeerLost(
                f"exchange edge {self.stats.edge} lost its producer")
        if kind == "local":
            return payload
        msg = message_from_wire(payload, self.schema, self.capacity)
        if isinstance(msg, StreamChunk):
            self.stats.chunks += 1
            ack = {"type": "exg_ack", "chan": self.chan,
                   "seq": self._ack_seq}
            self._ack_seq += 1
            try:
                await write_frame(writer, ack, wlock,
                                  link=self.link or None)
            except (ConnectionError, OSError):
                pass      # producer gone; its permits die with it
        elif isinstance(msg, Barrier):
            # per-edge barrier-epoch monotonicity: the auditor asserts
            # regressions == 0 after every chaos run
            self.stats.saw_barrier(msg.epoch.curr)
        return msg


class ExchangeOutput:
    """Producing end of a cross-worker edge: channel-shaped (``send``) so
    every dispatcher writes to it exactly like a local channel. Data
    consumes a peer-client permit before the frame is written (blocking
    this actor when the consumer is behind); control always passes."""

    def __init__(self, client: ExchangePeerClient, chan: int,
                 schema: Schema, stats: EdgeStats):
        self.client = client
        self.chan = chan
        self.schema = schema
        self.stats = stats

    async def send(self, msg: Message) -> None:
        is_data = isinstance(msg, StreamChunk)
        n = await self.client.send(self.chan, message_to_wire(msg, self.schema),
                                   is_data, self.stats)
        self.stats.bytes += n
        if is_data:
            self.stats.chunks += 1
        elif isinstance(msg, Barrier):
            self.stats.saw_barrier(msg.epoch.curr)


class FragmentJob:
    """The fragments of ONE spanning job hosted by THIS worker process.
    Job-shaped for the WorkerHost (wait_barrier / stop / sources /
    pipeline / table), so barrier conduction, drop, scan, and stats treat
    it like a whole worker-hosted job; completion of an epoch means EVERY
    local fragment actor forwarded that epoch's barrier (state staged),
    which is what the worker's ``barrier_complete`` ack asserts."""

    spanning = True

    def __init__(self, name: str):
        self.name = name
        self.sources: list = []               # local source-feed queues
        self.pipeline = None                  # root MaterializeExecutor
        self.table = None
        self.exchange_inputs: List[ExchangeInput] = []
        self.exchange_outputs: List[ExchangeOutput] = []
        self.local_chan_ids: List[int] = []
        # per-fragment executor roots + the root actor's owned vnode
        # range: the live-migration export walks fragment_execs for
        # state tables, and scans of a vnode-distributed root MV filter
        # to root_vnodes (meta/rescale.py, worker/host.py)
        self.fragment_execs: Dict[int, object] = {}
        self.root_vnodes: Optional[tuple] = None
        self._actors: list = []               # (fragment) coroutine factories
        self._tasks: List[asyncio.Task] = []
        self._events: Dict[int, asyncio.Event] = {}
        self._counts: Dict[int, int] = {}
        self._failure: Optional[BaseException] = None

    def add_actor(self, run) -> None:
        self._actors.append(run)

    def start(self) -> None:
        for run in self._actors:
            self._tasks.append(asyncio.ensure_future(self._guard(run)))

    async def _guard(self, run) -> None:
        try:
            await run()
        except asyncio.CancelledError:
            raise
        except BaseException as e:  # noqa: BLE001 - surfaced on next await
            self._failure = self._failure or e
            for ev in self._events.values():
                ev.set()
            raise

    def _mark(self, epoch: int) -> None:
        n = self._counts.get(epoch, 0) + 1
        self._counts[epoch] = n
        if n >= len(self._actors):
            self._events.setdefault(epoch, asyncio.Event()).set()
            self._counts.pop(epoch, None)

    async def wait_barrier(self, epoch: int) -> None:
        if self._failure is not None:
            raise self._failure
        ev = self._events.setdefault(epoch, asyncio.Event())
        await ev.wait()
        self._events.pop(epoch, None)
        if self._failure is not None:
            raise self._failure

    async def stop(self) -> None:
        for t in self._tasks:
            t.cancel()
        for t in self._tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
        self._tasks.clear()


def _fragment_actor(job: FragmentJob, ex, dispatcher):
    """One fragment actor: drain the fragment subtree, forward every
    message into the output edge(s), and mark barrier passage — AFTER the
    dispatch, so the barrier is on the wire (socket order: all of the
    epoch's data precedes it) before this worker can ack the epoch."""

    async def run() -> None:
        async for msg in ex.execute():
            if dispatcher is not None:
                await dispatcher.dispatch(msg)
            if isinstance(msg, Barrier):
                job._mark(msg.epoch.curr)
                if msg.is_stop():
                    return
    return run


def build_fragments(host, req: dict, store) -> FragmentJob:
    """Build this worker's share of a spanning job from a
    ``create_fragments`` request (the worker half of the meta scheduler's
    deployment; reference: stream_service.rs build_actors). Each
    fragment spec carries its serialized subtree (PExchange cut leaves),
    its input edges (channel per upstream actor), and its output edge
    (dispatch kind + per-target channels naming remote peers)."""
    from ..frontend.build import BuildConfig

    name = req["name"]
    permits = int(req.get("permits", 32))
    cfg = BuildConfig(**req.get("config", {}))
    job = FragmentJob(name)
    state_table_ids: list[int] = []

    try:
        _build_fragments_into(host, req, store, job, state_table_ids,
                              permits, cfg)
    except BaseException:
        # a half-built deployment must leave NO endpoint registrations
        # behind: retried deployments allocate fresh channel ids, so a
        # leaked registration would never be reclaimed
        for inp in job.exchange_inputs:
            if host.exchange_inputs.get(inp.chan) is inp:
                host.exchange_inputs.pop(inp.chan, None)
        for out in job.exchange_outputs:
            out.client.unregister(out.chan)
        for chan in job.local_chan_ids:
            host.span_chans.pop(chan, None)
        raise
    job.state_table_ids = state_table_ids  # type: ignore[attr-defined]
    return job


def _build_fragments_into(host, req: dict, store, job: FragmentJob,
                          state_table_ids: list, permits: int, cfg) -> None:
    from ..frontend.build import BuildContext, build_plan
    from ..frontend.plan_json import plan_from_json
    from ..frontend.planner import PExchange, PSource
    from ..storage.state_table import StateTable
    from ..stream.materialize import MaterializeExecutor

    name = req["name"]
    for spec in req["fragments"]:
        plan = plan_from_json(spec["plan"], host.catalog)
        ids = iter(range(spec["id_start"],
                         spec["id_start"] + req["id_stride"]))

        def next_table_id(_ids=ids) -> int:
            return next(_ids)

        exchange_i = [0]
        shard_i = [0]
        inputs = spec["inputs"]

        def factory(leaf, _spec=spec, _inputs=inputs, _exi=exchange_i,
                    _shi=shard_i, _ids=next_table_id):
            if isinstance(leaf, PSource):
                shard = _spec["shard_base"] + _shi[0]
                _shi[0] += 1
                ex = host._source_leaf(leaf, name, store, _ids,
                                       shard_id=shard)
                inner = ex
                from ..frontend.runtime import QueueSource
                while not isinstance(inner, QueueSource):
                    inner = inner.input
                job.sources.append(inner)
                return ex
            if isinstance(leaf, PExchange):
                edge_in = _inputs[_exi[0]]
                _exi[0] += 1
                chans = []
                for c in edge_in["chans"]:
                    if c["from_worker"] == host.worker_id:
                        ch = host.span_chan(c["chan"], permits)
                        job.local_chan_ids.append(c["chan"])
                        chans.append(ch)
                    else:
                        stats = EdgeStats(c["edge"], "in", c["from_worker"])
                        inp = ExchangeInput(
                            c["chan"], leaf.schema, host.chunk_capacity,
                            stats, name,
                            link=(f"w{host.worker_id}"
                                  f"->w{c['from_worker']}"))
                        host.exchange_inputs[c["chan"]] = inp
                        job.exchange_inputs.append(inp)
                        chans.append(inp)
                return MergeExecutor(chans, leaf.schema)
            raise ValueError(
                f"cannot build span leaf {type(leaf).__name__}")

        vnodes = spec.get("vnodes")
        ctx = BuildContext(store, next_table_id, factory, cfg, durable=True,
                           vnode_range=(tuple(vnodes) if vnodes else None))
        pipeline = build_plan(plan, ctx)
        state_table_ids.extend(ctx.state_table_ids)
        if ctx.actors:
            raise ValueError(
                "span fragments must build single-actor subtrees "
                "(fragment_parallelism belongs to the scheduler here)")

        out = spec.get("output")
        if spec["is_root"]:
            mat = MaterializeExecutor(
                pipeline, StateTable(store, req["mv_table_id"],
                                     plan.schema, list(plan.pk)))
            job.pipeline = mat
            job.table = mat.table
            if vnodes:
                job.root_vnodes = tuple(vnodes)
            job.fragment_execs[spec["fid"]] = mat
            job.add_actor(_fragment_actor(job, mat, None))
        else:
            outs = []
            for t in out["targets"]:
                if t["worker"] == host.worker_id:
                    ch = host.span_chan(t["chan"], permits)
                    job.local_chan_ids.append(t["chan"])
                    outs.append(ch)
                else:
                    client = host.peer_pool.get(t["host"], t["port"],
                                                peer_worker=t["worker"])
                    client.register(t["chan"], permits)
                    stats = EdgeStats(t["edge"], "out", t["worker"])
                    o = ExchangeOutput(client, t["chan"], plan.schema, stats)
                    job.exchange_outputs.append(o)
                    outs.append(o)
            if out["kind"] == "hash":
                disp = HashDispatcher(outs, list(out["keys"]), plan.schema)
            elif len(outs) == 1:
                disp = SimpleDispatcher(outs[0])
            else:
                disp = BroadcastDispatcher(outs)
            job.fragment_execs[spec["fid"]] = pipeline
            job.add_actor(_fragment_actor(job, pipeline, disp))


def exchange_stats(host) -> list:
    """Per-edge counter snapshot for this worker's stats frame: every
    cross-worker edge endpoint it hosts, in both directions."""
    out = []
    for chan, inp in sorted(host.exchange_inputs.items()):
        out.append(inp.stats.snapshot(backlog=inp.qsize()))
    for job in host.jobs.values():
        for o in getattr(job, "exchange_outputs", ()):
            out.append(o.stats.snapshot())
    return out
