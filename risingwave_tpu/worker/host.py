"""Worker host: a compute-node process executing stream jobs shipped as
serialized plans.

Counterpart of the reference's compute node (reference:
src/compute/src/server.rs node bring-up; StreamService handlers
src/compute/src/rpc/service/stream_service.rs:46-233 build/drop actors +
barrier inject/collect; ExchangeService exchange_service.rs:74-133 moves
permit-metered data between processes). TPU-first scaling: ONE worker
process owns one accelerator's executors (device parallelism inside the
process rides the jax mesh), so the cross-process fabric only needs a
single multiplexed socket per worker, carrying:

  control   create_job / drop_job / barrier / commit / scan / shutdown
  data      channel frames (DML deltas, upstream changelogs) with
            consumption-acked permit flow (exchange/permit.rs:35-107)

Durability: the worker owns a DurableStateStore under its own directory.
Checkpointing is TWO-PHASE across the cluster: a checkpoint barrier seals
and stages worker state (ack = this worker's state for the epoch is
staged), and the session's later ``commit`` frame — sent only after every
worker acked and the session committed its own tier — makes it durable.
A worker killed between ack and commit recovers at the previous
checkpoint and its deterministic sources replay the gap (the reference
gets the same property from meta-owned Hummock version bumps:
src/meta/src/hummock/manager/ commit_epoch).
"""

from __future__ import annotations

import asyncio
import base64
import sys
from typing import AsyncIterator, Optional

from ..common.chunk import RowIdSequence, StreamChunk
from ..common.row import encode_value_row
from ..common.types import Field, INT64, Schema, VARCHAR
from ..connector.base import feed_chunks
from ..frontend.build import BuildConfig, BuildContext, build_plan
from ..frontend.catalog import Catalog
from ..frontend.plan_json import defs_from_json, plan_from_json
from ..frontend.planner import PMvScan, PSource, PTableScan
from ..frontend.runtime import QueueSource, StreamJob
from ..rpc.wire import message_from_wire, read_frame, write_frame
from ..storage.checkpoint import DurableStateStore
from ..storage.state_table import StateTable
from ..stream.eowc import WatermarkFilterExecutor
from ..stream.executor import Executor
from ..stream.materialize import MaterializeExecutor
from ..stream.message import Barrier, Message, Mutation, MutationKind
from ..stream.row_id_gen import RowIdGenExecutor


class _Feed:
    """Worker-side source feed: connector reader + split-state table
    (mirrors the session's _SourceFeed; offsets persist with checkpoints
    and recovery seeks them)."""

    def __init__(self, queue: QueueSource, reader, state_table: StateTable,
                 job: str, row_ids: RowIdSequence):
        self.queue = queue
        self.reader = reader
        self.state_table = state_table
        self.offsets_at_epoch: dict[int, dict] = {}
        self.job = job
        self.row_ids = row_ids      # where the leaf's hidden _row_id stands


class _ChannelSource(Executor):
    """Executor view of a wire data channel: frames decode lazily and the
    permit ack is sent only when the consumer TAKES a chunk — end-to-end
    consumption-based flow control (reference: permit.rs — data consumes
    credits, control always passes). Session data frames carry per-chan
    sequence numbers (frontend/remote.py send_data); duplicates are
    dropped un-acked and delayed frames re-enter in send order — the
    session→worker half of the exchange-edge dedup discipline."""

    identity = "RemoteExchangeSource"

    def __init__(self, host: "WorkerHost", chan: int, schema: Schema,
                 capacity: int):
        from ..rpc.exchange import SeqReorderBuffer
        self.host = host
        self.chan = chan
        self.schema = schema
        self.capacity = capacity
        self.queue: asyncio.Queue = asyncio.Queue()
        self._seqbuf = SeqReorderBuffer()
        self._ack_seq = 0

    @property
    def dup_frames(self) -> int:
        return self._seqbuf.dup_frames

    @property
    def reordered(self) -> int:
        return self._seqbuf.reordered

    def feed(self, wire_msg, seq: Optional[int] = None) -> None:
        """Session data frame arrival: dedup + re-order by seq before
        the frame reaches the executor queue (a dropped duplicate is
        NOT acked — the session consumed one permit for it)."""
        for item in self._seqbuf.feed(seq, wire_msg):
            self.queue.put_nowait(item)

    async def execute(self) -> AsyncIterator[Message]:
        while True:
            d = await self.queue.get()
            if d is None:
                return
            if isinstance(d, Message):        # locally injected (init cut)
                msg = d
            else:
                msg = message_from_wire(d, self.schema, self.capacity)
                if isinstance(msg, StreamChunk):
                    ack_seq = self._ack_seq
                    self._ack_seq += 1
                    await self.host.send({"type": "ack", "chan": self.chan,
                                          "seq": ack_seq})
            yield msg
            if isinstance(msg, Barrier) and msg.is_stop():
                return


class WorkerHost:
    """One worker process: jobs + durable store + the session socket."""

    def __init__(self, data_dir: str, worker_id: int = 0):
        from ..rpc.exchange import PeerClientPool
        self.data_dir = data_dir
        self.worker_id = worker_id
        # one durable store per JOB: recovery scope and id space are both
        # per-job, so a fresh rebuild wipes one directory without
        # tombstone bookkeeping leaking across incarnations
        self.stores: dict[str, DurableStateStore] = {}
        self.catalog = Catalog()
        self.jobs: dict[str, StreamJob] = {}
        self.feeds: list[_Feed] = []
        self.channels: dict[int, _ChannelSource] = {}
        # cross-worker exchange state (stream/remote_exchange.py): inputs
        # fed by peer connections, worker-local span channels, and the
        # pooled client connections toward peer workers
        self.exchange_inputs: dict[int, object] = {}
        self.span_chans: dict[int, object] = {}
        self.peer_pool = PeerClientPool(worker_id)
        # session-generation fencing (ISSUE 9): each job records the
        # generation its deployment frame carried; a barrier or commit
        # frame from an OLDER generation — a stale pre-recovery session
        # view, or a chaos-delayed frame arriving after scoped recovery
        # rebuilt the graph — is refused instead of acked/committed
        self.job_gens: dict[str, int] = {}
        self.fenced_frames = 0
        # elastic scaling plane counters (meta/rescale.py): rows exported
        # to / imported from handoff segments by live vnode migrations
        self.migrated_rows_out = 0
        self.migrated_rows_in = 0
        self.chunks_per_tick = 1
        self.chunk_capacity = 1024
        self.seed = 42
        # session-propagated fault-tolerance knobs (create_job frames):
        # worker-hosted broker readers must honor the SAME reconnect
        # budget as session-hosted ones
        self.fault = None
        self._next_shard = worker_id * 4096 + 1
        self._writer: Optional[asyncio.StreamWriter] = None
        self._wlock = asyncio.Lock()
        # tracing-span outbox: drained batches are retained until the
        # session's NEXT stats request acknowledges their sequence
        # number, so a timed-out (discarded) stats reply loses no spans
        self._span_outbox: list = []
        self._span_seq = 0

    async def send(self, obj: dict, meta: bool = False) -> None:
        if self._writer is not None:
            await write_frame(self._writer, obj, self._wlock,
                              link=f"w{self.worker_id}->s", meta=meta)

    # -- job construction ------------------------------------------------------

    def span_chan(self, chan: int, permits: int):
        """Get-or-create a worker-LOCAL span edge channel (both endpoint
        fragments of the edge live in this process). Registered by id so
        whichever side builds first wires the same channel."""
        ch = self.span_chans.get(chan)
        if ch is None:
            from ..stream.dispatch import open_channel
            ch = open_channel(permits)
            self.span_chans[chan] = ch
        return ch

    def _source_leaf(self, leaf: PSource, job_name: str, store,
                     next_table_id, shard_id: Optional[int] = None) -> Executor:
        src = leaf.source
        from ..connector.factory import make_reader
        reader = make_reader(src.connector, src.options, src.schema,
                             self.chunk_capacity, self.seed,
                             fault=self.fault)
        # span fragments pin their shard id from the session (stable
        # across drop-and-rebuild recovery, so replayed rows reproduce
        # their pre-crash row ids — the exactly-once upsert condition for
        # row-id-keyed MVs); whole-job placement keeps the process-local
        # counter
        row_ids = RowIdSequence(self._alloc_shard()
                                if shard_id is None else shard_id)
        ex: Executor
        if reader is None:
            # nothing feeds it here; what is pushed is on the device already
            ex = RowIdGenExecutor(QueueSource(src.schema), leaf.schema,
                                  row_ids)
        else:
            st = StateTable(store, next_table_id(),
                            Schema((Field("split_id", VARCHAR),
                                    Field("next_offset", INT64))), [0])
            offsets = {VARCHAR.to_python(r[0]): int(r[1])
                       for r in st.scan_all()}
            if offsets:           # recovered split state: seek
                reader.seek(offsets)
                row_ids.next = reader.rows_emitted()
            # the feed's chunks are staged with their _row_id
            # (common/chunk.stage_chunks): the queue IS the leaf
            ex = q = QueueSource(leaf.schema)
            self.feeds.append(_Feed(q, reader, st, job_name, row_ids))
        if src.watermark is not None:
            col, delay = src.watermark
            ex = WatermarkFilterExecutor(ex, time_col=col, delay=delay)
        return ex

    def _alloc_shard(self) -> int:
        self._next_shard += 1
        return self._next_shard - 1

    def _set_fault(self, fault: dict) -> None:
        """Adopt the session's fault-tolerance knobs (shipped on every
        create frame) — including the exchange keepalive cadence the
        peer pool hands to new clients."""
        from ..common.config import FaultConfig
        self.fault = FaultConfig(**fault)
        self.peer_pool.keepalive_s = self.fault.exchange_keepalive_s
        self.peer_pool.keepalive_timeout_s = \
            self.fault.exchange_keepalive_timeout_s

    def _job_dir(self, name: str) -> str:
        import os
        return os.path.join(self.data_dir, "jobs", name)

    def _register_defs(self, defs_json: str) -> None:
        """Upsert the session's shipped catalog replicas (shared by job
        creation and batch tasks so the two cannot resolve different
        catalogs)."""
        for d in defs_from_json(defs_json):
            kind = type(d).__name__
            reg = {"SourceDef": self.catalog.sources,
                   "TableDef": self.catalog.tables,
                   "MaterializedViewDef": self.catalog.mvs}[kind]
            reg[d.name] = d

    async def handle_create_job(self, req: dict) -> dict:
        name = req["name"]
        if req.get("fresh"):
            # table-fed jobs rebuild from the upstream snapshot: wipe any
            # prior incarnation's durable state wholesale (in-memory AND
            # on-disk — the store object must not outlive the wipe)
            import shutil
            shutil.rmtree(self._job_dir(name), ignore_errors=True)
            self.stores.pop(name, None)
        store = self.stores.get(name)
        if store is None:
            store = DurableStateStore(self._job_dir(name))
            self.stores[name] = store
        self._register_defs(req["defs"])
        self.chunks_per_tick = req.get("chunks_per_tick", 1)
        self.chunk_capacity = req.get("chunk_capacity", 1024)
        self.seed = req.get("seed", 42)
        plan = plan_from_json(req["plan"], self.catalog)
        chan_of_leaf = {int(k): v for k, v in req.get("channels", {}).items()}
        ids = iter(range(req["id_start"], req["id_start"] + 10_000))
        leaf_i = [0]
        queues: list[QueueSource] = []

        def next_table_id() -> int:
            return next(ids)

        def factory(leaf) -> Executor:
            i = leaf_i[0]
            leaf_i[0] += 1
            if isinstance(leaf, PSource):
                ex = self._source_leaf(leaf, name, store, next_table_id)
                # find the root queue for barrier injection
                inner = ex
                while not isinstance(inner, QueueSource):
                    inner = inner.input
                queues.append(inner)
                return ex
            if isinstance(leaf, (PTableScan, PMvScan)):
                chan = chan_of_leaf.get(i)
                if chan is None:
                    raise ValueError(
                        f"scan leaf {i} of remote job {name!r} has no "
                        "exchange channel")
                ch = _ChannelSource(self, chan, leaf.schema,
                                    self.chunk_capacity)
                self.channels[chan] = ch
                return ch
            raise ValueError(
                f"cannot build remote leaf {type(leaf).__name__}")

        if req.get("fault"):
            self._set_fault(req["fault"])
        cfg = BuildConfig(**req.get("config", {}))
        ctx = BuildContext(store, next_table_id, factory, cfg,
                           durable=True)
        chans_before = set(self.channels)
        try:
            pipeline = build_plan(plan, ctx)
        except Exception:
            # half-built job: release anything the factory registered
            for c in set(self.channels) - chans_before:
                self.channels.pop(c, None)
            self.feeds = [f for f in self.feeds if f.job != name]
            raise
        mat = MaterializeExecutor(
            pipeline, StateTable(store, req["mv_table_id"],
                                 plan.schema, list(plan.pk)))
        job = StreamJob(name, mat, queues, actors=ctx.actors)
        self.jobs[name] = job
        self.job_gens[name] = int(req.get("gen", 0))
        job.start()                          # current (running) loop
        return {"ok": True, "state_table_ids": ctx.state_table_ids,
                "ids_end": next(ids)}

    async def handle_create_fragments(self, req: dict) -> dict:
        """Build this worker's fragments of a SPANNING job (the fragment
        scheduler placed the graph across workers; exchange edges name
        remote peers). Reference: stream_service.rs:46 build_actors — one
        request per compute node, naming the actors it hosts."""
        from ..stream.remote_exchange import build_fragments
        name = req["name"]
        if req.get("fresh"):
            import shutil
            shutil.rmtree(self._job_dir(name), ignore_errors=True)
            self.stores.pop(name, None)
        store = self.stores.get(name)
        created_store = store is None
        if store is None:
            # recover_at: the cluster-decided checkpoint cut — prepared
            # epochs ≤ it roll forward, later ones are discarded, so all
            # participants of the span rebuild the SAME epoch
            store = DurableStateStore(self._job_dir(name),
                                      recover_at=req.get("recover_at"))
            self.stores[name] = store
        # live-migration handoff: fragment specs may carry state REFS —
        # handoff segments a previous owner exported to shared storage
        # (storage/checkpoint.py write_handoff) for the vnode ranges this
        # actor is gaining. Import them into the committed tier BEFORE
        # the build below, so executors reload them like any other
        # recovered state (their load_vnodes filter scopes the reload to
        # the owned range either way).
        for spec in req.get("fragments", ()):
            for ref in spec.get("import_refs", ()) or ():
                from ..storage.checkpoint import read_handoff
                deltas = read_handoff(ref)
                self.migrated_rows_in += store.import_tables(
                    deltas, int(req.get("recover_at") or 0))
        self._register_defs(req["defs"])
        self.chunks_per_tick = req.get("chunks_per_tick", 1)
        self.chunk_capacity = req.get("chunk_capacity", 1024)
        self.seed = req.get("seed", 42)
        if req.get("fault"):
            self._set_fault(req["fault"])
        feeds0 = len(self.feeds)
        try:
            # (build_fragments rolls its own endpoint registrations back)
            job = build_fragments(self, req, store)
        except Exception:
            self.feeds = self.feeds[:feeds0]
            if created_store:
                # a retry must re-run recover_at against the on-disk
                # manifest, not reuse this half-initialized instance
                self.stores.pop(name, None)
            raise
        self.jobs[name] = job
        self.job_gens[name] = int(req.get("gen", 0))
        job.start()
        return {"ok": True,
                "state_table_ids": job.state_table_ids}

    def _release_span_job(self, job) -> None:
        """Unregister a FragmentJob's exchange endpoints so a later
        incarnation (recovery re-creates with FRESH channel ids) never
        collides with stale registrations."""
        for inp in getattr(job, "exchange_inputs", ()):
            if self.exchange_inputs.get(inp.chan) is inp:
                self.exchange_inputs.pop(inp.chan, None)
            inp.put_local(None)           # unblock a parked merge recv
        for out in getattr(job, "exchange_outputs", ()):
            out.client.unregister(out.chan)
        for chan in getattr(job, "local_chan_ids", ()):
            self.span_chans.pop(chan, None)

    async def handle_drop_job(self, req: dict) -> dict:
        name = req["name"]
        job = self.jobs.pop(name, None)
        if job is None:
            return {"ok": True}
        stop = Barrier.new(req["epoch"],
                           mutation=Mutation(MutationKind.STOP))
        for q in job.sources:
            q.push(stop)
        if getattr(job, "spanning", False):
            await job.stop()              # actors cancel mid-exchange
            self._release_span_job(job)
        else:
            for ch in _channel_roots(job):
                ch.queue.put_nowait(stop)
                self.channels.pop(ch.chan, None)
            await job.stop()
        self.feeds = [f for f in self.feeds if f.job != name]
        self.stores.pop(name, None)
        self.job_gens.pop(name, None)
        if req.get("drop_state", True):
            import shutil
            shutil.rmtree(self._job_dir(name), ignore_errors=True)
        return {"ok": True}

    # -- barrier conduction ----------------------------------------------------

    async def handle_barrier(self, req: dict) -> None:
        """Inject this epoch into worker-driven roots, then collect all
        in-scope jobs and ack with a PER-JOB failure map. Runs as its own
        task so data frames keep flowing while executors work (barrier
        pipelining). ``exclude`` names jobs the session already declared
        dead (a spanning job with a killed peer): they must be neither
        fed nor waited on — one starved job must not wedge this worker's
        healthy jobs."""
        epoch = int(req["epoch"])
        checkpoint = bool(req.get("checkpoint", False))
        only = req.get("only")
        scope = set(only) if only is not None else set(self.jobs)
        scope -= set(req.get("exclude") or ())
        gen = req.get("gen")
        if gen is not None:
            # fencing: a barrier from an older session generation must
            # not reach jobs a newer generation already rebuilt — acking
            # it would let a stale graph stage state under the cluster's
            # current epoch cut
            stale = {n for n in scope
                     if self.job_gens.get(n, 0) > int(gen)}
            if stale:
                self.fenced_frames += len(stale)
                scope -= stale
        mut = None
        if req.get("mutation"):
            mut = Mutation(MutationKind(req["mutation"]),
                           req.get("mutation_payload"))
        barrier = Barrier.new(epoch, checkpoint=checkpoint, mutation=mut)
        if req.get("generate", False):
            for feed in self.feeds:
                if feed.job not in scope:
                    continue
                feed_chunks(feed.reader.next_host_chunk,
                            self.chunks_per_tick, feed.queue.push,
                            row_ids=feed.row_ids)
        for feed in self.feeds:
            if feed.job in scope:
                feed.offsets_at_epoch[epoch] = feed.reader.offsets
                feed.queue.push(barrier)
        if req.get("init", False):
            # init cut for a just-created job: its channel roots have no
            # live upstream stream yet, so the barrier is injected locally
            # (span fragments skip this — their exchange inputs have live
            # peers and the init barrier arrives over the wire)
            for name in scope:
                job = self.jobs.get(name)
                if job is not None and not getattr(job, "spanning", False):
                    for ch in _channel_roots(job):
                        ch.queue.put_nowait(barrier)
        failed: dict[str, str] = {}

        async def collect(name: str, job) -> None:
            from ..rpc.exchange import PeerLost
            try:
                await job.wait_barrier(epoch)
            except PeerLost as e:
                failed[name] = f"PEER_LOST: {e}"
            except asyncio.CancelledError:
                raise
            except BaseException as e:  # noqa: BLE001 - shipped per job
                if isinstance(getattr(job, "_failure", None), PeerLost):
                    failed[name] = f"PEER_LOST: {job._failure}"
                else:
                    failed[name] = repr(e)

        from ..common.tracing import CAT_EPOCH, span
        with span("barrier.collect", epoch=epoch, stage="worker_collect",
                  cat=CAT_EPOCH, tid="conductor", checkpoint=checkpoint):
            await asyncio.gather(
                *(collect(n, self.jobs[n]) for n in scope
                  if n in self.jobs))
        if checkpoint:
            for feed in self.feeds:
                if feed.job not in scope or feed.job in failed:
                    continue
                latest = None
                for oe in sorted(list(feed.offsets_at_epoch)):
                    if oe <= epoch:
                        latest = feed.offsets_at_epoch.pop(oe)
                if latest is not None:
                    for sid, off in latest.items():
                        feed.state_table.insert(
                            (VARCHAR.to_physical(sid), int(off)))
                    feed.state_table.commit(epoch)
            # spanning jobs: phase 1 of the cluster 2PC — this ack asserts
            # the epoch is DURABLY staged (state + offsets), so a kill
            # between ack and the session's commit frame can be rolled
            # FORWARD at recovery to the epoch the peers committed
            for name in scope:
                job = self.jobs.get(name)
                if job is None or name in failed \
                        or not getattr(job, "spanning", False):
                    continue
                store = self.stores.get(name)
                if store is not None:
                    store.prepare(epoch)
        done = {"type": "barrier_complete", "epoch": epoch,
                "failed": failed, "init": bool(req.get("init", False))}
        if gen is not None:
            done["gen"] = int(gen)   # session drops acks from stale gens
        await self.send(done)

    def handle_job_epochs(self, req: dict) -> dict:
        """Recovery negotiation: what this worker durably holds for one
        job — its committed epoch and any prepared-but-uncommitted
        epochs. The session takes the MAX committed across participants
        as the decided cut and every store settles to it (roll forward
        or discard) via ``create_fragments``' ``recover_at``."""
        from ..storage.checkpoint import CheckpointLog
        name = req["name"]
        store = self.stores.get(name)
        log = store.log if store is not None \
            else CheckpointLog(self._job_dir(name))
        if not log.exists():
            return {"ok": True, "committed": 0, "prepared": []}
        committed, prepared = log.recovery_info()
        return {"ok": True, "committed": committed, "prepared": prepared}

    # -- elastic scaling plane (live vnode migration) --------------------------

    @staticmethod
    def _vnode_tables(ex) -> list:
        """The vnode-partitioned state tables under one fragment's
        executor subtree, as (StateTable, key_indices, key_types) —
        what a live migration must hand off for a moving range. Covers
        the shapes the scaling plane migrates (``shardable`` fragments:
        grouped-agg cores under row-wise operators, plus the root
        materialize); exchange leaves end the walk."""
        from ..stream.hash_agg import HashAggExecutor
        from ..stream.materialized_agg import MaterializedAggExecutor \
            as _MatAgg
        out = []
        stack, seen = [ex], set()
        while stack:
            node = stack.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            if isinstance(node, MaterializeExecutor) \
                    and node.table is not None:
                t = node.table
                out.append((t, tuple(t.pk_indices),
                            tuple(t.schema[i].type for i in t.pk_indices)))
            if isinstance(node, HashAggExecutor) \
                    and node.state_table is not None:
                nk = len(node.core.group_keys)
                out.append((node.state_table, tuple(range(nk)),
                            tuple(node.core.key_types)))
            if isinstance(node, _MatAgg) \
                    and node.state_table is not None and node.group_keys:
                nk = len(node.group_keys)
                out.append((node.state_table, tuple(range(nk)),
                            tuple(node.in_schema[i].type
                                  for i in node.group_keys)))
            for attr in ("input", "left", "right"):
                child = getattr(node, attr, None)
                if isinstance(child, Executor):
                    stack.append(child)
            for child in getattr(node, "inputs", ()):
                if isinstance(child, Executor):
                    stack.append(child)
        return out

    def handle_rescale_export(self, req: dict) -> dict:
        """Export the committed rows of one fragment's moving vnode
        ranges as handoff segments on shared storage, returning their
        REFS (paths). Runs on the quiesced pre-migration graph: the
        session drained + checkpoint-flushed first, so the committed
        tier is the complete state of the epoch being handed off
        (reference: scale.rs:657 shipping state as SST refs)."""
        import os

        from ..common.hashing import vnodes_of_rows
        from ..common.row import decode_value_row
        from ..storage.checkpoint import write_handoff
        name = req["name"]
        job = self.jobs.get(name)
        if job is None:
            return {"ok": False, "error": f"job {name!r} not found"}
        ex = getattr(job, "fragment_execs", {}).get(int(req["fragment"]))
        if ex is None:
            return {"ok": False,
                    "error": f"fragment {req['fragment']} not hosted here"}
        os.makedirs(req["dir"], exist_ok=True)
        refs = []
        tables = self._vnode_tables(ex)
        for start, end in req["ranges"]:
            deltas: dict[int, dict] = {}
            moved = 0
            for table, key_idx, key_types in tables:
                kept: dict[bytes, bytes] = {}
                pairs = list(table.store.iter_table(table.table_id))
                rows = [decode_value_row(v, table.schema.types)
                        for _k, v in pairs]
                vns = vnodes_of_rows(
                    key_types, [[r[i] for i in key_idx] for r in rows])
                for (k, v), vn in zip(pairs, vns):
                    if start <= vn < end:
                        kept[k] = v
                if kept:
                    deltas[table.table_id] = kept
                    moved += len(kept)
            path = os.path.join(
                req["dir"],
                f"f{int(req['fragment'])}_{start}_{end}"
                f"_w{self.worker_id}.seg")
            write_handoff(path, deltas)
            self.migrated_rows_out += moved
            refs.append({"path": path, "vnode_start": start,
                         "vnode_end": end, "rows": moved,
                         "tables": {str(t): len(r)
                                    for t, r in deltas.items()}})
        return {"ok": True, "refs": refs, "worker": self.worker_id}

    def handle_set_rate(self, req: dict) -> dict:
        """Adjust this worker's per-tick source generation rate live —
        the traffic-spike lever (sim.py run_traffic_spike drives it; the
        autoscaler reacts to the resulting backlog)."""
        self.chunks_per_tick = max(0, int(req["chunks_per_tick"]))
        return {"ok": True, "chunks_per_tick": self.chunks_per_tick}

    # -- distributed batch stage ----------------------------------------------

    def handle_batch_task(self, req: dict) -> dict:
        """Execute a batch plan FRAGMENT against this worker's job store
        and return its result rows — the distributed batch stage
        (reference: per-stage task execution on compute nodes,
        src/frontend/src/scheduler/distributed/query.rs:69,115 +
        BatchManager::fire_task, task_manager.rs:93). Only the stage's
        OUTPUT crosses the wire, not the scanned state."""
        from ..batch.executors import run_batch
        from ..batch.lower import lower_plan
        name = req["job"]
        store = self.stores.get(name)
        if store is None:
            return {"ok": False, "error": f"job {name!r} has no store"}
        self._register_defs(req["defs"])
        plan = plan_from_json(req["plan"], self.catalog)
        # optional per-task vnode slice (the serving plane's two-phase
        # partial tasks restrict their scans to the slice they own;
        # slice-unsafe shapes refuse by lowering to None)
        vnodes = req.get("vnodes")
        ex = lower_plan(plan, store, vnodes=vnodes)
        if ex is None:
            return {"ok": False,
                    "error": "stage plan is not batch-lowerable"}
        types = [f.type for f in plan.schema]
        rows = [base64.b64encode(encode_value_row(r, types)).decode()
                for r in run_batch(ex)]
        return {"ok": True, "rows": rows, "worker": self.worker_id,
                "n_rows": len(rows)}

    # -- monitor ---------------------------------------------------------------

    def handle_stats(self, req: dict) -> dict:
        """Monitor snapshot: per-job executor trees + counters + state
        bytes, exchange queue depths, and a drain of this process's
        tracing-span ring — the worker half of metrics federation
        (reference: MonitorService.stack_trace + Prometheus exporters,
        src/compute/src/rpc/service/monitor_service.rs:46)."""
        from ..common.memory import pipeline_state_bytes
        from ..common.profiling import GLOBAL_PROFILER
        from ..common.tracing import GLOBAL_TRACE
        from ..stream.metrics import pipeline_metrics
        from ..stream.trace import executor_tree
        jobs: dict = {}
        trees: dict = {}
        state_bytes: dict = {}
        for name, job in self.jobs.items():
            if job.pipeline is None:
                continue
            jobs[name] = pipeline_metrics(job.pipeline)
            trees[name] = executor_tree(job.pipeline)
            try:
                state_bytes[name] = pipeline_state_bytes(job.pipeline)
            except Exception:  # noqa: BLE001 - stats must never fail a job
                pass
        if req.get("span_ack") == self._span_seq:
            self._span_outbox = []         # previous batch safely landed
        new = GLOBAL_TRACE.drain()
        if new:
            self._span_outbox.extend(s.to_dict() for s in new)
            cap = GLOBAL_TRACE.capacity    # bound resends like the ring
            if len(self._span_outbox) > cap:
                del self._span_outbox[:-cap]
            self._span_seq += 1
        # barrier observatory: this process's epoch-stamped stage events
        # (storage prepare/settle/commit, worker collect) ride the SAME
        # stats frame as spans, with the same retained-until-acked outbox
        # discipline — no extra RPC, nothing on the barrier path
        from ..common.barrier_ledger import GLOBAL_STAGES
        stage_seq, stage_events = GLOBAL_STAGES.drain_outbox(
            req.get("stage_ack"))
        from ..rpc.faults import chaos_snapshot
        from ..stream.remote_exchange import exchange_stats
        return {
            "ok": True, "worker_id": self.worker_id,
            "jobs": jobs, "trees": trees, "state_bytes": state_bytes,
            "queue_depths": {str(c): ch.queue.qsize()
                             for c, ch in self.channels.items()},
            # per-exchange-edge counters (permits waited, chunks/bytes
            # forwarded, backlog) for every cross-worker edge endpoint
            # this process hosts — federated into metrics()["exchange"]
            "exchange": exchange_stats(self),
            # fault-plane state: this process's chaos injections plus
            # the fencing / dedup counters the plane's injection forced
            "chaos": {**chaos_snapshot(),
                      "fenced_frames": self.fenced_frames,
                      "pool_evictions": self.peer_pool.evictions,
                      "dup_data_frames": sum(
                          ch.dup_frames for ch in self.channels.values())},
            # elastic scaling plane: handoff rows this process exported /
            # imported across live vnode migrations (meta/rescale.py)
            "rescale": {"rows_out": self.migrated_rows_out,
                        "rows_in": self.migrated_rows_in},
            # device profiling plane: this process's per-dispatch
            # telemetry (common/profiling.py) — federated into
            # Session.metrics()["profiling"]["workers"]
            "profiling": GLOBAL_PROFILER.snapshot(),
            "spans": list(self._span_outbox), "span_seq": self._span_seq,
            "barrier_stages": stage_events, "stage_seq": stage_seq,
        }

    # -- scan ------------------------------------------------------------------

    def handle_scan(self, req: dict) -> dict:
        name = req["name"]
        job = self.jobs.get(name)
        if job is None:
            return {"ok": False, "error": f"job {name!r} not found"}
        if job.table is None:
            return {"ok": False,
                    "error": f"job {name!r} hosts no table on this worker"}
        schema = job.pipeline.schema
        types = [f.type for f in schema]
        rows = list(job.pipeline.scan_all())
        rv = getattr(job, "root_vnodes", None)
        if rv is not None:
            # vnode-distributed root MV: serve only the owned range. A
            # live migration leaves moved-away rows behind in this store
            # (bounded leftovers, reloaded by nobody); without this
            # filter the scan union across root actors would double-read
            # them (meta/rescale.py, docs/scaling.md).
            from ..common.hashing import filter_rows_vnodes
            pk = list(job.table.pk_indices)
            rows = filter_rows_vnodes(
                [types[i] for i in pk], rows, rv[0], rv[1],
                key_indices=pk)
        rows = [base64.b64encode(encode_value_row(r, types)).decode()
                for r in rows]
        return {"ok": True, "rows": rows}

    # -- serve -----------------------------------------------------------------

    async def _reply(self, frame: dict, handler,
                     meta: bool = False) -> None:
        """Per-request error isolation: a failing handler (bad plan,
        unknown connector, missing file) answers THIS request with the
        error — it must never tear down the worker and its other jobs
        (the local path surfaces the same failures as per-statement
        SqlErrors). ``meta`` marks wall-clock-driven replies (stats
        polls) so the fault plane keeps them out of the deterministic
        frame-seq stream."""
        try:
            resp = await handler(frame)
        except Exception as e:  # noqa: BLE001 - shipped to the session
            resp = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        resp.update({"type": "reply", "rid": frame["rid"]})
        await self.send(resp, meta=meta)

    async def handle_conn(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> str:
        """Dispatch a fresh inbound connection: the session's control
        socket, or a PEER worker's exchange socket (first frame is its
        ``exg_hello``). Returns which kind this was so the server only
        exits when the SESSION goes away."""
        first = await read_frame(reader)
        if first is None:
            # closed before identifying itself: a peer killed between
            # connect and its exg_hello, or a port probe. Treating it as
            # the session would clobber the real session's writer and
            # self-terminate a healthy worker.
            writer.close()
            return "empty"
        if first.get("type") == "exg_hello":
            await self._handle_peer_conn(reader, writer, first)
            return "peer"
        await self._handle_session_conn(reader, writer, first)
        return "session"

    async def _handle_peer_conn(self, reader, writer, hello: dict) -> None:
        """Exchange data plane from one peer worker: route exg_data
        frames to their registered inputs; the same socket carries the
        consumption acks back (reference: exchange_service.rs:74-133).
        On disconnect every edge fed by this peer is failed loudly —
        a silently starved merge would wedge barrier collection."""
        wlock = asyncio.Lock()
        fed: set[int] = set()
        peer = hello.get("worker")
        link = (f"w{self.worker_id}->w{peer}" if peer is not None
                else f"w{self.worker_id}->peer")
        try:
            while True:
                frame = await read_frame(reader)
                if frame is None:
                    break
                t = frame.get("type")
                if t == "exg_data":
                    chan = frame["chan"]
                    inp = self.exchange_inputs.get(chan)
                    if inp is not None:
                        fed.add(chan)
                        inp.feed_wire(frame["msg"], writer, wlock,
                                      seq=frame.get("seq"))
                elif t == "exg_ping":
                    # keepalive probe: answer on the same socket so a
                    # half-open link (answer eaten, or this process
                    # wedged) times out on the prober's side
                    try:
                        await write_frame(
                            writer, {"type": "exg_pong",
                                     "seq": frame.get("seq", 0)},
                            wlock, link=link, meta=True)
                    except (ConnectionError, OSError):
                        break
        finally:
            for chan in fed:
                inp = self.exchange_inputs.get(chan)
                if inp is not None:
                    inp.peer_lost()
            writer.close()

    async def _handle_session_conn(self, reader, writer,
                                   first: Optional[dict]) -> None:
        self._writer = writer
        tasks: list[asyncio.Task] = []
        frame = first
        try:
            while True:
                if frame is None:
                    break                        # session died: exit
                t = frame["type"]
                if t == "data":
                    ch = self.channels.get(frame["chan"])
                    if ch is not None:
                        ch.feed(frame["msg"], frame.get("seq"))
                elif t == "barrier":
                    tasks.append(
                        asyncio.ensure_future(self.handle_barrier(frame)))
                elif t == "commit":
                    # phase 2 of the cluster checkpoint: every job's
                    # staged state for the epoch becomes durable —
                    # except jobs the session excludes (a spanning job
                    # with a dead peer must not have its SURVIVING
                    # fragments' torn epochs committed under it) and
                    # jobs whose deployment generation FENCES this frame
                    # (a stale pre-recovery commit must not promote a
                    # rebuilt job's staged epochs)
                    skip = set(frame.get("skip_jobs") or ())
                    cgen = frame.get("gen")
                    for jname, store in self.stores.items():
                        if jname in skip:
                            continue
                        if cgen is not None \
                                and self.job_gens.get(jname, 0) > int(cgen):
                            self.fenced_frames += 1
                            continue
                        store.commit(int(frame["epoch"]))
                elif t == "create_job":
                    await self._reply(frame, self.handle_create_job)
                elif t == "create_fragments":
                    await self._reply(frame, self.handle_create_fragments)
                elif t == "job_epochs":
                    async def _je(f):
                        return self.handle_job_epochs(f)
                    await self._reply(frame, _je)
                elif t == "rescale_export":
                    async def _re(f):
                        return self.handle_rescale_export(f)
                    await self._reply(frame, _re)
                elif t == "set_rate":
                    async def _sr(f):
                        return self.handle_set_rate(f)
                    await self._reply(frame, _sr)
                elif t == "drop_job":
                    await self._reply(frame, self.handle_drop_job)
                elif t == "scan":
                    async def _scan(f):
                        return self.handle_scan(f)
                    await self._reply(frame, _scan)
                elif t == "stats":
                    async def _stats(f):
                        return self.handle_stats(f)
                    await self._reply(frame, _stats, meta=True)
                elif t == "batch_task":
                    async def _bt(f):
                        return self.handle_batch_task(f)
                    await self._reply(frame, _bt)
                elif t == "shutdown":
                    await self.send({"type": "reply", "rid": frame["rid"],
                                     "ok": True})
                    break
                else:
                    await self.send({"type": "reply",
                                     "rid": frame.get("rid"),
                                     "ok": False,
                                     "error": f"unknown frame {t!r}"})
                frame = await read_frame(reader)
        finally:
            for t in tasks:
                if not t.done():
                    t.cancel()
            for job in self.jobs.values():
                await job.stop()
            writer.close()


def _channel_roots(job: StreamJob):
    """The _ChannelSource leaves of a job's pipeline (walked, not
    registered: channels are created inside the build factory)."""
    out = []
    stack = [job.pipeline]
    while stack:
        node = stack.pop()
        if isinstance(node, _ChannelSource):
            out.append(node)
            continue
        for attr in ("input", "left", "right"):
            child = getattr(node, attr, None)
            if isinstance(child, Executor):
                stack.append(child)
        for child in getattr(node, "inputs", ()):
            stack.append(child)
    return out


async def amain(data_dir: str, worker_id: int, port: int) -> None:
    import os
    from ..common.failpoint import arm_from_env
    from ..rpc.faults import install_from_env
    # adopt the spawning session's chaos schedule (RWTPU_CHAOS env);
    # injections append to a per-worker trace file so a killed worker's
    # pre-death trace survives for seeded-replay comparison. The
    # crash-point sweep arms process-exit failpoints the same way
    # (RWTPU_FAILPOINTS) — a worker dies AT the armed 2PC site.
    install_from_env(trace_path=os.path.join(data_dir,
                                             "chaos_trace.jsonl"))
    arm_from_env(worker_id=worker_id)
    # claim the backend BEFORE announcing readiness: a worker that cannot
    # get its platform (a chip another process holds) dies here with
    # JAX's own error, and the spawning session sees the exit at once
    import jax
    jax.devices()
    host = WorkerHost(data_dir, worker_id)
    done = asyncio.Event()

    async def conn(reader, writer):
        kind = None
        try:
            kind = await host.handle_conn(reader, writer)
        finally:
            # peer (worker↔worker exchange) connections come and go with
            # jobs. Losing the SESSION's control socket — or an
            # unexpected handler crash (kind still None) — ends the
            # process. An "empty" close (no frame before EOF) is a stray
            # probe IF a session already attached; before any session
            # ever attached it can only be the spawning session dying
            # mid-connect — exit rather than orphan the process.
            if kind == "peer":
                return
            if kind == "empty" and host._writer is not None:
                return
            done.set()

    server = await asyncio.start_server(conn, "127.0.0.1", port)
    actual = server.sockets[0].getsockname()[1]
    print(f"WORKER_READY {actual}", flush=True)
    async with server:
        await done.wait()


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--worker-id", type=int, default=0)
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args(argv)
    asyncio.run(amain(args.data_dir, args.worker_id, args.port))


if __name__ == "__main__":
    main()
