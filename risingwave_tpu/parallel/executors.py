"""Sharded stream executors — the frontend-facing wrappers that run the
multi-chip cores inside the ordinary executor protocol.

This is the TPU-native replacement for the reference's parallel actor
fan-out: where the reference builds P parallel HashAgg/HashJoin actors
connected by hash dispatchers and merge executors over gRPC exchanges
(reference: src/stream/src/executor/dispatch.rs:532 hash dispatch,
src/stream/src/executor/merge.rs:36 fan-in, docs/consistent-hash.md), here a
SINGLE executor owns mesh-sharded device state and every chunk step is one
XLA program whose internal ``lax.all_to_all`` does the routing over ICI —
the exchange layer has no host-visible existence at all.

An input chunk of capacity C is split into n local chunks of capacity C/n
(leading [n] axis sharded over the mesh); the vnode shuffle inside the step
re-routes rows to their owner shard, so the host-side split is free-form.
The agg packs a chunk's leaves by dtype first (``pack_chunk``: one dispatch
and one transfer a dtype); the join still splits leaf by leaf
(``split_chunk``). Emission flattens the shards' output windows into one
wide chunk that the ordinary MaterializeExecutor fetches.

What the chip showed (four v5e chips, the benchmark's
``q5core_exec_mesh4_catchup``, PERF.md PR 31): the HOST bounds the path, as
on one chip. The leaf-by-leaf split was the largest piece of a barrier (141
of 257 ms for 16 chunks of 6 leaves: a reshape dispatch and a four-device
``device_put`` a leaf), which is why the agg packs. The step costs about 7 ms
of device time a 4,096-row chunk on EVERY chip — each probes a full
4,096-slot receive buffer for its quarter of the table, so four chips do
not divide the one-chip step's work, they repeat it. The checkpoint pulled
the whole sharded state to the host (0.55 s for 120 MB and 32 K dirty
groups) until PR 32; now every shard gathers its dirty groups where it
lives (``_checkpoint_to_state_table``: 42 ms, 1.1 MB). A device-resident
egress was not what the cell asked for: Materialize is 5 ms of the barrier.

Durability mirrors the single-chip executors: dirty deltas flush to host
StateTables on checkpoint barriers; recovery re-routes committed rows by
replaying them through the sharded step (join) or per-shard direct loads
(agg).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    Column, DEFAULT_CHUNK_CAPACITY, StreamChunk, count_units,
    gather_units_window, pad_chunk, physical_chunk,
)
from ..common.fetch import fetch
from ..common.tracing import (
    CAT_STORAGE, annotation, conductor_epoch, current_span, now_ns,
    record_span, span,
)
from ..common.types import Field, Schema
from ..expr.agg import AggCall
from ..ops.hash_table import ht_lookup_or_insert
from ..ops.join_state import JoinType
from ..storage.state_table import StateTable
from ..stream.barrier_align import barrier_align
from ..stream.executor import Executor, SingleInputExecutor
from ..stream.hash_agg import stage_agg_delta
from ..stream.hash_join import _clear_ckpt_marks
from ..stream.message import Barrier
from .sharded_agg import ShardedHashAgg, build_sharded_agg_step
from .sharded_join import ShardedHashJoin


def split_chunk(chunk: StreamChunk, n: int, sharding) -> StreamChunk:
    """Pad to a multiple of n and reshape into n local chunks (leading [n]
    axis placed on the mesh); the in-step vnode shuffle re-routes rows, so
    this split is free-form."""
    chunk = pad_chunk(chunk, -(-chunk.capacity // n) * n)
    stacked = jax.tree_util.tree_map(
        lambda x: x.reshape((n, -1) + x.shape[1:]), chunk)
    return jax.device_put(
        stacked, jax.tree_util.tree_map(lambda _: sharding, stacked))


def _stack_key(x) -> tuple:
    """Leaves that share a stack: same trailing shape, same dtype (the
    bools — ``vis``, null masks — ride as int8 beside ``ops``)."""
    dtype = jnp.int8 if x.dtype == jnp.bool_ else x.dtype
    return jnp.dtype(dtype), x.shape[1:]


def pack_chunk(chunk: StreamChunk, n: int) -> tuple:
    """A chunk as a few arrays ready for the mesh: padded to a multiple of
    ``n`` rows, every leaf reshaped ``[n, C/n, ...]`` and the leaves of one
    ``_stack_key`` stacked ``[n, k, C/n, ...]`` — q5's six leaves become one
    int64 and one int8 array. Jitted by the caller, it is ONE dispatch a
    chunk, and what crosses to the other chips is one transfer a stack
    where ``split_chunk`` pays a reshape and a transfer a leaf (0.5 + 1.3
    ms a leaf on a v5e host, PERF.md PR 31)."""
    chunk = pad_chunk(chunk, -(-chunk.capacity // n) * n)
    stacks: dict = {}
    for x in jax.tree_util.tree_leaves(chunk):
        dtype, trailing = key = _stack_key(x)
        stacks.setdefault(key, []).append(
            x.astype(dtype).reshape((n, -1) + trailing))
    return tuple(jnp.stack(xs, axis=1) for xs in stacks.values())


def unpack_like(chunk: StreamChunk):
    """The inverse of ``pack_chunk`` for chunks shaped like ``chunk``, for
    use INSIDE a ``shard_map`` body: local stacks ``[1, k, C/n, ...]`` →
    the local ``[C/n, ...]`` chunk."""
    leaves, treedef = jax.tree_util.tree_flatten(chunk)
    filled: dict = {}       # stack key → leaves placed so far, in stack order
    where = []
    for x in leaves:
        key = _stack_key(x)
        row = filled.get(key, 0)
        filled[key] = row + 1
        where.append((list(filled).index(key), row, x.dtype))

    def unpack(stacks) -> StreamChunk:
        return jax.tree_util.tree_unflatten(
            treedef, [stacks[g][0, j].astype(dtype)
                      for g, j, dtype in where])
    return unpack


class _SplitClock:
    """An epoch's chunks packed and put on the mesh, rolled up into ONE
    ``shard.split`` span at the barrier (a span a chunk would flood the
    ring; ``stream/metrics.ChunkClock`` does the same for ``.chunks``,
    inside whose time these pieces lie). Each piece runs inside a
    profiler annotation of the same name."""

    __slots__ = ("first_ns", "busy_ns", "chunks", "transfers")

    def __init__(self):
        self.first_ns = self.busy_ns = self.chunks = self.transfers = 0

    def add(self, t0: int, transfers: int) -> None:
        self.first_ns = self.first_ns or t0
        self.busy_ns += now_ns() - t0
        self.chunks += 1
        self.transfers += transfers

    def emit(self, identity: str, epoch: int) -> None:
        record_span("shard.split", self.first_ns or now_ns(), self.busy_ns,
                    epoch=epoch, parent="barrier.collect", tid=identity,
                    chunks=self.chunks, transfers=self.transfers)
        self.__init__()


class ShardedHashAggExecutor(SingleInputExecutor):
    """Data-parallel grouped aggregation over a device mesh, behind the
    single-chip HashAggExecutor's exact protocol surface."""

    identity = "ShardedHashAgg"

    def __init__(
        self,
        input: Executor,
        mesh,
        group_keys: Sequence[int],
        agg_calls: Sequence[AggCall],
        state_table: Optional[StateTable] = None,
        table_capacity: int = 1 << 14,
        out_capacity: int = DEFAULT_CHUNK_CAPACITY,
    ):
        super().__init__(input)
        in_schema = input.schema
        key_types = tuple(in_schema[i].type for i in group_keys)
        self.agg = ShardedHashAgg(mesh, key_types, list(group_keys),
                                  list(agg_calls), table_capacity, out_capacity)
        self.schema = Schema(
            tuple(in_schema[i] for i in group_keys)
            + tuple(Field(f"agg{i}", c.output_type)
                    for i, c in enumerate(agg_calls))
        )
        self.state_table = state_table
        self.n = self.agg.n
        core = self.agg.core
        from ..common.chunk import flatten_shards
        self._gather = jax.jit(
            jax.vmap(core.gather_flush_chunk, in_axes=(0, 0, None)))
        self._flatten = jax.jit(flatten_shards)
        self._rank = jax.jit(jax.vmap(core.flush_rank))
        self._finish = jax.jit(jax.vmap(core.finish_flush))
        # the state stays where it is sharded; a device trace shows the
        # program as jit_ckpt_delta_window, as on one chip
        self._delta_window = jax.jit(
            jax.vmap(core.ckpt_delta_window, in_axes=(0, None, None)),
            static_argnums=(2,))
        self._pack = jax.jit(pack_chunk, static_argnums=(1,))
        # the sharded step over packed chunks, one per chunk signature
        # (an executor's input has one; built at its first chunk)
        self._steps: dict = {}
        self._split = _SplitClock()
        # per-shard rows routed up to the last barrier (the step's running
        # count is never reset on the device)
        self._routed_seen = np.zeros(self.n, np.int64)
        if self.state_table is not None:
            self._load_from_state_table()

    def _packed_step(self, chunk: StreamChunk):
        signature = tuple((x.shape, x.dtype)
                          for x in jax.tree_util.tree_leaves(chunk))
        step = self._steps.get(signature)
        if step is None:
            step = self._steps[signature] = build_sharded_agg_step(
                self.agg.core, self.agg.mesh, unpack_like(chunk))
        return step

    async def map_chunk(self, chunk: StreamChunk):
        t0 = now_ns()
        with annotation("shard.split", conductor_epoch()):
            stacks = jax.device_put(self._pack(chunk, self.n),
                                    self.agg._sharding)
        self._split.add(t0, len(stacks))
        self.agg.step(stacks, self._packed_step(chunk))
        if False:
            yield

    async def on_barrier(self, barrier: Barrier):
        epoch = barrier.epoch.curr
        self._split.emit(self.identity, epoch)
        st = self.agg.state
        rank = self._rank(st)
        with span("agg.flush_wait", epoch=epoch, wait="device",
                  tid=self.identity):
            counts, overflow, routed = fetch(
                (rank[:, -1], st.overflow, self.agg.routed))
        epoch_routed = routed - self._routed_seen
        self._routed_seen = routed
        current_span().set(rows_routed=int(epoch_routed.sum()),
                           rows_routed_max=int(epoch_routed.max()))
        if bool(np.any(overflow)):
            raise RuntimeError(
                f"{self.identity}: group table overflow (per-shard capacity "
                f"{self.agg.core.capacity}); increase table_capacity")
        G = self.agg.core.groups_per_chunk
        lo = 0
        while lo < int(counts.max(initial=0)):
            # egress stays on device: all shards' windows flatten into ONE
            # wide chunk per window (invalid rows are vis-masked by the
            # gather) — no per-shard host slicing (VERDICT r3 item 9)
            batch = self._gather(self.agg.state, rank, jnp.int64(lo))
            yield self._flatten(batch)
            lo += G
        if barrier.checkpoint and self.state_table is not None:
            with span("agg.state_delta", epoch=epoch, stage="state_delta",
                      cat=CAT_STORAGE, tid=self.identity,
                      shards=self.n) as delta:
                self._checkpoint_to_state_table(epoch, delta)
        self.agg.state = self._finish(self.agg.state)

    # -- persistence ----------------------------------------------------------

    def _checkpoint_to_state_table(self, epoch: int, delta) -> None:
        """Every shard's dirty groups, selected and gathered where the
        shard lives (the one-chip window under ``vmap`` over the shard
        axis: window 0 of all shards is one dispatch and one fetch),
        staged shard after shard in ONE batch and one commit."""
        st = self.agg.state
        stage_agg_delta(self.state_table, epoch, delta,
                        lambda lo, G: self._delta_window(st, lo, G),
                        self.agg.core.capacity)
        self.agg.state = st.replace(
            ckpt_dirty=jnp.zeros_like(st.ckpt_dirty))

    def _load_from_state_table(self) -> None:
        """Recovery: route committed groups to their owner shard (same vnode
        map the shuffle uses) and load keys + lanes directly."""
        from ..common.hashing import vnode_of, vnode_to_shard

        rows = list(self.state_table.scan_all())
        if not rows:
            return
        core = self.agg.core
        nk = len(core.group_keys)
        key_cols = []
        for c in range(nk):
            vals = [r[c] for r in rows]
            mask = np.array([v is not None for v in vals])
            data = np.array([v if v is not None else 0 for v in vals],
                            dtype=core.key_types[c].np_dtype)
            key_cols.append(Column(jnp.asarray(data), jnp.asarray(mask)))
        shard = np.asarray(vnode_to_shard(vnode_of(key_cols), self.n))

        st_host = jax.device_get(self.agg.state)
        shards = []
        for s in range(self.n):
            local = jax.tree_util.tree_map(lambda x: jnp.asarray(x[s]), st_host)
            sel = np.nonzero(shard == s)[0]
            bs = 1024
            for i in range(0, len(sel), bs):
                batch_idx = sel[i:i + bs]
                n = len(batch_idx)
                valid = jnp.arange(bs) < n
                kcols = []
                for c in range(nk):
                    vals = [rows[j][c] for j in batch_idx]
                    mask = np.array([v is not None for v in vals]
                                    + [False] * (bs - n))
                    data = np.array(
                        [v if v is not None else 0 for v in vals] + [0] * (bs - n),
                        dtype=core.key_types[c].np_dtype)
                    kcols.append(Column(jnp.asarray(data), jnp.asarray(mask)))
                table, slots, _, ovf = ht_lookup_or_insert(
                    local.table, kcols, valid)
                if bool(ovf):
                    raise RuntimeError(
                        "sharded agg table overflow during recovery load")
                lanes = list(local.lanes)
                for j in range(len(lanes)):
                    vals = np.array(
                        [rows[r][nk + j] for r in batch_idx] + [0] * (bs - n),
                        dtype=np.dtype(core.lane_dtypes[j]))
                    lanes[j] = lanes[j].at[slots].set(
                        jnp.asarray(vals), mode="drop")
                local = local.replace(table=table, lanes=tuple(lanes))
            local = local.rebaselined()
            shards.append(local)
        stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *shards)
        self.agg.state = jax.device_put(
            stacked,
            jax.tree_util.tree_map(lambda _: self.agg._sharding, stacked))


class ShardedHashJoinExecutor(Executor):
    """Data-parallel streaming hash join over a device mesh, behind the
    single-chip HashJoinExecutor's exact protocol surface."""

    identity = "ShardedHashJoin"

    def __init__(
        self,
        left: Executor,
        right: Executor,
        mesh,
        left_keys: Sequence[int],
        right_keys: Sequence[int],
        join_type: JoinType = JoinType.INNER,
        condition=None,
        left_state_table: Optional[StateTable] = None,
        right_state_table: Optional[StateTable] = None,
        key_capacity: int = 1 << 10,
        bucket_width: int = 8,
        out_capacity: int = DEFAULT_CHUNK_CAPACITY,
    ):
        self.left, self.right = left, right
        from ..stream.metrics import ExecutorStats
        self.stats = ExecutorStats()
        self.join = ShardedHashJoin(
            mesh, left.schema, right.schema, left_keys, right_keys,
            join_type, condition=condition, key_capacity=key_capacity,
            bucket_width=bucket_width)
        self.schema = self.join.out_schema
        self.out_capacity = out_capacity
        self.n = self.join.n
        self.state_tables = {"left": left_state_table,
                             "right": right_state_table}
        self._count = jax.jit(jax.vmap(count_units))
        cap = out_capacity
        from ..common.chunk import flatten_shards
        self._gather = jax.jit(jax.vmap(
            lambda ch, lo: gather_units_window(ch, lo, cap),
            in_axes=(0, None)))
        self._flatten = jax.jit(flatten_shards)
        self._clear_ckpt = jax.jit(jax.vmap(_clear_ckpt_marks))
        # match-unit batches buffered in arrival order (interleaved with
        # watermarks, which must not outrun same-epoch data): counts are
        # fetched ONCE per flush for many chunks instead of one device_get
        # per chunk (per-chunk syncs serialize host and device). Flushed
        # at every barrier and
        # whenever MAX_PENDING_UNITS batches are resident, bounding HBM.
        self._pending_msgs: list = []      # ("units", big) | ("wm", wm)
        self._n_pending_units = 0
        # INPUT chunks also batch: a run of same-side chunks is held and
        # joined by ONE fused dispatch (ShardedHashJoin.step_epoch — the
        # generic sharded-fused equi-join surface) instead of one
        # dispatch per chunk; a side switch, watermark, barrier or the
        # MAX_PENDING_UNITS bound cuts the run
        self._in_side = None
        self._in_run: list = []
        if any(self.state_tables.values()):
            self._load_from_state_tables()

    #: device-resident unit batches allowed before a forced flush
    MAX_PENDING_UNITS = 16

    def _run_pending_inputs(self) -> None:
        """Join the buffered same-side input run in one fused dispatch;
        its emission grids queue for the next output flush in order."""
        if not self._in_run:
            return
        bigs = self.join.step_epoch(self._in_side, self._in_run)
        for big in bigs:
            self._pending_msgs.append(("units", big))
        self._n_pending_units += len(bigs)
        self._in_side = None
        self._in_run = []

    def _flush_pending(self):
        """Emit buffered match-unit windows and watermarks in arrival
        order; ONE host transfer covers every pending batch's counts."""
        if not self._n_pending_units:
            for kind, item in self._pending_msgs:
                yield item                     # watermarks only
            self._pending_msgs.clear()
            return
        counts_all = jax.device_get(
            [self._count(item) for kind, item in self._pending_msgs
             if kind == "units"])
        G = self.out_capacity // 2
        ci = 0
        for kind, item in self._pending_msgs:
            if kind == "wm":
                yield item
                continue
            counts = counts_all[ci]
            ci += 1
            lo = 0
            while lo < int(counts.max(initial=0)):
                self.stats.chunks_out += 1
                yield self._flatten(self._gather(item, jnp.int64(lo)))
                lo += G
        self._pending_msgs.clear()
        self._n_pending_units = 0

    async def execute(self):
        from ..stream.metrics import barrier_timer
        stats = self.stats
        async for ev in barrier_align(self.left, self.right):
            kind = ev[0]
            if kind == "chunk":
                _, side, chunk = ev
                stats.chunks_in += 1
                stats.capacity_rows_in += chunk.capacity
                if self._in_side is not None and self._in_side != side:
                    # side switch cuts the fused run (arrival order is
                    # the emission contract)
                    self._run_pending_inputs()
                self._in_side = side
                self._in_run.append(
                    split_chunk(chunk, self.n, self.join._sharding))
                # emission deferred (bounded): inputs AND outputs stay
                # resident on device until the next flush, so the data
                # path has no host sync — and no dispatch — per chunk
                if (len(self._in_run) + self._n_pending_units
                        >= self.MAX_PENDING_UNITS):
                    self._run_pending_inputs()
                    for out in self._flush_pending():
                        yield out
            elif kind == "barrier":
                barrier = ev[1]
                self._run_pending_inputs()
                for out in self._flush_pending():
                    yield out
                with barrier_timer(stats, self.identity, barrier.epoch.curr,
                                   self.node):
                    self._check_flags()
                    if barrier.checkpoint:
                        self._checkpoint(barrier.epoch.curr)
                yield barrier
                if barrier.is_stop():
                    return
            elif kind == "watermark":
                _, side, wm = ev
                stats.watermarks += 1
                out_idx = self._map_watermark_col(side, wm.col_idx)
                if out_idx is not None:
                    # buffered in order: a watermark must not overtake
                    # same-epoch data rows still pending on device —
                    # including input chunks not yet joined
                    self._run_pending_inputs()
                    self._pending_msgs.append(
                        ("wm", wm.__class__(out_idx, wm.value)))

    def _map_watermark_col(self, side: str, col_idx: int) -> Optional[int]:
        sa = self.join.core.join_type.semi_anti_side
        if sa is not None:
            return col_idx if sa == side else None
        return (col_idx if side == "left"
                else col_idx + len(self.join.core.left_schema))

    def _check_flags(self) -> None:
        st = jax.device_get(self.join.state)
        for side in ("left", "right"):
            s = getattr(st, side)
            if bool(np.any(s.inconsistent)):
                raise RuntimeError(
                    f"{self.identity}: {side} saw delete of an absent row")

    # -- persistence ----------------------------------------------------------

    def _checkpoint(self, epoch: int) -> None:
        st = jax.device_get(self.join.state)
        for side in ("left", "right"):
            table = self.state_tables[side]
            if table is None:
                continue
            side_st = getattr(st, side)
            # deletes strictly before inserts ACROSS ALL SHARDS: a same-pk
            # row whose join key moved to a lower-numbered shard within one
            # checkpoint window would otherwise have its old-shard delete
            # clobber the new-shard upsert (StateTable.delete is pk-keyed)
            deletes, inserts = [], []
            for sh in range(self.n):
                # rows a refilled lane overwrote (JoinCore's graveyard)
                buried = int(side_st.grave_n[sh])
                deletes.extend(
                    tuple(d[sh, i].item() if m[sh, i] else None
                          for d, m in zip(side_st.grave_data,
                                          side_st.grave_mask))
                    for i in range(buried))
                dirty = np.asarray(side_st.ckpt_dirty[sh])
                slots, lanes = np.nonzero(dirty)
                if not len(slots):
                    continue
                occ = np.asarray(side_st.occupied[sh])
                tomb = np.asarray(side_st.tomb[sh])
                datas = [np.asarray(d[sh]) for d in side_st.row_data]
                masks = [np.asarray(m[sh]) for m in side_st.row_mask]

                def row_at(s, l):
                    return tuple(
                        datas[c][s, l].item() if masks[c][s, l] else None
                        for c in range(len(datas)))

                for s, l in zip(slots, lanes):
                    if tomb[s, l] and not occ[s, l]:
                        deletes.append(row_at(s, l))
                    elif occ[s, l]:
                        inserts.append(row_at(s, l))
            for row in deletes:
                table.delete(row)
            for row in inserts:
                table.insert(row)
            table.commit(epoch)
        self.join.state = self._clear_ckpt(self.join.state)

    def _load_from_state_tables(self) -> None:
        """Recovery: replay both sides' committed rows through the sharded
        insert step (the all_to_all re-routes them); outputs discarded."""
        for side in ("left", "right"):
            table = self.state_tables[side]
            if table is None:
                continue
            schema = (self.join.core.left_schema if side == "left"
                      else self.join.core.right_schema)
            rows = list(table.scan_all())
            bs = 256
            stride = self.n * bs
            for i in range(0, len(rows), stride):
                group = rows[i:i + stride]
                chunks = [
                    physical_chunk(schema, group[j * bs:(j + 1) * bs], bs)
                    for j in range(self.n)
                ]
                self.join.step(side, self.join.batch_chunks(chunks))
        self.join.state = self._clear_ckpt(self.join.state)
