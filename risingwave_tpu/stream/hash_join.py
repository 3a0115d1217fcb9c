"""HashJoinExecutor — streaming equi-join on device-resident state.

Host control loop over the pure device join step (ops/join_state.py).
Counterpart of the reference's HashJoinExecutor
(reference: src/stream/src/executor/hash_join.rs:227-270; barrier-aligned
two-input loop :693; flush :837). All join types of the reference's
const-generic ``JoinTypePrimitive`` are supported, plus non-equi conditions.

Durability: each side has an optional StateTable holding its live rows
(pk = the stream pk). On checkpoint barriers the lanes dirtied since the
last checkpoint are flushed (upserts for live rows, deletes for tombstoned
ones) — degrees are NOT persisted; recovery replays both sides' rows
through the normal insert path with emission suppressed, which rebuilds
degrees exactly (cheaper and simpler than the reference's degree table,
managed_state/join/mod.rs:228-258).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import (
    physical_chunk,
    DEFAULT_CHUNK_CAPACITY, StreamChunk, count_units, flatten_shards,
    gather_units_window, make_chunk,
)
from ..common.fetch import fetch
from ..common.tracing import CAT_STORAGE, conductor_epoch, span
from ..ops.join_state import (
    JoinCore, JoinState, JoinType, SideLayout, apply_evict_side,
    clean_side_below, compact_side, import_state, join_ckpt_delta_window,
    join_evict_plan,
)
from ..storage.state_table import StateTable
from .barrier_align import barrier_align
from .executor import Executor
from .message import Barrier
from .state_delta import fetch_delta, stage_delta


class HashJoinExecutor(Executor):
    identity = "HashJoin"

    def __init__(
        self,
        left: Executor,
        right: Executor,
        left_keys: Sequence[int],
        right_keys: Sequence[int],
        join_type: JoinType = JoinType.INNER,
        condition=None,
        left_state_table: Optional[StateTable] = None,
        right_state_table: Optional[StateTable] = None,
        key_capacity: int = 1 << 13,
        bucket_width: int = 16,
        out_capacity: int = DEFAULT_CHUNK_CAPACITY,
        strict: bool = True,
        interval_clean: Sequence[tuple] = (),
        load_shard: Optional[tuple] = None,
        hbm_key_budget: Optional[int] = None,
        null_aware_anti: bool = False,
        row_keys: tuple = (None, None),
        row_capacity: int = 1 << 16,
    ):
        """``interval_clean``: state-cleaning rules for interval/windowed
        joins — tuples ``(clean_side, clean_col, watch_side, watch_col,
        lag)``: when a watermark arrives on ``watch_side``'s column
        ``watch_col``, rows on ``clean_side`` whose ``clean_col`` value is
        below ``watermark - lag`` are freed at the next checkpoint
        (reference: interval-join state cleaning, hash_join.rs).

        ``load_shard``: (shard_idx, n_shards) for fragmented builds — the N
        join actors of one fragment share BOTH logical state tables; on
        recovery each actor keeps only the rows whose JOIN KEY hashes to
        its shard (the same device vnode hash the HashDispatcher routes
        live rows with), so recovery works across any parallelism change
        (reference: vnode-bitmap reassignment, stream/scale.rs:657).

        ``hbm_key_budget``: cap on LIVE join keys held per device arena.
        When a checkpoint finds more, the coldest keys (LRU by touch step,
        synced across the two sides) are evicted from BOTH arenas to the
        state tables and faulted back when a chunk mentions them — device
        state becomes a cache over the durable tier instead of
        grow-or-raise (reference: JoinHashMap's ManagedLruCache,
        src/stream/src/executor/managed_state/join/mod.rs:228-258).
        Requires both state tables with JOIN-KEY-PREFIXED pks (the
        builder lays pks out as join_keys ++ stream_pk so fault-in is a
        pk prefix scan).

        ``row_keys``: per side, the columns that identify a row where the
        plan says a join key may hold many of them (the side's stream key
        is not inside its join key), else None. Such a side starts in the
        bucket arena and moves to a fan-out arena of ``row_capacity`` rows
        (or twice the rows it holds) the first time a key outgrows the
        bucket width, instead of widening every bucket
        (``ops/join_state.py`` "Side layouts"). Not under an
        ``hbm_key_budget``: eviction is by whole bucket."""
        self.left, self.right = left, right
        self.load_shard = load_shard
        # PG NOT IN semantics (planner.py _plan_in_subquery): a NULL
        # arriving on the build side would have to retract EVERY emitted
        # probe row — incremental null-aware anti join is a global flip
        # this executor does not implement, so it rejects loudly instead
        # of silently diverging from PG (NULL probe keys are already
        # filtered below the join at plan time). The build side's NULL
        # keys are counted on the device into the packed stats
        # (``_null_keys``: per side the key columns to look at; no entry
        # on any other plan, whose stats vector is one slot shorter).
        self._null_keys = {"left": (), "right": tuple(right_keys)} \
            if null_aware_anti and join_type == JoinType.LEFT_ANTI else {}
        from .metrics import ExecutorStats
        self.stats = ExecutorStats()
        # an insert may refill a lane tombstoned since the last checkpoint;
        # the core compares state-table keys to know whether the old row's
        # delete is still owed (no table: nothing durable to delete)
        self._join_args = dict(
            join_type=join_type, condition=condition,
            state_pks=tuple(() if t is None else tuple(t.pk_indices)
                            for t in (left_state_table, right_state_table)))
        self._key_args = (left_keys, right_keys)
        if hbm_key_budget is not None:
            row_keys = (None, None)
        self._row_keys = {"left": row_keys[0], "right": row_keys[1]}
        self.row_capacity = row_capacity
        self.interval_clean = tuple(interval_clean)
        self._pending_clean: dict[tuple[str, int], int] = {}
        # max threshold ever applied per (side, col) — the fault-in filter
        self._applied_clean: dict[tuple[str, int], int] = {}
        self.core = JoinCore(
            left.schema, right.schema, left_keys, right_keys,
            key_capacity=key_capacity, bucket_width=bucket_width,
            **self._join_args)
        self.schema = self.core.out_schema
        self.out_capacity = out_capacity
        # chunks applied per host sync (optimistic batched emission)
        self.emit_batch = 16
        # chunks scanned per dispatch when a whole ChunkBatch arrives
        # (memory-bounds the stacked emission grids of the scan)
        self.batch_chunks = 8
        self.strict = strict
        self.max_state_cells = 1 << 26    # growth ceiling (cap * W)
        self.state_tables = {"left": left_state_table,
                             "right": right_state_table}
        if hbm_key_budget is not None:
            if left_state_table is None or right_state_table is None:
                hbm_key_budget = None      # no cold tier to evict to
            elif hbm_key_budget >= key_capacity:
                raise ValueError("hbm_key_budget must be < key_capacity")
            else:
                # the growth ceiling exists to stop unbounded arenas; with
                # a cold tier the arena is bounded by eviction instead
                self.max_state_cells = 1 << 30
        self.hbm_key_budget = hbm_key_budget
        self._evicted: set = set()
        from .cache import LruClock
        self._lru_clock = LruClock(hbm_key_budget is not None)
        self.state = self.core.init_state()
        # what HashJoin.chunks reports for the epoch, reset at its barrier
        self._epoch_counts = {"rows_in_left": 0, "rows_in_right": 0,
                              **dict.fromkeys(EMIT_COUNTS, 0),
                              **dict.fromkeys(FANOUT_COUNTS, 0),
                              "rewinds": 0, "grows": 0}
        self._make_jits()
        if any(self.state_tables.values()):
            self._load_from_state_tables()

    def _make_jits(self) -> None:
        core = self.core

        # named functions: a profiler's trace lists the device programs by
        # them (jit_join_step_left, ...)
        def join_step_left(st, ch, step=None):
            return core.apply_chunk(st, ch, side="left", step=step)

        def join_step_right(st, ch, step=None):
            return core.apply_chunk(st, ch, side="right", step=step)

        self._apply = {"left": jax.jit(join_step_left),
                       "right": jax.jit(join_step_right)}

        # batched single-dispatch ingest: ONE lax.scan applies a whole
        # sub-batch of chunks to one side and stacks each chunk's packed
        # stats + emission grid — K chunks cost one dispatch and one stats
        # transfer instead of K of each (the ChunkBatch amortization the
        # agg path has had since round 3; docs/performance.md)
        def _apply_batch(state: JoinState, batched_chunk, steps, side: str):
            # steps=None (no LRU budget) traces the stamp-free variant —
            # the per-chunk path's static elision of the three lru
            # scatter-maxes, preserved under the scan
            def body(st, x):
                ch, step = x if steps is not None else (x, None)
                st, big = core.apply_chunk(st, ch, side=side, step=step)
                return st, (pack_stats(core, _flags(st), big, ch, side,
                                       self._null_keys.get(side)), big)

            xs = (batched_chunk, steps) if steps is not None \
                else batched_chunk
            state, (stats, bigs) = jax.lax.scan(body, state, xs)
            return state, stats, bigs

        self._apply_batch = {
            "left": jax.jit(functools.partial(_apply_batch, side="left")),
            "right": jax.jit(functools.partial(_apply_batch, side="right")),
        }

        def _gather_at(bigs, k, lo):
            big = jax.tree_util.tree_map(lambda x: x[k], bigs)
            return gather_units_window(big, lo, self.out_capacity)

        self._gather_at = jax.jit(_gather_at)
        self._evict_plan = jax.jit(join_evict_plan, static_argnums=(1,))

        def _apply_evict(state: JoinState, mask_l, mask_r) -> JoinState:
            return JoinState(left=apply_evict_side(state.left, mask_l),
                             right=apply_evict_side(state.right, mask_r))

        self._apply_evict = jax.jit(_apply_evict)
        def join_gather(ch, lo):
            return gather_units_window(ch, lo, self.out_capacity)

        self._gather = jax.jit(join_gather)

        def join_pack_stats(flags, big, ch, side):
            return pack_stats(core, flags, big, ch, side,
                              self._null_keys.get(side))

        self._pack_stats = jax.jit(join_pack_stats, static_argnums=(3,))
        # pads a stats fetch
        self._no_stats = jnp.zeros(
            N_STATS + bool(self._null_keys), jnp.int64)
        self._clear_ckpt = jax.jit(_clear_ckpt_marks)
        # reads a side and leaves it in place (not donated); a device
        # trace shows it as jit_join_ckpt_delta_window
        self._delta_window = jax.jit(join_ckpt_delta_window,
                                     static_argnums=(2,))
        self._clean_side = jax.jit(clean_side_below, static_argnums=(1,))

        def _compact(state: JoinState) -> JoinState:
            return JoinState(left=compact_side(core, state.left, "left"),
                             right=compact_side(core, state.right, "right"))

        self._compact = jax.jit(_compact)
        self._fanout_gauges = jax.jit(_fanout_gauges)

    # -- LRU stamping ----------------------------------------------------------

    def _lru(self):
        return self._lru_clock.next()

    def _pykey(self, values) -> tuple:
        from .cache import canonical_key
        return canonical_key(values, self.core.key_types)

    # -- adaptive growth -------------------------------------------------------

    def _apply_growing(self, side: str, chunk: StreamChunk):
        """Apply a chunk; on overflow discard the result, grow the state
        geometry of the side that overflowed (key capacity for table fill;
        for a key that outgrew its bucket, the fan-out arena where the plan
        allows it, else the bucket width; a fan-out probe's pair buffer),
        and retry on the untouched previous state. Functional state makes
        the retry exact — no partial effects to undo."""
        step = self._lru()
        while True:
            new_state, big = self._apply[side](self.state, chunk, step)
            sides = {"left": new_state.left, "right": new_state.right}
            grown = {s: self._grown_layout(s, st) for s, st in sides.items()}
            if grown == self.core.layout:
                self.state = new_state
                return big
            for lay in grown.values():
                if lay.cells > self.max_state_cells:
                    raise RuntimeError(
                        f"{self.identity}: join state would exceed "
                        f"{self.max_state_cells} cells ({lay})")
            self._grow((grown["left"], grown["right"]))
            self._epoch_counts["grows"] += 1

    def _grown_layout(self, side: str, st) -> SideLayout:
        """The geometry ``side`` needs after a step that left ``st``."""
        lay = self.core.layout[side]
        lane_ovf, ht_ovf = bool(st.lane_overflow), bool(st.ht_overflow)
        row_key = self._row_keys[side]
        if lane_ovf and not lay.fanout and row_key is not None:
            held = getattr(self.state, side)
            rows = int(jnp.sum(held.occupied | held.tomb)) + int(held.grave_n)
            return SideLayout(max(self.row_capacity,
                                  1 << max(2 * rows - 1, 1).bit_length()),
                              1, row_key)
        return dataclasses.replace(
            lay, width=lay.width * (2 if lane_ovf else 1),
            capacity=lay.capacity * (2 if ht_ovf else 1))

    def _grow(self, layouts: tuple) -> None:
        left_keys, right_keys = self._key_args
        self.core = JoinCore(
            self.left.schema, self.right.schema, left_keys, right_keys,
            layouts=layouts, **self._join_args)
        self.state = import_state(self.core, self.state)
        self._make_jits()

    # -- host loop -------------------------------------------------------------

    # -- optimistic batched emission ------------------------------------------
    # Applying a chunk is ONE async device dispatch, but reading its output
    # row count (and the overflow flags) is a host sync per chunk. The hot
    # path is therefore optimistic: apply up to ``emit_batch`` chunks without
    # syncing, then fetch ALL their packed stats in one transfer and emit.
    # If any chunk overflowed, rewind to the pre-batch state snapshot and
    # replay chunk-by-chunk through the growing path (rare; functional
    # state makes the rewind exact).

    def _fetch_stats(self, packed) -> np.ndarray:
        """The packed stats of the chunks applied since the last sync: the
        host blocks here until the device has run every one of them. A
        NOT IN plan's vectors end in the build-side rows whose key is
        NULL: PG would return zero rows for the WHOLE view, which
        incrementally means retracting everything already emitted —
        unsupported; fail with an actionable message instead of
        diverging, before the epoch's barrier is passed on (nothing of
        the chunk is committed)."""
        with span("join.emit_wait", epoch=conductor_epoch(), wait="device",
                  parent="barrier.collect", tid=self.identity):
            rows = np.asarray(packed)
        if self._null_keys and rows[..., N_STATS].any():
            raise RuntimeError(
                "NULL value in NOT IN (SELECT ...) subquery: PostgreSQL "
                "semantics would drop every row of the view, which a "
                "streaming anti join cannot express incrementally — "
                "filter NULLs in the subquery (WHERE col IS NOT NULL) "
                "or use NOT EXISTS")
        return rows

    def _count(self, side: str, rows: np.ndarray) -> None:
        """Add the packed stats of applied chunks (``[k, N_STATS]``) to
        what ``HashJoin.chunks`` reports for the epoch."""
        counts = self._epoch_counts
        counts[f"rows_in_{side}"] += int(rows[:, 5].sum())
        for i, name in enumerate(EMIT_COUNTS + FANOUT_COUNTS, 6):
            counts[name] += int(rows[:, i].sum())

    def _gather_units(self, big, n_units: int):
        for lo in range(0, n_units, self.out_capacity // 2):
            self.stats.chunks_out += 1
            yield self._gather(big, jnp.int64(lo))

    def _replay_growing(self, side: str, chunk: StreamChunk):
        """One chunk of a rewound batch again, through the growing path."""
        big = self._apply_growing(side, chunk)
        row = self._fetch_stats(
            self._pack_stats(_flags(self.state), big, chunk, side))
        self._count(side, row[None])
        yield from self._gather_units(big, int(row[4]))

    def _flush_pending(self):
        if not self._pending:
            return
        # always ``emit_batch`` vectors, so that ONE stack program serves
        # every count of pending chunks (a flush of one chunk more than
        # any barrier before it compiled inside that barrier)
        k = len(self._pending)
        packed = self._fetch_stats(jnp.stack(
            [p[2] for p in self._pending]
            + [self._no_stats] * (self.emit_batch - k)))[:k]
        if not packed[:, :4].any():
            for (side, _, _, big), row in zip(self._pending, packed):
                self._count(side, row[None])
                yield from self._gather_units(big, int(row[4]))
        else:
            # overflow inside the batch: rewind and replay with growth
            self.state = self._rewind_state
            self._epoch_counts["rewinds"] += 1
            for side, chunk, _, _ in self._pending:
                yield from self._replay_growing(side, chunk)
        self._pending.clear()
        self._rewind_state = None

    # -- batched single-dispatch ingest ---------------------------------------
    # A ChunkBatch arriving on either side is scanned on device in
    # sub-batches of ``batch_chunks``: one dispatch applies the chunks in
    # order, one transfer fetches all their packed stats — the unstack-
    # and-loop default paid K dispatches + K syncs per batch.

    def _consume_batch(self, side: str, batch):
        if self._evicted:
            hits = self._evicted_hits(side, flatten_shards(batch.chunk))
            if hits:
                self._fault_in(hits)
        for lo in range(0, batch.num_chunks, self.batch_chunks):
            sub = jax.tree_util.tree_map(
                lambda x: x[lo:lo + self.batch_chunks], batch.chunk)
            yield from self._apply_subbatch(side, sub)

    def _apply_subbatch(self, side: str, sub_chunk):
        stats = self.stats
        k = sub_chunk.ops.shape[0]
        steps = self._lru_clock.advance(k)    # None without an LRU budget
        rewind = self.state
        new_state, packed, bigs = self._apply_batch[side](
            self.state, sub_chunk, steps)
        self.state = new_state
        rows = self._fetch_stats(packed)      # ONE transfer for k chunks
        if not rows[:, :4].any():
            self._count(side, rows)
            for kk in range(k):
                n_units = int(rows[kk, 4])
                for lo in range(0, n_units, self.out_capacity // 2):
                    stats.chunks_out += 1
                    yield self._gather_at(bigs, jnp.int32(kk),
                                          jnp.int64(lo))
        else:
            # overflow inside the scanned sub-batch: rewind and replay
            # chunk-by-chunk through the growing path (functional state
            # makes the rewind exact, as in the optimistic path above)
            self.state = rewind
            self._epoch_counts["rewinds"] += 1
            for kk in range(k):
                ch = jax.tree_util.tree_map(lambda x: x[kk], sub_chunk)
                yield from self._replay_growing(side, ch)

    async def execute(self):
        from .metrics import ChunkClock, barrier_timer
        stats = self.stats
        clock = ChunkClock(stats, self.identity)
        self._pending: list = []
        self._rewind_state = None
        chunks_out = stats.chunks_out
        async for ev in barrier_align(self.left, self.right, batched=True):
            kind = ev[0]
            if kind == "batch":
                _, side, batch = ev
                stats.batches_in += 1
                stats.batch_chunks_in += batch.num_chunks
                stats.capacity_rows_in += (batch.num_chunks
                                           * batch.chunk_capacity)
                # scanned batches and the optimistic per-chunk window must
                # not interleave rewinds — flush pending output first
                for out in clock.timed(self._flush_pending()):
                    yield out
                for out in clock.timed(self._consume_batch(side, batch)):
                    yield out
            elif kind == "chunk":
                _, side, chunk = ev
                stats.chunks_in += 1
                stats.capacity_rows_in += chunk.capacity
                clock.begin()
                if self._evicted:
                    hits = self._evicted_hits(side, chunk)
                    if hits:
                        # flush the optimistic batch FIRST: fault-in
                        # replays mutate state, and a later rewind of the
                        # batch must not lose them
                        clock.end()
                        for out in clock.timed(self._flush_pending()):
                            yield out
                        clock.begin()
                        self._fault_in(hits)
                if self._rewind_state is None:
                    self._rewind_state = self.state
                new_state, big = self._apply[side](self.state, chunk,
                                                   self._lru())
                self.state = new_state
                self._pending.append(
                    (side, chunk,
                     self._pack_stats(_flags(new_state), big, chunk, side),
                     big))
                clock.end()
                if len(self._pending) >= self.emit_batch:
                    for out in clock.timed(self._flush_pending()):
                        yield out
            elif kind == "barrier":
                barrier = ev[1]
                for out in clock.timed(self._flush_pending()):
                    yield out
                clock.emit(barrier.epoch.curr, self.node,
                           chunks_out=stats.chunks_out - chunks_out,
                           bucket_width=self.core.W, **self._epoch_counts,
                           **self._fanout_args())
                chunks_out = stats.chunks_out
                self._epoch_counts = dict.fromkeys(self._epoch_counts, 0)
                with barrier_timer(stats, self.identity, barrier.epoch.curr,
                                   self.node):
                    self._check_flags()
                    if barrier.checkpoint:
                        cleaned = self._apply_pending_clean()
                        self._checkpoint(barrier.epoch.curr)
                        if self.hbm_key_budget is not None:
                            cleaned |= self._evict_cold()
                        if cleaned:
                            self.state = self._compact(self.state)
                yield barrier
                if barrier.is_stop():
                    return
            elif kind == "watermark":
                _, side, wm = ev
                stats.watermarks += 1
                for cs, cc, ws, wc, lag in self.interval_clean:
                    if ws == side and wc == wm.col_idx:
                        key = (cs, cc)
                        thr = wm.value - lag
                        if (key not in self._pending_clean
                                or thr > self._pending_clean[key]):
                            self._pending_clean[key] = thr
                # forward with the column index remapped into the output schema
                out_idx = self._map_watermark_col(side, wm.col_idx)
                if out_idx is not None:
                    # pending join output must not be overtaken by the
                    # watermark — downstream EOWC operators would finalize
                    # windows those buffered rows still belong to
                    for out in self._flush_pending():
                        yield out
                    yield wm.__class__(out_idx, wm.value)

    # -- eviction / fault-in ---------------------------------------------------

    def _evict_cold(self) -> bool:
        """Evict the coldest live keys' buckets from BOTH arenas down to
        3/4 of the budget (their durable rows were just written by this
        barrier's checkpoint). Returns True if anything was evicted (the
        caller compacts to reclaim the key slots)."""
        # ONE packed fetch covers the budget gate AND the plan: the evict
        # plan's packed already carries [n_evict_l, n_evict_r, n_live_l,
        # n_live_r] (ops/join_state.join_evict_plan), so the old
        # two-round-trip cadence — a live-count gate fetch, then the plan
        # fetch — coalesces into a single device→host transfer per
        # checkpoint. Under budget the plan's sort is wasted DEVICE work
        # (async-dispatched, off the critical path); the host sync it
        # replaces was on it.
        keep = max(self.hbm_key_budget * 3 // 4, 1)
        mask_l, mask_r, packed = self._evict_plan(self.state, keep)
        nel, ner, nl, nr = (int(x) for x in fetch(packed[:4]))
        if max(nl, nr) <= self.hbm_key_budget:
            return False
        if nel == 0 and ner == 0:
            return False
        for side, mask in (("left", mask_l), ("right", mask_r)):
            st = getattr(self.state, side)
            nm = np.asarray(mask)
            idx = np.nonzero(nm)[0]
            if not len(idx):
                continue
            key_np = [np.asarray(kd)[idx] for kd in st.ht.key_data]
            for row in zip(*key_np):
                self._evicted.add(self._pykey(row))
        self.state = self._apply_evict(self.state, mask_l, mask_r)
        return True

    def _evicted_hits(self, side: str, chunk: StreamChunk) -> list:
        """Evicted join keys mentioned by this chunk (host sync; paid only
        while evicted keys exist)."""
        key_idx = (self.core.left_keys if side == "left"
                   else self.core.right_keys)
        vis = np.asarray(chunk.vis)
        datas = [np.asarray(chunk.columns[i].data) for i in key_idx]
        ok = vis.copy()
        for i in key_idx:
            ok &= np.asarray(chunk.columns[i].mask)
        present = set(zip(*(d[ok] for d in datas))) if datas else set()
        return [k for k in (self._pykey(p) for p in present)
                if k in self._evicted]

    def _fault_in(self, keys: list) -> None:
        """Restore the given keys' rows on BOTH sides from the cold tier:
        prefix-scan each state table by join key (pks are join-key-
        prefixed) and replay through the insert path with emission
        discarded — degrees rebuild exactly, the same way recovery does."""
        nk = len(self.core.left_keys)
        for k in keys:
            self._evicted.discard(k)
        for side in ("left", "right"):
            table = self.state_tables[side]
            schema = (self.core.left_schema if side == "left"
                      else self.core.right_schema)
            rows = []
            for k in keys:
                rows.extend(table.scan_prefix(list(k), nk))
            # watermark state cleaning already retired rows below the
            # applied thresholds on DEVICE; an evicted key's durable rows
            # missed that — drop them here (and delete them durably)
            # instead of resurrecting expired state
            for (cs, cc), thr in self._applied_clean.items():
                if cs != side or not rows:
                    continue
                expired = [r for r in rows
                           if r[cc] is not None and r[cc] < thr]
                if expired:
                    for r in expired:
                        table.delete(r)
                    rows = [r for r in rows
                            if r[cc] is None or r[cc] >= thr]
            bs = 1024
            for i in range(0, len(rows), bs):
                ch = physical_chunk(schema, rows[i: i + bs], bs)
                big = self._apply_growing(side, ch)
                del big                      # outputs were emitted long ago

    def _apply_pending_clean(self) -> bool:
        """Free rows below the pending watermark thresholds (mark dead +
        tombstone; deletes persist via the checkpoint that follows)."""
        if not self._pending_clean:
            return False
        for (side, col), threshold in self._pending_clean.items():
            st = getattr(self.state, side)
            st = self._clean_side(st, col, jnp.asarray(threshold))
            self.state = self.state.replace(**{side: st})
            # evicted keys' durable rows are NOT on device: remember the
            # high-water threshold so fault-in drops (and durably deletes)
            # expired rows instead of resurrecting them
            prev = self._applied_clean.get((side, col))
            if prev is None or threshold > prev:
                self._applied_clean[(side, col)] = threshold
            # _applied_clean is process-local: durably retire the evicted
            # keys' expired rows NOW (staged; commits with the next
            # checkpoint, same atomicity as the device cleaning) so a
            # restart cannot resurrect them
            if self._evicted and self.state_tables.get(side) is not None:
                table = self.state_tables[side]
                nk = len(self.core.left_keys)
                for k in list(self._evicted):
                    for r in table.scan_prefix(list(k), nk):
                        if r[col] is not None and r[col] < threshold:
                            table.delete(r)
        self._pending_clean.clear()
        return True

    def _map_watermark_col(self, side: str, col_idx: int) -> Optional[int]:
        sa = self.core.join_type.semi_anti_side
        if sa is not None:
            return col_idx if sa == side else None
        return col_idx if side == "left" else col_idx + len(self.core.left_schema)

    def _fanout_args(self) -> dict:
        """What ``HashJoin.chunks`` says of the fan-out arenas at the
        barrier: the live rows they hold and how full their row tables
        are (one small fetch; nothing where no side is fan-out)."""
        fan = [s for s in ("left", "right") if self.core.layout[s].fanout]
        if not fan:
            return {}
        live, used = (int(x) for x in fetch(self._fanout_gauges(
            tuple(getattr(self.state, s) for s in fan))))
        rows = sum(self.core.layout[s].capacity for s in fan)
        return {"fanout_rows": live, "fanout_fill_pct": 100.0 * used / rows}

    def _check_flags(self) -> None:
        for side in ("left", "right"):
            st = getattr(self.state, side)
            if bool(st.ht_overflow) or bool(st.lane_overflow):
                raise RuntimeError(
                    f"{self.identity}: {side} join state overflow escaped "
                    f"growth ({self.core.layout[side]})")
            if self.strict and bool(st.inconsistent):
                raise RuntimeError(
                    f"{self.identity}: {side} saw delete of an absent row")

    # -- persistence -----------------------------------------------------------

    def _checkpoint(self, epoch: int) -> None:
        for side in ("left", "right"):
            if self.state_tables[side] is not None:
                with span("join.state_delta", epoch=epoch,
                          stage="state_delta", cat=CAT_STORAGE,
                          tid=self.identity, side=side) as delta:
                    self._stage_state_delta(side, epoch, delta)
        self.state = self._clear_ckpt(self.state)

    def _stage_state_delta(self, side: str, epoch: int, delta) -> None:
        """Stage the rows of one side dirtied since the last checkpoint,
        selected and gathered on the device (``join_ckpt_delta_window``):
        what crosses to the host follows the delta, not the arena's
        capacity. A dirty row is a put where it is occupied, a delete
        where it is a tombstone that was not filled again."""
        st = getattr(self.state, side)
        n_dirty, (occ, tomb, datas, masks), fetched = fetch_delta(
            lambda lo, G: self._delta_window(st, lo, G), st.ckpt_dirty.size)
        delta.set(dirty_rows=n_dirty, bytes_staged=0, **fetched)
        if n_dirty:
            delta.set(bytes_staged=stage_delta(
                self.state_tables[side], epoch, datas, masks, occ,
                tomb & ~occ))

    def _load_from_state_tables(self) -> None:
        """Recovery: replay both sides' committed rows through the insert
        path (left first, then right) — degrees rebuild exactly; outputs are
        discarded. Under an ``hbm_key_budget`` only the first ``budget``
        keys load hot; the rest stay in the cold tier and fault in on
        mention (keys are chosen jointly across the two sides — a key is
        hot or cold on BOTH, the degree-coherence invariant)."""
        cold_keys: Optional[set] = None
        if self.hbm_key_budget is not None:
            side_rows = {}
            seen: list = []
            seen_set: set = set()
            for side in ("left", "right"):
                table = self.state_tables[side]
                rows = list(table.scan_all()) if table is not None else []
                if rows and self.load_shard is not None:
                    key_idx = (self.core.left_keys if side == "left"
                               else self.core.right_keys)
                    schema = (self.core.left_schema if side == "left"
                              else self.core.right_schema)
                    rows = self._filter_shard(rows, key_idx, schema)
                side_rows[side] = rows
                key_idx = (self.core.left_keys if side == "left"
                           else self.core.right_keys)
                for r in rows:
                    kv = tuple(r[i] for i in key_idx)
                    if any(v is None for v in kv):
                        continue                   # null keys always hot
                    k = self._pykey(kv)
                    if k not in seen_set:
                        seen_set.add(k)
                        seen.append(k)
            if len(seen) > self.hbm_key_budget:
                cold_keys = set(seen[self.hbm_key_budget:])
                self._evicted |= cold_keys
        for side in ("left", "right"):
            table = self.state_tables[side]
            if table is None:
                continue
            schema = (self.core.left_schema if side == "left"
                      else self.core.right_schema)
            key_idx = (self.core.left_keys if side == "left"
                       else self.core.right_keys)
            if cold_keys is not None:
                rows = [
                    r for r in side_rows[side]
                    if any(r[i] is None for i in key_idx)
                    or self._pykey(tuple(r[i] for i in key_idx))
                    not in cold_keys]
            elif self.hbm_key_budget is not None:
                rows = side_rows[side]      # already scanned + shard-filtered
            else:
                rows = list(table.scan_all())
                if rows and self.load_shard is not None:
                    rows = self._filter_shard(rows, key_idx, schema)
            bs = 1024
            for i in range(0, len(rows), bs):
                chunk = physical_chunk(schema, rows[i: i + bs], bs)
                self._apply_growing(side, chunk)
        self.state = self._clear_ckpt(self.state)

    def _filter_shard(self, rows: list, key_idx, schema) -> list:
        """Keep rows whose join key hashes to this actor's shard — the same
        device hash the dispatcher routes live rows with, so reload
        placement always matches routing, for ANY shard count."""
        import jax.numpy as jnp
        from ..common.chunk import Column
        from ..common.hashing import vnode_of, vnode_to_shard
        idx, n_shards = self.load_shard
        out = []
        bs = 1024
        for i in range(0, len(rows), bs):
            batch = rows[i:i + bs]
            cols = []
            for c in key_idx:
                vals = [r[c] for r in batch]
                data = np.array([v if v is not None else 0 for v in vals],
                                dtype=schema[c].type.np_dtype)
                mask = np.array([v is not None for v in vals])
                cols.append(Column(jnp.asarray(data), jnp.asarray(mask)))
            shard = np.asarray(vnode_to_shard(vnode_of(cols), n_shards))
            out.extend(r for r, s in zip(batch, shard) if int(s) == idx)
        return out


def _flags(state: JoinState) -> tuple:
    """What ``pack_stats`` reads of the state: the four overflow flags and
    the fan-out sides' probe counts of the step (an empty tuple where no
    side is fan-out: nothing is computed outside the stats program).
    Handing the stats program these and not the whole state keeps its
    dispatch at a few arguments (a state is some sixty device buffers)."""
    return (state.left.lane_overflow, state.left.ht_overflow,
            state.right.lane_overflow, state.right.ht_overflow,
            tuple(st.probe_counts for st in (state.left, state.right)
                  if hasattr(st, "probe_counts")))


def _fanout_gauges(sides: tuple) -> jax.Array:
    """``[live rows, row-table slots in use]`` over fan-out sides."""
    return jnp.stack([sum(jnp.sum(st.occupied, dtype=jnp.int64)
                          for st in sides),
                      sum(jnp.sum(st.ht.occupied, dtype=jnp.int64)
                          for st in sides)])


#: what ``JoinCore.emit_counts`` gives, in the packed stats from slot 6 on
EMIT_COUNTS = ("rows_out", "null_padded_out", "transitions", "matched",
               "unmatched")
#: what a step's probes of a fan-out side did (``FanoutSideState.
#: probe_counts``), after ``EMIT_COUNTS``: live probe rows, the arena rows
#: of their keys that the scan read, the pairs that passed the condition
FANOUT_COUNTS = ("fanout_probes", "candidate_rows", "fanout_pairs")
#: slots of the packed stats every plan has
N_STATS = 6 + len(EMIT_COUNTS) + len(FANOUT_COUNTS)


def pack_stats(core: JoinCore, flags: tuple, big, chunk, side: str,
               null_keys=None) -> jax.Array:
    """Every host-read scalar of one applied chunk in ONE vector:
    [l.lane_ovf, l.ht_ovf, r.lane_ovf, r.ht_ovf, n_units, rows_in,
    *EMIT_COUNTS, *FANOUT_COUNTS] and, where ``null_keys`` is given (a NOT
    IN plan: the chunk's key columns to look at, none on the probe side),
    the visible rows of the chunk with a NULL among them."""
    stats = [
        *(f.astype(jnp.int64) for f in flags[:4]),
        count_units(big),
        jnp.sum(chunk.vis, dtype=jnp.int64),
        *core.emit_counts(big, side),
        *(sum(flags[4]) if flags[4]
          else jnp.zeros(len(FANOUT_COUNTS), jnp.int64)),
    ]
    if null_keys is not None:
        keyed = chunk.vis
        for i in null_keys:
            keyed = keyed & chunk.columns[i].mask
        stats.append(jnp.sum(chunk.vis & ~keyed, dtype=jnp.int64))
    return jnp.stack(stats)


def _clear_ckpt_marks(state: JoinState) -> JoinState:
    def clear(st):
        st = st.replace(ckpt_dirty=jnp.zeros_like(st.ckpt_dirty),
                        tomb=jnp.zeros_like(st.tomb))
        if hasattr(st, "grave_n"):
            st = st.replace(grave_n=jnp.zeros_like(st.grave_n))
        return st
    return state.replace(left=clear(state.left), right=clear(state.right))
