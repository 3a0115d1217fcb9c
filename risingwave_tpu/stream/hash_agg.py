"""HashAggExecutor — incremental group-by aggregation on device-resident state.

TPU-native counterpart of the reference's HashAggExecutor
(reference: src/stream/src/executor/hash_agg.rs:66-123, apply_chunk :319,
flush_data :404; per-group AggGroup, executor/aggregation/agg_group.rs:159).
Design differences, deliberately (SURVEY.md §7):

  * Group state is NOT an LRU cache over a row store — it lives wholly in
    device HBM as an open-addressing table (ops/hash_table.py) plus per-group
    aggregate "lanes" arrays. A whole chunk updates all its groups in one
    jitted step via scatter-reduce: no per-key host loop anywhere.
  * The dirty-group set is a device bitmask; on every barrier the changed
    groups are gathered into output chunks (Insert / U-,U+ / Delete exactly
    like the reference's flush), and ``prev`` lanes advance.
  * A second bitmask accumulates dirtiness between *checkpoint* barriers;
    on checkpoint the delta groups are flushed to the host StateTable (the
    durable tier) and recovery reloads them (hash_agg.rs state tables +
    recovery §3.4).

Row-count lane 0 is implicit (the reference's AggGroup ``row_count``) and
drives Insert-vs-Update-vs-Delete emission and group liveness.

The pure device logic lives in ops/grouped_agg.py (shared with the sharded
multi-chip path, parallel/sharded_agg.py); this class is the host control
loop + persistence.
"""

from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..common.chunk import DEFAULT_CHUNK_CAPACITY, Column, StreamChunk
from ..common.fetch import fetch
from ..common.tracing import CAT_STORAGE, span
from ..common.types import INT64, Field, Schema
from ..expr.agg import AggCall
from ..ops.grouped_agg import AggCore, AggState, load_rows_into_state
from ..storage.state_table import StateTable
from .executor import Executor, SingleInputExecutor
from .message import Barrier
from .state_delta import fetch_delta, stage_delta


class HashAggExecutor(SingleInputExecutor):
    """``group_keys``: input column indices; ``agg_calls``: AggCall specs.

    Output schema: group key columns then one column per agg call."""

    identity = "HashAgg"

    def __init__(
        self,
        input: Executor,
        group_keys: Sequence[int],
        agg_calls: Sequence[AggCall],
        state_table: Optional[StateTable] = None,
        table_capacity: int = 1 << 16,
        out_capacity: int = DEFAULT_CHUNK_CAPACITY,
        load_shard: Optional[tuple] = None,
        load_vnodes: Optional[tuple] = None,
        hbm_group_budget: Optional[int] = None,
    ):
        """``load_shard``: (shard_idx, n_shards) for fragmented builds —
        this actor shares its state table with its sibling shards and on
        recovery keeps only the rows whose group key hashes to its shard
        (vnode reassignment across a parallelism change, reference:
        stream/scale.rs:657 vnode-bitmap updates).

        ``load_vnodes``: (vnode_start, vnode_end) for SPANNING fragment
        actors (meta-placed vnode ranges): recovery keeps only rows in
        the owned range. After a live vnode migration the actor's local
        store may hold rows for ranges that moved away (and an imported
        handoff may sit beside foreign leftovers) — this filter is what
        makes reload placement equal live routing regardless of
        migration history (meta/rescale.py, docs/scaling.md).

        ``hbm_group_budget``: cap on LIVE groups held in device memory.
        When a checkpoint finds more, the coldest (LRU by touch step) are
        evicted to the state table and faulted back in on access
        (reference: ManagedLruCache over StateTables,
        src/stream/src/cache/managed_lru.rs) — device state becomes a
        cache over the durable tier instead of grow-or-raise. Requires a
        state_table; must be < table_capacity (headroom for growth
        between checkpoints)."""
        super().__init__(input)
        for c in agg_calls:
            if c.lanes_unsupported:
                # silent wrongness guard: fixed device lanes cannot dedup
                # or materialize input; the planner must route these to
                # MaterializedAggExecutor
                raise ValueError(
                    f"{c.kind}{'(distinct)' if c.distinct else ''} needs "
                    "materialized-input state (stream/materialized_agg.py)")
        self.load_shard = load_shard
        self.load_vnodes = load_vnodes
        if hbm_group_budget is not None:
            if state_table is None:
                hbm_group_budget = None       # no cold tier to evict to
            elif hbm_group_budget >= table_capacity:
                raise ValueError(
                    "hbm_group_budget must be < table_capacity")
        self.hbm_group_budget = hbm_group_budget
        self._evicted: set = set()
        from .cache import LruClock
        self._lru_clock = LruClock(hbm_group_budget is not None)
        in_schema = input.schema
        key_types = tuple(in_schema[i].type for i in group_keys)
        self.core = AggCore(key_types, group_keys, agg_calls, table_capacity,
                            out_capacity)
        self.schema = Schema(
            tuple(in_schema[i] for i in group_keys)
            + tuple(Field(f"agg{i}", c.output_type) for i, c in enumerate(agg_calls))
        )
        self.state_table = state_table
        self.state = self.core.init_state()
        # Donating the state pytree lets XLA update the group table in place
        # (no copy of the [capacity]-sized lanes per chunk) — on every
        # backend, so the CPU tests run what the chip runs.
        donate = (0,)
        self._apply = jax.jit(self.core.apply_chunk, donate_argnums=donate)
        # string MIN/MAX compares dictionary ranks, fetched fresh per apply
        self._needs_ranks = any(c.is_string_minmax for c in self.core.agg_calls)

        def _apply_batch(state, batched_chunk, str_ranks=None, step=None):
            def body(st, ch):
                return self.core.apply_chunk(st, ch, str_ranks, step), None
            state, _ = jax.lax.scan(body, state, batched_chunk)
            return state

        # One dispatch applies a whole ChunkBatch: the epoch loop stays on
        # device (lax.scan), amortizing host->device dispatch latency.
        self._apply_batch = jax.jit(_apply_batch, donate_argnums=donate)
        self._gather = jax.jit(self.core.gather_flush_chunk)
        self._finish = jax.jit(self.core.finish_flush)
        # the checkpoint's delta window reads the state and leaves it in
        # place (not donated); a device trace shows it under its own name,
        # jit_ckpt_delta_window
        self._delta_window = jax.jit(self.core.ckpt_delta_window,
                                     static_argnums=(2,))

        # barrier probe: ONE packed scalar fetch per barrier instead of
        # separate overflow + n_dirty + per-chunk cardinality syncs. The
        # dirty-rank prefix sums stay on device and are shared by all
        # flush windows of the barrier.
        def _probe(st):
            rank = self.core.flush_rank(st)
            if self.hbm_group_budget is not None:
                # live-group census gates cold eviction; only budgeted
                # executors pay for it (an O(capacity) int64 compare —
                # kept OFF the bench-critical unbudgeted probe, which is
                # the exact graph proven on-chip in round 3)
                n_live = jnp.sum(st.table.occupied & (st.lanes[0] > 0))
                n_live = n_live.astype(jnp.int32)
            else:
                n_live = jnp.zeros((), jnp.int32)
            packed = jnp.stack([rank[-1], st.overflow.astype(jnp.int32),
                                n_live])
            return packed, rank

        self._probe = jax.jit(_probe)
        self._clean = jax.jit(self.core.clean_below, static_argnums=(1,))
        self._compact = jax.jit(self.core.compact)
        self._evict_plan = jax.jit(self.core.evict_plan,
                                   static_argnums=(1,))
        self._apply_evict = jax.jit(self.core.apply_evict)
        self._absorb = jax.jit(self.core.absorb)
        # group-key watermark state cleaning (reference: hash_agg group-key
        # watermarks + state_table.rs:885 update_watermark)
        self._pending_clean: dict[int, Any] = {}
        if self.state_table is not None:
            self._load_from_state_table()

    # convenience accessors used by tests/tools
    @property
    def group_keys(self):
        return self.core.group_keys

    @property
    def agg_calls(self):
        return self.core.agg_calls

    # -- host control ---------------------------------------------------------

    def _str_ranks(self):
        if not self._needs_ranks:
            return None
        from ..common.types import GLOBAL_STRING_DICT
        return GLOBAL_STRING_DICT.device_ranks()

    def _pykey(self, values) -> tuple:
        from .cache import canonical_key
        return canonical_key(values, self.core.key_types)

    def _lru(self):
        return self._lru_clock.next()

    async def map_chunk(self, chunk: StreamChunk):
        self.state = self._apply(self.state, chunk, self._str_ranks(),
                                 self._lru())
        if self._evicted:
            self._fault_in(chunk.columns, chunk.vis)
        if False:
            yield

    async def map_chunk_batch(self, batch):
        self.state = self._apply_batch(self.state, batch.chunk,
                                       self._str_ranks(), self._lru())
        if self._evicted:
            self._fault_in(batch.chunk.columns, batch.chunk.vis)
        if False:
            yield

    # -- eviction / fault-in ---------------------------------------------------

    def _fault_in(self, columns, vis) -> None:
        """Reload any evicted group keys present in this chunk/batch from
        the cold tier and merge their stored lanes into device state
        (one host sync per chunk, paid only while evicted keys exist)."""
        nk = len(self.core.group_keys)
        key_np = [np.asarray(columns[i].data).ravel()
                  for i in self.core.group_keys]
        vis_np = np.asarray(vis).ravel()
        present = set(zip(*(k[vis_np] for k in key_np))) if nk else set()
        hits = [k for k in present if self._pykey(k) in self._evicted]
        if not hits:
            return
        rows = []
        keys = []
        for k in hits:
            pk = self._pykey(k)
            row = self.state_table.get_row(pk)
            if row is not None:
                rows.append(row)
                keys.append(k)
            self._evicted.discard(pk)
        if not rows:
            return
        n = len(rows)
        cap = 1
        while cap < n:
            cap *= 2
        valid = jnp.arange(cap) < n
        key_cols = []
        for c in range(nk):
            data = np.zeros(cap, self.core.key_types[c].np_dtype)
            data[:n] = [k[c] for k in keys]
            key_cols.append(Column(jnp.asarray(data),
                                   jnp.asarray(np.arange(cap) < n)))
        stored = []
        for j, dt in enumerate(self.core.lane_dtypes):
            arr = np.zeros(cap, np.dtype(dt))
            arr[:n] = [r[nk + j] for r in rows]
            stored.append(jnp.asarray(arr))
        self.state = self._absorb(self.state, key_cols, tuple(stored),
                                  valid, self._str_ranks())

    async def on_barrier(self, barrier: Barrier):
        packed, rank = self._probe(self.state)
        # through the async-fetch helper: the packed copy starts
        # streaming at enqueue, and the tick-path lint
        # (sync-fetch-discipline) can reason about one crossing
        with span("agg.flush_wait", epoch=barrier.epoch.curr, wait="device",
                  tid=self.identity):
            n_dirty, overflow, n_live = (int(x) for x in fetch(packed))
        if overflow:
            raise RuntimeError(
                f"{self.identity}: group table overflow (capacity "
                f"{self.core.capacity}); increase table_capacity")
        lo = 0
        while lo < n_dirty:
            # no cardinality gating: a rare all-invisible flush chunk (groups
            # born and killed within one epoch) is a downstream no-op, while
            # gating costs one RTT sync per chunk
            yield self._gather(self.state, rank, jnp.int64(lo))
            lo += self.core.groups_per_chunk
        cleaned = False
        if barrier.checkpoint and self._pending_clean:
            # mark dead BEFORE the checkpoint so it persists the deletes
            # (keys must still be readable from the table), compact AFTER
            for key_pos, threshold in self._pending_clean.items():
                self.state = self._clean(self.state, key_pos,
                                         jnp.asarray(threshold))
            self._pending_clean.clear()
            cleaned = True
        if barrier.checkpoint and self.state_table is not None:
            self._checkpoint_to_state_table(barrier.epoch.curr)
            if (self.hbm_group_budget is not None
                    and n_live > self.hbm_group_budget):
                self._evict_cold()
                cleaned = True
        if cleaned:
            self.state = self._compact(self.state)
        self.state = self._finish(self.state)

    def _evict_cold(self) -> None:
        """Evict the coldest live groups down to 3/4 of the budget (their
        durable rows were just written by this barrier's checkpoint).
        Null-keyed groups are never evicted (the fault-in key path carries
        no null masks)."""
        keep = max(self.hbm_group_budget * 3 // 4, 1)
        mask, _n = self._evict_plan(self.state, keep)
        all_keys_valid = None
        for km in self.state.table.key_mask:
            all_keys_valid = km if all_keys_valid is None \
                else (all_keys_valid & km)
        if all_keys_valid is not None:
            mask = mask & all_keys_valid
        nm = np.asarray(mask)
        idx = np.nonzero(nm)[0]
        if not len(idx):
            return
        key_np = [np.asarray(kd)[idx] for kd in self.state.table.key_data]
        for row in zip(*key_np):
            self._evicted.add(self._pykey(row))
        self.state = self._apply_evict(self.state, jnp.asarray(nm))

    async def on_watermark(self, watermark):
        """Watermark on a group-key column: remap to the output position and
        schedule state cleaning below it; other columns' watermarks cannot
        be propagated through a grouped agg."""
        if watermark.col_idx in self.core.group_keys:
            pos = self.core.group_keys.index(watermark.col_idx)
            prev = self._pending_clean.get(pos)
            if prev is None or watermark.value > prev:
                self._pending_clean[pos] = watermark.value
            yield watermark.__class__(pos, watermark.value)

    # -- persistence ----------------------------------------------------------

    def _checkpoint_to_state_table(self, epoch: int) -> None:
        """Flush groups dirtied since the last checkpoint to the durable tier.

        Host sync is bounded by the checkpoint delta, mirroring the
        reference's incremental StateTable.commit (state_table.rs:783).
        The span lives HERE so that both callers have it: ``on_barrier``
        and the co-scheduled tick, which borrows this executor as its
        persistence engine."""
        st = self.state
        with span("agg.state_delta", epoch=epoch, stage="state_delta",
                  cat=CAT_STORAGE, tid=self.identity) as delta:
            stage_agg_delta(self.state_table, epoch, delta,
                            lambda lo, G: self._delta_window(st, lo, G),
                            st.ckpt_dirty.shape[0])
            self.state = st.replace(
                ckpt_dirty=jnp.zeros_like(st.ckpt_dirty))

    def _filter_shard(self, rows: list) -> list:
        """Keep rows whose group key hashes to this actor's shard — the
        same device hash the dispatcher routes live rows with, so reload
        placement always matches routing, for ANY shard count."""
        from ..common.hashing import shard_rows
        idx, n_shards = self.load_shard
        return shard_rows(self.core.key_types, rows, n_shards)[idx]

    def _load_from_state_table(self) -> None:
        """Recovery: reload committed groups into the device table."""
        rows = list(self.state_table.scan_all())
        if rows and self.load_shard is not None:
            rows = self._filter_shard(rows)
        if rows and self.load_vnodes is not None:
            # spanning actor: keep only the meta-placed vnode range —
            # post-migration stores may hold rows that moved away
            from ..common.hashing import filter_rows_vnodes
            s, e = self.load_vnodes
            rows = filter_rows_vnodes(self.core.key_types, rows, s, e)
        if (self.hbm_group_budget is not None
                and len(rows) > self.hbm_group_budget):
            # under eviction the durable tier legitimately holds more
            # groups than the device budget: load up to the budget, leave
            # the rest cold (null-keyed rows always load — the fault-in
            # key path carries no null masks)
            nk0 = len(self.core.group_keys)
            hot, cold = [], []
            for r in rows:
                key = r[:nk0]
                if len(hot) < self.hbm_group_budget or any(
                        v is None for v in key):
                    hot.append(r)
                else:
                    cold.append(r)
            for r in cold:
                self._evicted.add(self._pykey(r[:nk0]))
            rows = hot
        if not rows:
            return
        self.state = load_rows_into_state(self.core, self.state, rows)
        # prev must match what was already emitted before the failure: the
        # recovered snapshot is the new baseline
        self.state = self.state.rebaselined()


def stage_agg_delta(table: StateTable, epoch: int, delta, window,
                    capacity: int) -> None:
    """Select and gather the groups dirtied since the last checkpoint ON
    THE DEVICE (``window`` dispatches ``AggCore.ckpt_delta_window``, alone
    or under ``vmap`` over a mesh's shards) and fetch only those rows: what
    crosses to the host follows the delta, not the table's capacity. A
    group whose row count is back at 0 is a delete. ``delta`` is the
    ``agg.state_delta`` span, whose counters this sets."""
    n_dirty, (keys_d, keys_m, lanes), fetched = fetch_delta(window, capacity)
    delta.set(dirty_groups=n_dirty, bytes_staged=0, **fetched)
    if n_dirty:
        live = lanes[0] > 0
        delta.set(bytes_staged=stage_delta(
            table, epoch, keys_d + lanes,
            keys_m + (np.ones(n_dirty, bool),) * len(lanes), live, ~live))


def agg_state_schema(key_fields: Sequence[Field], agg_calls: Sequence[AggCall]) -> Schema:
    """Schema of the durable agg state table: keys + raw lanes."""
    from ..common.types import FLOAT64
    lanes = [Field("row_count", INT64)]
    for i, c in enumerate(agg_calls):
        for j, dt in enumerate(c.state_dtypes()):
            lanes.append(Field(f"a{i}_l{j}", INT64 if dt == jnp.int64 else FLOAT64))
    return Schema(tuple(key_fields) + tuple(lanes))
