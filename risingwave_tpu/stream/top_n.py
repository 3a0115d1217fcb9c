"""TopN executor family: plain / group, ±append-only, WITH TIES.

Counterpart of the reference's four TopN executors
(reference: src/stream/src/executor/top_n/{top_n_plain,group_top_n,
top_n_appendonly,group_top_n_appendonly}.rs over TopNCache
top_n/top_n_cache.rs:43). One implementation covers the whole family here:
the device row set (ops/row_set.py) absorbs chunks with last-writer-wins
upserts, and each barrier recomputes the rank window by a full device sort
(ops/topn.py) and emits the membership/value diff. Append-only inputs need
no special path (deletes simply never arrive); the flag only gates the
sanity check. GroupTopN = TopN with a group-key hash table assigning a gid
per row; ranks are computed per-gid segment in the same sort.

Output schema = input schema (the reference emits the full row; ordering of
emitted chunks is not significant downstream).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import struct

from ..common.chunk import DEFAULT_CHUNK_CAPACITY, StreamChunk
from ..ops.hash_table import DeviceHashTable, ht_lookup_or_insert, ht_new
from ..common.chunk import physical_chunk
from ..ops.row_set import (
    RowSetState, rs_apply_chunk, rs_changed, rs_checkpoint, rs_finish_flush,
    rs_gather_delta, rs_new,
)
from ..ops.topn import (
    OrderSpec, _key_sentinels, key0_dtype, topn_candidate_flush,
    topn_in_set, topn_refill,
)
from ..storage.state_table import StateTable
from .executor import Executor, SingleInputExecutor
from .message import Barrier


@struct.dataclass
class TopNState:
    rows: RowSetState
    group_table: DeviceHashTable   # group key -> gid (own slot index)
    gid: jax.Array                 # int32[cap]: group slot per row
    cand: jax.Array                # bool[cap]: incremental candidate slots
    t1: jax.Array                  # scalar: forget threshold (leading key)


class TopNExecutor(SingleInputExecutor):
    """``order``: OrderSpec list; window = [offset, offset+limit).

    ``group_by``: input column indices (empty = plain TopN).
    ``pk_indices``: stream pk of the input — row identity under updates."""

    identity = "TopN"

    def __init__(
        self,
        input: Executor,
        order: Sequence[OrderSpec],
        offset: int,
        limit: int,
        pk_indices: Sequence[int],
        group_by: Sequence[int] = (),
        with_ties: bool = False,
        append_only: bool = False,
        state_table: Optional[StateTable] = None,
        table_capacity: int = 1 << 16,
        out_capacity: int = DEFAULT_CHUNK_CAPACITY,
    ):
        super().__init__(input)
        if with_ties and offset != 0:
            raise ValueError("WITH TIES requires OFFSET 0 (reference parity)")
        self.schema = input.schema
        # pk columns as final tiebreak: emitted membership must be a
        # deterministic function of row *values*, not hash-slot order —
        # recovery re-derives the emitted set from reloaded rows and any
        # slot-dependent tie choice would diverge from what downstream holds
        # (the reference orders its TopN state table by (order key, pk))
        import dataclasses as _dc
        order = list(order)
        self.n_user_keys = len(order)
        ordered_cols = {o.col for o in order}
        order += [OrderSpec(i) for i in pk_indices if i not in ordered_cols]
        # VARCHAR order columns sort by dictionary *rank*, not raw id
        # (ids are insertion-ordered — reference: memcmp_encoding.rs)
        order = [_dc.replace(o, is_string=input.schema[o.col].type.is_string)
                 for o in order]
        self.order = tuple(order)
        self._has_str_order = any(o.is_string for o in self.order)
        self._rank_ver = -1
        self.offset, self.limit = offset, limit
        self.pk_indices = tuple(pk_indices)
        self.group_by = tuple(group_by)
        self.with_ties = with_ties
        self.append_only = append_only
        self.capacity = table_capacity
        self.out_capacity = out_capacity
        self.state_table = state_table
        if group_by:
            self.identity = "GroupTopN"

        pk_types = [input.schema[i].type for i in self.pk_indices]
        col_types = [f.type for f in input.schema]
        rows = rs_new(pk_types, col_types, table_capacity)
        group_types = [input.schema[i].type for i in self.group_by]

        # incremental fast path (plain TopN): sort only a candidate subset
        # per barrier (reference: 3-segment TopNCache, top_n_cache.rs:43);
        # groups/ties fall back to the full-sort flush
        win = offset + limit
        cand_cap = 1
        while cand_cap < max(2 * win + 128, 512):
            cand_cap *= 2
        self.cand_cap = cand_cap
        self.cand_keep = max(win, cand_cap // 2)
        self.use_incremental = (not group_by and not with_ties
                                and cand_cap < table_capacity)
        big0, _ = _key_sentinels(key0_dtype(rows, self.order[0]))

        # group table sized like the row table: worst case every row is its
        # own group; gid values are group-table slot indices
        self.state = TopNState(
            rows=rows,
            group_table=ht_new(group_types, table_capacity),
            gid=jnp.zeros(table_capacity, jnp.int32),
            cand=jnp.zeros(table_capacity, jnp.bool_),
            t1=big0,
        )
        self._dirty = False
        self.n_fast_flushes = 0      # observability: incremental flushes…
        self.n_refills = 0           # …vs full-sort refills
        self._apply = jax.jit(self._apply_impl)

        def _apply_batch_impl(state: TopNState, batched_chunk):
            def body(st, ch):
                return self._apply_impl(st, ch), None

            state, _ = jax.lax.scan(body, state, batched_chunk)
            return state

        # whole-ChunkBatch ingest in ONE dispatch (lax.scan keeps the
        # epoch loop on device; the default unstack-and-loop pays one
        # dispatch per chunk) — same amortization as hash_agg's
        self._apply_batch = jax.jit(_apply_batch_impl)
        self._compute_flush = jax.jit(self._compute_flush_impl)
        self._flush_fast = jax.jit(self._flush_fast_impl)
        self._flush_refill = jax.jit(self._flush_refill_impl)
        self._gather = jax.jit(rs_gather_delta, static_argnames=("out_capacity",))
        self._finish = jax.jit(rs_finish_flush)
        if state_table is not None:
            self._load_from_state_table()

    # -- pure steps -----------------------------------------------------------

    def _apply_impl(self, state: TopNState, chunk: StreamChunk) -> TopNState:
        rows, slots, applied = rs_apply_chunk(state.rows, chunk, self.pk_indices)
        idx = jnp.where(applied, slots, self.capacity)
        cand = state.cand.at[idx].set(True, mode="drop")
        if not self.group_by:
            return state.replace(rows=rows, cand=cand)
        gcols = [chunk.columns[i] for i in self.group_by]
        gtable, gslots, _, govf = ht_lookup_or_insert(
            state.group_table, gcols, applied)
        gid = state.gid.at[idx].set(gslots, mode="drop")
        rows = rows.replace(overflow=rows.overflow | govf)
        return state.replace(rows=rows, group_table=gtable, gid=gid,
                             cand=cand)

    def _stats(self, state: TopNState, changed, bad):
        """All host-fetched scalars in ONE array → one device→host
        fetch."""
        return jnp.stack([
            jnp.sum(changed),
            bad.astype(jnp.int64),
            state.rows.overflow.astype(jnp.int64),
            state.rows.saw_delete.astype(jnp.int64),
        ])

    def _compute_flush_impl(self, state: TopNState, str_ranks=None):
        in_set = topn_in_set(
            state.rows, state.gid, self.order, self.offset, self.limit,
            self.with_ties, n_tie_keys=self.n_user_keys,
            str_ranks=str_ranks)
        changed = rs_changed(state.rows, in_set)
        return in_set, changed, self._stats(
            state, changed, jnp.zeros((), jnp.bool_))

    def _flush_fast_impl(self, state: TopNState, str_ranks=None):
        in_set, new_cand, new_t1, bad = topn_candidate_flush(
            state.rows, self.order, self.offset, self.limit,
            state.cand, self.cand_cap, self.cand_keep, state.t1,
            str_ranks=str_ranks)
        changed = rs_changed(state.rows, in_set)
        return in_set, changed, new_cand, new_t1, self._stats(
            state, changed, bad)

    def _flush_refill_impl(self, state: TopNState, str_ranks=None):
        in_set, cand, t1 = topn_refill(
            state.rows, state.gid, self.order, self.offset, self.limit,
            self.cand_keep, str_ranks=str_ranks)
        changed = rs_changed(state.rows, in_set)
        return in_set, changed, cand, t1, self._stats(
            state, changed, jnp.zeros((), jnp.bool_))

    def _cur_ranks(self):
        """(device rank table | None, dictionary version). Fetched fresh per
        flush — the table grows as strings are interned."""
        if not self._has_str_order:
            return None, self._rank_ver
        from ..common.types import GLOBAL_STRING_DICT
        return GLOBAL_STRING_DICT.device_ranks(), GLOBAL_STRING_DICT.version

    # -- host control ---------------------------------------------------------

    async def map_chunk(self, chunk: StreamChunk):
        self.state = self._apply(self.state, chunk)
        self._dirty = True
        if False:
            yield

    async def map_chunk_batch(self, batch):
        self.state = self._apply_batch(self.state, batch.chunk)
        self._dirty = True
        if False:
            yield

    async def on_barrier(self, barrier: Barrier):
        if not self._dirty:
            # idle barrier: membership cannot have changed — skip the sort
            # entirely (barrier cost independent of stored row count)
            if barrier.checkpoint and self.state_table is not None:
                self._checkpoint(barrier.epoch.curr)
            return
        self._dirty = False
        import numpy as np
        str_ranks, rank_ver = self._cur_ranks()
        if self.use_incremental:
            # a dictionary grown since the last flush may have re-ranked
            # keys under the stored t1 threshold / candidate set — the fast
            # path's invariants no longer hold, recompute from the full set
            bad = self._has_str_order and rank_ver != self._rank_ver
            if not bad:
                in_set, changed, cand, t1, stats = self._flush_fast(
                    self.state, str_ranks)
                n_changed, bad, ovf, sawdel = (
                    int(x) for x in np.asarray(stats))
            if bad:
                # candidate set over/underflowed or the window reached the
                # forgotten region: full-sort refill
                (in_set, changed, cand, t1,
                 stats) = self._flush_refill(self.state, str_ranks)
                n_changed, _, ovf, sawdel = (
                    int(x) for x in np.asarray(stats))
                self.n_refills += 1
            else:
                self.n_fast_flushes += 1
            self.state = self.state.replace(cand=cand, t1=t1)
        else:
            in_set, changed, stats = self._compute_flush(self.state, str_ranks)
            n_changed, _, ovf, sawdel = (int(x) for x in np.asarray(stats))
        self._rank_ver = rank_ver
        if ovf:
            raise RuntimeError(
                f"{self.identity}: row table overflow (capacity "
                f"{self.capacity}); increase table_capacity")
        if self.append_only and sawdel:
            raise RuntimeError(
                f"{self.identity}: delete arrived on declared append-only "
                "input")
        lo, n = 0, n_changed
        while lo < n:
            chunk = self._gather(self.state.rows, in_set, changed,
                                 jnp.int64(lo), out_capacity=self.out_capacity)
            yield chunk
            lo += self.out_capacity // 2
        if barrier.checkpoint and self.state_table is not None:
            self._checkpoint(barrier.epoch.curr)
        self.state = self.state.replace(rows=self._finish(self.state.rows, in_set))

    # -- persistence ----------------------------------------------------------
    # The durable row is the full input row; membership is recomputed on
    # recovery (reference persists the full managed state the same way and
    # rebuilds TopNCache from the state table on startup).

    def _checkpoint(self, epoch: int) -> None:
        rows = rs_checkpoint(self.state.rows, self.state_table, epoch)
        self.state = self.state.replace(rows=rows)

    def _load_from_state_table(self) -> None:
        rows = list(self.state_table.scan_all())
        if not rows:
            return
        bs = 1024
        for i in range(0, len(rows), bs):
            chunk = physical_chunk(self.schema, rows[i:i + bs], bs)
            self.state = self._apply(self.state, chunk)
        # recovered rows were already emitted before the failure: rebuild the
        # emitted snapshot so the first post-recovery flush emits no spurious
        # inserts; the reloaded slots are not checkpoint-dirty (they ARE the
        # checkpoint)
        # overflow during reload must surface immediately — idle barriers
        # skip the (sync-costing) check until the next data chunk
        if bool(self.state.rows.overflow):
            raise RuntimeError(
                f"{self.identity}: row table overflow while reloading "
                f"checkpoint (capacity {self.capacity})")
        str_ranks, rank_ver = self._cur_ranks()
        if self.use_incremental:
            in_set, _, cand, t1, _ = self._flush_refill(self.state, str_ranks)
            self.state = self.state.replace(cand=cand, t1=t1)
        else:
            in_set, _, _ = self._compute_flush(self.state, str_ranks)
        self._rank_ver = rank_ver
        self._dirty = False
        rows_st = self._finish(self.state.rows, in_set)
        import jax.numpy as _jnp
        rows_st = rows_st.replace(ckpt_dirty=_jnp.zeros_like(rows_st.ckpt_dirty))
        self.state = self.state.replace(rows=rows_st)

