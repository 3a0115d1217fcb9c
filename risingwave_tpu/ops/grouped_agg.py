"""Pure device-side grouped-aggregation core.

The functional heart shared by HashAggExecutor (single shard) and the
sharded/multi-chip path (parallel/sharded_agg.py): all logic is pure
(state, chunk) -> state / chunk, so it runs unchanged inside ``jit`` on one
chip or inside ``shard_map`` per mesh shard. See stream/hash_agg.py for the
semantics discussion and reference citations.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from flax import struct

from ..common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, Column,
    StreamChunk,
)
from ..expr.agg import AggCall
from .ckpt_delta import delta_window, dirty_slots
from .hash_table import DeviceHashTable, ht_lookup_or_insert, ht_new, scatter_reduce


@struct.dataclass
class AggState:
    table: DeviceHashTable
    lanes: tuple[jax.Array, ...]       # [cap] per lane; lane 0 = row count
    prev_lanes: tuple[jax.Array, ...]  # values as of last emitted flush
    dirty: jax.Array                   # bool[cap] since last barrier flush
    ckpt_dirty: jax.Array              # bool[cap] since last checkpoint
    overflow: jax.Array                # bool scalar, sticky
    last_used: jax.Array               # int32[cap]: step of last touch (LRU)

    def rebaselined(self) -> "AggState":
        """``prev_lanes`` := a COPY of ``lanes`` (recovery: the loaded
        snapshot is the baseline downstream already saw). A copy, not an
        alias: the epoch steps donate the whole state, and one buffer
        cannot be donated twice."""
        return self.replace(
            prev_lanes=tuple(jnp.copy(l) for l in self.lanes))


class AggCore:
    """Static config + pure methods for one grouped-agg operator."""

    def __init__(self, key_types: Sequence, group_keys: Sequence[int],
                 agg_calls: Sequence[AggCall], table_capacity: int,
                 out_capacity: int):
        self.key_types = tuple(key_types)
        self.group_keys = tuple(group_keys)
        self.agg_calls = tuple(agg_calls)
        self.capacity = table_capacity
        self.out_capacity = out_capacity
        self.groups_per_chunk = out_capacity // 2
        self.lane_dtypes = [jnp.int64]
        self.call_lane_ofs = []
        for c in self.agg_calls:
            self.call_lane_ofs.append(len(self.lane_dtypes))
            self.lane_dtypes.extend(c.state_dtypes())

    def init_state(self) -> AggState:
        cap = self.capacity

        def init_lanes() -> tuple:
            lanes = [jnp.zeros(cap, jnp.int64)]
            for c in self.agg_calls:
                for v, dt in zip(c.init_lanes(), c.state_dtypes()):
                    lanes.append(jnp.full(cap, v, dt))
            return tuple(lanes)

        # lanes and prev_lanes are separate buffers: the epoch steps
        # donate the whole state, and one buffer cannot be donated twice
        return AggState(
            table=ht_new(self.key_types, cap),
            lanes=init_lanes(),
            prev_lanes=init_lanes(),
            dirty=jnp.zeros(cap, jnp.bool_),
            ckpt_dirty=jnp.zeros(cap, jnp.bool_),
            overflow=jnp.zeros((), jnp.bool_),
            last_used=jnp.zeros(cap, jnp.int32),
        )

    # -- pure steps -----------------------------------------------------------

    def apply_chunk(self, state: AggState, chunk: StreamChunk,
                    str_ranks=None, step=None) -> AggState:
        """``step``: monotone host counter stamped onto touched slots for
        LRU eviction ordering (None = no tracking; the sharded path and
        budget-less executors skip it)."""
        key_cols = [chunk.columns[i] for i in self.group_keys]
        # named scopes are metadata only: "table_probe" (ops/hash_table.py)
        # and "lane_apply" show in the op names of a device trace
        table, slots, _is_new, ovf = ht_lookup_or_insert(
            state.table, key_cols, chunk.vis
        )
        with jax.named_scope("lane_apply"):
            lanes, mark = self._apply_lanes(state, chunk, slots, str_ranks)
        dirty = state.dirty.at[mark].set(True, mode="drop")
        ckpt_dirty = state.ckpt_dirty.at[mark].set(True, mode="drop")
        last_used = state.last_used
        if step is not None:
            last_used = last_used.at[mark].set(
                jnp.asarray(step, jnp.int32), mode="drop")
        return state.replace(
            table=table, lanes=tuple(lanes), dirty=dirty,
            ckpt_dirty=ckpt_dirty, overflow=state.overflow | ovf,
            last_used=last_used,
        )

    def _apply_lanes(self, state: AggState, chunk: StreamChunk, slots,
                     str_ranks):
        """Fold the chunk's rows into the lanes of their slots. Returns
        ``(lanes, mark)``; ``mark`` is the slot per visible row."""
        signs = chunk.signs()
        lanes = list(state.lanes)
        lanes[0] = scatter_reduce(lanes[0], slots, signs, "add")
        for call, ofs in zip(self.agg_calls, self.call_lane_ofs):
            if call.arg >= 0:
                col = chunk.columns[call.arg]
                value, vmask = col.data, col.mask & chunk.vis
            else:
                value = jnp.zeros_like(signs)
                vmask = chunk.vis
            contribs = call.contributions(value, vmask, signs, str_ranks)
            for j, (contrib, op) in enumerate(zip(contribs, call.reduce_ops())):
                # string MIN/MAX: reduce in packed rank|id space, store ids
                lane = call.pack_lane(lanes[ofs + j], str_ranks)
                lanes[ofs + j] = call.unpack_lane(
                    scatter_reduce(lane, slots, contrib, op))
        return lanes, jnp.where(chunk.vis, slots, self.capacity)

    def outputs(self, lanes) -> list[tuple[jax.Array, jax.Array]]:
        live = lanes[0] > 0
        outs = []
        for call, ofs in zip(self.agg_calls, self.call_lane_ofs):
            call_lanes = [lanes[ofs + j] for j in range(call.num_lanes)]
            data, mask = call.output(call_lanes, live)
            outs.append((data.astype(call.output_type.dtype), mask))
        return outs

    @jax.named_scope("flush_probe")
    def flush_rank(self, state: AggState) -> jax.Array:
        """Inclusive prefix count of dirty groups — computed ONCE per barrier
        and shared by every flush window (it is the only O(capacity) piece of
        the flush)."""
        return jnp.cumsum(state.dirty.astype(jnp.int32))

    @jax.named_scope("flush_gather")
    def gather_flush_chunk(self, state: AggState, rank: jax.Array,
                           lo: jax.Array) -> StreamChunk:
        """One output chunk for dirty groups with rank in [lo, lo+G).

        Pure gather formulation: the slot of the k-th dirty group is found by
        binary search over the rank prefix sums, then every output column is
        a [G]-sized gather + interleave. No scatters — TPU scatters serialize
        per update, and the old scatter-from-[capacity] form cost ~1 s per
        window at multi-million-row capacity."""
        G = self.groups_per_chunk
        slot, valid = dirty_slots(rank, lo, G)

        def interleave(a, b):
            return jnp.stack([a, b], axis=-1).reshape(2 * G)

        prev_g = [l[slot] for l in state.prev_lanes]
        cur_g = [l[slot] for l in state.lanes]
        prev_live = prev_g[0] > 0
        cur_live = cur_g[0] > 0

        op0 = jnp.where(cur_live, OP_UPDATE_DELETE, OP_DELETE)   # prev row
        op1 = jnp.where(prev_live, OP_UPDATE_INSERT, OP_INSERT)  # cur row
        ops = interleave(op0, op1).astype(jnp.int8)

        cols = []
        for kd, km in zip(state.table.key_data, state.table.key_mask):
            d, m = kd[slot], km[slot]
            cols.append(Column(interleave(d, d), interleave(m, m)))
        prev_outs = self.outputs(prev_g)
        cur_outs = self.outputs(cur_g)
        # a group touched again whose output row did not change emits
        # nothing (reference: AggGroup::build_change returns no change
        # where prev_outputs == curr_outputs): a GROUP BY without an
        # aggregate then emits each group once, not an update pair of two
        # equal rows on every later touch
        same = prev_live & cur_live
        for (pd, pm), (cd, cm) in zip(prev_outs, cur_outs):
            same = same & (pm == cm) & ((pd.astype(cd.dtype) == cd) | ~cm)
        emit = valid & ~same
        vis = interleave(prev_live & emit, cur_live & emit)
        for (pd, pm), (cd, cm) in zip(prev_outs, cur_outs):
            cols.append(Column(interleave(pd.astype(cd.dtype), cd),
                               interleave(pm, cm)))
        return StreamChunk(ops, vis, tuple(cols))

    @jax.named_scope("ckpt_delta")
    def ckpt_delta_window(self, state: AggState, lo: jax.Array, G: int):
        """The checkpoint delta's rows for dirty ranks [lo, lo+G), in
        ascending slot order: ``(n_dirty, valid[G], key_data, key_mask,
        lanes)``, each column gathered to ``G`` rows
        (``ckpt_delta.delta_window``: gathers only, so only the dirty rows
        need cross to the host). The capacity is the state's own: the tick
        compiler hands padded states."""
        n_dirty, valid, cols = delta_window(
            state.ckpt_dirty,
            (state.table.key_data, state.table.key_mask, state.lanes), lo, G)
        return (n_dirty, valid, *cols)

    @jax.named_scope("flush_finish")
    def finish_flush(self, state: AggState) -> AggState:
        prev = tuple(
            jnp.where(state.dirty, cur, prev)
            for cur, prev in zip(state.lanes, state.prev_lanes)
        )
        return state.replace(prev_lanes=prev, dirty=jnp.zeros_like(state.dirty))

    # -- watermark-driven state cleaning --------------------------------------
    # (reference: state cleaning via state-table watermarks,
    #  src/stream/src/common/table/state_table.rs:885 update_watermark;
    #  hash_agg group-key watermark handling)

    def clean_below(self, state: AggState, key_pos: int,
                    threshold) -> AggState:
        """Mark groups whose ``key_pos``-th group-key value < threshold as
        dead: lanes reset to init (row_count 0) and ckpt_dirty set so the
        next checkpoint writes durable deletes. The hash table is NOT
        touched here — freeing open-addressing slots in place would break
        probe chains; ``compact`` rebuilds it after the checkpoint."""
        kd = state.table.key_data[key_pos]
        km = state.table.key_mask[key_pos]
        dead = state.table.occupied & km & (kd < threshold)
        init = self.init_state()
        lanes = tuple(
            jnp.where(dead, il, l) for l, il in zip(state.lanes, init.lanes))
        return state.replace(
            lanes=lanes,
            ckpt_dirty=state.ckpt_dirty | dead,
            # no `dirty` mark: cleaning frees state, it does not retract
            # already-emitted results downstream
        )

    def compact(self, state: AggState) -> AggState:
        """Rebuild the hash table keeping only live groups (row_count > 0),
        remapping every lane array. Run AFTER the checkpoint that persisted
        the deletes (the delete path still needs the dead groups' keys)."""
        cap = self.capacity
        live = state.table.occupied & (state.lanes[0] > 0)
        key_cols = [
            Column(kd, km)
            for kd, km in zip(state.table.key_data, state.table.key_mask)
        ]
        ht, slots, _, rebuild_ovf = ht_lookup_or_insert(
            ht_new(self.key_types, cap), key_cols, live)
        dst = jnp.where(live, slots, cap)
        init = self.init_state()

        def move(arr, init_arr):
            return init_arr.at[dst].set(arr, mode="drop")

        return AggState(
            table=ht,
            lanes=tuple(move(l, il)
                        for l, il in zip(state.lanes, init.lanes)),
            prev_lanes=tuple(move(l, il)
                             for l, il in zip(state.prev_lanes, init.lanes)),
            dirty=move(state.dirty, init.dirty),
            ckpt_dirty=move(state.ckpt_dirty, init.ckpt_dirty),
            # a group that exhausts probing during rebuild would be silently
            # dropped by mode="drop" — surface it like every overflow path
            overflow=state.overflow | rebuild_ovf,
            last_used=move(state.last_used, init.last_used),
        )

    # -- HBM eviction to the cold tier ----------------------------------------
    # (reference: ManagedLruCache over StateTables under memory pressure,
    #  src/stream/src/cache/managed_lru.rs; JoinHashMap LRU,
    #  executor/managed_state/join/mod.rs:228-258. Device state is a CACHE
    #  over the state table: eviction frees slots whose durable copy is
    #  current, absorb() faults a key's stored value back in on access.)

    def evict_plan(self, state: AggState, keep: int):
        """Pick cold live slots to evict so ~``keep`` hottest remain.

        Returns (mask bool[cap], n_evicted). Threshold-based on the LRU
        step stamp: ties at the threshold may evict slightly more than
        asked — correctness is unaffected (cold copies are current)."""
        cap = self.capacity
        live = state.table.occupied & (state.lanes[0] > 0)
        n_live = jnp.sum(live)
        big = jnp.iinfo(jnp.int32).max
        key = jnp.where(live, state.last_used, big)
        skey = jnp.sort(key)
        k = jnp.clip(n_live - keep, 0, cap - 1)
        thr = skey[jnp.maximum(k - 1, 0)]
        mask = live & (state.last_used <= thr) & (k > 0)
        return mask, jnp.sum(mask)

    def apply_evict(self, state: AggState, mask: jax.Array) -> AggState:
        """Reset evicted slots to init WITHOUT marking ckpt_dirty: the
        durable row (just flushed by this barrier's checkpoint) IS the
        cold copy — a dirty mark would overwrite it with zeros. Call only
        at a checkpoint barrier, AFTER the flush, BEFORE compact()."""
        init = self.init_state()
        lanes = tuple(
            jnp.where(mask, il, l) for l, il in zip(state.lanes, init.lanes))
        prev = tuple(
            jnp.where(mask, il, l)
            for l, il in zip(state.prev_lanes, init.lanes))
        return state.replace(lanes=lanes, prev_lanes=prev,
                             dirty=state.dirty & ~mask,
                             ckpt_dirty=state.ckpt_dirty & ~mask)

    def absorb(self, state: AggState, key_cols, stored_lanes, valid,
               str_ranks=None) -> AggState:
        """Fault evicted groups back in: merge each stored lane into the
        (possibly freshly re-created) slot with the lane's reduce op, and
        set prev_lanes to the stored value — the value downstream last saw
        — so the next flush emits an exact U-/U+ pair, not a duplicate
        insert. ``stored_lanes``: one array per lane, [n] rows aligned
        with ``key_cols``; ``valid``: bool[n]."""
        table, slots, _, ovf = ht_lookup_or_insert(
            state.table, key_cols, valid)
        idx = jnp.where(valid, slots, self.capacity)
        lanes = list(state.lanes)
        prev = list(state.prev_lanes)

        def merge(lane, stored, op, call=None):
            if call is not None and call.is_string_minmax:
                cur = call.pack_lane(lane, str_ranks)
                sv = call.pack_lane(stored, str_ranks)
                merged = cur.at[idx].min(sv, mode="drop") if op == "min" \
                    else cur.at[idx].max(sv, mode="drop")
                return call.unpack_lane(merged)
            if op == "add":
                return lane.at[idx].add(stored, mode="drop")
            if op == "min":
                return lane.at[idx].min(stored, mode="drop")
            return lane.at[idx].max(stored, mode="drop")

        lanes[0] = merge(lanes[0], stored_lanes[0], "add")
        prev[0] = prev[0].at[idx].set(stored_lanes[0], mode="drop")
        for call, ofs in zip(self.agg_calls, self.call_lane_ofs):
            for j, op in enumerate(call.reduce_ops()):
                lanes[ofs + j] = merge(lanes[ofs + j], stored_lanes[ofs + j],
                                       op, call)
                prev[ofs + j] = prev[ofs + j].at[idx].set(
                    stored_lanes[ofs + j], mode="drop")
        dirty = state.dirty.at[idx].set(True, mode="drop")
        ckpt_dirty = state.ckpt_dirty.at[idx].set(True, mode="drop")
        return state.replace(
            table=table, lanes=tuple(lanes), prev_lanes=tuple(prev),
            dirty=dirty, ckpt_dirty=ckpt_dirty,
            overflow=state.overflow | ovf)


def load_rows_into_state(core: AggCore, state: AggState, rows) -> AggState:
    """Recovery bulk-load: fold state-table rows (keys ++ raw lanes) into
    ``state`` in 1024-row batches. Shared by the solo executor reload
    (stream/hash_agg.py) and the sharded-fused re-shard loader
    (parallel/fused.py) so the durable row layout decodes in exactly one
    place. Callers fix up ``prev_lanes`` themselves (the recovered
    snapshot is the downstream baseline)."""
    import numpy as np

    rows = list(rows)
    nk = len(core.group_keys)
    bs = 1024
    for i in range(0, len(rows), bs):
        batch = rows[i:i + bs]
        n = len(batch)
        valid = jnp.arange(bs) < n
        key_cols = []
        for c in range(nk):
            vals = [r[c] for r in batch]
            mask = np.array([v is not None for v in vals]
                            + [False] * (bs - n))
            data = np.array(
                [v if v is not None else 0 for v in vals] + [0] * (bs - n),
                dtype=core.key_types[c].np_dtype)
            key_cols.append(Column(jnp.asarray(data), jnp.asarray(mask)))
        table, slots, _, ovf = ht_lookup_or_insert(
            state.table, key_cols, valid)
        if bool(ovf):
            raise RuntimeError(
                f"agg table overflow during recovery load (capacity "
                f"{core.capacity})")
        lanes = list(state.lanes)
        for j in range(len(lanes)):
            vals = np.array(
                [r[nk + j] for r in batch] + [0] * (bs - n),
                dtype=np.dtype(core.lane_dtypes[j]))
            lanes[j] = lanes[j].at[slots].set(jnp.asarray(vals),
                                              mode="drop")
        state = state.replace(table=table, lanes=tuple(lanes))
    return state
