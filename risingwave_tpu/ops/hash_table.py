"""Device-resident open-addressing hash table (key → slot index).

This is the TPU-native replacement for the reference's host hash maps behind
HashAgg / HashJoin (reference: JoinHashMap over StateTables,
src/stream/src/executor/managed_state/join/mod.rs:228-258, and the per-key
AggGroup cache, src/stream/src/executor/aggregation/agg_group.rs:159). Instead
of pointer-chasing per row, a whole chunk of keys is probed **in parallel**
with XLA-friendly control flow: a bounded ``lax.while_loop`` of vectorized
gather/compare/scatter rounds with conflict resolution by scatter-min claim.

The table only maps keys to stable slot indices; callers keep their own
value arrays ``[capacity, ...]`` indexed by slot (agg lanes, join buckets).
Capacity is static (power of two); load factor should stay ≲ 0.7 — the
executor sizes it and checks the returned overflow flag on barriers.

Intra-batch duplicate keys resolve to the SAME slot (identical probe
sequences; the scatter-min claim makes one row the inserting winner, the rest
match it on the following round), so a scatter-add over the returned slots is
an exact grouped reduction even with duplicates.

Null semantics: group keys compare SQL-GROUP-BY style, i.e. NULL == NULL.
Slots are never freed (dead groups keep their key; re-insertion of the same
key reuses the slot). A rebuild-on-barrier compaction can reclaim space later
without changing this API.
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from flax import struct

from ..common.chunk import Column
from ..common.hashing import hash_columns

MAX_PROBE_ROUNDS = 128


@struct.dataclass
class DeviceHashTable:
    key_data: tuple[jax.Array, ...]   # per key column: dtype[cap]
    key_mask: tuple[jax.Array, ...]   # per key column: bool[cap] (True=non-null)
    occupied: jax.Array               # bool[cap]

    @property
    def capacity(self) -> int:
        return self.occupied.shape[0]

    def num_occupied(self) -> jax.Array:
        return jnp.sum(self.occupied)


def ht_new(key_types: Sequence, capacity: int) -> DeviceHashTable:
    """``key_types``: DataTypes of the key columns. ``capacity``: power of 2."""
    assert capacity & (capacity - 1) == 0, "capacity must be a power of two"
    return DeviceHashTable(
        key_data=tuple(jnp.zeros(capacity, t.dtype) for t in key_types),
        key_mask=tuple(jnp.zeros(capacity, jnp.bool_) for _ in key_types),
        occupied=jnp.zeros(capacity, jnp.bool_),
    )


def _keys_equal_at(table: DeviceHashTable, cand: jax.Array,
                   datas: Sequence[jax.Array], masks: Sequence[jax.Array]) -> jax.Array:
    """Row-wise: does the key stored at slot ``cand`` equal each probe key?"""
    eq = jnp.ones(cand.shape, jnp.bool_)
    for td, tm, d, m in zip(table.key_data, table.key_mask, datas, masks):
        sd = td[cand]
        sm = tm[cand]
        col_eq = (sm & m & (sd == d)) | (~sm & ~m)  # NULL == NULL for grouping
        eq = eq & col_eq
    return eq


def _home_slot(key_cols: Sequence[Column], cap: int) -> jax.Array:
    """A key's first probe position: the HIGH word of its 64-bit hash.

    The low bits belong to the routing: ``common/hashing.vnode_of`` is the
    same hash modulo 256, and a mesh shard (or a fragment actor) holds only
    the keys of its contiguous vnode range. Indexing with the low bits gave
    such a shard 64 home slots in every 256 — its keys piled up in runs, a
    quarter-full table probed 79 slots deep and a 40 % full one ran past
    ``MAX_PROBE_ROUNDS`` into overflow (ISSUE 31, seen on the four-chip
    cell as barriers slowing 1.6 x inside one window). The high word shares
    no bit with the vnode, so a shard's table fills like any other."""
    h = hash_columns(key_cols) >> jnp.uint64(32)
    return (h & jnp.uint64(cap - 1)).astype(jnp.int32)


@jax.named_scope("table_probe")
def ht_lookup_or_insert(
    table: DeviceHashTable, key_cols: Sequence[Column], valid: jax.Array
):
    """Find-or-insert a batch of keys.

    Returns ``(table, slots, is_new, overflow)``:
      * ``slots`` int32[N]: slot per row (== capacity for invalid/overflow rows,
        safe to use with ``.at[slots].add(..., mode='drop')``),
      * ``is_new`` bool[N]: True for the single winning row that inserted a
        previously-absent key,
      * ``overflow`` bool: some valid row failed to find/claim a slot.
    """
    cap = table.capacity
    datas = [c.data for c in key_cols]
    masks = [c.mask for c in key_cols]
    n = valid.shape[0]
    h = _home_slot(key_cols, cap)

    def cond(state):
        _, _, _, done, _, _, it = state
        return jnp.any(~done) & (it < MAX_PROBE_ROUNDS)

    def body(state):
        occupied, key_data, key_mask, done, slot, is_new, it = state
        t = table.replace(occupied=occupied, key_data=key_data, key_mask=key_mask)
        probe = slot  # reuse: slot holds current probe offset for not-done rows
        cand = (h + probe) & (cap - 1)
        occ = occupied[cand]
        eq = occ & _keys_equal_at(t, cand, datas, masks)
        newly_found = ~done & eq
        # claim attempt on empty slots: winner = min row_id among rows
        # targeting the same empty slot, resolved by sorting (slot, row_id)
        # pairs on the CHUNK — O(n log n) on n rows, never O(capacity).
        # (A capacity-sized scatter-min claims array would memset the whole
        # table every probe round — at multi-million-slot capacities that
        # dominates the entire step.)
        want = ~done & ~occ
        cand_eff = jnp.where(want, cand, cap)
        order = jnp.argsort(cand_eff, stable=True)  # stable ⇒ min row_id first
        sorted_slot = cand_eff[order]
        first = jnp.concatenate([
            jnp.ones(1, jnp.bool_), sorted_slot[1:] != sorted_slot[:-1]])
        winner_sorted = first & (sorted_slot < cap)
        winner = jnp.zeros(n, jnp.bool_).at[order].set(winner_sorted)
        widx = jnp.where(winner, cand, cap)
        occupied = occupied.at[widx].set(True, mode="drop")
        key_data = tuple(
            kd.at[widx].set(d, mode="drop") for kd, d in zip(key_data, datas)
        )
        key_mask = tuple(
            km.at[widx].set(m, mode="drop") for km, m in zip(key_mask, masks)
        )
        settled = newly_found | winner
        # advance probe offset on true collision (occupied, different key);
        # settled and done rows never advance, freezing their final offset
        advance = ~done & occ & ~eq
        slot = probe + advance.astype(jnp.int32)
        done2 = done | settled
        is_new = is_new | winner
        return occupied, key_data, key_mask, done2, slot, is_new, it + 1

    init = (
        table.occupied, table.key_data, table.key_mask,
        ~valid, jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.bool_), jnp.int32(0),
    )
    occupied, key_data, key_mask, done, offset, is_new, _ = jax.lax.while_loop(
        cond, body, init
    )
    settled = done & valid
    slots = jnp.where(settled, (h + offset) & (cap - 1), cap).astype(jnp.int32)
    overflow = jnp.any(valid & ~done)
    new_table = table.replace(
        occupied=occupied, key_data=key_data, key_mask=key_mask
    )
    return new_table, slots, is_new & valid, overflow


def ht_lookup(table: DeviceHashTable, key_cols: Sequence[Column], valid: jax.Array):
    """Read-only probe. Returns ``(slots, found)``; slots == capacity if absent."""
    cap = table.capacity
    datas = [c.data for c in key_cols]
    masks = [c.mask for c in key_cols]
    n = valid.shape[0]
    h = _home_slot(key_cols, cap)

    def cond(state):
        done, _, _, it = state
        return jnp.any(~done) & (it < MAX_PROBE_ROUNDS)

    def body(state):
        done, offset, found, it = state
        cand = (h + offset) & (cap - 1)
        occ = table.occupied[cand]
        eq = occ & _keys_equal_at(table, cand, datas, masks)
        hit = ~done & eq
        miss = ~done & ~occ          # empty slot ⇒ key absent (no tombstones)
        done2 = done | hit | miss
        found = found | hit
        offset = offset + (~done2).astype(jnp.int32)
        return done2, offset, found, it + 1

    init = (~valid, jnp.zeros(n, jnp.int32), jnp.zeros(n, jnp.bool_), jnp.int32(0))
    done, offset, found, _ = jax.lax.while_loop(cond, body, init)
    slots = jnp.where(found, (h + offset) & (cap - 1), cap).astype(jnp.int32)
    return slots, found


def scatter_reduce(target: jax.Array, slots: jax.Array, contrib: jax.Array, op: str) -> jax.Array:
    """Grouped reduction into per-slot state: target[slot] ⊕= contrib.

    Out-of-range slots (capacity sentinel) are dropped — this is how invalid
    rows are masked out. Duplicate slots within the batch combine exactly.
    """
    if op == "add":
        return target.at[slots].add(contrib.astype(target.dtype), mode="drop")
    if op == "min":
        return target.at[slots].min(contrib.astype(target.dtype), mode="drop")
    if op == "max":
        return target.at[slots].max(contrib.astype(target.dtype), mode="drop")
    raise ValueError(op)
