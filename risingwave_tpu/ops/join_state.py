"""Device-resident streaming hash-join state + pure join step.

TPU-native counterpart of the reference's HashJoinExecutor state machinery
(reference: src/stream/src/executor/hash_join.rs:227-270, probe/build
``eq_join_oneside`` :972; JoinHashMap = row + degree StateTables,
src/stream/src/executor/managed_state/join/mod.rs:228-258). Deliberately NOT
an LRU row-cache probed row-by-row: each side keeps ALL its rows
device-resident in a bucketed arena —

  * a DeviceHashTable maps join key -> bucket (ops/hash_table.py),
  * each bucket holds up to W rows (static bucket width) in struct-of-arrays
    ``[capacity, W]`` buffers, with per-row occupancy, tombstones, and a
    **degree** = number of condition-passing matches on the opposite side
    (the reference's degree table) driving outer/semi/anti emission with no
    re-probing.

One input chunk is joined in ONE jitted step: the opposite side is probed for
all rows at once (vectorized gathers), the serial-order effects the reference
gets from row-at-a-time processing (degree transitions when several same-key
rows arrive in one chunk) are recovered with rank/total **matmuls** over the
key-equality matrix — MXU work instead of scalar loops — and outputs land in
a fixed-capacity ``[N, 2W+1]`` lane grid (lanes 2w/2w+1 = match w's primary /
update-pair row; lane 2W = the null-padded or self row) that flattens into a
single visibility-masked chunk for downstream compaction
(common/chunk.py:gather_units_window).

A chunk is processed as two vectorized sub-passes — deletes first, then
inserts — preserving the one ordering streaming SQL relies on inside a chunk
(U- before U+ of the same key). Insert-then-delete of the same row inside one
chunk would be mis-ordered; that pattern trips the ``inconsistent`` flag
(checked on barriers) instead of silently corrupting state.

Join-key NULLs never match (SQL semantics), unlike GROUP BY: rows with a null
key are stored (for deletes / outer emission) but masked out of probing.

**Side layouts.** Each side has its own geometry (``SideLayout``). The
bucket arena above suits a side with few rows a key. A side whose key
holds thousands of rows (NEXmark q5's window key) takes the **fan-out
arena** instead (``FanoutSideState``): one flat ``[rows]`` array a
column, each row found by a hash table on the side's row key (the
state table's key), so an insert or a delete of a row costs one table
probe whatever its key holds. A probe from the other side scans the
arena's key column against blocks of ``FANOUT_BLOCK`` live probe rows (one
dense masked compare, the condition fused in), compacts the passing
pairs by count -> prefix sum -> gather into a pair buffer of the chunk's
own static size, and emits from it; degrees and the degree transitions
come from per-row counts, never from an ``[N, W]`` grid. The bucket
arena probed by any side costs ``[N, W]`` of ITS width.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import struct

from ..common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, Column,
    StreamChunk,
)
from ..common.types import Schema
from .ckpt_delta import delta_window, dirty_slots
from .hash_table import DeviceHashTable, ht_lookup, ht_lookup_or_insert, ht_new


#: graveyard slots a side (a smaller arena is its own bound): refills of a
#: tombstoned lane by another state-table key between two checkpoints;
#: past it the insert reports ``lane_overflow`` and the bucket width grows
GRAVE_ROWS = 1 << 13
#: live probe rows a fan-out scan compares against the arena at once
FANOUT_BLOCK = 8


class JoinType(enum.Enum):
    """reference: JoinTypePrimitive consts, src/stream/src/executor/hash_join.rs:83-100."""

    INNER = "inner"
    LEFT_OUTER = "left_outer"
    RIGHT_OUTER = "right_outer"
    FULL_OUTER = "full_outer"
    LEFT_SEMI = "left_semi"
    LEFT_ANTI = "left_anti"
    RIGHT_SEMI = "right_semi"
    RIGHT_ANTI = "right_anti"

    @property
    def preserves_left(self) -> bool:
        return self in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER)

    @property
    def preserves_right(self) -> bool:
        return self in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER)

    @property
    def semi_anti_side(self) -> Optional[str]:
        if self in (JoinType.LEFT_SEMI, JoinType.LEFT_ANTI):
            return "left"
        if self in (JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI):
            return "right"
        return None

    @property
    def is_anti(self) -> bool:
        return self in (JoinType.LEFT_ANTI, JoinType.RIGHT_ANTI)


@struct.dataclass
class JoinSideState:
    ht: DeviceHashTable                 # join key -> bucket
    row_data: tuple[jax.Array, ...]     # per column: dtype[cap, W]
    row_mask: tuple[jax.Array, ...]     # per column: bool[cap, W]
    occupied: jax.Array                 # bool[cap, W]
    tomb: jax.Array                     # bool[cap, W] — deleted since last ckpt
    degree: jax.Array                   # int32[cap, W] — opposite-side matches
    ckpt_dirty: jax.Array               # bool[cap, W] — changed since last ckpt
    # rows whose tombstoned lane was refilled by a row of ANOTHER
    # state-table key since the last checkpoint: the durable tier still owes
    # their delete (``grave_rows`` slots, the first ``grave_n`` in use)
    grave_data: tuple[jax.Array, ...]   # per column: dtype[grave_rows]
    grave_mask: tuple[jax.Array, ...]   # per column: bool[grave_rows]
    grave_n: jax.Array                  # int32 scalar
    lru: jax.Array                      # int32[cap] — key's last-touch step
    ht_overflow: jax.Array              # bool scalar, sticky: key table full
    lane_overflow: jax.Array            # bool scalar, sticky: bucket width full
    inconsistent: jax.Array             # bool scalar, sticky


@struct.dataclass
class FanoutSideState:
    """A side whose key may hold thousands of rows: ``[rows]`` per column.
    ``ht`` maps the side's row key to its slot, and the slot IS the row,
    so an insert or a delete finds its row by one table probe. A deleted
    row keeps its slot (and its values, for the durable delete) until a
    rehash drops it; the same row key inserted again takes it back."""
    ht: DeviceHashTable                 # row key -> row
    row_data: tuple[jax.Array, ...]     # per column: dtype[rows]
    row_mask: tuple[jax.Array, ...]     # per column: bool[rows]
    occupied: jax.Array                 # bool[rows]
    tomb: jax.Array                     # bool[rows] — deleted since last ckpt
    degree: jax.Array                   # int32[rows] — opposite-side matches
    ckpt_dirty: jax.Array               # bool[rows] — changed since last ckpt
    # what the last step's probes of this side did: live probe rows,
    # candidate rows read (rows of their keys), pairs passed
    probe_counts: jax.Array             # int64[3]
    ht_overflow: jax.Array              # bool scalar, sticky: rows full
    lane_overflow: jax.Array            # bool scalar, sticky: pair buffer full
    inconsistent: jax.Array             # bool scalar, sticky


@struct.dataclass
class JoinState:
    left: JoinSideState | FanoutSideState
    right: JoinSideState | FanoutSideState


@dataclasses.dataclass(frozen=True)
class SideLayout:
    """One side's device geometry. Bucket arena (``row_key`` None):
    ``capacity`` key slots of ``width`` lanes. Fan-out arena: ``capacity``
    rows found by ``row_key`` (the columns that identify a row), and
    ``width`` pair slots per row of a chunk that probes the side."""
    capacity: int
    width: int = 1
    row_key: Optional[tuple] = None

    @property
    def fanout(self) -> bool:
        return self.row_key is not None

    @property
    def cells(self) -> int:
        return self.capacity if self.fanout else self.capacity * self.width


def _other(side: str) -> str:
    return "right" if side == "left" else "left"


class JoinCore:
    """Static config + pure (state, chunk) -> (state, out) step for one
    streaming hash join. Shardable: runs unchanged under shard_map with
    vnode-partitioned inputs (both sides shuffled by join key)."""

    def __init__(
        self,
        left_schema: Schema,
        right_schema: Schema,
        left_keys: Sequence[int],
        right_keys: Sequence[int],
        join_type: JoinType,
        condition=None,
        key_capacity: int = 1 << 13,
        bucket_width: int = 16,
        state_pks: tuple = (None, None),
        layouts: Optional[tuple] = None,
    ):
        """``state_pks``: per side, the columns of the durable state
        table's key (``()``: the side has no durable tier). An insert may
        take a lane tombstoned since the last checkpoint; where the old
        row's state-table key differs from the new row's, the old row is
        kept in the side's graveyard for the checkpoint to delete. ``None``
        (the key is not known here) buries every such row: a delete too
        many is harmless, deletes are staged before puts.

        ``layouts``: per side a ``SideLayout``; without it both sides are
        bucket arenas of ``key_capacity`` x ``bucket_width``."""
        self.left_schema = left_schema
        self.right_schema = right_schema
        self.left_keys = tuple(left_keys)
        self.right_keys = tuple(right_keys)
        self.join_type = join_type
        self.condition = condition
        if layouts is None:
            layouts = (SideLayout(key_capacity, bucket_width),) * 2
        self.layout = {"left": layouts[0], "right": layouts[1]}
        self.state_pks = {"left": state_pks[0], "right": state_pks[1]}
        lkt = tuple(left_schema[i].type for i in self.left_keys)
        rkt = tuple(right_schema[i].type for i in self.right_keys)
        assert tuple(t.dtype for t in lkt) == tuple(t.dtype for t in rkt), (
            "equi-join key physical types must match (planner inserts casts)")
        self.key_types = lkt
        sa = join_type.semi_anti_side
        if sa == "left":
            self.out_schema = left_schema
        elif sa == "right":
            self.out_schema = right_schema
        else:
            self.out_schema = left_schema.concat(right_schema)

    @property
    def capacity(self) -> int:
        """The bucket arenas' key capacity (the larger, where they differ)."""
        return max((lay.capacity for lay in self.layout.values()
                    if not lay.fanout), default=0)

    @property
    def W(self) -> int:
        """The bucket arenas' width (the larger, where they differ)."""
        return max((lay.width for lay in self.layout.values()
                    if not lay.fanout), default=0)

    def grave_rows(self, side: str) -> int:
        return min(self.layout[side].cells, GRAVE_ROWS)

    def schema_of(self, side: str) -> Schema:
        return self.left_schema if side == "left" else self.right_schema

    def keys_of(self, side: str) -> tuple:
        return self.left_keys if side == "left" else self.right_keys

    # -- state ----------------------------------------------------------------

    def _new_side(self, side: str):
        lay, schema = self.layout[side], self.schema_of(side)
        if lay.fanout:
            return _new_fanout(schema, lay)
        cap, W = lay.capacity, lay.width
        key_types = tuple(schema[i].type for i in self.keys_of(side))
        grave_rows = self.grave_rows(side)
        return JoinSideState(
            ht=ht_new(key_types, cap),
            row_data=tuple(jnp.zeros((cap, W), f.type.dtype) for f in schema),
            row_mask=tuple(jnp.zeros((cap, W), jnp.bool_) for _ in schema),
            occupied=jnp.zeros((cap, W), jnp.bool_),
            tomb=jnp.zeros((cap, W), jnp.bool_),
            degree=jnp.zeros((cap, W), jnp.int32),
            ckpt_dirty=jnp.zeros((cap, W), jnp.bool_),
            grave_data=tuple(jnp.zeros(grave_rows, f.type.dtype)
                             for f in schema),
            grave_mask=tuple(jnp.zeros(grave_rows, jnp.bool_)
                             for _ in schema),
            grave_n=jnp.zeros((), jnp.int32),
            lru=jnp.zeros(cap, jnp.int32),
            ht_overflow=jnp.zeros((), jnp.bool_),
            lane_overflow=jnp.zeros((), jnp.bool_),
            inconsistent=jnp.zeros((), jnp.bool_),
        )

    def init_state(self) -> JoinState:
        return JoinState(left=self._new_side("left"),
                         right=self._new_side("right"))

    # -- the step --------------------------------------------------------------

    def apply_chunk(self, state: JoinState, chunk: StreamChunk, *, side: str,
                    step=None):
        """Join one chunk arriving on ``side``; returns (state, big_chunk).

        ``big_chunk`` is mostly invisible: the delete pass's emission then
        the insert pass's (``_empty_out``'s shape); compact it with
        gather_units_window before sending downstream.

        ``step``: optional int32 LRU stamp — when set, both the own-side
        key slot and every probed opposite-side slot are touched, so the
        two sides' stamps for one key value stay in sync (the invariant
        cold-tier eviction relies on to evict a key from BOTH arenas).
        A fan-out side keeps no stamps."""
        is_del = chunk.vis & (
            (chunk.ops == OP_DELETE) | (chunk.ops == OP_UPDATE_DELETE))
        is_ins = chunk.vis & (
            (chunk.ops == OP_INSERT) | (chunk.ops == OP_UPDATE_INSERT))
        for s in ("left", "right"):
            if self.layout[s].fanout:        # the counts are this step's
                st = getattr(state, s)
                state = state.replace(**{s: st.replace(
                    probe_counts=jnp.zeros(3, jnp.int64))})

        def run_del(st):
            return self._pass(st, chunk, is_del, False, side, step)

        def run_ins(st):
            return self._pass(st, chunk, is_ins, True, side, step)

        def skip(st):
            return st, self._empty_out(chunk.capacity, side)

        state, out_d = jax.lax.cond(jnp.any(is_del), run_del, skip, state)
        state, out_i = jax.lax.cond(jnp.any(is_ins), run_ins, skip, state)
        ops = jnp.concatenate([out_d[0].reshape(-1), out_i[0].reshape(-1)])
        vis = jnp.concatenate([out_d[1].reshape(-1), out_i[1].reshape(-1)])
        cols = tuple(
            Column(jnp.concatenate([d0.reshape(-1), d1.reshape(-1)]),
                   jnp.concatenate([m0.reshape(-1), m1.reshape(-1)]))
            for (d0, m0), (d1, m1) in zip(out_d[2], out_i[2])
        )
        return state, StreamChunk(ops, vis, cols)

    def emit_counts(self, big: StreamChunk, side: str) -> tuple:
        """``(rows_out, null_padded_out, transitions, matched, unmatched)``
        of one step's emission (a chunk that arrived on ``side``), as int64
        scalars: the visible rows; those on the ``pself`` lane (the input
        row NULL-padded, or a semi / anti join's own row); and the degree
        transitions of the opposite side that the step emitted — the
        second rows of an outer join's update pairs (lane 1 of a match), a
        semi / anti join's opposite rows (lane 0; its own-side passes leave
        them empty) — in all and by direction: ``matched`` 0 -> 1 (the
        insert pass, the emission's second half), ``unmatched`` 1 -> 0 (the
        delete pass, its first). A match is lanes 2w / 2w+1 of the bucket
        grid, or one slot of a fan-out probe's pair buffer."""
        vis = big.vis.reshape(2, -1)                 # [delete | insert] pass
        first = 1 if self.join_type.semi_anti_side is None else 0
        lay = self.layout[_other(side)]
        if lay.fanout:
            n_self = vis.shape[1] // (2 * lay.width + 1)
            lanes = vis[:, :-n_self].reshape(2, -1, 2)[:, :, first]
            own = vis[:, -n_self:]
        else:
            W = lay.width
            grid = vis.reshape(2, -1, 2 * W + 1)
            lanes = grid[:, :, first:2 * W:2]
            own = grid[:, :, 2 * W]
        unmatched, matched = jnp.sum(lanes.reshape(2, -1), axis=1,
                                     dtype=jnp.int64)
        return (jnp.sum(vis, dtype=jnp.int64), jnp.sum(own, dtype=jnp.int64),
                matched + unmatched, matched, unmatched)

    # -- internals -------------------------------------------------------------

    def _empty_out(self, N: int, side: str):
        lay = self.layout[_other(side)]
        shape = ((2 * N * lay.width + N,) if lay.fanout
                 else (N, 2 * lay.width + 1))
        return (
            jnp.zeros(shape, jnp.int8),
            jnp.zeros(shape, jnp.bool_),
            tuple(
                (jnp.zeros(shape, f.type.dtype), jnp.zeros(shape, jnp.bool_))
                for f in self.out_schema
            ),
        )

    def _pair_condition(self, a_cols, b_datas, b_masks, side: str):
        """The non-equi condition on aligned flat pairs: ``a_cols`` the
        input side's columns and ``b_*`` the opposite side's, one entry a
        pair -> bool[pairs]."""
        n = b_datas[0].shape[0]
        b_cols = [Column(d, m) for d, m in zip(b_datas, b_masks)]
        pair = list(a_cols) + b_cols if side == "left" else b_cols + list(a_cols)
        pseudo = StreamChunk(
            jnp.zeros(n, jnp.int8), jnp.ones(n, jnp.bool_), tuple(pair))
        res = self.condition.eval(pseudo)
        return res.data & res.mask

    def _eval_condition(self, chunk, b_datas, b_masks, side: str):
        """Evaluate the non-equi condition on all candidate pairs -> bool[N, W]."""
        N, W = chunk.capacity, b_datas[0].shape[1]
        a_cols = [
            Column(jnp.repeat(c.data, W), jnp.repeat(c.mask, W))
            for c in chunk.columns
        ]
        return self._pair_condition(
            a_cols, [d.reshape(-1) for d in b_datas],
            [m.reshape(-1) for m in b_masks], side).reshape(N, W)

    def _pass(self, state: JoinState, chunk: StreamChunk, sel: jax.Array,
              is_insert: bool, side: str, step=None):
        N = chunk.capacity
        A = state.left if side == "left" else state.right
        B = state.right if side == "left" else state.left
        a_key_idx = self.left_keys if side == "left" else self.right_keys
        a_key_cols = [chunk.columns[i] for i in a_key_idx]

        has_null_key = jnp.zeros(N, jnp.bool_)
        for c in a_key_cols:
            has_null_key = has_null_key | ~c.mask
        match_ok = sel & ~has_null_key

        fan_b = self.layout[_other(side)].fanout
        with jax.named_scope("join_fanout_scan" if fan_b else "join_probe"):
            if fan_b:
                B, probed = self._probe_fanout(B, chunk, a_key_cols, match_ok,
                                               is_insert, side)
            else:
                B, probed = self._probe(B, chunk, a_key_cols, match_ok,
                                        is_insert, side, step)
        with jax.named_scope("join_insert" if is_insert else "join_delete"):
            if self.layout[side].fanout:
                A = self._update_own_fanout(A, chunk, sel, is_insert,
                                            probed[1], side)
            else:
                A = self._update_own(A, chunk, a_key_cols, sel, is_insert,
                                     probed[1], step, self.state_pks[side],
                                     side)
        state = (state.replace(left=A, right=B) if side == "left"
                 else state.replace(left=B, right=A))
        with jax.named_scope("join_emit"):
            if fan_b:
                out = self._emit_pairs(chunk, sel, is_insert, side, *probed)
            else:
                out = self._emit(chunk, sel, is_insert, side, *probed)
        return state, out

    def _probe(self, B: JoinSideState, chunk: StreamChunk, a_key_cols,
               match_ok, is_insert: bool, side: str, step):
        """Probe the opposite side for all rows at once and maintain its
        degrees; returns it and what ``_emit`` takes of the probe."""
        lay = self.layout[_other(side)]
        cap, W = lay.capacity, lay.width
        b_slot, b_found = ht_lookup(B.ht, a_key_cols, match_ok)
        bs = jnp.where(b_found, b_slot, 0)
        occ_b = B.occupied[bs] & b_found[:, None]                      # [N, W]
        b_datas = [rd[bs] for rd in B.row_data]                        # [N, W]
        b_masks = [rm[bs] & occ_b for rm in B.row_mask]
        matches = occ_b
        if self.condition is not None:
            matches = matches & self._eval_condition(chunk, b_datas, b_masks, side)
        c_cnt = jnp.sum(matches, axis=1).astype(jnp.int32)             # [N]

        # ---- rank/total of same-key rows within this pass:
        # r[i,w] = |{j<i: key_j == key_i, (j,w) matches}|, t = same over all j.
        # On TPU the fused Pallas kernel generates the [N,N] equality
        # tiles in VMEM and feeds the MXU directly (ops/pallas_rank.py);
        # elsewhere the jnp matmul formulation runs.
        from .pallas_rank import rank_totals
        ident = jnp.where(b_found, b_slot, -1)
        r, t = rank_totals(ident, matches)
        d0 = B.degree[bs]                                              # [N, W]

        # ---- opposite-side degree maintenance (reference join/mod.rs degrees)
        lane_w = jnp.arange(W, dtype=jnp.int32)[None, :]
        g = jnp.where(matches, bs[:, None] * W + lane_w, cap * W).reshape(-1)
        delta = jnp.where(matches, 1 if is_insert else -1, 0).astype(jnp.int32)
        # degrees are rebuilt on recovery, not persisted — no ckpt_dirty here
        B = B.replace(
            degree=B.degree.reshape(-1).at[g].add(delta.reshape(-1), mode="drop")
                    .reshape(cap, W),
        )
        if step is not None:
            B = B.replace(lru=B.lru.at[jnp.where(b_found, b_slot, cap)]
                          .max(step, mode="drop"))
        return B, (matches, c_cnt, r, t, d0, b_datas, b_masks)

    def _probe_fanout(self, B: FanoutSideState, chunk: StreamChunk,
                      a_key_cols, match_ok, is_insert: bool, side: str):
        """Probe a fan-out side: the live probe rows, ``FANOUT_BLOCK`` at a
        time, against the arena's key column — one dense masked compare
        with the condition fused in (``[block, rows]``, reduced at once to
        per-row and per-probe counts). The arena rows with a passing pair
        are compacted (prefix count + binary search), the pairs among them
        laid out probe row by probe row into the pair buffer (``width``
        slots a chunk row), each with its degree transition: insert — the
        row's degree before the pass plus the earlier probe rows' matches
        of it is 0; delete — this match takes it to 0. The degrees move by
        the per-row counts. Returns the side and ``(pairs, c_cnt)``; a
        pass whose pairs outrun the buffer sets the side's
        ``lane_overflow`` (the executor widens it and runs the chunk again)."""
        lay = self.layout[_other(side)]
        N, R, P = chunk.capacity, lay.capacity, FANOUT_BLOCK
        PB = N * lay.width
        b_keys = self.keys_of(_other(side))
        n_live = jnp.sum(match_ok, dtype=jnp.int32)
        live = jnp.nonzero(match_ok, size=N + P,
                           fill_value=N)[0].astype(jnp.int32)
        d0 = B.degree
        b_key_d = [B.row_data[i] for i in b_keys]
        b_key_m = [B.row_mask[i] & B.occupied for i in b_keys]

        def block_matches(rows, b_rows):
            """bool[P, X]: probe rows ``rows`` [P] (N = none) against the
            arena rows ``b_rows`` (None: all of them)."""
            ok = rows < N
            at = jnp.minimum(rows, N - 1)
            take = (lambda x: x) if b_rows is None else (lambda x: x[b_rows])
            m = ok[:, None]
            for kd, km, c in zip(b_key_d, b_key_m, a_key_cols):
                m = m & take(km)[None, :] & (take(kd)[None, :]
                                             == c.data[at][:, None])
            keq = m
            if self.condition is not None:
                X = m.shape[1]
                a_cols = [Column(jnp.broadcast_to(c.data[at][:, None],
                                                  (P, X)).reshape(-1),
                                 jnp.broadcast_to(c.mask[at][:, None],
                                                  (P, X)).reshape(-1))
                          for c in chunk.columns]
                b_d = [jnp.broadcast_to(take(d)[None, :], (P, X)).reshape(-1)
                       for d in B.row_data]
                b_m = [jnp.broadcast_to(take(mk)[None, :], (P, X)).reshape(-1)
                       for mk in B.row_mask]
                m = m & self._pair_condition(a_cols, b_d, b_m,
                                             side).reshape(P, X)
            return keq, m

        def body(carry):
            b, seen, c_cnt, p_row, p_b, p_trans, total, cand = carry
            rows = jax.lax.dynamic_slice(live, (b * P,), (P,))
            keq, m = block_matches(rows, None)
            per_row = jnp.sum(m, axis=0, dtype=jnp.int32)             # [R]
            c_cnt = c_cnt.at[rows].set(
                jnp.sum(m, axis=1, dtype=jnp.int32), mode="drop")
            cand = cand + jnp.sum(keq, dtype=jnp.int64)
            # the arena rows with a passing pair, in row order
            hit, hit_ok = dirty_slots(
                jnp.cumsum(per_row > 0, dtype=jnp.int32), jnp.int32(0), PB)
            _, ms = block_matches(rows, hit)
            ms = ms & hit_ok[None, :]                                   # [P, PB]
            r = seen[hit][None, :] + jnp.cumsum(ms, axis=0, dtype=jnp.int32) \
                - ms
            trans = ms & ((d0[hit][None, :] + r == 0) if is_insert
                          else (d0[hit][None, :] - r - 1 == 0))
            # this block's pairs, probe row by probe row, after the
            # earlier blocks' in the buffer
            flat = ms.reshape(-1)
            rank = jnp.cumsum(flat, dtype=jnp.int32)
            q = jnp.arange(PB, dtype=jnp.int32) - total
            mine = (q >= 0) & (q < rank[-1])
            at = jnp.searchsorted(rank, q + 1, side="left").astype(jnp.int32)
            at = jnp.where(mine, at, 0)
            p_row = jnp.where(mine, rows[at // PB], p_row)
            p_b = jnp.where(mine, hit[at % PB], p_b)
            p_trans = jnp.where(mine, trans.reshape(-1)[at], p_trans)
            # the true count: an overflow shows even where more arena
            # rows than the buffer holds had a pair
            return (b + 1, seen + per_row, c_cnt, p_row, p_b, p_trans,
                    total + jnp.sum(per_row, dtype=jnp.int32), cand)

        init = (jnp.int32(0), jnp.zeros(R, jnp.int32), jnp.zeros(N, jnp.int32),
                jnp.zeros(PB, jnp.int32), jnp.zeros(PB, jnp.int32),
                jnp.zeros(PB, jnp.bool_), jnp.int32(0), jnp.int64(0))
        _, seen, c_cnt, p_row, p_b, p_trans, total, cand = jax.lax.while_loop(
            lambda c: c[0] * P < n_live, body, init)
        # degrees are rebuilt on recovery, not persisted — no ckpt_dirty here
        B = B.replace(
            degree=d0 + (seen if is_insert else -seen),
            probe_counts=B.probe_counts + jnp.stack(
                [n_live.astype(jnp.int64), cand, total.astype(jnp.int64)]),
            lane_overflow=B.lane_overflow | (total > PB))
        n_pairs = jnp.minimum(total, PB)
        return B, ((p_row, p_b, p_trans, n_pairs,
                    tuple(d[p_b] for d in B.row_data),
                    tuple(m[p_b] for m in B.row_mask)), c_cnt)

    def _update_own(self, A: JoinSideState, chunk: StreamChunk, a_key_cols,
                    sel, is_insert: bool, c_cnt, step,
                    state_pk=None, side: str = "left") -> JoinSideState:
        """The input side's arena: place the inserted rows (each with its
        degree ``c_cnt``, the matches the probe found), or tombstone the
        deleted ones. An insert takes a free lane of its key's bucket
        and, when those run out, a lane tombstoned since the last
        checkpoint (the U+ of an update pair lands where its U- was): the
        lane is then BOTH occupied and tombstoned, which the checkpoint
        reads as a put."""
        lay = self.layout[side]
        cap, W = lay.capacity, lay.width
        N = chunk.capacity
        idx = jnp.arange(N)
        if is_insert:
            a_ht, a_slot, _, ht_ovf = ht_lookup_or_insert(A.ht, a_key_cols, sel)
            a_ok = sel & (a_slot < cap)
            as_ = jnp.where(a_ok, a_slot, 0)
            aident = jnp.where(a_ok, a_slot, -1)
            alower = ((aident[:, None] == aident[None, :])
                      & (aident >= 0)[:, None] & (idx[None, :] < idx[:, None]))
            a_rank = jnp.sum(alower, axis=1).astype(jnp.int32)
            # one gather for both marks: bit 0 occupied, bit 1 tombstoned
            marks = (A.occupied.astype(jnp.int8)
                     | (A.tomb.astype(jnp.int8) << 1))[as_]            # [N, W]
            dead = marks == 2
            free = marks == 0
            want = (a_rank + 1)[:, None]
            n_free = jnp.sum(free, axis=1, dtype=jnp.int32)[:, None]
            hit_dead = (jnp.cumsum(dead, axis=1) == want - n_free) & dead
            hit = ((jnp.cumsum(free, axis=1) == want) & free) | hit_dead
            lane = jnp.argmax(hit, axis=1).astype(jnp.int32)
            lane_ok = jnp.any(hit, axis=1) & a_ok
            f = jnp.where(lane_ok, as_ * W + lane, cap * W)
            A, grave_full = self._bury_refilled(
                A, chunk, f, lane_ok & jnp.any(hit_dead, axis=1), state_pk)
            A = A.replace(
                ht=a_ht,
                occupied=A.occupied.reshape(-1).at[f].set(True, mode="drop")
                          .reshape(cap, W),
                row_data=tuple(
                    rd.reshape(-1).at[f].set(c.data, mode="drop").reshape(cap, W)
                    for rd, c in zip(A.row_data, chunk.columns)),
                row_mask=tuple(
                    rm.reshape(-1).at[f].set(c.mask, mode="drop").reshape(cap, W)
                    for rm, c in zip(A.row_mask, chunk.columns)),
                degree=A.degree.reshape(-1).at[f].set(c_cnt, mode="drop")
                        .reshape(cap, W),
                ckpt_dirty=A.ckpt_dirty.reshape(-1).at[f].set(True, mode="drop")
                            .reshape(cap, W),
                ht_overflow=A.ht_overflow | ht_ovf
                            | jnp.any(sel & (a_slot >= cap)),
                lane_overflow=A.lane_overflow | jnp.any(a_ok & ~lane_ok)
                              | grave_full,
            )
            if step is not None:
                A = A.replace(lru=A.lru.at[jnp.where(a_ok, a_slot, cap)]
                              .max(step, mode="drop"))
        else:
            a_slot, a_found = ht_lookup(A.ht, a_key_cols, sel)
            as_ = jnp.where(a_found, a_slot, 0)
            delmatch = A.occupied[as_] & a_found[:, None]
            for rd, rm, c in zip(A.row_data, A.row_mask, chunk.columns):
                srd, srm = rd[as_], rm[as_]
                delmatch = delmatch & (
                    (srm & c.mask[:, None] & (srd == c.data[:, None]))
                    | (~srm & ~c.mask[:, None]))
            # rank among value-identical delete rows -> distinct lanes
            roweq = sel[:, None] & sel[None, :]
            for c in chunk.columns:
                roweq = roweq & (
                    (c.mask[:, None] & c.mask[None, :]
                     & (c.data[:, None] == c.data[None, :]))
                    | (~c.mask[:, None] & ~c.mask[None, :]))
            drank = jnp.sum(roweq & (idx[None, :] < idx[:, None]), axis=1)
            cs = jnp.cumsum(delmatch, axis=1)
            hit = (cs == (drank + 1)[:, None]) & delmatch
            lane = jnp.argmax(hit, axis=1).astype(jnp.int32)
            lane_ok = jnp.any(hit, axis=1)
            f = jnp.where(lane_ok, as_ * W + lane, cap * W)
            # values stay in row_data for the durable-tier delete at checkpoint
            A = A.replace(
                occupied=A.occupied.reshape(-1).at[f].set(False, mode="drop")
                          .reshape(cap, W),
                tomb=A.tomb.reshape(-1).at[f].set(True, mode="drop")
                      .reshape(cap, W),
                ckpt_dirty=A.ckpt_dirty.reshape(-1).at[f].set(True, mode="drop")
                            .reshape(cap, W),
                inconsistent=A.inconsistent | jnp.any(sel & ~lane_ok),
            )
            if step is not None:
                A = A.replace(lru=A.lru.at[jnp.where(a_found, a_slot, cap)]
                              .max(step, mode="drop"))
        return A

    def _update_own_fanout(self, A: FanoutSideState, chunk: StreamChunk, sel,
                           is_insert: bool, c_cnt, side: str):
        """The input side's fan-out arena: a row is found by its row key
        (one table probe), whatever its join key holds. An insert takes
        the row's slot (a row key inserted again, such as the U+ of an
        update pair, takes back the slot of its U-: occupied and
        tombstoned, which the checkpoint reads as a put) and its degree
        ``c_cnt``; a delete tombstones it. An insert of a live row or a
        delete of an absent one is ``inconsistent``."""
        R = self.layout[side].capacity
        key_cols = [chunk.columns[i] for i in self.layout[side].row_key]
        if is_insert:
            ht, slot, _, ht_ovf = ht_lookup_or_insert(A.ht, key_cols, sel)
            ok = sel & (slot < R)
            f = jnp.where(ok, slot, R)
            was_live = A.occupied[jnp.minimum(slot, R - 1)] & ok
            return A.replace(
                ht=ht,
                occupied=A.occupied.at[f].set(True, mode="drop"),
                row_data=tuple(rd.at[f].set(c.data, mode="drop")
                               for rd, c in zip(A.row_data, chunk.columns)),
                row_mask=tuple(rm.at[f].set(c.mask, mode="drop")
                               for rm, c in zip(A.row_mask, chunk.columns)),
                degree=A.degree.at[f].set(c_cnt, mode="drop"),
                ckpt_dirty=A.ckpt_dirty.at[f].set(True, mode="drop"),
                ht_overflow=A.ht_overflow | ht_ovf | jnp.any(sel & ~ok),
                inconsistent=A.inconsistent | jnp.any(was_live))
        slot, found = ht_lookup(A.ht, key_cols, sel)
        ok = sel & found & A.occupied[jnp.minimum(slot, R - 1)]
        f = jnp.where(ok, slot, R)
        # values stay in row_data for the durable-tier delete at checkpoint
        return A.replace(
            occupied=A.occupied.at[f].set(False, mode="drop"),
            tomb=A.tomb.at[f].set(True, mode="drop"),
            ckpt_dirty=A.ckpt_dirty.at[f].set(True, mode="drop"),
            inconsistent=A.inconsistent | jnp.any(sel & ~ok))

    def _bury_refilled(self, A: JoinSideState, chunk: StreamChunk, f,
                       refill, state_pk):
        """Before the rows of ``chunk`` overwrite the tombstoned lanes
        ``f[refill]``: copy those lanes' old rows into the graveyard where
        the durable tier would otherwise keep them — where their
        state-table key is not the new row's. Returns the side and whether
        the graveyard ran out of slots (the caller's ``lane_overflow``).
        A chunk that refills nothing (every insert-only stream) pays one
        ``any`` and a skipped branch."""
        if state_pk is not None and not len(state_pk):
            return A, jnp.zeros((), jnp.bool_)     # no durable tier
        G = A.grave_data[0].shape[0]

        def bury(grave):
            at = jnp.where(refill, f, 0)
            same_pk = jnp.full(refill.shape, state_pk is not None)
            for i in (state_pk or ()):
                c = chunk.columns[i]
                old_d = A.row_data[i].reshape(-1)[at]
                old_m = A.row_mask[i].reshape(-1)[at]
                same_pk = same_pk & ((old_m & c.mask & (old_d == c.data))
                                     | (~old_m & ~c.mask))
            owed = refill & ~same_pk
            pos = A.grave_n + jnp.cumsum(owed, dtype=jnp.int32) - 1
            g = jnp.where(owed & (pos < G), pos, G)

            def write(grave):
                gd, gm = grave
                return (tuple(d.at[g].set(rd.reshape(-1)[at], mode="drop")
                              for d, rd in zip(gd, A.row_data)),
                        tuple(m.at[g].set(rm.reshape(-1)[at], mode="drop")
                              for m, rm in zip(gm, A.row_mask)))

            n_owed = jnp.sum(owed, dtype=jnp.int32)
            return (*jax.lax.cond(n_owed > 0, write, lambda gr: gr, grave),
                    n_owed)

        gd, gm, n_owed = jax.lax.cond(
            jnp.any(refill), bury,
            lambda grave: (*grave, jnp.zeros((), jnp.int32)),
            (A.grave_data, A.grave_mask))
        return (A.replace(grave_data=gd, grave_mask=gm,
                          grave_n=jnp.minimum(A.grave_n + n_owed, G)),
                A.grave_n + n_owed > G)

    def _lane_rules(self, matches, trans, is_insert: bool, side: str):
        """What a match emits, for matches of any shape: ``(p0, p1, op0,
        a0, a1)`` — its first / second row visible, the first row's op
        (the second's is always U+), and whether the input side's columns
        are non-null in the first / second row."""
        jt = self.join_type
        sa = jt.semi_anti_side
        op_plain = OP_INSERT if is_insert else OP_DELETE
        b_outer = (jt.preserves_right if side == "left" else jt.preserves_left)
        none = jnp.zeros(matches.shape, jnp.bool_)
        p0, p1 = none, none
        op0 = jnp.full(matches.shape, op_plain, jnp.int8)
        a0 = a1 = jnp.ones(matches.shape, jnp.bool_)
        if sa is None:
            p0 = matches
            if b_outer:
                # transitions emit an adjacent update pair replacing /
                # restoring the opposite side's null-padded row
                p1 = trans
                op0 = jnp.where(trans, OP_UPDATE_DELETE, op_plain).astype(jnp.int8)
                if is_insert:
                    a0 = ~trans   # U- row is (B row, A-null)
                else:
                    a1 = none     # U+ row is (B row, A-null)
        elif sa != side:
            # input on the non-preserved side: emit/retract opposite rows on
            # degree transitions
            p0 = trans
            emit = (OP_DELETE if is_insert else OP_INSERT) if jt.is_anti \
                else (OP_INSERT if is_insert else OP_DELETE)
            op0 = jnp.full(matches.shape, emit, jnp.int8)
        return p0, p1, op0, a0, a1

    def _self_lane(self, sel, c_cnt, side: str):
        """Whether each input row is emitted on its own (the ``pself``
        lane): NULL-padded by an outer join that preserves its side, or a
        semi / anti join's own row."""
        jt = self.join_type
        sa = jt.semi_anti_side
        a_outer = (jt.preserves_left if side == "left" else jt.preserves_right)
        if sa is None:
            return sel & (c_cnt == 0) if a_outer else jnp.zeros_like(sel)
        if sa == side:
            # input on the preserved side: emit/retract own row only
            return sel & ((c_cnt == 0) if jt.is_anti else (c_cnt > 0))
        return jnp.zeros_like(sel)

    def _out_columns(self, side: str, a_cols, b_cols):
        sa = self.join_type.semi_anti_side
        if sa is None:
            return tuple(a_cols + b_cols if side == "left" else b_cols + a_cols)
        return tuple(a_cols if sa == side else b_cols)

    def _emit(self, chunk, sel, is_insert: bool, side: str, matches, c_cnt,
              r, t, d0, b_datas, b_masks):
        """Build the [N, 2W+1] emission grid for one pass."""
        N, W = matches.shape
        op_plain = OP_INSERT if is_insert else OP_DELETE
        if is_insert:
            trans = matches & (d0 + r == 0)
        else:
            trans = matches & (d0 - t == 0) & (r == t - 1)
        p0, p1, op0, a0, a1 = self._lane_rules(matches, trans, is_insert, side)
        op1 = jnp.full((N, W), OP_UPDATE_INSERT, jnp.int8)
        pself = self._self_lane(sel, c_cnt, side)

        # ---- assemble ops/vis  [N, 2W+1]
        L = 2 * W + 1
        ops = jnp.zeros((N, L), jnp.int8)
        vis = jnp.zeros((N, L), jnp.bool_)
        ops = ops.at[:, 0:2 * W:2].set(op0).at[:, 1:2 * W:2].set(op1)
        ops = ops.at[:, 2 * W].set(jnp.full(N, op_plain, jnp.int8))
        vis = vis.at[:, 0:2 * W:2].set(p0).at[:, 1:2 * W:2].set(p1)
        vis = vis.at[:, 2 * W].set(pself)

        # ---- assemble output columns
        def lanes(w0_d, w0_m, w1_d, w1_m, self_d, self_m, dtype):
            d = jnp.zeros((N, L), dtype)
            m = jnp.zeros((N, L), jnp.bool_)
            d = d.at[:, 0:2 * W:2].set(w0_d).at[:, 1:2 * W:2].set(w1_d)
            d = d.at[:, 2 * W].set(self_d)
            m = m.at[:, 0:2 * W:2].set(w0_m).at[:, 1:2 * W:2].set(w1_m)
            m = m.at[:, 2 * W].set(self_m)
            return d, m

        a_col_list = []   # input side's columns in output
        for c in chunk.columns:
            bd = jnp.broadcast_to(c.data[:, None], (N, W))
            bm = jnp.broadcast_to(c.mask[:, None], (N, W))
            a_col_list.append(lanes(
                bd, bm & a0, bd, bm & a1, c.data, c.mask, c.data.dtype))
        b_col_list = []   # opposite side's columns in output (null in self lane)
        for d, m in zip(b_datas, b_masks):
            zeros_self = jnp.zeros(N, d.dtype)
            b_col_list.append(lanes(
                d, m, d, m, zeros_self, jnp.zeros(N, jnp.bool_), d.dtype))
        return ops, vis, self._out_columns(side, a_col_list, b_col_list)

    def _emit_pairs(self, chunk, sel, is_insert: bool, side: str, pairs,
                    c_cnt):
        """One pass's emission from a fan-out probe: the pair buffer's
        slots, two rows each (a match's first and second row, as lanes
        2w / 2w+1 of the grid), then the chunk's own rows (the ``pself``
        lane) — ``2 x slots + N`` rows, flat."""
        p_row, p_b, trans, n_pairs, b_datas, b_masks = pairs
        PB = p_row.shape[0]
        op_plain = OP_INSERT if is_insert else OP_DELETE
        matches = jnp.arange(PB, dtype=jnp.int32) < n_pairs
        trans = trans & matches
        p0, p1, op0, a0, a1 = self._lane_rules(matches, trans, is_insert, side)
        pself = self._self_lane(sel, c_cnt, side)

        def rows(first, second, own):
            return jnp.concatenate(
                [jnp.stack([first, second], axis=1).reshape(-1), own])

        ops = rows(op0, jnp.full(PB, OP_UPDATE_INSERT, jnp.int8),
                   jnp.full(chunk.capacity, op_plain, jnp.int8))
        vis = rows(p0, p1, pself)
        a_cols = []
        for c in chunk.columns:
            d, m = c.data[p_row], c.mask[p_row]
            a_cols.append((rows(d, d, c.data), rows(m & a0, m & a1, c.mask)))
        none = jnp.zeros(chunk.capacity, jnp.bool_)
        b_cols = [(rows(d, d, jnp.zeros(chunk.capacity, d.dtype)),
                   rows(m, m, none)) for d, m in zip(b_datas, b_masks)]
        return ops, vis, self._out_columns(side, a_cols, b_cols)


def clean_side_below(st: JoinSideState, col_idx: int, threshold) -> JoinSideState:
    """Watermark-driven state cleaning: free rows whose ``col_idx`` value is
    below ``threshold`` (reference: interval-join inequality-watermark
    cleaning in src/stream/src/executor/hash_join.rs). Freed lanes become
    tombstones + ckpt_dirty so the next checkpoint persists their deletes;
    ``compact_side`` afterwards reclaims the hash-table slots. Opposite-side
    degrees are NOT adjusted — the watermark contract is that cleaned rows
    can never match again."""
    cleaned = st.occupied & st.row_mask[col_idx] & (st.row_data[col_idx] < threshold)
    return st.replace(
        occupied=st.occupied & ~cleaned,
        tomb=st.tomb | cleaned,
        ckpt_dirty=st.ckpt_dirty | cleaned,
    )


@jax.named_scope("ckpt_delta")
def join_ckpt_delta_window(st, lo: jax.Array, G: int):
    """One side's checkpoint delta for dirty ranks [lo, lo+G): ``(n_dirty,
    valid[G], occupied, tomb, row_data, row_mask)``, each column gathered
    to ``G`` rows. The dirty lanes of the ``[capacity, W]`` arena come
    first, read row-major (slot, then lane; ``ckpt_delta.delta_window``);
    the graveyard's rows follow them as tombstones. A fan-out arena's
    dirty rows are read in row order; it has no graveyard (a row key
    always takes back its own slot). Degrees, LRU stamps and the key table
    are not persisted: recovery rebuilds them."""
    n_arena, valid, (occ, tomb, datas, masks) = delta_window(
        st.ckpt_dirty, (st.occupied, st.tomb, st.row_data, st.row_mask),
        lo, G)
    if isinstance(st, FanoutSideState):
        return n_arena, valid, occ, tomb, datas, masks
    k = lo.astype(jnp.int32) + jnp.arange(G, dtype=jnp.int32) - n_arena
    buried = (k >= 0) & (k < st.grave_n)
    at = jnp.clip(k, 0, st.grave_mask[0].shape[0] - 1)
    return (n_arena + st.grave_n, valid | buried, occ & ~buried,
            tomb | buried,
            tuple(jnp.where(buried, g[at], d)
                  for g, d in zip(st.grave_data, datas)),
            tuple(jnp.where(buried, g[at], m)
                  for g, m in zip(st.grave_mask, masks)))


def _new_fanout(schema: Schema, lay: SideLayout) -> FanoutSideState:
    R = lay.capacity
    return FanoutSideState(
        ht=ht_new(tuple(schema[i].type for i in lay.row_key), R),
        row_data=tuple(jnp.zeros(R, f.type.dtype) for f in schema),
        row_mask=tuple(jnp.zeros(R, jnp.bool_) for _ in schema),
        occupied=jnp.zeros(R, jnp.bool_),
        tomb=jnp.zeros(R, jnp.bool_),
        degree=jnp.zeros(R, jnp.int32),
        ckpt_dirty=jnp.zeros(R, jnp.bool_),
        probe_counts=jnp.zeros(3, jnp.int64),
        ht_overflow=jnp.zeros((), jnp.bool_),
        lane_overflow=jnp.zeros((), jnp.bool_),
        inconsistent=jnp.zeros((), jnp.bool_),
    )


def _fanout_of(schema: Schema, lay: SideLayout, groups, inconsistent):
    """A fan-out arena of ``lay`` holding the rows of ``groups``: each a
    tuple ``(datas, masks, occupied, tomb, degree, ckpt_dirty, keep)`` of
    flat arrays, the rows where ``keep``; a later group's row wins a row
    key that an earlier group's holds."""
    st = _new_fanout(schema, lay)
    R = lay.capacity
    for datas, masks, occ, tomb, degree, dirty, keep in groups:
        ht, slot, _, ovf = ht_lookup_or_insert(
            st.ht, [Column(datas[i], masks[i]) for i in lay.row_key], keep)
        f = jnp.where(keep & (slot < R), slot, R)
        st = st.replace(
            ht=ht,
            row_data=tuple(rd.at[f].set(d, mode="drop")
                           for rd, d in zip(st.row_data, datas)),
            row_mask=tuple(rm.at[f].set(m, mode="drop")
                           for rm, m in zip(st.row_mask, masks)),
            occupied=st.occupied.at[f].set(occ, mode="drop"),
            tomb=st.tomb.at[f].set(tomb, mode="drop"),
            degree=st.degree.at[f].set(degree, mode="drop"),
            ckpt_dirty=st.ckpt_dirty.at[f].set(dirty, mode="drop"),
            ht_overflow=st.ht_overflow | ovf)
    return st.replace(inconsistent=inconsistent)


def _fanout_rows(st: FanoutSideState):
    """A fan-out arena's rows as one ``_fanout_of`` group: those live or
    owed a durable delete (a row deleted before the last checkpoint is
    dropped)."""
    return (st.row_data, st.row_mask, st.occupied, st.tomb, st.degree,
            st.ckpt_dirty, st.occupied | st.tomb)


def _bucket_rows(st: JoinSideState) -> list:
    """A bucket arena's rows as ``_fanout_of`` groups, later winning: the
    graveyard's (owed deletes), the tombstoned lanes', the live lanes'."""
    G = st.grave_data[0].shape[0]
    owed = jnp.arange(G) < st.grave_n
    flat = (tuple(d.reshape(-1) for d in st.row_data),
            tuple(m.reshape(-1) for m in st.row_mask))
    occ, tomb = st.occupied.reshape(-1), st.tomb.reshape(-1)
    degree, dirty = st.degree.reshape(-1), st.ckpt_dirty.reshape(-1)
    return [
        (st.grave_data, st.grave_mask, jnp.zeros(G, jnp.bool_),
         jnp.ones(G, jnp.bool_), jnp.zeros(G, jnp.int32),
         jnp.ones(G, jnp.bool_), owed),
        (*flat, occ, tomb, degree, dirty, tomb & ~occ),
        (*flat, occ, tomb, degree, dirty, occ),
    ]


def compact_side(core: "JoinCore", old, side: str):
    """Rebuild the side's hash table keeping only keys with live rows,
    remapping the bucket arrays — open-addressing slots cannot be freed in
    place (probe chains), so cleaning reclaims space by rebuild. Run AFTER
    the checkpoint cleared tombstones (their deletes are persisted). A
    fan-out arena is rebuilt from its live rows the same way."""
    lay = core.layout[side]
    schema = core.schema_of(side)
    if lay.fanout:
        return _fanout_of(schema, lay, [_fanout_rows(old)], old.inconsistent)
    cap, W = lay.capacity, lay.width
    key_types = tuple(schema[i].type for i in core.keys_of(side))
    key_live = old.ht.occupied & jnp.any(old.occupied | old.tomb, axis=1)
    key_cols = [
        Column(kd, km) for kd, km in zip(old.ht.key_data, old.ht.key_mask)
    ]
    ht, slots, _, rebuild_ovf = ht_lookup_or_insert(
        ht_new(key_types, cap), key_cols, key_live)
    dst = jnp.where(key_live, slots, cap)

    def move(arr, fill):
        out = jnp.full((cap, W), fill, arr.dtype)
        return out.at[dst].set(arr, mode="drop")

    return JoinSideState(
        ht=ht,
        row_data=tuple(move(rd, 0) for rd in old.row_data),
        row_mask=tuple(move(rm, False) for rm in old.row_mask),
        occupied=move(old.occupied, False),
        tomb=move(old.tomb, False),
        degree=move(old.degree, 0),
        ckpt_dirty=move(old.ckpt_dirty, False),
        grave_data=old.grave_data, grave_mask=old.grave_mask,
        grave_n=old.grave_n,
        lru=jnp.zeros(cap, jnp.int32).at[dst].set(old.lru, mode="drop"),
        # a key that exhausts probing during rebuild would silently drop its
        # whole bucket via mode="drop" — surface it
        ht_overflow=old.ht_overflow | rebuild_ovf,
        lane_overflow=old.lane_overflow,
        inconsistent=old.inconsistent,
    )


def side_any_overflow(st: JoinSideState) -> bool:
    return bool(st.ht_overflow) | bool(st.lane_overflow)


def _side_live_keys(st: JoinSideState) -> jax.Array:
    """bool[cap]: key slots with at least one live row."""
    return st.ht.occupied & jnp.any(st.occupied, axis=1)


def _side_evictable_keys(st: JoinSideState) -> jax.Array:
    """bool[cap]: live key slots that CAN evict — null-keyed slots are
    permanently resident (their rows can't be faulted back by key
    lookup), so they must not count toward the budget either, or a
    null-heavy side could never get under budget and hot non-null keys
    would thrash."""
    live = _side_live_keys(st)
    for km in st.ht.key_mask:
        live = live & km
    return live


def join_evict_plan(state: JoinState, keep: int):
    """Pick cold keys to evict from BOTH arenas so ~``keep`` hottest
    remain per side (reference: JoinHashMap's ManagedLruCache,
    src/stream/src/executor/managed_state/join/mod.rs:228-258 +
    cache/managed_lru.rs — here eviction is whole-key: a key's buckets
    leave both sides together, so opposite-side degrees stay coherent).

    LRU stamps for one key value are kept in sync across the two sides by
    ``apply_chunk(step=...)``, so ONE threshold — the max of the two
    per-side thresholds — names a consistent key set on both sides.
    Null-keyed slots never evict (their rows can't be faulted back by key
    lookup). Returns (mask_l bool[cap], mask_r bool[cap], packed
    [n_evict_l, n_evict_r, n_live_l, n_live_r])."""
    cap = state.left.lru.shape[0]
    big = jnp.iinfo(jnp.int32).max

    def thr_of(st):
        live = _side_evictable_keys(st)
        n_live = jnp.sum(live)
        key = jnp.where(live, st.lru, big)
        skey = jnp.sort(key)
        k = jnp.clip(n_live - keep, 0, cap - 1)
        thr = jnp.where(k > 0, skey[jnp.maximum(k - 1, 0)], jnp.int32(-1))
        return thr, n_live, live

    thr_l, nl, live_l = thr_of(state.left)
    thr_r, nr, live_r = thr_of(state.right)
    thr = jnp.maximum(thr_l, thr_r)

    mask_l = live_l & (state.left.lru <= thr)
    mask_r = live_r & (state.right.lru <= thr)
    packed = jnp.stack([jnp.sum(mask_l), jnp.sum(mask_r), nl, nr])
    return mask_l, mask_r, packed


def apply_evict_side(st: JoinSideState, mask: jax.Array) -> JoinSideState:
    """Clear evicted keys' buckets WITHOUT tombstones or dirty marks: the
    durable rows (flushed by this barrier's checkpoint) ARE the cold
    copies. Call at a checkpoint barrier AFTER the flush cleared
    tomb/ckpt_dirty, BEFORE compact (which reclaims the key slots)."""
    m2 = mask[:, None]
    return st.replace(
        occupied=st.occupied & ~m2,
        row_mask=tuple(rm & ~m2 for rm in st.row_mask),
        degree=jnp.where(m2, 0, st.degree),
        lru=jnp.where(mask, 0, st.lru),
    )


def import_side(core: "JoinCore", old, side: str):
    """Re-layout one side's state into ``core``'s geometry for it.

    Functional growth: the streaming executor applies a chunk, checks the
    overflow flags, and on overflow discards the new state, grows, and
    retries on the UNTOUCHED old state — possible only because the whole
    join state is an immutable pytree (the TPU-native analogue of the
    reference growing its hash maps on the heap).

    Width growth pads lanes; capacity growth rehashes keys into the new
    table and moves whole buckets by the slot remap. A bucket arena that
    becomes a fan-out one hands over every row it holds (and its
    graveyard's, as owed deletes); a fan-out arena rehashes its rows into
    the new row count (a wider pair buffer changes no state). Degrees move
    with the rows (they depend only on the opposite side's content)."""
    lay = core.layout[side]
    schema = core.schema_of(side)
    if lay.fanout:
        if isinstance(old, JoinSideState):
            return _fanout_of(schema, lay, _bucket_rows(old), old.inconsistent)
        if old.occupied.shape[0] == lay.capacity:
            return old.replace(lane_overflow=jnp.zeros((), jnp.bool_))
        return _fanout_of(schema, lay, [_fanout_rows(old)], old.inconsistent)
    cap, W = lay.capacity, lay.width
    old_cap, old_W = old.occupied.shape
    assert cap >= old_cap and W >= old_W

    def pad(a, fill=False):
        out = jnp.full((old_cap, W), fill, a.dtype)
        return out.at[:, :old_W].set(a)

    row_data = tuple(pad(rd, 0) for rd in old.row_data)
    row_mask = tuple(pad(rm) for rm in old.row_mask)
    occupied = pad(old.occupied)
    tomb = pad(old.tomb)
    degree = pad(old.degree, 0)
    ckpt_dirty = pad(old.ckpt_dirty)

    # the graveyard is no arena: it keeps its rows, in a buffer of the new
    # geometry's size
    def longer(a):
        return jnp.zeros(core.grave_rows(side), a.dtype).at[:a.shape[0]].set(a)

    grave = dict(grave_data=tuple(longer(g) for g in old.grave_data),
                 grave_mask=tuple(longer(g) for g in old.grave_mask),
                 grave_n=old.grave_n)

    key_types = tuple(schema[i].type for i in core.keys_of(side))
    if cap == old_cap:
        ht = old.ht
        new = JoinSideState(
            ht=ht, row_data=row_data, row_mask=row_mask, occupied=occupied,
            tomb=tomb, degree=degree, ckpt_dirty=ckpt_dirty, lru=old.lru,
            **grave,
            ht_overflow=jnp.zeros((), jnp.bool_),
            lane_overflow=jnp.zeros((), jnp.bool_),
            inconsistent=old.inconsistent,
        )
        return new
    # rehash keys into the larger table, then move buckets by slot remap
    ht = ht_new(key_types, cap)
    key_cols = [
        Column(kd, km) for kd, km in zip(old.ht.key_data, old.ht.key_mask)
    ]
    ht, new_slots, _, ovf = ht_lookup_or_insert(ht, key_cols, old.ht.occupied)
    if bool(ovf):  # cannot happen: cap > old_cap
        raise RuntimeError("rehash overflow")
    dst = jnp.where(old.ht.occupied, new_slots, cap)

    def move(padded, init_fill):
        out = jnp.full((cap, W), init_fill, padded.dtype)
        return out.at[dst].set(padded, mode="drop")

    return JoinSideState(
        ht=ht,
        row_data=tuple(move(rd, 0) for rd in row_data),
        row_mask=tuple(move(rm, False) for rm in row_mask),
        occupied=move(occupied, False),
        tomb=move(tomb, False),
        degree=move(degree, 0),
        ckpt_dirty=move(ckpt_dirty, False),
        **grave,
        lru=jnp.zeros(cap, jnp.int32).at[dst].set(old.lru, mode="drop"),
        ht_overflow=jnp.zeros((), jnp.bool_),
        lane_overflow=jnp.zeros((), jnp.bool_),
        inconsistent=old.inconsistent,
    )


def import_state(core: "JoinCore", old: JoinState) -> JoinState:
    return JoinState(left=import_side(core, old.left, "left"),
                     right=import_side(core, old.right, "right"))
