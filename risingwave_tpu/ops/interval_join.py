"""Bucketed interval/window join core — the q7 hot path as O(N·W) work.

The generic streaming hash join (ops/join_state.py) recovers serial-order
semantics with [N, N] all-pairs compares per chunk (rank/total matmuls) —
correct for arbitrary equi-joins under retraction, but ~23× too slow for
the q7 shape, where the join key is a TIME WINDOW and the build side is a
per-window aggregate. This core exploits both structural facts:

  * **Bucketing**: both sides are bucketed by window id
    (``ts // window_us``) into a ring of ``n_buckets`` slots. Event time
    advances monotonically, so a slot is reclaimed by the next window that
    hashes onto it long after the old window went cold; no hash table, no
    probing — a bucket index is ONE modulo.
  * **Aggregate build side**: q7's build input is MAX(price) per window —
    at most ONE live build row per key. Probing is a [N] gather + compare,
    not a [N, W] candidate scan, and no degree bookkeeping exists (the
    join is INNER).
  * **Band filter**: stored rows join bucket-equal pairs; an optional band
    (``band_col``/``band_us``) further restricts matches to rows whose raw
    timestamp lies in ``[win_start, win_start + band_us)`` — the interval
    part of an interval join, applied per lane, never per pair-of-rows.

Per chunk the work is O(N log N) (a sort assigns same-bucket lanes) +
O(N) scatters; the epoch flush is O(n_buckets · W) ONCE per barrier —
the O(N²) all-pairs compare is gone. The flush match grid is an
MXU/VPU-friendly [n_buckets, W] tile computation: ``interval_match``
lowers to a Pallas TPU kernel (the ops/pallas_rank.py pattern — tiles
generated in VMEM, jnp twin off the TPU, bit-identical results; int64
values ride as hi/lo int32 halves because Mosaic has no native s64
compare).

Emission parity with the executor pipeline (HashAgg max → HashJoin) is
exact, including the churn the executor produces: its agg flush emits
U-/U+ for every TOUCHED group (even when the max did not change), and the
join then retracts + re-emits every matching stored row. The flush here
keys on a ``touched`` bitmask for the same reason — bit-exact output
multisets, verified by tests/test_interval_join.py.

The probe side is **append-only** (q7 bids). A delete arriving on the
probe side sets the sticky ``saw_delete`` flag instead of corrupting
state; retraction still flows through the OUTPUT (max changes retract
previously emitted matches) — that is the retraction surface q7 needs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_INSERT, Column, StreamChunk,
)
from ..common.types import Field, Schema

# Pallas tile: TB buckets per grid cell; the lane axis (W) rides whole.
TILE_B = 256

_NEG = jnp.iinfo(jnp.int64).min


@struct.dataclass
class IntervalJoinState:
    win_id: jax.Array       # int64[nb]: window id resident in slot; -1 empty
    fill: jax.Array         # int32[nb]: stored probe rows (lanes 0..fill-1)
    row_data: tuple[jax.Array, ...]   # per probe column: dtype[nb, W]
    row_mask: tuple[jax.Array, ...]   # per probe column: bool[nb, W]
    touched: jax.Array      # bool[nb]: bucket hit since last flush
    cur_max: jax.Array      # int64[nb]: running MAX incl. unflushed chunks
    cur_cnt: jax.Array      # int64[nb]: contributing rows (liveness)
    emitted_max: jax.Array  # int64[nb]: build value downstream last saw
    emitted_live: jax.Array  # bool[nb]: build row exists downstream
    lane_overflow: jax.Array  # bool scalar, sticky: bucket lane width full
    ring_clobber: jax.Array   # bool scalar, sticky: slot reused while dirty
    saw_delete: jax.Array     # bool scalar, sticky: delete on probe side


class IntervalJoinCore:
    """Static config + pure steps for one bucketed interval join.

    ``probe_schema``: schema of the (already projected) probe input.
    ``ts_col``: probe column holding the window start (tumble_start
    output — any value with ``value // window_us`` == window id works).
    ``val_col``: probe column compared against the build aggregate
    (q7: price == MAX(price) OVER window).
    ``band_col``/``band_us``: optional interval band — rows only match
    while ``band_col`` value ∈ [win_start, win_start + band_us).

    Output schema = probe columns ++ (window_start, agg value) — exactly
    the inner-join output of the executor pipeline."""

    def __init__(self, probe_schema: Schema, ts_col: int, val_col: int,
                 window_us: int, n_buckets: int = 1 << 15,
                 lane_width: int = 128,
                 band_col: Optional[int] = None,
                 band_us: Optional[int] = None):
        if window_us <= 0:
            raise ValueError("window_us must be positive")
        if (band_col is None) != (band_us is None):
            raise ValueError("band_col and band_us come together")
        self.probe_schema = probe_schema
        self.ts_col = ts_col
        self.val_col = val_col
        self.window_us = int(window_us)
        self.n_buckets = int(n_buckets)
        self.W = int(lane_width)
        self.band_col = band_col
        self.band_us = band_us
        self.out_schema = probe_schema.concat(Schema((
            Field("window_start", probe_schema[ts_col].type),
            Field("agg_val", probe_schema[val_col].type),
        )))

    # -- state ----------------------------------------------------------------

    def init_state(self) -> IntervalJoinState:
        nb, W = self.n_buckets, self.W
        return IntervalJoinState(
            win_id=jnp.full(nb, -1, jnp.int64),
            fill=jnp.zeros(nb, jnp.int32),
            row_data=tuple(jnp.zeros((nb, W), f.type.dtype)
                           for f in self.probe_schema),
            row_mask=tuple(jnp.zeros((nb, W), jnp.bool_)
                           for _ in self.probe_schema),
            touched=jnp.zeros(nb, jnp.bool_),
            cur_max=jnp.full(nb, _NEG, jnp.int64),
            cur_cnt=jnp.zeros(nb, jnp.int64),
            emitted_max=jnp.full(nb, _NEG, jnp.int64),
            emitted_live=jnp.zeros(nb, jnp.bool_),
            lane_overflow=jnp.zeros((), jnp.bool_),
            ring_clobber=jnp.zeros((), jnp.bool_),
            saw_delete=jnp.zeros((), jnp.bool_),
        )

    # -- chunk step ------------------------------------------------------------

    def apply_chunk(self, state: IntervalJoinState, chunk: StreamChunk):
        """Insert one probe chunk, emit matches against the build rows the
        downstream has already seen (``emitted_*`` — build updates land at
        the next ``flush``, mirroring the executor where the agg flushes
        at barriers only). Returns (state, out_chunk) with out capacity =
        chunk capacity (≤1 build row per window ⇒ ≤1 match per probe row).
        """
        nb, W = self.n_buckets, self.W
        N = chunk.capacity
        ts = chunk.columns[self.ts_col]
        val = chunk.columns[self.val_col]
        is_ins = (chunk.ops == OP_INSERT) | (chunk.ops == OP_UPDATE_INSERT)
        saw_delete = state.saw_delete | jnp.any(chunk.vis & ~is_ins)
        valid = chunk.vis & is_ins & ts.mask & val.mask
        wid = ts.data.astype(jnp.int64) // self.window_us
        slot = (wid % nb).astype(jnp.int32)

        # ---- ring turnover: the newest window id claims its slot. A slot
        # whose resident still had an unflushed delta loses emissions —
        # sticky ring_clobber (size n_buckets past one epoch's window span
        # and this can never fire).
        claim = jnp.where(valid, wid, jnp.int64(-1))
        win_id = state.win_id.at[jnp.where(valid, slot, nb)].max(
            claim, mode="drop")
        turned = win_id != state.win_id
        cur_live = state.cur_cnt > 0
        slot_dirty = state.touched & (
            (cur_live != state.emitted_live)
            | (cur_live & (state.cur_max != state.emitted_max)))
        # rows whose slot now belongs to a NEWER window (ring wrapped
        # within one chunk) cannot be stored — flagged, then dropped
        stale = valid & (win_id[slot] != wid)
        ring_clobber = (state.ring_clobber
                        | jnp.any(turned & slot_dirty) | jnp.any(stale))
        ok = valid & ~stale

        fill = jnp.where(turned, 0, state.fill)
        touched = jnp.where(turned, False, state.touched)
        cur_max = jnp.where(turned, _NEG, state.cur_max)
        cur_cnt = jnp.where(turned, 0, state.cur_cnt)
        emitted_max = jnp.where(turned, _NEG, state.emitted_max)
        emitted_live = jnp.where(turned, False, state.emitted_live)

        # ---- lane assignment: rank among same-slot rows of this chunk by
        # a stable sort (O(N log N) — the [N, N] all-pairs rank is gone),
        # then lane = bucket fill + rank.
        sort_key = jnp.where(ok, slot, nb)
        order = jnp.argsort(sort_key, stable=True)
        ks = sort_key[order]
        idx = jnp.arange(N, dtype=jnp.int32)
        run_start = jnp.concatenate(
            [jnp.ones(1, jnp.bool_), ks[1:] != ks[:-1]])
        rank_sorted = idx - jax.lax.cummax(
            jnp.where(run_start, idx, 0))
        rank = jnp.zeros(N, jnp.int32).at[order].set(rank_sorted)

        lane = fill[slot] + rank
        lane_ok = ok & (lane < W)
        lane_overflow = state.lane_overflow | jnp.any(ok & (lane >= W))
        f = jnp.where(lane_ok, slot * W + lane, nb * W)
        s_ok = jnp.where(lane_ok, slot, nb)

        row_data = tuple(
            rd.reshape(-1).at[f].set(c.data, mode="drop").reshape(nb, W)
            for rd, c in zip(state.row_data, chunk.columns))
        row_mask = tuple(
            rm.reshape(-1).at[f].set(c.mask, mode="drop").reshape(nb, W)
            for rm, c in zip(state.row_mask, chunk.columns))
        one = jnp.where(lane_ok, 1, 0)
        fill = fill.at[s_ok].add(one.astype(jnp.int32), mode="drop")
        touched = touched.at[s_ok].set(True, mode="drop")
        v = val.data.astype(jnp.int64)
        cur_max = cur_max.at[s_ok].max(jnp.where(lane_ok, v, _NEG),
                                       mode="drop")
        cur_cnt = cur_cnt.at[s_ok].add(one.astype(jnp.int64), mode="drop")

        # ---- probe emission against the flushed build rows
        match = lane_ok & emitted_live[slot] & (v == emitted_max[slot])
        if self.band_col is not None:
            bts = chunk.columns[self.band_col].data.astype(jnp.int64)
            ws = wid * self.window_us
            match = match & (bts >= ws) & (bts < ws + self.band_us)
        out = self._emit_probe(chunk, slot, wid, emitted_max, match)

        return state.replace(
            win_id=win_id, fill=fill, row_data=row_data, row_mask=row_mask,
            touched=touched, cur_max=cur_max, cur_cnt=cur_cnt,
            emitted_max=emitted_max, emitted_live=emitted_live,
            lane_overflow=lane_overflow, ring_clobber=ring_clobber,
            saw_delete=saw_delete,
        ), out

    def _emit_probe(self, chunk, slot, wid, emitted_max, match):
        ts_dtype = self.probe_schema[self.ts_col].type.dtype
        val_dtype = self.probe_schema[self.val_col].type.dtype
        win_start = (wid * self.window_us).astype(ts_dtype)
        bmax = emitted_max[slot].astype(val_dtype)
        cols = tuple(chunk.columns) + (
            Column(win_start, match),
            Column(bmax, match),
        )
        return StreamChunk(jnp.zeros(chunk.capacity, jnp.int8), match, cols)

    # -- barrier flush ---------------------------------------------------------

    def _occ_band(self, state: IntervalJoinState) -> jax.Array:
        """bool[nb, W]: stored lanes that are live AND inside the band."""
        occ = (jnp.arange(self.W, dtype=jnp.int32)[None, :]
               < state.fill[:, None])
        if self.band_col is not None:
            bts = state.row_data[self.band_col].astype(jnp.int64)
            ws = (state.win_id * self.window_us)[:, None]
            occ = occ & (bts >= ws) & (bts < ws + self.band_us)
        return occ

    def flush_plan(self, state: IntervalJoinState):
        """Match grids for the epoch flush: the build-side delta applied to
        the stored probe arena. DELETE matches against the OLD emitted max,
        INSERT matches against the new one — for every TOUCHED bucket,
        exactly the churn the executor's dirty-set agg flush produces.
        Returns (del_mask [nb, W], ins_mask [nb, W], packed
        [n_units, lane_ovf, ring_clobber, saw_delete])."""
        occ = self._occ_band(state)
        vals = state.row_data[self.val_col].astype(jnp.int64)
        cur_live = state.cur_cnt > 0
        del_mask, ins_mask = interval_match(
            vals, occ,
            state.emitted_max, state.touched & state.emitted_live,
            state.cur_max, state.touched & cur_live)
        packed = jnp.stack([
            jnp.sum(del_mask) + jnp.sum(ins_mask),
            state.lane_overflow.astype(jnp.int64),
            state.ring_clobber.astype(jnp.int64),
            state.saw_delete.astype(jnp.int64),
        ])
        return del_mask, ins_mask, packed

    def gather_flush(self, state: IntervalJoinState, del_mask, ins_mask,
                     old_emitted_max, lo, out_capacity: int) -> StreamChunk:
        """Pack flush units with global rank in [lo, lo+out_capacity) into
        one output chunk — deletes (vs ``old_emitted_max``) rank first,
        inserts (vs the new ``cur_max``) after, preserving the executor's
        delete-pass-before-insert-pass order. Pure + shape-static; drive
        as ``for lo in range(0, n_units, out_capacity)``.

        Gather formulation: the in-window unit POSITIONS are extracted
        with a fixed-size nonzero, then every output column is a
        [out_capacity]-sized gather — per-window cost is a few linear
        passes over the [nb·W] masks plus tiny gathers. (The first cut
        scattered FROM the full [nb·W] arena per window: ~25 scatter
        passes over 4M cells each, ~3 s per window on the CPU stand-in —
        the same scatter-vs-gather lesson as AggCore.gather_flush_chunk.)
        """
        nb, W = self.n_buckets, self.W
        cap = out_capacity
        dflat = del_mask.reshape(-1)
        iflat = ins_mask.reshape(-1)
        n_del = jnp.sum(dflat)
        drank = jnp.cumsum(dflat) - 1
        irank = n_del + jnp.cumsum(iflat) - 1
        d_in = dflat & (drank >= lo) & (drank < lo + cap)
        i_in = iflat & (irank >= lo) & (irank < lo + cap)
        # ascending-index nonzero == ascending rank, so output slot j holds
        # delete unit lo+j for j < d_n, then insert units in rank order
        (d_idx,) = jnp.nonzero(d_in, size=cap, fill_value=nb * W)
        (i_idx,) = jnp.nonzero(i_in, size=cap, fill_value=nb * W)
        d_n = jnp.sum(d_in)
        j = jnp.arange(cap)
        take_del = j < d_n
        src = jnp.where(take_del, d_idx,
                        i_idx[jnp.clip(j - d_n, 0, cap - 1)])
        vis = src < nb * W
        src = jnp.where(vis, src, 0)
        bucket = src // W

        ops = jnp.where(take_del, OP_DELETE, OP_INSERT).astype(jnp.int8)
        cols = []
        for rd, rm in zip(state.row_data, state.row_mask):
            cols.append(Column(rd.reshape(-1)[src],
                               rm.reshape(-1)[src] & vis))
        ts_dtype = self.probe_schema[self.ts_col].type.dtype
        val_dtype = self.probe_schema[self.val_col].type.dtype
        ws = (state.win_id[bucket] * self.window_us).astype(ts_dtype)
        bval = jnp.where(take_del, old_emitted_max[bucket],
                         state.cur_max[bucket]).astype(val_dtype)
        cols.append(Column(ws, vis))
        cols.append(Column(bval, vis))
        return StreamChunk(ops, vis, tuple(cols))

    def finish_flush(self, state: IntervalJoinState) -> IntervalJoinState:
        """Advance the downstream-visible build rows to the current agg and
        clear the touched set — the fused analogue of the executor's agg
        ``finish_flush`` + the join arena absorbing the U-/U+ chunk."""
        cur_live = state.cur_cnt > 0
        return state.replace(
            emitted_max=jnp.where(state.touched, state.cur_max,
                                  state.emitted_max),
            emitted_live=jnp.where(state.touched, cur_live,
                                   state.emitted_live),
            touched=jnp.zeros_like(state.touched),
        )

    # -- checkpoint / recovery -------------------------------------------------

    def export_host(self, state: IntervalJoinState) -> dict:
        """Device state → named numpy arrays (the checkpoint payload). One
        transfer; the arrays round-trip bit-exactly through import_host."""
        host = jax.device_get(state)
        out = {f: getattr(host, f) for f in (
            "win_id", "fill", "touched", "cur_max", "cur_cnt",
            "emitted_max", "emitted_live", "lane_overflow",
            "ring_clobber", "saw_delete")}
        out["row_data"] = [np.asarray(a) for a in host.row_data]
        out["row_mask"] = [np.asarray(a) for a in host.row_mask]
        return out

    def import_host(self, payload: dict) -> IntervalJoinState:
        """Recovery: numpy checkpoint payload → fresh device state."""
        return IntervalJoinState(
            win_id=jnp.asarray(payload["win_id"]),
            fill=jnp.asarray(payload["fill"]),
            row_data=tuple(jnp.asarray(a) for a in payload["row_data"]),
            row_mask=tuple(jnp.asarray(a) for a in payload["row_mask"]),
            touched=jnp.asarray(payload["touched"]),
            cur_max=jnp.asarray(payload["cur_max"]),
            cur_cnt=jnp.asarray(payload["cur_cnt"]),
            emitted_max=jnp.asarray(payload["emitted_max"]),
            emitted_live=jnp.asarray(payload["emitted_live"]),
            lane_overflow=jnp.asarray(payload["lane_overflow"]),
            ring_clobber=jnp.asarray(payload["ring_clobber"]),
            saw_delete=jnp.asarray(payload["saw_delete"]),
        )


# ---------------------------------------------------------------------------
# The bucketed match kernel: [nb, W] tiles, Pallas on TPU, jnp elsewhere
# ---------------------------------------------------------------------------


def interval_match_jnp(vals, occ, old_max, old_live, new_max, new_live):
    """Reference formulation: per (bucket, lane) delete/insert matches of
    the flush. All inputs int64/bool; outputs (bool[nb, W], bool[nb, W])."""
    del_mask = occ & old_live[:, None] & (vals == old_max[:, None])
    ins_mask = occ & new_live[:, None] & (vals == new_max[:, None])
    return del_mask, ins_mask


def _split64(a: jax.Array):
    """int64 → (lo, hi) int32 halves (Mosaic has no native s64 compare;
    equality of both halves == equality of the 64-bit value)."""
    lo = (a & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32).astype(jnp.int32)
    hi = (a >> 32).astype(jnp.int32)
    return lo, hi


def _match_kernel(vlo_ref, vhi_ref, occ_ref, olo_ref, ohi_ref, olive_ref,
                  nlo_ref, nhi_ref, nlive_ref, del_ref, ins_ref):
    """One [TB, W] tile: the equality grids are generated in VMEM from the
    [TB, 1] per-bucket columns and never exist at [nb, W] intermediate
    granularity beyond the output masks themselves. Everything is 2-D
    int32: Mosaic has no layout for the rank change of a 1-D block."""
    vlo = vlo_ref[:]
    vhi = vhi_ref[:]
    occ = occ_ref[:] != 0

    def lanes(ref):
        return jnp.broadcast_to(ref[:], vlo.shape)

    eq_old = ((vlo == lanes(olo_ref)) & (vhi == lanes(ohi_ref))
              & (lanes(olive_ref) != 0))
    eq_new = ((vlo == lanes(nlo_ref)) & (vhi == lanes(nhi_ref))
              & (lanes(nlive_ref) != 0))
    # typed constants: under x64 a bare 1 is an int64, which Mosaic has
    # no layout for
    one, zero = jnp.int32(1), jnp.int32(0)
    del_ref[:] = jnp.where(occ & eq_old, one, zero)
    ins_ref[:] = jnp.where(occ & eq_new, one, zero)


def interval_match_pallas_call(vals, occ, old_max, old_live,
                               new_max, new_live, interpret: bool = False):
    """The raw pallas_call — no backend choice (compiled for a described
    v5e by tests/test_pallas_compile.py, like
    ops/pallas_rank.rank_totals_pallas_call)."""
    from jax.experimental import pallas as pl

    nb, w = vals.shape
    tb = min(TILE_B, nb)
    vlo, vhi = _split64(vals)
    olo, ohi = _split64(old_max)
    nlo, nhi = _split64(new_max)
    grid = (nb // tb,)
    z = np.int32(0)     # block indices are int32 (a bare 0 is int64 here)
    vec = pl.BlockSpec((tb, 1), lambda i: (i, z))
    mat = pl.BlockSpec((tb, w), lambda i: (i, z))

    def column(a):
        return a.astype(jnp.int32).reshape(nb, 1)

    return pl.pallas_call(
        _match_kernel,
        grid=grid,
        in_specs=[mat, mat, mat, vec, vec, vec, vec, vec, vec],
        out_specs=[mat, mat],
        out_shape=[jax.ShapeDtypeStruct((nb, w), jnp.int32),
                   jax.ShapeDtypeStruct((nb, w), jnp.int32)],
        interpret=interpret,
    )(vlo, vhi, occ.astype(jnp.int32), column(olo), column(ohi),
      column(old_live), column(nlo), column(nhi), column(new_live))


@functools.partial(jax.jit, static_argnames=("interpret",))
def interval_match_pallas(vals, occ, old_max, old_live, new_max, new_live,
                          interpret: bool = False):
    """The kernel with its bool epilogue. ``interpret=False`` compiles
    for the attached TPU and fails anywhere else; the backend choice is
    ``interval_match``'s alone."""
    nb, w = vals.shape
    if nb % min(TILE_B, nb):
        # a choice by shape: ragged bucket counts have no tile grid
        return interval_match_jnp(vals, occ, old_max, old_live,
                                  new_max, new_live)
    d, ins = interval_match_pallas_call(vals, occ, old_max, old_live,
                                        new_max, new_live,
                                        interpret=interpret)
    return d != 0, ins != 0


def interval_match(vals, occ, old_max, old_live, new_max, new_live):
    """Flush match grids — Pallas kernel on TPU, jnp elsewhere; both
    bit-identical (tests/test_interval_join.py asserts parity). ONE
    selector shared with the rank kernel (ops/pallas_rank
    .pallas_selected) so the two can never disagree about when Pallas
    is active."""
    from .pallas_rank import pallas_selected
    if pallas_selected():
        return interval_match_pallas(vals, occ, old_max, old_live,
                                     new_max, new_live)
    return interval_match_jnp(vals, occ, old_max, old_live,
                              new_max, new_live)
