"""Host drivers for the mesh-sharded fused epochs (ops/fused_sharded.py).

``ShardedFusedAgg`` / ``ShardedFusedJoin`` / ``ShardedFusedSession`` /
``ShardedFusedQ3`` own one surface's sharded stacked state (leading
``[n_shards]`` axis, ``NamedSharding(mesh, P('shard'))``);
``ShardedCoGroup`` (+ the signature-keyed ``ShardedCoScheduler``) owns a
whole co-scheduled group's ``[n_shards, J]`` state — K signature-equal
MVs × S shards in ONE dispatch per tick (fusion surface 6). All share
the per-epoch control loop:

* ``run_epoch(start, key, k)`` — ONE jit dispatch for the whole mesh.
* ``flush()`` — ONE packed stats fetch covering every shard (the agg
  reuses ops/fused_multi.py's vmapped barrier steps: the shard axis is
  served by exactly the machinery the co-scheduler built for its job
  axis), then per-window output gathers via a traced shard index, so one
  compiled gather serves every shard.
* routing-overflow grow-retry: the compacted all-to-all receive width
  (``recv_width`` chunks) can overflow under hot-key skew; the epoch's
  sticky per-shard ``route_ovf`` flag surfaces in the SAME packed fetch,
  and the driver doubles the width and re-runs the epoch from the
  untouched previous state — the functional grow-retry of
  parallel/sharded_join.py, applied to the fused path (which is why the
  sharded epochs never donate their buffers).

Durability composes with the ordinary split-state tables: per-shard
states are solo-shaped (``shard_states()``), so the agg checkpoints
through ONE HashAggExecutor persistence engine (its own state-table
delta flush), and recovery re-shards committed rows onto ANY mesh size
by replaying the vnode mapping (``load_shard_states`` — the same
``vnode_to_shard`` in-dispatch routing uses). The join exports/imports
per-shard ``IntervalJoinCore`` payloads; ``reshard_join_payloads``
re-buckets them for a differently-sized mesh.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..common.chunk import Column, flatten_shards, gather_units_window
from ..common.fetch import PendingFlush, async_fetch, fetch
from ..common.hashing import (
    shard_rows, vnode_of, vnode_to_shard, vnodes_of_rows,
)
from ..common.profiling import GLOBAL_PROFILER, profile_dispatch
from ..ops.fused_multi import (
    gather_job_flush_chunk, index_state, multi_agg_finish, stack_states,
    unstack_states,
)
from ..ops.fused_sharded import (
    build_sharded_group_epoch, sharded_agg_epoch, sharded_join_epoch,
    sharded_q3_epoch, sharded_session_epoch,
)
from ..ops.grouped_agg import load_rows_into_state
from ..ops.hash_table import ht_lookup_or_insert
from ..stream.coschedule import JobAxisGroup, restack_span
from .sharded_agg import SHARD_AXIS

_NEG = np.iinfo(np.int64).min


def _sharded_agg_probe(core, job_axis: bool = False) -> Callable:
    """``probe(stacked, route_ovf) -> (packed [..., 3], rank [..., cap])``
    — the whole mesh's barrier probe in one dispatch / one fetch; slot 2
    carries the epoch's routing-overflow flag so retry detection costs no
    extra sync. With ``job_axis`` the vmap nests over ``[n, J]`` (the
    K×S co-scheduled group's layout) instead of ``[n]``."""

    def probe_one(st, rovf):
        rank = core.flush_rank(st)
        packed = jnp.stack([rank[-1], st.overflow.astype(jnp.int32),
                            rovf.astype(jnp.int32)])
        return packed, rank

    vm = jax.vmap(jax.vmap(probe_one)) if job_axis \
        else jax.vmap(probe_one)

    def probe(stacked, rovf):
        return vm(stacked, rovf)

    return profile_dispatch(jax.jit(probe), probe.__qualname__)


class _GrowRetryMixin:
    """The routing-overflow grow-retry plumbing every sharded-fused
    driver shares: per-width epoch cache, sharded device_put, and the
    width-doubling replay. Requires ``_init_retry`` to have run and a
    ``_build_epoch(width)`` implementation."""

    def _init_retry(self, mesh, recv_width: int) -> None:
        self.mesh = mesh
        self.n = mesh.devices.size
        self.recv_width = min(int(recv_width), self.n)
        self._sharding = NamedSharding(mesh, P(SHARD_AXIS))
        self._epochs: dict[int, Callable] = {}   # recv_width -> jitted
        self._pending = None    # (prev_stacked, epoch_args) to retry
        self.epochs_run = 0
        self.route_grows = 0    # grow-retry events (observability)

    def _put(self, stacked):
        return jax.device_put(
            stacked,
            jax.tree_util.tree_map(lambda _: self._sharding, stacked))

    def _build_epoch(self, width: int) -> Callable:
        raise NotImplementedError

    def _epoch_fn(self) -> Callable:
        fn = self._epochs.get(self.recv_width)
        if fn is None:
            fn = self._build_epoch(self.recv_width)
            self._epochs[self.recv_width] = fn
        return fn

    def _grow_and_retry(self):
        """Routing overflow: the last epoch dropped rows on some shard.
        Double the receive width (capped at full n·C, where overflow is
        impossible) and replay the epoch from the untouched pre-epoch
        state — deterministic epoch args make the retry exact."""
        prev, args = self._pending
        self.recv_width = min(max(self.recv_width * 2, 2), self.n)
        self.route_grows += 1
        return self._epoch_fn()(prev, *args)

    # -- the shared retry loop for drivers that hold the epoch's full
    # output tuple in self._out (join / session / q3). Subclasses set
    # _PACKED_POS (index of the packed array in the tuple) and _OVF_COL
    # (packed column carrying the per-shard route-overflow flag).
    _PACKED_POS: int = -1
    _OVF_COL: int = -1

    def _settle(self) -> None:
        """Validate a still-pending epoch (routing overflow →
        grow-retry) before piling another one on top of it. The usual
        driver cadence — run_epoch, flush, run_epoch, … — settles
        inside flush() for free; this extra fetch is paid only by
        epoch-chaining callers."""
        while self._pending is not None:
            packed_h = np.asarray(fetch(self._out[self._PACKED_POS]))
            if packed_h[:, self._OVF_COL].any():
                self._out = self._grow_and_retry()
                self.stacked = self._out[0]
            else:
                self._pending = None

    def _settled_packed(self) -> np.ndarray:
        """The flush-side twin: retry until the packed flags are
        overflow-free, clear the pending marker, return the host copy
        (ONE fetch per attempt covers flags AND the retry signal)."""
        while True:
            packed_h = np.asarray(fetch(self._out[self._PACKED_POS]))
            if self._pending is not None and \
                    packed_h[:, self._OVF_COL].any():
                self._out = self._grow_and_retry()
                self.stacked = self._out[0]
                continue
            break
        self._pending = None
        return packed_h


class _ShardedFusedBase(_GrowRetryMixin):
    """Shared mesh/state plumbing for the single-job sharded drivers."""

    def __init__(self, mesh, core, chunk_fn, exprs, rows_per_chunk: int,
                 recv_width: int = 2, states: Optional[Sequence] = None):
        self._init_retry(mesh, recv_width)
        self.core = core
        self.chunk_fn = chunk_fn
        self.exprs = tuple(exprs)
        self.rows_per_chunk = int(rows_per_chunk)
        if states is None:
            states = [core.init_state() for _ in range(self.n)]
        if len(states) != self.n:
            raise ValueError(
                f"{len(states)} shard states for a {self.n}-device mesh")
        self.stacked = self._put(stack_states(list(states)))

    # -- per-shard state views (solo-shaped; checkpoint/test surface) ---------

    def shard_states(self) -> list:
        return unstack_states(self.stacked, self.n)

    def set_states(self, states: Sequence) -> None:
        self.stacked = self._put(stack_states(list(states)))


class ShardedFusedAgg(_ShardedFusedBase):
    """The q5 shape (source → project → AggCore) fused over a mesh."""

    def __init__(self, mesh, core, chunk_fn, exprs, rows_per_chunk: int,
                 recv_width: int = 2, states: Optional[Sequence] = None):
        super().__init__(mesh, core, chunk_fn, exprs, rows_per_chunk,
                         recv_width, states)
        self._rovf = jnp.zeros(self.n, jnp.bool_)
        self._probe = _sharded_agg_probe(core)
        self._finish = multi_agg_finish(core)
        self._gather = gather_job_flush_chunk(core)

    def _build_epoch(self, width: int) -> Callable:
        return sharded_agg_epoch(self.chunk_fn, self.exprs, self.core,
                                 self.rows_per_chunk, self.mesh, width)

    def _settle(self) -> None:
        """Validate a still-pending epoch (routing overflow → grow-retry)
        before piling another one on top of it. The usual driver cadence
        — run_epoch, flush, run_epoch, … — settles inside flush() for
        free; this extra fetch is paid only by epoch-chaining callers."""
        while self._pending is not None:
            if bool(np.any(np.asarray(fetch(self._rovf)))):
                self.stacked, self._rovf = self._grow_and_retry()
            else:
                self._pending = None

    def run_epoch(self, start: int, key, k: int) -> None:
        """ONE dispatch: k chunks generated, routed and aggregated across
        the whole mesh. Validation (routing overflow) settles at the next
        ``flush()`` — same tick, zero extra host syncs."""
        self._settle()
        args = (jnp.int64(start), key, int(k))
        self._pending = (self.stacked, args)
        self.stacked, self._rovf = self._epoch_fn()(self.stacked, *args)
        self.epochs_run += 1

    def flush(self) -> list:
        """Barrier flush: one packed [n, 3] fetch for every shard's dirty
        count / overflow / route flag, per-window churn gathers (traced
        shard index — one compiled gather for the mesh), one vmapped
        finish. Returns the flush StreamChunks in shard-major order."""
        while True:
            packed, ranks = self._probe(self.stacked, self._rovf)
            packed_h = np.asarray(
                fetch(packed, dispatch=self._probe.__qualname__))
            if self._pending is not None and packed_h[:, 2].any():
                self.stacked, self._rovf = self._grow_and_retry()
                continue
            break
        self._pending = None
        self._rovf = jnp.zeros(self.n, jnp.bool_)
        chunks = []
        for s in range(self.n):
            n_dirty, overflow = int(packed_h[s, 0]), int(packed_h[s, 1])
            if overflow:
                raise RuntimeError(
                    f"sharded fused agg: shard {s} group table overflow "
                    f"(per-shard capacity {self.core.capacity}); increase "
                    "agg_table_capacity")
            lo = 0
            while lo < n_dirty:
                chunks.append(self._gather(self.stacked, ranks,
                                           jnp.int64(s), jnp.int64(lo)))
                lo += self.core.groups_per_chunk
        self.stacked = self._finish(self.stacked)
        return chunks

    def checkpoint(self, engine, epoch: int) -> None:
        """Write every shard's checkpoint delta through ONE
        HashAggExecutor persistence engine (its own state-table flush —
        hash partitioning keeps per-shard keys disjoint, so the deltas
        union cleanly in the shared table), then restack once."""
        states = []
        for s in range(self.n):
            engine.state = index_state(self.stacked, s)
            engine._checkpoint_to_state_table(epoch)
            states.append(engine.state)
        self.set_states(states)

    def merged_group_values(self) -> dict:
        """All shards' live groups → {key_tuple: (lanes...)}. Test/debug
        surface (production egress is the flush chunks)."""
        host = jax.device_get(self.stacked)
        out: dict = {}
        for s in range(self.n):
            st = jax.tree_util.tree_map(lambda x: x[s], host)
            occ = np.asarray(st.table.occupied)
            live = np.asarray(st.lanes[0]) > 0
            kd = [np.asarray(x) for x in st.table.key_data]
            km = [np.asarray(x) for x in st.table.key_mask]
            lanes = [np.asarray(x) for x in st.lanes]
            for slot in np.nonzero(occ & live)[0]:
                key = tuple(kd[c][slot].item() if km[c][slot] else None
                            for c in range(len(kd)))
                out[key] = tuple(l[slot].item() for l in lanes)
        return out


class ShardedFusedJoin(_ShardedFusedBase):
    """The q7 shape (source → project → bucketed interval join + max
    flush) fused over a mesh. ``core``: the PER-SHARD IntervalJoinCore —
    windows spread uniformly under the vnode hash, so its ring only
    needs ~1/n of the solo bucket count."""

    def __init__(self, mesh, core, chunk_fn, exprs, rows_per_chunk: int,
                 recv_width: int = 2, states: Optional[Sequence] = None):
        super().__init__(mesh, core, chunk_fn, exprs, rows_per_chunk,
                         recv_width, states)
        self._out = None        # last epoch's full output tuple

        def gather_flush(stacked, dels, inss, olds, s, lo,
                         out_capacity: int):
            st = index_state(stacked, s)
            return core.gather_flush(st, dels[s], inss[s], olds[s], lo,
                                     out_capacity)

        def gather_probe(probe_out, s, lo, out_capacity: int):
            pj = jax.tree_util.tree_map(lambda x: x[s], probe_out)
            return gather_units_window(flatten_shards(pj), lo,
                                       out_capacity)

        self._gather_flush = profile_dispatch(
            jax.jit(gather_flush, static_argnames=("out_capacity",)),
            gather_flush.__qualname__)
        self._gather_probe = profile_dispatch(
            jax.jit(gather_probe, static_argnames=("out_capacity",)),
            gather_probe.__qualname__)

    _PACKED_POS = 5
    _OVF_COL = 5

    def _build_epoch(self, width: int) -> Callable:
        return sharded_join_epoch(self.chunk_fn, self.exprs, self.core,
                                  self.rows_per_chunk, self.mesh, width)

    def run_epoch(self, start: int, key, k: int) -> None:
        """ONE dispatch: ingest + probe emission + the barrier flush plan
        for every shard (the join epoch body flushes in-dispatch)."""
        self._settle()
        args = (jnp.int64(start), key, int(k))
        self._pending = (self.stacked, args)
        self._out = self._epoch_fn()(self.stacked, *args)
        self.stacked = self._out[0]
        self.epochs_run += 1

    def flush(self, out_capacity: int):
        """Drain the epoch's two emission surfaces. ONE [n, 6] packed
        fetch covers every shard's flags, counts and the route-overflow
        retry signal. Returns ``(probe_chunks, churn_chunks)``."""
        if self._out is None:
            return [], []
        packed_h = self._settled_packed()
        _, probe_out, del_m, ins_m, old_emitted, _ = self._out
        probe_chunks, churn_chunks = [], []
        for s in range(self.n):
            n_flush, ovf, clobber, sawdel, n_probe, _ = (
                int(x) for x in packed_h[s])
            if ovf or clobber or sawdel:
                raise RuntimeError(
                    f"sharded fused join: shard {s} flags ovf={ovf} "
                    f"clobber={clobber} sawdel={sawdel}")
            lo = 0
            while lo < n_probe:
                probe_chunks.append(self._gather_probe(
                    probe_out, jnp.int64(s), jnp.int64(lo),
                    out_capacity=out_capacity))
                lo += out_capacity // 2
            lo = 0
            while lo < n_flush:
                churn_chunks.append(self._gather_flush(
                    self.stacked, del_m, ins_m, old_emitted,
                    jnp.int64(s), jnp.int64(lo),
                    out_capacity=out_capacity))
                lo += out_capacity
        self._out = None
        return probe_chunks, churn_chunks

    # -- checkpoint / recovery -------------------------------------------------

    def export_host(self) -> list:
        """Per-shard checkpoint payloads (IntervalJoinCore.export_host)."""
        return [self.core.export_host(index_state(self.stacked, s))
                for s in range(self.n)]

    def import_host(self, payloads: Sequence) -> None:
        self.set_states([self.core.import_host(p) for p in payloads])


class ShardedFusedSession(_ShardedFusedBase):
    """The q8 shape (source → project → session-gap windows, watermark
    close included) fused over a mesh. ``core``: the PER-SHARD
    SessionWindowCore — keys spread uniformly under the vnode hash, so
    its table and closed buffer only need ~1/n of the solo capacity."""

    def __init__(self, mesh, core, chunk_fn, exprs, rows_per_chunk: int,
                 recv_width: int = 2, states: Optional[Sequence] = None):
        super().__init__(mesh, core, chunk_fn, exprs, rows_per_chunk,
                         recv_width, states)
        self._out = None        # last epoch's (stacked, snapshot, packed)

        def gather_closed(snap, s, n_closed, lo, out_capacity: int):
            sn = jax.tree_util.tree_map(lambda x: x[s], snap)
            return core.gather_closed(sn, n_closed, lo, out_capacity)

        self._gather = profile_dispatch(
            jax.jit(gather_closed, static_argnames=("out_capacity",)),
            gather_closed.__qualname__)

    _PACKED_POS = 2
    _OVF_COL = 5

    def _build_epoch(self, width: int) -> Callable:
        return sharded_session_epoch(self.chunk_fn, self.exprs, self.core,
                                     self.rows_per_chunk, self.mesh, width)

    def run_epoch(self, start: int, key, k: int, watermark: int) -> None:
        """ONE dispatch: k chunks generated, routed by session key and
        sessionized across the whole mesh, plus the watermark close."""
        self._settle()
        args = (jnp.int64(start), key, int(k), jnp.int64(watermark))
        self._pending = (self.stacked, args)
        self._out = self._epoch_fn()(self.stacked, *args)
        self.stacked = self._out[0]
        self.epochs_run += 1

    def flush(self, out_capacity: int) -> list:
        """Drain the epoch's closed sessions. ONE [n, 6] packed fetch
        covers every shard's emission count, sticky flags and the
        route-overflow retry signal; per-shard emission windows gather
        through one compiled gather with a traced shard index."""
        if self._out is None:
            return []
        packed_h = self._settled_packed()
        _, snap, _ = self._out
        chunks = []
        for s in range(self.n):
            n_closed, ovf, covf, sawdel, ooo, _ = (
                int(x) for x in packed_h[s])
            if ovf or covf or sawdel or ooo:
                raise RuntimeError(
                    f"sharded fused session: shard {s} flags "
                    f"table_ovf={ovf} closed_ovf={covf} sawdel={sawdel} "
                    f"out_of_order={ooo}")
            lo = 0
            while lo < n_closed:
                chunks.append(self._gather(
                    snap, jnp.int64(s), jnp.int64(n_closed),
                    jnp.int64(lo), out_capacity=out_capacity))
                lo += out_capacity
        self._out = None
        return chunks

    # -- checkpoint / recovery -------------------------------------------------

    def export_host(self) -> list:
        return [self.core.export_host(index_state(self.stacked, s))
                for s in range(self.n)]

    def import_host(self, payloads: Sequence) -> None:
        self.set_states([self.core.import_host(p) for p in payloads])


class ShardedFusedQ3(_ShardedFusedBase):
    """The TPC-H q3 shape (orders build + lineitem probe + revenue agg +
    global top-n churn) fused over a mesh. Orders, their lineitems and
    their revenue group co-locate under the orderkey vnode; the flush's
    global top-``limit`` runs in-dispatch over an all-gathered candidate
    union, so the churn chunk comes back replicated — the driver reads
    shard 0's copy, ONE extra fetch beyond the packed flags."""

    def __init__(self, mesh, core, chunk_fn, rows_per_chunk: int,
                 recv_width: int = 2, states: Optional[Sequence] = None):
        super().__init__(mesh, core, chunk_fn, (), rows_per_chunk,
                         recv_width, states)
        self._out = None        # last epoch's (stacked, churn, packed)

    _PACKED_POS = 2
    _OVF_COL = 4

    def _build_epoch(self, width: int) -> Callable:
        return sharded_q3_epoch(self.chunk_fn, self.core,
                                self.rows_per_chunk, self.mesh, width)

    def run_epoch(self, start: int, key, k: int) -> None:
        """ONE dispatch: build + probe + aggregate k event chunks across
        the mesh AND recompute the global top-n churn."""
        self._settle()
        args = (jnp.int64(start), key, int(k))
        self._pending = (self.stacked, args)
        self._out = self._epoch_fn()(self.stacked, *args)
        self.stacked = self._out[0]
        self.epochs_run += 1

    def flush(self) -> list:
        """ONE [n, 5] packed fetch (flags + retry signal); the churn
        chunk is the dispatch's own output, replicated per shard —
        shard 0's copy is returned (at top-n cardinality, no windowed
        drain is ever needed)."""
        if self._out is None:
            return []
        packed_h = self._settled_packed()
        for s in range(self.n):
            _n_out, o_ovf, a_ovf, sawdel, _ = (
                int(x) for x in packed_h[s])
            if o_ovf or a_ovf or sawdel:
                raise RuntimeError(
                    f"sharded fused q3: shard {s} flags orders_ovf={o_ovf} "
                    f"agg_ovf={a_ovf} sawdel={sawdel}")
        out = jax.tree_util.tree_map(lambda x: x[0], self._out[1])
        self._out = None
        return [out]

    # -- checkpoint / recovery -------------------------------------------------

    def export_host(self) -> list:
        return [self.core.export_host(index_state(self.stacked, s))
                for s in range(self.n)]

    def import_host(self, payloads: Sequence) -> None:
        self.set_states([self.core.import_host(p) for p in payloads])


# ---------------------------------------------------------------------------
# re-sharding: replay the vnode mapping over durable state so a job
# recovers onto a DIFFERENTLY-sized mesh
# ---------------------------------------------------------------------------


def load_agg_rows(core, rows: Sequence) -> object:
    """Fold state-table rows (keys ++ lanes) into a fresh AggState via
    the SAME bulk loader the executor recovery uses
    (ops/grouped_agg.load_rows_into_state). ``prev_lanes`` ends equal to
    ``lanes``: the recovered snapshot is the baseline downstream already
    saw."""
    state = load_rows_into_state(core, core.init_state(), rows)
    return state.rebaselined()


def load_shard_states(core, rows: Sequence, n_shards: int) -> list:
    """Partition committed agg rows onto ``n_shards`` by REPLAYING the
    vnode mapping (common/hashing.shard_rows — the same ``vnode_of →
    vnode_to_shard`` the in-dispatch all_to_all routes with), then load
    each shard's slice. This is the re-shard path: the durable table is
    shard-count-agnostic, so an 8-shard checkpoint reopens cleanly on a
    4-shard (or solo) mesh."""
    per_shard = shard_rows(core.key_types, rows, n_shards)
    return [load_agg_rows(core, rs) for rs in per_shard]


def _empty_join_payload(core) -> dict:
    nb, W = core.n_buckets, core.W
    return {
        "win_id": np.full(nb, -1, np.int64),
        "fill": np.zeros(nb, np.int32),
        "touched": np.zeros(nb, bool),
        "cur_max": np.full(nb, _NEG, np.int64),
        "cur_cnt": np.zeros(nb, np.int64),
        "emitted_max": np.full(nb, _NEG, np.int64),
        "emitted_live": np.zeros(nb, bool),
        "lane_overflow": np.zeros((), bool),
        "ring_clobber": np.zeros((), bool),
        "saw_delete": np.zeros((), bool),
        "row_data": [np.zeros((nb, W), f.type.np_dtype)
                     for f in core.probe_schema],
        "row_mask": [np.zeros((nb, W), bool) for _ in core.probe_schema],
    }


_JOIN_BUCKET_FIELDS = ("win_id", "fill", "touched", "cur_max", "cur_cnt",
                       "emitted_max", "emitted_live")
_JOIN_FLAG_FIELDS = ("lane_overflow", "ring_clobber", "saw_delete")


def reshard_join_payloads(old_core, payloads: Sequence, new_core,
                          new_n: int) -> list:
    """Re-bucket per-shard interval-join checkpoint payloads onto a
    ``new_n``-shard mesh: every resident window re-routes by replaying
    the vnode mapping over its window-start value — the exact hash the
    in-dispatch all_to_all applies to that window's rows — and lands at
    ``win_id % new_nb`` in its new owner's ring. Ring geometry may shrink
    with the mesh (windows spread ~uniformly); a destination collision
    (two live windows sharing a slot) raises instead of clobbering."""
    if old_core.W != new_core.W or \
            len(old_core.probe_schema) != len(new_core.probe_schema):
        raise ValueError("re-shard requires identical lane geometry "
                         "(lane_width / probe schema)")
    if old_core.window_us != new_core.window_us or \
            old_core.ts_col != new_core.ts_col or \
            old_core.probe_schema[old_core.ts_col].type.np_dtype != \
            new_core.probe_schema[new_core.ts_col].type.np_dtype:
        # win_id values are copied verbatim: a different window (or ts
        # layout) would relabel every resident window AND route it
        # differently than the live all_to_all — refuse, don't split-brain
        raise ValueError("re-shard requires identical window config "
                         "(window_us / ts_col)")
    nb_new = new_core.n_buckets
    ts_dtype = old_core.probe_schema[old_core.ts_col].type.np_dtype
    outs = [_empty_join_payload(new_core) for _ in range(new_n)]
    for p in payloads:
        for f in _JOIN_FLAG_FIELDS:
            flag = bool(np.asarray(p[f]))
            for o in outs:      # sticky flags stay visible on every shard
                o[f] = o[f] | flag
        win = np.asarray(p["win_id"])
        idx = np.nonzero(win >= 0)[0]
        if not len(idx):
            continue
        ws = (win[idx] * old_core.window_us).astype(np.dtype(ts_dtype))
        col = Column(jnp.asarray(ws), jnp.ones(len(idx), jnp.bool_))
        shard = np.asarray(vnode_to_shard(vnode_of([col]), new_n))
        slot = win[idx] % nb_new
        for j, b in enumerate(idx):
            s, t = int(shard[j]), int(slot[j])
            o = outs[s]
            if o["win_id"][t] != -1:
                raise RuntimeError(
                    f"re-shard bucket collision on shard {s} slot {t}; "
                    "increase the new core's n_buckets")
            for f in _JOIN_BUCKET_FIELDS:
                o[f][t] = p[f][b]
            for c in range(len(o["row_data"])):
                o["row_data"][c][t] = p["row_data"][c][b]
                o["row_mask"][c][t] = p["row_mask"][c][b]
    return outs


def _route_keys(key_type, keys: Sequence, new_n: int) -> np.ndarray:
    """Owner shard per key value — the host-side replay of the exact
    ``vnode_of → vnode_to_shard`` hash the in-dispatch all_to_all routes
    with, composed from the canonical helpers (never re-derived, so a
    future change to the vnode→shard mapping cannot strand durable
    rows)."""
    vns = vnodes_of_rows((key_type,), [(k,) for k in keys])
    return np.asarray(vnode_to_shard(jnp.asarray(vns, jnp.int32), new_n))


def reshard_session_payloads(core, payloads: Sequence, new_n: int) -> list:
    """Re-partition per-shard session-window checkpoint payloads
    (SessionWindowCore.export_host) onto a ``new_n``-shard mesh: every
    open session re-routes by replaying the vnode mapping over its key —
    the exact hash the in-dispatch all_to_all applies to that key's
    rows — and closed-but-undrained buffer rows follow their key. Sticky
    flags stay visible on every shard. An 8-shard checkpoint reopens
    cleanly on 4 shards (or solo)."""
    open_rows: list = []     # (key, sess_start, last_ts, count)
    closed_rows: list = []   # (key, start, end, cnt)
    flags = {f: False for f in ("overflow", "closed_overflow",
                                "saw_delete", "out_of_order")}
    for p in payloads:
        for f in flags:
            flags[f] = flags[f] or bool(np.asarray(p[f]))
        occ = np.asarray(p["table_occupied"])
        live = occ & (np.asarray(p["sess_start"]) >= 0)
        kd = np.asarray(p["table_key_data"][0])
        for slot in np.nonzero(live)[0]:
            open_rows.append((int(kd[slot]),
                              int(p["sess_start"][slot]),
                              int(p["last_ts"][slot]),
                              int(p["count"][slot])))
        fill = int(np.asarray(p["closed_fill"]))
        for r in range(fill):
            closed_rows.append((int(p["closed_key"][r]),
                                int(p["closed_start"][r]),
                                int(p["closed_end"][r]),
                                int(p["closed_cnt"][r])))
    open_shard = _route_keys(core.key_type, [r[0] for r in open_rows],
                             new_n)
    closed_shard = _route_keys(core.key_type,
                               [r[0] for r in closed_rows], new_n)
    states = []
    for s in range(new_n):
        st = core.init_state()
        mine = [open_rows[i] for i in np.nonzero(open_shard == s)[0]]
        if mine:
            data = np.array([r[0] for r in mine],
                            dtype=core.key_type.np_dtype)
            kcol = Column(jnp.asarray(data),
                          jnp.ones(len(mine), jnp.bool_))
            table, slots, _, ovf = ht_lookup_or_insert(
                st.table, [kcol], jnp.ones(len(mine), jnp.bool_))
            if bool(ovf):
                raise RuntimeError(
                    f"session re-shard: shard {s} key table overflow "
                    f"(capacity {core.capacity}); increase capacity")
            st = st.replace(
                table=table,
                sess_start=st.sess_start.at[slots].set(
                    jnp.asarray([r[1] for r in mine], jnp.int64)),
                last_ts=st.last_ts.at[slots].set(
                    jnp.asarray([r[2] for r in mine], jnp.int64)),
                count=st.count.at[slots].set(
                    jnp.asarray([r[3] for r in mine], jnp.int64)))
        cmine = [closed_rows[i] for i in np.nonzero(closed_shard == s)[0]]
        if cmine:
            if len(cmine) > core.closed_capacity:
                raise RuntimeError(
                    f"session re-shard: shard {s} closed buffer overflow")
            pos = jnp.arange(len(cmine))
            st = st.replace(
                closed_key=st.closed_key.at[pos].set(
                    jnp.asarray([r[0] for r in cmine], jnp.int64)),
                closed_start=st.closed_start.at[pos].set(
                    jnp.asarray([r[1] for r in cmine], jnp.int64)),
                closed_end=st.closed_end.at[pos].set(
                    jnp.asarray([r[2] for r in cmine], jnp.int64)),
                closed_cnt=st.closed_cnt.at[pos].set(
                    jnp.asarray([r[3] for r in cmine], jnp.int64)),
                closed_fill=jnp.asarray(len(cmine), jnp.int32))
        st = st.replace(**{
            f: jnp.asarray(v, jnp.bool_) for f, v in flags.items()})
        states.append(st)
    return states


def reshard_q3_payloads(core, payloads: Sequence, new_n: int) -> list:
    """Re-partition per-shard q3 checkpoint payloads
    (Q3Core.export_host) onto a ``new_n``-shard mesh: qualifying orders
    (key + odate/prio lanes) and their revenue groups re-route by the
    orderkey vnode — the same hash the in-dispatch all_to_all routes
    events with, so an order and its group always land together — and
    the replicated emitted top-n buffer copies to every shard. Requires
    the same core geometry (capacities / limit are mesh-independent)."""
    order_rows: list = []    # (okey, odate, prio)
    agg_rows: list = []      # (okey, *lanes)
    flags = {f: False for f in ("orders_overflow", "saw_delete")}
    agg_overflow = False
    for p in payloads:
        for f in flags:
            flags[f] = flags[f] or bool(np.asarray(p[f]))
        agg = p["agg"]
        agg_overflow = agg_overflow or bool(np.asarray(agg.overflow))
        occ = np.asarray(p["orders_occupied"])
        kd = np.asarray(p["orders_key_data"][0])
        for slot in np.nonzero(occ)[0]:
            order_rows.append((int(kd[slot]), int(p["odate"][slot]),
                               int(p["prio"][slot])))
        aocc = np.asarray(agg.table.occupied)
        akd = np.asarray(agg.table.key_data[0])
        lanes = [np.asarray(l) for l in agg.lanes]
        for slot in np.nonzero(aocc)[0]:
            agg_rows.append((int(akd[slot]),)
                            + tuple(int(l[slot]) for l in lanes))
    from ..common.types import INT64
    order_shard = _route_keys(INT64, [r[0] for r in order_rows], new_n)
    agg_by_shard = [[] for _ in range(new_n)]
    for r, s in zip(agg_rows,
                    _route_keys(INT64, [r[0] for r in agg_rows], new_n)):
        agg_by_shard[int(s)].append(r)
    emitted = payloads[0]       # replicated across shards by the flush
    states = []
    for s in range(new_n):
        st = core.init_state()
        mine = [order_rows[i] for i in np.nonzero(order_shard == s)[0]]
        if mine:
            data = np.array([r[0] for r in mine], dtype=np.int64)
            kcol = Column(jnp.asarray(data),
                          jnp.ones(len(mine), jnp.bool_))
            orders, slots, _, ovf = ht_lookup_or_insert(
                st.orders, [kcol], jnp.ones(len(mine), jnp.bool_))
            if bool(ovf):
                raise RuntimeError(
                    f"q3 re-shard: shard {s} orders table overflow "
                    f"(capacity {core.orders_capacity})")
            st = st.replace(
                orders=orders,
                odate=st.odate.at[slots].set(
                    jnp.asarray([r[1] for r in mine], jnp.int64)),
                prio=st.prio.at[slots].set(
                    jnp.asarray([r[2] for r in mine], jnp.int64)))
        agg_state = load_rows_into_state(core.agg, st.agg,
                                         agg_by_shard[s])
        st = st.replace(
            agg=agg_state.rebaselined().replace(
                overflow=jnp.asarray(agg_overflow, jnp.bool_)),
            emitted_key=jnp.asarray(emitted["emitted_key"]),
            emitted_rev=jnp.asarray(emitted["emitted_rev"]),
            emitted_odate=jnp.asarray(emitted["emitted_odate"]),
            emitted_prio=jnp.asarray(emitted["emitted_prio"]),
            emitted_valid=jnp.asarray(emitted["emitted_valid"]),
            **{f: jnp.asarray(v, jnp.bool_) for f, v in flags.items()})
        states.append(st)
    return states


# ---------------------------------------------------------------------------
# co-scheduled groups × the shard axis: the K-jobs × S-shards driver
# ---------------------------------------------------------------------------


#: dispatch_count / profiler identity of the K×S group epoch
SHARDED_GROUP_EPOCH_FN = \
    "build_sharded_group_epoch.<locals>.sharded_coscheduled_epoch"


class ShardedCoGroup(_GrowRetryMixin, JobAxisGroup):
    """One signature's job set sharded over a mesh: K signature-equal
    source+agg MVs × S shards tick in ONE dispatch per epoch
    (ops/fused_sharded.build_sharded_group_epoch — the sixth fusion
    surface). State leaves carry ``[n_shards, J, ...]`` with the leading
    axis on the mesh; per-job identity (event cursor, PRNG seed, batch
    counter) rides as data exactly like the mesh-less CoGroup, and the
    routing-overflow grow-retry is the ShardedFusedAgg idiom applied
    group-wide (one overflowing job replays the whole group's epoch from
    the untouched previous state — deterministic, so the retry is
    exact for every member)."""

    epoch_qualname = SHARDED_GROUP_EPOCH_FN

    def __init__(self, mesh, spec, recv_width: int = 2):
        if spec.kind != "agg":
            raise ValueError(
                "sharded co-scheduling covers the source+agg shape only")
        self._init_retry(mesh, recv_width)
        self.core = spec.core
        self.chunk_fn = spec.chunk_fn
        self.exprs = tuple(spec.exprs)
        self.rows_per_chunk = int(spec.rows_per_chunk)
        self.signature = spec.signature
        self.names: list[str] = []
        self.starts: list[int] = []
        self.batch_nos: list[int] = []
        self.seeds: list[int] = []
        self.stacked = None
        self._base_keys = None
        self._rovf = None
        self.pending: Optional[PendingFlush] = None
        self._probe = _sharded_agg_probe(self.core, job_axis=True)
        self._finish = profile_dispatch(
            jax.jit(jax.vmap(jax.vmap(self.core.finish_flush))),
            "sharded_group_finish")

        core = self.core

        def gather(stacked, ranks, s, j, lo):
            st = jax.tree_util.tree_map(lambda x: x[s, j], stacked)
            return core.gather_flush_chunk(st, ranks[s, j], lo)

        self._gather = profile_dispatch(jax.jit(gather),
                                        gather.__qualname__)

    def _build_epoch(self, width: int) -> Callable:
        return build_sharded_group_epoch(
            self.chunk_fn, self.exprs, self.core, self.rows_per_chunk,
            self.mesh, width)

    # -- membership -----------------------------------------------------------

    def add(self, name: str, shard_states: Optional[Sequence] = None,
            start: int = 0, seed: int = 0, batch_no: int = 0) -> None:
        """Join the group. ``shard_states``: the job's n solo-shaped
        per-shard states (recovery re-shard), or None for fresh."""
        if name in self.names:
            raise ValueError(f"job {name!r} already sharded-co-scheduled")
        assert self.pending is None, \
            "membership change with a flush in flight (drain first)"
        self._settle()
        self._rovf = None       # shaped [n, J_old]; J changes below
        if shard_states is None:
            shard_states = [self.core.init_state()
                            for _ in range(self.n)]
        if len(shard_states) != self.n:
            raise ValueError(
                f"{len(shard_states)} shard states for a "
                f"{self.n}-device mesh")
        ss = stack_states(list(shard_states))          # leaves [n, ...]
        if self.stacked is None:
            self.stacked = self._put(jax.tree_util.tree_map(
                lambda x: jnp.expand_dims(x, 1), ss))
        else:
            self.stacked = self._put(jax.tree_util.tree_map(
                lambda xs, x: jnp.concatenate(
                    [xs, jnp.expand_dims(x, 1)], axis=1),
                self.stacked, ss))
        self.names.append(name)
        self.starts.append(int(start))
        self.batch_nos.append(int(batch_no))
        self.seeds.append(int(seed))
        self._base_keys = None

    def remove(self, name: str) -> list:
        """Drop a job; returns its final n solo-shaped shard states."""
        assert self.pending is None, \
            "membership change with a flush in flight (drain first)"
        self._settle()
        self._rovf = None       # shaped [n, J_old]; J changes below
        j = self.names.index(name)
        states = self.shard_states_of(name)
        if self.n_jobs > 1:
            self.stacked = self._put(jax.tree_util.tree_map(
                lambda x: jnp.concatenate([x[:, :j], x[:, j + 1:]],
                                          axis=1), self.stacked))
        else:
            self.stacked = None
        for lst in (self.names, self.starts, self.batch_nos, self.seeds):
            lst.pop(j)
        self._base_keys = None
        return states

    def shard_states_of(self, name: str) -> list:
        j = self.names.index(name)
        return [jax.tree_util.tree_map(lambda x: x[s, j], self.stacked)
                for s in range(self.n)]

    # -- ticking --------------------------------------------------------------

    def _settle(self) -> None:
        while self._pending is not None:
            if bool(np.any(np.asarray(fetch(self._rovf)))):
                self.stacked, self._rovf = self._grow_and_retry()
            else:
                self._pending = None

    def run_epoch(self, k: int) -> None:
        """ONE dispatch: every member job advances k chunks across every
        shard of the mesh. Routing-overflow validation settles at the
        next flush — same tick, zero extra host syncs."""
        self._settle()
        starts = jnp.asarray(self.starts, jnp.int64)
        nos = jnp.asarray(self.batch_nos, jnp.int64)
        args = (starts, self._keys(), nos, int(k))
        self._pending = (self.stacked, args)
        self.stacked, self._rovf = self._epoch_fn()(self.stacked, *args)
        for j in range(self.n_jobs):
            self.starts[j] += k * self.rows_per_chunk
            self.batch_nos[j] += 1
        self.epochs_run += 1

    def begin_flush(self) -> PendingFlush:
        """Start the K×S barrier flush without resolving it: probe
        enqueued, packed [n, J, 3] stats streaming host-ward, vmapped
        finish enqueued eagerly so the next epoch can dispatch before
        the fetch resolves (pipeline_depth = 2). The route-overflow
        retry signal rides the same packed fetch, so validation is
        deferred with it — the grow-retry in ``finish_flush`` replays
        from the untouched pre-epoch state the ``_pending`` slot holds
        (sharded epochs never donate)."""
        assert self.pending is None, "flush already in flight"
        packed, ranks = self._probe(
            self.stacked,
            self._rovf if self._rovf is not None
            else jnp.zeros((self.n, self.n_jobs), jnp.bool_))
        self.pending = PendingFlush(
            self.stacked, packed, ranks,
            async_fetch(packed, dispatch=self._probe.__qualname__))
        self.stacked = self._finish(self.stacked)
        return self.pending

    def finish_flush(self) -> dict:
        """Resolve the in-flight K×S flush: ONE packed fetch covers
        every (shard, job) cell's dirty count / overflow / route flag;
        a set route flag drains the pipeline and grow-retries the whole
        group's epoch before gathering. Returns
        {job: [StreamChunk, ...]} in shard-major order per job."""
        p = self.pending
        if p is None:
            p = self.begin_flush()
        self.pending = None
        packed_h = np.asarray(p.fetch.result())
        gather_stacked, ranks = p.stacked, p.ranks
        retried = False
        while self._pending is not None and packed_h[:, :, 2].any():
            # grow-retry drains the pipeline: the replayed epoch (and
            # its re-probe) must validate before anything else may
            # dispatch, so this re-fetch is deliberately synchronous
            gather_stacked, self._rovf = self._grow_and_retry()
            packed, ranks = self._probe(gather_stacked, self._rovf)
            # rwlint: allow(sync-fetch-discipline): grow-retry drain — the replayed epoch must validate before the tick proceeds
            packed_h = np.asarray(jax.device_get(packed))
            # the raw fetch above IS this probe's completion: pop the
            # profiler's inflight FIFO or every later completion would
            # match a stale enqueue timestamp
            GLOBAL_PROFILER.note_complete(self._probe.__qualname__)
            retried = True
        if retried:
            # ONE finish over the settled state (begin_flush already
            # finished the no-retry case; per-iteration finishes would
            # just be discarded dispatches)
            self.stacked = self._finish(gather_stacked)
        self._pending = None
        self._rovf = None
        out: dict = {}
        for j, name in enumerate(self.names):
            chunks = []
            for s in range(self.n):
                n_dirty = int(packed_h[s, j, 0])
                if int(packed_h[s, j, 1]):
                    raise RuntimeError(
                        f"sharded co-scheduled job {name!r}: shard {s} "
                        f"group table overflow (per-shard capacity "
                        f"{self.core.capacity}); increase "
                        "agg_table_capacity")
                lo = 0
                while lo < n_dirty:
                    chunks.append(self._gather(
                        gather_stacked, ranks, jnp.int64(s),
                        jnp.int64(j), jnp.int64(lo)))
                    lo += self.core.groups_per_chunk
            out[name] = chunks
        return out

    # -- durability -----------------------------------------------------------

    def checkpoint(self, engines: dict, epoch: int) -> None:
        """Write every (job, shard) delta through each job's OWN
        HashAggExecutor persistence engine (hash partitioning keeps a
        job's per-shard keys disjoint, so the deltas union cleanly in
        that job's state table), then restack the whole group once."""
        self._settle()
        per_job = []
        for name in self.names:
            engine = engines[name]
            shard_states = []
            for s in range(self.n):
                with restack_span(epoch):
                    engine.state = jax.tree_util.tree_map(
                        lambda x, s=s, j=self.names.index(name): x[s, j],
                        self.stacked)
                engine._checkpoint_to_state_table(epoch)
                shard_states.append(engine.state)
            per_job.append(stack_states(shard_states))
        with restack_span(epoch):
            self.stacked = self._put(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=1), *per_job))


class ShardedCoScheduler:
    """Signature-keyed registry of K×S groups (one per mesh Session) —
    the sharded twin of stream/coschedule.CoScheduler."""

    def __init__(self, mesh, recv_width: int = 2):
        self.mesh = mesh
        self.recv_width = recv_width
        self.groups: dict[tuple, ShardedCoGroup] = {}
        self.jobs: dict[str, ShardedCoGroup] = {}

    def add(self, name: str, spec, shard_states=None, start: int = 0,
            batch_no: int = 0) -> ShardedCoGroup:
        group = self.groups.get(spec.signature)
        if group is None:
            group = ShardedCoGroup(self.mesh, spec,
                                   recv_width=self.recv_width)
            self.groups[spec.signature] = group
        group.add(name, shard_states, start=start, seed=spec.seed,
                  batch_no=batch_no)
        self.jobs[name] = group
        return group

    def remove(self, name: str):
        """Drop a job; returns ``(shard_states, group)`` (group for the
        caller's epoch-retirement bookkeeping) or ``(None, None)``."""
        group = self.jobs.pop(name, None)
        if group is None:
            return None, None
        states = group.remove(name)
        if group.n_jobs == 0:
            self.groups.pop(group.signature, None)
        return states, group

    def stats(self) -> dict:
        return {
            "jobs": len(self.jobs),
            "groups": [
                {"shards": g.n, "jobs": list(g.names),
                 "epochs_run": g.epochs_run,
                 "recv_width": g.recv_width,
                 "route_grows": g.route_grows}
                for g in self.groups.values()
            ],
        }
