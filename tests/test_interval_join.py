"""Bucketed interval-join core (ops/interval_join.py): output parity with
the executor q7 pipeline (HashAgg max → HashJoin price==max), retraction
included, plus checkpoint/recovery, ring turnover, band filter, and
Pallas/jnp kernel parity.

Parity schedule note: a streaming join's intermediate churn depends on the
intra-epoch interleaving of probe chunks vs the agg's flush chunks (any
interleaving is a valid Chandy-Lamport cut; only the net effect is
schedule-independent). The fused core implements the canonical schedule —
all probe chunks of an epoch, then the build flush — which is exactly what
the epoch-batched bench source delivers; the executor run below pins the
same schedule by gating the build-side source on probe progress."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.common import INT64, Schema, chunk_to_rows, make_chunk
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_INSERT,
)
from risingwave_tpu.expr import call, col
from risingwave_tpu.expr.agg import agg as agg_call
from risingwave_tpu.ops.interval_join import (
    IntervalJoinCore, interval_match_jnp, interval_match_pallas,
)
from risingwave_tpu.stream import (
    Barrier, HashAggExecutor, HashJoinExecutor,
)
from risingwave_tpu.stream.executor import Executor

CAP = 64
WINDOW = 100

# probe rows: (window_start, auction, price)
PROBE_SCHEMA = Schema.of(("ws", INT64), ("auction", INT64), ("price", INT64))
PRE_SCHEMA = Schema.of(("ws", INT64), ("price", INT64))


def pchunk(rows):
    return make_chunk(PROBE_SCHEMA, rows, capacity=CAP)


# ---------------------------------------------------------------------------
# executor pipeline, pinned to the canonical probe-then-flush schedule
# ---------------------------------------------------------------------------


class _ProbeSource(Executor):
    """MockSource that releases one gate per epoch AFTER its chunks were
    consumed (just before yielding the epoch's barrier)."""

    identity = "ProbeSource"

    def __init__(self, schema: Schema, messages, gates):
        self.schema = schema
        self._messages = list(messages)
        self._gates = gates
        self._epoch_i = 0

    async def execute(self):
        for m in self._messages:
            if isinstance(m, Barrier):
                self._gates[self._epoch_i].set()
                self._epoch_i += 1
            yield m
            await asyncio.sleep(0)


class _GatedSource(Executor):
    """Build-side source that holds each epoch's chunks until the probe
    side's gate for that epoch opens."""

    identity = "GatedSource"

    def __init__(self, schema: Schema, messages, gates):
        self.schema = schema
        self._messages = list(messages)
        self._gates = gates
        self._epoch_i = 0

    async def execute(self):
        waited = False
        for m in self._messages:
            if not waited:
                await self._gates[self._epoch_i].wait()
                waited = True
            yield m
            await asyncio.sleep(0)
            if isinstance(m, Barrier):
                self._epoch_i += 1
                waited = False


def run_executor_q7(epochs_rows):
    """Drive the REAL q7 executor pipeline over scripted epochs; returns
    per-epoch [(op, row), ...] lists."""
    gates = [asyncio.Event() for _ in range(len(epochs_rows) + 2)]
    probe_msgs, build_msgs = [Barrier.new(1)], [Barrier.new(1)]
    e = 1
    for rows in epochs_rows:
        probe_msgs.append(pchunk(rows))
        build_msgs.append(make_chunk(PRE_SCHEMA,
                                     [(ws, p) for ws, _, p in rows],
                                     capacity=CAP))
        e += 1
        probe_msgs.append(Barrier.new(e))
        build_msgs.append(Barrier.new(e))

    async def drive():
        probe = _ProbeSource(PROBE_SCHEMA, probe_msgs, gates)
        build_pre = _GatedSource(PRE_SCHEMA, build_msgs, gates)
        build = HashAggExecutor(build_pre, [0], [agg_call("max", 1, INT64)],
                                table_capacity=1 << 10, out_capacity=CAP)
        cond = call("equal", col(2, INT64), col(4, INT64))
        join = HashJoinExecutor(
            probe, build, [0], [0], condition=cond,
            key_capacity=1 << 10, bucket_width=16, out_capacity=CAP)
        per_epoch, cur = [], []
        async for m in join.execute():
            from risingwave_tpu.common import StreamChunk
            if isinstance(m, StreamChunk):
                cur.extend(chunk_to_rows(m, join.schema, with_ops=True))
            elif isinstance(m, Barrier):
                per_epoch.append(cur)
                cur = []
        return per_epoch[1:]   # drop the empty first barrier

    return asyncio.run(drive())


# ---------------------------------------------------------------------------
# interval core driver
# ---------------------------------------------------------------------------


def make_core(**kw):
    kw.setdefault("n_buckets", 256)
    kw.setdefault("lane_width", 16)
    return IntervalJoinCore(PROBE_SCHEMA, ts_col=0, val_col=2,
                            window_us=WINDOW, **kw)


def run_core_q7(epochs_rows, core=None, snapshot_at=None):
    """Apply the same epochs through IntervalJoinCore; returns per-epoch
    [(op, row), ...]. ``snapshot_at``: after that epoch index, export the
    state to host numpy and continue on a FRESH core via import_host (the
    checkpoint/recovery cycle)."""
    core = core or make_core()
    apply_c = jax.jit(core.apply_chunk)
    plan = jax.jit(core.flush_plan)
    gather = jax.jit(core.gather_flush, static_argnames=("out_capacity",))
    finish = jax.jit(core.finish_flush)
    state = core.init_state()
    per_epoch = []
    for ei, rows in enumerate(epochs_rows):
        cur = []
        state, out = apply_c(state, pchunk(rows))
        cur.extend(chunk_to_rows(out, core.out_schema, with_ops=True))
        old_emitted = state.emitted_max
        del_m, ins_m, packed = plan(state)
        n_units, ovf, clobber, sawdel = (int(x) for x in np.asarray(packed))
        assert not (ovf or clobber or sawdel)
        lo = 0
        while lo < n_units:
            ch = gather(state, del_m, ins_m, old_emitted, jnp.int64(lo),
                        out_capacity=CAP)
            cur.extend(chunk_to_rows(ch, core.out_schema, with_ops=True))
            lo += CAP
        state = finish(state)
        per_epoch.append(cur)
        if snapshot_at is not None and ei == snapshot_at:
            payload = core.export_host(state)
            core2 = make_core()
            state = core2.import_host(payload)
            apply_c = jax.jit(core2.apply_chunk)
            plan = jax.jit(core2.flush_plan)
            gather = jax.jit(core2.gather_flush,
                             static_argnames=("out_capacity",))
            finish = jax.jit(core2.finish_flush)
    return per_epoch


EPOCHS = [
    # epoch 1: two windows born; window 0 max=9, window 100 max=7
    [(0, 1, 5), (0, 2, 9), (100, 3, 7)],
    # epoch 2: window 0 max unchanged (churn: touched, same max) + a
    # late row equal to the OLD emitted max (probe-time emission, then
    # retracted+re-emitted by the churn flush)
    [(0, 4, 9), (100, 4, 3)],
    # epoch 3: window 0 max RISES → retraction of every price-9 match,
    # new max emitted; window 200 born
    [(0, 5, 12), (200, 6, 4)],
    # epoch 4: quiet window 100 gets a sub-max row (churn only), window
    # 200 tied rows
    [(100, 7, 2), (200, 8, 4), (200, 9, 4)],
]


def net_change(rows) -> dict:
    """An epoch's messages as the change they make: row -> copies added.
    The hash agg emits nothing for a group whose max did not move (ISSUE
    27), the interval core still retracts and re-emits its matches; both
    leave the same rows."""
    import collections
    net = collections.Counter()
    for op, row in rows:
        net[row] += 1 if op in (OP_INSERT, OP_UPDATE_INSERT) else -1
    return {row: n for row, n in net.items() if n}


def test_parity_with_executor_pipeline_under_retraction():
    expected = run_executor_q7(EPOCHS)
    got = run_core_q7(EPOCHS)
    assert len(expected) == len(got)
    for ei, (e_rows, g_rows) in enumerate(zip(expected, got)):
        assert net_change(e_rows) == net_change(g_rows), \
            f"epoch {ei + 1} diverged"
    # retraction actually exercised: epoch 3 must contain DELETEs
    assert any(op == OP_DELETE for op, _ in expected[2])


def test_parity_across_checkpoint_recovery_cycle():
    expected = run_executor_q7(EPOCHS)
    got = run_core_q7(EPOCHS, snapshot_at=1)   # kill+recover mid-run
    for ei, (e_rows, g_rows) in enumerate(zip(expected, got)):
        assert net_change(e_rows) == net_change(g_rows), \
            f"epoch {ei + 1} diverged"


def test_probe_time_emission_against_flushed_max():
    # window flushed with max 9; a later bid at 9 matches at probe time
    per_epoch = run_core_q7([
        [(0, 1, 9)],
        [(0, 2, 9)],
    ])
    # epoch 1: insert of (0,1,9) via flush
    assert (OP_INSERT, (0, 1, 9, 0, 9)) in per_epoch[0]
    # epoch 2 contains the probe-time insert of the late row
    assert (OP_INSERT, (0, 2, 9, 0, 9)) in per_epoch[1]


def test_ring_turnover_reclaims_slots():
    core = make_core(n_buckets=4, lane_width=4)
    apply_c = jax.jit(core.apply_chunk)
    finish = jax.jit(core.finish_flush)
    state = core.init_state()
    # windows 0 and 4*WINDOW map to the same ring slot
    state, _ = apply_c(state, pchunk([(0, 1, 5)]))
    state = finish(state)
    state, _ = apply_c(state, pchunk([(4 * WINDOW, 2, 7)]))
    assert not bool(state.ring_clobber)
    assert int(state.win_id[0]) == 4
    assert int(state.cur_max[0]) == 7       # old window's max was reset
    assert not bool(state.emitted_live[0])  # downstream build row dropped


def test_ring_clobber_of_dirty_slot_is_flagged():
    core = make_core(n_buckets=4, lane_width=4)
    apply_c = jax.jit(core.apply_chunk)
    state = core.init_state()
    # window 0 has an UNFLUSHED delta when window 4 steals its slot
    state, _ = apply_c(state, pchunk([(0, 1, 5)]))
    state, _ = apply_c(state, pchunk([(4 * WINDOW, 2, 7)]))
    assert bool(state.ring_clobber)


def test_probe_delete_sets_sticky_flag():
    core = make_core()
    apply_c = jax.jit(core.apply_chunk)
    state = core.init_state()
    ch = make_chunk(PROBE_SCHEMA, [(0, 1, 5)], ops=[OP_DELETE],
                    capacity=CAP)
    state, _ = apply_c(state, ch)
    assert bool(state.saw_delete)


def test_lane_overflow_sets_sticky_flag():
    core = make_core(lane_width=2)
    apply_c = jax.jit(core.apply_chunk)
    state = core.init_state()
    state, _ = apply_c(state, pchunk([(0, i, i) for i in range(3)]))
    assert bool(state.lane_overflow)


def test_band_filter_restricts_matches():
    # band over the raw ts (col 0 doubles as the band column here):
    # only rows in [win_start, win_start + 50) may match
    core = IntervalJoinCore(PROBE_SCHEMA, ts_col=0, val_col=2,
                            window_us=WINDOW, n_buckets=64, lane_width=8,
                            band_col=0, band_us=50)
    apply_c = jax.jit(core.apply_chunk)
    plan = jax.jit(core.flush_plan)
    gather = jax.jit(core.gather_flush, static_argnames=("out_capacity",))
    state = core.init_state()
    # ts 10 in band; ts 60 (same window, same max price) out of band
    state, _ = apply_c(state, pchunk([(10, 1, 9), (60, 2, 9)]))
    old = state.emitted_max
    del_m, ins_m, packed = plan(state)
    assert int(packed[0]) == 1
    ch = gather(state, del_m, ins_m, old, jnp.int64(0), out_capacity=CAP)
    rows = chunk_to_rows(ch, core.out_schema, with_ops=True)
    assert rows == [(OP_INSERT, (10, 1, 9, 0, 9))]


def test_interval_match_kernel_parity():
    """Pallas (interpret) and jnp formulations are bit-identical."""
    rng = np.random.default_rng(7)
    nb, w = 512, 128
    vals = jnp.asarray(rng.integers(0, 5, (nb, w)), jnp.int64)
    occ = jnp.asarray(rng.random((nb, w)) < 0.7)
    old_max = jnp.asarray(rng.integers(0, 5, nb), jnp.int64)
    new_max = jnp.asarray(rng.integers(0, 5, nb), jnp.int64)
    old_live = jnp.asarray(rng.random(nb) < 0.8)
    new_live = jnp.asarray(rng.random(nb) < 0.8)
    # exercise the 64-bit halves: some values only differ in the high word
    vals = vals + (jnp.asarray(
        rng.integers(0, 2, (nb, w)), jnp.int64) << 33)
    old_max = old_max + (jnp.asarray(
        rng.integers(0, 2, nb), jnp.int64) << 33)
    d0, i0 = interval_match_jnp(vals, occ, old_max, old_live,
                                new_max, new_live)
    d1, i1 = interval_match_pallas(vals, occ, old_max, old_live,
                                   new_max, new_live, interpret=True)
    np.testing.assert_array_equal(np.asarray(d0), np.asarray(d1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
