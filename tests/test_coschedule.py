"""Epoch co-scheduler (stream/coschedule.py + ops/fused_multi.py): K
co-scheduled MVs must tick in EXACTLY one jit dispatch per epoch, and
every per-job result — state, flush churn, checkpoint export — must be
bit-exact against the solo fused path (the vmapped body IS the solo
body; these tests pin that contract for K ∈ {1, 4, 16} and across a
checkpoint/recovery cycle, per the round's acceptance criteria)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common import INT64, TIMESTAMP
from risingwave_tpu.common.chunk import OP_UPDATE_DELETE, OP_UPDATE_INSERT
from risingwave_tpu.common.dispatch_count import count_dispatches
from risingwave_tpu.connector import BID_SCHEMA, NexmarkConfig
from risingwave_tpu.connector.nexmark import DeviceBidGenerator
from risingwave_tpu.expr import Literal, call, col
from risingwave_tpu.expr.agg import agg as agg_call, count_star
from risingwave_tpu.ops import fused_multi as fm
from risingwave_tpu.ops.fused_epoch import fused_source_agg_epoch
from risingwave_tpu.stream import HashAggExecutor, ProjectExecutor
from risingwave_tpu.stream.coschedule import (
    CoGroup, CoScheduler, FusedJobSpec, agg_signature,
)
from risingwave_tpu.stream.source import MockSource

CAP = 256
GROUP_EPOCH_FN = "build_group_epoch.<locals>.coscheduled_epoch"


def _parts(calls=None, table_capacity=1 << 12):
    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(1_000_000, INT64)),
        col(0, INT64),
        col(2, INT64),
    ]
    proj = ProjectExecutor(MockSource(BID_SCHEMA, []), exprs,
                           names=("ws", "auction", "price"))
    agg = HashAggExecutor(
        proj, [0, 1], list(calls or [count_star()]),
        table_capacity=table_capacity, out_capacity=CAP)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CAP))
    return exprs, agg, gen.chunk_fn()


def _mk_group(n_jobs, calls=None):
    exprs, agg, chunk_fn = _parts(calls)
    spec = FusedJobSpec(
        "agg", agg_signature(agg.core, exprs, CAP, ("nexmark_bid", CAP)),
        chunk_fn, tuple(exprs), agg.core, CAP, seed=0)
    group = CoGroup(spec)
    for j in range(n_jobs):
        group.add(f"mv{j}", agg.core.init_state(), seed=100 + j)
    return exprs, agg, chunk_fn, group


def _solo_epoch_and_flush(solo, agg, state, start, key, k):
    """The solo fused path's full epoch: one fused dispatch + the
    executor's own jitted flush helpers (bench measure_q5_fused)."""
    state = solo(state, jnp.int64(start), key, k)
    packed, rank = agg._probe(state)
    n_dirty, overflow, _ = (int(x) for x in jax.device_get(packed))
    assert not overflow
    chunks = []
    lo = 0
    while lo < n_dirty:
        chunks.append(agg._gather(state, rank, jnp.int64(lo)))
        lo += agg.core.groups_per_chunk
    return agg._finish(state), chunks


def _assert_tree_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n_jobs", [1, 4, 16])
def test_k_jobs_one_dispatch_per_epoch(n_jobs):
    """THE acceptance regression: K co-scheduled MVs = exactly 1 jit
    dispatch per epoch, independent of K, and the whole group's barrier
    probe/finish are 1 vmapped dispatch each (only per-job output
    gathers scale with K — they are per-job data)."""
    with count_dispatches() as c:
        _, agg, _, group = _mk_group(n_jobs)
        group.run_epoch(4)
        group.flush()
        c.reset()
        group.run_epoch(4)
        assert c.counts[GROUP_EPOCH_FN] == 1
        assert c.total == 1          # nothing else dispatched at all
        c.reset()
        group.flush()
        non_gather = sum(n for name, n in c.counts.items()
                         if "gather" not in name)
        assert non_gather == 2       # one vmapped probe + one finish
        c.reset()
        group.run_epoch(8)           # k changes; still one dispatch
        assert c.counts[GROUP_EPOCH_FN] == 1
        assert c.total == 1


@pytest.mark.parametrize("n_jobs", [1, 4, 16])
def test_coscheduled_bit_exact_vs_solo(n_jobs):
    """Per-job states AND flush churn bit-exact vs the solo fused path,
    over several epochs (distinct per-job PRNG seeds / event cursors)."""
    exprs, agg, chunk_fn, group = _mk_group(n_jobs)
    solo = fused_source_agg_epoch(chunk_fn, exprs, agg.core, CAP)
    k = 4
    flushes = []
    for _ in range(3):
        group.run_epoch(k)
        flushes.append(group.flush())
    for j in range(n_jobs):
        st = agg.core.init_state()
        start = 0
        solo_chunks_all = []
        for e in range(3):
            key = jax.random.fold_in(jax.random.PRNGKey(100 + j), e)
            st, chunks = _solo_epoch_and_flush(solo, agg, st, start, key, k)
            start += k * CAP
            solo_chunks_all.append(chunks)
        _assert_tree_equal(group.state_of(f"mv{j}"), st)
        for e in range(3):
            got = flushes[e][f"mv{j}"]
            assert len(got) == len(solo_chunks_all[e])
            for ca, cb in zip(got, solo_chunks_all[e]):
                _assert_tree_equal(ca, cb)


def test_flush_emits_retraction_churn():
    """After the first epoch the flush carries the executor's U-/U+
    retraction pairs for touched groups — the co-scheduled path must
    reproduce that retraction stream, not just inserts."""
    _, agg, _, group = _mk_group(2)
    group.run_epoch(4)
    group.flush()
    group.run_epoch(4)
    outs = group.flush()
    ops = np.concatenate([np.asarray(c.ops)[np.asarray(c.vis)]
                          for c in outs["mv0"]])
    assert (ops == OP_UPDATE_DELETE).any()
    assert (ops == OP_UPDATE_INSERT).any()


def test_checkpoint_recovery_cycle_bit_exact():
    """Export every job's state mid-stream (the checkpoint payload),
    rebuild a fresh group from the exported copies, continue both —
    bit-exact. Proves the job-axis stacking round-trips through
    recovery."""
    exprs, agg, chunk_fn, group = _mk_group(4)
    group.run_epoch(4)
    group.flush()

    spec = FusedJobSpec(
        "agg", agg_signature(agg.core, exprs, CAP, ("nexmark_bid", CAP)),
        chunk_fn, tuple(exprs), agg.core, CAP, seed=0)
    recovered = CoGroup(spec)
    for j in range(4):
        host = jax.device_get(group.state_of(f"mv{j}"))   # checkpoint
        state = jax.tree_util.tree_map(jnp.asarray, host)  # recovery
        recovered.add(f"mv{j}", state, start=group.starts[j],
                      seed=100 + j, batch_no=group.batch_nos[j])

    group.run_epoch(4)
    f1 = group.flush()
    recovered.run_epoch(4)
    f2 = recovered.flush()
    _assert_tree_equal(group.stacked, recovered.stacked)
    for name in f1:
        for ca, cb in zip(f1[name], f2[name]):
            _assert_tree_equal(ca, cb)


def test_signature_separates_incompatible_jobs():
    """Different agg calls / shapes => different trace => different
    group; same signature => same group (the grouping rule)."""
    sched = CoScheduler()
    exprs, agg1, chunk_fn = _parts()
    sig1 = agg_signature(agg1.core, exprs, CAP, ("nexmark_bid", CAP))
    _, agg2, _ = _parts(calls=[count_star(), agg_call("max", 2, INT64)])
    sig2 = agg_signature(agg2.core, exprs, CAP, ("nexmark_bid", CAP))
    assert sig1 != sig2
    g1 = sched.add("a", FusedJobSpec("agg", sig1, chunk_fn, tuple(exprs),
                                     agg1.core, CAP, seed=1),
                   agg1.core.init_state())
    g2 = sched.add("b", FusedJobSpec("agg", sig1, chunk_fn, tuple(exprs),
                                     agg1.core, CAP, seed=2),
                   agg1.core.init_state())
    g3 = sched.add("c", FusedJobSpec("agg", sig2, chunk_fn, tuple(exprs),
                                     agg2.core, CAP, seed=3),
                   agg2.core.init_state())
    assert g1 is g2 and g1 is not g3
    assert sched.stats()["jobs"] == 3
    assert len(sched.stats()["groups"]) == 2
    st = sched.remove("a")
    assert st is not None and g1.n_jobs == 1
    sched.remove("b")
    assert sig1 not in sched.groups


def test_multi_join_epoch_bit_exact_vs_solo():
    """The source+join group shape (ops/fused_multi.fused_multi_join_epoch
    over IntervalJoinCore): one dispatch for J jobs, every output slice
    bit-exact vs the solo fused join epoch."""
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.ops.fused_epoch import fused_source_join_epoch
    from risingwave_tpu.ops.interval_join import IntervalJoinCore
    from risingwave_tpu.stream.coschedule import join_signature

    W = 5_000
    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(W, INT64)),
        col(0, INT64),
        col(2, INT64),
    ]
    ps = Schema((Field("ws", TIMESTAMP), Field("auction", INT64),
                 Field("price", INT64)))
    core = IntervalJoinCore(ps, ts_col=0, val_col=2, window_us=W,
                            n_buckets=512, lane_width=64)
    # the join-group grouping rule: same core config => same signature,
    # a different window => a different trace => a different group
    other = IntervalJoinCore(ps, ts_col=0, val_col=2, window_us=2 * W,
                             n_buckets=512, lane_width=64)
    sig = join_signature(core, exprs, CAP, ("nexmark_bid", CAP))
    assert sig == join_signature(core, exprs, CAP, ("nexmark_bid", CAP))
    assert sig != join_signature(other, exprs, CAP, ("nexmark_bid", CAP))
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CAP))
    chunk_fn = gen.chunk_fn()
    solo = fused_source_join_epoch(chunk_fn, exprs, core, CAP)
    with count_dispatches() as c:
        multi = fm.fused_multi_join_epoch(chunk_fn, exprs, core, CAP)
        J, k = 3, 4
        stacked = fm.stack_states([core.init_state() for _ in range(J)])
        starts = jnp.arange(J, dtype=jnp.int64) * 777
        keys = jnp.stack([jax.random.PRNGKey(j) for j in range(J)])
        res = multi(stacked, starts, keys, k)
        c.reset()
        res = multi(res[0], starts + k * CAP, keys, k)
        assert c.counts["fused_multi_join_epoch.<locals>.epoch"] == 1
        assert c.total == 1
    per_job = fm.unstack_states(res[0], J)
    for j in range(J):
        st = core.init_state()
        for e in range(2):
            out = solo(st, jnp.int64(j * 777 + e * k * CAP),
                       jax.random.PRNGKey(j), k)
            st = out[0]
        _assert_tree_equal(per_job[j], st)
        for got, want in zip(res[1:], out[1:]):
            _assert_tree_equal(fm.index_state(got, j), want)


# ---------------------------------------------------------------------------
# Session integration: CREATE MATERIALIZED VIEW routing, ticking, DROP,
# durability (opt-in via BuildConfig.coschedule / [streaming] coschedule)
# ---------------------------------------------------------------------------

SRC_SQL = """CREATE SOURCE bid (auction BIGINT, bidder BIGINT,
    price BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
    extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'bid')"""
MV_SQL = ("CREATE MATERIALIZED VIEW {n} AS SELECT auction, count(*) AS c "
          "FROM bid GROUP BY auction")


def _session(tmp_path=None, coschedule=True, **kw):
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig
    return Session(config=BuildConfig(coschedule=coschedule,
                                      agg_table_capacity=1 << 12),
                   source_chunk_capacity=CAP,
                   data_dir=str(tmp_path) if tmp_path else None, **kw)


def test_session_groups_and_single_dispatch_per_tick():
    with count_dispatches() as c:
        s = _session()
        try:
            s.run_sql(SRC_SQL)
            for j in range(3):
                s.run_sql(MV_SQL.format(n=f"m{j}"))
            stats = s.metrics()["coschedule"]
            assert stats["jobs"] == 3
            assert [g["jobs"] for g in stats["groups"]] == [
                ["m0", "m1", "m2"]]
            s.tick()
            c.reset()
            s.tick()
            # the whole 3-MV group ingests in ONE dispatch per tick
            assert c.counts[GROUP_EPOCH_FN] == 1
            total = sum(r[1] for r in s.run_sql(
                "SELECT auction, c FROM m1"))
            assert total == 2 * CAP
            # MVs answer independently and identically-shaped
            assert s.run_sql("SELECT sum(c) FROM m0") == [(2 * CAP,)]
        finally:
            s.close()


def test_session_drop_and_solo_fallback():
    s = _session()
    try:
        s.run_sql(SRC_SQL)
        s.run_sql(MV_SQL.format(n="m0"))
        # ineligible shape (no grouped agg over the source) falls back to
        # the executor path and does NOT join the scheduler
        s.run_sql("CREATE MATERIALIZED VIEW raw AS SELECT auction, price "
                  "FROM bid")
        stats = s.metrics()["coschedule"]
        assert stats["jobs"] == 1
        s.tick()
        s.run_sql("DROP MATERIALIZED VIEW m0")
        assert s.metrics()["coschedule"]["jobs"] == 0
        s.tick()                       # scheduler empty; ticking still fine
        assert len(s.run_sql("SELECT * FROM raw")) == 2 * CAP
    finally:
        s.close()


def test_session_coschedule_recovery(tmp_path):
    s = _session(tmp_path, checkpoint_frequency=2)
    s.run_sql(SRC_SQL)
    s.run_sql(MV_SQL.format(n="m0"))
    for _ in range(5):                 # epochs 2..6; checkpoints at 2,4,6
        s.tick()
    committed = dict(s.run_sql("SELECT auction, c FROM m0"))
    s.close()

    s2 = _session(tmp_path, checkpoint_frequency=2)
    try:
        assert s2.metrics()["coschedule"]["jobs"] == 1
        # recovered at the last checkpoint cut, bit-exact
        assert dict(s2.run_sql("SELECT auction, c FROM m0")) == committed
        # deterministic source cursor resumes: 3 more ticks add exactly
        # 3 * CAP rows on top of the recovered cut
        base = sum(committed.values())
        for _ in range(3):
            s2.tick()
        assert s2.run_sql("SELECT sum(c) FROM m0") == [(base + 3 * CAP,)]
    finally:
        s2.close()


@pytest.mark.parametrize("kind,marker,flags", [
    ("coschedule", "-- coschedule m0", dict(coschedule=True)),
    ("hetero", "-- hetero m0", dict(tick_compiler=True)),
    ("shardfused", "-- shardfused m0", dict(coschedule=True, mesh_n=4)),
])
def test_session_marker_line_routes_back_to_its_kind(kind, marker, flags,
                                                     tmp_path):
    """The DDL log's marker lines are a durable format: a ``data_dir``
    written by an earlier build must reopen. The line is spelled here,
    not derived from the registry's table."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    def open_(**more):
        f = {**flags, **more}
        n = f.pop("mesh_n", 0)
        return Session(config=BuildConfig(
            mesh=make_mesh(n) if n else None, agg_table_capacity=1 << 12,
            **f), source_chunk_capacity=CAP, checkpoint_frequency=2,
            data_dir=str(tmp_path))

    s = open_()
    s.run_sql(SRC_SQL)
    s.run_sql(MV_SQL.format(n="m0"))
    for _ in range(3):
        s.tick()
    ddl = s.store.log.ddl()
    assert ddl.count(marker) == 1 and ddl.index(marker) == len(ddl) - 2
    committed = dict(s.run_sql("SELECT auction, c FROM m0"))
    s.close()
    # reopened with every mesh-less kind's flag on: only the marker says
    # which of them laid the tables out
    s2 = open_(**({} if kind == "shardfused"
                  else dict(coschedule=True, tick_compiler=True)))
    try:
        assert s2._fused.engines["m0"].kind.name == kind
        assert dict(s2.run_sql("SELECT auction, c FROM m0")) == committed
    finally:
        s2.close()


def test_session_solo_mv_reopened_with_flag_stays_solo(tmp_path):
    """The reverse recovery direction: an MV created WITHOUT the flag
    must replay down the executor path even when the session reopens
    with coschedule=true — the solo table-id layout only decodes there
    (marker-directed routing in both directions)."""
    s = _session(tmp_path, coschedule=False, checkpoint_frequency=2)
    s.run_sql(SRC_SQL)
    s.run_sql(MV_SQL.format(n="m0"))
    for _ in range(5):
        s.tick()
    committed = dict(s.run_sql("SELECT auction, c FROM m0"))
    s.close()

    s2 = _session(tmp_path, coschedule=True, checkpoint_frequency=2)
    try:
        # recovered on the executor path, NOT captured by the scheduler
        assert s2.metrics()["coschedule"]["jobs"] == 0
        assert dict(s2.run_sql("SELECT auction, c FROM m0")) == committed
        s2.tick()
        # but a NEW eligible MV in the same session co-schedules
        s2.run_sql(MV_SQL.format(n="m1"))
        assert s2.metrics()["coschedule"]["jobs"] == 1
        s2.tick()
    finally:
        s2.close()


def test_session_recovery_refuses_without_flag(tmp_path):
    s = _session(tmp_path, checkpoint_frequency=2)
    s.run_sql(SRC_SQL)
    s.run_sql(MV_SQL.format(n="m0"))
    s.tick()
    s.close()
    from risingwave_tpu.frontend.session import SqlError
    with pytest.raises(SqlError, match="co-scheduled"):
        _session(tmp_path, coschedule=False, checkpoint_frequency=2)
