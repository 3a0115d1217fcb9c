"""Mesh-sharded execution tests (virtual 8-device CPU mesh from conftest).

Covers VERDICT r2 weak #3: the sharded path previously had zero pytest
coverage. Every test cross-checks against either an independent host model
or a single-chip session running the identical deterministic workload."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common import INT64, Schema, chunk_to_rows, make_chunk
from risingwave_tpu.common.chunk import OP_DELETE, OP_INSERT
from risingwave_tpu.expr.agg import agg as agg_call, count_star
from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend.build import BuildConfig
from risingwave_tpu.ops.join_state import JoinType
from risingwave_tpu.parallel import (
    ShardedHashAgg, ShardedHashJoin, build_sharded_q5_step,
    build_sharded_q7_step, make_mesh,
)

N_DEV = 8


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) >= N_DEV, "conftest must force 8 CPU devices"
    return make_mesh(N_DEV)


SCHEMA2 = Schema.of(("k", INT64), ("v", INT64))


def _chunks_for(mesh, rows_per_shard, ops_per_shard=None, cap=16):
    out = []
    for s in range(N_DEV):
        rows = rows_per_shard[s]
        ops = ops_per_shard[s] if ops_per_shard else None
        out.append(make_chunk(SCHEMA2, rows, ops=ops, capacity=cap))
    return out


def test_sharded_q5_step_dryrun():
    build_sharded_q5_step(N_DEV)


def test_sharded_q7_step_dryrun():
    build_sharded_q7_step(N_DEV)


def test_sharded_agg_insert_delete(mesh):
    agg = ShardedHashAgg(mesh, [INT64], [0], [count_star(), agg_call("sum", 1, INT64)],
                         table_capacity=256, out_capacity=32)
    ins = [[(k % 5, k) for k in range(s, s + 10)] for s in range(N_DEV)]
    batch = agg.batch_chunks(_chunks_for(mesh, ins))
    agg.step(batch)
    # retract a few rows from different shards
    dels = [[(s % 5, s)] for s in range(N_DEV)]
    ops = [[OP_DELETE] for _ in range(N_DEV)]
    agg.step(agg.batch_chunks(_chunks_for(mesh, dels, ops)))

    expected: dict = {}
    for s in range(N_DEV):
        for k, v in ins[s]:
            c, t = expected.get((k,), (0, 0))
            expected[(k,)] = (c + 1, t + v)
        k, v = dels[s][0]
        c, t = expected[(k,)]
        expected[(k,)] = (c - 1, t - v)
    expected = {k: v for k, v in expected.items() if v[0] > 0}
    got = agg.merged_group_values()
    got = {k: (v[1], v[2]) for k, v in got.items()}
    assert got == expected


def host_join(l_rows, r_rows):
    return sorted((0, lr + rr) for lr in l_rows for rr in r_rows
                  if lr[0] == rr[0])


def test_sharded_join_basic(mesh):
    join = ShardedHashJoin(mesh, SCHEMA2, SCHEMA2, [0], [0], JoinType.INNER,
                           key_capacity=256, bucket_width=4)
    l_rows = [[(k % 7, 100 * s + k) for k in range(8)] for s in range(N_DEV)]
    r_rows = [[(k % 7, 200 * s + k) for k in range(4)] for s in range(N_DEV)]
    out_r = join.step("right", join.batch_chunks(_chunks_for(mesh, r_rows)))
    out_l = join.step("left", join.batch_chunks(_chunks_for(mesh, l_rows)))
    got = sorted(join.collect_rows(out_r) + join.collect_rows(out_l))
    exp = host_join([r for s in l_rows for r in s],
                    [r for s in r_rows for r in s])
    assert got == exp
    assert len(got) > 0


def test_sharded_join_growth_on_hot_key(mesh):
    """All rows share ONE key -> one shard's bucket must grow far past the
    initial width; growth retries must not lose or duplicate rows."""
    join = ShardedHashJoin(mesh, SCHEMA2, SCHEMA2, [0], [0], JoinType.INNER,
                           key_capacity=64, bucket_width=2)
    l_rows = [[(1, 100 * s + k) for k in range(6)] for s in range(N_DEV)]
    r_rows = [[(1, 7000 + s)] for s in range(N_DEV)]
    out_r = join.step("right", join.batch_chunks(_chunks_for(mesh, r_rows)))
    out_l = join.step("left", join.batch_chunks(_chunks_for(mesh, l_rows)))
    got = sorted(join.collect_rows(out_r) + join.collect_rows(out_l))
    exp = host_join([r for s in l_rows for r in s],
                    [r for s in r_rows for r in s])
    assert got == exp
    assert join.core.W > 2  # growth actually happened
    assert len(got) == 6 * N_DEV * N_DEV


def test_sharded_join_retraction(mesh):
    """Deletes on the build side retract previously emitted join rows."""
    join = ShardedHashJoin(mesh, SCHEMA2, SCHEMA2, [0], [0], JoinType.INNER,
                           key_capacity=256, bucket_width=4)
    r_rows = [[(s, 10 + s)] for s in range(N_DEV)]
    l_rows = [[(s, 20 + s)] for s in range(N_DEV)]
    join.step("right", join.batch_chunks(_chunks_for(mesh, r_rows)))
    out_l = join.step("left", join.batch_chunks(_chunks_for(mesh, l_rows)))
    ins = sorted(join.collect_rows(out_l))
    assert len(ins) == N_DEV
    # retract all right rows -> every joined row is deleted
    ops = [[OP_DELETE] for _ in range(N_DEV)]
    out_d = join.step("right", join.batch_chunks(_chunks_for(mesh, r_rows, ops)))
    dels = sorted(join.collect_rows(out_d))
    assert [(OP_DELETE, r) for _, r in ins] == dels


# ---------------------------------------------------------------------------
# End-to-end: CREATE MV runs data-parallel over the mesh and matches the
# single-chip session on the identical deterministic NEXmark stream.
# ---------------------------------------------------------------------------

DDL = """
CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,
  channel VARCHAR, url VARCHAR, date_time TIMESTAMP, extra VARCHAR)
WITH (connector = 'nexmark', nexmark_table = 'bid');
CREATE SOURCE auction (id BIGINT, item_name VARCHAR, description VARCHAR,
  initial_bid BIGINT, reserve BIGINT, date_time TIMESTAMP,
  expires TIMESTAMP, seller BIGINT, category BIGINT, extra VARCHAR)
WITH (connector = 'nexmark', nexmark_table = 'auction')
"""


def _run(sql: str, name: str, mesh=None, ticks: int = 3):
    cfg = BuildConfig(mesh=mesh, agg_table_capacity=1 << 10,
                      join_key_capacity=1 << 9, join_bucket_width=8)
    s = Session(source_chunk_capacity=64, config=cfg)
    s.run_sql(DDL)
    s.run_sql(sql)
    for _ in range(ticks):
        s.tick()
    return sorted(s.mv_rows(name))


def test_sharded_mv_q5_core_equivalence(mesh):
    sql = """CREATE MATERIALIZED VIEW q5c AS
        SELECT auction, COUNT(*) AS cnt, SUM(price) AS total
        FROM bid GROUP BY auction"""
    assert _run(sql, "q5c", mesh=mesh) == _run(sql, "q5c", mesh=None)


@pytest.mark.slow
def test_sharded_mv_q7_core_equivalence(mesh):
    sql = """CREATE MATERIALIZED VIEW q7c AS
        SELECT B.auction, B.price, A.seller
        FROM bid B INNER JOIN auction A ON B.auction = A.id
        WHERE B.date_time <= A.expires"""
    got = _run(sql, "q7c", mesh=mesh)
    want = _run(sql, "q7c", mesh=None)
    assert got == want
    assert len(got) > 0


def test_sharded_mv_checkpoint_recovery(mesh):
    """Sharded agg state survives: checkpoint, rebuild executor from the
    state table, verify groups."""
    from risingwave_tpu.parallel.executors import ShardedHashAggExecutor
    from risingwave_tpu.storage.state_store import MemoryStateStore
    from risingwave_tpu.storage.state_table import StateTable
    from risingwave_tpu.stream.hash_agg import agg_state_schema
    from risingwave_tpu.stream.source import MockSource
    from risingwave_tpu.stream.message import Barrier
    from risingwave_tpu.stream.executor import collect_until_barrier

    store = MemoryStateStore()
    schema = agg_state_schema([SCHEMA2[0]], [count_star(), agg_call("sum", 1, INT64)])
    table = StateTable(store, 7, schema, [0])
    rows = [(k % 11, k) for k in range(100)]
    msgs = [make_chunk(SCHEMA2, rows, capacity=128),
            Barrier.new(2, checkpoint=True)]
    src = MockSource(SCHEMA2, [Barrier.new(1)] + msgs)
    ex = ShardedHashAggExecutor(src, mesh, [0],
                                [count_star(), agg_call("sum", 1, INT64)],
                                state_table=table, table_capacity=256,
                                out_capacity=32)

    async def drain():
        chunks = []
        async for m in ex.execute():
            from risingwave_tpu.common.chunk import StreamChunk
            if isinstance(m, StreamChunk):
                chunks.append(m)
        return chunks

    import asyncio
    chunks = asyncio.run(drain())
    store.commit(2)
    emitted = sorted(r for c in chunks
                     for r in chunk_to_rows(c, ex.schema, physical=True))
    expected: dict = {}
    for k, v in rows:
        c, t = expected.get(k, (0, 0))
        expected[k] = (c + 1, t + v)
    assert emitted == sorted((k, c, t) for k, (c, t) in expected.items())

    # recover a fresh executor from the durable tier
    table2 = StateTable(store, 7, schema, [0])
    src2 = MockSource(SCHEMA2, [Barrier.new(3)])
    ex2 = ShardedHashAggExecutor(src2, mesh, [0],
                                 [count_star(), agg_call("sum", 1, INT64)],
                                 state_table=table2, table_capacity=256,
                                 out_capacity=32)
    got = {k[0]: (v[1], v[2])
           for k, v in ex2.agg.merged_group_values().items()}
    assert got == expected


def test_sharded_join_update_pairs_refill_at_width_one(mesh):
    """The mesh join shares ``JoinCore``'s lane refill (ISSUE 33) and does
    not know its state tables' keys, so every refilled tombstone is
    buried: its checkpoint has to stage the graveyard's deletes before
    the puts. Update pairs on a side unique on the join key, bucket width
    1, the state table keyed ``(k, v)``: no growth, and the durable rows
    are the live rows at each checkpoint."""
    import asyncio

    from risingwave_tpu.common.chunk import OP_UPDATE_DELETE, OP_UPDATE_INSERT
    from risingwave_tpu.parallel.executors import ShardedHashJoinExecutor
    from risingwave_tpu.storage.state_store import MemoryStateStore
    from risingwave_tpu.storage.state_table import StateTable
    from risingwave_tpu.stream.message import Barrier, Mutation, MutationKind
    from risingwave_tpu.stream.source import MockSource

    keys = list(range(1, 25))
    U_, UP = OP_UPDATE_DELETE, OP_UPDATE_INSERT

    def pairs(old, new):
        rows, ops = [], []
        for k in keys[::2]:
            rows += [(k, old + k), (k, new + k)]
            ops += [U_, UP]
        return make_chunk(SCHEMA2, rows, ops=ops, capacity=64)

    left = [Barrier.new(1), make_chunk(SCHEMA2, [(k, k) for k in keys],
                                       capacity=64)]
    right = [Barrier.new(1), make_chunk(SCHEMA2, [(k, 100 + k) for k in keys],
                                        capacity=64)]
    stop = Mutation(MutationKind.STOP)
    for e, chunk in ((2, pairs(100, 200)), (3, pairs(200, 300)),
                     (4, pairs(300, 400))):
        right.append(chunk)
        for side in (left, right):
            side.append(Barrier.new(e, checkpoint=e != 3,
                                    mutation=stop if e == 4 else None))
    store = MemoryStateStore()
    lt = StateTable(store, 1, SCHEMA2, [0, 1])
    rt = StateTable(store, 2, SCHEMA2, [0, 1])
    ex = ShardedHashJoinExecutor(
        MockSource(SCHEMA2, left), MockSource(SCHEMA2, right), mesh, [0], [0],
        JoinType.LEFT_OUTER, left_state_table=lt, right_state_table=rt,
        key_capacity=64, bucket_width=1, out_capacity=64)

    async def drain():
        async for _ in ex.execute():
            pass

    asyncio.run(drain())
    store.commit(4)
    assert ex.join.core.W == 1, "the join grew"
    want = sorted((k, (400 if k in keys[::2] else 100) + k) for k in keys)
    assert sorted(rt.scan_all()) == want
    assert sorted(lt.scan_all()) == [(k, k) for k in keys]
