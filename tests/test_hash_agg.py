"""HashAggExecutor tests — chunk-in/chunk-out against MockSource, the
reference's executor test style (src/stream/src/executor/hash_agg.rs tests)."""

import asyncio

import pytest

from risingwave_tpu.common import (
    FLOAT64, INT64, OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
    Schema, chunk_to_rows, make_chunk,
)
from risingwave_tpu.expr.agg import agg, count_star
from risingwave_tpu.storage import MemoryStateStore, StateTable
from risingwave_tpu.stream import (
    Barrier, HashAggExecutor, MaterializeExecutor, MockSource, wrap_debug,
    agg_state_schema,
)

IN_SCHEMA = Schema.of(("k", INT64), ("v", INT64))


def run(coro):
    return asyncio.run(coro)


async def drain(executor):
    chunks, barriers = [], []
    async for msg in executor.execute():
        from risingwave_tpu.stream import is_chunk
        if is_chunk(msg):
            chunks.append(msg)
        elif isinstance(msg, Barrier):
            barriers.append(msg)
    return chunks, barriers


def agg_rows(chunks, schema):
    out = []
    for c in chunks:
        out.extend(chunk_to_rows(c, schema, with_ops=True))
    return out


def test_count_sum_basic():
    src = MockSource(IN_SCHEMA, [
        Barrier.new(1),
        make_chunk(IN_SCHEMA, [(1, 10), (2, 20), (1, 5)]),
        Barrier.new(2),
    ])
    ex = HashAggExecutor(src, [0], [count_star(), agg("sum", 1, INT64)])
    chunks, _ = run(drain(wrap_debug(ex)))
    got = sorted(agg_rows(chunks, ex.schema))
    assert got == sorted([
        (OP_INSERT, (1, 2, 15)),
        (OP_INSERT, (2, 1, 20)),
    ])


def test_incremental_updates_and_deletes():
    c1 = make_chunk(IN_SCHEMA, [(1, 10), (2, 20)])
    c2 = make_chunk(IN_SCHEMA, [(1, 7), (2, 20)], ops=[OP_INSERT, OP_DELETE])
    src = MockSource(IN_SCHEMA, [Barrier.new(1), c1, Barrier.new(2), c2, Barrier.new(3)])
    ex = HashAggExecutor(src, [0], [count_star(), agg("sum", 1, INT64)])
    chunks, _ = run(drain(wrap_debug(ex)))
    rows = agg_rows(chunks, ex.schema)
    # epoch 2 flush: two inserts; epoch 3 flush: update for group 1, delete for group 2
    assert (OP_INSERT, (1, 1, 10)) in rows and (OP_INSERT, (2, 1, 20)) in rows
    assert (OP_UPDATE_DELETE, (1, 1, 10)) in rows
    assert (OP_UPDATE_INSERT, (1, 2, 17)) in rows
    assert (OP_DELETE, (2, 1, 20)) in rows
    assert len(rows) == 5


def test_avg_and_nulls():
    sch = Schema.of(("k", INT64), ("v", FLOAT64))
    c = make_chunk(sch, [(1, 4.0), (1, None), (1, 8.0)])
    src = MockSource(sch, [Barrier.new(1), c, Barrier.new(2)])
    ex = HashAggExecutor(src, [0], [count_star(), agg("avg", 1, FLOAT64)])
    chunks, _ = run(drain(ex))
    rows = agg_rows(chunks, ex.schema)
    assert rows == [(OP_INSERT, (1, 3, 6.0))]  # count counts null rows; avg skips


def test_group_cancel_between_barriers_emits_nothing():
    c = make_chunk(IN_SCHEMA, [(9, 1), (9, 1)], ops=[OP_INSERT, OP_DELETE])
    src = MockSource(IN_SCHEMA, [Barrier.new(1), c, Barrier.new(2)])
    ex = HashAggExecutor(src, [0], [count_star()])
    chunks, _ = run(drain(ex))
    assert agg_rows(chunks, ex.schema) == []


def test_null_group_key():
    sch = IN_SCHEMA
    c = make_chunk(sch, [(None, 1), (None, 2), (5, 3)])
    src = MockSource(sch, [Barrier.new(1), c, Barrier.new(2)])
    ex = HashAggExecutor(src, [0], [count_star(), agg("sum", 1, INT64)])
    chunks, _ = run(drain(ex))
    got = sorted(agg_rows(chunks, ex.schema), key=str)
    assert (OP_INSERT, (None, 2, 3)) in got
    assert (OP_INSERT, (5, 1, 3)) in got


def test_min_max_append_only():
    c = make_chunk(IN_SCHEMA, [(1, 10), (1, 3), (1, 25)])
    src = MockSource(IN_SCHEMA, [Barrier.new(1), c, Barrier.new(2)])
    ex = HashAggExecutor(src, [0], [agg("min", 1, INT64), agg("max", 1, INT64)])
    chunks, _ = run(drain(ex))
    assert agg_rows(chunks, ex.schema) == [(OP_INSERT, (1, 3, 25))]


def test_checkpoint_and_recovery():
    store = MemoryStateStore()
    calls = [count_star(), agg("sum", 1, INT64)]
    st_schema = agg_state_schema([IN_SCHEMA[0]], calls)
    c1 = make_chunk(IN_SCHEMA, [(1, 10), (2, 20)])
    src = MockSource(IN_SCHEMA, [
        Barrier.new(1),
        c1,
        Barrier.new(2, checkpoint=True),
    ])
    table = StateTable(store, 101, st_schema, [0])
    ex = HashAggExecutor(src, [0], calls, state_table=table)
    run(drain(ex))
    store.commit(2)
    assert store.table_len(101) == 2

    # "restart": new executor over the same store resumes the counts
    c2 = make_chunk(IN_SCHEMA, [(1, 5)])
    src2 = MockSource(IN_SCHEMA, [Barrier.new(3), c2, Barrier.new(4)])
    table2 = StateTable(store, 101, st_schema, [0])
    ex2 = HashAggExecutor(src2, [0], calls, state_table=table2)
    chunks, _ = run(drain(ex2))
    rows = agg_rows(chunks, ex2.schema)
    assert (OP_UPDATE_DELETE, (1, 1, 10)) in rows
    assert (OP_UPDATE_INSERT, (1, 2, 15)) in rows
    assert len(rows) == 2  # group 2 untouched -> not re-emitted


def test_many_groups_multi_chunk_flush():
    n = 700  # > groups_per_chunk for out_capacity 256 -> multiple flush chunks
    rows = [(i, i) for i in range(n)]
    chunks_in = [make_chunk(IN_SCHEMA, rows[i:i + 256], capacity=256)
                 for i in range(0, n, 256)]
    src = MockSource(IN_SCHEMA, [Barrier.new(1), *chunks_in, Barrier.new(2)])
    ex = HashAggExecutor(src, [0], [count_star()], out_capacity=256,
                         table_capacity=2048)
    chunks, _ = run(drain(ex))
    rows_out = agg_rows(chunks, ex.schema)
    assert len(rows_out) == n
    assert sorted(r[1][0] for r in rows_out) == list(range(n))


def test_materialized_pipeline():
    store = MemoryStateStore()
    c1 = make_chunk(IN_SCHEMA, [(1, 10), (2, 20), (1, 30)])
    src = MockSource(IN_SCHEMA, [Barrier.new(1), c1, Barrier.new(2, checkpoint=True)])
    ex = HashAggExecutor(src, [0], [count_star(), agg("sum", 1, INT64)])
    mv = MaterializeExecutor(ex, StateTable(store, 1, ex.schema, [0]))
    run(drain(mv))
    assert sorted(mv.rows()) == [(1, 2, 40), (2, 1, 20)]


# -- a touched group whose output row did not move emits nothing (ISSUE 27) ---

def _epochs(calls, *epochs):
    msgs = [Barrier.new(1)]
    for i, rows in enumerate(epochs):
        msgs += [make_chunk(IN_SCHEMA, rows), Barrier.new(i + 2)]
    ex = HashAggExecutor(MockSource(IN_SCHEMA, msgs), [0], calls)
    chunks, _ = run(drain(wrap_debug(ex)))
    return agg_rows(chunks, ex.schema)


def test_group_by_without_aggregate_emits_each_group_once():
    """NEXmark q8's dedup: a group touched in a later epoch changes no
    column, so no update pair of two equal rows goes downstream (a hash
    join below would tombstone and re-insert the row every time)."""
    got = _epochs([], [(1, 10), (2, 20), (1, 5)], [(1, 7), (3, 1)],
                  [(2, 2), (2, 3)])
    assert sorted(got) == [(OP_INSERT, (1,)), (OP_INSERT, (2,)),
                           (OP_INSERT, (3,))]


def test_unmoved_max_emits_nothing_a_moved_one_an_update_pair():
    got = _epochs([agg("max", 1, INT64)], [(1, 10), (2, 20)],
                  [(1, 7), (2, 25)])
    assert sorted(got[:2]) == [(OP_INSERT, (1, 10)), (OP_INSERT, (2, 20))]
    assert got[2:] == [(OP_UPDATE_DELETE, (2, 20)),
                       (OP_UPDATE_INSERT, (2, 25))]


def test_count_still_emits_an_update_pair_on_every_touch():
    got = _epochs([count_star()], [(1, 10)], [(1, 7)])
    assert got == [(OP_INSERT, (1, 1)), (OP_UPDATE_DELETE, (1, 1)),
                   (OP_UPDATE_INSERT, (1, 2))]


def test_a_group_that_dies_and_one_reborn_are_not_suppressed():
    dead = make_chunk(IN_SCHEMA, [(1, 10)], ops=[OP_DELETE])
    msgs = [Barrier.new(1), make_chunk(IN_SCHEMA, [(1, 10)]), Barrier.new(2),
            dead, Barrier.new(3), make_chunk(IN_SCHEMA, [(1, 10)]),
            Barrier.new(4)]
    ex = HashAggExecutor(MockSource(IN_SCHEMA, msgs), [0], [])
    chunks, _ = run(drain(wrap_debug(ex)))
    assert agg_rows(chunks, ex.schema) == [
        (OP_INSERT, (1,)), (OP_DELETE, (1,)), (OP_INSERT, (1,))]
