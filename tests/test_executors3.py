"""HopWindow / Union / Values / Expand / Dedup / RowIdGen / WatermarkFilter /
Sort / Now executor tests (reference: the matching in-module tests under
src/stream/src/executor/)."""

import asyncio

import jax.numpy as jnp

from risingwave_tpu.common import (
    INT64, TIMESTAMP, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
    Schema, chunk_to_rows, make_chunk,
)
from risingwave_tpu.storage import MemoryStateStore, StateTable
from risingwave_tpu.stream import (
    AppendOnlyDedupExecutor, Barrier, ExpandExecutor, HopWindowExecutor,
    MockSource, NowExecutor, RowIdGenExecutor, SortExecutor, UnionExecutor,
    ValuesExecutor, Watermark, WatermarkFilterExecutor, is_chunk, wrap_debug,
)

TS = Schema.of(("id", INT64), ("ts", TIMESTAMP))


def run(coro):
    return asyncio.run(coro)


async def drain(executor):
    chunks, barriers, wms = [], [], []
    async for msg in executor.execute():
        if is_chunk(msg):
            chunks.append(msg)
        elif isinstance(msg, Barrier):
            barriers.append(msg)
        else:
            wms.append(msg)
    return chunks, barriers, wms


def rows_of(chunks, schema, with_ops=False):
    out = []
    for c in chunks:
        out.extend(chunk_to_rows(c, schema, with_ops=with_ops))
    return out


def us(sec):
    return sec * 1_000_000


def test_hop_window_expansion():
    # slide 10s, size 30s -> each row in 3 windows
    src = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(1, us(25))], capacity=4),
        Barrier.new(2),
    ])
    ex = HopWindowExecutor(src, time_col=1, window_slide=us(10),
                           window_size=us(30))
    chunks, _, _ = run(drain(wrap_debug(ex)))
    rows = sorted(rows_of(chunks, ex.schema))
    assert rows == [
        (1, us(25), us(0), us(30)),
        (1, us(25), us(10), us(40)),
        (1, us(25), us(20), us(50)),
    ]


def test_union_and_watermark_min():
    a = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(1, 10)], capacity=4),
        Watermark(1, 100),
        Barrier.new(2),
    ])
    b = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(2, 20)], capacity=4),
        Watermark(1, 50),
        Barrier.new(2),
    ])
    ex = UnionExecutor([a, b])
    chunks, barriers, wms = run(drain(ex))
    assert sorted(rows_of(chunks, ex.schema)) == [(1, 10), (2, 20)]
    assert len(barriers) == 2
    # min across inputs
    assert [(w.col_idx, w.value) for w in wms] == [(1, 50)]


def test_values_emits_once():
    barriers = MockSource(TS, [Barrier.new(1), Barrier.new(2)])
    ex = ValuesExecutor(TS, [(1, 5), (2, 6)], barriers)
    chunks, bs, _ = run(drain(ex))
    assert rows_of(chunks, ex.schema) == [(1, 5), (2, 6)]
    assert len(bs) == 2


def test_expand_subsets():
    src = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(7, 30)], capacity=2),
        Barrier.new(2),
    ])
    ex = ExpandExecutor(src, [[0], [1]])
    chunks, _, _ = run(drain(ex))
    got = sorted(rows_of(chunks, ex.schema), key=lambda r: r[2])
    assert got == [(7, None, 0), (None, 30, 1)]


def test_append_only_dedup():
    src = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(1, 10), (2, 20), (1, 30)], capacity=4),
        Barrier.new(2),
        make_chunk(TS, [(2, 40), (3, 50)], capacity=4),
        Barrier.new(3),
    ])
    ex = AppendOnlyDedupExecutor(src, [0], table_capacity=64)
    chunks, _, _ = run(drain(wrap_debug(ex)))
    # keep-first within chunk; cross-chunk dups dropped
    assert rows_of(chunks, ex.schema) == [(1, 10), (2, 20), (3, 50)]


def test_dedup_checkpoint_recovery():
    store = MemoryStateStore()
    pk_schema = Schema.of(("id", INT64))

    def table():
        return StateTable(store, 5, pk_schema, [0])

    src = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(1, 10)], capacity=4),
        Barrier.new(2, checkpoint=True),
    ])
    ex = AppendOnlyDedupExecutor(src, [0], state_table=table(),
                                 table_capacity=64)
    run(drain(ex))
    store.commit(2)

    src2 = MockSource(TS, [
        Barrier.new(3),
        make_chunk(TS, [(1, 99), (4, 40)], capacity=4),
        Barrier.new(4),
    ])
    ex2 = AppendOnlyDedupExecutor(src2, [0], state_table=table(),
                                  table_capacity=64)
    chunks, _, _ = run(drain(ex2))
    assert rows_of(chunks, ex2.schema) == [(4, 40)]


def test_row_id_gen():
    """The one step that gives chunks ALREADY on the device their ids: the
    column appended and filled in one dispatch, the ids serial over the
    visible rows of consecutive chunks, from where the sequence stood."""
    from risingwave_tpu.common.chunk import RowIdSequence, append_row_ids
    from risingwave_tpu.common.types import SERIAL, Field
    gaps = make_chunk(TS, [(7, 10), (8, 20), (9, 25)], capacity=4)
    gaps = gaps.with_vis(gaps.vis.at[1].set(False))
    src = MockSource(TS, [
        Barrier.new(1),
        gaps,
        make_chunk(TS, [(None, 30)], capacity=4),
        Barrier.new(2),
    ])
    out_schema = Schema(tuple(TS) + (Field("_row_id", SERIAL),))
    ex = RowIdGenExecutor(src, out_schema, RowIdSequence(3, 5))
    chunks, _, _ = run(drain(ex))
    rows = rows_of(chunks, ex.schema)
    base = (3 << 48) + 5
    assert rows == [(7, 10, base), (9, 25, base + 1), (None, 30, base + 2)]
    assert int(ex.next_id) == base + 3
    assert all(c.columns[-1].data.dtype == jnp.int64 for c in chunks)
    # one program for the layout, whoever runs it: not one an executor
    programs = append_row_ids._cache_size()
    ex2 = RowIdGenExecutor(MockSource(TS, [
        Barrier.new(1), make_chunk(TS, [(1, 1)], capacity=4), Barrier.new(2),
    ]), out_schema, RowIdSequence(4))
    chunks, _, _ = run(drain(ex2))
    assert rows_of(chunks, ex2.schema) == [(1, 1, 4 << 48)]
    assert append_row_ids._cache_size() == programs


def test_watermark_filter_drops_late_rows():
    src = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(1, 100), (2, 50)], capacity=4),
        # watermark now 100-20=80; late row ts=70 must drop
        make_chunk(TS, [(3, 70), (4, 130)], capacity=4),
        Barrier.new(2),
    ])
    ex = WatermarkFilterExecutor(src, time_col=1, delay=20)
    chunks, _, wms = run(drain(ex))
    rows = rows_of(chunks, ex.schema)
    assert (3, 70) not in rows  # below announced watermark 80 -> dropped
    assert rows == [(1, 100), (2, 50), (4, 130)]
    assert [w.value for w in wms] == [80, 110]


def test_sort_eowc_emits_in_order():
    src = MockSource(TS, [
        Barrier.new(1),
        make_chunk(TS, [(1, 30), (2, 10), (3, 50)], capacity=4),
        Watermark(1, 35),
        Barrier.new(2),
        make_chunk(TS, [(4, 20)], capacity=4),  # ts=20 < wm: would be late,
        Watermark(1, 60),                        # but Sort just orders by ts
        Barrier.new(3),
    ])
    ex = SortExecutor(src, time_col=1, pk_indices=[0], table_capacity=64,
                      out_capacity=4)
    chunks, _, _ = run(drain(ex))
    rows = rows_of(chunks, ex.schema)
    assert rows == [(2, 10), (1, 30), (4, 20), (3, 50)]


def test_now_executor():
    barriers = MockSource(TS, [Barrier.new(1), Barrier.new(2)])
    ex = NowExecutor(barriers)
    chunks, bs, wms = run(drain(ex))
    rows = rows_of(chunks, ex.schema, with_ops=True)
    assert rows[0][0] == OP_INSERT
    assert rows[1][0] == OP_UPDATE_DELETE and rows[2][0] == OP_UPDATE_INSERT
    assert len(wms) == 2 and wms[0].value < wms[1].value
