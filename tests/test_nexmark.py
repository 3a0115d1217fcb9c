"""NEXmark generator sanity + q1/q5-core pipelines end-to-end."""

import asyncio
import hashlib

import jax
import pytest

import numpy as np

from risingwave_tpu.common import INT64, TIMESTAMP, Schema, chunk_to_rows
from risingwave_tpu.common.chunk import (
    HostChunk, StagedCounts, stage_chunks,
)
from risingwave_tpu.common.types import GLOBAL_STRING_DICT
from risingwave_tpu.connector import (
    BID_SCHEMA, NexmarkConfig, NexmarkGenerator,
)
from risingwave_tpu.connector.nexmark import AUCTION_SCHEMA, PERSON_SCHEMA
from risingwave_tpu.expr import Literal, call, col
from risingwave_tpu.expr.agg import count_star
from risingwave_tpu.stream import (
    Barrier, HashAggExecutor, MaterializeExecutor, MockSource, ProjectExecutor,
)
from risingwave_tpu.storage import MemoryStateStore, StateTable


def test_bid_chunk_shape_and_monotonic_time():
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=256))
    c1 = gen.next_bid_chunk()
    c2 = gen.next_bid_chunk()
    rows1 = chunk_to_rows(c1, BID_SCHEMA)
    rows2 = chunk_to_rows(c2, BID_SCHEMA)
    assert len(rows1) == 256 and len(rows2) == 256
    ts1 = [r[5] for r in rows1]
    ts2 = [r[5] for r in rows2]
    assert ts1 == sorted(ts1) and ts1[-1] <= ts2[0]
    channels = {r[3] for r in rows1}
    assert channels <= {"Google", "Facebook", "Baidu", "Apple"}
    # hot-auction skew: top auction takes a large share
    auctions = np.array([r[0] for r in rows1])
    top_share = np.bincount(auctions - auctions.min()).max() / len(auctions)
    assert top_share > 0.3


def test_q1_style_projection():
    # q1: SELECT auction, bidder, 0.908 * price, date_time FROM bid
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=128))
    chunk = gen.next_bid_chunk()
    src = MockSource(BID_SCHEMA, [Barrier.new(1), chunk, Barrier.new(2)])
    from risingwave_tpu.common import FLOAT64
    from risingwave_tpu.expr import cast
    ex = ProjectExecutor(src, [
        col(0, INT64), col(1, INT64),
        cast(col(2, INT64), FLOAT64) * 0.908, col(5, TIMESTAMP),
    ])

    async def drain():
        out = []
        async for m in ex.execute():
            from risingwave_tpu.common import StreamChunk
            if isinstance(m, StreamChunk):
                out.extend(chunk_to_rows(m, ex.schema))
        return out

    rows = asyncio.run(drain())
    src_rows = chunk_to_rows(chunk, BID_SCHEMA)
    assert len(rows) == len(src_rows)
    # TPU f64 is emulated (ulp-level rounding differs from host), so approx.
    assert rows[0][2] == pytest.approx(src_rows[0][2] * 0.908, rel=1e-12)


def test_q5_core_counts_match_numpy():
    """Windowed per-auction counts == offline numpy groupby."""
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=256))
    chunks = [gen.next_bid_chunk() for _ in range(4)]
    window = 10_000_000
    src = MockSource(BID_SCHEMA, [Barrier.new(1), *chunks, Barrier.new(2, checkpoint=True)])
    proj = ProjectExecutor(src, [
        call("tumble_start", col(5, TIMESTAMP), Literal(window, INT64)),
        col(0, INT64),
    ], names=("window_start", "auction"))
    agg = HashAggExecutor(proj, [0, 1], [count_star()], table_capacity=1 << 12)
    store = MemoryStateStore()
    mv = MaterializeExecutor(agg, StateTable(store, 1, agg.schema, [0, 1]))

    async def drain():
        async for _ in mv.execute():
            pass

    asyncio.run(drain())
    got = {(r[0], r[1]): r[2] for r in mv.rows()}

    expected: dict = {}
    for c in chunks:
        for r in chunk_to_rows(c, BID_SCHEMA):
            key = ((r[5] // window) * window, r[0])
            expected[key] = expected.get(key, 0) + 1
    assert got == expected


# -- host columns → stage_chunks (ISSUE 30) ----------------------------------

#: sha256 over the first 40 chunks (seed 7, capacity 256, every third chunk
#: 200 rows): ops, vis, every mask and every non-string column's data as
#: dtype, shape and bytes, string columns decoded (their dictionary ids depend
#: on what the process interned before). Recorded from the column-by-column
#: generator of the commit before ``stage_chunks`` (c112cd7).
RECORDED = {
    "bid": "49714ff7dca0714feca9e59d8324c92fb3d1ccdad7fb35684531273b20931913",
    "person": "bd89f9da3180aad9bcfaad281d981cc6b6da5309a7defd72a28231d5eec2f547",
    "auction": "3a2490cb9de412b3feb77f06cd1126a3ae2f1935ff83a12ee5028a91683f2b23",
}


def leaf_bytes(chunk) -> list:
    return [(str(a.dtype), a.shape, np.asarray(a).tobytes())
            for a in jax.tree_util.tree_leaves(chunk)]


@pytest.mark.parametrize("table", sorted(RECORDED))
def test_first_40_chunks_equal_the_recorded_stream(table):
    schema = {"bid": BID_SCHEMA, "person": PERSON_SCHEMA,
              "auction": AUCTION_SCHEMA}[table]
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=256), seed=7)
    h = hashlib.sha256()
    for i in range(40):
        chunk = getattr(gen, f"next_{table}_chunk")(None if i % 3 else 200)
        leaves = [chunk.ops, chunk.vis]
        for column, field in zip(chunk.columns, schema):
            data = np.asarray(column.data)
            if field.type.is_string:
                h.update(str(data.dtype).encode())
                h.update("\x00".join(
                    GLOBAL_STRING_DICT.lookup(int(v))
                    for v in data[np.asarray(column.mask)]).encode())
                leaves.append(column.mask)
            else:
                leaves += [column.data, column.mask]
        for leaf in leaves:
            a = np.asarray(leaf)
            h.update(str(a.dtype).encode() + str(a.shape).encode()
                     + a.tobytes())
    assert h.hexdigest() == RECORDED[table]


def test_transfers_a_bid_chunk_and_a_q8_barrier():
    """A bid chunk is 2 transfers (int64 x 4, int32 x 3) + 1 dispatch where
    it was 17 copies; a q8 barrier (4 person + 4 auction chunks, each
    feed's staged together) 4 + 2 where it was 160."""
    from risingwave_tpu.connector.nexmark_split import NexmarkReader
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=128), seed=3)
    counts = StagedCounts()
    stage_chunks([HostChunk(BID_SCHEMA, gen.bid_columns(128), 128, 128)],
                 counts)
    assert counts == StagedCounts(2, 128 * (4 * 8 + 3 * 4), 1)
    readers = [NexmarkReader("person", 64, seed=3),
               NexmarkReader("auction", 192, seed=3)]
    counts = StagedCounts()
    chunks = [stage_chunks([r.next_host_chunk() for _ in range(4)], counts)
              for r in readers]
    assert counts.transfers <= 16 and counts.dispatches <= 8
    assert counts == StagedCounts(
        4, 4 * (64 * (2 * 8 + 6 * 4) + 192 * (7 * 8 + 3 * 4)), 2)
    # staged together or one at a time: the same chunks
    for reader, staged in zip(readers, chunks):
        again = NexmarkReader(reader.table, reader.chunk_capacity, seed=3)
        assert ([leaf_bytes(c) for c in staged]
                == [leaf_bytes(again.next_chunk()) for _ in range(4)])


@pytest.mark.parametrize("table", ["bid", "person", "auction"])
def test_seek_transfers_nothing_and_lands_on_the_same_chunk(
        table, monkeypatch):
    from risingwave_tpu.common import chunk as chunk_mod
    from risingwave_tpu.connector.nexmark_split import NexmarkReader

    def no_staging(*_args, **_kw):
        raise AssertionError("seek staged a chunk")
    k = 5
    plain = NexmarkReader(table, chunk_capacity=64, seed=9)
    for _ in range(k):
        plain.next_chunk()
    want = plain.next_chunk()
    sought = NexmarkReader(table, chunk_capacity=64, seed=9)
    with monkeypatch.context() as m:
        m.setattr(chunk_mod, "_stage_run", no_staging)
        with pytest.raises(AssertionError):     # the guard does guard
            sought.next_chunk()
        sought = NexmarkReader(table, chunk_capacity=64, seed=9)
        sought.seek({"0": k})
    assert sought.offsets == {"0": k}
    assert leaf_bytes(sought.next_chunk()) == leaf_bytes(want)
    # backwards: the generator restarts, still without a transfer
    with monkeypatch.context() as m:
        m.setattr(chunk_mod, "_stage_run", no_staging)
        sought.seek({"0": 2})
    again = NexmarkReader(table, chunk_capacity=64, seed=9)
    again.seek({"0": 2})
    assert leaf_bytes(sought.next_chunk()) == leaf_bytes(again.next_chunk())


def test_chunks_stay_readable_after_the_aggs_donated_apply():
    """HashAggExecutor donates its STATE to ``apply_chunk``; no buffer of a
    staged chunk is shared with another chunk or with the state, so every
    chunk of the feed reads the same after the agg consumed the first."""
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=64), seed=5)
    chunks = stage_chunks([HostChunk(BID_SCHEMA, gen.bid_columns(n), n, 64)
                           for n in (64, 40, 64)])
    want = [leaf_bytes(c) for c in chunks]
    # a chunk's vis and null-free masks are one array; no buffer is shared
    # BETWEEN chunks
    ptrs = [{leaf.unsafe_buffer_pointer()
             for leaf in jax.tree_util.tree_leaves(c)} for c in chunks]
    assert [len(p) for p in ptrs] == [2 + len(BID_SCHEMA)] * 3
    assert len(set().union(*ptrs)) == sum(len(p) for p in ptrs)
    src = MockSource(BID_SCHEMA, [Barrier.new(1), chunks[0], Barrier.new(2),
                                  chunks[1], Barrier.new(3)])
    agg = HashAggExecutor(src, [0], [count_star()], table_capacity=1 << 8)

    async def drain():
        return [m async for m in agg.execute()]

    asyncio.run(drain())
    assert [leaf_bytes(c) for c in chunks] == want
