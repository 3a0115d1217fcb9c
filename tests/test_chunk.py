"""Data-plane tests: chunk round-trip, visibility, hashing, vnodes.

Mirrors the reference's in-module array/chunk tests
(src/common/src/array/data_chunk.rs tests)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common import (
    BOOL, FLOAT32, INT16, INT64, FLOAT64, VARCHAR, OP_DELETE, OP_INSERT,
    Schema, StreamChunk, chunk_to_rows, compact_chunk_host, make_chunk,
    vnode_of, vnode_to_shard, hash_columns, VNODE_COUNT,
)
from risingwave_tpu.common.chunk import (
    Column, HostChunk, RowIdSequence, StagedCounts, stage_chunks,
)
from risingwave_tpu.connector.datagen import DatagenReader, _field_values
from risingwave_tpu.connector.nexmark import (
    AUCTION_SCHEMA, BID_SCHEMA, PERSON_SCHEMA, NexmarkConfig, NexmarkGenerator,
)


SCHEMA = Schema.of(("id", INT64), ("price", FLOAT64), ("name", VARCHAR))


def test_roundtrip_with_nulls():
    rows = [(1, 2.5, "alice"), (2, None, "bob"), (3, 7.0, None)]
    chunk = make_chunk(SCHEMA, rows, capacity=8)
    assert chunk.capacity == 8
    assert int(chunk.cardinality()) == 3
    assert chunk_to_rows(chunk, SCHEMA) == rows


def test_ops_and_signs():
    rows = [(1, 1.0, "a"), (2, 2.0, "b"), (3, 3.0, "c")]
    chunk = make_chunk(SCHEMA, rows, ops=[OP_INSERT, OP_DELETE, OP_INSERT], capacity=4)
    signs = np.asarray(chunk.signs())
    assert list(signs) == [1, -1, 1, 0]
    got = chunk_to_rows(chunk, SCHEMA, with_ops=True)
    assert got[1] == (OP_DELETE, (2, 2.0, "b"))


def test_vis_masking_and_compact():
    rows = [(i, float(i), "x") for i in range(5)]
    chunk = make_chunk(SCHEMA, rows, capacity=8)
    keep = jnp.asarray([True, False, True, False, True, True, True, True])
    filtered = chunk.mask_vis(keep)
    assert int(filtered.cardinality()) == 3
    compacted = compact_chunk_host(filtered)
    assert chunk_to_rows(compacted, SCHEMA) == [rows[0], rows[2], rows[4]]
    assert bool(np.asarray(compacted.vis)[:3].all())


def test_hash_deterministic_and_null_distinct():
    rows = [(1, 1.0, "a"), (1, 1.0, "a"), (2, 1.0, "a"), (None, 1.0, "a")]
    chunk = make_chunk(SCHEMA, rows, capacity=4)
    h = np.asarray(hash_columns([chunk.columns[0]]))
    assert h[0] == h[1]
    assert h[0] != h[2]
    assert h[3] != h[0] and h[3] != h[2]


def test_vnode_range_and_spread():
    n = 1000
    rows = [(i, 0.0, "") for i in range(n)]
    chunk = make_chunk(SCHEMA, rows, capacity=1024)
    vn = np.asarray(vnode_of([chunk.columns[0]]))[:n]
    assert vn.min() >= 0 and vn.max() < VNODE_COUNT
    # splitmix64 should spread 1000 sequential keys over >200 of 256 vnodes
    assert len(np.unique(vn)) > 200
    shards = np.asarray(vnode_to_shard(jnp.asarray(vn), 8))
    assert shards.min() >= 0 and shards.max() < 8
    # contiguous-range property: vnode // 32 == shard
    assert (shards == vn // 32).all()


def test_project_and_append():
    rows = [(1, 2.0, "a")]
    chunk = make_chunk(SCHEMA, rows, capacity=2)
    p = chunk.project([2, 0])
    assert chunk_to_rows(p, SCHEMA.select([2, 0])) == [("a", 1)]


# -- stage_chunks: the one host → device construction (ISSUE 30) --------------

def stage_chunk(schema, arrays, n, capacity, masks=None, ops=None,
                counts=None):
    return stage_chunks(
        [HostChunk(schema, arrays, n, capacity, masks, ops)], counts)[0]


def by_columns(schema, arrays, n, cap, masks=None, ops=None):
    """The column-by-column construction ``stage_chunk`` replaced (17 copies a
    bid chunk), kept here as the reference it must equal bit for bit."""
    cols = []
    for i, (arr, field) in enumerate(zip(arrays, schema)):
        buf = np.zeros(cap, field.type.np_dtype)
        buf[:n] = np.asarray(arr[:n]).astype(field.type.np_dtype)
        mask = np.zeros(cap, bool)
        mask[:n] = True if masks is None else masks[i][:n]
        cols.append(Column(jnp.asarray(buf), jnp.asarray(mask)))
    ops_arr = np.zeros(cap, np.int8)
    if ops is not None:
        ops_arr[:n] = ops[:n]
    return StreamChunk(jnp.asarray(ops_arr), jnp.asarray(np.arange(cap) < n),
                       tuple(cols))


def make_chunk_by_columns(schema, rows, ops=None, capacity=1024):
    """``make_chunk`` as it was before its copies went through ``stage_chunk``."""
    n = len(rows)
    ops_arr = np.zeros(capacity, np.int8)
    ops_arr[:n] = np.asarray(list(ops if ops is not None else [0] * n), np.int8)
    vis = np.zeros(capacity, bool)
    vis[:n] = True
    cols = []
    for ci, field in enumerate(schema):
        t = field.type
        data = np.full(capacity, t.null_sentinel(), t.np_dtype)
        mask = np.zeros(capacity, bool)
        for ri, row in enumerate(rows):
            if row[ci] is not None:
                data[ri] = t.to_physical(row[ci])
                mask[ri] = True
        cols.append(Column(jnp.asarray(data), jnp.asarray(mask)))
    return StreamChunk(jnp.asarray(ops_arr), jnp.asarray(vis), tuple(cols))


def assert_same_bits(got, want):
    g, w = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))


STAGE_CAP = 48
MIXED = Schema.of(("id", INT64), ("price", FLOAT64), ("name", VARCHAR),
                  ("flag", BOOL), ("small", INT16), ("ratio", FLOAT32))


def _mixed_rows(n, nulls):
    rows = [(i - 3, i * 0.25, f"n{i % 5}", i % 3 == 0, i - 7, i / 8)
            for i in range(n)]
    if nulls:
        rows = [tuple(None if (i + j) % 4 == 0 else v for j, v in enumerate(r))
                for i, r in enumerate(rows)]
    return rows


@pytest.mark.parametrize("n", [STAGE_CAP, 17, 0], ids=["full", "part", "empty"])
@pytest.mark.parametrize("case", ["bid", "person", "auction", "datagen",
                                  "make_chunk_nulls", "make_chunk_deletes",
                                  "make_chunk_plain"])
def test_stage_chunk_equals_column_by_column(case, n):
    if case in ("bid", "person", "auction"):
        schema = {"bid": BID_SCHEMA, "person": PERSON_SCHEMA,
                  "auction": AUCTION_SCHEMA}[case]
        cfg = NexmarkConfig(chunk_capacity=STAGE_CAP)
        columns = getattr(NexmarkGenerator(cfg, seed=11), f"{case}_columns")
        want = by_columns(schema, columns(n), n, STAGE_CAP)
        gen = NexmarkGenerator(cfg, seed=11)
        if n:   # the generator's own entry point (n = 0 means "a full chunk")
            got = getattr(gen, f"next_{case}_chunk")(n)
        else:
            got = stage_chunk(schema, getattr(gen, f"{case}_columns")(0), 0,
                              STAGE_CAP)
    elif case == "datagen":
        schema = Schema.of(("k", INT64), ("v", FLOAT64), ("s", VARCHAR))
        opts = {"datagen.rows.per.chunk": STAGE_CAP, "datagen.max.rows": n,
                "fields.v.end": 9, "fields.s.kind": "random",
                "fields.s.end": 3}
        got = DatagenReader(schema, opts).next_chunk()
        if n == 0:
            assert got is None      # a drained split emits nothing
            return
        arrays = []
        for f, kind, start, end in DatagenReader(schema, opts)._fields:
            vals = _field_values(f, kind, start, end, 0, 1, 0, n)
            if f.type == VARCHAR:
                vals = np.array([VARCHAR.to_physical(f"{f.name}_{int(v)}")
                                 for v in vals], np.int32)
            arrays.append(vals)
        want = by_columns(schema, arrays, n, STAGE_CAP)
    else:
        rows = _mixed_rows(n, nulls=case == "make_chunk_nulls")
        ops = ([OP_DELETE if i % 2 else OP_INSERT for i in range(n)]
               if case == "make_chunk_deletes" else None)
        got = make_chunk(MIXED, rows, ops=ops, capacity=STAGE_CAP)
        want = make_chunk_by_columns(MIXED, rows, ops=ops, capacity=STAGE_CAP)
    assert_same_bits(got, want)


def test_stage_chunk_counts_transfers_by_dtype_and_flags():
    """One host buffer per distinct dtype; masks and ops ride in ONE more
    (int8) only when the chunk has a null or a non-Insert op."""
    schema = Schema.of(("a", INT64), ("b", VARCHAR), ("c", INT64), ("d", INT16))
    cap, n = 40, 9
    arrays = [np.arange(n, dtype=f.type.np_dtype) for f in schema]
    data_bytes = cap * (2 * 8 + 4 + 2)
    full = [np.ones(n, bool)] * 4
    holes = [np.ones(n, bool)] * 3 + [np.arange(n) % 2 == 0]
    for kwargs, transfers, nbytes in [
            ({}, 3, data_bytes),
            ({"masks": full, "ops": np.zeros(n, np.int8)}, 3, data_bytes),
            ({"masks": holes}, 4, data_bytes + cap * 5),
            ({"ops": np.full(n, OP_DELETE, np.int8)}, 4, data_bytes + cap * 5)]:
        counts = StagedCounts()
        chunk = stage_chunk(schema, arrays, n, cap, counts=counts, **kwargs)
        assert counts == StagedCounts(transfers, nbytes, 1)
        assert_same_bits(chunk, by_columns(schema, arrays, n, cap, **kwargs))
    with pytest.raises(ValueError):
        stage_chunk(schema, [np.zeros(cap + 1, f.type.np_dtype)
                             for f in schema], cap + 1, cap)


def test_stage_chunk_compiles_once_per_layout_and_capacity():
    """``n`` is a runtime scalar: whatever it is, one (dtype layout,
    capacity) is one program; another capacity or a chunk with nulls is
    one more."""
    from risingwave_tpu.common.chunk import _unpack
    schema = Schema.of(("a", INT64), ("b", VARCHAR), ("c", INT64))
    cap = 136                       # no other test stages this capacity
    arrays = [np.arange(cap, dtype=f.type.np_dtype) for f in schema]
    before = _unpack._cache_size()
    for n in (0, 1, 77, cap, 5):
        stage_chunk(schema, arrays, n, cap)
    assert _unpack._cache_size() == before + 1
    # same dtypes under other names: the same program
    stage_chunk(Schema.of(("x", INT64), ("y", VARCHAR), ("z", INT64)),
                arrays, 3, cap)
    assert _unpack._cache_size() == before + 1
    stage_chunk(schema, arrays, 3, cap + 8)
    assert _unpack._cache_size() == before + 2
    nulls = [np.arange(cap) % 3 > 0] * 3
    for n in (2, 60):
        stage_chunk(schema, arrays, n, cap, masks=nulls)
    assert _unpack._cache_size() == before + 3


def test_stage_chunk_keeps_int64_and_is_a_jit_pytree():
    big = np.array([2**62 + 5, -2**61, 7], np.int64)
    chunk = stage_chunk(Schema.of(("a", INT64),), [big], 3, 4)
    assert chunk.columns[0].data.dtype == jnp.int64
    assert np.asarray(chunk.columns[0].data).tolist() == big.tolist() + [0]
    total = jax.jit(lambda c: jnp.sum(jnp.where(c.vis, c.columns[0].data, 0)))
    assert int(total(chunk)) == int(big.sum())


def test_stage_chunks_stages_a_run_together():
    """Chunks of one dtype layout and capacity go over in one transfer per
    dtype and one dispatch, in buffers of just their count (one program per
    count); a capacity change splits the run; the caller's counts add up
    over calls and no other caller's staging shows in them."""
    from risingwave_tpu.common.chunk import _unpack
    schema = Schema.of(("a", INT64), ("b", VARCHAR), ("c", FLOAT64))
    cap = 72                        # no other test stages this capacity
    rng = np.random.default_rng(0)

    def host(n, capacity=cap, nulls=False, ops=None):
        arrays = [rng.integers(0, 99, n).astype(f.type.np_dtype)
                  for f in schema]
        masks = [rng.random(n) < 0.7 for _ in schema] if nulls else None
        return HostChunk(schema, arrays, n, capacity, masks, ops)

    run = [host(cap), host(5), host(0)]
    counts, programs = StagedCounts(), _unpack._cache_size()
    got = stage_chunks(run, counts)
    assert counts == StagedCounts(3, 3 * cap * (8 + 4 + 8), 1)
    assert _unpack._cache_size() == programs + 1
    stage_chunks([host(1), host(2), host(3)])   # uncounted: not this caller's
    assert _unpack._cache_size() == programs + 1
    stage_chunks([host(1), host(2), host(3), host(4)], counts)
    assert _unpack._cache_size() == programs + 2
    assert counts == StagedCounts(6, 7 * cap * (8 + 4 + 8), 2)
    for h, chunk in zip(run, got):
        assert_same_bits(chunk, by_columns(schema, h.arrays, h.n, cap))

    # a null anywhere in a run stages the int8 stack for the whole run;
    # another capacity is another run
    mixed = [host(9), host(7, nulls=True),
             host(4, ops=np.full(4, OP_DELETE, np.int8)),
             host(6, capacity=cap + 8), host(0, capacity=cap + 8)]
    counts = StagedCounts()
    got = stage_chunks(mixed, counts)
    assert counts == StagedCounts(
        4 + 3, 3 * cap * (8 + 4 + 8 + 4) + 2 * (cap + 8) * (8 + 4 + 8), 2)
    for h, chunk in zip(mixed, got):
        assert chunk.capacity == h.capacity
        assert_same_bits(chunk, by_columns(schema, h.arrays, h.n, h.capacity,
                                           h.masks, h.ops))
    assert stage_chunks([]) == []


def parent_row_id_step(base, seq, chunk):
    """``RowIdGenExecutor._step`` as it was before the ids moved into the
    staging dispatch (PR 36), kept as the reference the staged column must
    equal: ``shard_id << 48 | (seq + rank among the visible rows)``."""
    vis = chunk.vis
    offset = jnp.cumsum(vis) - vis.astype(jnp.int64)
    return seq + jnp.sum(vis), base | (seq + offset)


ROW_ID_SCHEMA = Schema.of(("a", INT64), ("b", VARCHAR), ("c", INT64))


def row_id_barrier(rng, k, cap, flags):
    """K host chunks of one barrier: full, partly full and empty ones; with
    ``flags`` one has a null and one a non-Insert op."""
    run = []
    for c in range(k):
        n = (cap, int(rng.integers(0, cap)), 0, cap - 1)[c % 4]
        arrays = [rng.integers(0, 1 << 40, n).astype(f.type.np_dtype)
                  for f in ROW_ID_SCHEMA]
        masks = ops = None
        if flags and c == 0:
            masks = [np.ones(n, bool), np.arange(n) % 3 > 0, np.ones(n, bool)]
        if flags and c == k - 1:
            ops = np.full(n, OP_DELETE, np.int8)
        run.append(HostChunk(ROW_ID_SCHEMA, arrays, n, cap, masks, ops))
    return run


@pytest.mark.parametrize("flags", [False, True], ids=["plain", "int8_stack"])
@pytest.mark.parametrize("k", [1, 4, 16])
def test_staged_row_ids_equal_the_parents_step(k, flags):
    """ISSUE 36 (a): over three consecutive barriers the ``_row_id`` of
    every visible row is what the parent's RowIdGen step gave the same
    stream, the column's mask is ``vis`` (the same array), every other leaf
    is what staging without a sequence gives, and the sequence and the
    counts advance by the rows staged — in one dispatch a barrier."""
    cap, shard, start = 48, 3, 1_000_003
    rng = np.random.default_rng(k)
    row_ids = RowIdSequence(shard, start)
    seq, base = jnp.asarray(start, jnp.int64), jnp.int64(shard) << 48
    fed = 0
    for _barrier in range(3):
        run = row_id_barrier(rng, k, cap, flags)
        counts = StagedCounts()
        got = stage_chunks(run, counts, row_ids)
        plain = stage_chunks(run)
        fed += sum(h.n for h in run)
        assert counts.dispatches == 1
        assert counts.row_ids == sum(h.n for h in run)
        assert counts.transfers == 2 + flags
        for h, chunk, bare in zip(run, got, plain):
            assert len(chunk.columns) == len(ROW_ID_SCHEMA) + 1
            assert_same_bits(chunk.replace(columns=chunk.columns[:-1]), bare)
            ids = chunk.columns[-1]
            assert ids.data.dtype == jnp.int64
            assert ids.mask is chunk.vis
            seq, want = parent_row_id_step(base, seq, bare)
            vis = np.asarray(bare.vis)
            assert np.array_equal(np.asarray(ids.data)[vis],
                                  np.asarray(want)[vis])
            assert vis.sum() == h.n
    assert row_ids == RowIdSequence(shard, start + fed)
    assert int(seq) == start + fed


@pytest.mark.parametrize("flags", [False, True], ids=["plain", "int8_stack"])
def test_staging_without_a_sequence_is_what_it_was(flags):
    """ISSUE 36 (b): ``stage_chunks(host)`` with no row-id sequence gives
    the column-by-column chunk leaf for leaf — no further column, and a
    null-free chunk's masks are still its ``vis`` array."""
    rng = np.random.default_rng(36)
    run = row_id_barrier(rng, 4, 56, flags)
    counts = StagedCounts()
    for h, chunk in zip(run, stage_chunks(run, counts)):
        assert_same_bits(chunk, by_columns(ROW_ID_SCHEMA, h.arrays, h.n, 56,
                                           h.masks, h.ops))
        if not flags:
            assert all(c.mask is chunk.vis for c in chunk.columns)
    assert counts.row_ids == 0 and counts.dispatches == 1


def test_a_run_of_other_capacity_continues_the_sequence():
    """A capacity change splits a feed's barrier into two dispatches; the
    ids run on across the split in the order the chunks were drawn."""
    schema = Schema.of(("a", INT64),)
    host = [HostChunk(schema, [np.arange(n, dtype=np.int64)], n, cap)
            for n, cap in ((5, 8), (8, 8), (3, 16), (16, 16))]
    counts = StagedCounts()
    got = stage_chunks(host, counts, RowIdSequence(1, 10))
    assert counts.dispatches == 2 and counts.row_ids == 32
    ids = np.concatenate([np.asarray(c.columns[-1].data)[np.asarray(c.vis)]
                          for c in got])
    assert ids.tolist() == [(1 << 48) | (10 + i) for i in range(32)]


def test_row_ids_of_a_retried_feed_are_contiguous():
    """ISSUE 36 (d): a draw that raises mid-barrier — the chunks drawn
    before it are pushed with their ids, and the retried call goes on from
    the id after the last one staged."""
    from risingwave_tpu.connector.base import feed_chunks
    schema = Schema.of(("a", INT64),)
    cap, drawn = 16, []

    def draw():
        if len(drawn) == 2:
            drawn.append(None)
            raise OSError("fetch failed, out of retries")
        drawn.append(None)
        n = cap - len(drawn) % 3
        return HostChunk(schema, [np.zeros(n, np.int64)], n, cap)

    row_ids, counts, pushed = RowIdSequence(2, 7), StagedCounts(), []
    with pytest.raises(OSError):
        feed_chunks(draw, 4, pushed.append, counts, row_ids)
    assert len(pushed) == 2 and counts.dispatches == 1
    feed_chunks(draw, 4, pushed.append, counts, row_ids)
    assert len(pushed) == 6 and counts.dispatches == 2
    ids = np.concatenate([np.asarray(c.columns[-1].data)[np.asarray(c.vis)]
                          for c in pushed])
    assert counts.row_ids == len(ids) == row_ids.next - 7
    assert ids.tolist() == [(2 << 48) | (7 + i) for i in range(len(ids))]


def test_feed_chunks_pushes_what_was_drawn_when_a_later_draw_raises():
    """Offsets advance with each draw, so the chunks drawn before a failing
    draw reach the queue before the error leaves ``feed_chunks`` (Session
    and WorkerHost both feed through it); a drained reader's ``None`` is
    skipped, and the caller's counts see only this call."""
    from risingwave_tpu.connector.base import feed_chunks
    schema = Schema.of(("a", INT64), ("b", VARCHAR))
    cap = 24

    def reader(fail_at=None, drained_at=None):
        drawn = []

        def draw():
            i = len(drawn)
            if i == fail_at:
                raise OSError("read failed")
            drawn.append(i)
            if drained_at is not None and i >= drained_at:
                return None
            return HostChunk(schema, [np.full(cap, i, np.int64),
                                      np.full(cap, -i, np.int32)], cap, cap)
        return draw, drawn

    draw, drawn = reader(fail_at=2)
    pushed, counts = [], StagedCounts()
    with pytest.raises(OSError):
        feed_chunks(draw, 4, pushed.append, counts)
    assert drawn == [0, 1]
    assert [int(c.columns[0].data[0]) for c in pushed] == [0, 1]
    assert counts == StagedCounts(2, 2 * cap * (8 + 4), 1)

    draw, drawn = reader(drained_at=3)
    pushed = []
    out = feed_chunks(draw, 5, pushed.append)
    assert len(drawn) == 5 and len(out) == 3 and pushed == out
    assert feed_chunks(reader(fail_at=None, drained_at=0)[0], 2,
                       pushed.append) == []
