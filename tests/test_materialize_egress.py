"""Materialize egress as columns (ISSUE 28): ``map_chunk`` starts one
async fetch a chunk, the barrier stages the epoch's visible rows as one
ordered columnar batch. Pinned here: the bytes that reach the store are
those of the row-by-row path (``insert`` / ``delete`` a row, the Python
encoders), with the native codec and without it; one fetch a chunk and
none resolved before the barrier; ``rows()`` sees the open epoch."""

import asyncio

import jax.numpy as jnp
import pytest

import risingwave_tpu.native as native_mod
from risingwave_tpu.common import fetch as fetch_mod
from risingwave_tpu.common import tracing
from risingwave_tpu.common.packed import dict_view
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, chunk_to_rows,
    make_chunk,
)
from risingwave_tpu.common.types import (
    FLOAT64, INT64, JSONB, VARCHAR, Schema,
)
from risingwave_tpu.storage.state_store import MemoryStateStore
from risingwave_tpu.storage.state_table import StateTable
from risingwave_tpu.stream import Barrier, MaterializeExecutor, MockSource
from risingwave_tpu.stream.materialize import MAX_PENDING_FETCHES

SCHEMA = Schema.of(("k", INT64), ("s", VARCHAR), ("v", INT64), ("f", FLOAT64))
PK = [0, 1]
I, D, UD, UI = OP_INSERT, OP_DELETE, OP_UPDATE_DELETE, OP_UPDATE_INSERT
CAP = 8


def chunk(rows, ops=None, vis=None, schema=SCHEMA, capacity=CAP):
    c = make_chunk(schema, rows, ops=ops, capacity=capacity)
    if vis is not None:
        c = c.with_vis(jnp.asarray(vis + [False] * (capacity - len(vis))))
    return c


SEED = [chunk([(1, "a", 10, 1.5), (2, "b", 20, -0.0), (3, "c", 30, 2.25)])]

#: name → epochs, each a list of chunks; every case starts from SEED's rows
CASES = {
    "inserts": [[chunk([(4, "d", 40, 0.5), (5, "e", 50, 1e300)])]],
    "deletes": [[chunk([(1, "a", 10, 1.5), (3, "c", 30, 2.25)], [D, D])]],
    "update_pairs": [[chunk(
        [(1, "a", 10, 1.5), (1, "a", 11, 2.5), (2, "b", 20, -0.0),
         (2, "b", 21, 0.0)], [UD, UI, UD, UI])]],
    "insert_then_delete_in_a_chunk": [[chunk(
        [(7, "g", 70, 7.0), (7, "g", 70, 7.0), (1, "a", 12, 1.0)],
        [I, D, I])]],
    "delete_then_insert_in_a_chunk": [[chunk(
        [(2, "b", 20, -0.0), (2, "b", 22, 2.0)], [D, I])]],
    "insert_then_delete_across_chunks": [[
        chunk([(7, "g", 70, 7.0), (8, "h", 80, 8.0)]),
        chunk([(7, "g", 70, 7.0)], [D])]],
    "delete_then_insert_across_chunks": [[
        chunk([(3, "c", 30, 2.25)], [D]),
        chunk([(3, "c", 33, 3.0), (3, "c", 34, 4.0)])]],
    "nulls": [[chunk([(9, None, None, None), (None, "n", 90, None),
                      (1, "a", None, 1.5)])]],
    "varchar_keys_sort_and_escape": [[chunk(
        [(1, "", 1, 0.0), (1, "a\x00b", 2, 0.0), (1, "βeta", 3, 0.0)])]],
    "invisible_rows": [[chunk(
        [(4, "d", 40, 0.5), (1, "a", 10, 1.5), (5, "e", 50, 5.0)],
        [I, D, I], vis=[True, False, True])]],
    "empty_chunk": [[chunk([]), chunk([(4, "d", 40, 0.5)])]],
    "all_invisible_epoch": [[chunk([(4, "d", 40, 0.5)], vis=[False])]],
    "epoch_without_a_chunk": [[], [chunk([(4, "d", 40, 0.5)])], []],
    "later_epoch_overwrites": [[chunk([(4, "d", 40, 0.5)])],
                               [chunk([(4, "d", 41, 0.5)]),
                                chunk([(1, "a", 10, 1.5)], [D])]],
    # one pk written by every chunk of an epoch longer than the bound on
    # pending fetches: the oldest are staged inside map_chunk, in order
    "more_chunks_than_the_bound": [[
        chunk([(6, "f", i, 0.0)], [D if i % 5 == 4 else I], capacity=2)
        for i in range(MAX_PENDING_FETCHES + 6)]],
}

JSON_SCHEMA = Schema.of(("k", INT64), ("j", JSONB))


def messages(epochs):
    msgs = [Barrier.new(1)]
    for e, chunks in enumerate(epochs, start=2):
        msgs += [*chunks, Barrier.new(e, checkpoint=(e % 2 == 0))]
    return msgs


def run_executor(schema, pk, epochs):
    """The epochs through ``MaterializeExecutor``: what each barrier handed
    the store (keys → value bytes, None = delete), and the committed
    table."""
    store = MemoryStateStore()
    mv = MaterializeExecutor(MockSource(schema, messages(epochs)),
                             StateTable(store, 1, schema, pk))

    async def drive():
        async for _ in mv.execute():
            assert len(mv._pending) <= MAX_PENDING_FETCHES
    asyncio.run(drive())
    return sealed(store, len(epochs))


def run_row_by_row(schema, pk, epochs):
    """The same through ``insert`` / ``delete`` a row, Python encoders."""
    store = MemoryStateStore()
    table = StateTable(store, 1, schema, pk)
    for e, chunks in enumerate(epochs, start=2):
        for c in chunks:
            for op, row in chunk_to_rows(c, schema, with_ops=True,
                                         physical=True):
                (table.insert if op in (I, UI) else table.delete)(row)
        table.commit(e)
    return sealed(store, len(epochs))


def sealed(store, n_epochs):
    pending = {e: dict(dict_view(tables.get(1, [])))
               for e, tables in store._pending.items()}
    store.commit(n_epochs + 1)
    return pending, list(store.iter_table(1))


@pytest.fixture(params=["native", "python"])
def codec_mode(request, monkeypatch):
    """The codec as it builds here, or absent as under
    ``RW_TPU_DISABLE_NATIVE=1``."""
    if request.param == "python":
        monkeypatch.setenv("RW_TPU_DISABLE_NATIVE", "1")
        monkeypatch.setattr(native_mod, "_lib", None)
        monkeypatch.setattr(native_mod, "_tried", False)
        assert native_mod.codec() is None
    elif native_mod.codec() is None:
        pytest.skip("native toolchain unavailable")
    return request.param


@pytest.mark.parametrize("case", [*CASES, "type_the_codec_lacks"])
def test_store_bytes_equal_the_row_by_row_path(case, codec_mode):
    if case == "type_the_codec_lacks":
        schema, pk = JSON_SCHEMA, [0]
        epochs = [[chunk([(1, '{"a": 1}'), (2, None)], schema=schema)],
                  [chunk([(1, '{"a": 1}'), (3, "[1, 2]")], [D, I],
                         schema=schema)]]
    else:
        schema, pk, epochs = SCHEMA, PK, [SEED, *CASES[case]]
    got = run_executor(schema, pk, epochs)
    want = run_row_by_row(schema, pk, epochs)
    assert got == want
    assert all(isinstance(v, (bytes, type(None)))
               for buf in got[0].values() for v in buf.values())


def materialize_spans(name):
    return [d for spans in tracing.epoch_spans().values() for d in spans
            if d["name"] == name]


def test_one_fetch_a_chunk_and_none_resolved_before_the_barrier(
        codec_mode, monkeypatch):
    made, resolved = [], []
    real_init, real_result = (fetch_mod.FetchFuture.__init__,
                              fetch_mod.FetchFuture.result)

    def init(self, tree, dispatch=None):
        made.append(tree)
        real_init(self, tree, dispatch)

    def result(self):
        resolved.append(self)
        return real_result(self)
    monkeypatch.setattr(fetch_mod.FetchFuture, "__init__", init)
    monkeypatch.setattr(fetch_mod.FetchFuture, "result", result)

    chunks = [*SEED, *CASES["update_pairs"][0], chunk([])]
    store = MemoryStateStore()
    mv = MaterializeExecutor(
        MockSource(SCHEMA, [Barrier.new(1), *chunks, Barrier.new(2)]),
        StateTable(store, 1, SCHEMA, PK))
    seen = []

    async def drive():
        async for msg in mv.execute():
            if not isinstance(msg, Barrier):
                # the chunk went on with its copy started and nothing
                # fetched: no blocking crossing inside map_chunk
                seen.append((len(made), len(resolved), msg))
    tracing.GLOBAL_TRACE.clear()
    asyncio.run(drive())
    assert [(m, r) for m, r, _ in seen] == [(1, 0), (2, 0), (3, 0)]
    assert all(out is sent for (_, _, out), sent in zip(seen, chunks))
    assert all(m is c for m, c in zip(made, chunks))
    assert len(resolved) == len(chunks)
    rolled = [d for d in materialize_spans("Materialize.chunks")
              if d["epoch"] == 2]
    (args,) = [d["args"] for d in rolled]
    assert args["chunks"] == args["fetches"] == len(chunks)
    assert args["rows_staged"] == 3 + 4
    assert args["bytes_fetched"] == len(chunks) * CAP * (1 + 1 + 4 * 1
                                                         + 8 + 4 + 8 + 8)
    assert args["native"] == (codec_mode == "native")
    (wait,) = [d for d in materialize_spans("materialize.fetch_wait")
               if d["epoch"] == 2]
    assert wait["wait"] == "device" and wait["args"]["fetches"] == 3


def test_rows_mid_epoch_sees_the_pending_chunks(codec_mode):
    store = MemoryStateStore()
    mv = MaterializeExecutor(MockSource(SCHEMA, []),
                             StateTable(store, 1, SCHEMA, PK))

    async def feed(c):
        async for _ in mv.map_chunk(c):
            pass
    asyncio.run(feed(SEED[0]))
    assert not mv.table.is_dirty() and len(mv._pending) == 1
    assert sorted(mv.rows()) == [(1, "a", 10, 1.5), (2, "b", 20, -0.0),
                                 (3, "c", 30, 2.25)]
    assert not mv._pending
    asyncio.run(feed(CASES["deletes"][0][0]))
    assert mv.rows() == [(2, "b", 20, -0.0)]
