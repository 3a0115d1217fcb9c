"""The packed delta (ISSUE 38): a checkpoint's rows stay in the codec's blobs
from ``stage_delta`` and ``Materialize`` to the segment writer.

What must not change is checked against the row-at-a-time forms kept as
references: the segment's bytes against ``_encode_segment_py`` over the
layers' dict view, a staged packed layer's reads against the same rows
written through ``insert`` / ``delete``, and a checkpoint's call counts
against the per-row entry points it may no longer enter.
"""

import asyncio
import random
import struct

import numpy as np
import pytest

import risingwave_tpu.native as native_mod
from risingwave_tpu.common import INT64, Schema, make_chunk
from risingwave_tpu.common import tracing
from risingwave_tpu.common.chunk import OP_DELETE, OP_INSERT
from risingwave_tpu.common.packed import (
    PackedBatch, PackedColumn, apply_layer, dict_view,
)
from risingwave_tpu.common.row import encode_key, encode_value_row
from risingwave_tpu.common.types import VARCHAR
from risingwave_tpu.storage import MemoryStateStore, StateTable
from risingwave_tpu.storage.checkpoint import CheckpointLog, DurableStateStore
from risingwave_tpu.stream import Barrier, MaterializeExecutor, MockSource
from risingwave_tpu.stream.state_delta import stage_delta


def column(items: list) -> PackedColumn:
    offsets = np.zeros(len(items) + 1, np.int64)
    np.cumsum([len(i) for i in items], out=offsets[1:])
    return PackedColumn(b"".join(items), offsets)


def packed(rows: list) -> PackedBatch:
    """``[(key, value | None), ...]`` in application order, as a batch."""
    return PackedBatch(column([k for k, _v in rows]),
                       column([v for _k, v in rows if v is not None]),
                       np.array([v is not None for _k, v in rows], np.uint8))


def native_or_skip():
    if native_mod.codec() is None:
        pytest.skip("native toolchain unavailable")


# -- the column's cut and the batch's view -----------------------------------

@pytest.mark.parametrize("items", [
    [], [b""], [b"", b""], [b"abc"], [b"abc", b"def", b"\x00\x00\x00"],
    [b"a", b"", b"bcd"], [b"ab\x00", b"\x00"],
    [bytes([i % 256]) * 19 for i in range(300)],
    [bytes([i % 251]) * (i % 7) for i in range(300)],
], ids=["none", "one_empty", "all_empty", "one", "fixed_with_nuls", "ragged",
        "ragged_nuls", "fixed_300", "ragged_300"])
def test_cut_equals_the_slices(items):
    col = column(items)
    offs = col.offsets
    assert col.cut() == [col.blob[offs[r]:offs[r + 1]]
                         for r in range(len(items))] == items
    assert all(type(b) is bytes for b in col.cut())


VIEW_CASES = {
    "puts": [(b"a", b"1"), (b"b", b"2")],
    "put_put": [(b"a", b"1"), (b"a", b"2")],
    "put_tombstone": [(b"a", b"1"), (b"b", b"2"), (b"a", None)],
    "tombstone_put": [(b"a", None), (b"b", None), (b"a", b"3")],
    "update_pairs": [(b"a", None), (b"a", b"1"), (b"b", None), (b"b", b"")],
    "tombstones": [(b"a", None), (b"b", None)],
    "empty_value_is_live": [(b"a", b""), (b"b", None), (b"c", b"\x00")],
    "empty": [],
}


@pytest.mark.parametrize("case", list(VIEW_CASES))
def test_view_and_apply_are_the_rows_one_by_one(case):
    rows = VIEW_CASES[case]
    assert packed(rows).view() == dict(rows)
    seed = {b"a": b"old-a", b"b": b"old-b", b"z": b"old-z"}
    want = dict(seed)
    for k, v in rows:
        if v is None:
            want.pop(k, None)
        else:
            want[k] = v
    for read_first in (False, True):
        batch, got = packed(rows), dict(seed)
        if read_first:
            batch.view()
        apply_layer(got, batch)
        assert got == want


def test_batch_refuses_columns_that_do_not_match():
    with pytest.raises(ValueError):
        PackedBatch(column([b"a", b"b"]), column([b"1"]),
                    np.ones(2, np.uint8))
    with pytest.raises(ValueError):
        PackedBatch(column([b"a"]), column([b"1"]), np.ones(2, np.uint8))


# -- the segment, packed against the Python row loop -------------------------

def _random_layers():
    rng = random.Random(38)
    layers = []
    for _ in range(5):
        rows = [(b"%05d" % rng.randrange(4000),
                 rng.randbytes(rng.randrange(0, 40))
                 if rng.random() < 0.8 else None) for _ in range(2000)]
        layers.append(packed(rows) if rng.random() < 0.7 else dict(rows))
    return {3: layers, 1: [packed([(b"%07d" % i, b"v") for i in range(9)])]}


SEGMENT_CASES = {
    "one_layer": lambda: {1: [packed([(b"b", b"2"), (b"a", b"1"),
                                      (b"c", None)])]},
    "put_put": lambda: {1: [packed([(b"k", b"1"), (b"j", b"x")]),
                            packed([(b"k", b"2")])]},
    "put_tombstone": lambda: {1: [packed([(b"k", b"1"), (b"j", b"x")]),
                                  packed([(b"k", None)])]},
    "tombstone_put": lambda: {1: [packed([(b"k", None)]),
                                  packed([(b"k", b"2"), (b"k", b"3")])]},
    "within_one_batch": lambda: {1: [packed([(b"k", b"1"), (b"k", None),
                                             (b"j", None), (b"j", b""),
                                             (b"k", b"9")])]},
    "dict_between_packed": lambda: {1: [packed([(b"a", b"1"), (b"b", b"1")]),
                                        {b"a": None, b"c": b"2"},
                                        packed([(b"c", None), (b"d", b"3"),
                                                (b"a", b"4")])]},
    "empty_batch": lambda: {1: [packed([])], 2: [packed([]), {b"a": b"1"}],
                            3: []},
    "prefix_keys": lambda: {1: [packed([
        (b"ab", b"2"), (b"a", b"1"), (b"abc", None), (b"", b"e"),
        (b"ab\x00", b"z"), (b"abcdefgh", b"8"), (b"abcdefghi", b"9"),
        (b"abcdefgh\x00", None), (b"abcdefg", b"7"), (b"ab", b"22")])]},
    "key_65535": lambda: {1: [packed([(b"k" * 65535, b"v"), (b"a", None)])]},
    "key_65536": lambda: {1: [packed([(b"a", b"1")])],
                          2: [packed([(b"k" * 65536, b"v")])]},
    "tables_unordered": lambda: {9: [packed([(b"n", b"9")])],
                                 2: [packed([(b"t", None)])],
                                 7: [{b"b": b""}, packed([(b"a", b"7")])]},
    "random_10k": _random_layers,
}


@pytest.mark.parametrize("case", list(SEGMENT_CASES))
def test_segment_of_layers_equals_the_python_loop(case):
    """Byte for byte the segment the Python row loop writes from the
    layers' dict view; a key past the ``<H`` length stands the native path
    aside and the same error surfaces."""
    native_or_skip()
    deltas = SEGMENT_CASES[case]()
    by_dict = CheckpointLog._dict_deltas(deltas)
    got = CheckpointLog._segment_native(deltas)
    if case == "key_65536":
        assert got is None
        with pytest.raises(struct.error):
            CheckpointLog._encode_segment(deltas)
        return
    payload, rows = got
    assert payload == CheckpointLog._encode_segment_py(by_dict)
    assert rows == sum(map(len, by_dict.values()))
    assert CheckpointLog._encode_segment(deltas) == payload
    assert CheckpointLog._decode_segment(payload) == by_dict


def spans_named(epoch: int, name: str) -> list:
    return [s for s in tracing.GLOBAL_TRACE.snapshot(epoch)
            if s.name == name]


def test_a_key_in_two_epochs_is_written_once_and_the_last_wins(tmp_path):
    """Epochs 1–3 pending at one commit: the layers are handed on in epoch
    order, unmerged; the segment carries each key's last row (q104 puts and
    retracts a row between two commits: the tombstone is what is kept)."""
    native_or_skip()
    tracing.GLOBAL_TRACE.clear()
    st = DurableStateStore(str(tmp_path))
    st.ingest_layers(7, 1, [packed([(b"a", b"1"), (b"b", b"1"),
                                    (b"r", b"1")])])
    st.ingest_layers(7, 2, [packed([(b"a", b"2"), (b"r", None)])])
    st.ingest_layers(7, 3, [{b"c": b"3"}, packed([(b"b", None),
                                                  (b"b", b"4")])])
    st.commit(3)
    want = {b"a": b"2", b"b": b"4", b"c": b"3", b"r": None}
    segment = st.log.store.get("epoch_000000000003.seg")
    assert segment == CheckpointLog._encode_segment_py({7: want})
    (pending,) = spans_named(3, "commit.pending")
    (encode,) = spans_named(3, "segment.encode")
    (apply,) = spans_named(3, "store.apply")
    assert pending.args == {"rows": 8, "packed": 7, "dict_tables": [7]}
    assert (encode.args["rows"], encode.args["packed"]) == (4, 7)
    assert apply.args == {"rows": 8, "packed": 7}
    assert dict(st.iter_table(7)) == {b"a": b"2", b"b": b"4", b"c": b"3"}
    assert dict(DurableStateStore(str(tmp_path)).iter_table(7)) == \
        dict(st.iter_table(7))


# -- reads of a staged packed layer, before any commit -----------------------

SCHEMA = Schema.of(("g", INT64), ("id", INT64), ("name", VARCHAR))
PK = [0, 1]


def phys(row):
    return tuple(None if v is None else t.to_physical(v)
                 for v, t in zip(row, SCHEMA.types))


def enc(row) -> tuple:
    p = phys(row)
    return (encode_key([p[i] for i in PK], [SCHEMA.types[i] for i in PK]),
            encode_value_row(p, SCHEMA.types))


COMMITTED = [(1, 1, "one"), (1, 2, "two"), (2, 1, "uno"), (3, 1, "gone")]
#: staged on top, in order: an update, a delete, a new row, a row put and
#: retracted, a delete re-put
STAGED = [((1, 2, "TWO"), True), ((3, 1, "gone"), False),
          ((2, 2, "dos"), True), ((4, 1, "flash"), True),
          ((4, 1, "flash"), False), ((1, 1, "one"), False),
          ((1, 1, "ONE"), True)]


def staged_table(form: str, store=None):
    """COMMITTED, then STAGED on top of it: ``packed`` as one batch,
    ``rows`` through insert() / delete() (the reference)."""
    store = store or MemoryStateStore()
    table = StateTable(store, 5, SCHEMA, PK)
    for row in COMMITTED:
        table.insert(phys(row))
    table.commit(1)
    store.commit(1)
    if form == "packed":
        table.stage_packed(packed(
            [(enc(row)[0], enc(row)[1] if put else None)
             for row, put in STAGED]))
    else:
        for row, put in STAGED:
            (table.insert if put else table.delete)(phys(row))
    return store, table


def reads_of(store, table) -> dict:
    """Every read surface, of the table and of the store under it."""
    after = enc((1, 2, "x"))[0]
    return {
        "get_row": [table.get_row(phys(r)[:2]) for r in
                    [(1, 1), (1, 2), (2, 1), (2, 2), (3, 1), (4, 1), (9, 9)]],
        "scan_all": list(table.scan_all()),
        "scan_after": [table.scan_after(None, 2), table.scan_after(after, 10)],
        "scan_prefix": [list(table.scan_prefix([g], 1)) for g in (1, 2, 3, 4)],
        "len": len(table),
        "dirty": table.is_dirty(),
        "store.get": [store.get(5, enc(r)[0]) for r in COMMITTED],
        "iter_table": list(store.iter_table(5)),
        "table_len": store.table_len(5),
    }


@pytest.mark.parametrize("where", ["in_the_table", "in_the_store"])
def test_reads_see_a_staged_packed_layer(where):
    """Read-your-writes of a packed layer — in the table's own buffer, and
    sealed into the store's pending epoch — equals the rows written one by
    one; then insert() / delete() land on top of it."""
    got_store, got = staged_table("packed")
    want_store, want = staged_table("rows")
    if where == "in_the_store":
        got.commit(2)
        want.commit(2)
        assert not got.is_dirty()
        (layer,) = got_store._pending[2][5]
        assert isinstance(layer, PackedBatch)
    assert reads_of(got_store, got) == reads_of(want_store, want)
    assert got.get_row(phys((4, 1))[:2]) is None
    assert got.get_row(phys((1, 1))[:2]) == phys((1, 1, "ONE"))
    for table in (got, want):
        table.insert(phys((3, 1, "back")))
        table.delete(phys((2, 2, "dos")))
        table.insert(phys((5, 5, "new")))
    assert reads_of(got_store, got) == reads_of(want_store, want)
    assert [type(layer) for layer in got._layers][-1] is dict
    for store, table in ((got_store, got), (want_store, want)):
        table.commit(3)
        store.commit(3)
    assert reads_of(got_store, got) == reads_of(want_store, want)
    assert got_store._pending == {} and not got.is_dirty()
    assert sorted(r[2] for r in got.scan_all()) == sorted(
        SCHEMA.types[2].to_physical(s)
        for s in ("ONE", "TWO", "uno", "back", "new"))


def test_a_raw_insert_under_a_packed_batch_keeps_its_place():
    """insert()'s raw rows are sealed under a batch staged after them: the
    batch's delete of the same key wins, whatever the key's bytes."""
    store = MemoryStateStore()
    table = StateTable(store, 5, SCHEMA, PK)
    table.insert(phys((1, 1, "raw")))
    table.insert(phys((1, 2, "kept")))
    table.stage_packed(packed([(enc((1, 1, "x"))[0], None)]))
    assert table.get_row(phys((1, 1))[:2]) is None
    assert list(table.scan_all()) == [phys((1, 2, "kept"))]
    table.commit(1)
    assert [type(layer) for layer in store._pending[1][5]] == \
        [dict, PackedBatch]
    store.commit(1)
    assert dict(store.iter_table(5)) == dict([enc((1, 2, "kept"))])


# -- a checkpoint of N rows enters no per-row entry point --------------------

def count_calls(monkeypatch, owner, name: str, calls: dict,
                wrap=lambda f: f) -> None:
    real = getattr(owner, name)

    def spy(*a, **kw):
        calls[name] = calls.get(name, 0) + 1
        return real(*a, **kw)
    monkeypatch.setattr(owner, name, wrap(spy))


def checkpoint_through_both_writers(data_dir: str, n: int):
    """``n`` MV rows through ``MaterializeExecutor`` (two chunks, an
    update among them) and ``n`` state rows through ``stage_delta`` (ten
    of them deletes), sealed to epoch 2 and committed."""
    store = DurableStateStore(data_dir)
    mv_table = StateTable(store, 1, SCHEMA, PK)
    state = StateTable(store, 2, SCHEMA, PK)
    rows = [(i % 7, i, "n%d" % (i % 13)) for i in range(n)]
    half = n // 2
    msgs = [Barrier.new(1), make_chunk(SCHEMA, rows[:half]),
            make_chunk(SCHEMA, rows[half:] + rows[:1] + [(0, 0, "upd")],
                       ops=[OP_INSERT] * (n - half) + [OP_DELETE, OP_INSERT]),
            Barrier.new(2, checkpoint=True)]
    mv = MaterializeExecutor(MockSource(SCHEMA, msgs), mv_table)

    async def drive():
        async for _ in mv.execute():
            pass
    asyncio.run(drive())
    datas = [np.array([phys(r)[c] for r in rows]) for c in range(3)]
    masks = [np.ones(n, bool)] * 3
    dels = np.zeros(n, bool)
    dels[:10] = True
    with tracing.span("agg.state_delta", epoch=2):
        stage_delta(state, 2, datas, masks, ~dels, dels)
    store.commit(2)
    return store


def test_checkpoint_of_n_rows_runs_no_python_row_call(tmp_path, monkeypatch):
    """Counts, no timing (after PR 34's ``test_commit_of_n_rows_runs_no_
    python_row_loop``): with the codec a checkpoint through ``stage_delta``
    and ``Materialize`` never enters a per-row entry point, and the spans
    read ``packed == rows``."""
    native_or_skip()
    calls: dict = {}
    for name in ("stage_encoded", "insert", "delete"):
        count_calls(monkeypatch, StateTable, name, calls)
    for name in ("encode_keys", "encode_value_rows"):
        count_calls(monkeypatch, native_mod.RowCodec, name, calls)
    count_calls(monkeypatch, CheckpointLog, "_encode_segment_py", calls,
                wrap=staticmethod)
    cuts = []
    real_cut = PackedColumn.cut
    monkeypatch.setattr(PackedColumn, "cut",
                        lambda self: cuts.append(len(self)) or real_cut(self))
    tracing.GLOBAL_TRACE.clear()
    n = 1000
    pending_layers = []
    real_commit = DurableStateStore.commit
    monkeypatch.setattr(
        DurableStateStore, "commit",
        lambda self, epoch: pending_layers.extend(
            self.pending_layers(1) + self.pending_layers(2))
        or real_commit(self, epoch))
    store = checkpoint_through_both_writers(str(tmp_path), n)
    assert calls == {}
    (pending,) = spans_named(2, "commit.pending")
    (encode,) = spans_named(2, "segment.encode")
    (apply,) = spans_named(2, "store.apply")
    rows = (n + 2) + n
    assert pending.args == {"rows": rows, "packed": rows, "dict_tables": []}
    assert apply.args == {"rows": rows, "packed": rows}
    # a key once (tombstones are rows of a segment too)
    assert encode.args == {"rows": 2 * n, "packed": rows, "native": 1,
                           "bytes": encode.args["bytes"]}
    (delta,) = spans_named(2, "delta.encode")
    (stage,) = spans_named(2, "delta.stage")
    assert delta.args["rows"] == n and delta.args["native"] == 1
    assert delta.args["bytes"] > 0
    assert stage.args == {"puts": n - 10, "deletes": 10}
    # the ONE cut: each layer's keys and values once — the MV's batch at
    # its barrier (its view is kept), the delta's at the store's apply
    assert sorted(cuts) == sorted([n + 2, n + 1, n, n - 10])
    assert [layer.viewed for layer in pending_layers] == [True, False]
    assert store.table_len(1) == n and store.table_len(2) == n - 10
    segment = store.log.store.get("epoch_000000000002.seg")
    assert segment == CheckpointLog._encode_segment_py(
        CheckpointLog._decode_segment(segment))


def test_disable_env_writes_the_same_bytes_through_rows(tmp_path,
                                                        monkeypatch):
    """RW_TPU_DISABLE_NATIVE=1: no layer is packed (``packed`` 0, the
    Python loop writes the segment) and the bytes on disk are the same."""
    native_or_skip()
    n = 200
    checkpoint_through_both_writers(str(tmp_path / "native"), n)
    monkeypatch.setenv("RW_TPU_DISABLE_NATIVE", "1")
    monkeypatch.setattr(native_mod, "_lib", None)
    monkeypatch.setattr(native_mod, "_tried", False)
    assert native_mod.codec() is None
    tracing.GLOBAL_TRACE.clear()
    store = checkpoint_through_both_writers(str(tmp_path / "python"), n)
    name = "epoch_000000000002.seg"
    assert store.log.store.get(name) == DurableStateStore(
        str(tmp_path / "native")).log.store.get(name)
    (pending,) = spans_named(2, "commit.pending")
    (encode,) = spans_named(2, "segment.encode")
    (apply,) = spans_named(2, "store.apply")
    assert pending.args["packed"] == apply.args["packed"] == 0
    assert pending.args["dict_tables"] == [1, 2]
    assert (encode.args["packed"], encode.args["native"]) == (0, 0)
    assert pending.args["rows"] == apply.args["rows"] == encode.args["rows"]


def test_dict_view_of_layers_is_last_wins():
    layers = [packed([(b"a", b"1"), (b"b", b"1")]), {b"a": None, b"c": b"2"},
              packed([(b"c", None), (b"a", b"4")])]
    assert dict_view(layers) == {b"a": b"4", b"b": b"1", b"c": None}
    assert dict_view([]) == {}
    one = {b"k": b"v"}
    assert dict_view([one]) is one
