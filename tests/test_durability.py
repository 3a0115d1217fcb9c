"""Durable checkpoint + crash recovery (VERDICT r2 item 3).

The e2e test REALLY kills the process: a subprocess builds a session over a
data dir, checkpoints via FLUSH, then os._exit(0)s without any graceful
shutdown; the parent recovers a fresh Session from the directory and
cross-checks MV contents, then keeps streaming into the recovered session."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from risingwave_tpu.common.row import decode_value_row, encode_value_row
from risingwave_tpu.common.types import (
    BOOL, FLOAT64, INT64, VARCHAR, GLOBAL_STRING_DICT,
)
from risingwave_tpu.storage.checkpoint import CheckpointLog, DurableStateStore


def test_value_row_roundtrip():
    types = [INT64, FLOAT64, BOOL, VARCHAR, INT64]
    sid = GLOBAL_STRING_DICT.intern("hello world")
    row = (42, -1.5, True, sid, None)
    enc = encode_value_row(row, types)
    assert decode_value_row(enc, types) == row
    # all-null row
    row2 = (None, None, None, None, None)
    assert decode_value_row(encode_value_row(row2, types), types) == row2


def test_durable_store_roundtrip(tmp_path):
    d = str(tmp_path / "ckpt")
    s1 = DurableStateStore(d)
    s1.ingest(7, 2, {b"a": b"row-a", b"b": b"row-b"}, set())
    s1.commit(2)
    s1.ingest(7, 3, {b"c": b"row-c"}, {b"a"})
    s1.ingest(9, 3, {b"x": b"row-x"}, set())
    s1.commit(3)

    s2 = DurableStateStore(d)
    assert s2.committed_epoch == 3
    assert dict(s2.iter_table(7)) == {b"b": b"row-b", b"c": b"row-c"}
    assert dict(s2.iter_table(9)) == {b"x": b"row-x"}

    # compaction folds segments without changing the view
    s2.log.compact()
    s3 = DurableStateStore(d)
    assert dict(s3.iter_table(7)) == {b"b": b"row-b", b"c": b"row-c"}
    assert s3.committed_epoch == 3


def test_mv_created_after_last_checkpoint_rebackfills(tmp_path):
    """Crash in the window between CREATE MV (logged immediately) and the
    next checkpoint (which would persist its state): recovery must re-run
    the backfill snapshot from the recovered upstream."""
    d = str(tmp_path / "db")
    child = textwrap.dedent(f"""
        import os, sys
        from risingwave_tpu.frontend import Session
        s = Session(data_dir={d!r})
        s.run_sql("CREATE TABLE t (k BIGINT, v BIGINT)")
        s.run_sql("INSERT INTO t VALUES (1,10),(2,20)")
        s.flush()                      # t's rows durably committed
        s.run_sql('''CREATE MATERIALIZED VIEW m AS
            SELECT k, v * 2 AS d FROM t''')
        # crash BEFORE any checkpoint that includes m's state
        os._exit(0)
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-2000:]

    from risingwave_tpu.frontend import Session
    s = Session(data_dir=d)
    assert sorted(s.mv_rows("m")) == [(1, 20), (2, 40)]


def test_empty_flush_adds_no_segments(tmp_path):
    d = str(tmp_path / "db")
    from risingwave_tpu.frontend import Session
    s = Session(data_dir=d)
    s.run_sql("CREATE TABLE t (k BIGINT)")
    s.run_sql("INSERT INTO t VALUES (1)")
    s.flush()
    n0 = len(s.store.log._read_manifest()["segments"])
    for _ in range(5):
        s.flush()   # nothing new to persist
    m = s.store.log._read_manifest()
    assert len(m["segments"]) == n0
    assert m["committed_epoch"] == s.store.committed_epoch


def test_drop_tombstones_durable_state(tmp_path):
    d = str(tmp_path / "db")
    from risingwave_tpu.frontend import Session
    s = Session(data_dir=d)
    s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY)")
    tid = s.catalog.tables["t"].table_id
    s.run_sql("INSERT INTO t VALUES (1),(2),(3)")
    s.flush()
    s.run_sql("DROP TABLE t")
    s.flush()
    assert s.store.table_len(tid) == 0

    s2 = Session(data_dir=d)
    assert "t" not in s2.catalog.tables
    assert s2.store.table_len(tid) == 0   # not resurrected from old segments
    # compaction discards the dead rows entirely
    s2.store.log.compact()
    _, tables = s2.store.log.load_tables()
    assert tid not in tables


def test_crash_recovery_e2e(tmp_path):
    d = str(tmp_path / "db")
    child = textwrap.dedent(f"""
        import json, os, sys
        from risingwave_tpu.frontend import Session
        s = Session(data_dir={d!r})
        s.run_sql('''
            CREATE TABLE events (k BIGINT, cat VARCHAR, v BIGINT);
            CREATE MATERIALIZED VIEW agg AS
              SELECT cat, COUNT(*) AS cnt, SUM(v) AS total
              FROM events GROUP BY cat
        ''')
        s.run_sql("INSERT INTO events VALUES (1,'a',10),(2,'b',20),(3,'a',30)")
        s.flush()
        s.run_sql("INSERT INTO events VALUES (4,'b',5),(5,'c',7)")
        s.flush()
        # one more insert that is NOT checkpointed: must be lost on crash
        s.run_sql("INSERT INTO events VALUES (6,'z',999)")
        s.tick(generate=False, checkpoint=False)
        print("EXPECT " + json.dumps(sorted(s.mv_rows('agg'))))
        sys.stdout.flush()
        os._exit(0)   # crash: no graceful shutdown, no final checkpoint
    """)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", child], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-2000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("EXPECT ")][0]
    pre_crash = [tuple(r) for r in json.loads(line[len("EXPECT "):])]
    # the 'z' row was never checkpointed
    committed = sorted(r for r in pre_crash if r[0] != "z")
    assert ("z", 1, 999) in pre_crash

    from risingwave_tpu.frontend import Session
    s = Session(data_dir=d)
    assert sorted(s.mv_rows("agg")) == committed
    assert sorted(s.run_sql("SELECT k, cat, v FROM events")) == [
        (1, "a", 10), (2, "b", 20), (3, "a", 30), (4, "b", 5), (5, "c", 7)]

    # the recovered session keeps streaming: new DML folds into the MV
    s.run_sql("INSERT INTO events VALUES (7,'a',100)")
    s.flush()
    got = {r[0]: (r[1], r[2]) for r in s.mv_rows("agg")}
    assert got["a"] == (3, 140)
    assert got["b"] == (2, 25)
    assert got["c"] == (1, 7)

    # and survives a SECOND recovery
    s2 = Session(data_dir=d)
    assert sorted(s2.mv_rows("agg")) == sorted(s.mv_rows("agg"))
    # row ids continued above the recovered ones: all 6 rows distinct
    assert len(s2.run_sql("SELECT k, cat, v FROM events")) == 6


def test_folded_segment_name_never_collides_across_restart(tmp_path):
    """Advisor r4: _compact_seq is process-local; a fold after restart must
    not regenerate (and overwrite) an existing folded segment's name."""
    log = CheckpointLog(str(tmp_path), compact_after=1000)
    log.append_epoch(1, {7: {b"a": b"1"}})
    log.append_epoch(2, {7: {b"b": b"2"}})
    log.compact()
    first = log._read_manifest()["segments"]
    assert len(first) == 1 and ".c1-" in first[0]

    # fresh process: seq resets to 0; same committed epoch gets new segments
    log2 = CheckpointLog(str(tmp_path), compact_after=1000)
    log2.append_epoch(2, {7: {b"c": b"3"}})
    log2.compact()
    folded = log2._read_manifest()["segments"]
    assert len(folded) == 1
    # the per-process uuid token keeps the new fold's name distinct from
    # the still-live pre-restart fold
    assert folded[0] != first[0]
    _, tables = log2.load_tables()
    assert tables[7] == {b"a": b"1", b"b": b"2", b"c": b"3"}


def test_load_tables_retries_when_compactor_deletes_segment(tmp_path):
    """Advisor r4: a reader that fetched the manifest just before a
    compaction swap must converge by re-reading, not raise FileNotFound."""
    log = CheckpointLog(str(tmp_path), compact_after=1000)
    log.append_epoch(1, {7: {b"a": b"1"}})
    log.append_epoch(2, {7: {b"b": b"2"}})

    reader = CheckpointLog(str(tmp_path), compact_after=1000)
    stale = reader._read_manifest()
    log.compact()  # deletes the base segments the stale manifest references

    # simulate the race: first manifest read returns the stale snapshot
    calls = {"n": 0}
    real = reader._read_manifest

    def flaky():
        calls["n"] += 1
        return stale if calls["n"] == 1 else real()

    reader._read_manifest = flaky
    epoch, tables = reader.load_tables()
    assert epoch == 2
    assert tables[7] == {b"a": b"1", b"b": b"2"}


# -- segments laid out by the native codec (native/rowcodec.cpp) -------------

def _native_or_skip():
    from risingwave_tpu.native import codec
    if codec() is None:
        pytest.skip("native toolchain unavailable")


def _drive_epochs(st, model, epochs, seed, form="dict"):
    """Ingest and commit ``epochs`` of random puts and deletes over three
    tables — as a dict layer, or as a packed batch (the deletes first) the
    way ``stage_delta`` stages one; ``model`` follows as plain dicts."""
    import random
    from test_packed_delta import packed
    rng = random.Random(seed)
    for e in epochs:
        for tid in (4, 2, 9):
            tbl = model.setdefault(tid, {})
            puts = {b"%d-%04d" % (tid, rng.randrange(400)):
                    rng.randbytes(rng.randrange(0, 24)) for _ in range(60)}
            live = sorted(set(tbl) - set(puts))
            dels = set(rng.sample(live, min(len(live), 15)))
            if form == "packed":
                st.ingest_layers(tid, e, [packed(
                    [(k, None) for k in sorted(dels)] + list(puts.items()))])
            else:
                st.ingest(tid, e, puts, dels)
            tbl.update(puts)
            for k in dels:
                del tbl[k]
        st.commit(e)


def _tables(st):
    return {tid: dict(st.iter_table(tid)) for tid in (4, 2, 9)}


@pytest.mark.parametrize("form", ["dict", "packed"])
@pytest.mark.parametrize("how", ["straight", "folded", "torn"])
def test_native_segments_recover(tmp_path, how, form):
    """Written through the native encoder — from dict layers, and from
    packed batches (ISSUE 38) — read back by a fresh store: straight, after
    a fold, and with a torn segment left unreferenced."""
    from risingwave_tpu.common.failpoint import failpoints
    _native_or_skip()
    d = str(tmp_path)
    st, model = DurableStateStore(d, compact_after=1000), {}
    _drive_epochs(st, model, range(1, 7), seed=34, form=form)
    if how == "folded":
        st.log.compact()
        assert len(st.log._read_manifest()["segments"]) == 1
    if how == "torn":
        committed = {tid: dict(t) for tid, t in model.items()}
        st.ingest(4, 7, {b"4-torn": b"x"}, set())
        with failpoints(**{"checkpoint.segment.write.partial": OSError}):
            with pytest.raises(OSError):
                st.commit(7)
        torn = "epoch_000000000007.seg"
        assert len(st.log.store.get(torn)) == 4
        assert torn not in st.log._read_manifest()["segments"]
        st2 = DurableStateStore(d)
        assert st2.committed_epoch == 6 and _tables(st2) == committed
        st.commit(7)                      # the retry overwrites the torn one
        model[4][b"4-torn"] = b"x"
    st3 = DurableStateStore(d)
    assert st3.committed_epoch == (7 if how == "torn" else 6)
    assert _tables(st3) == model


@pytest.mark.parametrize("first", ["python", "native"])
def test_segments_of_either_encoder_reopen_under_the_other(
        tmp_path, monkeypatch, first):
    """An older data_dir (segments of the Python loop) reopens under a store
    that writes natively, keeps growing, and the other way round."""
    _native_or_skip()
    d = str(tmp_path)
    native = CheckpointLog._segment_native

    def use(encoder):
        monkeypatch.setattr(
            CheckpointLog, "_segment_native",
            staticmethod(native if encoder == "native"
                         else (lambda deltas: None)))

    second = "native" if first == "python" else "python"
    model = {}
    use(first)
    _drive_epochs(DurableStateStore(d), model, range(1, 4), seed=1)
    use(second)
    st = DurableStateStore(d)
    assert st.committed_epoch == 3 and _tables(st) == model
    _drive_epochs(st, model, range(4, 7), seed=2)
    st.log.compact()
    use(first)
    st2 = DurableStateStore(d)
    assert st2.committed_epoch == 6 and _tables(st2) == model
