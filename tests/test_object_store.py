"""ObjectStore abstraction + background compaction (VERDICT r3 item 7).

The durable checkpoint log is parameterized by ObjectStore (reference:
src/object_store/src/object/mod.rs:93-136); segments fold on a background
thread off the barrier path (reference: standalone compactor,
src/storage/compactor/src/server.rs:57) while ticks keep committing.
"""

import threading

import pytest

from risingwave_tpu.storage.checkpoint import CheckpointLog, DurableStateStore
from risingwave_tpu.storage.object_store import (
    LocalFsObjectStore, MemObjectStore,
)


class TestObjectStoreBackends:
    @pytest.mark.parametrize("mk", [
        lambda tmp: MemObjectStore(),
        lambda tmp: LocalFsObjectStore(str(tmp / "objs")),
    ])
    def test_put_get_list_delete(self, tmp_path, mk):
        st = mk(tmp_path)
        assert st.get("a/x") is None and not st.exists("a/x")
        st.put("a/x", b"1")
        st.put("a/y", b"22")
        st.put("b/z", b"333")
        assert st.get("a/y") == b"22" and st.exists("a/x")
        assert st.list("a/") == ["a/x", "a/y"]
        assert st.list() == ["a/x", "a/y", "b/z"]
        st.atomic_put("a/x", b"new")
        assert st.get("a/x") == b"new"
        st.delete("a/x")
        assert st.get("a/x") is None
        st.delete("missing")          # idempotent

    def test_atomic_put_leaves_no_tmp_visible(self, tmp_path):
        st = LocalFsObjectStore(str(tmp_path / "objs"))
        st.atomic_put("m.json", b"{}")
        assert st.list() == ["m.json"]


class TestCheckpointLogOverObjectStore:
    def test_mem_backend_round_trip(self):
        store = MemObjectStore()
        log = CheckpointLog(object_store=store)
        log.append_epoch(2, {1: {b"k1": b"v1", b"k2": b"v2"}})
        log.append_epoch(4, {1: {b"k2": None}, 2: {b"a": b"b"}})
        log.log_ddl("CREATE TABLE t")
        epoch, tables = CheckpointLog(object_store=store).load_tables()
        assert epoch == 4
        assert tables[1] == {b"k1": b"v1"} and tables[2] == {b"a": b"b"}
        assert CheckpointLog(object_store=store).ddl() == ["CREATE TABLE t"]

    def test_durable_store_over_mem_object_store(self):
        store = MemObjectStore()
        s = DurableStateStore(object_store=store)
        s.ingest(7, 3, {b"k": ("row",)}, set())
        # value must be bytes for durability; emulate the table layer
        s._pending[3][7][0][b"k"] = b"row-bytes"
        s.commit(3)
        s2 = DurableStateStore(object_store=store)
        assert s2.committed_epoch == 3
        assert s2.get(7, b"k") == b"row-bytes"


class TestBackgroundCompaction:
    def test_fold_runs_off_thread_and_appends_interleave(self, tmp_path):
        log = CheckpointLog(str(tmp_path / "d"), compact_after=4)
        for e in range(1, 8):
            log.append_epoch(e, {1: {f"k{e}".encode(): b"v"}})
        log.wait_compaction()
        m = log._read_manifest()
        assert len(m["segments"]) <= 5          # folded under the threshold
        epoch, tables = log.load_tables()
        assert epoch == 7
        assert tables[1] == {f"k{e}".encode(): b"v" for e in range(1, 8)}

    def test_concurrent_appends_during_fold_survive(self, tmp_path):
        log = CheckpointLog(str(tmp_path / "d"), compact_after=2)
        n_appends = 40
        errs = []

        def appender():
            try:
                for e in range(100, 100 + n_appends):
                    log.append_epoch(e, {1: {f"c{e}".encode(): b"x"}})
            except BaseException as ex:   # noqa: BLE001
                errs.append(ex)

        t = threading.Thread(target=appender)
        t.start()
        while t.is_alive():               # folds race the appends
            log.compact()
        t.join()
        log.wait_compaction()
        assert not errs
        _, tables = log.load_tables()
        # every appended key survived every fold
        assert sorted(tables[1]) == [
            f"c{e}".encode() for e in range(100, 100 + n_appends)]
        assert all(v == b"x" for v in tables[1].values())

    def test_dropped_tables_discarded_in_fold(self, tmp_path):
        log = CheckpointLog(str(tmp_path / "d"))
        log.append_epoch(1, {1: {b"a": b"1"}, 2: {b"b": b"2"}})
        log.append_epoch(2, {1: {b"c": b"3"}})
        log.drop_table(1)
        log.compact()
        _, tables = log.load_tables()
        assert 1 not in tables and tables[2] == {b"b": b"2"}
