"""Hummock-lite state tiering: L0 flush + versioned manifest, recovery,
pinned snapshot reads under concurrent compaction, vacuum safety, and the
Session running end-to-end over the tier (incl. a REAL crash)."""

import os
import subprocess
import sys
import textwrap

import pytest

from risingwave_tpu.common.failpoint import failpoints
from risingwave_tpu.meta.hummock import HummockManager
from risingwave_tpu.storage.hummock import (
    SST_PREFIX, HummockStateStore, HummockVersion, run_compact_task,
)
from risingwave_tpu.storage.object_store import (
    LocalFsObjectStore, MemObjectStore,
)


def _store(**kw):
    kw.setdefault("object_store", MemObjectStore())
    kw.setdefault("inline_compaction", False)
    return HummockStateStore(**kw)


def _fill(st, table=7, epochs=range(1, 6)):
    for e in epochs:
        st.ingest(table, e, {b"k%03d" % e: b"v%d" % e}, set())
        st.commit(e)


class TestHummockStore:
    def test_commit_recover_roundtrip(self, tmp_path):
        d = str(tmp_path / "hm")
        st = HummockStateStore(data_dir=d, inline_compaction=False)
        st.ingest(7, 2, {b"a": b"row-a", b"b": b"row-b"}, set())
        st.commit(2)
        st.ingest(7, 3, {b"c": b"row-c"}, {b"a"})
        st.ingest(9, 3, {b"x": b"row-x"}, set())
        st.commit(3)

        st2 = HummockStateStore(data_dir=d)
        assert st2.committed_epoch == 3
        assert dict(st2.iter_table(7)) == {b"b": b"row-b", b"c": b"row-c"}
        assert dict(st2.iter_table(9)) == {b"x": b"row-x"}

        # compaction folds runs without changing the view
        st2.compact()
        st3 = HummockStateStore(data_dir=d)
        assert dict(st3.iter_table(7)) == {b"b": b"row-b", b"c": b"row-c"}
        assert st3.committed_epoch == 3

    def test_commit_of_a_table_staged_packed(self, tmp_path):
        """ISSUE 38: the tier takes the pending layers' dict view — a
        packed batch (an update pair and a retraction in it), a dict layer
        of another epoch under it — and recovers the same rows."""
        from test_packed_delta import packed
        d = str(tmp_path / "hm")
        st = HummockStateStore(data_dir=d, inline_compaction=False)
        st.ingest(7, 2, {b"a": b"old", b"gone": b"x"}, set())
        st.ingest_layers(7, 3, [packed([
            (b"a", None), (b"a", b"new"), (b"b", b"row-b"), (b"gone", None),
            (b"flash", b"1"), (b"flash", None)])])
        assert st.get(7, b"a") == b"new" and st.get(7, b"gone") is None
        st.commit(3)
        want = {b"a": b"new", b"b": b"row-b"}
        assert dict(st.iter_table(7)) == want
        st2 = HummockStateStore(data_dir=d)
        assert st2.committed_epoch == 3
        assert dict(st2.iter_table(7)) == want
        assert st2.get(7, b"flash") is None

    def test_idle_commit_adds_no_runs(self):
        st = _store()
        _fill(st, epochs=range(1, 3))
        n0 = len(st.manager.version.all_runs())
        for e in range(3, 8):
            st.commit(e)                     # nothing staged
        v = st.manager.version
        assert len(v.all_runs()) == n0
        assert v.committed_epoch == 7

    def test_drop_table_then_compact_discards_rows(self):
        st = _store()
        _fill(st, table=5)
        _fill(st, table=6, epochs=range(6, 9))
        st.drop_table(5)
        st.compact()
        # the folded tier holds only the live table
        st2 = HummockStateStore(object_store=st.object_store)
        assert dict(st2.iter_table(5)) == {}
        assert len(dict(st2.iter_table(6))) == 3

    def test_vacuum_no_orphans_after_drop(self):
        """CI vacuum-leak assertion: after drop + compact + vacuum, every
        SST the object store lists is referenced by the current version —
        object-store growth stays bounded."""
        st = _store()
        _fill(st, table=5)
        st.drop_table(5)
        st.compact()                          # also vacuums
        st.vacuum()
        listed = set(st.object_store.list(SST_PREFIX))
        assert listed == set(st.manager.version.all_runs())

    def test_tombstones_survive_until_bottom_compaction(self):
        st = _store()
        st.ingest(7, 1, {b"a": b"1"}, set())
        st.commit(1)
        st.ingest(7, 2, {}, {b"a"})           # delete in a later run
        st.commit(2)
        st2 = HummockStateStore(object_store=st.object_store)
        assert dict(st2.iter_table(7)) == {}
        st.compact()                          # bottom: tombstone dropped
        st3 = HummockStateStore(object_store=st.object_store)
        assert dict(st3.iter_table(7)) == {}


class TestPinnedReads:
    def test_pin_survives_concurrent_rewrite_and_vacuum(self):
        """Acceptance: a reader pinned to a version sees identical
        results while compaction rewrites that version's runs, and vacuum
        afterwards deletes every SST unreferenced by any pinned
        version."""
        st = _store()
        _fill(st, epochs=range(1, 9))
        snap = st.pin()
        before = dict(snap.iter_table(7))
        pinned_runs = set(snap.version.all_runs())
        assert before and pinned_runs

        st.compact()                          # rewrites + vacuums
        # the pinned runs survived vacuum (still referenced by the pin)
        listed = set(st.object_store.list(SST_PREFIX))
        assert pinned_runs <= listed
        # identical results through the pinned snapshot
        assert dict(snap.iter_table(7)) == before
        for e in range(1, 9):
            assert snap.get(7, b"k%03d" % e) == b"v%d" % e

        snap.unpin()
        deleted = st.vacuum()
        assert set(deleted) == pinned_runs - set(
            st.manager.version.all_runs())
        assert set(st.object_store.list(SST_PREFIX)) == set(
            st.manager.version.all_runs())

    def test_vacuum_spares_in_progress_upload(self):
        """Regression: the barrier path PUTs the L0 object before the
        version publish references it; a concurrently running vacuum (the
        compaction pump's) must not eat it in that window."""
        st = _store()
        _fill(st, epochs=range(1, 3))
        name = SST_PREFIX + "e000000000099-test.sst"
        st.manager.begin_upload(name)
        st.object_store.put(name, b"payload")
        assert name not in st.vacuum()          # protected while pending
        assert st.object_store.get(name) is not None
        st.manager.commit_epoch(99, name)       # now referenced
        assert name not in st.vacuum()
        # an aborted upload loses protection and becomes vacuum food
        name2 = SST_PREFIX + "e000000000100-test.sst"
        st.manager.begin_upload(name2)
        st.object_store.put(name2, b"payload")
        st.manager.abort_upload(name2)
        assert name2 in st.vacuum()

    def test_vacuum_spares_inflight_task_outputs(self):
        """Regression: a compactor (possibly another process) writes its
        ``c{task_id}-…`` outputs before the report references them —
        vacuum must skip them mid-task and reap them only if the task is
        cancelled."""
        st = _store()
        _fill(st)
        task = st.manager.get_compact_task(force=True)
        half = f"{SST_PREFIX}c{task.task_id:06d}-000-deadbeef.sst"
        st.object_store.put(half, b"half-written output")
        assert half not in st.vacuum()          # protected mid-task
        st.manager.cancel_compact_task(task.task_id)
        assert half in st.vacuum()              # zombie output reaped

    def test_vacuum_spares_inflight_task_inputs(self):
        st = _store()
        _fill(st)
        task = st.manager.get_compact_task(force=True)
        assert task is not None
        st.vacuum()
        for name in task.inputs:              # still readable mid-task
            assert st.object_store.get(name) is not None
        outputs = run_compact_task(st.object_store, task)
        st.manager.report_compact_task(task.task_id, outputs)
        st.vacuum()
        assert set(st.object_store.list(SST_PREFIX)) == set(outputs)


class TestVersionManager:
    def test_version_swap_is_atomic_and_monotonic(self):
        os_ = MemObjectStore()
        mgr = HummockManager(os_)
        mgr.commit_epoch(1, None)
        v1 = mgr.version
        mgr.log_ddl("CREATE TABLE t (k BIGINT)")
        v2 = mgr.version
        assert v2.vid == v1.vid + 1 and v2.ddl == ("CREATE TABLE t (k BIGINT)",)
        # a fresh manager over the same store sees the same version
        mgr2 = HummockManager(os_)
        assert mgr2.version == v2

    def test_late_report_from_cancelled_task_is_rejected(self):
        st = _store()
        _fill(st)
        task = st.manager.get_compact_task(force=True)
        outputs = run_compact_task(st.object_store, task)
        st.manager.cancel_compact_task(task.task_id)
        assert st.manager.report_compact_task(task.task_id, outputs) \
            is False
        # the zombie's outputs are orphans: vacuum removes them
        st.vacuum()
        for name in outputs:
            assert st.object_store.get(name) is None
        # the version still folds correctly
        st2 = HummockStateStore(object_store=st.object_store)
        assert len(dict(st2.iter_table(7))) == 5

    def test_roundtrip_version_codec(self):
        v = HummockVersion(vid=4, committed_epoch=9, l0=("a", "b"),
                           l1=("c",), ddl=("X",), dropped_tables=(3,))
        assert HummockVersion.from_bytes(v.to_bytes()) == v


class TestHummockFailpoints:
    def test_sst_write_fault_is_atomic(self):
        st = _store()
        _fill(st, epochs=range(1, 3))
        st.ingest(7, 3, {b"k003": b"v3"}, set())
        with failpoints(**{"hummock.sst.write": OSError}):
            with pytest.raises(OSError):
                st.commit(3)
        st2 = HummockStateStore(object_store=st.object_store)
        assert st2.committed_epoch == 2
        assert b"k003" not in dict(st2.iter_table(7))

    def test_torn_sst_object_never_referenced(self):
        st = _store()
        _fill(st, epochs=range(1, 3))
        st.ingest(7, 3, {b"k003": b"v3"}, set())
        with failpoints(**{"hummock.sst.write.partial": OSError}):
            with pytest.raises(OSError):
                st.commit(3)
        # a truncated orphan landed; recovery ignores it, and the SAME
        # process's vacuum eats it — the failed put must have aborted
        # its upload registration (it would otherwise be shielded for
        # the process lifetime)
        assert len(st.vacuum()) == 1
        st2 = HummockStateStore(object_store=st.object_store)
        assert st2.committed_epoch == 2
        assert st2.vacuum() == []

    def test_version_publish_fault_keeps_previous_epoch(self):
        st = _store()
        _fill(st, epochs=range(1, 3))
        st.ingest(7, 3, {b"k003": b"v3"}, set())
        with failpoints(**{"hummock.version.publish": OSError}):
            with pytest.raises(OSError):
                st.commit(3)
        st2 = HummockStateStore(object_store=st.object_store)
        assert st2.committed_epoch == 2     # no lost epochs ≤ committed


class TestSessionOverHummock:
    def test_session_e2e_and_recovery(self, tmp_path):
        from risingwave_tpu.frontend import Session
        d = str(tmp_path / "db")
        s = Session(data_dir=d, state_store="hummock",
                    checkpoint_frequency=1)
        s.run_sql("CREATE TABLE t (k BIGINT, v BIGINT)")
        s.run_sql("""CREATE MATERIALIZED VIEW m AS
                     SELECT k, v * 2 AS d FROM t""")
        for i in range(4):
            s.run_sql(f"INSERT INTO t VALUES ({i}, {i * 10})")
            s.flush()
        assert s.metrics()["storage"]["tier"] == "hummock"
        s.close()

        # plain Session(data_dir=...) auto-detects the hummock tier
        s2 = Session(data_dir=d)
        assert s2.state_store_kind == "hummock"
        assert sorted(s2.mv_rows("m")) == [(i, i * 20) for i in range(4)]
        s2.run_sql("INSERT INTO t VALUES (9, 90)")
        s2.flush()
        assert (9, 180) in s2.mv_rows("m")
        s2.close()

    def test_crash_recovery_loses_only_uncheckpointed(self, tmp_path):
        d = str(tmp_path / "db")
        child = textwrap.dedent(f"""
            import os
            from risingwave_tpu.frontend import Session
            s = Session(data_dir={d!r}, state_store="hummock")
            s.run_sql("CREATE TABLE t (k BIGINT, v BIGINT)")
            s.run_sql("INSERT INTO t VALUES (1,10),(2,20)")
            s.flush()
            s.run_sql("INSERT INTO t VALUES (3,999)")
            s.tick(generate=False, checkpoint=False)  # staged, not durable
            os._exit(0)                               # crash
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
        assert sorted(s.run_sql("SELECT k, v FROM t")) == [(1, 10), (2, 20)]
        s.close()

    def test_session_pin_version_api(self, tmp_path):
        from risingwave_tpu.frontend import Session
        d = str(tmp_path / "db")
        s = Session(data_dir=d, state_store="hummock")
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY)")
        s.run_sql("INSERT INTO t VALUES (1),(2)")
        s.flush()
        with s.pin_version() as snap:
            assert snap.version.committed_epoch == s.store.committed_epoch
            assert s.metrics()["storage"]["pinned_versions"] == 1
        assert s.metrics()["storage"]["pinned_versions"] == 0
        s.close()

    def test_rw_config_reopen_auto_detects_tier(self, tmp_path):
        """Regression: StorageConfig.state_store defaults to None (auto)
        — reopening a hummock dir through rw_config must not silently
        initialize a fresh segment store over it."""
        from risingwave_tpu.common.config import load_config
        from risingwave_tpu.frontend import Session
        d = str(tmp_path / "db")
        s = Session(data_dir=d, state_store="hummock")
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY)")
        s.run_sql("INSERT INTO t VALUES (1)")
        s.flush()
        s.close()
        cfg = load_config(**{"storage.data_dir": d})
        s2 = Session(rw_config=cfg)
        assert s2.state_store_kind == "hummock"
        assert s2.run_sql("SELECT k FROM t") == [(1,)]
        s2.close()

    def test_explicit_tier_mismatch_refuses(self, tmp_path):
        """An explicit state_store that contradicts the dir's actual
        tier must refuse instead of recovering an empty store."""
        from risingwave_tpu.frontend import Session
        d = str(tmp_path / "hmdir")
        s = Session(data_dir=d, state_store="hummock")
        s.run_sql("CREATE TABLE t (k BIGINT)")
        s.flush()
        s.close()
        with pytest.raises(ValueError, match="hummock"):
            Session(data_dir=d, state_store="segment")
        d2 = str(tmp_path / "segdir")
        s3 = Session(data_dir=d2)            # segment by default
        s3.run_sql("CREATE TABLE t (k BIGINT)")
        s3.flush()
        s3.close()
        with pytest.raises(ValueError, match="segment"):
            Session(data_dir=d2, state_store="hummock")

    def test_pin_requires_hummock(self):
        from risingwave_tpu.frontend import Session
        from risingwave_tpu.frontend.session import SqlError
        s = Session()
        with pytest.raises(SqlError, match="hummock"):
            s.pin_version()
        s.close()


class TestHummockBackup:
    def test_backup_restore_hummock_dir(self, tmp_path):
        from risingwave_tpu.frontend import Session
        from risingwave_tpu.storage.backup import (
            create_backup, restore_backup,
        )
        d = str(tmp_path / "db")
        s = Session(data_dir=d, state_store="hummock")
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
        s.run_sql("INSERT INTO t VALUES (1, 10), (2, 20)")
        s.flush()
        s.close()

        bdir = str(tmp_path / "bk")
        desc = create_backup(d, bdir)
        assert desc["tier"] == "hummock"
        assert "hummock/version.json" in desc["files"]

        d2 = str(tmp_path / "restored")
        restore_backup(bdir, d2)
        s2 = Session(data_dir=d2)
        assert s2.state_store_kind == "hummock"
        assert sorted(s2.run_sql("SELECT k, v FROM t")) == [(1, 10), (2, 20)]
        s2.close()
