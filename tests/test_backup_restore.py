"""Meta-snapshot backup/restore (VERDICT r4 missing #8; reference:
src/meta/src/backup_restore/backup_manager.rs, src/storage/backup/)."""

import os
import subprocess
import sys
import tempfile

import pytest

from risingwave_tpu.frontend import Session
from risingwave_tpu.storage.backup import (
    BackupError, create_backup, list_backup, restore_backup,
)


def _populate(data):
    s = Session(data_dir=data)
    s.run_sql("CREATE TABLE t (id BIGINT PRIMARY KEY, v BIGINT)")
    s.run_sql("CREATE MATERIALIZED VIEW m AS "
              "SELECT count(*) AS n, sum(v) AS sv FROM t")
    s.run_sql("INSERT INTO t VALUES (1, 10), (2, 20), (3, 30)")
    s.tick()
    s.run_sql("FLUSH")
    rows = s.mv_rows("m")
    s.close()
    return rows


def test_backup_restore_roundtrip():
    with tempfile.TemporaryDirectory() as d:
        data = os.path.join(d, "data")
        bak = os.path.join(d, "bak")
        restored = os.path.join(d, "restored")
        before = _populate(data)

        desc = create_backup(data, bak)
        assert desc["committed_epoch"] is not None
        assert "manifest.json" in desc["files"]
        assert any(f.endswith(".seg") for f in desc["files"])
        assert list_backup(bak)["backup_id"] == desc["backup_id"]

        restore_backup(bak, restored)
        s = Session(data_dir=restored)
        assert s.mv_rows("m") == before
        # the restored cluster is fully live: writes keep flowing
        s.run_sql("INSERT INTO t VALUES (4, 40)")
        s.tick()
        assert s.mv_rows("m") == [(4, 100)]
        s.close()

        # and the ORIGINAL is untouched by the restored cluster's writes
        s0 = Session(data_dir=data)
        assert s0.mv_rows("m") == before
        s0.close()


def test_backup_after_restore_divergence_and_preconditions():
    with tempfile.TemporaryDirectory() as d:
        data = os.path.join(d, "data")
        bak = os.path.join(d, "bak")
        _populate(data)
        create_backup(data, bak)
        with pytest.raises(BackupError):
            create_backup(data, bak)          # double-backup refused
        with pytest.raises(BackupError):
            restore_backup(bak, data)         # non-empty target refused
        with pytest.raises(BackupError):
            list_backup(data)                 # not a backup dir


def test_backup_excludes_orphan_segments():
    """A torn-publish orphan segment (present on disk, absent from the
    manifest) must not be captured — the snapshot is the manifest's
    version, like the reference excluding unreferenced SSTs."""
    with tempfile.TemporaryDirectory() as d:
        data = os.path.join(d, "data")
        bak = os.path.join(d, "bak")
        _populate(data)
        orphan = os.path.join(data, "epoch_999999.seg")
        with open(orphan, "wb") as f:
            f.write(b"torn")
        desc = create_backup(data, bak)
        assert "epoch_999999.seg" not in desc["files"]
        assert not os.path.exists(os.path.join(bak, "epoch_999999.seg"))


def test_ctl_backup_cli():
    with tempfile.TemporaryDirectory() as d:
        data = os.path.join(d, "data")
        bak = os.path.join(d, "bak")
        _populate(data)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run(
            [sys.executable, "-m", "risingwave_tpu", "ctl", "backup",
             "--data-dir", data, "--backup-dir", bak],
            capture_output=True, text=True, env=env, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, r.stderr[-500:]
        assert "backup_id" in r.stdout
