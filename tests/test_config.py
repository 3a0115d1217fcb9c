"""Config system + system params + CLI (coverage #6/#7/#83)."""

import subprocess
import sys
import os

import pytest

from risingwave_tpu.common.config import RwConfig, load_config
from risingwave_tpu.frontend import Session


class TestConfig:
    def test_defaults(self):
        cfg = load_config()
        assert cfg.streaming.barrier_interval_ms == 1000
        assert cfg.streaming.checkpoint_frequency == 10
        assert cfg.server.port == 4566

    def test_toml_layering_and_overrides(self, tmp_path):
        p = tmp_path / "rw.toml"
        p.write_text("""
[streaming]
checkpoint_frequency = 4

[server]
port = 5433
""")
        cfg = load_config(str(p), **{"streaming.chunk_capacity": 256})
        assert cfg.streaming.checkpoint_frequency == 4
        assert cfg.server.port == 5433
        assert cfg.streaming.chunk_capacity == 256
        assert cfg.streaming.barrier_interval_ms == 1000   # untouched default

    def test_fallback_parser_subset(self, tmp_path):
        """The tomllib-less fallback parser (py3.10) handles the config
        subset: sections, ints/floats/bools, quoted strings — including
        '#' INSIDE a quoted value — and trailing comments."""
        from risingwave_tpu.common.config import _parse_toml_subset
        data = _parse_toml_subset("""
# header comment
[storage]
data_dir = "/tmp/run#3"          # trailing comment
compactors = 2

[streaming]
coschedule = true
slow_epoch_threshold_ms = 1.5
""")
        assert data["storage"]["data_dir"] == "/tmp/run#3"
        assert data["storage"]["compactors"] == 2
        assert data["streaming"]["coschedule"] is True
        assert data["streaming"]["slow_epoch_threshold_ms"] == 1.5

    def test_unknown_keys_rejected(self, tmp_path):
        p = tmp_path / "rw.toml"
        p.write_text("[streaming]\nbogus_key = 1\n")
        with pytest.raises(ValueError, match="bogus_key"):
            load_config(str(p))
        with pytest.raises(ValueError, match="section"):
            load_config(**{"nosection.x": 1})

    def test_session_from_rw_config(self):
        cfg = load_config(**{"streaming.checkpoint_frequency": 3,
                             "streaming.chunk_capacity": 128})
        s = Session(rw_config=cfg)
        assert s.checkpoint_frequency == 3
        assert s.config.chunk_capacity == 128


class TestSystemParams:
    def test_set_and_show(self):
        s = Session()
        s.run_sql("SET checkpoint_frequency = 2")
        assert s.checkpoint_frequency == 2
        s.run_sql("SET in_flight_barrier_nums TO 4")
        assert s.in_flight_barriers == 4
        params = dict(s.run_sql("SHOW PARAMETERS"))
        assert params["checkpoint_frequency"] == "2"
        with pytest.raises(Exception, match="parameter"):
            s.run_sql("SET nonsense = 1")

    def test_set_applies_to_checkpoints(self, tmp_path):
        s = Session(data_dir=str(tmp_path / "db"))
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY)")
        s.run_sql("SET checkpoint_frequency = 1")
        s.run_sql("INSERT INTO t VALUES (1)")
        s.tick()          # every tick checkpoints now
        s._drain_inflight()
        assert s.store.committed_epoch > 0


class TestCli:
    def test_sql_subcommand(self):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        res = subprocess.run(
            [sys.executable, "-m", "risingwave_tpu", "sql",
             "CREATE TABLE t (k BIGINT PRIMARY KEY); "
             "INSERT INTO t VALUES (41); FLUSH; SELECT k + 1 FROM t"],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert res.returncode == 0, res.stderr[-2000:]
        assert res.stdout.strip().splitlines()[-1] == "42"


class TestCtl:
    def test_ctl_inspection(self, tmp_path):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        cwd = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        d = str(tmp_path / "db")
        res = subprocess.run(
            [sys.executable, "-m", "risingwave_tpu", "sql",
             "CREATE TABLE t (k BIGINT PRIMARY KEY); "
             "CREATE MATERIALIZED VIEW m AS SELECT count(*) AS c FROM t; "
             "FLUSH", "--data-dir", d],
            capture_output=True, text=True, timeout=600, env=env, cwd=cwd)
        assert res.returncode == 0, res.stderr[-1500:]
        res = subprocess.run(
            [sys.executable, "-m", "risingwave_tpu", "ctl", "jobs",
             "--data-dir", d],
            capture_output=True, text=True, timeout=600, env=env, cwd=cwd)
        assert res.returncode == 0, res.stderr[-1500:]
        assert "TABLE\tt" in res.stdout and "MV\tm" in res.stdout
        res = subprocess.run(
            [sys.executable, "-m", "risingwave_tpu", "ctl", "trace",
             "--data-dir", d],
            capture_output=True, text=True, timeout=600, env=env, cwd=cwd)
        assert res.returncode == 0 and "job 'm':" in res.stdout
