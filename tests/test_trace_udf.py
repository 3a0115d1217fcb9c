"""Tracing dump, UDFs (coverage #85/#14)."""

import pytest

from risingwave_tpu.common.types import FLOAT64, INT64, VARCHAR
from risingwave_tpu.expr.udf import drop_udf, register_udf
from risingwave_tpu.frontend import Session
from risingwave_tpu.stream.trace import dump_session


class TestTrace:
    def test_dump_shows_pipeline_and_counters(self):
        s = Session()
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
        s.run_sql("CREATE MATERIALIZED VIEW m AS "
                  "SELECT k, sum(v) AS sv FROM t GROUP BY k")
        s.run_sql("INSERT INTO t VALUES (1, 2)")
        s.flush()
        out = dump_session(s)
        assert "job 'm':" in out
        assert "Materialize" in out and "HashAgg" in out
        assert "barriers=" in out
        assert f"completed={s.epoch}" in out


class TestUdf:
    def test_scalar_udf_in_sql(self):
        register_udf("add_tax", lambda v: int(v * 1.1), [INT64], INT64)
        try:
            s = Session()
            s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
            s.run_sql("INSERT INTO t VALUES (1, 100), (2, 200)")
            s.flush()
            rows = dict(s.run_sql("SELECT k, add_tax(v) FROM t"))
            assert rows == {1: 110, 2: 220}
            # strict NULL handling
            s.run_sql("INSERT INTO t VALUES (3, NULL)")
            s.flush()
            rows = dict(s.run_sql("SELECT k, add_tax(v) FROM t"))
            assert rows[3] is None
        finally:
            drop_udf("add_tax")

    def test_varchar_udf_and_mv(self):
        register_udf("shout", lambda s_: s_.upper() + "!", [VARCHAR], VARCHAR)
        try:
            s = Session()
            s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, s VARCHAR)")
            s.run_sql("CREATE MATERIALIZED VIEW m AS "
                      "SELECT k, shout(s) AS x FROM t")
            s.run_sql("INSERT INTO t VALUES (1, 'hey')")
            s.flush()
            assert s.mv_rows("m") == [(1, "HEY!")]
        finally:
            drop_udf("shout")

    def test_vectorized_udf(self):
        import numpy as np
        register_udf("sq", lambda a: a * a, [FLOAT64], FLOAT64,
                     vectorized=True)
        try:
            s = Session()
            s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, x DOUBLE)")
            s.run_sql("INSERT INTO t VALUES (1, 3.0)")
            s.flush()
            assert s.run_sql("SELECT sq(x) FROM t") == [(9.0,)]
        finally:
            drop_udf("sq")

    def test_name_collision_rejected(self):
        with pytest.raises(ValueError, match="already exists"):
            register_udf("lower", lambda s_: s_, [VARCHAR], VARCHAR)


class TestDropUdfGuard:
    def test_drop_udf_refuses_builtins(self):
        with pytest.raises(ValueError, match="not a registered UDF"):
            drop_udf("upper")
