"""Out-of-process UDF plane (ISSUE 15, docs/robustness.md).

Fast tier: wire codecs, function shipping, bit-exact parity inproc vs
out-of-process, restart semantics (deadline trip, deterministic
kill -9 mid-batch, reply-after-fence dropped, retry-exhausted typed
error, user exceptions not burning respawns), backpressure, metrics.

Slow tier (scripts/check.sh UDF subset): the seeded udf-link chaos
scenario + replay determinism, the kill-mid-epoch acceptance run under
pipeline_depth=2 with a co-scheduled group, the crash-point sweep over
the udf.* failpoint sites, `ctl udf serve` + external attach, and the
soak seed and its schema-stable record.
"""

import json
import os
import threading
import time

import numpy as np
import pytest

from risingwave_tpu.common.config import UdfConfig
from risingwave_tpu.common.types import (
    BOOL, FLOAT64, INT64, VARCHAR, DataType, TypeKind,
)
from risingwave_tpu.expr.udf import drop_udf, register_udf
from risingwave_tpu.frontend import Session
from risingwave_tpu.udf.client import (
    UdfOverloadedError, UdfServerError, UdfTimeoutError, udf_plane,
)
from risingwave_tpu.udf.registry import (
    UdfNotPortableError, UdfSpec, load_function, ship_function,
)


@pytest.fixture(autouse=True)
def _restore_plane_config():
    """Every test gets the default plane config back (the plane is
    process-global; tests tune deadlines/backpressure)."""
    plane = udf_plane()
    old_cfg, old_trace = plane.config, plane.trace_dir
    yield
    plane.configure(old_cfg)
    plane.trace_dir = old_trace


def _register(name, fn, args, ret, **kw):
    register_udf(name, fn, args, ret, **kw)
    return name


# ---------------------------------------------------------------------------
# wire codecs (common/interchange.py)
# ---------------------------------------------------------------------------

class TestWireCodec:
    def test_fixed_width_round_trip(self):
        from risingwave_tpu.common.interchange import (
            udf_batch_to_wire, wire_to_udf_batch,
        )
        types = [INT64, FLOAT64, BOOL]
        datas = [np.array([1, -7, 2**40], np.int64),
                 np.array([0.5, -1.25, 3.0]),
                 np.array([True, False, True])]
        masks = [np.array([True, True, False]),
                 np.array([True, False, True]),
                 np.array([True, True, True])]
        wire = udf_batch_to_wire(datas, masks, types)
        out_d, out_m = wire_to_udf_batch(
            json.loads(json.dumps(wire)), types)  # must be JSON-safe
        for d, od in zip(datas, out_d):
            assert od.tolist() == d.tolist()
        for m, om in zip(masks, out_m):
            assert om.tolist() == m.tolist()

    def test_decimal_rides_physical_scaled_int(self):
        from risingwave_tpu.common.interchange import (
            udf_col_to_wire, wire_to_udf_col,
        )
        from risingwave_tpu.common.types import decimal
        t = decimal(2)
        d, m = wire_to_udf_col(
            udf_col_to_wire(np.array([125, -50], np.int64),
                            np.array([True, True]), t), t)
        assert d.tolist() == [125, -50] and d.dtype == np.int64

    def test_string_col_decodes_and_nulls(self):
        from risingwave_tpu.common.interchange import (
            udf_col_to_wire, wire_to_udf_col,
        )
        ids = np.array([VARCHAR.to_physical("hey"),
                        0,
                        VARCHAR.to_physical("yo")], np.int64)
        mask = np.array([True, False, True])
        wire = udf_col_to_wire(ids, mask, VARCHAR)
        assert wire["enc"] == "utf8"
        assert wire["values"] == ["hey", None, "yo"]
        d, m = wire_to_udf_col(wire, VARCHAR)
        assert list(d) == ["hey", None, "yo"]
        assert m.tolist() == [True, False, True]

    def test_list_struct_refuse_with_remediation(self):
        from risingwave_tpu.common.interchange import udf_type_to_wire
        t = DataType(TypeKind.LIST, elem_kind=TypeKind.INT64)
        with pytest.raises(TypeError, match="inproc"):
            udf_type_to_wire(t)


# ---------------------------------------------------------------------------
# function shipping (udf/registry.py)
# ---------------------------------------------------------------------------

class TestShipping:
    def test_module_function_ships_by_reference(self):
        from risingwave_tpu.sim import _chaos_tax
        d = ship_function(_chaos_tax)
        assert d["how"] == "ref" and d["module"] == "risingwave_tpu.sim"
        assert load_function(d)(5) == _chaos_tax(5)

    def test_lambda_ships_by_code(self):
        d = ship_function(lambda v: v * 10)
        assert d["how"] == "code"
        assert load_function(json.loads(json.dumps(d)))(4) == 40

    def test_closure_ships_cell_values(self):
        rate = 3

        def taxed(v):
            return v * rate

        d = ship_function(taxed)
        assert d["how"] == "code"
        assert load_function(d)(2) == 6

    def test_unmarshalable_closure_refuses_loudly(self):
        sock = threading.Lock()   # no marshal encoding exists

        def bad(v):
            return v if sock else None

        with pytest.raises(UdfNotPortableError, match="inproc"):
            ship_function(bad)

    def test_registration_validates_eagerly(self):
        lock = threading.Lock()
        with pytest.raises(UdfNotPortableError):
            register_udf("bad_udf", lambda v: v if lock else None,
                         [INT64], INT64)
        from risingwave_tpu.expr.expr import _REGISTRY
        assert "bad_udf" not in _REGISTRY


# ---------------------------------------------------------------------------
# parity: out-of-process bit-exact vs inproc (shared evaluator)
# ---------------------------------------------------------------------------

class TestParity:
    DDL = ("CREATE TABLE pt (k BIGINT PRIMARY KEY, v BIGINT, "
           "s VARCHAR, x DOUBLE)")
    ROWS = ("INSERT INTO pt VALUES (1, 100, 'hey', 3.0), "
            "(2, NULL, 'yo', 4.0), (3, 300, NULL, NULL)")
    Q = "SELECT k, p_tax(v), p_shout(s), p_sq(x) FROM pt"

    def _run(self, mode):
        udf_plane().configure(UdfConfig(mode=mode))
        register_udf("p_tax", lambda v: int(v * 1.1), [INT64], INT64)
        register_udf("p_shout", lambda s: s.upper() + "!",
                     [VARCHAR], VARCHAR)
        register_udf("p_sq", lambda a: a * a, [FLOAT64], FLOAT64,
                     vectorized=True)
        try:
            s = Session()
            s.run_sql(self.DDL)
            s.run_sql(self.ROWS)
            s.flush()
            rows = sorted(s.run_sql(self.Q))
            s.close()
            return rows
        finally:
            for n in ("p_tax", "p_shout", "p_sq"):
                drop_udf(n)

    def test_process_bit_exact_vs_inproc(self):
        got_proc = self._run("process")
        got_inproc = self._run("inproc")
        assert got_proc == got_inproc
        assert got_proc == [(1, 110, "HEY!", 9.0),
                            (2, None, "YO!", 16.0),
                            (3, 330, None, None)]

    def test_strict_null_never_calls_fn(self):
        calls = []

        def spy(v):
            calls.append(v)
            return v

        udf_plane().configure(UdfConfig(mode="inproc"))
        register_udf("p_spy", spy, [INT64], INT64)
        try:
            s = Session()
            s.run_sql("CREATE TABLE nt (k BIGINT PRIMARY KEY, v BIGINT)")
            s.run_sql("INSERT INTO nt VALUES (1, NULL), (2, 5)")
            s.flush()
            rows = dict(s.run_sql("SELECT k, p_spy(v) FROM nt"))
            assert rows == {1: None, 2: 5}
            assert calls == [5]
            s.close()
        finally:
            drop_udf("p_spy")


# ---------------------------------------------------------------------------
# restart semantics
# ---------------------------------------------------------------------------

class TestRestartSemantics:
    @pytest.mark.slow   # 2 deliberate deadline trips + 3 server spawns
    def test_deadline_trip_exhausts_to_typed_error_session_survives(self):
        udf_plane().configure(UdfConfig(call_timeout_s=0.4,
                                        max_retries=1,
                                        spawn_timeout_s=30.0))
        register_udf("hang", lambda v: time.sleep(30) or v,
                     [INT64], INT64)
        register_udf("fine", lambda v: v + 1, [INT64], INT64)
        try:
            s = Session()
            s.run_sql("CREATE TABLE ht (k BIGINT PRIMARY KEY, v BIGINT)")
            s.run_sql("INSERT INTO ht VALUES (1, 10)")
            s.flush()
            base = udf_plane().snapshot()
            with pytest.raises(UdfTimeoutError, match="hang"):
                s.run_sql("SELECT hang(v) FROM ht")
            snap = udf_plane().snapshot()
            assert snap["timeouts"] - base["timeouts"] == 2  # 2 attempts
            assert snap["respawns"] - base["respawns"] == 2
            # the STATEMENT failed; the session/epoch loop did not:
            s.tick()
            assert s.run_sql("SELECT fine(v) FROM ht") == [(11,)]
            s.close()
        finally:
            drop_udf("hang")
            drop_udf("fine")

    @pytest.mark.slow   # 2 real server spawns (one dies at the site)
    def test_server_killed_mid_batch_respawn_replays(self, tmp_path):
        """Deterministic kill -9 mid-batch: RWTPU_FAILPOINTS arms a real
        os._exit at udf.server.eval in the SERVER process (once via
        marker); the client detects the death, respawns a seeded server,
        replays the batch, and the statement SUCCEEDS."""
        marker = str(tmp_path / "udf_died.marker")
        os.environ["RWTPU_FAILPOINTS"] = json.dumps(
            {"udf.server.eval": {"action": "exit",
                                 "once_marker": marker}})
        udf_plane().shutdown_server()   # next spawn inherits the env
        register_udf("k9", lambda v: v * 2, [INT64], INT64)
        try:
            base = udf_plane().snapshot()
            s = Session()
            s.run_sql("CREATE TABLE kt (k BIGINT PRIMARY KEY, v BIGINT)")
            s.run_sql("INSERT INTO kt VALUES (1, 21)")
            s.flush()
            assert s.run_sql("SELECT k9(v) FROM kt") == [(42,)]
            assert os.path.exists(marker), "server never died at the site"
            snap = udf_plane().snapshot()
            assert snap["spawns"] - base["spawns"] >= 2
            s.close()
        finally:
            os.environ.pop("RWTPU_FAILPOINTS", None)
            drop_udf("k9")
            udf_plane().shutdown_server()   # drop the armed-env server

    def test_reply_after_fence_dropped(self):
        """A chaos-duplicated reply (same rid, stale by the time it
        arrives) is dropped by the (gen, rid) fence, never returned to
        a later call."""
        from risingwave_tpu.rpc.faults import (
            ChaosRule, ChaosSchedule, install,
        )
        udf_plane().configure(UdfConfig())
        udf_plane().shutdown_server()
        register_udf("fence", lambda v: v + 5, [INT64], INT64)
        try:
            plane = udf_plane()
            spec_args = ([np.array([1, 2], np.int64)],
                         [np.ones(2, bool)])
            # server spawns WITHOUT chaos env; the SESSION-side plane
            # duplicates the server's... replies are server-side, so
            # duplicate the REQUEST instead: the server evaluates twice
            # and sends two replies with the same rid — the second must
            # be dropped, not taken for call #2's answer.
            install(ChaosSchedule(3, [ChaosRule(
                kind="duplicate", link="s->udf", types=["udf_call"],
                count=1)]))
            try:
                d1, _ = plane.call("fence", *spec_args)
                base_stale = plane.snapshot()["stale_replies_dropped"]
                d2, _ = plane.call(
                    "fence", [np.array([10, 20], np.int64)],
                    [np.ones(2, bool)])
                assert d1.tolist() == [6, 7]
                assert d2.tolist() == [15, 25]
                assert plane.snapshot()["stale_replies_dropped"] \
                    >= base_stale + 1
            finally:
                install(None)
        finally:
            drop_udf("fence")

    def test_user_exception_typed_no_respawn_burn(self):
        register_udf("boom", lambda v: 1 // 0, [INT64], INT64)
        try:
            plane = udf_plane()
            base = plane.snapshot()
            with pytest.raises(UdfServerError, match="ZeroDivision"):
                plane.call("boom", [np.array([1], np.int64)],
                           [np.ones(1, bool)])
            snap = plane.snapshot()
            assert snap["respawns"] == base["respawns"]
            assert snap["user_errors"] == base["user_errors"] + 1
        finally:
            drop_udf("boom")

    def test_backpressure_overload_typed(self):
        udf_plane().configure(UdfConfig(max_inflight=1,
                                        queue_timeout_s=0.05,
                                        call_timeout_s=10.0))
        register_udf("slow", lambda v: time.sleep(0.6) or v,
                     [INT64], INT64)
        try:
            plane = udf_plane()
            plane.call("slow", [np.array([0], np.int64)],
                       [np.ones(1, bool)])   # warm spawn outside timing
            errs, oks = [], []

            def one():
                try:
                    plane.call("slow", [np.array([1], np.int64)],
                               [np.ones(1, bool)])
                    oks.append(1)
                except UdfOverloadedError as e:
                    errs.append(e)

            ts = [threading.Thread(target=one) for _ in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            assert len(oks) == 1 and len(errs) == 1
        finally:
            drop_udf("slow")

    def test_drop_and_reregister(self):
        register_udf("cycle", lambda v: v, [INT64], INT64)
        drop_udf("cycle")
        register_udf("cycle", lambda v: v + 1, [INT64], INT64)
        try:
            d, _ = udf_plane().call("cycle",
                                    [np.array([1], np.int64)],
                                    [np.ones(1, bool)])
            assert d.tolist() == [2]
        finally:
            drop_udf("cycle")


# ---------------------------------------------------------------------------
# wiring: metrics, config, placement routing
# ---------------------------------------------------------------------------

class TestWiring:
    def test_metrics_section(self):
        s = Session()
        m = s.metrics()["udf"]
        for k in ("mode", "generation", "calls", "respawns", "timeouts",
                  "stale_replies_dropped", "registered", "server_alive"):
            assert k in m
        s.close()

    def test_rw_config_udf_section_round_trip(self, tmp_path):
        from risingwave_tpu.common.config import load_config
        p = tmp_path / "rw.toml"
        p.write_text("[udf]\nmode = \"inproc\"\ncall_timeout_s = 1.5\n"
                     "max_retries = 7\n")
        cfg = load_config(str(p))
        assert cfg.udf.mode == "inproc"
        assert cfg.udf.call_timeout_s == 1.5
        assert cfg.udf.max_retries == 7
        with pytest.raises(ValueError):
            load_config(str(p), **{"udf.nonsense": 1})

    def test_session_only_imposes_explicit_udf_config(self):
        plane = udf_plane()
        plane.configure(UdfConfig(call_timeout_s=1.25))
        s = Session()          # no rw_config: must NOT clobber
        assert plane.config.call_timeout_s == 1.25
        s.close()
        from risingwave_tpu.common.config import RwConfig
        rw = RwConfig()
        rw.udf.call_timeout_s = 9.0
        s2 = Session(rw_config=rw)
        assert plane.config.call_timeout_s == 9.0
        s2.close()

    @pytest.mark.slow
    def test_udf_mv_stays_local_with_workers(self):
        """A UDF-projecting MV must build session-local: worker
        processes hold no UDF registrations (ISSUE 15 routing rule)."""
        register_udf("loc_tax", lambda v: v * 2, [INT64], INT64)
        try:
            s = Session(workers=2)
            try:
                s.run_sql("CREATE TABLE wt (k BIGINT PRIMARY KEY, "
                          "v BIGINT)")
                s.run_sql("CREATE MATERIALIZED VIEW wmu AS "
                          "SELECT k, loc_tax(v) AS tv FROM wt")
                assert "wmu" not in s._remote_specs
                assert "wmu" not in s._spanning_specs
                s.run_sql("INSERT INTO wt VALUES (1, 5)")
                s.flush()
                assert s.mv_rows("wmu") == [(1, 10)]
            finally:
                s.close()
        finally:
            drop_udf("loc_tax")


# ---------------------------------------------------------------------------
# slow tier: chaos scenario + sweep + soak + ctl serve
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestUdfChaosSlow:
    def test_udf_link_chaos_audited_and_replayable(self, tmp_path):
        from risingwave_tpu.sim import run_udf_chaos
        r1 = run_udf_chaos(seed=13, data_dir=str(tmp_path / "a"))
        assert all(r1["audit"].values())
        assert r1["timeouts"] >= 1          # drops actually struck
        assert r1["spawns"] >= 2            # kill + respawn happened
        r2 = run_udf_chaos(seed=13, data_dir=str(tmp_path / "b"))
        assert r1["trace"] == r2["trace"], "seeded replay diverged"

    def test_kill_mid_epoch_pipeline_depth2_cosched_green(self, tmp_path):
        """THE acceptance run: UDF server killed mid-run while a
        co-scheduled fused group ticks under pipeline_depth=2 — the
        epoch loop keeps ticking, results land bit-exact vs control,
        ConsistencyAuditor green."""
        from risingwave_tpu.sim import run_udf_chaos
        r = run_udf_chaos(seed=10, data_dir=str(tmp_path),
                          pipeline_depth=2, coschedule=True)
        assert all(r["audit"].values())
        assert r["cosched_groups"] >= 1, \
            "co-scheduled group never engaged — the run proved nothing"
        assert r["pipeline_depth"] == 2
        assert r["spawns"] >= 2

    def test_crash_point_sweep_covers_udf_sites(self, tmp_path):
        from risingwave_tpu.sim import crash_point_sweep
        res = crash_point_sweep(
            str(tmp_path), sites=["udf.spawn", "udf.call", "udf.reply"])
        for site, st in res.items():
            assert st["hit"], f"{site} never fired in the sweep workload"
            assert st.get("audit") == "ok", f"{site}: {st}"

    def test_ctl_udf_serve_external_attach(self, tmp_path):
        """`ctl udf serve` + [udf] addr: sessions attach to an
        operator-managed persistent server instead of auto-spawning."""
        import subprocess
        import sys
        proc = subprocess.Popen(
            [sys.executable, "-m", "risingwave_tpu", "ctl", "udf",
             "serve", "--port", "0"],
            stdout=subprocess.PIPE,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
        try:
            line = proc.stdout.readline().decode()
            assert line.startswith("UDF_READY"), line
            port = int(line.split()[1])
            udf_plane().configure(
                UdfConfig(addr=f"127.0.0.1:{port}"))
            udf_plane().shutdown_server()
            register_udf("ext_tax", lambda v: v + 100, [INT64], INT64)
            try:
                d, _ = udf_plane().call(
                    "ext_tax", [np.array([1], np.int64)],
                    [np.ones(1, bool)])
                assert d.tolist() == [101]
                assert udf_plane().server_pid() is None  # not ours
            finally:
                drop_udf("ext_tax")
        finally:
            proc.kill()
            proc.wait()
            udf_plane().shutdown_server()

    def test_soak_seed_record_is_schema_stable(self, tmp_path):
        """The ~60s soak composition (satellite): RPC chaos + UDF-server
        kills + serving readers live together, auditor green, and the
        emitted record is schema-stable."""
        from risingwave_tpu.sim import run_udf_soak
        rec = run_udf_soak(duration_s=40.0, seed=5,
                           data_dir=str(tmp_path / "soak"),
                           kill_every=5, min_ticks=10)
        assert rec["audit_ok"] == 1
        assert rec["reader_errors"] == 0
        assert rec["udf_spawns"] >= 2          # kills were absorbed
        assert rec["chaos_injections"] >= 1    # rpc chaos actually ran
        assert rec["reader_queries"] > 0
        # schema-stable: the exact field set
        assert sorted(rec) == sorted([
            "seed", "duration_s", "ticks", "rows_per_sec", "udf_calls",
            "udf_spawns", "udf_respawns", "udf_timeouts",
            "udf_stale_drops", "reader_queries", "reader_errors",
            "chaos_injections", "mv_rows", "audit_ok"])
