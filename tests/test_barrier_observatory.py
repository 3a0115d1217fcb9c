"""Barrier observatory (ISSUE 16): per-barrier lifecycle ledger,
stuck-barrier blame, and the SQL-queryable telemetry catalog.

Acceptance pinned here:
  * every completed epoch gets a waterfall record whose conductor-stage
    sum reconciles with the session's barrier-latency percentiles, with
    ZERO added dispatches at pipeline_depth 1 and 2;
  * a 2-worker spanning job's federated record carries both workers'
    collect/storage stages, matching the single-process record
    stage-for-stage on the conductor side;
  * a chaos-partitioned exchange edge is named — consumer actor + link —
    by ``Session.barrier_blame()``, ``ctl trace barrier --inflight`` AND
    ``SELECT * FROM rw_catalog.rw_barrier_inflight`` over pgwire, all
    BEFORE the epoch-deadline recovery path fires;
  * rw_catalog system relations never touch the serving plan cache;
  * the slow-epoch capture ring is config-sized and attaches the
    offending barrier's waterfall record.
"""

import asyncio
import json
import os
import tempfile
import types

import pytest

from risingwave_tpu.common.barrier_ledger import (
    ALL_STAGES, BarrierLedger, CONDUCTOR_STAGES, StageEventLog,
)
from risingwave_tpu.frontend import Session

CAP = 64

BID_DDL = """CREATE SOURCE bid (auction BIGINT, bidder BIGINT,
    price BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
    extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'bid')"""
AGG = ("CREATE MATERIALIZED VIEW q AS SELECT auction, count(*) AS n, "
       "max(price) AS mx FROM bid GROUP BY auction")


# -- unit: the ledger + stage-event log ---------------------------------------


class TestStageEventLog:
    def test_outbox_retains_until_acked(self):
        log = StageEventLog()
        log.record(5, "storage_prepare", 1.5)
        seq1, ev1 = log.drain_outbox(None)
        assert [e["stage"] for e in ev1] == ["storage_prepare"]
        # unacked: the batch is retained and re-shipped
        seq2, ev2 = log.drain_outbox(None)
        assert seq2 == seq1 and ev2 == ev1
        # acked: the batch clears; no fresh events → same seq, empty
        seq3, ev3 = log.drain_outbox(seq1)
        assert seq3 == seq1 and ev3 == []

    def test_seq_bumps_only_on_fresh_events(self):
        log = StageEventLog()
        s0, _ = log.drain_outbox(None)
        log.record(1, "sink_deliver", 0.2)
        s1, ev = log.drain_outbox(s0)
        assert s1 == s0 + 1 and len(ev) == 1


class TestBarrierLedger:
    def test_waterfall_assembly_and_late_attach(self):
        led = BarrierLedger(capacity=4)
        led.begin(7, True, 123.0)
        led.stage(7, "collect", 2.0)
        rec = led.finish(7, 10.0, "ok")
        assert rec["total_ms"] == 10.0 and rec["result"] == "ok"
        # late worker events attach to the SEALED ring record by epoch
        led.ingest_events([{"epoch": 7, "stage": "worker_collect",
                            "ms": 3.25}], worker=1)
        got = led.get(7)
        assert got["stages"]["worker_collect"] == 3.25
        assert got["workers"][1] == {"worker_collect": 3.25}
        assert got["workers"][-1] == {"collect": 2.0}

    def test_ring_eviction_and_percentiles(self):
        led = BarrierLedger(capacity=2)
        for e in (1, 2, 3):
            led.begin(e, False, 0.0)
            led.stage(e, "collect", float(e))
            led.finish(e, float(e), "ok")
        assert len(led) == 2
        assert led.get(1) is None          # evicted with its index
        pct = led.stage_percentiles()["collect"]
        assert pct["n"] == 2 and pct["p99_ms"] == 3.0
        assert led.summary()["total"] == {"ok": 3, "failed": 0}

    def test_failed_results_counted(self):
        led = BarrierLedger()
        led.begin(1, False, 0.0)
        led.finish(1, 5.0, "failed")
        assert led.summary()["total"]["failed"] == 1
        assert led.history()[0]["result"] == "failed"

    def test_malformed_events_ignored(self):
        led = BarrierLedger()
        led.begin(1, False, 0.0)
        led.ingest_events([{"nope": 1}, None, {"epoch": 1,
                           "stage": "collect", "ms": "x"}])
        led.ingest_events([{"epoch": 1, "stage": "collect", "ms": 1.0}])
        assert led.get(1)["stages"] == {"collect": 1.0}


# -- single-process waterfall + reconciliation --------------------------------


def _ticked_session(data_dir=None, **kw):
    s = Session(source_chunk_capacity=CAP, checkpoint_frequency=2,
                data_dir=data_dir, **kw)
    s.run_sql(BID_DDL)
    s.run_sql(AGG)
    for _ in range(6):
        s.tick()
    s.flush()
    return s


class TestSingleProcessWaterfall:
    def test_every_epoch_has_a_record_with_conductor_stages(self, tmp_path):
        s = _ticked_session(data_dir=str(tmp_path))
        try:
            hist = s._barrier_ledger.history()
            assert len(hist) >= 6
            for rec in hist:
                assert set(CONDUCTOR_STAGES) - {"commit"} \
                    <= set(rec["stages"])
                assert rec["result"] == "ok"
                assert rec["total_ms"] is not None
            # checkpoint epochs commit durable state: commit +
            # storage_commit appear on exactly those records (the commit
            # may land from the async flush thread — drain it in)
            from risingwave_tpu.common.barrier_ledger import GLOBAL_STAGES
            s._barrier_ledger.ingest_events(GLOBAL_STAGES.drain())
            ckpt = [r for r in hist if r["checkpoint"]]
            assert ckpt
            for rec in ckpt:
                assert "commit" in rec["stages"]
                assert "storage_commit" in rec["stages"]
        finally:
            s.close()

    def test_stage_sum_reconciles_with_barrier_latency(self):
        """The ISSUE acceptance: waterfall stage sums reconcile with the
        existing p50/p99 barrier latency metrics — per record, the
        conductor stages account for the measured total (inject is
        outside the latency clock), and the ledger's totals line up with
        the latency recorder's percentiles."""
        s = _ticked_session()
        try:
            hist = s._barrier_ledger.history()
            for rec in hist:
                ssum = sum(rec["stages"].get(st, 0.0)
                           for st in CONDUCTOR_STAGES)
                assert ssum <= rec["total_ms"] + 1.0
                assert ssum >= 0.8 * rec["total_ms"] - 1.0, \
                    (rec["epoch"], ssum, rec["total_ms"])
            lat = s.metrics()["barrier_latency"]
            totals = sorted(r["total_ms"] for r in hist)
            # same sample population → the recorder's percentiles fall
            # inside the ledger's observed range
            assert totals[0] - 0.5 <= lat["p50_ms"] <= totals[-1] + 0.5
            assert totals[0] - 0.5 <= lat["p99_ms"] <= totals[-1] + 0.5
        finally:
            s.close()

    def test_sink_deliver_stage_recorded(self, tmp_path):
        s = Session(data_dir=str(tmp_path), checkpoint_frequency=2)
        try:
            s.run_sql("CREATE TABLE t (a INT)")
            out = tmp_path / "out.jsonl"
            s.run_sql(f"CREATE SINK snk FROM t WITH ("
                      f"connector='file', path='{out}', format='jsonl')")
            s.run_sql("INSERT INTO t VALUES (1), (2)")
            s.run_sql("FLUSH")
            stages = set()
            for rec in s._barrier_ledger.history():
                stages |= set(rec["stages"])
            assert "sink_deliver" in stages
        finally:
            s.close()

    def test_zero_added_dispatches_depth_1_and_2(self):
        """The observatory is host-side bookkeeping only: the fused
        one-dispatch-per-epoch invariant holds untouched at pipeline
        depth 1 AND 2 (ISSUE 16 acceptance)."""
        from risingwave_tpu.common.dispatch_count import count_dispatches
        from risingwave_tpu.frontend.build import BuildConfig
        qn = "build_group_epoch.<locals>.coscheduled_epoch"

        from risingwave_tpu.common import tracing

        def run(depth):
            tracing.GLOBAL_TRACE.clear()
            with count_dispatches() as c:
                s = Session(config=BuildConfig(coschedule=True),
                            source_chunk_capacity=CAP,
                            pipeline_depth=depth,
                            checkpoint_frequency=2)
                try:
                    s.run_sql(BID_DDL)
                    s.run_sql(AGG)
                    for _ in range(5):
                        s.tick()
                    s.flush()
                    n_records = len(s._barrier_ledger.history())
                finally:
                    s.close()
                return dict(c.counts), n_records, tracing.epoch_spans()

        c1, n1, spans1 = run(1)
        c2, n2, spans2 = run(2)
        assert n1 >= 5 and n2 >= 5       # the ledger observed the run
        assert c1.get(qn) == c2.get(qn) and c1.get(qn), (c1, c2)
        # ... with the whole tick under spans (ISSUE 25): every epoch has
        # its root and its device wait, and a deferred flush resolves
        # under a span of the NEXT epoch at depth 2 only
        for spans in (spans1, spans2):
            roots = [[d["name"] for d in per_epoch].count("session.tick")
                     for per_epoch in spans.values()]
            # (the MV's initial barrier is no tick: it has no root)
            assert set(roots) <= {0, 1} and sum(roots) >= 5
        waits = [sum(d["name"] == "cosched.epoch_wait"
                     for per_epoch in spans.values() for d in per_epoch)
                 for spans in (spans1, spans2)]
        assert waits[0] == waits[1] >= 5
        deferred = [sum(d["name"] == "cosched.resolve_deferred"
                        for per_epoch in spans.values() for d in per_epoch)
                    for spans in (spans1, spans2)]
        assert deferred[0] == 0 < deferred[1]

    def test_chrome_trace_exports_barrier_flow_events(self):
        s = _ticked_session()
        try:
            obj = s.export_chrome_trace()
            flows = [ev for ev in obj["traceEvents"]
                     if ev.get("ph") in ("s", "t", "f")]
            assert flows, "no barrier flow events in the trace"
            starts = [ev for ev in flows if ev["ph"] == "s"]
            finishes = [ev for ev in flows if ev["ph"] == "f"]
            assert {ev["id"] for ev in starts} \
                == {ev["id"] for ev in finishes}
            assert all(ev["cat"] == "epoch" for ev in flows)
        finally:
            s.close()


# -- config knobs (satellite: capture ring size + history capacity) -----------


class TestObservabilityKnobs:
    def test_knobs_load_from_toml_and_size_the_rings(self, tmp_path):
        from risingwave_tpu.common.config import load_config
        p = tmp_path / "rw.toml"
        p.write_text("""
[observability]
barrier_history_capacity = 7
slow_epoch_capture_capacity = 3
""")
        cfg = load_config(str(p))
        assert cfg.observability.barrier_history_capacity == 7
        assert cfg.observability.slow_epoch_capture_capacity == 3
        s = Session(rw_config=cfg)
        try:
            assert s._barrier_ledger.capacity == 7
            assert s._slow_epochs.maxlen == 3
        finally:
            s.close()

    def test_defaults_keep_legacy_sizes(self):
        from risingwave_tpu.common.config import load_config
        from risingwave_tpu.common.tracing import GLOBAL_TRACE
        s = Session(rw_config=load_config(None))
        try:
            assert s._barrier_ledger.capacity == 256
            assert s._slow_epochs.maxlen == 16
            # the span ring holds a 400-barrier run (ISSUE 25; was 4096)
            assert GLOBAL_TRACE.capacity == 16384
        finally:
            s.close()

    def test_slow_epoch_capture_attaches_waterfall(self):
        s = Session(source_chunk_capacity=CAP, checkpoint_frequency=2)
        try:
            s.run_sql(BID_DDL)
            s.run_sql(AGG)
            s.run_sql("SET slow_epoch_threshold_ms = 0.0001")
            s.tick()
            s.tick()
            slow = s.slow_epochs()
            assert slow
            for cap in slow:
                rec = cap["barrier"]
                assert rec["epoch"] == cap["epoch"]
                assert rec["stages"], rec
            # metrics() strips the heavy span dump but keeps the record
            mslow = s.metrics()["slow_epochs"]
            assert all("spans" not in se and "barrier" in se
                       for se in mslow)
        finally:
            s.close()


# -- SQL catalog + serving-cache exclusion ------------------------------------


class TestTelemetryCatalog:
    def test_history_relation_matches_ledger(self):
        s = _ticked_session()
        try:
            rows = s.run_sql(
                "SELECT epoch, checkpoint, result, total_ms "
                "FROM rw_catalog.rw_barrier_history")
            hist = s._barrier_ledger.history()
            assert [(r["epoch"], r["checkpoint"], r["result"])
                    for r in hist] == [(e, c, res)
                                       for e, c, res, _ in rows]
            # stage columns surface in waterfall order
            cols = [c for c, _ in s.last_select_schema]
            rows2 = s.run_sql("SELECT * FROM rw_barrier_history")
            cols2 = [c for c, _ in s.last_select_schema]
            assert cols2[5:7] == ["tick_ms", "compiles"]
            assert [f"{st}_ms" for st in ALL_STAGES] == cols2[7:-1]
            assert len(rows2) == len(hist)
        finally:
            s.close()

    def test_estate_relations_answer(self):
        s = _ticked_session()
        try:
            assert s.run_sql(
                "SELECT * FROM rw_catalog.rw_barrier_inflight") == []
            frags = s.run_sql("SELECT * FROM rw_fragments")
            assert any(r[0] == "q" for r in frags)
            assert s.run_sql("SELECT * FROM rw_worker_nodes") == []
            prof = s.run_sql(
                "SELECT worker, qualname, calls "
                "FROM rw_dispatch_profiles WHERE calls > 0")
            assert prof and all(r[0] == -1 for r in prof)
            hbm = s.run_sql("SELECT job, state_bytes FROM rw_hbm_ledger")
            assert any(r[0] == "q" and r[1] > 0 for r in hbm)
            assert s.run_sql(
                "SELECT * FROM rw_autoscaler_decisions") == []
        finally:
            s.close()

    def test_describe_path_plans_without_session(self):
        """The session-less Planner (DESCRIBE, recovery replay) must
        still resolve the telemetry relations: schema, zero rows."""
        from risingwave_tpu.frontend.system_catalog import system_relation
        s = Session()
        try:
            for name in ("rw_barrier_history", "rw_barrier_inflight",
                         "rw_actors", "rw_hbm_ledger"):
                schema, rows = system_relation(s.catalog, name)
                assert len(schema) > 0 and rows == []
        finally:
            s.close()

    def test_system_relations_never_touch_serving_cache(self):
        """Satellite: a rw_catalog query must neither populate nor hit
        the plan cache — repeated reads are always fresh plans."""
        s = _ticked_session()
        try:
            stats0 = s.metrics()["serving"]
            for _ in range(3):
                s.run_sql("SELECT * FROM rw_catalog.rw_barrier_history")
                s.run_sql("SELECT * FROM rw_relations")
            stats1 = s.metrics()["serving"]
            assert s._serving.cache_len() == 0
            assert stats1["cache_hits"] == stats0["cache_hits"]
            assert stats1["cache_misses"] == stats0["cache_misses"]
            assert stats1["system_catalog_reads"] \
                >= stats0["system_catalog_reads"] + 6
            # sanity: user queries still cache (the bypass is scoped to
            # system relations, not the plane)
            s.run_sql("SELECT auction, n FROM q")
            s.run_sql("SELECT auction, n FROM q")
            stats2 = s.metrics()["serving"]
            assert s._serving.cache_len() == 1
            assert stats2["cache_hits"] >= 1
            # freshness is the point of the exclusion: new barriers are
            # visible to the very next history read
            before = len(s.run_sql(
                "SELECT epoch FROM rw_catalog.rw_barrier_history"))
            s.tick()
            after = len(s.run_sql(
                "SELECT epoch FROM rw_catalog.rw_barrier_history"))
            assert after == before + 1
        finally:
            s.close()

    def test_subquery_and_join_references_also_bypass(self):
        s = Session()
        try:
            s.run_sql("SELECT * FROM (SELECT name FROM rw_relations) r")
            s.run_sql("SELECT r.name FROM rw_relations r "
                      "JOIN rw_relations r2 ON r.name = r2.name")
            assert s._serving.cache_len() == 0
            assert s.metrics()["serving"]["system_catalog_reads"] >= 2
        finally:
            s.close()


# -- prometheus + ctl surfaces ------------------------------------------------


class TestSurfaces:
    def test_metrics_and_prometheus_families(self):
        from risingwave_tpu.frontend.prometheus import render_metrics
        s = _ticked_session()
        try:
            b = s.metrics()["barrier"]
            assert b["inflight"] == 0 and b["total"]["ok"] >= 6
            assert "collect" in b["stages"]
            text = render_metrics(s)
            assert 'rw_barrier_stage_seconds{stage="collect",' \
                   'quantile="0.5"}' in text
            assert "rw_barrier_inflight 0" in text
            assert 'rw_barrier_total{result="ok"}' in text
            assert 'rw_barrier_total{result="failed"} 0' in text
        finally:
            s.close()

    def test_ctl_trace_barrier_over_live_session(self, capsys):
        from risingwave_tpu.cli import _ctl_dispatch
        s = _ticked_session()
        try:
            args = types.SimpleNamespace(what="trace", sub="barrier",
                                         json=False, inflight=False)
            _ctl_dispatch(args, s, json)
            out = capsys.readouterr().out
            assert "epoch\tckpt\tresult\ttotal_ms" in out
            assert "collect\t" in out            # percentile table
            args.json = True
            _ctl_dispatch(args, s, json)
            obj = json.loads(capsys.readouterr().out)
            assert len(obj["history"]) >= 6
            assert "collect" in obj["stages"]
            args.json, args.inflight = False, True
            _ctl_dispatch(args, s, json)
            assert "no in-flight barriers" in capsys.readouterr().out
        finally:
            s.close()


# -- 2-worker federation + chaos blame (the acceptance runs) ------------------


def _spanning_session(data_dir, **kw):
    from risingwave_tpu.frontend.build import BuildConfig
    return Session(workers=2, seed=42, data_dir=data_dir,
                   source_chunk_capacity=CAP,
                   config=BuildConfig(fragment_parallelism=2), **kw)


@pytest.mark.slow
class TestFederatedWaterfall:
    def test_spanning_record_matches_single_process_stage_for_stage(self):
        """A 2-worker spanning job's federated waterfall carries every
        conductor stage the single-process record has — stage for
        stage — plus both workers' collect/storage detail."""
        sp = _spanning_session(tempfile.mkdtemp(),
                               checkpoint_frequency=2)
        try:
            sp.run_sql(BID_DDL)
            sp.run_sql(AGG)
            for _ in range(6):
                sp.tick()
            sp.flush()
            sp._federate_worker_stats(force=True)
            span_hist = {r["epoch"]: r
                         for r in sp._barrier_ledger.history()}
        finally:
            sp.close()
        lo = _ticked_session()
        try:
            local_hist = {r["epoch"]: r
                          for r in lo._barrier_ledger.history()}
        finally:
            lo.close()
        shared = sorted(set(span_hist) & set(local_hist))
        assert len(shared) >= 4
        for e in shared:
            sp_rec, lo_rec = span_hist[e], local_hist[e]
            assert sp_rec["checkpoint"] == lo_rec["checkpoint"]
            # conductor stages agree stage-for-stage
            for st in CONDUCTOR_STAGES:
                assert (st in sp_rec["stages"]) \
                    == (st in lo_rec["stages"]), (e, st)
        # worker-side stages federated in: both workers contributed
        # barrier collection, and checkpoint epochs their 2PC prepare
        wids = set()
        stages_by_wid: dict = {}
        for rec in span_hist.values():
            for wid, st in rec["workers"].items():
                if wid >= 0:
                    wids.add(wid)
                    stages_by_wid.setdefault(wid, set()).update(st)
        assert wids == {0, 1}, wids
        for wid in (0, 1):
            assert "worker_collect" in stages_by_wid[wid]
            assert "storage_prepare" in stages_by_wid[wid]

    def test_worker_and_placement_relations_over_spanning_job(self):
        s = _spanning_session(tempfile.mkdtemp())
        try:
            s.run_sql(BID_DDL)
            s.run_sql(AGG)
            for _ in range(2):
                s.tick()
            nodes = s.run_sql(
                "SELECT worker_id, dead FROM rw_worker_nodes")
            assert [(0, False), (1, False)] == sorted(nodes)
            actors = s.run_sql(
                "SELECT job, fragment_id, actor_id, worker "
                "FROM rw_actors WHERE job = 'q'")
            assert len(actors) >= 2
            assert {r[3] for r in actors} == {0, 1}
            placements = s.run_sql("SELECT job, workers "
                                   "FROM rw_placements")
            assert ("q", "0,1") in placements
        finally:
            s.close()


@pytest.mark.slow
class TestStuckBarrierBlame:
    def test_partitioned_edge_blamed_by_name_before_deadline(self):
        """THE acceptance run: one exchange edge of a spanning 2-worker
        job partitioned by a seeded ChaosSchedule; the in-flight barrier
        is diagnosed by name — consumer actor + link — through
        ``barrier_blame()``, ``ctl trace barrier --inflight`` and
        ``SELECT * FROM rw_catalog.rw_barrier_inflight`` over pgwire,
        all while the epoch deadline has NOT fired."""
        from risingwave_tpu.cli import _ctl_dispatch
        from risingwave_tpu.common.config import FaultConfig
        from risingwave_tpu.rpc.faults import (
            CHAOS_ENV, ChaosRule, ChaosSchedule, install,
        )
        # partition barrier frames on the w0->w1 exchange edge from
        # epoch 8 on; epochs before that warm the graph up cleanly
        stuck_from = 8
        schedule = ChaosSchedule(11, [ChaosRule(
            kind="partition", link="w0->w1",
            types=["exg_data:barrier"], epochs=[stuck_from, 10_000])])
        os.environ[CHAOS_ENV] = schedule.to_json()
        install(schedule)
        s = None
        try:
            s = _spanning_session(
                tempfile.mkdtemp(),
                fault_config=FaultConfig(worker_epoch_timeout_s=60.0))
            s.run_sql(BID_DDL)
            s.run_sql(AGG)
            while s.epoch < stuck_from - 1:
                s.tick()
            s.run_sql("SET in_flight_barrier_nums = 2")
            # this tick injects the first partitioned epoch; with the
            # pipelined window open it returns WITHOUT collecting
            s.tick()
            assert s._inflight, "barrier unexpectedly completed"
            stuck_epoch = s._inflight[0][0]
            assert stuck_epoch >= stuck_from
            # (1) the API names the starved edge's consumer actor
            findings = s.barrier_blame()
            assert findings
            assert not s._dead_jobs          # deadline has NOT fired
            edge = [f for f in findings if f["kind"] == "exchange_edge"
                    and f["link"] == "w0->w1"]
            assert edge, findings
            f = edge[0]
            assert f["epoch"] == stuck_epoch and f["job"] == "q"
            assert f["worker"] == 1          # the starved consumer side
            assert f["actor"] is not None and f["fragment"] is not None
            assert f["edge"].startswith("q:f")
            # the named consumer actor really lives on worker 1
            placed = {(r[1], r[2]): r[3] for r in s.run_sql(
                "SELECT job, fragment_id, actor_id, worker "
                "FROM rw_actors WHERE job = 'q'")}
            assert placed[(f["fragment"], f["actor"])] == 1
            # the un-acking worker is named too
            assert any(ff["kind"] == "worker" and ff["worker"] == 1
                       for ff in findings), findings
            # (2) ctl trace barrier --inflight over the live session
            import io
            from contextlib import redirect_stdout
            buf = io.StringIO()
            args = types.SimpleNamespace(what="trace", sub="barrier",
                                         json=False, inflight=True)
            with redirect_stdout(buf):
                _ctl_dispatch(args, s, json)
            out = buf.getvalue()
            assert "exchange_edge" in out and "w0->w1" in out
            assert f"f{f['fragment']}a{f['actor']}" in out
            # (3) the same diagnosis over pgwire
            cols, rows = _pgwire_select(
                s, "SELECT epoch, kind, job, worker, actor, link "
                   "FROM rw_catalog.rw_barrier_inflight")
            assert "link" in cols
            hits = [r for r in rows if r[1] == "exchange_edge"
                    and r[5] == "w0->w1"]
            assert hits, rows
            assert hits[0][0] == str(stuck_epoch)
            assert hits[0][4] == str(f["actor"])
            assert not s._dead_jobs          # still before the deadline
        finally:
            os.environ.pop(CHAOS_ENV, None)
            install(None)
            if s is not None:
                # the stuck epoch can only resolve through the deadline
                # path; shorten it so teardown doesn't ride out 60 s
                for w in s.workers:
                    w.epoch_timeout = 1.0
                try:
                    s.close()
                except Exception:
                    pass


def _pgwire_select(session, sql):
    """Run one SELECT over a real pgwire connection against the live
    session; returns (columns, text rows)."""
    import struct

    from risingwave_tpu.frontend.pgwire import PgWireServer

    async def go():
        server = PgWireServer(session, "127.0.0.1", 0)
        await server.start()
        port = server._server.sockets[0].getsockname()[1]
        try:
            reader, writer = await asyncio.open_connection(
                "127.0.0.1", port)
            params = b"user\x00test\x00database\x00dev\x00\x00"
            body = struct.pack("!I", 196608) + params
            writer.write(struct.pack("!I", len(body) + 4) + body)
            await writer.drain()

            async def read_msg():
                hdr = await reader.readexactly(5)
                ln = struct.unpack("!I", hdr[1:5])[0]
                return hdr[0:1], await reader.readexactly(ln - 4)

            while True:
                tag, _ = await read_msg()
                if tag == b"Z":
                    break
            q = sql.encode() + b"\x00"
            writer.write(b"Q" + struct.pack("!I", len(q) + 4) + q)
            await writer.drain()
            cols, rows = [], []
            while True:
                tag, payload = await read_msg()
                if tag == b"T":
                    n = struct.unpack("!H", payload[:2])[0]
                    off = 2
                    for _ in range(n):
                        end = payload.index(b"\x00", off)
                        cols.append(payload[off:end].decode())
                        off = end + 1 + 18
                elif tag == b"D":
                    n = struct.unpack("!H", payload[:2])[0]
                    off = 2
                    row = []
                    for _ in range(n):
                        ln = struct.unpack("!i",
                                           payload[off:off + 4])[0]
                        off += 4
                        if ln == -1:
                            row.append(None)
                        else:
                            row.append(payload[off:off + ln].decode())
                            off += ln
                    rows.append(tuple(row))
                elif tag == b"E":
                    raise AssertionError(payload)
                elif tag == b"Z":
                    break
            writer.write(b"X" + struct.pack("!I", 4))
            writer.close()
            return cols, rows
        finally:
            await server.close()

    return asyncio.new_event_loop().run_until_complete(go())
