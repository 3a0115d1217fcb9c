"""Network fault plane unit surface (ISSUE 9): deterministic schedules,
per-link transports, frame seq dedup/reorder, duplicated-ack credit
protection, the idle-link keepalive + pool eviction regression, the
failpoint registry, and the ConsistencyAuditor's checks — all fast and
process-local (the cross-process integration lives in test_chaos.py)."""

import asyncio
import json

import pytest

from risingwave_tpu.rpc.faults import (
    ChaosPlane, ChaosRule, ChaosSchedule, FaultyTransport, install, plane,
)


def _mk_plane(rules, seed=7):
    p = ChaosPlane()
    p.install(ChaosSchedule(seed, rules))
    return p


async def _send(p, link, obj, meta=False):
    out = []

    async def emit(b):
        out.append(b)

    t = FaultyTransport(link, p)
    await t.send(obj, json.dumps(obj).encode(), emit, meta=meta)
    return out


class TestChaosSchedule:
    def test_json_round_trip(self):
        s = ChaosSchedule(11, [
            ChaosRule(kind="partition", link="w0<->w1",
                      types=["exg_data"], epochs=[3, 6]),
            ChaosRule(kind="duplicate", link="w*->s", prob=0.5,
                      count=2),
            ChaosRule(kind="delay", link="s->w0", delay_frames=2),
        ], name="x")
        s2 = ChaosSchedule.from_json(s.to_json())
        assert s2.to_json() == s.to_json()
        assert s2.seed == 11 and s2.name == "x"
        assert [r.kind for r in s2.rules] == \
            ["partition", "duplicate", "delay"]

    def test_unknown_kind_refused(self):
        with pytest.raises(ValueError):
            ChaosRule(kind="gremlins")

    def test_bidirectional_link_shorthand(self):
        r = ChaosRule(kind="drop", link="w0<->w1")
        assert r.matches_link("w0->w1") and r.matches_link("w1->w0")
        assert not r.matches_link("w0->w2")

    def test_prob_draws_are_deterministic(self):
        """Same (seed, link, seq) → same decision, across plane
        instances (the cross-process replay property)."""
        rules = [ChaosRule(kind="drop", link="a->b", prob=0.4)]
        traces = []
        for _ in range(2):
            p = _mk_plane(rules, seed=3)
            for i in range(50):
                p.decide("a->b", "exg_data", "exg_data:chunk", None,
                         False)
            traces.append([(e["seq"], e["kind"]) for e in p.trace])
        assert traces[0] == traces[1]
        assert 0 < len(traces[0]) < 50       # prob actually filtered
        # a different seed draws a different injection set
        p2 = _mk_plane(rules, seed=4)
        for i in range(50):
            p2.decide("a->b", "exg_data", "exg_data:chunk", None, False)
        assert [(e["seq"], e["kind"]) for e in p2.trace] != traces[0]

    def test_epoch_window_tracks_per_link_barriers(self):
        p = _mk_plane([ChaosRule(kind="partition", link="a->b",
                                 epochs=[5, 8])])
        # below the window: passes
        acts, _ = p.decide("a->b", "exg_data", "exg_data:chunk", None,
                           False)
        assert not acts
        # a barrier carrying epoch 5 opens the window ON THIS LINK
        acts, _ = p.decide("a->b", "exg_data", "exg_data:barrier", 5,
                           False)
        assert [k for k, _, _ in acts] == ["partition"]
        acts, _ = p.decide("a->b", "exg_data", "exg_data:chunk", None,
                           False)
        assert acts, "window stays open for subsequent frames"
        # other links unaffected
        acts, _ = p.decide("b->a", "exg_data", "exg_data:chunk", None,
                           False)
        assert not acts
        # epoch 8 closes it
        acts, _ = p.decide("a->b", "exg_data", "exg_data:barrier", 8,
                           False)
        assert not acts

    def test_count_caps_rule_fires(self):
        p = _mk_plane([ChaosRule(kind="duplicate", link="*", count=2)])
        fires = 0
        for _ in range(10):
            acts, _ = p.decide("x->y", "reply", "reply", None, False)
            fires += bool(acts)
        assert fires == 2


class TestFaultyTransport:
    def test_drop_and_duplicate(self):
        async def run():
            p = _mk_plane([
                ChaosRule(kind="drop", link="a->b", frames=[1, 2]),
                ChaosRule(kind="duplicate", link="a->b",
                          frames=[2, 3]),
            ])
            assert len(await _send(p, "a->b", {"type": "x"})) == 1
            assert len(await _send(p, "a->b", {"type": "x"})) == 0
            assert len(await _send(p, "a->b", {"type": "x"})) == 2
            return p
        p = asyncio.run(run())
        assert p.injections == {"drop": 1, "duplicate": 1}
        assert [e["kind"] for e in p.trace] == ["drop", "duplicate"]

    def test_delay_frames_reorders(self):
        async def run():
            p = _mk_plane([ChaosRule(kind="delay", link="a->b",
                                     frames=[0, 1], delay_frames=2)])
            sent = []

            async def emit(b):
                sent.append(json.loads(b)["i"])

            t = FaultyTransport("a->b", p)
            for i in range(4):
                obj = {"type": "x", "i": i}
                await t.send(obj, json.dumps(obj).encode(), emit)
            return sent
        # frame 0 held until 2 more frames passed: 1, 2, 0, 3
        assert asyncio.run(run()) == [1, 2, 0, 3]

    def test_meta_frames_skip_seq_and_trace_but_honor_partition(self):
        async def run():
            p = _mk_plane([ChaosRule(kind="sever", link="a->b",
                                     frames=[0, 10 ** 9])])
            out = await _send(p, "a->b", {"type": "exg_ping"},
                              meta=True)
            return p, out
        p, out = asyncio.run(run())
        assert out == []                 # severed: the ping is eaten
        assert p.trace == []             # …but leaves no trace entry
        assert p._links["a->b"].seq == 0  # …and consumes no seq

    def test_uninstalled_plane_passes_through(self):
        async def run():
            p = ChaosPlane()
            return await _send(p, "a->b", {"type": "x"})
        assert len(asyncio.run(run())) == 1


class TestExchangeSeqDiscipline:
    def _mk_input(self):
        from risingwave_tpu.common.types import Field, INT64, Schema
        from risingwave_tpu.rpc.exchange import EdgeStats
        from risingwave_tpu.stream.remote_exchange import ExchangeInput
        stats = EdgeStats("j:f0.0->f1.0", "in", 1)
        return ExchangeInput(7, Schema((Field("a", INT64),)), 16,
                             stats, "j"), stats

    def test_duplicates_dropped_reorders_resequenced(self):
        inp, stats = self._mk_input()
        for seq in (0, 2, 1, 1, 3, 0):
            inp.feed_wire({"i": seq}, None, None, seq=seq)
        # delivered queue holds seqs 0..3 in order
        order = [payload["i"]
                 for (_kind, payload, _w, _l) in list(inp._q._items)]
        assert order == [0, 1, 2, 3]
        assert stats.dup_frames == 2 and stats.reordered == 1

    def test_legacy_frames_without_seq_pass(self):
        inp, stats = self._mk_input()
        inp.feed_wire({"i": 9}, None, None, seq=None)
        assert inp.qsize() == 1 and stats.dup_frames == 0

    def test_barrier_epoch_regression_counted(self):
        from risingwave_tpu.rpc.exchange import EdgeStats
        st = EdgeStats("e", "in", 0)
        st.saw_barrier(4)
        st.saw_barrier(5)
        st.saw_barrier(5)            # duplicate epoch = regression
        st.saw_barrier(3)            # went backwards = regression
        assert st.last_barrier_epoch == 5
        assert st.epoch_regressions == 2
        snap = st.snapshot()
        assert snap["epoch_regressions"] == 2
        assert snap["last_barrier_epoch"] == 5

    def test_channel_source_dedups_session_data(self):
        from risingwave_tpu.worker.host import _ChannelSource
        from risingwave_tpu.common.types import Field, INT64, Schema
        ch = _ChannelSource(None, 3, Schema((Field("a", INT64),)), 16)
        for seq in (0, 1, 1, 3, 2):
            ch.feed({"i": seq}, seq=seq)
        got = []
        while not ch.queue.empty():
            got.append(ch.queue.get_nowait()["i"])
        assert got == [0, 1, 2, 3]
        assert ch.dup_frames == 1 and ch.reordered == 1

    def test_duplicated_ack_does_not_inflate_credit(self):
        """A duplicated ack must not release a second permit (credit
        inflation lets the producer overrun the consumer), but a
        REORDERED genuine ack must still release exactly one — the
        naive seq<expected check misread it as a duplicate and leaked
        its permit forever."""
        from risingwave_tpu.rpc.exchange import AckWatermark
        wm = AckWatermark()
        # in-order dup
        assert [wm.accept(s) for s in (0, 0, 1)] == [True, False, True]
        # reorder: 3 overtakes 2; both are genuine, each accepted once
        assert wm.accept(3) is True
        assert wm.accept(2) is True
        assert wm.accept(2) is False and wm.accept(3) is False
        assert wm.next == 4 and not wm._seen   # compacted, no growth
        # legacy peers without seqs always pass
        assert wm.accept(None) is True

    def test_reorder_buffer_shared_helper(self):
        from risingwave_tpu.rpc.exchange import SeqReorderBuffer
        b = SeqReorderBuffer()
        out = []
        for seq, p in ((0, "a"), (2, "c"), (1, "b"), (1, "b'"),
                       (3, "d")):
            out.extend(b.feed(seq, p))
        assert out == ["a", "b", "c", "d"]
        assert b.dup_frames == 1 and b.reordered == 1
        assert b.feed(None, "x") == ["x"]      # legacy pass-through


class TestKeepaliveEviction:
    def test_half_open_peer_detected_and_pool_evicts(self):
        """Satellite regression: a peer socket that stops answering
        (half-open — no FIN, no pongs) used to look healthy until the
        next send wedged a permit. The keepalive prober must mark the
        client broken and PeerClientPool.get must EVICT it and hand
        back a fresh client."""
        from risingwave_tpu.rpc.exchange import PeerClientPool

        async def run():
            release = asyncio.Event()

            async def silent_server(reader, writer):
                await reader.read(64)        # swallow hello + pings
                try:
                    await release.wait()     # silent until the test ends
                finally:
                    # on Python 3.12 server.wait_closed() waits for every
                    # connection to drop: a handler that never closes its
                    # writer hangs the test forever
                    writer.close()

            server = await asyncio.start_server(
                silent_server, "127.0.0.1", 0)
            port = server.sockets[0].getsockname()[1]
            pool = PeerClientPool(0, keepalive_s=0.05,
                                  keepalive_timeout_s=0.05)
            client = pool.get("127.0.0.1", port, peer_worker=1)
            client.register(1, permits=4)
            await client._ensure_connected()
            for _ in range(100):             # ≤ ~2s for 2 missed pongs
                if client.broken:
                    break
                await asyncio.sleep(0.05)
            assert client.broken, "keepalive never declared the " \
                                  "half-open peer dead"
            fresh = pool.get("127.0.0.1", port, peer_worker=1)
            assert fresh is not client
            assert pool.evictions == 1
            await client.aclose()
            await fresh.aclose()
            release.set()
            server.close()
            await asyncio.wait_for(server.wait_closed(), timeout=10)
        asyncio.run(run())


class TestFailpointRegistry:
    def test_failpoint_honesty_lint_is_wired(self):
        """The declared==executed registry check moved to lint time
        (rwlint's failpoint-honesty rule, docs/static-analysis.md) — it
        now runs on every scripts/check.sh invocation instead of only
        when this suite does. Keep a thin smoke here that the lint IS
        wired: the rule parses a non-empty declared set, sees the 2PC
        checkpoint sites, and reports the package clean."""
        from risingwave_tpu.analysis import lint_package, RULES, \
            all_rules
        from risingwave_tpu.common.failpoint import declared_sites
        all_rules()
        rule = RULES["failpoint-honesty"]
        findings, counts, package = lint_package(rules=[rule])
        declared, _, _ = rule.declared(package)
        assert {"checkpoint.prepare", "checkpoint.commit"} <= declared
        # the UDF plane's sites joined the registry (ISSUE 15)
        assert {"udf.spawn", "udf.call", "udf.reply", "udf.respawn",
                "udf.server.eval"} <= declared
        # the lint's static parse of the literal must agree with the
        # runtime mirror
        assert declared == set(declared_sites())
        assert counts["failpoint-honesty"] == 0, findings

    def test_arming_undeclared_site_refuses(self):
        """Registry hygiene (ISSUE 15 satellite): arming a site that is
        not in the declared registry used to succeed silently and never
        fire — a typo'd test proved nothing, and a future plane could
        add sites the crash-point sweep never iterates. Now it refuses
        loudly, both directly and via the contextmanager."""
        import pytest as _pytest
        from risingwave_tpu.common.failpoint import (
            arm, disarm, failpoints,
        )
        with _pytest.raises(ValueError, match="not a declared site"):
            arm("udf.totally_bogus", OSError)
        with _pytest.raises(ValueError, match="not a declared site"):
            with failpoints(**{"nope.nope": OSError}):
                pass
        # declared sites still arm/disarm fine
        arm("udf.call", OSError, once=True)
        disarm("udf.call")

    def test_meta_store_txn_failpoint_keeps_atomicity(self, tmp_path):
        from risingwave_tpu.common.failpoint import failpoints
        from risingwave_tpu.meta.store import FileMetaStore
        st = FileMetaStore(str(tmp_path / "meta.jsonl"))
        st.put("a", "1")
        with failpoints(**{"meta.store.txn": OSError}):
            with pytest.raises(OSError):
                st.put("b", "2")
        assert st.get("b") is None      # memory agrees with disk
        st2 = FileMetaStore(str(tmp_path / "meta.jsonl"))
        assert st2.get("a") == "1" and st2.get("b") is None


class TestMetaIoChaos:
    def test_meta_fault_rule_hits_meta_link(self, tmp_path):
        from risingwave_tpu.meta.store import FileMetaStore
        install(ChaosSchedule(3, [ChaosRule(kind="meta_fault",
                                            link="meta", count=1)]))
        try:
            st = FileMetaStore(str(tmp_path / "m.jsonl"))
            with pytest.raises(OSError):
                st.put("k", "v")
            st.put("k2", "v2")          # count=1: next txn passes
            assert st.get("k") is None and st.get("k2") == "v2"
            assert plane().injections.get("meta_fault") == 1
        finally:
            install(None)


class TestAuditorUnits:
    def test_sink_exactly_once_detects_dupes_and_loss(self, tmp_path):
        from risingwave_tpu.common.audit import ConsistencyAuditor

        class _Sink:
            def __init__(self, path):
                self.path, self.fmt = path, "jsonl"

        class _Sess:
            def __init__(self, path, rows):
                self._sink = _Sink(path)
                self.catalog = type("C", (), {"sinks": {"s": None},
                                              "mvs": {}})()
                with open(path, "w") as f:
                    for r in rows:
                        f.write(json.dumps(r) + "\n")

            def sink_of(self, name):
                return self._sink

            def flush(self):
                pass

        a = _Sess(str(tmp_path / "a.jsonl"),
                  [{"k": 1, "__op": "insert"}, {"k": 1, "__op": "insert"},
                   {"k": 2, "__op": "insert"}])
        b = _Sess(str(tmp_path / "b.jsonl"),
                  [{"k": 1, "__op": "insert"}, {"k": 2, "__op": "insert"},
                   {"k": 3, "__op": "insert"}])
        res = ConsistencyAuditor(a).check_sink_exactly_once(b)
        assert not res["ok"]
        v = res["violations"]["s"]
        assert v["duplicated"] == 1 and v["lost"] == 1

    def test_audit_green_on_clean_local_session(self):
        from risingwave_tpu.common.audit import ConsistencyAuditor
        from risingwave_tpu.frontend import Session
        s = Session()
        control = Session()
        try:
            for sess in (s, control):
                sess.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, "
                             "v BIGINT)")
                sess.run_sql("CREATE MATERIALIZED VIEW m AS "
                             "SELECT sum(v) AS n FROM t")
                sess.run_sql("INSERT INTO t VALUES (1, 5)")
                sess.run_sql("FLUSH")
            report = ConsistencyAuditor(s).audit(control=control)
            report.assert_ok()
            assert report.checks["mv_parity"]["ok"]
        finally:
            s.close()
            control.close()

    def test_audit_red_on_diverged_mv(self):
        from risingwave_tpu.common.audit import (
            AuditViolation, ConsistencyAuditor,
        )
        from risingwave_tpu.frontend import Session
        s = Session()
        control = Session()
        try:
            for sess, v in ((s, 5), (control, 6)):
                sess.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, "
                             "v BIGINT)")
                sess.run_sql("CREATE MATERIALIZED VIEW m AS "
                             "SELECT sum(v) AS n FROM t")
                sess.run_sql(f"INSERT INTO t VALUES (1, {v})")
                sess.run_sql("FLUSH")
            report = ConsistencyAuditor(s).audit(control=control)
            assert not report.ok and report.failed() == ["mv_parity"]
            with pytest.raises(AuditViolation):
                report.assert_ok()
        finally:
            s.close()
            control.close()


class TestSessionChaosSurface:
    def test_metrics_chaos_section_without_schedule(self):
        from risingwave_tpu.frontend import Session
        s = Session()
        try:
            m = s.metrics()["chaos"]
            assert m["installed"] is False
            assert m["generation"] == 1
            assert m["stale_acks_dropped"] == 0
        finally:
            s.close()

    def test_generation_persists_across_restart(self, tmp_path):
        from risingwave_tpu.frontend import Session
        d = str(tmp_path / "db")
        s = Session(data_dir=d)
        g1 = s._generation
        s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY)")
        s.run_sql("FLUSH")
        s.close()
        s2 = Session(data_dir=d)
        try:
            assert s2._generation == g1 + 1   # restart = new generation
        finally:
            s2.close()
