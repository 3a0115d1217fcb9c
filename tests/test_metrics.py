"""Per-executor metrics + session barrier-latency observability
(VERDICT r2 item 8)."""

from risingwave_tpu.frontend import Session

DDL = """
CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,
  channel VARCHAR, url VARCHAR, date_time TIMESTAMP, extra VARCHAR)
WITH (connector = 'nexmark', nexmark_table = 'bid');
CREATE SOURCE auction (id BIGINT, item_name VARCHAR, description VARCHAR,
  initial_bid BIGINT, reserve BIGINT, date_time TIMESTAMP,
  expires TIMESTAMP, seller BIGINT, category BIGINT, extra VARCHAR)
WITH (connector = 'nexmark', nexmark_table = 'auction')
"""


def test_session_metrics_surface():
    s = Session(source_chunk_capacity=64)
    s.run_sql(DDL)
    s.run_sql("""CREATE MATERIALIZED VIEW q AS
        SELECT auction, COUNT(*) AS c FROM bid GROUP BY auction""")
    s.run_sql("""CREATE MATERIALIZED VIEW j AS
        SELECT B.auction, A.seller FROM bid B
        INNER JOIN auction A ON B.auction = A.id""")
    for _ in range(4):
        s.tick()
    m = s.metrics()
    assert m["epoch"] == s.epoch
    bl = m["barrier_latency"]
    assert bl["count"] >= 4 and bl["p99_ms"] is not None
    assert bl["p50_ms"] <= bl["p99_ms"] <= bl["max_ms"]

    q = m["jobs"]["q"]
    # the materialize + agg stage both saw chunks and barriers
    agg = next(v for k, v in q.items() if k.startswith("HashAgg"))
    mat = next(v for k, v in q.items() if k.startswith("Materialize"))
    assert agg["chunks_in"] == 4
    assert agg["capacity_rows_in"] == 4 * 64
    assert agg["barriers"] >= 4
    assert agg["chunks_out"] >= 1
    assert mat["chunks_in"] >= 1
    assert mat["barrier_seconds"] >= 0.0

    j = m["jobs"]["j"]
    join = next(v for k, v in j.items() if k.startswith("HashJoin"))
    assert join["chunks_in"] == 8        # both sides
    assert join["barriers"] >= 4
    assert join["chunks_out"] >= 1


def test_metrics_count_batches():
    import asyncio
    from risingwave_tpu.common import INT64, Schema, make_chunk
    from risingwave_tpu.common.chunk import stack_chunks
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.stream import Barrier, HashAggExecutor, MockSource

    S = Schema.of(("k", INT64), ("v", INT64))
    chunks = [make_chunk(S, [(i, i)], capacity=8) for i in range(4)]
    src = MockSource(S, [Barrier.new(1), stack_chunks(chunks), Barrier.new(2)])
    agg = HashAggExecutor(src, [0], [count_star()], table_capacity=64,
                          out_capacity=16)

    async def drain():
        async for _ in agg.execute():
            pass

    asyncio.run(drain())
    st = agg.stats.snapshot()
    assert st["batches_in"] == 1
    assert st["batch_chunks_in"] == 4
    assert st["capacity_rows_in"] == 4 * 8


# -- epoch-aware tracing spans (common/tracing.py) ----------------------------

def test_trace_recorder_ring_and_drain():
    from risingwave_tpu.common.tracing import Span, TraceRecorder

    rec = TraceRecorder(capacity=4)
    for i in range(10):
        rec.record(Span(f"s{i}", "epoch", i * 1000, 1000, epoch=i))
    spans = rec.snapshot()
    assert [s.name for s in spans] == ["s6", "s7", "s8", "s9"]  # bounded
    assert rec.epochs() == [6, 7, 8, 9]
    assert [s.epoch for s in rec.snapshot(epoch=7)] == [7]
    drained = rec.drain()
    assert len(drained) == 4 and rec.snapshot() == []           # take+clear
    # wire round-trip (Span.to_dict/from_dict is the stats-frame codec)
    back = [Span.from_dict(s.to_dict()) for s in drained]
    assert [(s.name, s.epoch) for s in back] == [
        (s.name, s.epoch) for s in drained]
    # unknown keys from a newer worker are ignored, not fatal; ingest
    # re-records shipped dicts tagged with the sender's pid
    d = drained[0].to_dict()
    d["new_field_from_the_future"] = 1
    rec.ingest([d], pid=3)
    (got,) = rec.snapshot()
    assert got.name == drained[0].name and got.pid == 3


def test_chrome_trace_export_covers_epochs_and_executors():
    """Acceptance: after a NEXmark-source run, the Chrome trace-event
    export is valid JSON whose spans cover >= 2 epochs, each with
    per-executor child spans on their own tracks."""
    import json

    from risingwave_tpu.common.tracing import GLOBAL_TRACE

    GLOBAL_TRACE.clear()
    s = Session(source_chunk_capacity=64, checkpoint_frequency=2)
    s.run_sql(DDL)
    s.run_sql("""CREATE MATERIALIZED VIEW q AS
        SELECT auction, count(*) AS n, max(price) AS mx
        FROM bid GROUP BY auction""")
    for _ in range(4):
        s.tick()
    s._drain_inflight()
    obj = json.loads(json.dumps(s.export_chrome_trace()))  # JSON-clean
    events = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
    assert all({"name", "cat", "ts", "dur", "pid", "tid"} <= set(e)
               for e in events)
    epoch_spans = {e["args"]["epoch"] for e in events
                   if e["name"].startswith("epoch ")}
    assert len(epoch_spans) >= 2
    for ep in epoch_spans:
        per_exec = {e["tid"] for e in events
                    if e["cat"] == "barrier" and e["args"].get("epoch") == ep}
        assert {"HashAgg", "Materialize"} <= per_exec
    # conductor phases present and storage commits attributed
    names = {e["name"] for e in events}
    assert {"barrier.inject", "barrier.collect"} <= names
    assert any(e["cat"] == "storage" for e in events)
    # process metadata names the session track
    metas = [e for e in obj["traceEvents"] if e.get("ph") == "M"]
    assert any(m["args"]["name"] == "session" for m in metas)
    s.close()


def test_slow_epoch_threshold_captures_span_tree():
    """An epoch whose barrier latency meets slow_epoch_threshold_ms gets
    its span tree snapshotted into the session's slow-epoch ring."""
    s = Session(source_chunk_capacity=64)
    s.run_sql(DDL)
    s.run_sql("""CREATE MATERIALIZED VIEW q AS
        SELECT auction, count(*) AS c FROM bid GROUP BY auction""")
    s.tick()
    s._drain_inflight()
    assert s.slow_epochs() == []               # disabled by default
    s.run_sql("SET slow_epoch_threshold_ms = 0.0001")   # everything trips
    s.tick()
    s._drain_inflight()
    caught = s.slow_epochs()
    assert caught and caught[-1]["latency_ms"] > 0
    spans = caught[-1]["spans"]
    assert any(sp["name"].startswith("epoch ") for sp in spans)
    assert any(sp["cat"] == "barrier" for sp in spans)  # executor children
    m = s.metrics()
    assert m["slow_epoch_total"] == len(caught)
    # metrics() summarizes without the heavy span payload
    assert all("spans" not in se for se in m["slow_epochs"])
    s.close()
