"""Cross-process metrics federation (VERDICT rec #9): a job scheduled
onto a WorkerHost must be as observable as a local one — its executor
tree and counters reach the dashboard HTTP payload, the /metrics
Prometheus exposition, and the Chrome trace export WHILE it runs
(reference: MonitorService.stack_trace + per-compute-node exporters,
src/compute/src/rpc/service/monitor_service.rs:46)."""

import json
import urllib.request

import pytest

from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend.dashboard import serve_dashboard
from risingwave_tpu.frontend.prometheus import render_metrics


@pytest.fixture
def cluster(tmp_path):
    from risingwave_tpu.common.tracing import GLOBAL_TRACE

    GLOBAL_TRACE.clear()
    s = Session(workers=1, seed=11, data_dir=str(tmp_path / "cluster"))
    s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
    s.run_sql("CREATE MATERIALIZED VIEW m AS SELECT k, v * 2 AS d FROM t")
    assert "m" in s._remote_specs          # placed on the worker
    s.run_sql("INSERT INTO t VALUES (1, 10), (2, 20)")
    s.flush()
    yield s
    s.close()


def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.read().decode()


def test_worker_job_counters_federate_into_metrics(cluster):
    s = cluster
    m = s.metrics()
    # the worker-hosted job appears exactly like a local job
    assert "m" in m["jobs"]
    mat = next(v for k, v in m["jobs"]["m"].items()
               if k.startswith("Materialize"))
    assert mat["barriers"] >= 1 and mat["chunks_in"] >= 1
    assert "m" in m["state_bytes"]
    (w,) = m["workers"]
    assert w["worker"] == 0 and not w["dead"] and "m" in w["jobs"]


def test_worker_job_in_prometheus_exposition(cluster):
    text = render_metrics(cluster)
    assert 'rw_executor_counter{job="m"' in text
    assert 'rw_state_bytes{job="m"}' in text
    assert 'rw_worker_up{worker="0"} 1' in text


def test_worker_await_tree_visible_over_http(cluster):
    """The done-criterion: the await-tree of a worker-hosted job,
    visible over HTTP while it runs."""
    s = cluster
    dash = serve_dashboard(s)
    try:
        status, tree = _get(dash.port, "/api/await_tree")
        assert status == 200
        assert "job 'm' (worker 0)" in tree
        assert "Materialize" in tree           # the tree, not just a name

        status, body = _get(dash.port, "/api/metrics")
        dm = json.loads(body)
        assert "m" in dm["jobs"] and "m" in dm["state_bytes"]
        assert dm["workers"][0]["jobs"] == ["m"]
    finally:
        dash.close()


def test_worker_spans_merge_into_chrome_trace(cluster):
    """Worker barrier spans ship over the stats frame and land in the
    export as their own process, aligned on the shared wall clock."""
    s = cluster
    obj = s.export_chrome_trace()
    events = [e for e in obj["traceEvents"] if e.get("ph") == "X"]
    worker_events = [e for e in events if e["pid"] == 1]   # worker 0
    assert any(e["cat"] == "barrier" for e in worker_events)
    # federated spans keep their tree: ids, parents and the epoch, on
    # the host-wide monotonic clock (ISSUE 25)
    by_id = {e["args"]["id"]: e for e in worker_events}
    collects = [e for e in worker_events if e["name"] == "barrier.collect"]
    assert collects
    children = [e for e in worker_events
                if e["args"].get("parent") in {c["args"]["id"]
                                               for c in collects}]
    assert children
    for e in children:
        parent = by_id[e["args"]["parent"]]
        assert parent["args"]["epoch"] == e["args"]["epoch"]
        assert parent["ts"] <= e["ts"]
    metas = [e for e in obj["traceEvents"] if e.get("ph") == "M"]
    names = {m["args"]["name"] for m in metas}
    assert {"session", "worker-0"} <= names


def test_slow_epoch_capture_includes_worker_spans(cluster):
    """The slow-epoch snapshot force-polls workers first, so a
    worker-hosted job's capture holds its executor spans — not just the
    conductor side."""
    s = cluster
    s.run_sql("SET slow_epoch_threshold_ms = 0.0001")   # everything trips
    s.run_sql("INSERT INTO t VALUES (3, 30)")
    s.flush()
    slow = s.slow_epochs()
    assert slow
    spans = slow[-1]["spans"]
    assert any(sp["pid"] == 1 for sp in spans), spans   # worker-0 spans


def test_stats_span_outbox_resends_until_acked(tmp_path):
    """A drained span batch is retained by the worker until the next
    stats request acknowledges its sequence number — a timed-out
    (discarded) stats reply resends spans instead of losing them."""
    from risingwave_tpu.common.tracing import GLOBAL_TRACE, Span
    from risingwave_tpu.worker.host import WorkerHost

    GLOBAL_TRACE.clear()
    h = WorkerHost(str(tmp_path), worker_id=0)
    GLOBAL_TRACE.record(Span("a", "barrier", 0, 1000, epoch=1))
    r1 = h.handle_stats({"type": "stats"})
    assert [s["name"] for s in r1["spans"]] == ["a"]
    # reply lost: the next request carries a stale ack -> resend + new
    GLOBAL_TRACE.record(Span("b", "barrier", 0, 1000, epoch=2))
    r2 = h.handle_stats({"type": "stats", "span_ack": r1["span_seq"] - 1})
    assert [s["name"] for s in r2["spans"]] == ["a", "b"]
    # reply processed: acking the current seq clears the outbox
    r3 = h.handle_stats({"type": "stats", "span_ack": r2["span_seq"]})
    assert r3["spans"] == []
    GLOBAL_TRACE.clear()


def test_dead_worker_keeps_last_snapshot(cluster):
    """A dead worker's last stats snapshot survives for post-hoc
    inspection, and the exposition flips its liveness gauge."""
    import time

    s = cluster
    s.metrics()                               # populate the cache
    s.workers[0].kill9()
    time.sleep(0.6)                           # past the poll rate-limit
    m = s.metrics()                           # federation skips the corpse
    assert "m" in m["jobs"]                   # cached snapshot retained
    assert m["workers"][0]["dead"]
    assert 'rw_worker_up{worker="0"} 0' in render_metrics(s)
