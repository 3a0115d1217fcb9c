"""One span primitive over the whole of ``Session.tick()`` (ISSUE 25).

Every barrier leaves a tree of spans in the process ring, rooted at
``session.tick``, on one monotonic clock, and — whenever a profiler
session runs — the same spans in the profiler's trace. Pinned here, on
the CPU at tiny sizes and with no timing thresholds: the span names and
parents of both tick paths (they are a contract: PERF.md, the benchmark's
readers and ``docs/observability.md`` key on them), interval nesting, the
ledger's account of the whole tick, what survives ``Session.close()``,
and that none of it adds a dispatch or renames a program.
"""

import asyncio
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

from risingwave_tpu.common import tracing
from risingwave_tpu.common.barrier_ledger import ALL_STAGES
from risingwave_tpu.common.tracing import GLOBAL_TRACE, Span, TraceRecorder
from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend.build import BuildConfig

CAP = 64
CHUNKS = 16
BID_DDL = """CREATE SOURCE bid (auction BIGINT, bidder BIGINT,
    price BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
    extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'bid')"""
MV = ("CREATE MATERIALIZED VIEW q5 AS SELECT window_start, auction, "
      "count(*) AS num FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
      "GROUP BY window_start, auction")

PATHS = ("fused", "exec")
NEW_STAGES = ("source_feed", "epoch_dispatch", "epoch_wait", "flush_decode",
              "state_delta")

#: span → parent, per path; ``None`` = no parent. Spans of category
#: "dispatch" (one per profiled jit call) hang under whatever enqueued them
#: and are not part of the contract.
CONDUCTOR = {
    "session.tick": None,
    "source.feed": "session.tick",
    "barrier.inject": "session.tick",
    "barrier.collect": "session.tick",
    "actor.run": "barrier.collect",
    "Materialize.chunks": "barrier.collect",
    "Materialize.barrier": "barrier.collect",
    "materialize.fetch_wait": "Materialize.barrier",
    "Materialize.seal": "Materialize.barrier",
}
#: what the one fused driver (stream/fused_jobs.py) emits for any kind
FUSED_DRIVER = {**CONDUCTOR,
                "cosched.dispatch": "session.tick",
                "cosched.flush_begin": "session.tick"}
#: the wait / decode split of ``JobAxisGroup.finish_flush``
FUSED_FLUSH = {"cosched.epoch_wait": "session.tick",
               "cosched.flush_decode": "session.tick"}
EVERY_BARRIER = {
    "fused": {**FUSED_DRIVER, **FUSED_FLUSH},
    "hetero": {**FUSED_DRIVER, **FUSED_FLUSH},
    # ShardedCoGroup.finish_flush (grow-retry inside) has no split yet
    "shardfused": FUSED_DRIVER,
    "exec": {**CONDUCTOR,
             **{f"{ident}.{kind}": "barrier.collect"
                for ident in ("Project", "HashAgg")
                for kind in ("chunks", "barrier")},
             "agg.flush_wait": "HashAgg.barrier"},
}
#: the checkpoint by part (ISSUE 35): one leaf a kind of work, under
#: whatever ``*.state_delta`` / store writer's span is open
DELTA_PARTS = ("delta.fetch_wait", "delta.encode", "delta.stage")
SEGMENT_PARTS = ("segment.encode", "segment.put", "manifest.write")
COMMIT = {"checkpoint.commit": "session.tick",
          "commit.pending": "checkpoint.commit",
          "DurableStateStore.commit": "checkpoint.commit",
          **dict.fromkeys(SEGMENT_PARTS, "DurableStateStore.commit"),
          "store.apply": "checkpoint.commit",
          **dict.fromkeys(DELTA_PARTS, "agg.state_delta")}
FUSED_CHECKPOINT = {"agg.state_delta": "session.tick",
                    "cosched.restack": "session.tick", **COMMIT}
CHECKPOINT_ONLY = {
    "fused": FUSED_CHECKPOINT,
    "hetero": FUSED_CHECKPOINT,
    "shardfused": FUSED_CHECKPOINT,
    "exec": {"agg.state_delta": "HashAgg.barrier", **COMMIT},
}
#: never owed to a reader: they occur only sometimes
SOMETIMES = {"xla.compile", "cosched.resolve_deferred"}


def open_session(path: str, data_dir=None, capacity: int = 1 << 16,
                 **kw) -> Session:
    from risingwave_tpu.parallel.sharded_agg import make_mesh
    s = Session(config=BuildConfig(coschedule=path in ("fused", "shardfused"),
                                   tick_compiler=(path == "hetero"),
                                   mesh=(make_mesh(4) if path == "shardfused"
                                         else None),
                                   agg_table_capacity=capacity),
                source_chunk_capacity=CAP, chunks_per_tick=CHUNKS,
                checkpoint_frequency=3, data_dir=data_dir, **kw)
    s.run_sql(BID_DDL)
    s.run_sql(MV)
    return s


def run(path: str, tmp_path, ticks: int = 7):
    """A session of ``ticks`` barriers on a cleared ring. Returns
    ``(session, {epoch: [span dict]})``."""
    s = open_session(path, str(tmp_path / path))
    GLOBAL_TRACE.clear()
    first = s.epoch + 1
    for _ in range(ticks):
        s.tick()
    spans = {e: v for e, v in tracing.epoch_spans().items() if e >= first}
    assert sorted(spans) == list(range(first, first + ticks))
    return s, spans


def contract(spans: list) -> list:
    """The spans a reader may count on: no per-dispatch spans, none of
    the sometimes-spans, not the barrier-latency summary."""
    return [d for d in spans if d["cat"] != tracing.CAT_DISPATCH
            and d["name"] not in SOMETIMES
            and not d["name"].startswith("epoch ")]


# -- (1, 2, 8) names and parents, every epoch, both paths ---------------------

@pytest.mark.parametrize("path", PATHS + ("hetero", "shardfused"))
def test_every_epoch_has_exactly_the_contract_spans(path, tmp_path):
    s, by_epoch = run(path, tmp_path)
    try:
        if path != "exec":
            assert s._fused.engines["q5"].kind.name == {
                "fused": "coschedule"}.get(path, path)
        history = {r["epoch"]: r for r in s._barrier_ledger.history()}
        for epoch, spans in by_epoch.items():
            want = dict(EVERY_BARRIER[path])
            if history[epoch]["checkpoint"]:
                want.update(CHECKPOINT_ONLY[path])
            by_id = {d["id"]: d for d in spans}
            got = {}
            for d in contract(spans):
                parent = by_id[d["parent"]]["name"] if d["parent"] else None
                got.setdefault(d["name"], set()).add(parent)
            assert {n: {p} for n, p in want.items()} == got, epoch
            assert all(d["epoch"] == epoch for d in spans)
            # the barrier's latency interval rides on a track of its own
            (summary,) = [d for d in spans if d["name"] == f"epoch {epoch}"]
            assert summary["parent"] is None
            roots = [d for d in contract(spans) if d["parent"] is None]
            assert [d["name"] for d in roots] == ["session.tick"]
    finally:
        s.close()


@pytest.mark.parametrize("path", PATHS)
def test_no_span_takes_the_harness_annotation_names(path, tmp_path):
    """The benchmark zips its own ``tick`` annotations with its barriers;
    a program span of that name would mislabel them."""
    s, by_epoch = run(path, tmp_path, ticks=4)
    try:
        names = {d["name"] for spans in by_epoch.values() for d in spans}
        assert not names & {"tick", "tick.checkpoint"}
    finally:
        s.close()


# -- (3) intervals ------------------------------------------------------------

@pytest.mark.parametrize("path", PATHS)
def test_children_lie_inside_parents_and_conductor_siblings_are_disjoint(
        path, tmp_path):
    s, by_epoch = run(path, tmp_path)
    try:
        for spans in by_epoch.values():
            by_id = {d["id"]: d for d in spans}
            for d in spans:
                if d["parent"] is None:
                    continue
                p = by_id[d["parent"]]
                assert p["start_ns"] <= d["start_ns"], (d["name"], p["name"])
                assert d["start_ns"] + d["dur_ns"] \
                    <= p["start_ns"] + p["dur_ns"], (d["name"], p["name"])
            (tick,) = [d for d in spans if d["name"] == "session.tick"]
            kids = sorted((d for d in spans if d["parent"] == tick["id"]),
                          key=lambda d: d["start_ns"])
            for a, b in zip(kids, kids[1:]):
                assert a["start_ns"] + a["dur_ns"] <= b["start_ns"], \
                    (a["name"], b["name"])
            assert sum(d["dur_ns"] for d in kids) <= tick["dur_ns"]
    finally:
        s.close()


# -- (4) the ledger accounts for the whole tick -------------------------------

@pytest.mark.parametrize("path", PATHS)
def test_ledger_record_spans_the_tick_and_history_shows_it(path, tmp_path):
    s, by_epoch = run(path, tmp_path)
    try:
        seen = set()
        for rec in s._barrier_ledger.history():
            if rec["epoch"] not in by_epoch:
                continue
            st = rec["stages"]
            assert rec["tick_ms"] >= st["inject"] + rec["total_ms"]
            assert rec["compiles"] >= 0
            (tick,) = [d for d in by_epoch[rec["epoch"]]
                       if d["name"] == "session.tick"]
            assert rec["tick_ms"] == pytest.approx(tick["dur_ns"] / 1e6,
                                                   abs=1e-3)
            assert "source_feed" in st
            seen |= set(st)
        assert set(NEW_STAGES) <= set(ALL_STAGES)
        owed = {"fused": set(NEW_STAGES),
                "exec": {"source_feed", "state_delta"}}[path]
        assert owed <= seen
        cols = ["tick_ms", "compiles"] + [f"{x}_ms" for x in NEW_STAGES]
        rows = s.run_sql(f"SELECT epoch, {', '.join(cols)} "
                         "FROM rw_barrier_history")
        by = {r[0]: dict(zip(cols, r[1:])) for r in rows}
        for epoch in by_epoch:
            assert by[epoch]["tick_ms"] > 0
            assert by[epoch]["source_feed_ms"] is not None
    finally:
        s.close()


# -- (6) the state delta: checkpoint barriers only, both callers --------------

def agg_engine(s: Session, path: str):
    if path == "fused":
        return s._fused.engines["q5"].agg
    from risingwave_tpu.stream.metrics import iter_executors
    (agg,) = [ex for ex in iter_executors(s.jobs["q5"].pipeline)
              if ex.identity == "HashAgg"]
    return agg


@pytest.mark.parametrize("path", PATHS)
def test_state_delta_on_checkpoints_only_counts_the_rows_it_writes(
        path, tmp_path):
    s = open_session(path, str(tmp_path / path))
    try:
        table = agg_engine(s, path).state_table
        written = []
        for name in ("stage_packed", "stage_encoded", "insert", "delete"):
            real = getattr(table, name)

            def spy(*a, _real=real, _name=name):
                written[-1] += (sum(map(len, a))
                                if _name.startswith("stage_") else 1)
                return _real(*a)
            setattr(table, name, spy)
        GLOBAL_TRACE.clear()
        epochs = []
        for _ in range(7):
            written.append(0)
            s.tick()
            epochs.append(s.epoch)
        by_epoch = tracing.epoch_spans()
        history = {r["epoch"]: r for r in s._barrier_ledger.history()}
        checkpoints = 0
        for epoch, n_written in zip(epochs, written):
            deltas = [d for d in by_epoch[epoch]
                      if d["name"] == "agg.state_delta"]
            if not history[epoch]["checkpoint"]:
                assert deltas == [] and n_written == 0
                continue
            checkpoints += 1
            (delta,) = deltas
            assert delta["args"]["dirty_groups"] == n_written > 0
            assert delta["args"]["bytes_staged"] >= 0
            assert delta["args"]["windows"] >= 1
            assert delta["args"]["bytes_fetched"] > 0
            assert history[epoch]["stages"]["state_delta"] > 0
        assert checkpoints == 2
    finally:
        s.close()


@pytest.mark.parametrize("path", PATHS)
def test_state_delta_fetches_the_dirty_rows_not_the_table(path, tmp_path):
    """The same traffic into a table eight times the size moves the same
    bytes across the link: a count, so it holds on the CPU too."""
    fetched = {}
    for capacity in (1 << 13, 1 << 16):
        s = open_session(path, str(tmp_path / f"{path}{capacity}"),
                         capacity=capacity)
        try:
            GLOBAL_TRACE.clear()
            for _ in range(6):
                s.tick()
            fetched[capacity] = [
                (d["args"]["dirty_groups"], d["args"]["windows"],
                 d["args"]["bytes_fetched"])
                for spans in tracing.epoch_spans().values() for d in spans
                if d["name"] == "agg.state_delta"]
        finally:
            s.close()
    small, large = fetched.values()
    assert len(small) == 2 and small == large
    assert all(0 < dirty < 1 << 13 and windows == 1
               for dirty, windows, _ in small)


# -- (7) the shared clock: the same spans in a profiler's trace ---------------

def host_annotations(log_dir: str) -> list:
    from jax.profiler import ProfileData
    (xplane,) = glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                stats = dict(ev.stats)
                if "epoch" in stats:
                    out.append({"name": ev.name, "epoch": int(stats["epoch"]),
                                "wait": stats.get("wait"),
                                "start_ns": int(ev.start_ns),
                                "dur_ns": int(ev.duration_ns)})
    return out


@pytest.mark.parametrize("path", PATHS)
def test_profiler_trace_holds_the_same_spans_on_a_shared_clock(
        path, tmp_path):
    s = open_session(path, str(tmp_path / path))
    try:
        for _ in range(2):
            s.tick()                      # compile outside the trace
        GLOBAL_TRACE.clear()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        log_dir = str(tmp_path / "trace")
        jax.profiler.start_trace(log_dir, profiler_options=options)
        try:
            for _ in range(3):
                s.tick()
        finally:
            jax.profiler.stop_trace()
        notes = host_annotations(log_dir)
        # everything recorded around a body is annotated; roll-ups and the
        # latency summary have no body to annotate
        # (a roll-up's STEPS are annotated under its name: the test of
        # the steps in the trace, below)
        notes = [n for n in notes if not n["name"].endswith(".chunks")]
        ring = [d for spans in tracing.epoch_spans().values() for d in spans
                if not d["name"].endswith(".chunks")
                and not d["name"].startswith("epoch ")
                and d["name"] not in ("xla.compile", "actor.run")]
        assert len(ring) >= 3 * 8
        key = lambda d: (d["epoch"], d["name"])       # noqa: E731
        by_key: dict = {}
        for n in sorted(notes, key=lambda n: n["start_ns"]):
            by_key.setdefault(key(n), []).append(n)
        paired = []
        for d in sorted(ring, key=lambda d: d["start_ns"]):
            assert by_key.get(key(d)), f"not in the trace: {key(d)}"
            paired.append((d, by_key[key(d)].pop(0)))
        assert not any(by_key.values()), "annotations the ring lacks"
        offsets = []
        for d, n in paired:
            assert abs(d["dur_ns"] - n["dur_ns"]) < 1e6, d["name"]
            assert d["wait"] == n["wait"]
            offsets.append(n["start_ns"] - d["start_ns"])
        # one clock rate: the two time bases differ by a constant
        assert max(offsets) - min(offsets) < 1e6
        # and the same nesting, read from the trace's own intervals
        note_of = {d["id"]: n for d, n in paired}
        for d, n in paired:
            if d["parent"] in note_of:
                p = note_of[d["parent"]]
                assert p["start_ns"] <= n["start_ns"]
                assert n["start_ns"] + n["dur_ns"] \
                    <= p["start_ns"] + p["dur_ns"]
    finally:
        s.close()


# -- (9) the per-operator chunk roll-up ---------------------------------------

def test_chunk_rollup_counts_sixteen_chunks_per_operator(tmp_path):
    s, by_epoch = run("exec", tmp_path, ticks=4)
    try:
        for spans in by_epoch.values():
            # by name, the executor nearest the source last (two Projects)
            rollups = {d["name"]: d
                       for d in sorted(spans, key=lambda d: d["args"].get(
                           "node", -1))
                       if d["name"].endswith(".chunks")}
            for ident in ("Project", "HashAgg"):
                args = rollups[f"{ident}.chunks"]["args"]
                assert args["chunks"] == CHUNKS
                assert args["capacity_rows"] == CHUNKS * CAP
                assert args["batches"] == 0
            # one roll-up per operator instance, never one span a chunk
            assert len([d for d in spans
                        if d["name"] == "HashAgg.chunks"]) == 1
    finally:
        s.close()


def test_chunk_rollup_excludes_the_consumers_time():
    """A slow downstream does not inflate its upstream's roll-up: only
    the operator's own steps are timed."""
    from risingwave_tpu.common.chunk import make_chunk
    from risingwave_tpu.common.types import INT64, Field, Schema
    from risingwave_tpu.stream.executor import SingleInputExecutor
    from risingwave_tpu.stream.message import Barrier
    from risingwave_tpu.stream.source import MockSource

    schema = Schema((Field("k", INT64),))
    chunks = [make_chunk(schema, [(i,)]) for i in range(4)]

    class Upstream(SingleInputExecutor):
        identity = "Upstream"

        def __init__(self, input):
            super().__init__(input)
            self.schema = input.schema

    class SlowConsumer(SingleInputExecutor):
        identity = "SlowConsumer"

        def __init__(self, input):
            super().__init__(input)
            self.schema = input.schema

        async def map_chunk(self, chunk):
            time.sleep(0.05)
            yield chunk

    async def drive():
        pipeline = SlowConsumer(Upstream(
            MockSource(schema, chunks + [Barrier.new(5)])))
        async for msg in pipeline.execute():
            if isinstance(msg, Barrier):
                return

    GLOBAL_TRACE.clear()
    asyncio.run(drive())
    rollups = {d["name"]: d for d in tracing.epoch_spans()[5]}
    assert rollups["Upstream.chunks"]["args"]["chunks"] == 4
    assert rollups["SlowConsumer.chunks"]["dur_ns"] >= 4 * 0.05 * 1e9
    assert rollups["Upstream.chunks"]["dur_ns"] < 0.05 * 1e9


# -- (10) a compile inside a barrier ------------------------------------------

def test_recompile_inside_a_barrier_is_a_span_and_a_counter(tmp_path):
    s = open_session("exec", str(tmp_path / "exec"))
    try:
        for _ in range(3):
            s.tick()                      # steady state: nothing compiles
        feed = s.feeds[0]
        real = feed.generator
        fresh = jax.jit(lambda x: x * 2 + 1)
        fired = []

        def generator():
            if not fired:
                fired.append(fresh(np.arange(7)))
            return real()
        feed.generator = generator
        GLOBAL_TRACE.clear()
        s.tick()
        epoch = s.epoch
        s.tick()
        by_epoch = tracing.epoch_spans()
        (compile_span,) = [d for d in by_epoch[epoch]
                           if d["name"] == "xla.compile"]
        assert compile_span["args"]["seconds"] > 0
        assert compile_span["args"]["cache"] in ("hit", "miss", "off")
        assert not [d for d in by_epoch[epoch + 1]
                    if d["name"] == "xla.compile"]
        records = {r["epoch"]: r for r in s._barrier_ledger.history()}
        assert records[epoch]["compiles"] == 1
        assert records[epoch + 1]["compiles"] == 0
    finally:
        s.close()


# -- (11) the run's spans survive the run; a small ring drops whole epochs ----

def test_epoch_spans_answer_after_close(tmp_path):
    s, by_epoch = run("fused", tmp_path, ticks=4)
    s.close()
    after = tracing.epoch_spans()
    assert set(by_epoch) <= set(after)
    for epoch, spans in by_epoch.items():
        assert [d["id"] for d in after[epoch]] == [d["id"] for d in spans]


def test_small_ring_drops_whole_oldest_epochs_never_half_of_one():
    rec = TraceRecorder(capacity=10)
    for epoch in range(1, 5):
        for i in range(4):
            rec.record(Span(f"s{i}", "epoch", epoch * 100 + i, 1,
                            epoch=epoch, id=epoch * 10 + i))
    # the ring holds the last 10 of 16 spans: epoch 2 lost two of its four
    assert len(rec.snapshot(epoch=2)) == 2
    answer = rec.epoch_spans()
    assert sorted(answer) == [3, 4]
    assert all(len(spans) == 4 for spans in answer.values())
    # late spans of a complete epoch push out the rest of epoch 2's and
    # one of epoch 3's
    for i in range(3):
        rec.record(Span("late", "storage", 450 + i, 1, epoch=4, id=90 + i))
    answer = rec.epoch_spans()
    assert sorted(answer) == [4] and len(answer[4]) == 7
    # shrinking the ring counts as losing spans too
    rec.set_capacity(3)
    assert rec.epoch_spans() == {}
    rec.clear()
    rec.record(Span("x", "epoch", 1, 1, epoch=9))
    assert sorted(rec.epoch_spans()) == [9]


def test_default_ring_holds_a_run_of_both_cells():
    """400 barriers at the measured span rate of either tick path (17 a
    barrier here, up to 22 on a checkpoint) fit the default ring with
    room (docs/observability.md 'Sizing the ring')."""
    from risingwave_tpu.common.config import StreamingConfig
    assert TraceRecorder().capacity == StreamingConfig().trace_ring_capacity
    assert TraceRecorder().capacity >= 400 * 40


# -- (13) export and federation carry the new fields --------------------------

def test_export_and_federation_carry_the_span_fields(tmp_path):
    s, by_epoch = run("exec", tmp_path, ticks=3)
    try:
        events = [e for e in s.export_chrome_trace()["traceEvents"]
                  if e.get("ph") == "X"]
        by_id = {e["args"]["id"]: e for e in events}
        waits = [e for e in events if e["name"] == "agg.flush_wait"]
        assert waits and all(e["args"]["wait"] == "device" for e in waits)
        for e in waits:
            assert by_id[e["args"]["parent"]]["name"] == "HashAgg.barrier"
            assert e["ts"] >= by_id[e["args"]["parent"]]["ts"]
        rollup = next(e for e in events if e["name"] == "HashAgg.chunks")
        assert rollup["args"]["chunks"] == CHUNKS
    finally:
        s.close()
    # the stats-frame codec: a worker's span dicts re-ingest whole
    shipped = [d for spans in by_epoch.values() for d in spans]
    rec = TraceRecorder()
    rec.ingest(shipped, pid=3)
    back = rec.snapshot()
    assert [(b.id, b.parent, b.wait, b.start_ns, b.dur_ns, b.args)
            for b in back] == [(d["id"], d["parent"], d["wait"],
                                d["start_ns"], d["dur_ns"], d["args"])
                               for d in shipped]
    assert {b.pid for b in back} == {3}


# -- (14) the programs keep the names the roofline metric finds them by -------

def test_jitted_epoch_programs_keep_their_module_names(tmp_path):
    import jax.numpy as jnp
    fused = open_session("fused", str(tmp_path / "fused"))
    execp = open_session("exec", str(tmp_path / "exec"))
    try:
        group = fused._fused.groups()[0]
        starts = jnp.asarray(group.starts, jnp.int64)
        nos = jnp.asarray(group.batch_nos, jnp.int64)
        packed, ranks = group._probe(group.stacked)
        agg = agg_engine(execp, "exec")
        chunk = execp.feeds[0].reader.next_chunk()
        _, rank = agg._probe(agg.state)
        lowered = {
            "jit_coscheduled_epoch": group._epoch.lower(
                group.stacked, starts, group._keys(), nos, 2),
            "jit_probe": group._probe.lower(group.stacked),
            "jit_finish": group._finish.lower(group.stacked),
            "jit_gather": group._gather.lower(
                group.stacked, ranks, jnp.int64(0), jnp.int64(0)),
            "jit__probe": agg._probe.lower(agg.state),
            "jit_finish_flush": agg._finish.lower(agg.state),
            "jit_gather_flush_chunk": agg._gather.lower(
                agg.state, rank, jnp.int64(0)),
        }
        for name, low in lowered.items():
            assert f"module @{name} " in low.as_text()[:200], name
        # the per-chunk step is jitted from a closure: ask the function
        assert agg._apply.__wrapped__.__name__ == "_apply_chunk" or \
            "apply_chunk" in agg._apply.lower(
                agg.state, chunk, agg._str_ranks(),
                agg._lru()).as_text()[:200]
    finally:
        fused.close()
        execp.close()


# -- named scopes inside the two epoch programs -------------------------------

@pytest.mark.parametrize("path,scopes", [
    ("fused", ("source_gen", "project", "table_probe", "lane_apply")),
    ("exec", ("table_probe", "lane_apply")),
])
def test_epoch_programs_carry_named_scopes(path, scopes, tmp_path):
    import jax.numpy as jnp
    s = open_session(path, str(tmp_path / path))
    try:
        if path == "fused":
            group = s._fused.groups()[0]
            low = group._epoch.lower(
                group.stacked, jnp.asarray(group.starts, jnp.int64),
                group._keys(), jnp.asarray(group.batch_nos, jnp.int64), 2)
            flush = {"flush_probe": group._probe.lower(group.stacked),
                     "flush_finish": group._finish.lower(group.stacked)}
        else:
            agg = agg_engine(s, path)
            low = agg._apply.lower(agg.state, s.feeds[0].reader.next_chunk(),
                                   agg._str_ranks(), agg._lru())
            flush = {"flush_probe": agg._probe.lower(agg.state),
                     "flush_finish": agg._finish.lower(agg.state)}
        text = low.as_text(debug_info=True)
        for scope in scopes:
            assert re.search(rf"{scope}\)?/", text), scope
        for scope, lowered in flush.items():
            # under vmap the scope reads "vmap(flush_probe)/"
            assert re.search(rf"{scope}\)?/",
                             lowered.as_text(debug_info=True)), scope
    finally:
        s.close()


# -- scripts/idle_by_span.py: gaps split at span boundaries -------------------

def test_idle_gaps_are_charged_to_the_innermost_span():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "idle_by_span", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "idle_by_span.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    s = 1_000_000_000
    raw = {"annotations": [["session.tick", 0, 10 * s],
                           ["source.feed", 0, 3 * s],
                           ["barrier.collect", 4 * s, 5 * s],
                           ["HashAgg.barrier", 6 * s, 2 * s],
                           ["session.tick", 11 * s, 1 * s]],
           "devices": [{"plane": "/device:TPU:0",
                        "ops": [["%a", 1 * s, 1 * s], ["%b", 7 * s, 2 * s],
                                ["%c", 11 * s, 1 * s]],
                        "programs": [["jit_step(1)", 1 * s, 1 * s],
                                     ["jit_flush(2)", 7 * s, 2 * s],
                                     ["jit_step(1)", 11 * s, 1 * s]]}]}
    out = mod.attribute(raw)
    assert out["window_s"] == 12 and out["busy_s"] == 4
    by_span = dict(out["idle_by_span_s"])
    # idle: [0,1) feed; [2,3) feed, [3,4) tick, [4,6) collect, [6,7) agg;
    # [9,10) tick; [10,11) between ticks
    assert by_span == {"source.feed": 2, "session.tick": 2,
                       "barrier.collect": 2, "HashAgg.barrier": 1,
                       "between ticks": 1}
    assert sum(by_span.values()) == out["idle_s"] == 8
    by_pair = dict(out["idle_by_span_after_program_s"])
    assert by_pair["source.feed after start"] == 1
    assert by_pair["barrier.collect after jit_step"] == 2
    assert by_pair["between ticks after jit_flush"] == 1


# -- the hash join's spans, counters and named scopes (ISSUE 27) --------------

Q8_DDL = (
    """CREATE SOURCE person (id BIGINT, name VARCHAR, email_address VARCHAR,
    credit_card VARCHAR, city VARCHAR, state VARCHAR, date_time TIMESTAMP,
    extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'person',
    rows_per_chunk = 64)""",
    """CREATE SOURCE auction (id BIGINT, item_name VARCHAR,
    description VARCHAR, initial_bid BIGINT, reserve BIGINT,
    date_time TIMESTAMP, expires TIMESTAMP, seller BIGINT, category BIGINT,
    extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'auction',
    rows_per_chunk = 192)""")
Q8_MV = """CREATE MATERIALIZED VIEW q8 AS
    SELECT P.id, P.name, P.starttime FROM (
        SELECT id, name, window_start AS starttime, window_end AS endtime
        FROM TUMBLE(person, date_time, INTERVAL '10' SECOND)
        GROUP BY id, name, window_start, window_end) P
    JOIN (
        SELECT seller, window_start AS starttime, window_end AS endtime
        FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND)
        GROUP BY seller, window_start, window_end) A
    ON P.id = A.seller AND P.starttime = A.starttime
       AND P.endtime = A.endtime"""
Q8_CHUNKS = 2


def open_q8(data_dir, join_key_capacity=1 << 12) -> Session:
    s = Session(config=BuildConfig(chunk_capacity=128,
                                   agg_table_capacity=1 << 12,
                                   join_key_capacity=join_key_capacity,
                                   join_bucket_width=1),
                chunks_per_tick=Q8_CHUNKS, checkpoint_frequency=3,
                data_dir=data_dir)
    for ddl in Q8_DDL:
        s.run_sql(ddl)
    s.run_sql(Q8_MV)
    return s


def join_engine(s: Session):
    from risingwave_tpu.stream.metrics import iter_executors
    (join,) = [ex for ex in iter_executors(s.jobs["q8"].pipeline)
               if ex.identity == "HashJoin"]
    return join


@pytest.fixture(scope="module")
def q8_run(tmp_path_factory):
    s = open_q8(str(tmp_path_factory.mktemp("q8") / "db"))
    GLOBAL_TRACE.clear()
    first = s.epoch + 1
    for _ in range(7):
        s.tick()
    spans = {e: v for e, v in tracing.epoch_spans().items() if e >= first}
    history = {r["epoch"]: r for r in s._barrier_ledger.history()}
    yield s, spans, history
    s.close()


@pytest.mark.parametrize("kind", ["ordinary", "checkpoint"])
def test_join_spans_and_their_args_on_both_kinds_of_barrier(q8_run, kind):
    s, by_epoch, history = q8_run
    epochs = [e for e in by_epoch
              if history[e]["checkpoint"] == (kind == "checkpoint")]
    assert len(epochs) >= 2
    for epoch in epochs:
        spans = by_epoch[epoch]
        by_id = {d["id"]: d for d in spans}

        def parent_of(d):
            return by_id[d["parent"]]["name"] if d["parent"] else None

        (chunks,) = [d for d in spans if d["name"] == "HashJoin.chunks"]
        assert parent_of(chunks) == "barrier.collect"
        args = chunks["args"]
        # every person is a new (id, name, window) group: all of the
        # barrier's persons reach the join's left input, once
        assert args["rows_in_left"] == Q8_CHUNKS * 64
        assert 0 < args["rows_in_right"] <= Q8_CHUNKS * 192
        assert args["chunks"] >= 2 and args["chunks_out"] >= 0
        assert args["rewinds"] == 0 and args["grows"] == 0
        waits = [d for d in spans if d["name"] == "join.emit_wait"]
        assert waits and all(d["wait"] == "device" for d in waits)
        assert {parent_of(d) for d in waits} == {"barrier.collect"}
        deltas = [d for d in spans if d["name"] == "join.state_delta"]
        if kind == "ordinary":
            assert deltas == []
            continue
        assert [d["args"]["side"] for d in deltas] == ["left", "right"]
        assert {parent_of(d) for d in deltas} == {"HashJoin.barrier"}
        for d in deltas:
            assert d["args"]["dirty_rows"] > 0
            assert d["args"]["windows"] >= 1
            assert d["args"]["bytes_fetched"] > 0
            assert d["args"]["bytes_staged"] > 0
        assert history[epoch]["stages"]["state_delta"] > 0
    # the left side's dirty rows of a checkpoint are the persons since the
    # one before it
    if kind == "checkpoint":
        left = [d["args"]["dirty_rows"] for e in epochs[1:]
                for d in by_epoch[e] if d["name"] == "join.state_delta"
                and d["args"]["side"] == "left"]
        assert left == [3 * Q8_CHUNKS * 64] * len(left)


def test_join_state_delta_fetches_the_dirty_rows_not_the_arena(tmp_path):
    """The join's twin of the agg's test above: the same q8 traffic into
    arenas of 2^13 and 2^16 keys moves the same bytes across the link."""
    fetched = {}
    for capacity in (1 << 13, 1 << 16):
        s = open_q8(str(tmp_path / f"q8_{capacity}"),
                    join_key_capacity=capacity)
        try:
            GLOBAL_TRACE.clear()
            for _ in range(6):
                s.tick()
            fetched[capacity] = [
                (d["args"]["side"], d["args"]["dirty_rows"],
                 d["args"]["windows"], d["args"]["bytes_fetched"])
                for spans in tracing.epoch_spans().values() for d in spans
                if d["name"] == "join.state_delta"]
        finally:
            s.close()
    small, large = fetched.values()
    assert [side for side, *_ in small] == ["left", "right"] * 2
    assert small == large
    assert all(0 < dirty < 1 << 13 and windows == 1
               for _side, dirty, windows, _bytes in small)


@pytest.mark.parametrize("path", ["exec", "q8"])
def test_source_feed_counts_what_it_staged(path, tmp_path, q8_run):
    """``source.feed`` (ISSUE 30): a feed's chunks of a barrier are staged
    together — one transfer per dtype of its schema and one unpack
    dispatch — and the args add up over the feeds: 16 bid chunks are 2 + 1
    where they were 272 copies. Since ISSUE 36 the same dispatch makes the
    hidden ``_row_id``: ``row_ids`` = the rows fed, ``dispatches`` what it
    was (one a feed), and no executor of the plan spends a span on ids."""
    if path == "exec":
        s, by_epoch = run("exec", tmp_path, ticks=3)
        feeds, chunks, cap_rows = 1, CHUNKS, CHUNKS * CAP
        # int64 x 4 + int32 x 3 a bid
        nbytes = CHUNKS * CAP * (4 * 8 + 3 * 4)
    else:
        s, by_epoch, _history = q8_run
        feeds, chunks, cap_rows = 2, 2 * Q8_CHUNKS, Q8_CHUNKS * (64 + 192)
        # person int64 x 2 + int32 x 6, auction int64 x 7 + int32 x 3
        nbytes = Q8_CHUNKS * (64 * (2 * 8 + 6 * 4) + 192 * (7 * 8 + 3 * 4))
    try:
        for spans in by_epoch.values():
            (feed,) = [d for d in spans if d["name"] == "source.feed"]
            assert feed["args"] == {
                "chunks": chunks, "capacity_rows": cap_rows,
                "transfers": 2 * feeds, "dispatches": feeds,
                "bytes_staged": nbytes, "row_ids": cap_rows}
            assert not [d["name"] for d in spans
                        if d["name"].startswith("RowId")]
    finally:
        if path == "exec":
            s.close()


def test_a_failed_draw_loses_none_of_the_chunks_drawn_before_it(tmp_path):
    """A draw advances the reader's offsets. Where a later draw of the same
    barrier raises (a broker fetch out of retries, a file read error), what
    was drawn is staged and queued all the same: the retried tick goes on
    from there, and the checkpoint's offsets cover only rows the MV holds —
    in this session and in the one recovered from it."""
    data_dir = str(tmp_path / "flaky")
    s = open_session("exec", data_dir)
    try:
        s.tick()
        feed = s.feeds[0]
        real, draws = feed.generator, []

        def flaky():
            draws.append(None)
            if len(draws) == 3:
                raise OSError("fetch failed, out of retries")
            return real()
        feed.generator = flaky
        with pytest.raises(OSError):
            s.tick()
        assert feed.reader.offsets == {"0": CHUNKS + 2}
        ticks = 2                           # the ticker retries
        s.tick()
        while s.epoch % 3:
            s.tick()                        # ... through a checkpoint
            ticks += 1
        fed = feed.reader.offsets["0"]
        assert fed == ticks * CHUNKS + 2
        assert sum(r[2] for r in s.mv_rows("q5")) == fed * CAP
    finally:
        s.close()
    # recovered: catalog, split offsets and MV from the last checkpoint
    s = Session(config=BuildConfig(agg_table_capacity=1 << 16),
                source_chunk_capacity=CAP, chunks_per_tick=CHUNKS,
                checkpoint_frequency=3, data_dir=data_dir)
    try:
        assert s.feeds[0].reader.offsets == {"0": fed}
        assert sum(r[2] for r in s.mv_rows("q5")) == fed * CAP
    finally:
        s.close()


def test_join_programs_carry_their_names_and_scopes(q8_run):
    s, _by_epoch, _history = q8_run
    join = join_engine(s)
    from risingwave_tpu.common.chunk import physical_chunk
    ch = physical_chunk(join.core.left_schema, [], 128)
    low = join._apply["left"].lower(join.state, ch, None)
    assert "module @jit_join_step_left " in low.as_text()[:200]
    text = low.as_text(debug_info=True)
    for scope in ("join_probe", "join_insert", "join_emit"):
        assert re.search(rf"{scope}\)?/", text), scope
    assert join._apply["right"].__wrapped__.__name__ == "join_step_right"
    assert join._gather.__wrapped__.__name__ == "join_gather"
    assert join._pack_stats.__wrapped__.__name__ == "join_pack_stats"


# -- the closed span tree of the five cells' plans (ISSUE 35) -----------------
# Every executor's steps in the profiler's trace under its roll-up's name,
# a clock for the job's task (``actor.run``), and the checkpoint's delta
# and commit by part. The plans are the benchmark's configurations at
# their tiny sizes, through the benchmark's own ``System``.

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL_CONFIGS = {"fused": "nexmark-q5core-fused",
                "exec": "nexmark-q5core-exec",
                "q8": "nexmark-q8",
                "q101": "nexmark-q101",
                "q104": "nexmark-q104",
                "mesh": "nexmark-q5core-exec-mesh4"}
DEFAULT_PATH = ("exec", "q8", "q101", "q104", "mesh")
#: the store writer's children (``commit.pending`` and ``store.apply`` sit
#: beside it, under ``checkpoint.commit``)
COMMIT_PARTS = ("commit.pending", "DurableStateStore.commit", "store.apply")
CELL_TICKS = 7


@pytest.fixture(scope="module", params=list(CELL_CONFIGS))
def cell_run(request, tmp_path_factory):
    """Seven barriers (two checkpoints) of one cell's plan under a
    profiler session: ``(cell, session, {epoch: [span dict]}, ledger
    records by epoch, the trace's directory)``."""
    import json
    import sys
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import run as bench_run
    from benchmark import system
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{CELL_CONFIGS[request.param]}.json")) as f:
        config = bench_run.tiny_sizes(json.load(f))
    config["rw_toml"] = {**config["rw_toml"],
                         "streaming.checkpoint_frequency": 3}
    tmp = tmp_path_factory.mktemp(request.param)
    sut = system.System(config, str(tmp / "data"), 3_500_000_017)
    sut.create()
    for _ in range(2):
        sut.barrier()                     # compile outside the trace
    GLOBAL_TRACE.clear()
    first = sut.session.epoch + 1
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    log_dir = str(tmp / "trace")
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        for _ in range(CELL_TICKS):
            sut.barrier()
    finally:
        jax.profiler.stop_trace()
    spans = {e: v for e, v in tracing.epoch_spans().items() if e >= first}
    assert sorted(spans) == list(range(first, first + CELL_TICKS))
    history = {r["epoch"]: r for r in sut.session._barrier_ledger.history()}
    yield request.param, sut.session, spans, history, log_dir
    sut.close()


def children_of(spans: list, parent: dict) -> list:
    return [d for d in spans if d["parent"] == parent["id"]]


def test_every_executor_of_a_plan_has_a_clock_and_a_node(cell_run):
    """(a) what ``iter_executors`` walks is what emits ``.chunks`` and
    ``.barrier``, on every barrier, told apart by ``node``."""
    from risingwave_tpu.stream.metrics import iter_executors
    cell, s, by_epoch, _history, _log = cell_run
    (job,) = s.jobs.values()
    plan = list(iter_executors(job.pipeline))
    assert [ex.node for ex in plan] == list(range(len(plan)))
    # the queue under the fused job's Materialize stays bare: its own
    # work is a ``queue.get()``
    from risingwave_tpu.frontend.runtime import QueueSource
    assert all(isinstance(ex, QueueSource) for ex in plan
               if not hasattr(ex, "stats"))
    want = sorted((ex.identity, ex.node) for ex in plan
                  if hasattr(ex, "stats"))
    if cell in DEFAULT_PATH:
        idents = {ident for ident, _node in want}
        assert {"Project", "Materialize"} <= idents
        # two executors of one identity in every default-path plan
        assert len(idents) < len(want)
        # ISSUE 36: a connector's chunks are staged with their _row_id, so
        # no executor stands between a source's queue and its Project
        assert not [ident for ident in idents if ident.startswith("RowId")]
        fed = {id(f.queue) for f in s.feeds}
        over_queue = [ex for ex in plan if id(getattr(ex, "input", None)) in fed]
        assert len(over_queue) == len(fed) == len(job.sources)
        assert {ex.identity for ex in over_queue} == {"Project"}
        assert all(f.reader is not None and f.row_ids is not None
                   for f in s.feeds)
    for epoch, spans in by_epoch.items():
        for kind in (".chunks", ".barrier"):
            got = sorted((d["name"][:-len(kind)], d["args"]["node"])
                         for d in spans if d["name"].endswith(kind))
            assert got == want, (epoch, kind)


def test_actor_run_is_the_tasks_clock_under_collect(cell_run):
    """(a) one ``actor.run`` a job task and epoch, inside
    ``barrier.collect``, and no executor's time outside it."""
    _cell, s, by_epoch, _history, _log = cell_run
    (job,) = s.jobs.values()
    for epoch, spans in by_epoch.items():
        (collect,) = [d for d in spans if d["name"] == "barrier.collect"]
        (actor,) = [d for d in spans if d["name"] == "actor.run"]
        assert actor["parent"] == collect["id"]
        assert actor["tid"] == job.name and actor["args"]["task"] == 0
        # the feeds' chunks + the barrier, of every source
        assert actor["args"]["messages"] >= len(job.sources)
        assert collect["start_ns"] <= actor["start_ns"]
        assert actor["start_ns"] + actor["dur_ns"] \
            <= collect["start_ns"] + collect["dur_ns"]
        own = [d for d in children_of(spans, collect)
               if d["name"].endswith((".chunks", ".barrier"))]
        for d in own:
            assert actor["start_ns"] <= d["start_ns"], d["name"]
        unowned_ns = actor["dur_ns"] - sum(d["dur_ns"] for d in own)
        assert unowned_ns >= -0.5e6, epoch


def test_executor_steps_are_in_the_profilers_trace(cell_run):
    """(b) the steps a roll-up sums are annotations of the roll-up's name,
    found by the names ``epoch_spans()`` hands out, as many as steps ran,
    each inside a ``barrier.collect`` annotation."""
    from benchmark import trace
    cell, _s, by_epoch, _history, log_dir = cell_run
    names = {d["name"] for spans in by_epoch.values() for d in spans}
    raw = trace.extract(trace.find_xplane(log_dir), tuple(names))
    events: dict = {}
    for name, start, dur in raw["annotations"]:
        events.setdefault(name, []).append((start, start + dur))
    collects = events["barrier.collect"]
    assert len(collects) == CELL_TICKS
    rollups: dict = {}
    for spans in by_epoch.values():
        for d in spans:
            if d["name"].endswith(".chunks") or d["name"] == "shard.split":
                rollups[d["name"]] = (rollups.get(d["name"], 0)
                                      + d["args"]["chunks"])
    owed = {"fused": ("Materialize.chunks",),
            "mesh": ("Project.chunks", "ShardedHashAgg.chunks",
                     "Materialize.chunks", "shard.split")}.get(
        cell, ("Project.chunks", "HashAgg.chunks", "Materialize.chunks"))
    assert set(owed) <= set(rollups)
    # ISSUE 36: no barrier of any cell's plan has a row-id span or step
    assert not [name for name in set(names) | set(events)
                if name.startswith(("RowIdAppend", "RowIdGen"))]
    for name, chunks in rollups.items():
        steps = events.get(name, [])
        if name == "HashJoin.chunks":
            # a step a chunk and one a flush of pending output
            assert len(steps) >= chunks > 0
        elif name == "shard.split":
            assert len(steps) == chunks > 0, name
        else:
            # each chunk: a step an output and the closing StopIteration
            assert chunks <= len(steps) <= 2 * chunks + CELL_TICKS, name
        for lo, hi in steps:
            assert any(c0 <= lo and hi <= c1 for c0, c1 in collects), name


def test_checkpoint_by_part_under_every_delta_and_the_commit(cell_run):
    """(c) each ``*.state_delta`` has its three children, the commit its
    three and the store writer its three; children never sum to more than
    their parent; an ordinary barrier has none of them."""
    cell, _s, by_epoch, history, _log = cell_run
    parts = set(DELTA_PARTS + SEGMENT_PARTS + COMMIT_PARTS)
    deltas_a_checkpoint = {"fused": 1, "exec": 1, "mesh": 1, "q8": 4,
                           "q101": 3, "q104": 3}[cell]
    checkpoints = 0
    for epoch, spans in by_epoch.items():
        if not history[epoch]["checkpoint"]:
            assert not [d["name"] for d in spans if d["name"] in parts]
            continue
        checkpoints += 1
        deltas = [d for d in spans if d["name"].endswith(".state_delta")]
        assert len(deltas) == deltas_a_checkpoint
        (commit,) = [d for d in spans if d["name"] == "checkpoint.commit"]
        (writer,) = [d for d in spans
                     if d["name"] == "DurableStateStore.commit"]
        for parent, want in ([(d, DELTA_PARTS) for d in deltas]
                             + [(commit, COMMIT_PARTS),
                                (writer, SEGMENT_PARTS)]):
            kids = children_of(spans, parent)
            assert tuple(d["name"] for d in kids) == want, parent["name"]
            assert sum(d["dur_ns"] for d in kids) <= parent["dur_ns"]
            assert not any(d["dur_ns"] < 0 for d in kids)
        for d in spans:
            if d["name"] == "delta.fetch_wait":
                assert d["wait"] == "device" and d["args"]["windows"] >= 1
            elif d["name"] == "delta.encode":
                assert d["args"]["rows"] > 0 and "native" in d["args"]
                assert d["args"]["bytes"] > 0 or not d["args"]["native"]
            elif d["name"] == "delta.stage":
                stage = d["args"]
        # the last delta's parts add up to its rows
        assert stage["puts"] + stage["deletes"] > 0
        encode = next(d for d in children_of(spans, writer)
                      if d["name"] == "segment.encode")
        assert {k: encode["args"][k] for k in ("rows", "bytes", "native")} \
            == {k: writer["args"][k] for k in ("rows", "bytes", "native")}
        put, manifest = [next(d for d in spans if d["name"] == n)
                         for n in ("segment.put", "manifest.write")]
        assert put["args"]["bytes"] == writer["args"]["bytes"]
        assert manifest["args"]["segments"] >= 1
        pending, apply = [next(d for d in spans if d["name"] == n)
                          for n in ("commit.pending", "store.apply")]
        # the rows handed on, a key once an epoch that wrote it; the
        # segment keeps the last of each
        assert pending["args"]["rows"] >= writer["args"]["rows"]
        assert apply["args"]["rows"] >= pending["args"]["rows"]
        # ISSUE 38: every row of every state table and MV reaches all
        # three packed; the sources' split offsets, one insert() a split,
        # are the dict layers
        feeds = pending["args"]["dict_tables"]
        assert 1 <= len(feeds) <= 2
        packed = pending["args"]["packed"]
        assert packed == encode["args"]["packed"] == apply["args"]["packed"]
        assert len(feeds) <= pending["args"]["rows"] - packed <= 4 < packed
    assert checkpoints == 2


def test_no_new_span_folds_into_a_ledger_stage(cell_run):
    """(d) a child span carries no ``stage``: the ledger's stages read
    what the spans that carried them before read, and only those."""
    _cell, _s, by_epoch, history, _log = cell_run
    carried = {"collect": ("barrier.collect",),
               "commit": ("checkpoint.commit",),
               "storage_commit": ("DurableStateStore.commit",),
               "inject": ("barrier.inject",),
               "source_feed": ("source.feed",)}
    for epoch, spans in by_epoch.items():
        stages = history[epoch]["stages"]
        for stage, names in carried.items():
            ms = sum(d["dur_ns"] for d in spans if d["name"] in names) / 1e6
            assert stages.get(stage, 0.0) == pytest.approx(ms, abs=1e-6), \
                (epoch, stage)
        ms = sum(d["dur_ns"] for d in spans
                 if d["name"].endswith(".state_delta")
                 or d["name"] == "cosched.restack") / 1e6
        assert stages.get("state_delta", 0.0) == pytest.approx(ms, abs=1e-6)
        waits = [d["name"] for d in spans if d["wait"] == "device"]
        assert ("delta.fetch_wait" in waits) == history[epoch]["checkpoint"]


@pytest.mark.parametrize("writer", ["prepare", "commit_async", "fold"])
def test_segment_spans_from_every_writer_of_a_segment(writer, tmp_path):
    """(e) 2PC's prepare, the deferred commit's thread and the background
    fold write their segments through the same parts; a thread with no
    span around it records them without an epoch."""
    import threading
    from risingwave_tpu.storage.checkpoint import DurableStateStore
    store = DurableStateStore(str(tmp_path / "db"))
    rows = {bytes([i]) * 4: bytes([i]) * 16 for i in range(32)}
    GLOBAL_TRACE.clear()
    if writer == "fold":
        for epoch in (1, 2, 3):
            store.ingest(7, epoch, rows, set())
            store.commit(epoch)
        GLOBAL_TRACE.clear()
        t = threading.Thread(target=store.log.compact)
        t.start()
        t.join()
        got = [s for s in GLOBAL_TRACE.snapshot()
               if s.name in SEGMENT_PARTS]
        assert [s.name for s in got] == ["segment.encode", "segment.put"]
        assert all(s.epoch is None and s.parent is None for s in got)
        assert got[0].args["rows"] == 32 and got[0].args["native"] in (0, 1)
        assert tracing.epoch_spans() == {}
        assert len(store.log._read_manifest()["segments"]) == 1
        return
    store.ingest(7, 1, rows, {b"gone"})
    if writer == "prepare":
        store.prepare(1)
        store.commit(1)
    else:
        store.commit_async(1)
        store.join_commits()
    spans = tracing.epoch_spans()[1]
    by_id = {d["id"]: d for d in spans}
    parent = f"DurableStateStore.{writer}"
    got = [d for d in spans if d["name"] in SEGMENT_PARTS]
    assert [d["name"] for d in got] == list(SEGMENT_PARTS)
    assert {by_id[d["parent"]]["name"] for d in got} == {parent}
    assert sum(d["dur_ns"] for d in got) <= by_id[got[0]["parent"]]["dur_ns"]
    (pending,) = [d for d in spans if d["name"] == "commit.pending"]
    (apply,) = [d for d in spans if d["name"] == "store.apply"]
    assert pending["args"]["rows"] == apply["args"]["rows"] == 33
    assert store.committed_epoch == 1
