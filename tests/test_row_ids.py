"""The hidden ``_row_id`` of a source's rows, end to end (ISSUE 36): made
where the chunks are staged, from a host sequence the feed owns — unique
and serial per source, continued above every id handed out before a
close, contiguous over a tick that failed half way. The staging arithmetic
itself is held to the parent's step in ``tests/test_chunk.py``."""

import numpy as np
import pytest

from risingwave_tpu.common.chunk import RowIdSequence, make_chunk
from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend.runtime import QueueSource
from risingwave_tpu.stream.metrics import iter_executors

ROWS = 64
CHUNKS = 4
BID_DDL = f"""CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,
channel VARCHAR, url VARCHAR, date_time TIMESTAMP, extra VARCHAR)
WITH (connector='nexmark', nexmark_table='bid', rows_per_chunk='{ROWS}')"""
#: keyed by the hidden _row_id: a duplicate id would overwrite a row
MV = "CREATE MATERIALIZED VIEW m AS SELECT auction, price FROM bid"
SEQ_MASK = (1 << 48) - 1


def open_session(data_dir, **kw) -> Session:
    return Session(data_dir=data_dir, seed=36, source_chunk_capacity=ROWS,
                   chunks_per_tick=CHUNKS, checkpoint_frequency=2, **kw)


def row_ids_of(s: Session, name: str = "m") -> list:
    """The MV's hidden ``_row_id`` column, from wherever the table lives."""
    s._drain_inflight()
    mv = s.catalog.mvs[name]
    if s._mv_worker(name) is not None:
        rows = s._remote_scan(name, mv.schema)
    else:
        rows = s.jobs[name].pipeline.scan_all()
    (pk,) = mv.pk                 # the source's _row_id, carried as _pk0
    assert pk == mv.n_visible
    return sorted(int(r[pk]) for r in rows)


def assert_serial(ids: list, rows: int) -> int:
    """Unique, one shard, ``seq`` = 0 .. rows-1. -> the shard."""
    assert len(ids) == len(set(ids)) == rows
    shards = {i >> 48 for i in ids}
    assert len(shards) == 1
    assert [i & SEQ_MASK for i in ids] == list(range(rows))
    return shards.pop()


@pytest.mark.parametrize("where", ["session", "worker"])
def test_ids_continue_above_every_id_of_a_closed_session(where, tmp_path):
    """ISSUE 36 (c): checkpointed, closed and reopened on its ``data_dir``
    the source goes on above every id in the MV — no duplicate, the row
    count exact — on the session's feed and on ``worker/host.py``'s."""
    data_dir = str(tmp_path / where)
    kw = {"workers": 1} if where == "worker" else {}
    s = open_session(data_dir, **kw)
    try:
        s.run_sql(BID_DDL)
        s.run_sql(MV)
        if where == "session":
            # the plan's first executor over the queue is Project: the ids
            # ride in with the chunks
            (feed,) = s.feeds
            assert isinstance(feed.row_ids, RowIdSequence)
            plan = list(iter_executors(s.jobs["m"].pipeline))
            assert [ex.identity for ex in plan if not isinstance(
                ex, QueueSource)] == ["Materialize", "Project"]
        for _ in range(4):
            s.tick()
        s.flush()
        before, fed = row_ids_of(s), 4 * CHUNKS * ROWS
        shard = assert_serial(before, fed)
    finally:
        s.close()
    s = open_session(data_dir, **kw)
    try:
        assert row_ids_of(s) == before
        for _ in range(3):
            s.tick()
        s.flush()
        after = row_ids_of(s)
        assert after[:fed] == before
        assert min(after[fed:]) > max(before)
        assert assert_serial(after, fed + 3 * CHUNKS * ROWS) == shard
    finally:
        s.close()


def test_a_failed_draw_leaves_no_gap_and_no_duplicate(tmp_path):
    """ISSUE 36 (d): the third draw of a barrier raises — the two chunks
    drawn before it are queued with their ids, the retried tick goes on
    from the id after them."""
    s = open_session(str(tmp_path / "flaky"))
    try:
        s.run_sql(BID_DDL)
        s.run_sql(MV)
        s.tick()
        (feed,) = s.feeds
        real, draws = feed.generator, []

        def flaky():
            draws.append(None)
            if len(draws) == 3:
                raise OSError("fetch failed, out of retries")
            return real()
        feed.generator = flaky
        with pytest.raises(OSError):
            s.tick()
        assert feed.row_ids.next == (CHUNKS + 2) * ROWS
        s.tick()
        assert feed.row_ids.next == (2 * CHUNKS + 2) * ROWS
        s.tick(generate=False)              # nothing drawn: no id taken
        assert feed.row_ids.next == (2 * CHUNKS + 2) * ROWS
        s.tick()
        s.flush()
        assert_serial(row_ids_of(s), (3 * CHUNKS + 2) * ROWS)
    finally:
        s.close()


def test_two_sources_take_ids_of_their_own_shards(tmp_path):
    """Each source leaf has a sequence of its own under its own shard
    prefix, so a join's two inputs never share an id."""
    s = open_session(str(tmp_path / "two"))
    try:
        s.run_sql(BID_DDL)
        s.run_sql(MV)
        s.run_sql(MV.replace(" m ", " m2 "))
        for _ in range(2):
            s.tick()
        s.flush()
        one, two = row_ids_of(s, "m"), row_ids_of(s, "m2")
        assert assert_serial(one, 2 * CHUNKS * ROWS) \
            != assert_serial(two, 2 * CHUNKS * ROWS)
        assert [f.row_ids.next for f in s.feeds] == [2 * CHUNKS * ROWS] * 2
    finally:
        s.close()


@pytest.mark.parametrize("relation", ["source", "table"])
def test_chunks_already_on_the_device_get_their_ids_in_one_step(relation):
    """What reaches a queue ALREADY on the device — a push into a
    reader-less source, a row-id table's INSERT — goes through the one
    merged step: ``RowIdGen`` sits over the queue because the feed has no
    reader, and only visible rows take ids."""
    s = Session()
    try:
        if relation == "source":
            s.run_sql("CREATE SOURCE e (g BIGINT, v BIGINT)")
            s.run_sql("CREATE MATERIALIZED VIEW w AS SELECT g, v FROM e")
            (feed,) = s.feeds
            assert feed.reader is None and feed.row_ids is None
            schema = s.catalog.sources["e"].schema
            chunk = make_chunk(schema, [(1, 10), (2, 20), (3, 30)], capacity=4)
            feed.queue.push(chunk.with_vis(chunk.vis.at[1].set(False)))
            feed.queue.push(make_chunk(schema, [(4, 40)], capacity=4))
            s.tick(generate=False)
            job, want = s.jobs["w"], [(1, 10), (3, 30), (4, 40)]
        else:
            s.run_sql("CREATE TABLE t (g BIGINT, v BIGINT)")
            s.run_sql("INSERT INTO t VALUES (1, 10), (3, 30)")
            s.tick(generate=False)
            s.run_sql("INSERT INTO t VALUES (4, 40)")
            s.tick(generate=False)
            job, want = s.jobs["t"], [(1, 10), (3, 30), (4, 40)]
        over_queue = [ex for ex in iter_executors(job.pipeline)
                      if isinstance(getattr(ex, "input", None), QueueSource)]
        assert [ex.identity for ex in over_queue] == ["RowIdGen"]
        rows = sorted(job.pipeline.scan_all(), key=lambda r: r[2])
        assert [tuple(r[:2]) for r in rows] == want
        ids = np.array([r[2] for r in rows])
        assert (ids & SEQ_MASK).tolist() == [0, 1, 2]
        assert len(set((ids >> 48).tolist())) == 1
    finally:
        s.close()
