"""RisingWave's nexmark q104 (ISSUE 37): the auctions that are NOT IN the
set of auctions with fewer than 20 bids — a HAVING filter on an agg's
changelog into the right side of a null-aware LEFT ANTI hash join, rows
inserted and retracted between two checkpoints. The deployment is the
benchmark's configuration at its tiny sizes through the benchmark's own
``System``; the reference and a brute-force dict count say what the MV
has to hold."""

import asyncio
import collections
import json
import os
import sys

import jax
import numpy as np
import pytest

from risingwave_tpu.common import INT64, Schema, chunk_to_rows, make_chunk
from risingwave_tpu.common import tracing
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, StreamChunk,
)
from risingwave_tpu.expr import col
from risingwave_tpu.ops import JoinType
from risingwave_tpu.stream import Barrier, HashJoinExecutor, MockSource
from risingwave_tpu.stream.hash_join import EMIT_COUNTS, N_STATS
from risingwave_tpu.stream.project import FilterExecutor, ProjectExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark import system  # noqa: E402

SEED = 3_700_000_043            # more than 32 signed bits hold
BARRIERS = 30
UNDER = 20


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark-q104.json")) as f:
        return bench_run.tiny_sizes(json.load(f))


@pytest.fixture(scope="module")
def ref():
    return bench_run.load_by_name("reference", "q104_host_stream")


def brute_force(ref, config: dict, seed: int, barriers: int) -> list:
    """q104 by a python dict over the replayed rows, one bid at a time."""
    items, bids = {}, collections.Counter()
    for aid, item, bid_auction, _price in ref.streams(config, seed, barriers):
        items.update(zip(aid.tolist(), item.tolist()))
        for a in bid_auction.tolist():
            bids[a] += 1
    return sorted((f"item-{i}", a) for a, i in items.items()
                  if not 0 < bids[a] < UNDER)


def join_spans() -> list:
    return [d["args"] for _e, spans in sorted(tracing.epoch_spans().items())
            for d in spans if d["name"] == "HashJoin.chunks"]


def test_the_mv_equals_reference_and_brute_force_over_30_barriers(
        config, ref, tmp_path):
    """Thirty barriers, the MV closed and opened again on the same
    ``data_dir`` after the 17th (eight barriers past a commit: what was
    put and retracted since is lost and replayed): exactly the
    reference's rows, which are the brute-force count's; both transition
    directions at work, and every one of them counted by its direction."""
    sut = system.System(config, str(tmp_path / "data"), SEED)
    sut.create()
    first = sut.session.epoch + 1
    tracing.GLOBAL_TRACE.clear()        # the ring outlives a session
    for _ in range(17):
        sut.barrier()
    assert sorted(sut.read_back()) == brute_force(ref, config, SEED, 17)
    counted = join_spans()
    committed = sut.committed_epoch() - first + 1        # barriers durable
    sut.close()

    again = system.System(config, str(tmp_path / "data"), SEED)
    # the sources resume after the last commit, the MV is what it held
    assert 0 < committed < 17
    assert sorted(again.read_back()) == brute_force(ref, config, SEED,
                                                    committed)
    for _ in range(BARRIERS - committed):
        again.barrier()
    rows = again.read_back()
    history = again.barrier_history()
    again.close()

    exp = ref.expected(config, SEED, BARRIERS)
    assert ref.compare(exp, rows) == {
        "rows_wrong": 0, "unbid_rows_off": 0, "events_off": 0,
        "rows_expected": len(rows)}
    assert sorted(rows) == brute_force(ref, config, SEED, BARRIERS)
    assert len({r[1] for r in rows}) == len(rows) > 100        # never two
    assert sum(h["checkpoint"] for h in history) == 2
    # the reference's own account of the mechanism
    assert exp["unbid_rows"] > 0 and exp["retracted"] > 0
    assert len(exp["hot_ids"]) > 0                 # someone reached 20

    assert len(counted) == 17
    for args in counted:
        assert args["matched"] + args["unmatched"] == args["transitions"]
        assert args["rows_out"] == args["null_padded_out"] \
            + args["transitions"]
        assert args["bucket_width"] == 1
        assert args["rewinds"] == args["grows"] == 0
    assert sum(a["matched"] for a in counted) > 0
    assert sum(a["unmatched"] for a in counted) > 0
    # an auction's row is taken back more often than it returns
    assert sum(a["matched"] for a in counted) \
        > sum(a["unmatched"] for a in counted)


# -- the NOT IN check rides in the packed stats --------------------------------

L_SCHEMA = Schema.of(("k", INT64), ("a", INT64))
R_SCHEMA = Schema.of(("k", INT64),)
CAP = 64


def lchunk(rows):
    return make_chunk(L_SCHEMA, rows, capacity=CAP)


def rchunk(rows, ops=None):
    return make_chunk(R_SCHEMA, rows, ops=ops, capacity=CAP)


def anti_join(left_msgs, right_msgs, null_aware=True):
    return HashJoinExecutor(
        MockSource(L_SCHEMA, left_msgs), MockSource(R_SCHEMA, right_msgs),
        [0], [0], JoinType.LEFT_ANTI, key_capacity=64, bucket_width=1,
        out_capacity=32, null_aware_anti=null_aware)


def drain(ex, fetched=None):
    """Run the executor to its end: ``[(op, row)]`` and how many barriers
    it passed on; ``fetched`` collects every packed-stats fetch."""
    if fetched is not None:
        inner = ex._fetch_stats

        def fetch_stats(packed):
            fetched.append(np.asarray(packed))
            return inner(packed)

        ex._fetch_stats = fetch_stats

    async def go():
        out, barriers = [], 0
        async for m in ex.execute():
            if isinstance(m, StreamChunk):
                out.extend(chunk_to_rows(m, ex.schema, with_ops=True))
            elif isinstance(m, Barrier):
                barriers += 1
        return out, barriers

    return asyncio.run(go())


def epoch_msgs(*epochs_):
    left, right = [Barrier.new(1)], [Barrier.new(1)]
    for e, (lcs, rcs) in enumerate(epochs_, 2):
        left += [*lcs, Barrier.new(e)]
        right += [*rcs, Barrier.new(e)]
    return left, right


def test_a_null_build_key_is_counted_on_the_device_and_raises_at_the_fetch():
    """Three build-side chunks of one epoch, the second with a NULL key:
    ONE stats fetch for the epoch, its last slot the NULL keys of each
    chunk, and the same error as before — raised before the epoch's
    barrier is passed on."""
    l, r = epoch_msgs(([lchunk([(1, 100), (2, 200)])],
                       [rchunk([(1,)]), rchunk([(3,), (None,)]),
                        rchunk([(4,)])]))
    ex = anti_join(l, r)
    fetched = []
    with pytest.raises(RuntimeError, match=r"NULL value in NOT IN \(SELECT"):
        drain(ex, fetched)
    (rows,) = fetched
    # four chunks' vectors, the stack padded to its fixed length
    assert rows.shape == (ex.emit_batch, N_STATS + 1)
    assert not rows[4:].any()
    assert sorted(rows[:4, N_STATS].tolist()) == [0, 0, 0, 1]
    # the chunk with the NULL is the one that took two rows in
    assert rows[rows[:, N_STATS] == 1][0, 5] == 2


def test_a_scanned_batch_of_build_side_chunks_carries_the_count_too():
    """``_consume_batch``: three build-side chunks arrive as ONE
    ``ChunkBatch`` and are scanned in one dispatch; the NULL of the
    second is in the stacked stats of that dispatch, and raises."""
    from risingwave_tpu.common.chunk import stack_chunks
    rights = [rchunk([(1,)]), rchunk([(3,), (None,)]), rchunk([(4,)])]
    l, r = epoch_msgs(([lchunk([(1, 100), (2, 200)])], [stack_chunks(rights)]))
    ex = anti_join(l, r)
    fetched = []
    with pytest.raises(RuntimeError, match=r"NULL value in NOT IN \(SELECT"):
        drain(ex, fetched)
    assert ex.stats.batches_in == 1
    assert fetched[-1].shape == (3, N_STATS + 1)
    assert fetched[-1][:, N_STATS].tolist() == [0, 1, 0]
    # without the NULL the same batch retracts auction 1 and passes on
    l, r = epoch_msgs(([lchunk([(1, 100), (2, 200)])], []),
                      ([], [stack_chunks([rchunk([(1,)]), rchunk([(3,)])])]))
    out, barriers = drain(anti_join(l, r))
    assert barriers == 3
    assert out == [(OP_INSERT, (1, 100)), (OP_INSERT, (2, 200)),
                   (OP_DELETE, (1, 100))]


def test_a_plan_that_is_not_null_aware_carries_no_null_count():
    l, r = epoch_msgs(([lchunk([(1, 100)])], [rchunk([(None,)])]))
    ex = anti_join(l, r, null_aware=False)
    fetched = []
    out, barriers = drain(ex, fetched)          # NOT EXISTS: no error
    assert barriers == 2 and out == [(OP_INSERT, (1, 100))]
    assert [rows.shape[1] for rows in fetched] == [N_STATS]


def test_the_streaming_anti_join_syncs_once_an_epoch_not_once_a_chunk(
        monkeypatch):
    """No ``bool(device array)`` and no stats fetch of the NOT IN check's
    own: an epoch with three build-side chunks costs the host exactly
    the syncs an epoch with one costs."""
    array_type = type(jax.numpy.zeros(1))
    inner = array_type.__bool__
    calls = []

    def counting_bool(self):
        calls.append(1)
        return inner(self)

    def syncs(right_chunks):
        l, r = epoch_msgs(([lchunk([(1, 100), (2, 200)])], []),
                          ([], right_chunks))
        ex = anti_join(l, r)
        fetched = []
        with monkeypatch.context() as m:
            m.setattr(array_type, "__bool__", counting_bool)
            calls.clear()
            out, barriers = drain(ex, fetched)
            n = len(calls)
        assert barriers == 3
        return n, len(fetched), out

    one = syncs([rchunk([(1,), (3,), (4,)])])
    three = syncs([rchunk([(1,)]), rchunk([(3,)]), rchunk([(4,)])])
    assert one[:2] == three[:2]
    assert one[0] > 0                           # the barrier's flag checks
    assert three[1] == 2                        # one fetch an epoch
    assert one[2] == three[2] == [(OP_INSERT, (1, 100)),
                                  (OP_INSERT, (2, 200)),
                                  (OP_DELETE, (1, 100))]


# -- HAVING on a changelog: Filter's pairs, Project's equal rows ---------------

AGG_SCHEMA = Schema.of(("auction", INT64), ("n", INT64))


def having_under_20(msgs):
    """The subquery's tail as the planner builds it: Filter (n < 20) over
    the agg's flush, Project (auction) over that."""
    flt = FilterExecutor(MockSource(AGG_SCHEMA, msgs),
                         col(1, INT64) < UNDER)
    return ProjectExecutor(flt, [col(0, INT64)], ["auction"])


def test_filter_degrades_a_broken_pair_and_project_makes_equal_pairs():
    """One flush of the COUNT agg: group 1 passes 20 (its U+ fails the
    predicate: a plain Delete), group 2 comes under it (its U- fails: a
    plain Insert; no COUNT does that, a retracting source would), group 3
    stays under (the pair survives and, projected on the key, is a U-/U+
    of EQUAL rows), group 4 is new."""
    U_, UP = OP_UPDATE_DELETE, OP_UPDATE_INSERT
    flush = make_chunk(
        AGG_SCHEMA,
        [(1, 19), (1, 20), (2, 25), (2, 19), (3, 5), (3, 6), (4, 2)],
        ops=[U_, UP, U_, UP, U_, UP, OP_INSERT], capacity=CAP)
    ex = having_under_20([Barrier.new(1), flush, Barrier.new(2)])

    async def go():
        return [chunk_to_rows(m, ex.schema, with_ops=True)
                async for m in ex.execute() if isinstance(m, StreamChunk)]

    (rows,) = asyncio.run(go())
    assert rows == [(OP_DELETE, (1,)), (OP_INSERT, (2,)),
                    (U_, (3,)), (UP, (3,)), (OP_INSERT, (4,))]
    assert ex.input._step.__wrapped__.__name__ == "filter_step"


def test_the_anti_join_takes_the_degraded_pairs_as_delete_and_insert():
    """The same flush into the join's right side, after an epoch that put
    groups 1 and 3 into the set: auction 1 comes back (1 -> 0), auction 2
    is retracted (0 -> 1), the equal pair of auction 3 returns its row
    and retracts it again — the view is where it was, the lane too — and
    auction 4 is retracted."""
    U_, UP = OP_UPDATE_DELETE, OP_UPDATE_INSERT
    auctions = [(a, 100 * a) for a in (1, 2, 3, 4, 5)]
    left = [Barrier.new(1), lchunk(auctions), Barrier.new(2), Barrier.new(3)]
    right = having_under_20([
        Barrier.new(1),
        make_chunk(AGG_SCHEMA, [(1, 19), (3, 5)], capacity=CAP),
        Barrier.new(2),
        make_chunk(AGG_SCHEMA,
                   [(1, 19), (1, 20), (2, 25), (2, 19), (3, 5), (3, 6),
                    (4, 2)],
                   ops=[U_, UP, U_, UP, U_, UP, OP_INSERT], capacity=CAP),
        Barrier.new(3)])
    ex = HashJoinExecutor(MockSource(L_SCHEMA, left), right, [0], [0],
                          JoinType.LEFT_ANTI, key_capacity=64,
                          bucket_width=1, out_capacity=32,
                          null_aware_anti=True)
    fetched = []
    out, barriers = drain(ex, fetched)
    assert barriers == 3
    shown = collections.Counter()
    for op, row in out:
        shown[row] += 1 if op in (OP_INSERT, UP) else -1
    assert {row for row, n in shown.items() if n} == {(1, 100), (5, 500)}
    assert all(n in (0, 1) for n in shown.values())
    # a step runs its deletes before its inserts
    assert out[-5:] == [(OP_INSERT, (1, 100)), (OP_INSERT, (3, 300)),
                        (OP_DELETE, (2, 200)), (OP_DELETE, (3, 300)),
                        (OP_DELETE, (4, 400))]
    last = fetched[-1]
    assert last[:, :4].sum() == 0               # no overflow: W stays 1
    matched, unmatched = (last[:, 6 + EMIT_COUNTS.index(name)].sum()
                          for name in ("matched", "unmatched"))
    assert (matched, unmatched) == (3, 2)
    assert ex.core.W == 1 and not np.asarray(ex.state.right.lane_overflow)
