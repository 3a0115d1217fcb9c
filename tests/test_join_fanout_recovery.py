"""The fan-out join side against a plain nested-loop join, continued
(``tests/test_join_fanout.py`` has the helpers and every join type with a
one-row side): both sides fanning out, a checkpoint and a recovery in
between, a side unique on its key that keeps the bucket arena, and the
plan's rule (`frontend/build.py`)."""

import asyncio
import collections

import numpy as np
import pytest

from risingwave_tpu.common import StreamChunk, chunk_to_rows
from risingwave_tpu.ops import JoinType
from risingwave_tpu.storage import MemoryStateStore, StateTable
from risingwave_tpu.stream import Barrier, HashJoinExecutor, MockSource

from test_join_fanout import L_SCHEMA, R_SCHEMA, Side, apply, check, drive, \
    script


@pytest.mark.parametrize("jt", [JoinType.INNER, JoinType.FULL_OUTER,
                                JoinType.RIGHT_ANTI], ids=lambda j: j.value)
def test_both_sides_fan_out(jt):
    """Two to five right rows a key: both sides may fan out; the left
    one does, its probes from the right meet several rows of one key in
    one pass (the ranks across a probe block)."""
    epochs = script(11, 4, many_right=True)
    per_epoch, ex = drive(epochs, jt, many_right=True)
    check(epochs, per_epoch, jt, many_right=True)
    assert ex.core.layout["left"].fanout


@pytest.mark.parametrize("jt", [JoinType.LEFT_OUTER, JoinType.RIGHT_SEMI],
                         ids=lambda j: j.value)
def test_checkpoint_and_recovery_of_a_fanout_side(jt):
    """A checkpoint after three epochs, a new executor on what the store
    holds (recovery replays both tables, the left side moves to the
    fan-out arena again and the degrees are rebuilt), three more epochs:
    the MV stays the nested loop's, and the state tables hold the live
    rows."""
    epochs = script(5, 6, many_right=False)
    store = MemoryStateStore()
    first, ex1 = drive(epochs[:3], jt, False, store, epoch0=1, stop=True)
    store.commit(4)
    assert ex1.core.layout["left"].fanout
    mv = check(epochs[:3], first, jt, False)
    lt = StateTable(store, 1, L_SCHEMA, [0, 1])
    assert sorted(lt.scan_all(), key=str) == sorted(epochs[2][2].values(),
                                                   key=str)
    second, ex2 = drive(epochs[3:], jt, False, store, epoch0=4, stop=True)
    assert ex2.core.layout["left"].fanout
    check(epochs[3:], second, jt, False, mv)
    store.commit(7)
    lt = StateTable(store, 1, L_SCHEMA, [0, 1])
    assert sorted(lt.scan_all(), key=str) == sorted(epochs[5][2].values(),
                                                   key=str)


def test_a_side_unique_on_its_key_keeps_the_bucket_arena():
    """Without a row key (the plan's word that a join key holds at most one
    live row of the side) a side never moves: both stay bucket arenas at
    width 1 over update pairs, and nothing grows."""
    rng = np.random.default_rng(3)
    left, right = Side(rng, 0), Side(rng, 0)
    epochs = []
    for _ in range(3):
        for k in range(40):
            for side in (left, right):
                if k in side.live:
                    side.update(k, (k, int(rng.integers(0, 9))))
                else:
                    side.insert((k, int(rng.integers(0, 9))))
        epochs.append((left.chunks(R_SCHEMA), right.chunks(R_SCHEMA)))
    lm, rm = [Barrier.new(1)], [Barrier.new(1)]
    for n, (lcs, rcs) in enumerate(epochs):
        lm.extend(lcs)
        rm.extend(rcs)
        lm.append(Barrier.new(n + 2))
        rm.append(Barrier.new(n + 2))
    ex = HashJoinExecutor(MockSource(R_SCHEMA, lm), MockSource(R_SCHEMA, rm),
                          [0], [0], JoinType.INNER, key_capacity=64,
                          bucket_width=1, out_capacity=64)

    async def drain():
        mv = collections.Counter()
        async for m in ex.execute():
            if isinstance(m, StreamChunk):
                apply(mv, chunk_to_rows(m, ex.schema, with_ops=True))
        return mv

    mv = asyncio.run(drain())
    assert mv == collections.Counter(
        left.live[k] + right.live[k] for k in range(40))
    assert not any(lay.fanout for lay in ex.core.layout.values())
    assert (ex.core.W, ex.core.capacity) == (1, 64)


def test_row_key_of_a_plan_side():
    """The plan's rule (``frontend/build.py``): a side whose stream key lies inside its join
    key keeps the bucket arena; any other known stream key gives the row
    key join keys ++ the rest of the stream key."""
    from risingwave_tpu.frontend.build import fanout_row_key
    assert fanout_row_key([2], [2]) is None          # GROUP BY the key
    assert fanout_row_key([0, 1], [1]) is None
    assert fanout_row_key([1], []) is None           # stream key unknown
    assert fanout_row_key([2], [2, 0]) == (2, 0)     # q5's AuctionBids
    assert fanout_row_key([0], [3]) == (0, 3)        # a source's rows
