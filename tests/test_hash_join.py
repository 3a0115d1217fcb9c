"""HashJoin semantics — golden cases mirroring the reference's join unit
tests (reference: src/stream/src/executor/hash_join.rs:1552-3398): insert /
delete / update flows for inner, outer, semi, anti; degree transitions with
duplicate keys in one chunk; non-equi conditions; null join keys."""

import asyncio

import pytest

from risingwave_tpu.common import INT64, Schema, chunk_to_rows, make_chunk
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
)
from risingwave_tpu.expr import col
from risingwave_tpu.ops import JoinType
from risingwave_tpu.storage import MemoryStateStore, StateTable
from risingwave_tpu.stream import Barrier, HashJoinExecutor, MockSource

L_SCHEMA = Schema.of(("k", INT64), ("a", INT64))
R_SCHEMA = Schema.of(("k", INT64), ("b", INT64))

CAP = 64  # small chunks keep CPU-sim compiles fast


def lchunk(rows, ops=None):
    return make_chunk(L_SCHEMA, rows, ops=ops, capacity=CAP)


def rchunk(rows, ops=None):
    return make_chunk(R_SCHEMA, rows, ops=ops, capacity=CAP)


def run_join(left_msgs, right_msgs, join_type=JoinType.INNER, **kw):
    """Drive a join over scripted epochs; returns [(op, row), ...]."""
    kw.setdefault("key_capacity", 64)
    kw.setdefault("bucket_width", 4)
    kw.setdefault("out_capacity", 32)
    ex = HashJoinExecutor(
        MockSource(L_SCHEMA, left_msgs), MockSource(R_SCHEMA, right_msgs),
        [0], [0], join_type, **kw)

    async def drain():
        out = []
        async for m in ex.execute():
            from risingwave_tpu.common import StreamChunk
            if isinstance(m, StreamChunk):
                out.extend(chunk_to_rows(m, ex.schema, with_ops=True))
        return out

    return asyncio.run(drain()), ex


def epochs(*sides_per_epoch):
    """Build aligned (left_msgs, right_msgs): each arg is (left_chunks,
    right_chunks) for one epoch."""
    left, right = [], []
    e = 1
    left.append(Barrier.new(e)); right.append(Barrier.new(e))
    for lcs, rcs in sides_per_epoch:
        left.extend(lcs); right.extend(rcs)
        e += 1
        left.append(Barrier.new(e)); right.append(Barrier.new(e))
    return left, right


def test_inner_insert_then_match():
    l, r = epochs(
        ([lchunk([(1, 100), (2, 200)])], []),
        ([], [rchunk([(1, 10), (3, 30)])]),
    )
    rows, _ = run_join(l, r, JoinType.INNER)
    assert rows == [(OP_INSERT, (1, 100, 1, 10))]


def test_inner_multi_match_and_delete():
    l, r = epochs(
        ([lchunk([(1, 100), (1, 101)])], []),
        ([], [rchunk([(1, 10)])]),
        ([], [rchunk([(1, 10)], ops=[OP_DELETE])]),
    )
    rows, _ = run_join(l, r, JoinType.INNER)
    inserts = [x for x in rows if x[0] == OP_INSERT]
    deletes = [x for x in rows if x[0] == OP_DELETE]
    assert sorted(x[1] for x in inserts) == [(1, 100, 1, 10), (1, 101, 1, 10)]
    assert sorted(x[1] for x in deletes) == [(1, 100, 1, 10), (1, 101, 1, 10)]


def test_left_outer_null_pad_then_retract():
    l, r = epochs(
        ([lchunk([(1, 100)])], []),
        ([], [rchunk([(1, 10)])]),
        ([], [rchunk([(1, 10)], ops=[OP_DELETE])]),
    )
    rows, _ = run_join(l, r, JoinType.LEFT_OUTER)
    assert rows == [
        (OP_INSERT, (1, 100, None, None)),
        (OP_UPDATE_DELETE, (1, 100, None, None)),
        (OP_UPDATE_INSERT, (1, 100, 1, 10)),
        (OP_UPDATE_DELETE, (1, 100, 1, 10)),
        (OP_UPDATE_INSERT, (1, 100, None, None)),
    ]


def test_left_outer_second_match_plain_insert():
    """Second right row with the same key emits a plain Insert, not U-/U+
    (degree transition only fires on 0 -> 1)."""
    l, r = epochs(
        ([lchunk([(1, 100)])], []),
        ([], [rchunk([(1, 10), (1, 11)])]),
    )
    rows, _ = run_join(l, r, JoinType.LEFT_OUTER)
    assert rows[0] == (OP_INSERT, (1, 100, None, None))
    assert (OP_UPDATE_DELETE, (1, 100, None, None)) in rows
    pair_ops = [op for op, row in rows[1:]]
    assert pair_ops.count(OP_UPDATE_DELETE) == 1
    assert pair_ops.count(OP_UPDATE_INSERT) == 1
    assert pair_ops.count(OP_INSERT) == 1
    assert (OP_INSERT, (1, 100, 1, 11)) in rows or (OP_INSERT, (1, 100, 1, 10)) in rows


def test_right_outer_mirrors_left():
    l, r = epochs(
        ([], [rchunk([(7, 70)])]),
        ([lchunk([(7, 700)])], []),
    )
    rows, _ = run_join(l, r, JoinType.RIGHT_OUTER)
    assert rows == [
        (OP_INSERT, (None, None, 7, 70)),
        (OP_UPDATE_DELETE, (None, None, 7, 70)),
        (OP_UPDATE_INSERT, (7, 700, 7, 70)),
    ]


def test_full_outer_both_sides_pad():
    l, r = epochs(
        ([lchunk([(1, 100)])], [rchunk([(2, 20)])]),
        ([], [rchunk([(1, 10)])]),
    )
    rows, _ = run_join(l, r, JoinType.FULL_OUTER)
    first_epoch = set(x for x in rows[:2])
    assert (OP_INSERT, (1, 100, None, None)) in first_epoch
    assert (OP_INSERT, (None, None, 2, 20)) in first_epoch
    assert rows[2:] == [
        (OP_UPDATE_DELETE, (1, 100, None, None)),
        (OP_UPDATE_INSERT, (1, 100, 1, 10)),
    ]


def test_left_semi():
    l, r = epochs(
        ([lchunk([(1, 100), (2, 200)])], []),
        ([], [rchunk([(1, 10)])]),
        ([], [rchunk([(1, 11)])]),          # second match: no re-emit
        ([], [rchunk([(1, 10)], ops=[OP_DELETE])]),  # still matched by (1,11)
        ([], [rchunk([(1, 11)], ops=[OP_DELETE])]),  # now unmatched
    )
    rows, _ = run_join(l, r, JoinType.LEFT_SEMI)
    assert rows == [
        (OP_INSERT, (1, 100)),
        (OP_DELETE, (1, 100)),
    ]


def test_left_semi_insert_on_matched_side():
    l, r = epochs(
        ([], [rchunk([(1, 10)])]),
        ([lchunk([(1, 100)])], []),
        ([lchunk([(1, 100)], ops=[OP_DELETE])], []),
    )
    rows, _ = run_join(l, r, JoinType.LEFT_SEMI)
    assert rows == [(OP_INSERT, (1, 100)), (OP_DELETE, (1, 100))]


def test_left_anti():
    l, r = epochs(
        ([lchunk([(1, 100), (2, 200)])], []),
        ([], [rchunk([(1, 10)])]),
        ([], [rchunk([(1, 10)], ops=[OP_DELETE])]),
    )
    rows, _ = run_join(l, r, JoinType.LEFT_ANTI)
    assert rows == [
        (OP_INSERT, (1, 100)),
        (OP_INSERT, (2, 200)),
        (OP_DELETE, (1, 100)),
        (OP_INSERT, (1, 100)),
    ]


def test_duplicate_key_batch_degree_transitions():
    """Two same-key right rows in ONE chunk against a degree-0 left row:
    exactly one U-/U+ transition + one plain insert (rank logic)."""
    l, r = epochs(
        ([lchunk([(1, 100)])], []),
        ([], [rchunk([(1, 10), (1, 11)])]),
    )
    rows, _ = run_join(l, r, JoinType.LEFT_OUTER)
    ops = [op for op, _ in rows]
    assert ops == [OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, OP_INSERT]


def test_update_pair_flows_through():
    l, r = epochs(
        ([lchunk([(1, 100)])], [rchunk([(1, 10)])]),
        ([lchunk([(1, 100), (1, 150)],
                 ops=[OP_UPDATE_DELETE, OP_UPDATE_INSERT])], []),
    )
    rows, _ = run_join(l, r, JoinType.INNER)
    assert (OP_INSERT, (1, 100, 1, 10)) in rows
    assert (OP_DELETE, (1, 100, 1, 10)) in rows
    assert (OP_INSERT, (1, 150, 1, 10)) in rows
    # delete emitted before the replacement insert
    assert rows.index((OP_DELETE, (1, 100, 1, 10))) < rows.index(
        (OP_INSERT, (1, 150, 1, 10)))


def test_non_equi_condition():
    # ON l.k = r.k AND l.a < r.b
    cond = col(1, INT64) < col(3, INT64)
    l, r = epochs(
        ([lchunk([(1, 5), (1, 50)])], []),
        ([], [rchunk([(1, 10)])]),
    )
    rows, _ = run_join(l, r, JoinType.INNER, condition=cond)
    assert rows == [(OP_INSERT, (1, 5, 1, 10))]


def test_condition_affects_outer_degrees():
    cond = col(1, INT64) < col(3, INT64)
    l, r = epochs(
        ([lchunk([(1, 50)])], []),
        ([], [rchunk([(1, 10)])]),   # fails condition -> left stays padded
        ([], [rchunk([(1, 99)])]),   # passes -> transition
    )
    rows, _ = run_join(l, r, JoinType.LEFT_OUTER, condition=cond)
    assert rows == [
        (OP_INSERT, (1, 50, None, None)),
        (OP_UPDATE_DELETE, (1, 50, None, None)),
        (OP_UPDATE_INSERT, (1, 50, 1, 99)),
    ]


def test_null_keys_never_match():
    l, r = epochs(
        ([lchunk([(None, 100)])], [rchunk([(None, 10)])]),
    )
    rows_inner, _ = run_join(l, r, JoinType.INNER)
    assert rows_inner == []
    l, r = epochs(
        ([lchunk([(None, 100)])], [rchunk([(None, 10)])]),
    )
    rows_outer, _ = run_join(l, r, JoinType.LEFT_OUTER)
    assert rows_outer == [(OP_INSERT, (None, 100, None, None))]


def test_checkpoint_and_recovery_rebuild_degrees():
    store = MemoryStateStore()
    lt = StateTable(store, 1, L_SCHEMA, [0, 1])
    rt = StateTable(store, 2, R_SCHEMA, [0, 1])
    l, r = epochs(
        ([lchunk([(1, 100)])], [rchunk([(1, 10)])]),
    )
    # run with checkpoint on the closing stop barrier
    l[-1] = Barrier.new(2, checkpoint=True, mutation=l[-1].mutation)
    r[-1] = Barrier.new(2, checkpoint=True, mutation=r[-1].mutation)
    from risingwave_tpu.stream.message import Mutation, MutationKind
    stop = Mutation(MutationKind.STOP)
    l.append(Barrier.new(3, checkpoint=True, mutation=stop))
    r.append(Barrier.new(3, checkpoint=True, mutation=stop))
    rows1, _ = run_join(l, r, JoinType.LEFT_OUTER,
                        left_state_table=lt, right_state_table=rt)
    store.commit(3)
    assert len(list(lt.scan_all())) == 1
    assert len(list(rt.scan_all())) == 1

    # recover into a fresh executor; delete the right row -> retraction,
    # proving degrees were rebuilt
    lt2 = StateTable(store, 1, L_SCHEMA, [0, 1])
    rt2 = StateTable(store, 2, R_SCHEMA, [0, 1])
    l2, r2 = epochs(
        ([], [rchunk([(1, 10)], ops=[OP_DELETE])]),
    )
    rows2, _ = run_join(l2, r2, JoinType.LEFT_OUTER,
                        left_state_table=lt2, right_state_table=rt2)
    assert rows2 == [
        (OP_UPDATE_DELETE, (1, 100, 1, 10)),
        (OP_UPDATE_INSERT, (1, 100, None, None)),
    ]


# -- a lane tombstoned since the last checkpoint is refilled -------------------
#
# Each side is unique on the join key and the bucket width is 1, so the U+
# of an update pair has only the lane its U- tombstoned to land in. The
# durable tier must still see the old row go where its state-table key is
# not the new row's (ops/join_state.py: the graveyard).

REFILL_KEYS = list(range(1, 13))


def _refill_script():
    """Three checkpoint intervals of two epochs over a right side that is
    unique on ``k``: per epoch ``(left_chunks, right_chunks)`` and the
    right side's live rows after it."""
    U_, UP = OP_UPDATE_DELETE, OP_UPDATE_INSERT
    live = {k: 10 * k for k in REFILL_KEYS if k % 4}      # 4, 8, 12: no row
    script = [([lchunk([(k, 100 + k) for k in REFILL_KEYS])],
               [rchunk([(k, b) for k, b in live.items()])])]
    snapshots = [dict(live)]

    def step(pairs=(), deletes=(), inserts=()):
        rows, ops = [], []
        for k in deletes:
            rows.append((k, live.pop(k))); ops.append(OP_DELETE)
        for k, b in pairs:                  # the update pair, adjacent
            rows += [(k, live[k]), (k, b)]; ops += [U_, UP]
            live[k] = b
        for k, b in inserts:
            rows.append((k, b)); ops.append(OP_INSERT)
            live[k] = b
        script.append(([], [rchunk(rows, ops=ops)]))
        snapshots.append(dict(live))

    # interval 1: pairs in the epoch of the first insert's checkpoint
    step(pairs=[(1, 11), (2, 21), (3, 31)], deletes=[5])
    # interval 2: a pair on a refilled lane, a delete refilled an epoch
    # later by ANOTHER row of its key, a first match for a padded row
    step(pairs=[(1, 12), (6, 61)], deletes=[7], inserts=[(5, 55)])
    step(pairs=[(1, 13), (5, 56)], inserts=[(7, 77), (4, 40)])
    # interval 3: the same lane twice in one interval, and a row that
    # stays deleted over the checkpoint
    step(pairs=[(2, 22), (7, 78)], deletes=[9])
    step(pairs=[(2, 23)], deletes=[4], inserts=[(8, 80)])
    return script, snapshots


def _expected_mv(join_type, right_live: dict) -> list:
    if join_type == JoinType.LEFT_SEMI:
        return sorted((k, 100 + k) for k in REFILL_KEYS if k in right_live)
    return sorted((k, 100 + k, k if k in right_live else None,
                   right_live.get(k)) for k in REFILL_KEYS)


def _apply_ops(mv: list, out_rows: list) -> None:
    for op, row in out_rows:
        if op in (OP_INSERT, OP_UPDATE_INSERT):
            mv.append(row)
        else:
            mv.remove(row)          # a retraction of an absent row raises


def _run_interval(epoch0, interval, join_type, store, right_pk):
    """One executor over ``interval`` epochs, opened on what ``store``
    holds (a recovery after the first), closed by a checkpoint + stop."""
    from risingwave_tpu.stream.message import Mutation, MutationKind
    lt = StateTable(store, 1, L_SCHEMA, [0, 1])
    rt = StateTable(store, 2, R_SCHEMA, right_pk)
    left, right = [Barrier.new(epoch0)], [Barrier.new(epoch0)]
    e = epoch0
    for n, (lcs, rcs) in enumerate(interval):
        left.extend(lcs); right.extend(rcs)
        e += 1
        last = n == len(interval) - 1
        stop = Mutation(MutationKind.STOP) if last else None
        left.append(Barrier.new(e, checkpoint=last, mutation=stop))
        right.append(Barrier.new(e, checkpoint=last, mutation=stop))
    rows, ex = run_join(left, right, join_type, bucket_width=1,
                        left_state_table=lt, right_state_table=rt)
    store.commit(e)
    return rows, ex, e, sorted(rt.scan_all())


@pytest.mark.parametrize("join_type", [JoinType.LEFT_OUTER,
                                       JoinType.LEFT_SEMI],
                         ids=["left_outer", "left_semi"])
@pytest.mark.parametrize("right_pk", [[0], [0, 1]],
                         ids=["same_state_key", "different_state_key"])
def test_update_pairs_refill_the_tombstoned_lane_at_width_one(
        join_type, right_pk):
    """``grows`` 0 at ``bucket_width`` 1, the MV exact after every
    checkpoint interval, the state table exact at every checkpoint, and
    every interval but the first runs on an executor REOPENED from the
    checkpoint before it (rows and degrees rebuilt: a wrong degree would
    emit a wrong transition)."""
    script, snapshots = _refill_script()
    intervals = [(script[0:2], snapshots[1]), (script[2:4], snapshots[3]),
                 (script[4:6], snapshots[5])]
    # one executor through all three checkpoints, and a reopen at each
    for reopen in (False, True):
        store, mv, epoch = MemoryStateStore(), [], 1
        spans = ([(sum((i for i, _ in intervals), []), snapshots[5])]
                 if not reopen else intervals)
        for interval, live in spans:
            rows, ex, epoch, durable = _run_interval(
                epoch, interval, join_type, store, right_pk)
            _apply_ops(mv, rows)
            assert (ex.core.W, ex.core.capacity) == (1, 64), "the join grew"
            assert sorted(mv) == _expected_mv(join_type, live)
            assert durable == sorted(live.items())


def test_refill_of_another_state_key_buries_the_old_row():
    """The unit under the test above: the insert of ``(1, 11)`` into the
    lane ``(1, 10)`` was deleted from keeps ``(1, 10)`` for the checkpoint
    where the state-table key is ``(k, b)``, and keeps nothing where it
    is ``k`` alone (the put overwrites the row)."""
    from risingwave_tpu.ops.join_state import JoinCore, join_ckpt_delta_window
    import jax.numpy as jnp
    import numpy as np
    for pk, buried in (((0, 1), 1), ((0,), 0), (None, 1), ((), 0)):
        core = JoinCore(L_SCHEMA, R_SCHEMA, [0], [0], JoinType.LEFT_OUTER,
                        key_capacity=16, bucket_width=1,
                        state_pks=((0, 1), pk))
        st = core.init_state()
        st, _ = core.apply_chunk(st, rchunk([(1, 10), (2, 20)]), side="right")
        st = st.replace(right=st.right.replace(
            ckpt_dirty=jnp.zeros_like(st.right.ckpt_dirty)))   # checkpointed
        st, _ = core.apply_chunk(
            st, rchunk([(1, 10), (1, 11)],
                       ops=[OP_UPDATE_DELETE, OP_UPDATE_INSERT]), side="right")
        side = st.right
        assert not bool(side.lane_overflow)
        assert int(side.grave_n) == buried
        n, valid, occ, tomb, datas, _ = (np.asarray(x) if not isinstance(
            x, tuple) else [np.asarray(y) for y in x]
            for x in join_ckpt_delta_window(side, jnp.int32(0), 16))
        delta = sorted((int(datas[0][i]), int(datas[1][i]),
                        "put" if occ[i] else "del")
                       for i in range(int(n)))
        assert delta == ([(1, 10, "del")] * buried + [(1, 11, "put")])


def test_emit_counts_read_the_outer_join_mechanism_off_the_grid():
    """What ``HashJoin.chunks`` reports as rows_out / null_padded_out /
    transitions / matched / unmatched (``JoinCore.emit_counts``, summed
    on the device)."""
    from risingwave_tpu.ops.join_state import JoinCore

    def counts(core, st, chunk, side):
        st, big = core.apply_chunk(st, chunk, side=side)
        return st, tuple(int(x) for x in core.emit_counts(big, side))

    U_, UP = OP_UPDATE_DELETE, OP_UPDATE_INSERT
    core = JoinCore(L_SCHEMA, R_SCHEMA, [0], [0], JoinType.LEFT_OUTER,
                    key_capacity=16, bucket_width=1)
    st = core.init_state()
    st, got = counts(core, st, lchunk([(1, 100), (2, 200)]), "left")
    assert got == (2, 2, 0, 0, 0)            # two NULL-padded rows
    st, got = counts(core, st, rchunk([(1, 10), (3, 30)]), "right")
    assert got == (2, 0, 1, 1, 0)            # one pair replaces a padded row
    st, got = counts(core, st, rchunk([(1, 10), (1, 11)], ops=[U_, UP]),
                     "right")
    assert got == (4, 0, 2, 1, 1)            # 1 -> 0 and 0 -> 1: two pairs
    st, got = counts(core, st, lchunk([(3, 300)]), "left")
    assert got == (1, 0, 0, 0, 0)            # a plain matched insert
    st, got = counts(core, st, rchunk([(3, 30)], ops=[OP_DELETE]), "right")
    assert got == (2, 0, 1, 0, 1)            # the padded row comes back

    semi = JoinCore(L_SCHEMA, R_SCHEMA, [0], [0], JoinType.LEFT_SEMI,
                    key_capacity=16, bucket_width=2)
    st = semi.init_state()
    st, got = counts(semi, st, lchunk([(1, 100), (2, 200)]), "left")
    assert got == (0, 0, 0, 0, 0)
    st, got = counts(semi, st, rchunk([(1, 10)]), "right")
    assert got == (1, 0, 1, 1, 0)            # the left row appears
    st, got = counts(semi, st, lchunk([(1, 101)]), "left")
    assert got == (1, 1, 0, 0, 0)            # its own row, on the self lane

    anti = JoinCore(L_SCHEMA, R_SCHEMA, [0], [0], JoinType.LEFT_ANTI,
                    key_capacity=16, bucket_width=1)
    st = anti.init_state()
    st, got = counts(anti, st, lchunk([(1, 100), (2, 200)]), "left")
    assert got == (2, 2, 0, 0, 0)            # both own rows: no match yet
    st, got = counts(anti, st, rchunk([(1, 10), (3, 30)]), "right")
    assert got == (1, 0, 1, 1, 0)            # row 1 is retracted
    st, got = counts(anti, st, rchunk([(1, 10)], ops=[OP_DELETE]), "right")
    assert got == (1, 0, 1, 0, 1)            # and comes back
    st, got = counts(anti, st, lchunk([(3, 300)]), "left")
    assert got == (0, 0, 0, 0, 0)            # matched on arrival: never shown


def test_a_new_count_of_pending_chunks_compiles_nothing():
    """The join fetches the packed stats of every chunk applied since its
    last sync in one stacked transfer; the stack is padded to
    ``emit_batch`` vectors, so an epoch with one chunk more than any
    before it (a flush that is one chunk longer: seen on the chip in the
    benchmark's q101 cell) runs no program it has not run before."""
    import jax.monitoring

    def right(epoch, n):                # a key a chunk, none used twice
        return [rchunk([(100 * epoch + i, i)]) for i in range(n)]

    l, r = epochs(([lchunk([(1, 1)])], right(1, 2)),
                  ([lchunk([(2, 2)])], right(2, 2)),
                  ([lchunk([(3, 3)])], right(3, 5)),  # counts not seen yet
                  ([], right(4, 1)))
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    ex = HashJoinExecutor(MockSource(L_SCHEMA, l), MockSource(R_SCHEMA, r),
                          [0], [0], JoinType.LEFT_OUTER, key_capacity=64,
                          bucket_width=1, out_capacity=32)
    per_epoch = []

    async def drain():
        async for m in ex.execute():
            if isinstance(m, Barrier):
                per_epoch.append(len(compiles))

    asyncio.run(drain())
    # everything compiled by the end of the second epoch; the epochs with
    # 6 and with 1 pending chunks add nothing
    assert per_epoch[2] > 0
    assert per_epoch[2] == per_epoch[3] == per_epoch[4]
