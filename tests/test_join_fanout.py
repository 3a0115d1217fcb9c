"""The hash join's fan-out side (ops/join_state.py "Side layouts") against a
plain nested-loop join, on seeded random data: keys with one to thousands
of rows, update pairs and plain deletes, ties at a key's maximum, NULL
keys, a non-equi condition, every join type. Both sides fanning out, a
checkpoint and a recovery in between, and a side unique on its join key
that keeps the bucket arena: ``tests/test_join_fanout_recovery.py``."""

import asyncio
import collections

import numpy as np
import pytest

from risingwave_tpu.common import INT64, Schema, StreamChunk, chunk_to_rows, \
    make_chunk
from risingwave_tpu.common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
)
from risingwave_tpu.expr import col
from risingwave_tpu.ops import JoinType
from risingwave_tpu.storage import StateTable
from risingwave_tpu.stream import Barrier, HashJoinExecutor, MockSource
from risingwave_tpu.stream.message import Mutation, MutationKind

#: the many side: (k, id, v), stream key id; the one side: (k, m), unique on k
L_SCHEMA = Schema.of(("k", INT64), ("id", INT64), ("v", INT64))
R_SCHEMA = Schema.of(("k", INT64), ("m", INT64))
#: the other side many too: (k, rid, m), stream key rid
R2_SCHEMA = Schema.of(("k", INT64), ("rid", INT64), ("m", INT64))

CAP = 128
HOT_ROWS = 1500
#: v >= m over the joined columns (k, id, v, k, m) / (k, id, v, k, rid, m)
GE = {False: col(2, INT64) >= col(4, INT64),
      True: col(2, INT64) >= col(5, INT64)}


class Side:
    """A side's live rows by stream key, and the chunks of one epoch."""

    def __init__(self, rng, pk_col: int):
        self.rng, self.pk_col = rng, pk_col
        self.live: dict = {}
        self.units: list = []          # [(op, row), ...] one unit each

    def insert(self, row):
        self.live[row[self.pk_col]] = row
        self.units.append([(OP_INSERT, row)])

    def delete(self, key):
        self.units.append([(OP_DELETE, self.live.pop(key))])

    def update(self, key, row):
        old = self.live[key]
        self.live[key] = row
        self.units.append([(OP_UPDATE_DELETE, old), (OP_UPDATE_INSERT, row)])

    def chunks(self, schema):
        """The epoch's units in chunks of ``CAP`` rows, a unit never split;
        a row inserted this epoch is never deleted in the same chunk (a
        chunk runs its deletes first)."""
        out, rows, ops, keys = [], [], [], set()
        for unit in self.units:
            key = unit[0][1][self.pk_col]
            if len(rows) + len(unit) > CAP or key in keys:
                out.append(make_chunk(schema, rows, ops=ops, capacity=CAP))
                rows, ops, keys = [], [], set()
            keys.add(key)
            for op, row in unit:
                ops.append(op)
                rows.append(row)
        if rows:
            out.append(make_chunk(schema, rows, ops=ops, capacity=CAP))
        self.units = []
        return out


def script(seed: int, n_epochs: int, many_right: bool):
    """Per epoch ``(left_chunks, right_chunks)`` and the live rows after
    it. Key 0 is hot: it takes ``HOT_ROWS`` left rows over the first
    epochs; keys 1..30 take a few; a NULL key now and then. The right side
    keeps one row a key (``many_right``: two to five) whose ``m`` follows
    the key's left maximum (a tie) or a random value."""
    rng = np.random.default_rng(seed)
    left, right = Side(rng, 1), Side(rng, 1 if many_right else 0)
    next_id, next_rid, out = 0, 0, []
    for e in range(n_epochs):
        for _ in range(HOT_ROWS // 3 if e < 3 else 40):
            k = 0 if rng.random() < 0.7 else int(rng.integers(1, 31))
            k = None if rng.random() < 0.02 else k
            left.insert((k, next_id, int(rng.integers(0, 50))))
            next_id += 1
        ids = list(left.live)
        for i in rng.choice(len(ids), size=min(60, len(ids)), replace=False):
            key = ids[i]
            if rng.random() < 0.3:
                left.delete(key)
            else:
                k, rid, _v = left.live[key]
                left.update(key, (k, rid, int(rng.integers(0, 50))))
        best = collections.defaultdict(int)
        for k, _i, v in left.live.values():
            if k is not None:
                best[k] = max(best[k], v)
        for k in range(0, 31):
            m = best[k] if rng.random() < 0.6 else int(rng.integers(0, 50))
            mine = [key for key, r in right.live.items() if r[0] == k]
            if many_right:
                if len(mine) < 2 or rng.random() < 0.3:
                    right.insert((k, next_rid, m))
                    next_rid += 1
                elif rng.random() < 0.3:
                    right.delete(mine[0])
                else:
                    right.update(mine[-1], (k, mine[-1], m))
            elif not mine:
                right.insert((k, m))
            elif rng.random() < 0.15:
                right.delete(mine[0])
            else:
                right.update(mine[0], (k, m))
        rs = R2_SCHEMA if many_right else R_SCHEMA
        out.append((left.chunks(L_SCHEMA), right.chunks(rs),
                    dict(left.live), dict(right.live)))
    return out


def nested_loop(jt: JoinType, left: dict, right: dict, r_width: int,
                cond) -> collections.Counter:
    """The join's rows over the live rows, one by one."""
    out = collections.Counter()
    r_hit = collections.Counter()
    for lrow in left.values():
        hits = [r for r in right.values()
                if lrow[0] is not None and r[0] == lrow[0] and cond(lrow, r)]
        for r in hits:
            r_hit[r] += 1
            if jt in (JoinType.INNER, JoinType.LEFT_OUTER,
                      JoinType.RIGHT_OUTER, JoinType.FULL_OUTER):
                out[lrow + r] += 1
        if jt in (JoinType.LEFT_OUTER, JoinType.FULL_OUTER) and not hits:
            out[lrow + (None,) * r_width] += 1
        if (jt == JoinType.LEFT_SEMI and hits) or \
                (jt == JoinType.LEFT_ANTI and not hits):
            out[lrow] += 1
    for r in right.values():
        if jt in (JoinType.RIGHT_OUTER, JoinType.FULL_OUTER) and not r_hit[r]:
            out[(None,) * 3 + r] += 1
        if (jt == JoinType.RIGHT_SEMI and r_hit[r]) or \
                (jt == JoinType.RIGHT_ANTI and not r_hit[r]):
            out[r] += 1
    return out


def ge(lrow, r):
    return lrow[2] >= r[-1]


def drive(epochs: list, jt: JoinType, many_right: bool, store=None,
          epoch0: int = 1, stop: bool = False):
    """One executor over scripted epochs (the last a checkpoint + stop when
    ``stop``); returns its output rows by epoch and the executor."""
    rs = R2_SCHEMA if many_right else R_SCHEMA
    tables = {}
    if store is not None:
        tables = dict(
            left_state_table=StateTable(store, 1, L_SCHEMA, [0, 1]),
            right_state_table=StateTable(store, 2, rs,
                                         [0, 1] if many_right else [0]))
    left, right = [Barrier.new(epoch0)], [Barrier.new(epoch0)]
    for n, (lcs, rcs, *_live) in enumerate(epochs):
        left.extend(lcs)
        right.extend(rcs)
        last = stop and n == len(epochs) - 1
        mut = Mutation(MutationKind.STOP) if last else None
        left.append(Barrier.new(epoch0 + n + 1, checkpoint=last, mutation=mut))
        right.append(Barrier.new(epoch0 + n + 1, checkpoint=last,
                                 mutation=mut))
    ex = HashJoinExecutor(
        MockSource(L_SCHEMA, left), MockSource(rs, right), [0], [0], jt,
        condition=GE[many_right], key_capacity=64, bucket_width=4,
        out_capacity=64,
        row_keys=((0, 1), (0, 1) if many_right else None),
        row_capacity=4096, **tables)

    async def drain():
        per_epoch, rows = [], []
        async for m in ex.execute():
            if isinstance(m, StreamChunk):
                rows.extend(chunk_to_rows(m, ex.schema, with_ops=True))
            elif isinstance(m, Barrier) and m.epoch.curr > epoch0:
                per_epoch.append(rows)
                rows = []
        return per_epoch

    return asyncio.run(drain()), ex


def apply(mv: collections.Counter, rows: list) -> None:
    for op, row in rows:
        if op in (OP_INSERT, OP_UPDATE_INSERT):
            mv[row] += 1
        else:
            assert mv[row] > 0, f"retraction of an absent row {row}"
            mv[row] -= 1
            if not mv[row]:
                del mv[row]


def check(epochs, per_epoch, jt, many_right, mv=None):
    mv = collections.Counter() if mv is None else mv
    r_width = 3 if many_right else 2
    for (_l, _r, l_live, r_live), rows in zip(epochs, per_epoch):
        apply(mv, rows)
        assert mv == nested_loop(jt, l_live, r_live, r_width, ge)
    return mv


ALL_TYPES = [JoinType.INNER, JoinType.LEFT_OUTER, JoinType.RIGHT_OUTER,
             JoinType.FULL_OUTER, JoinType.LEFT_SEMI, JoinType.LEFT_ANTI,
             JoinType.RIGHT_SEMI, JoinType.RIGHT_ANTI]


@pytest.mark.parametrize("jt", ALL_TYPES, ids=lambda j: j.value)
def test_fanout_side_matches_a_nested_loop_join(jt):
    """The left side takes 1,500 rows on one key and moves to the fan-out
    arena; the right side, unique on the key, keeps the bucket arena at
    its width. After every epoch the MV equals the nested loop's."""
    epochs = script(7, 6, many_right=False)
    per_epoch, ex = drive(epochs, jt, many_right=False)
    check(epochs, per_epoch, jt, many_right=False)
    assert ex.core.layout["left"].fanout
    assert not ex.core.layout["right"].fanout
    assert ex.core.layout["right"].width == 4       # not widened by the left
