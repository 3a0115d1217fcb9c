"""The checkpoint's state delta, gathered on the device (ISSUEs 26, 32).

The hash agg, the hash join and the sharded hash agg select and gather
their dirty rows on the device (``ops/ckpt_delta.delta_window``) and fetch
only those; one host routine (``stream/state_delta``) encodes and stages
them. What a state table hands its store at the commit must be byte for
byte, and in the same order, what the HOST formulations handed it — kept
here as the references (``host_agg``, ``host_join``, ``host_mesh_agg``):
pull every column whole, index the dirty rows on the host. Each reference
stages into a twin of the executor's table; the two stores' ingests are
compared.
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import risingwave_tpu.native as native
from risingwave_tpu.common import (
    FLOAT64, INT64, OP_DELETE, OP_INSERT, Schema, make_chunk,
)
from risingwave_tpu.common.chunk import (
    Column, OP_UPDATE_DELETE, OP_UPDATE_INSERT,
)
from risingwave_tpu.common.hashing import vnode_of, vnode_to_shard
from risingwave_tpu.common.packed import dict_view
from risingwave_tpu.expr.agg import agg, count_star
from risingwave_tpu.ops import JoinType
from risingwave_tpu.ops.join_state import join_ckpt_delta_window
from risingwave_tpu.parallel import make_mesh
from risingwave_tpu.parallel.executors import ShardedHashAggExecutor
from risingwave_tpu.storage import MemoryStateStore, StateTable
from risingwave_tpu.stream import (
    HashAggExecutor, HashJoinExecutor, MockSource, agg_state_schema,
    state_delta,
)

IN_SCHEMA = Schema.of(("a", INT64), ("b", INT64), ("v", INT64),
                      ("f", FLOAT64))
CALLS = [count_star(), agg("sum", 2, INT64), agg("avg", 3, FLOAT64)]
CAPACITY = 64
SHARDS = 4

#: join sides: pk = join key ++ stream pk, one payload column beside it
L_SCHEMA = Schema.of(("k", INT64), ("id", INT64), ("x", FLOAT64))
R_SCHEMA = Schema.of(("k", INT64), ("id", INT64), ("y", INT64))
JOIN_SCHEMAS = {"left": L_SCHEMA, "right": R_SCHEMA}
KEY_CAPACITY, BUCKET_WIDTH = 32, 4


# -- the references: the old host formulations ------------------------------

def host_agg(st, table, codec, epoch) -> None:
    """The old ``HashAggExecutor`` checkpoint delta: full-capacity pulls,
    host indexing."""
    idx = np.nonzero(np.asarray(st.ckpt_dirty))[0]
    if not len(idx):
        return
    if codec is not None:
        keys_d = [np.asarray(kd) for kd in st.table.key_data]
        keys_m = [np.asarray(km) for km in st.table.key_mask]
        lanes = [np.asarray(l) for l in st.lanes]
        datas = keys_d + lanes
        masks = keys_m + [np.ones(lanes[0].shape, bool)] * len(lanes)
        types = table.schema.types
        nk = len(keys_d)
        live = lanes[0][idx] > 0
        ins_idx, del_idx = idx[live], idx[~live]
        pk_t = list(types[:nk])
        puts = dict(zip(
            codec.encode_keys(keys_d, keys_m, pk_t, ins_idx),
            codec.encode_value_rows(datas, masks, types, ins_idx)))
        dels = codec.encode_keys(keys_d, keys_m, pk_t, del_idx)
        table.stage_encoded(puts, dels)
    else:
        keys_d = [np.asarray(kd)[idx] for kd in st.table.key_data]
        keys_m = [np.asarray(km)[idx] for km in st.table.key_mask]
        lanes = [np.asarray(l)[idx] for l in st.lanes]
        for r in range(len(idx)):
            key_vals = [keys_d[c][r].item() if keys_m[c][r] else None
                        for c in range(len(keys_d))]
            row = tuple(key_vals) + tuple(l[r].item() for l in lanes)
            if lanes[0][r] > 0:
                table.insert(row)
            else:
                table.delete(row)
    table.commit(epoch)


def host_join(st, table, codec, epoch) -> None:
    """The old ``HashJoinExecutor._stage_state_delta`` for one side: the
    dirty marks and every ``[capacity, W]`` column pulled whole."""
    dirty = np.asarray(st.ckpt_dirty)
    slots, lanes = np.nonzero(dirty)
    if not len(slots):
        return
    occ = np.asarray(st.occupied)
    tomb = np.asarray(st.tomb)
    datas = [np.asarray(d) for d in st.row_data]
    masks = [np.asarray(m) for m in st.row_mask]
    if codec is not None:
        width = occ.shape[1]
        flat = slots * width + lanes
        fdatas = [d.reshape(-1) for d in datas]
        fmasks = [m.reshape(-1) for m in masks]
        occ_f = occ.reshape(-1)
        tomb_f = tomb.reshape(-1)
        del_idx = flat[tomb_f[flat] & ~occ_f[flat]]
        ins_idx = flat[occ_f[flat]]
        types = table.schema.types
        pk = table.pk_indices
        pk_d = [fdatas[i] for i in pk]
        pk_m = [fmasks[i] for i in pk]
        pk_t = [types[i] for i in pk]
        puts = dict(zip(
            codec.encode_keys(pk_d, pk_m, pk_t, ins_idx),
            codec.encode_value_rows(fdatas, fmasks, types, ins_idx)))
        dels = codec.encode_keys(pk_d, pk_m, pk_t, del_idx)
        table.stage_encoded(puts, dels)
        table.commit(epoch)
        return

    def row_at(s, l):
        return tuple(datas[c][s, l].item() if masks[c][s, l] else None
                     for c in range(len(datas)))

    for s, l in zip(slots, lanes):
        if tomb[s, l] and not occ[s, l]:
            table.delete(row_at(s, l))
    for s, l in zip(slots, lanes):
        if occ[s, l]:
            table.insert(row_at(s, l))
    table.commit(epoch)


def host_mesh_agg(state, n, table, epoch) -> None:
    """The old ``ShardedHashAggExecutor._checkpoint_to_state_table``: the
    whole sharded state to the host, then a Python loop a dirty group."""
    st = jax.device_get(state)
    wrote = False
    for s in range(n):
        idx = np.nonzero(np.asarray(st.ckpt_dirty[s]))[0]
        if not len(idx):
            continue
        wrote = True
        keys_d = [np.asarray(kd[s])[idx] for kd in st.table.key_data]
        keys_m = [np.asarray(km[s])[idx] for km in st.table.key_mask]
        lanes = [np.asarray(l[s])[idx] for l in st.lanes]
        for r in range(len(idx)):
            key_vals = [keys_d[c][r].item() if keys_m[c][r] else None
                        for c in range(len(keys_d))]
            row = tuple(key_vals) + tuple(l[r].item() for l in lanes)
            if lanes[0][r] > 0:
                table.insert(row)
            else:
                table.delete(row)
    if wrote:
        table.commit(epoch)


# -- what is compared --------------------------------------------------------

def spy_on_store(store) -> list:
    """Every ingest of ``store``: the dict view of the layers it was
    handed (``{key: value | None}``, the executor's packed batch or the
    reference's dict alike), as bytes and in order."""
    got = []
    real = store.ingest_layers

    def ingest_layers(table_id, epoch, layers):
        got.append((table_id, epoch, list(dict_view(layers).items())))
        return real(table_id, epoch, layers)
    store.ingest_layers = ingest_layers
    return got


def spy_on_table(table) -> list:
    """The name of every call that stages or commits, in order."""
    calls = []
    for name in ("stage_packed", "stage_encoded", "insert", "delete",
                 "commit"):
        def spy(*a, _real=getattr(table, name), _name=name):
            calls.append(_name)
            return _real(*a)
        setattr(table, name, spy)
    return calls


def twin_of(table, store) -> StateTable:
    return StateTable(store, table.table_id, table.schema, table.pk_indices)


def check_calls(calls: list, n_dirty: int, with_codec: bool) -> None:
    """One batch and one commit with the codec; without it the deletes
    before the puts; nothing at all for an empty delta."""
    if not n_dirty:
        assert calls == []
    elif with_codec:
        assert calls == ["stage_packed", "commit"]
    else:
        assert calls[-1] == "commit" and len(calls) > 1
        assert calls[:-1] == sorted(calls[:-1])     # "delete" < "insert"


# -- the agg's steps ---------------------------------------------------------

def apply(rows, ops=None):
    def step(ex):
        ex.state = ex._apply(ex.state, make_chunk(IN_SCHEMA, rows, ops=ops),
                             None, None)
    return step


def clean_below(threshold):
    def step(ex):
        ex.state = ex._clean(ex.state, 0, jnp.asarray(threshold))
    return step


def all_dirty(ex):
    ex.state = ex.state.replace(
        ckpt_dirty=jnp.ones_like(ex.state.ckpt_dirty))


def nothing(ex):
    pass


def rows(n, start=0):
    return [(start + i, (start + i) % 7, 10 * i, i / 4) for i in range(n)]


# -- the join's steps --------------------------------------------------------

def side(name, rows, ops=None):
    def step(ex):
        ex._apply_growing(name, make_chunk(JOIN_SCHEMAS[name], rows, ops=ops))
    return step


def both(*steps):
    def step(ex):
        for s in steps:
            s(ex)
    return step


def join_sides(*steps):
    """``both``, and no delete may have missed its row."""
    def step(ex):
        both(*steps)(ex)
        ex._check_flags()
    return step


def lrows(n, start=0):
    return [(start + i, 100 + start + i, i / 4) for i in range(n)]


def rrows(n, start=0):
    # three rows a join key: lanes 0..2 of its bucket
    return [((start + i) // 3, start + i, 10 * i) for i in range(n)]


def pks_dirty_in_two_lanes(st) -> int:
    """Stream pks with a tombstone in one dirty lane and a live row in
    another: the case the delete-before-put rule exists for."""
    dirty = np.asarray(st.ckpt_dirty)
    occ, tomb = np.asarray(st.occupied), np.asarray(st.tomb)
    ids = np.asarray(st.row_data[1])
    dead = set(ids[dirty & tomb & ~occ].tolist())
    return len(dead & set(ids[dirty & occ].tolist()))


# -- the mesh agg's steps ----------------------------------------------------

def rows_by_shard(per_shard: dict, start=0) -> list:
    """Distinct group keys, ``per_shard[s]`` of them owned by shard ``s``
    (the vnode map the in-program shuffle routes by)."""
    cand = rows(4000, start)
    cols = [Column(jnp.asarray(np.array([r[c] for r in cand])),
                   jnp.ones(len(cand), bool)) for c in (0, 1)]
    shard = np.asarray(vnode_to_shard(vnode_of(cols), SHARDS))
    out = []
    for s, n in per_shard.items():
        mine = [r for r, owner in zip(cand, shard) if owner == s][:n]
        assert len(mine) == n
        out += mine
    return out


def mesh_apply(per_shard: dict, start=0, op=OP_INSERT):
    def step(ex):
        rows = rows_by_shard(per_shard, start)
        ops = [op] * len(rows)

        async def run():
            for lo in range(0, len(rows), 32):
                chunk = make_chunk(IN_SCHEMA, rows[lo:lo + 32], capacity=32,
                                   ops=ops[lo:lo + 32])
                async for _ in ex.map_chunk(chunk):
                    pass
        asyncio.run(run())
    return step


#: site -> name -> steps; a checkpoint is taken, and compared, after EACH
SCENARIOS = {
    "agg": {
        "inserts_only": [apply(rows(20))],
        "retraction_stages_deletes": [
            apply(rows(12)),
            # groups 0..5 return to a row count of 0; 100 is born and dies
            # between two checkpoints; 6 is updated and stays live
            apply(rows(6) + [(100, 2, 1, 1.0), (100, 2, 1, 1.0),
                             (6, 6, 5, 0.5)],
                  ops=[OP_DELETE] * 6 + [OP_INSERT, OP_DELETE, OP_INSERT]),
        ],
        "clean_below_stages_deletes": [apply(rows(16)), clean_below(9)],
        "null_group_keys": [
            apply([(None, 1, 5, 1.0), (None, None, 6, 2.0),
                   (3, None, 7, 3.0), (None, 1, 8, 4.0), (3, 4, 9, None)]),
        ],
        "no_dirty_group": [apply(rows(5)), nothing],
        "several_windows": [apply(rows(37)), apply(rows(21, start=30))],
        "every_slot_dirty": [apply(rows(9)), all_dirty],
    },
    "join": {
        "inserts_only": [join_sides(side("left", lrows(9)),
                                    side("right", rrows(14)))],
        "retraction_stages_deletes": [
            join_sides(side("left", lrows(8)), side("right", rrows(12))),
            # (50, 150) is born and dies between two checkpoints: a
            # tombstone that no table row stands behind
            join_sides(side("left", [(50, 150, 1.0)]),
                       side("left", lrows(3) + [(50, 150, 1.0)],
                            ops=[OP_DELETE] * 4),
                       side("right", rrows(12)[4:7], ops=[OP_DELETE] * 3)),
        ],
        "same_pk_update_inside_one_interval": [
            side("right", rrows(6)),
            # the U- leaves a tombstone in its lane and the U+ of the same
            # pk takes another lane of the bucket: the delete has to be
            # staged before the put, whatever the lanes' order
            join_sides(side("right", [(0, 1, 10), (0, 1, 77)],
                            ops=[OP_UPDATE_DELETE, OP_UPDATE_INSERT]),
                       side("right", [(1, 3, 30), (1, 3, 78)],
                            ops=[OP_UPDATE_DELETE, OP_UPDATE_INSERT])),
        ],
        "null_columns": [
            join_sides(
                side("left", [(1, 101, None), (None, 102, 2.0),
                              (3, 103, None), (None, 104, None)]),
                side("right", [(1, 1, None), (None, 2, 5), (None, 3, None)])),
        ],
        "no_dirty_row_on_one_side": [
            side("left", lrows(5)), side("right", rrows(4)), nothing],
        "several_windows": [
            join_sides(side("left", lrows(11)), side("right", rrows(19))),
            join_sides(side("left", lrows(6, start=8)),
                       side("right", rrows(19)[2:12], ops=[OP_DELETE] * 10)),
        ],
    },
    "mesh_agg": {
        "groups_on_every_shard": [
            mesh_apply({0: 5, 1: 7, 2: 3, 3: 6}),
            # shard 1's groups die, shard 2 gets new ones
            both(mesh_apply({1: 7}, op=OP_DELETE),
                 mesh_apply({2: 4}, start=5000)),
        ],
        "one_empty_shard": [mesh_apply({0: 4, 1: 6, 3: 2}), nothing],
        "several_windows_on_one_shard_none_on_another": [
            mesh_apply({0: 11, 2: 1, 3: 5})],
    },
}
#: rows of one window, where a scenario needs a small one (the callers
#: derive theirs from the module's constant and the state's capacity)
WINDOW_ROWS = {("agg", "several_windows"): 8,
               ("agg", "every_slot_dirty"): 16,
               ("join", "several_windows"): 4,
               ("mesh_agg", "several_windows_on_one_shard_none_on_another"): 4}


class AggSite:
    def __init__(self, store):
        self.table = StateTable(
            store, 7, agg_state_schema(IN_SCHEMA.fields[:2], CALLS), [0, 1])
        self.ex = HashAggExecutor(MockSource(IN_SCHEMA, []), [0, 1], CALLS,
                                  state_table=self.table,
                                  table_capacity=CAPACITY)
        self.tables = [self.table]

    def dirty(self) -> list:
        return [int(np.asarray(self.ex.state.ckpt_dirty).sum())]

    def reference(self, store, codec, epoch) -> None:
        host_agg(self.ex.state, twin_of(self.table, store), codec, epoch)

    def checkpoint(self, epoch) -> None:
        self.ex._checkpoint_to_state_table(epoch)

    def marks(self) -> list:
        return [self.ex.state.ckpt_dirty]


class JoinSite:
    def __init__(self, store):
        self.tables = [StateTable(store, 1, L_SCHEMA, [0, 1]),
                       StateTable(store, 2, R_SCHEMA, [0, 1])]
        self.ex = HashJoinExecutor(
            MockSource(L_SCHEMA, []), MockSource(R_SCHEMA, []), [0], [0],
            JoinType.LEFT_OUTER, left_state_table=self.tables[0],
            right_state_table=self.tables[1], key_capacity=KEY_CAPACITY,
            bucket_width=BUCKET_WIDTH)

    def sides(self):
        return zip((self.ex.state.left, self.ex.state.right), self.tables)

    def dirty(self) -> list:
        return [int(np.asarray(st.ckpt_dirty).sum()) for st, _t in self.sides()]

    def reference(self, store, codec, epoch) -> None:
        for st, table in self.sides():
            host_join(st, twin_of(table, store), codec, epoch)

    def checkpoint(self, epoch) -> None:
        self.ex._checkpoint(epoch)

    def marks(self) -> list:
        return [m for st, _t in self.sides() for m in (st.ckpt_dirty, st.tomb)]


class MeshAggSite:
    def __init__(self, store):
        if len(jax.devices()) < SHARDS:
            pytest.skip(f"needs {SHARDS} devices")
        self.table = StateTable(
            store, 7, agg_state_schema(IN_SCHEMA.fields[:2], CALLS), [0, 1])
        self.ex = ShardedHashAggExecutor(
            MockSource(IN_SCHEMA, []), make_mesh(SHARDS), [0, 1], CALLS,
            state_table=self.table, table_capacity=CAPACITY, out_capacity=32)
        self.tables = [self.table]

    def dirty(self) -> list:
        return [int(np.asarray(self.ex.agg.state.ckpt_dirty).sum())]

    def per_shard(self) -> list:
        return np.asarray(self.ex.agg.state.ckpt_dirty).sum(axis=1).tolist()

    def reference(self, store, codec, epoch) -> None:
        host_mesh_agg(self.ex.agg.state, SHARDS,
                      twin_of(self.table, store), epoch)

    def checkpoint(self, epoch) -> None:
        from risingwave_tpu.common.tracing import CAT_STORAGE, span
        with span("agg.state_delta", epoch=epoch, stage="state_delta",
                  cat=CAT_STORAGE, shards=SHARDS) as delta:
            self.ex._checkpoint_to_state_table(epoch, delta)

    def marks(self) -> list:
        return [self.ex.agg.state.ckpt_dirty]


SITES = {"agg": AggSite, "join": JoinSite, "mesh_agg": MeshAggSite}


@pytest.mark.parametrize("with_codec", [True, False],
                         ids=["native_codec", "no_codec"])
@pytest.mark.parametrize(
    "site, scenario",
    [(site, name) for site in SCENARIOS for name in sorted(SCENARIOS[site])])
def test_staged_delta_is_byte_identical_to_the_host_formulation(
        site, scenario, with_codec, monkeypatch):
    if with_codec:
        codec = native.codec()
        if codec is None:
            pytest.skip("the native row codec did not build here")
    else:
        codec = None
        monkeypatch.setattr(native, "codec", lambda: None)
    window = WINDOW_ROWS.get((site, scenario))
    if window:
        monkeypatch.setattr(state_delta, "DELTA_WINDOW_ROWS", window)
    store, twin_store = MemoryStateStore(), MemoryStateStore()
    at = SITES[site](store)
    got, want = spy_on_store(store), spy_on_store(twin_store)
    calls = [spy_on_table(t) for t in at.tables]
    dirty_by_table = [0] * len(at.tables)
    for epoch, step in enumerate(SCENARIOS[site][scenario], start=1):
        step(at.ex)
        dirty = at.dirty()
        del got[:], want[:]
        for c in calls:
            del c[:]
        if scenario == "same_pk_update_inside_one_interval" and epoch == 2:
            assert pks_dirty_in_two_lanes(at.ex.state.right) == 2
        at.reference(twin_store, codec, epoch)
        at.checkpoint(epoch)
        assert got == want
        assert len(got) <= len(at.tables)
        for c, n in zip(calls, dirty):
            check_calls(c, n, with_codec)
        assert not any(np.asarray(m).any() for m in at.marks())
        dirty_by_table = [a + b for a, b in zip(dirty_by_table, dirty)]
        if step is nothing:
            assert sum(dirty) == 0 and got == []
        if scenario == "no_dirty_row_on_one_side":
            assert len(got) == (0 if step is nothing else 1)
        if window and scenario.startswith("several_windows"):
            assert max(dirty) > 2 * window
        if scenario == "every_slot_dirty" and step is all_dirty:
            assert dirty == [CAPACITY]
    assert sum(dirty_by_table) > 0


@pytest.mark.parametrize("scenario, want", [
    ("groups_on_every_shard", [5, 7, 3, 6]),
    ("one_empty_shard", [4, 6, 0, 2]),
    ("several_windows_on_one_shard_none_on_another", [11, 0, 1, 5]),
])
def test_mesh_scenarios_dirty_the_shards_they_name(scenario, want):
    at = MeshAggSite(MemoryStateStore())
    SCENARIOS["mesh_agg"][scenario][0](at.ex)
    assert at.per_shard() == want


@pytest.mark.parametrize("G", [4, 16, 64])
def test_windows_walk_the_dirty_slots_in_ascending_order(G):
    """The pure function, window by window, against ``np.nonzero``."""
    ex = HashAggExecutor(MockSource(IN_SCHEMA, []), [0, 1], CALLS,
                         table_capacity=CAPACITY)
    apply(rows(23))(ex)
    st = ex.state
    slots = np.nonzero(np.asarray(st.ckpt_dirty))[0]
    window = jax.jit(ex.core.ckpt_delta_window, static_argnums=(2,))
    for lo in range(0, len(slots) + G, G):
        n_dirty, valid, keys_d, keys_m, lanes = jax.device_get(
            window(st, np.int32(lo), G))
        assert n_dirty == len(slots) == 23
        want = slots[lo:lo + G]
        assert valid.tolist() == [True] * len(want) + [False] * (G - len(want))
        for got, whole in zip(keys_d + keys_m + lanes,
                              st.table.key_data + st.table.key_mask
                              + st.lanes):
            assert got.shape == (G,)
            assert np.array_equal(got[:len(want)], np.asarray(whole)[want])


@pytest.mark.parametrize("G", [4, 16])
def test_join_windows_walk_the_arena_row_major(G):
    """The join's window over its ``[capacity, W]`` arena: dirty (slot,
    lane) pairs in row-major order, as ``np.nonzero`` lists them."""
    at = JoinSite(MemoryStateStore())
    side("right", rrows(17))(at.ex)
    st = at.ex.state.right
    slots, lanes = np.nonzero(np.asarray(st.ckpt_dirty))
    assert len(set(lanes)) > 1
    window = jax.jit(join_ckpt_delta_window, static_argnums=(2,))
    for lo in range(0, len(slots) + G, G):
        n_dirty, valid, occ, tomb, datas, masks = jax.device_get(
            window(st, np.int32(lo), G))
        assert n_dirty == len(slots) == 17
        s, l = slots[lo:lo + G], lanes[lo:lo + G]
        assert valid.tolist() == [True] * len(s) + [False] * (G - len(s))
        for got, whole in zip((occ, tomb) + datas + masks,
                              (st.occupied, st.tomb) + st.row_data
                              + st.row_mask):
            assert got.shape == (G,)
            assert np.array_equal(got[:len(s)], np.asarray(whole)[s, l])
