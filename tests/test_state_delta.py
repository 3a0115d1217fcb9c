"""The checkpoint's state delta, gathered on the device (ISSUE 26).

``HashAggExecutor._stage_state_delta`` selects and gathers the dirty
groups on the device and fetches only those rows. What it stages must be
byte for byte, and in the same order, what the HOST formulation staged —
kept here as the reference (``host_formulation``): pull every column
whole, index the dirty rows on the host.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import risingwave_tpu.native as native
from risingwave_tpu.common import (
    FLOAT64, INT64, OP_DELETE, OP_INSERT, Schema, make_chunk,
)
from risingwave_tpu.expr.agg import agg, count_star
from risingwave_tpu.storage import MemoryStateStore, StateTable
from risingwave_tpu.stream import (
    HashAggExecutor, MockSource, agg_state_schema, hash_agg,
)

IN_SCHEMA = Schema.of(("a", INT64), ("b", INT64), ("v", INT64),
                      ("f", FLOAT64))
CALLS = [count_star(), agg("sum", 2, INT64), agg("avg", 3, FLOAT64)]
CAPACITY = 64


def host_formulation(ex, codec) -> list:
    """What the old ``_stage_state_delta`` handed the state table, as the
    calls it made: the reference. Full-capacity pulls, host indexing."""
    st = ex.state
    idx = np.nonzero(np.asarray(st.ckpt_dirty))[0]
    calls = []
    if not len(idx):
        return calls
    if codec is not None:
        keys_d = [np.asarray(kd) for kd in st.table.key_data]
        keys_m = [np.asarray(km) for km in st.table.key_mask]
        lanes = [np.asarray(l) for l in st.lanes]
        datas = keys_d + lanes
        masks = keys_m + [np.ones(lanes[0].shape, bool)] * len(lanes)
        types = ex.state_table.schema.types
        nk = len(keys_d)
        live = lanes[0][idx] > 0
        ins_idx, del_idx = idx[live], idx[~live]
        pk_t = list(types[:nk])
        puts = list(zip(
            codec.encode_keys(keys_d, keys_m, pk_t, ins_idx),
            codec.encode_value_rows(datas, masks, types, ins_idx)))
        dels = codec.encode_keys(keys_d, keys_m, pk_t, del_idx)
        calls.append(("stage_encoded", puts, list(dels)))
    else:
        keys_d = [np.asarray(kd)[idx] for kd in st.table.key_data]
        keys_m = [np.asarray(km)[idx] for km in st.table.key_mask]
        lanes = [np.asarray(l)[idx] for l in st.lanes]
        for r in range(len(idx)):
            key_vals = [keys_d[c][r].item() if keys_m[c][r] else None
                        for c in range(len(keys_d))]
            row = tuple(key_vals) + tuple(l[r].item() for l in lanes)
            calls.append(("insert" if lanes[0][r] > 0 else "delete", row))
    calls.append(("commit",))
    return calls


def spy_on(table) -> list:
    """Record, in order, every call that stages or commits."""
    calls = []

    def wrap(name, record):
        real = getattr(table, name)

        def spy(*a):
            calls.append(record(*a))
            return real(*a)
        setattr(table, name, spy)

    wrap("stage_encoded",
         lambda puts, dels: ("stage_encoded", list(puts.items()), list(dels)))
    wrap("insert", lambda row: ("insert", tuple(row)))
    wrap("delete", lambda row: ("delete", tuple(row)))
    wrap("commit", lambda epoch: ("commit",))
    return calls


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


#: name -> steps; a checkpoint is taken, and compared, after EACH step
SCENARIOS = {
    "inserts_only": [apply(rows(20))],
    "retraction_stages_deletes": [
        apply(rows(12)),
        # groups 0..5 return to a row count of 0; 100 is born and dies
        # between two checkpoints; 6 is updated and stays live
        apply(rows(6) + [(100, 2, 1, 1.0), (100, 2, 1, 1.0), (6, 6, 5, 0.5)],
              ops=[OP_DELETE] * 6 + [OP_INSERT, OP_DELETE, OP_INSERT]),
    ],
    "clean_below_stages_deletes": [apply(rows(16)), clean_below(9)],
    "null_group_keys": [
        apply([(None, 1, 5, 1.0), (None, None, 6, 2.0), (3, None, 7, 3.0),
               (None, 1, 8, 4.0), (3, 4, 9, None)]),
    ],
    "no_dirty_group": [apply(rows(5)), nothing],
    "several_windows": [apply(rows(37)), apply(rows(21, start=30))],
    "every_slot_dirty": [apply(rows(9)), all_dirty],
}
#: rows of one window, where a scenario needs a small one (the executor
#: derives it; the constant it derives it from is module state)
WINDOW_ROWS = {"several_windows": 8, "every_slot_dirty": 16}


@pytest.mark.parametrize("with_codec", [True, False],
                         ids=["native_codec", "no_codec"])
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_staged_delta_is_byte_identical_to_the_host_formulation(
        scenario, with_codec, monkeypatch):
    if with_codec:
        codec = native.codec()
        if codec is None:
            pytest.skip("the native row codec did not build here")
    else:
        codec = None
        monkeypatch.setattr(native, "codec", lambda: None)
    if scenario in WINDOW_ROWS:
        monkeypatch.setattr(hash_agg, "_DELTA_WINDOW_ROWS",
                            WINDOW_ROWS[scenario])
    table = StateTable(MemoryStateStore(), 7,
                       agg_state_schema(IN_SCHEMA.fields[:2], CALLS), [0, 1])
    ex = HashAggExecutor(MockSource(IN_SCHEMA, []), [0, 1], CALLS,
                         state_table=table, table_capacity=CAPACITY)
    got = spy_on(table)
    staged = 0
    for epoch, step in enumerate(SCENARIOS[scenario], start=1):
        step(ex)
        n_dirty = int(np.asarray(ex.state.ckpt_dirty).sum())
        want = host_formulation(ex, codec)
        del got[:]
        ex._checkpoint_to_state_table(epoch)
        assert got == want
        assert not np.asarray(ex.state.ckpt_dirty).any()
        staged += n_dirty
        if scenario == "no_dirty_group" and step is nothing:
            assert n_dirty == 0 and got == []
        if scenario == "several_windows":
            assert n_dirty > 2 * WINDOW_ROWS[scenario]
        if scenario == "every_slot_dirty" and step is all_dirty:
            assert n_dirty == CAPACITY
    assert staged > 0


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
