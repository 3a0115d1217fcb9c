"""Native C++ row codec: byte-identical to the Python encoders, and the
checkpoint fast path produces the same durable state (task: native runtime
components)."""

import numpy as np
import pytest

from risingwave_tpu.common.row import encode_key, encode_value_row
from risingwave_tpu.common.types import (
    BOOL, DATE, FLOAT32, FLOAT64, GLOBAL_STRING_DICT, INT16, INT32, INT64,
    VARCHAR, Field, Schema, decimal,
)
from risingwave_tpu.native import codec

pytestmark = pytest.mark.skipif(codec() is None,
                                reason="native toolchain unavailable")

TYPES = [INT64, INT32, INT16, BOOL, FLOAT64, FLOAT32, DATE, decimal(2),
         VARCHAR]

ROWS = [
    (42, -7, 3, True, 1.5, -2.25, 9204, 1234, "alpha"),
    (-1, None, -3, False, -0.0, None, None, -505, "with\x00zero"),
    (0, 2**31 - 1, None, None, float("inf"), 1.0, -10, None, ""),
    (2**62, -2**31, -32768, True, -1e300, -1.5, 0, 99, "βeta"),
]


def _columns(rows, types):
    n = len(rows)
    datas, masks = [], []
    for c, t in enumerate(types):
        arr = np.zeros(n, t.np_dtype)
        mask = np.zeros(n, bool)
        for r, row in enumerate(rows):
            if row[c] is not None:
                arr[r] = t.to_physical(row[c])
                mask[r] = True
        datas.append(arr)
        masks.append(mask)
    return datas, masks


def _physical(row, types):
    return tuple(None if v is None else t.to_physical(v)
                 for v, t in zip(row, types))


class TestByteIdentical:
    def test_value_rows_match_python(self):
        datas, masks = _columns(ROWS, TYPES)
        got = codec().encode_value_rows(datas, masks, TYPES,
                                        np.arange(len(ROWS)))
        for r, row in enumerate(ROWS):
            expect = encode_value_row(_physical(row, TYPES), TYPES)
            assert got[r] == expect, f"row {r} value encoding differs"

    def test_keys_match_python(self):
        datas, masks = _columns(ROWS, TYPES)
        got = codec().encode_keys(datas, masks, TYPES, np.arange(len(ROWS)))
        for r, row in enumerate(ROWS):
            expect = encode_key(_physical(row, TYPES), TYPES)
            assert got[r] == expect, f"row {r} key encoding differs"

    def test_key_order_preserved(self):
        vals = [(-(2**40),), (-5,), (0,), (3,), (2**50,), (None,)]
        datas, masks = _columns(vals, [INT64])
        keys = codec().encode_keys(datas, masks, [INT64],
                                   np.arange(len(vals)))
        order = sorted(range(len(vals)), key=lambda i: keys[i])
        # NULL sorts first, then numeric order
        assert order == [5, 0, 1, 2, 3, 4]

    def test_row_subset_selection(self):
        datas, masks = _columns(ROWS, TYPES)
        got = codec().encode_value_rows(datas, masks, TYPES,
                                        np.array([2, 0]))
        assert got[0] == encode_value_row(_physical(ROWS[2], TYPES), TYPES)
        assert got[1] == encode_value_row(_physical(ROWS[0], TYPES), TYPES)


class TestCheckpointPath:
    def test_rs_checkpoint_native_equals_python(self, monkeypatch):
        """The same dirty row-set checkpointed through the native path and
        the Python path must produce identical durable KV state."""
        import jax.numpy as jnp
        from risingwave_tpu.common.chunk import OP_DELETE, make_chunk
        from risingwave_tpu.ops.row_set import rs_apply_chunk, rs_checkpoint
        from risingwave_tpu.ops.row_set import rs_new
        from risingwave_tpu.storage.state_store import MemoryStateStore
        from risingwave_tpu.storage.state_table import StateTable

        schema = Schema((Field("k", INT64), Field("s", VARCHAR),
                         Field("x", FLOAT64)))
        rows = [(1, "a", 1.5), (2, "b", None), (3, None, -2.0),
                (4, "dd", 0.25)]

        def run(disable_native):
            import risingwave_tpu.native as native_mod
            store = MemoryStateStore()
            st = StateTable(store, 1, schema, [0])
            rs = rs_new([INT64], [INT64, VARCHAR, FLOAT64], 64)
            chunk = make_chunk(schema, rows, capacity=8)
            rs, _, _ = rs_apply_chunk(rs, chunk, (0,))
            dchunk = make_chunk(schema, [rows[1]], ops=[OP_DELETE],
                                capacity=2)
            rs, _, _ = rs_apply_chunk(rs, dchunk, (0,))
            if disable_native:
                monkeypatch.setattr(native_mod, "_lib", None)
                monkeypatch.setattr(native_mod, "_tried", True)
            else:
                monkeypatch.setattr(native_mod, "_tried", False)
            rs_checkpoint(rs, st, epoch=1)
            store.commit(1)
            return dict(store.iter_table(1))

        native_kv = run(False)
        python_kv = run(True)
        assert native_kv == python_kv
        assert len(native_kv) == 3


# -- checkpoint segment: a table's block laid out by the native codec --------

def _random_table(rng, n, tombstones=0.2):
    buf = {}
    while len(buf) < n:
        k = rng.randbytes(rng.choice((1, 4, 9, 20, 21, 40)))
        buf[k] = (None if rng.random() < tombstones
                  else rng.randbytes(rng.randrange(0, 64)))
    return buf


def _random_10k():
    import random
    rng = random.Random(34)
    return {3: _random_table(rng, 6000), 11: _random_table(rng, 4000, 0.0)}


SEGMENT_CASES = {
    "no_tables": lambda: {},
    "empty_table": lambda: {5: {}},
    "puts_only": lambda: {1: {b"b": b"2", b"a": b"1", b"c": b"333"}},
    "tombstones_only": lambda: {1: {b"z": None, b"y": None, b"x": None}},
    "mixed": lambda: {1: {b"k2": None, b"k1": b"v1", b"k3": b"v3",
                          b"k0": None}},
    # the order on a tie: a key sorts before every key it is a prefix of,
    # a trailing zero byte included (the 8-byte compare prefix pads with 0)
    "prefix_keys": lambda: {1: {b"ab": b"1", b"abc": None, b"a": b"2",
                                b"": b"e", b"ab\x00": b"z", b"b": b"3",
                                b"ab\x00\x00": b"y",
                                b"abcdefgh": b"8", b"abcdefghi": b"9",
                                b"abcdefgh\x00": None, b"abcdefg": b"7"}},
    # live with vlen 0 — not a tombstone
    "empty_value": lambda: {1: {b"k": b"", b"j": None, b"l": b"x"}},
    "key_65535": lambda: {1: {b"k" * 65535: b"v", b"a": None}},
    "key_65536": lambda: {1: {b"a": b"1"}, 2: {b"k" * 65536: b"v"}},
    "tables_unordered": lambda: {9: {b"n": b"9"}, 2: {b"t": None},
                                 5: {}, 7: {b"b": b"", b"a": b"7"}},
    "random_10k": _random_10k,
}


@pytest.mark.parametrize("case", list(SEGMENT_CASES))
def test_segment_native_equals_python(case):
    """Byte for byte the segment the Python row loop writes; where that
    loop raises (a key past the ``<H`` length) the native path stands
    aside and the same error surfaces."""
    import struct
    from risingwave_tpu.storage.checkpoint import CheckpointLog
    deltas = SEGMENT_CASES[case]()
    got = CheckpointLog._encode_segment_native(deltas)
    if case == "key_65536":
        assert got is None
        with pytest.raises(struct.error):
            CheckpointLog._encode_segment(deltas)
        return
    expect = CheckpointLog._encode_segment_py(deltas)
    assert got == expect
    assert CheckpointLog._encode_segment(deltas) == expect
    assert CheckpointLog._decode_segment(got) == deltas


def _commit_span(epoch):
    from risingwave_tpu.common import tracing
    (sp,) = [s for s in tracing.GLOBAL_TRACE.snapshot(epoch)
             if s.name == "DurableStateStore.commit"]
    return sp.args


def test_commit_of_n_rows_runs_no_python_row_loop(tmp_path, monkeypatch):
    """Counts, no timing: with the codec present a commit never enters the
    per-row Python body, and its span says what was written and how."""
    from risingwave_tpu.common import tracing
    from risingwave_tpu.storage.checkpoint import (
        CheckpointLog, DurableStateStore,
    )
    calls = []
    py = CheckpointLog._encode_segment_py
    monkeypatch.setattr(
        CheckpointLog, "_encode_segment_py",
        staticmethod(lambda deltas: calls.append(1) or py(deltas)))
    tracing.GLOBAL_TRACE.clear()
    st = DurableStateStore(str(tmp_path))
    puts = {b"k%05d" % i: b"v%d" % i for i in range(1000)}
    st.ingest(7, 1, puts, set())
    st.ingest(9, 1, {b"x": b"1"}, {b"gone-a", b"gone-b"})
    st.commit(1)
    assert calls == []
    segment = st.log.store.get("epoch_000000000001.seg")
    assert _commit_span(1) == {"tables": 2, "rows": 1003,
                               "bytes": len(segment), "native": 1}
    assert segment == py(st.log._decode_segment(segment))
    # an epoch with nothing to write appends no segment
    st.commit(2)
    assert _commit_span(2) == {"tables": 0, "rows": 0, "bytes": 0,
                               "native": 0}


def test_segment_fallback_under_disable_env(tmp_path, monkeypatch):
    """RW_TPU_DISABLE_NATIVE=1: the Python loop writes the segment, the
    span reads native = 0, the bytes are the same."""
    import risingwave_tpu.native as native_mod
    from risingwave_tpu.common import tracing
    from risingwave_tpu.storage.checkpoint import (
        CheckpointLog, DurableStateStore,
    )
    deltas = SEGMENT_CASES["mixed"]()
    native_bytes = CheckpointLog._encode_segment_native(deltas)
    monkeypatch.setenv("RW_TPU_DISABLE_NATIVE", "1")
    monkeypatch.setattr(native_mod, "_lib", None)
    monkeypatch.setattr(native_mod, "_tried", False)
    assert native_mod.codec() is None
    assert CheckpointLog._encode_segment_native(deltas) is None
    tracing.GLOBAL_TRACE.clear()
    st = DurableStateStore(str(tmp_path))
    st.ingest(1, 1, {k: v for k, v in deltas[1].items() if v is not None},
              {k for k, v in deltas[1].items() if v is None})
    st.commit(1)
    segment = st.log.store.get("epoch_000000000001.seg")
    assert segment == native_bytes
    assert _commit_span(1) == {"tables": 1, "rows": 4,
                               "bytes": len(segment), "native": 0}
    st2 = DurableStateStore(str(tmp_path))
    assert dict(st2.iter_table(1)) == {b"k1": b"v1", b"k3": b"v3"}
