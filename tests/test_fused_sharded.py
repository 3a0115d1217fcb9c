"""Mesh-sharded fused epochs (ops/fused_sharded.py + parallel/fused.py):
one dispatch per epoch across the virtual 8-device mesh, bit-exact vs the
solo fused path — merged group values, flush churn (U-/U+ retraction
pairs included), probe emissions, checkpoint export → kill → import, and
mesh-resize re-shard by vnode replay. Plus the mesh-topology recovery gap
(8-device-saved → 4-device-reopened refuses loudly) and the
[streaming] mesh_shape / --mesh opt-in knobs."""

import argparse
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.common import INT64, TIMESTAMP, chunk_to_rows
from risingwave_tpu.common.config import MeshUnavailableError
from risingwave_tpu.common.dispatch_count import count_dispatches
from risingwave_tpu.common.types import Field, Schema
from risingwave_tpu.connector import NexmarkConfig
from risingwave_tpu.connector.nexmark import DeviceBidGenerator
from risingwave_tpu.expr import Literal, call, col
from risingwave_tpu.expr.agg import agg as agg_call, count_star
from risingwave_tpu.ops.fused_epoch import (
    fused_source_agg_epoch, fused_source_join_epoch,
)
from risingwave_tpu.ops.grouped_agg import AggCore
from risingwave_tpu.ops.interval_join import IntervalJoinCore
from risingwave_tpu.parallel.fused import (
    ShardedFusedAgg, ShardedFusedJoin, load_shard_states,
    reshard_join_payloads,
)
from risingwave_tpu.parallel.sharded_agg import make_mesh

CAP = 256
N_DEV = 8
Q5_WINDOW = 1_000_000
Q7_WINDOW = 5_000

Q5_EPOCH_FN = "sharded_agg_epoch.<locals>.epoch"
Q7_EPOCH_FN = "sharded_join_epoch.<locals>.epoch"


def _q5_parts(table_capacity=1 << 12):
    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(Q5_WINDOW, INT64)),
        col(0, INT64),
        col(2, INT64),
    ]
    core = AggCore([INT64, INT64], [0, 1],
                   [count_star(), agg_call("max", 2, INT64)],
                   table_capacity, CAP)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CAP))
    return exprs, core, gen.chunk_fn()


def _q7_parts(n_buckets=512, lane_width=64):
    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(Q7_WINDOW, INT64)),
        col(0, INT64),
        col(2, INT64),
    ]
    probe_schema = Schema((
        Field("window_start", TIMESTAMP), Field("auction", INT64),
        Field("price", INT64)))
    core = IntervalJoinCore(probe_schema, ts_col=0, val_col=2,
                            window_us=Q7_WINDOW, n_buckets=n_buckets,
                            lane_width=lane_width)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CAP))
    return exprs, core, gen.chunk_fn()


def _agg_groups(state_h):
    """{key: (lanes...)} of one solo-shaped host AggState."""
    out = {}
    occ = np.asarray(state_h.table.occupied)
    live = np.asarray(state_h.lanes[0]) > 0
    kd = [np.asarray(x) for x in state_h.table.key_data]
    km = [np.asarray(x) for x in state_h.table.key_mask]
    lanes = [np.asarray(x) for x in state_h.lanes]
    return {
        tuple(kd[c][s].item() if km[c][s] else None
              for c in range(len(kd))):
        tuple(l[s].item() for l in lanes)
        for s in np.nonzero(occ & live)[0]
    }


def _rows(chunks, schema):
    out = []
    for c in chunks:
        out.extend(chunk_to_rows(c, schema, with_ops=True, physical=True))
    return sorted(out)


def _solo_q5_epoch_and_flush(solo, core, state, start, key, k):
    """The solo fused q5 epoch + the executor-identical flush: returns
    (state, flush chunks)."""
    probe = jax.jit(lambda st: (jnp.stack(
        [core.flush_rank(st)[-1], st.overflow.astype(jnp.int32)]),
        core.flush_rank(st)))
    gather = jax.jit(core.gather_flush_chunk)
    finish = jax.jit(core.finish_flush)
    state = solo(state, jnp.int64(start), key, k)
    packed, rank = probe(state)
    n_dirty, overflow = (int(x) for x in jax.device_get(packed))
    assert not overflow
    chunks = []
    lo = 0
    while lo < n_dirty:
        chunks.append(gather(state, rank, jnp.int64(lo)))
        lo += core.groups_per_chunk
    return finish(state), chunks


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= N_DEV, "conftest must force 8 CPU devices"
    return make_mesh(N_DEV)


# ---------------------------------------------------------------------------
# q5: bit-exact state + flush churn vs the solo fused path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_shards,k", [(8, 8), (4, 6), (1, 4)])
def test_sharded_agg_bit_exact_vs_solo(mesh8, n_shards, k):
    """Merged per-group values AND the flush churn multiset (U-/U+
    retraction pairs included) equal the solo fused epoch's over two
    epochs — for full meshes, partial meshes and the 1-shard edge, with
    k both divisible and not divisible by the shard count."""
    exprs, core, chunk_fn = _q5_parts()
    mesh = mesh8 if n_shards == N_DEV else make_mesh(n_shards)
    sf = ShardedFusedAgg(mesh, core, chunk_fn, exprs, CAP)
    solo = fused_source_agg_epoch(chunk_fn, exprs, core, CAP,
                                  donate=False)
    flush_schema = Schema(
        (Field("ws", INT64), Field("auction", INT64),
         Field("cnt", INT64), Field("mx", INT64)))
    st = core.init_state()
    start = 0
    for epoch in range(2):
        key = jax.random.fold_in(jax.random.PRNGKey(7), epoch)
        sf.run_epoch(start, key, k)
        got_chunks = sf.flush()
        st, want_chunks = _solo_q5_epoch_and_flush(
            solo, core, st, start, key, k)
        start += k * CAP
        # epoch 2's churn retracts epoch 1's rows: U-/U+ pairs
        assert _rows(got_chunks, flush_schema) == \
            _rows(want_chunks, flush_schema)
    merged = sf.merged_group_values()
    want = _agg_groups(jax.device_get(st))
    assert merged == want and len(merged) > 10


def test_sharded_agg_route_overflow_grows_and_stays_exact(mesh8):
    """NEXmark's hot-auction skew overflows a width-1 receive buffer;
    the driver must grow + retry from the untouched pre-epoch state and
    still produce the solo-exact result."""
    exprs, core, chunk_fn = _q5_parts()
    sf = ShardedFusedAgg(mesh8, core, chunk_fn, exprs, CAP, recv_width=1)
    solo = fused_source_agg_epoch(chunk_fn, exprs, core, CAP,
                                  donate=False)
    key = jax.random.PRNGKey(3)
    sf.run_epoch(0, key, 8)
    sf.flush()
    assert sf.route_grows > 0 and sf.recv_width > 1
    st = solo(core.init_state(), jnp.int64(0), key, 8)
    assert sf.merged_group_values() == _agg_groups(jax.device_get(st))


# ---------------------------------------------------------------------------
# q7: probe emissions + flush churn vs the solo fused join epoch
# ---------------------------------------------------------------------------


def _solo_q7_epoch_rows(solo, core, state, start, key, k):
    from risingwave_tpu.common.chunk import (
        flatten_shards, gather_units_window,
    )
    gather = jax.jit(core.gather_flush,
                     static_argnames=("out_capacity",))
    pgather = jax.jit(lambda po, lo: gather_units_window(
        flatten_shards(po), lo, CAP))
    (state, probe_out, del_m, ins_m, old_emitted, packed) = solo(
        state, jnp.int64(start), key, k)
    n_flush, ovf, clobber, sawdel, n_probe = (
        int(x) for x in jax.device_get(packed))
    assert not (ovf or clobber or sawdel)
    probe_chunks, churn_chunks = [], []
    lo = 0
    while lo < n_probe:
        probe_chunks.append(pgather(probe_out, jnp.int64(lo)))
        lo += CAP // 2
    lo = 0
    while lo < n_flush:
        churn_chunks.append(gather(state, del_m, ins_m, old_emitted,
                                   jnp.int64(lo), out_capacity=CAP))
        lo += CAP
    return state, probe_chunks, churn_chunks


@pytest.mark.parametrize("n_shards", [8, 4])
def test_sharded_join_bit_exact_vs_solo(mesh8, n_shards):
    """Two epochs of the q7 shape: epoch 1 builds per-window maxes,
    epoch 2 emits probe matches against them AND the flush churn
    (delete-vs-old-max / insert-vs-new-max) — every emission surface's
    multiset must equal the solo fused join epoch's."""
    exprs, core, chunk_fn = _q7_parts()
    mesh = mesh8 if n_shards == N_DEV else make_mesh(n_shards)
    sf = ShardedFusedJoin(mesh, core, chunk_fn, exprs, CAP)
    solo = fused_source_join_epoch(chunk_fn, exprs, core, CAP,
                                   donate=False)
    st = core.init_state()
    start = 0
    saw_probe = saw_churn = False
    for epoch in range(2):
        key = jax.random.fold_in(jax.random.PRNGKey(11), epoch)
        sf.run_epoch(start, key, 8)
        got_probe, got_churn = sf.flush(out_capacity=CAP)
        st, want_probe, want_churn = _solo_q7_epoch_rows(
            solo, core, st, start, key, 8)
        start += 8 * CAP
        assert _rows(got_probe, core.out_schema) == \
            _rows(want_probe, core.out_schema)
        assert _rows(got_churn, core.out_schema) == \
            _rows(want_churn, core.out_schema)
        saw_probe |= bool(want_probe)
        saw_churn |= bool(want_churn)
    assert saw_churn          # the build side actually flushed
    # per-shard state equals the solo state bucket-for-bucket: every
    # solo-resident window must appear identically on exactly one shard
    host = jax.device_get(sf.stacked)
    solo_h = jax.device_get(st)
    nb = core.n_buckets
    solo_live = {
        int(w): b for b, w in enumerate(np.asarray(solo_h.win_id))
        if w >= 0 and solo_h.fill[b] > 0
    }
    found = 0
    for s in range(sf.n):
        win = np.asarray(host.win_id[s])
        for b in np.nonzero(win >= 0)[0]:
            w = int(win[b])
            if w not in solo_live or host.fill[s][b] == 0:
                continue
            sb = solo_live[w]
            assert int(host.fill[s][b]) == int(solo_h.fill[sb])
            assert int(host.cur_max[s][b]) == int(solo_h.cur_max[sb])
            W = int(host.fill[s][b])
            for c in range(len(host.row_data)):
                np.testing.assert_array_equal(
                    np.asarray(host.row_data[c][s][b][:W]),
                    np.asarray(solo_h.row_data[c][sb][:W]))
            found += 1
    assert found == len(solo_live) > 0


# ---------------------------------------------------------------------------
# dispatch-count regression: exactly 1 dispatch per sharded epoch,
# independent of shard count and k
# ---------------------------------------------------------------------------


def _nongather_total(counter):
    return sum(n for name, n in counter.counts.items()
               if "gather" not in name)


@pytest.mark.parametrize("n_shards", [2, 4, 8])
def test_sharded_agg_epoch_dispatch_count(n_shards):
    with count_dispatches() as c:
        exprs, core, chunk_fn = _q5_parts()
        sf = ShardedFusedAgg(make_mesh(n_shards), core, chunk_fn, exprs,
                             CAP, recv_width=n_shards)
        key = jax.random.PRNGKey(17)
        sf.run_epoch(0, key, 4)
        sf.flush()
        c.reset()
        sf.run_epoch(4 * CAP, key, 4)
        assert c.counts[Q5_EPOCH_FN] == 1
        sf.flush()
        n4 = _nongather_total(c)
        c.reset()
        sf.run_epoch(8 * CAP, key, 8)
        assert c.counts[Q5_EPOCH_FN] == 1
        sf.flush()
        n8 = _nongather_total(c)
        assert n4 == n8   # per-epoch dispatches independent of k


@pytest.mark.parametrize("n_shards", [4, 8])
def test_sharded_join_epoch_dispatch_count(n_shards):
    with count_dispatches() as c:
        exprs, core, chunk_fn = _q7_parts()
        sf = ShardedFusedJoin(make_mesh(n_shards), core, chunk_fn, exprs,
                              CAP, recv_width=n_shards)
        key = jax.random.PRNGKey(19)
        sf.run_epoch(0, key, 4)
        sf.flush(out_capacity=CAP)
        c.reset()
        sf.run_epoch(4 * CAP, key, 4)
        assert c.counts[Q7_EPOCH_FN] == 1
        sf.flush(out_capacity=CAP)
        n4 = _nongather_total(c)
        c.reset()
        sf.run_epoch(8 * CAP, key, 8)
        assert c.counts[Q7_EPOCH_FN] == 1
        sf.flush(out_capacity=CAP)
        n8 = _nongather_total(c)
        assert n4 == n8


# ---------------------------------------------------------------------------
# checkpoint export → kill → import, and mesh-resize re-shard
# ---------------------------------------------------------------------------


def test_sharded_agg_checkpoint_cycle_and_reshard(mesh8):
    """Checkpoint the 8-shard state through a real HashAggExecutor
    persistence engine into one shared state table, 'kill' it, then
    recover TWICE — onto 8 shards and onto a 4-shard mesh — by replaying
    the vnode mapping over the committed rows. Both continuations must
    match the solo path exactly."""
    from risingwave_tpu.storage.state_store import MemoryStateStore
    from risingwave_tpu.storage.state_table import StateTable
    from risingwave_tpu.stream import HashAggExecutor, ProjectExecutor
    from risingwave_tpu.stream.hash_agg import agg_state_schema
    from risingwave_tpu.stream.source import MockSource
    from risingwave_tpu.connector import BID_SCHEMA

    exprs, core, chunk_fn = _q5_parts()
    proj = ProjectExecutor(MockSource(BID_SCHEMA, []), exprs,
                           names=("ws", "auction", "price"))
    store = MemoryStateStore()
    st_table = StateTable(
        store, 7,
        agg_state_schema([proj.schema[0], proj.schema[1]],
                         core.agg_calls), [0, 1])
    engine = HashAggExecutor(proj, [0, 1], list(core.agg_calls),
                             state_table=None, table_capacity=1 << 12,
                             out_capacity=CAP)
    engine.state_table = st_table

    sf = ShardedFusedAgg(mesh8, core, chunk_fn, exprs, CAP)
    key = jax.random.PRNGKey(5)
    sf.run_epoch(0, key, 8)
    sf.flush()
    sf.checkpoint(engine, epoch=2)
    store.commit(2)
    committed = sf.merged_group_values()

    solo = fused_source_agg_epoch(chunk_fn, exprs, core, CAP,
                                  donate=False)
    st = solo(core.init_state(), jnp.int64(0), key, 8)
    key2 = jax.random.fold_in(jax.random.PRNGKey(5), 1)
    st = solo(st, jnp.int64(8 * CAP), key2, 8)
    want = _agg_groups(jax.device_get(st))

    for new_n in (8, 4):    # same-size recovery AND shrink re-shard
        rows = list(st_table.scan_all())
        states = load_shard_states(core, rows, new_n)
        sf2 = ShardedFusedAgg(make_mesh(new_n), core, chunk_fn, exprs,
                              CAP, states=states)
        assert sf2.merged_group_values() == committed
        sf2.run_epoch(8 * CAP, key2, 8)
        sf2.flush()
        assert sf2.merged_group_values() == want


def test_sharded_join_checkpoint_cycle_and_reshard(mesh8):
    """Per-shard IntervalJoinCore payloads round-trip through
    export_host → import_host bit-exactly, and re-bucket onto a 4-shard
    mesh (reshard_join_payloads replays the vnode mapping over each
    resident window) with identical downstream emissions."""
    exprs, core, chunk_fn = _q7_parts()
    sf = ShardedFusedJoin(mesh8, core, chunk_fn, exprs, CAP)
    key = jax.random.PRNGKey(13)
    sf.run_epoch(0, key, 8)
    sf.flush(out_capacity=CAP)
    payloads = sf.export_host()

    key2 = jax.random.fold_in(jax.random.PRNGKey(13), 1)

    def continue_and_rows(sj):
        sj.run_epoch(8 * CAP, key2, 8)
        probe, churn = sj.flush(out_capacity=CAP)
        return (_rows(probe, core.out_schema),
                _rows(churn, core.out_schema))

    # same-size import cycle
    sf2 = ShardedFusedJoin(mesh8, core, chunk_fn, exprs, CAP)
    sf2.import_host(payloads)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(sf.stacked)),
                    jax.tree_util.tree_leaves(jax.device_get(sf2.stacked))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    want = continue_and_rows(sf2)
    assert want[0] or want[1]

    # a different window config must refuse (win_ids copied verbatim
    # would relabel + misroute every resident window)
    other = IntervalJoinCore(core.probe_schema, ts_col=0, val_col=2,
                             window_us=2 * Q7_WINDOW, n_buckets=512,
                             lane_width=64)
    with pytest.raises(ValueError, match="window"):
        reshard_join_payloads(core, payloads, other, 4)

    # shrink to 4 shards: re-bucketed state, identical emissions
    new_core = IntervalJoinCore(core.probe_schema, ts_col=0, val_col=2,
                                window_us=Q7_WINDOW, n_buckets=512,
                                lane_width=64)
    re = reshard_join_payloads(core, payloads, new_core, 4)
    sf4 = ShardedFusedJoin(make_mesh(4), new_core, chunk_fn, exprs, CAP)
    sf4.import_host(re)
    assert continue_and_rows(sf4) == want


# ---------------------------------------------------------------------------
# Session integration: routing, parity with the co-scheduled path,
# durability, refusal in both directions
# ---------------------------------------------------------------------------

SRC_SQL = """CREATE SOURCE bid (auction BIGINT, bidder BIGINT,
    price BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
    extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'bid')"""
MV_SQL = ("CREATE MATERIALIZED VIEW {n} AS SELECT auction, count(*) AS c "
          "FROM bid GROUP BY auction")


def _session(tmp_path=None, mesh_n=0, coschedule=True, **kw):
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig
    return Session(
        config=BuildConfig(coschedule=coschedule,
                           mesh=make_mesh(mesh_n) if mesh_n else None,
                           agg_table_capacity=1 << 12),
        source_chunk_capacity=CAP,
        data_dir=str(tmp_path) if tmp_path else None, **kw)


def test_session_routes_and_matches_cosched_path():
    """A mesh+coschedule session routes the eligible MV down the
    sharded-fused path; its MV contents are bit-identical to the
    co-scheduled (mesh-less) session's — same CREATE, same seed, same
    device-generated stream, different placement only."""
    s = _session(mesh_n=8)
    try:
        s.run_sql(SRC_SQL)
        s.run_sql(MV_SQL.format(n="m0"))
        assert s.metrics()["shardfused"]["m0"]["shards"] == 8
        assert not s.metrics()["coschedule"]["jobs"]
        # ineligible shape falls back to the mesh EXECUTOR path
        s.run_sql("CREATE MATERIALIZED VIEW raw AS SELECT auction, price "
                  "FROM bid")
        assert "raw" not in s.metrics()["shardfused"]
        for _ in range(3):
            s.tick()
        got = sorted(s.run_sql("SELECT auction, c FROM m0"))
    finally:
        s.close()
    c = _session(mesh_n=0)
    try:
        c.run_sql(SRC_SQL)
        c.run_sql(MV_SQL.format(n="m0"))
        assert c.metrics()["coschedule"]["jobs"] == 1
        for _ in range(3):
            c.tick()
        want = sorted(c.run_sql("SELECT auction, c FROM m0"))
    finally:
        c.close()
    assert got == want and len(got) > 10


def test_session_shardfused_recovery_and_mesh_resize(tmp_path):
    s = _session(tmp_path, mesh_n=8, checkpoint_frequency=2)
    s.run_sql(SRC_SQL)
    s.run_sql(MV_SQL.format(n="m0"))
    for _ in range(5):
        s.tick()
    committed = dict(s.run_sql("SELECT auction, c FROM m0"))
    s.close()

    # reopen on a SMALLER mesh: committed rows re-shard by vnode replay
    s2 = _session(tmp_path, mesh_n=4, checkpoint_frequency=2)
    try:
        assert s2.metrics()["shardfused"]["m0"]["shards"] == 4
        assert dict(s2.run_sql("SELECT auction, c FROM m0")) == committed
        base = sum(committed.values())
        for _ in range(3):
            s2.tick()
        # deterministic cursor resume: exactly 3 * CAP more rows
        assert s2.run_sql("SELECT sum(c) FROM m0") == [(base + 3 * CAP,)]
    finally:
        s2.close()


def test_session_shardfused_refusal_both_directions(tmp_path):
    from risingwave_tpu.frontend.session import SqlError
    s = _session(tmp_path, mesh_n=4, checkpoint_frequency=2)
    s.run_sql(SRC_SQL)
    s.run_sql(MV_SQL.format(n="m0"))
    s.tick()
    s.close()
    # sharded-fused MV reopened WITHOUT a mesh: refuse loudly
    with pytest.raises(SqlError, match="mesh-sharded fused"):
        _session(tmp_path, mesh_n=0, coschedule=False)

    # reverse direction: a co-scheduled (mesh-less) MV reopened WITH a
    # mesh must not be captured by the sharded-fused path — its durable
    # layout decodes on the coschedule path only, which refuses since
    # the mesh session cannot host it
    d2 = tmp_path / "cosched"
    c = _session(d2, mesh_n=0, checkpoint_frequency=2)
    c.run_sql(SRC_SQL)
    c.run_sql(MV_SQL.format(n="m1"))
    c.tick()
    c.close()
    with pytest.raises(SqlError, match="co-scheduled"):
        _session(d2, mesh_n=4)


def test_session_drop_cleans_shardfused(tmp_path):
    s = _session(tmp_path, mesh_n=4)
    try:
        s.run_sql(SRC_SQL)
        s.run_sql(MV_SQL.format(n="m0"))
        s.tick()
        s.run_sql("DROP MATERIALIZED VIEW m0")
        assert not s.metrics()["shardfused"]
        s.tick()
        # a re-CREATE after the drop is a NEW sharded-fused job
        s.run_sql(MV_SQL.format(n="m0"))
        s.tick()
        assert s.metrics()["shardfused"]["m0"]["epochs_run"] >= 1
    finally:
        s.close()


# ---------------------------------------------------------------------------
# mesh-topology recovery gap: 8-device-saved → 4-device-reopened
# ---------------------------------------------------------------------------


def _run_in_n_device_proc(n_devices: int, script: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices}")
    res = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_mesh_recovery_gap_refuses_loudly(tmp_path):
    """An 8-device-saved reschedule config reopened in a 4-device
    process must refuse loudly (MeshUnavailableError), not silently
    recover unsharded; allow_reshard=True is the explicit escape."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig, config_to_json

    d = str(tmp_path / "db")
    s = Session(data_dir=d)
    s.run_sql("CREATE TABLE t (k BIGINT PRIMARY KEY, v BIGINT)")
    s.run_sql("CREATE MATERIALIZED VIEW g AS "
              "SELECT k % 4 AS grp, sum(v) AS sv FROM t GROUP BY k % 4")
    for i in range(8):
        s.run_sql(f"INSERT INTO t VALUES ({i}, {i * 10})")
    s.flush()
    s.reschedule("g", BuildConfig(mesh=make_mesh(8)))
    s.close()

    cfg_json = config_to_json(BuildConfig(mesh=make_mesh(8)))
    script = f"""
import json
out = {{}}
from risingwave_tpu.common.config import MeshUnavailableError
from risingwave_tpu.frontend.build import config_from_json
try:
    config_from_json({cfg_json!r})
    out["raised"] = False
except MeshUnavailableError as e:
    out["raised"] = True
    out["msg"] = str(e)
cfg = config_from_json({cfg_json!r}, allow_reshard=True)
out["reshard_devices"] = int(cfg.mesh.devices.size)
from risingwave_tpu.frontend import Session
try:
    Session(data_dir={d!r})
    out["session_raised"] = False
except RuntimeError as e:
    out["session_raised"] = True
    out["session_msg"] = str(e)
# the operator's explicit escape: consented shrink onto 4 devices
import os
os.environ["RWTPU_ALLOW_MESH_RESHARD"] = "1"
s = Session(data_dir={d!r})
out["reshard_rows"] = sorted(s.mv_rows("g"))
s.close()
print(json.dumps(out))
"""
    out = _run_in_n_device_proc(4, script)
    assert out["raised"] and "8 devices" in out["msg"]
    assert out["reshard_devices"] == 4          # explicit re-shard path
    assert out["session_raised"]                # loud, not a warning
    assert "reschedule g" in out["session_msg"]
    assert "RWTPU_ALLOW_MESH_RESHARD" in out["session_msg"]
    # the env escape actually reopens the job, re-sharded, rows intact
    want = sorted([i, sum(j * 10 for j in range(8) if j % 4 == i)]
                  for i in range(4))
    assert [list(r) for r in out["reshard_rows"]] == want


# ---------------------------------------------------------------------------
# opt-in without code: [streaming] mesh_shape and --mesh
# ---------------------------------------------------------------------------


def test_cli_mesh_flag_builds_mesh_config():
    from risingwave_tpu.cli import _build_session
    args = argparse.Namespace(data_dir=None, fragment_parallelism=1,
                              mesh=2)
    s = _build_session(args)
    try:
        assert s.config.mesh is not None
        assert s.config.mesh.devices.size == 2
    finally:
        s.close()


def test_cli_mesh_flag_parses():
    import risingwave_tpu.cli as cli
    from unittest import mock
    captured = {}

    def fake_playground(args):
        captured["mesh"] = args.mesh
        return 0

    with mock.patch.object(cli, "_playground", fake_playground):
        assert cli.main(["playground", "--mesh", "4"]) == 0
    assert captured["mesh"] == 4


def test_rw_config_mesh_shape_flows_to_build_config():
    from risingwave_tpu.common.config import load_config
    from risingwave_tpu.frontend.session import Session
    cfg = load_config(**{"streaming.mesh_shape": 2,
                         "streaming.coschedule": True})
    s = Session(rw_config=cfg)
    try:
        assert s.config.mesh is not None
        assert s.config.mesh.devices.size == 2
        assert s.config.coschedule
    finally:
        s.close()
    # mesh_shape = 1 builds a 1-device mesh, agreeing with `--mesh 1`
    # (a durable job created either way recovers under the other)
    s1 = Session(rw_config=load_config(**{"streaming.mesh_shape": 1}))
    try:
        assert s1.config.mesh is not None
        assert s1.config.mesh.devices.size == 1
    finally:
        s1.close()


def test_make_mesh_refuses_when_short_of_devices():
    with pytest.raises(MeshUnavailableError, match="devices"):
        make_mesh(len(jax.devices()) + 1)
