"""q5-core at default settings on a four-chip mesh (ISSUE 31): the
deployment ``benchmark/configs/nexmark-q5core-exec-mesh4.json`` at its
tiny sizes, on the virtual CPU devices ``conftest.py`` forces.

``[streaming] mesh_shape = 4`` and nothing else puts the grouped agg on
``ShardedHashAggExecutor``: every chunk split over the mesh, rows routed
to the shard that owns the vnode of their group key by an in-program
all-to-all, state sharded on the leading axis. The mesh must not change
one row: every comparison here is with the benchmark's plain reference
(``benchmark/reference/q5core_host_stream.py``, numpy only) or with a
host group-by, and the spans and counts the sharded executor records are
held as the contract the benchmark's ``mesh_*`` readers key on.
"""

import json
import os
import random
import sys

import jax
import numpy as np
import pytest

from risingwave_tpu.common import tracing
from risingwave_tpu.common.config import load_config
from risingwave_tpu.frontend import Session
from risingwave_tpu.native import codec as native_codec
from risingwave_tpu.parallel.executors import ShardedHashAggExecutor
from risingwave_tpu.stream import state_delta
from risingwave_tpu.stream.metrics import iter_executors

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench_run  # noqa: E402
from benchmark import system  # noqa: E402
from benchmark.reference import q5core_host_stream as reference  # noqa: E402

SEED = 3_000_000_019        # above 2**31, as the driver's are
FREQUENCY = 10


def tiny_config(mesh_shape=4) -> dict:
    """The benchmark's configuration at its rehearsal sizes; ``mesh_shape``
    None is the one-chip deployment of the same sizes."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark-q5core-exec-mesh4.json")) as f:
        config = bench_run.tiny_sizes(json.load(f))
    config["rw_toml"] = dict(config["rw_toml"])
    if mesh_shape is None:
        del config["rw_toml"]["streaming.mesh_shape"]
    else:
        config["rw_toml"]["streaming.mesh_shape"] = mesh_shape
    return config


def sharded_aggs(session) -> list:
    return [ex for job in session.jobs.values()
            for ex in iter_executors(job.pipeline)
            if isinstance(ex, ShardedHashAggExecutor)]


def drive(config: dict, data_dir: str, barriers: int, create=True):
    sut = system.System(config, data_dir, SEED)
    if create:
        sut.create()
    for _ in range(barriers):
        sut.barrier()
    return sut


# -- (a), (b): the configuration against the reference and one chip ----------

@pytest.fixture(scope="module")
def mesh_run(tmp_path_factory):
    """30 barriers (3 checkpoints) of the configuration at tiny sizes:
    rows, ledger, the spans of every barrier, and what the shards hold."""
    config = tiny_config()
    tracing.GLOBAL_TRACE.clear()
    # the deployment's checkpoint window is 8,192 rows of a 2^19-slot
    # shard; the rehearsal's 2,048-slot shard would be its own window
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(state_delta, "DELTA_WINDOW_ROWS", 256)
        sut = drive(config, str(tmp_path_factory.mktemp("mesh4")), 30)
    (ex,) = sharded_aggs(sut.session)
    out = {"config": config, "rows": sut.read_back(),
           "history": sut.barrier_history(),
           "committed_epoch": sut.committed_epoch(),
           "spans": tracing.epoch_spans(),
           "state": jax.device_get(ex.agg.state), "n": ex.n}
    sut.close()
    return out


def test_config_at_tiny_sizes_equals_the_reference(mesh_run):
    config = mesh_run["config"]
    assert config["rw_toml"]["streaming.mesh_shape"] == 4
    assert "streaming.coschedule" not in config["rw_toml"]
    history = mesh_run["history"]
    assert len(history) == 30
    assert sum(h["checkpoint"] for h in history) == 3
    numbers = reference.compare(reference.expected(config, SEED, 30),
                                mesh_run["rows"])
    assert numbers["rows_expected"] > 500
    assert {k: v for k, v in numbers.items() if k != "rows_expected"} \
        == {"rows_wrong": 0, "events_off": 0}
    assert bench_run.generic_numbers(
        history, mesh_run["committed_epoch"], FREQUENCY) == {
            "barriers_failed": 0, "checkpoints_missing": 0,
            "committed_epoch_lag": 0}


def test_mesh_rows_equal_one_chips(mesh_run, tmp_path):
    one = drive(tiny_config(None), str(tmp_path), 30)
    assert not sharded_aggs(one.session)
    rows = one.read_back()
    one.close()
    assert sorted(mesh_run["rows"]) == sorted(rows)


def test_the_control_is_not_correct(mesh_run):
    config = mesh_run["config"]
    broken = reference.expected(config, SEED, 30,
                                broken=config["control"])["rows"]
    numbers = reference.compare(reference.expected(config, SEED, 30), broken)
    assert numbers["rows_wrong"] > 0


# -- (f): one shard per group ------------------------------------------------

def test_every_group_lives_on_exactly_one_shard(mesh_run):
    """The stated guarantee: a group's slot is occupied on exactly one
    shard, the one that owns the vnode of its key."""
    import jax.numpy as jnp
    from risingwave_tpu.common.chunk import Column
    from risingwave_tpu.common.hashing import vnode_of, vnode_to_shard

    st, n = mesh_run["state"], mesh_run["n"]
    seen: dict = {}
    for s in range(n):
        occ = np.nonzero(np.asarray(st.table.occupied[s]))[0]
        keys = [np.asarray(kd[s])[occ] for kd in st.table.key_data]
        owner = np.asarray(vnode_to_shard(vnode_of(
            [Column(jnp.asarray(k), jnp.ones(len(occ), bool))
             for k in keys]), n))
        assert (owner == s).all(), f"shard {s} holds another shard's group"
        for key in zip(*(k.tolist() for k in keys)):
            assert key not in seen, f"{key} on shards {seen[key]} and {s}"
            seen[key] = s
    assert len(seen) == len(mesh_run["rows"])
    assert set(seen) == {(w, a) for w, a, _n in mesh_run["rows"]}
    assert len(set(seen.values())) == n          # every shard owns some


# -- (e): spans and counts ---------------------------------------------------

def window_spans(mesh_run):
    by_epoch = mesh_run["spans"]
    for h in mesh_run["history"]:
        yield h, by_epoch[h["epoch"]]


def only(spans, name):
    found = [s for s in spans if s["name"] == name]
    assert len(found) == 1, (name, sorted({s["name"] for s in spans}))
    return found[0]


def test_sharded_executor_spans_on_every_barrier(mesh_run):
    config = mesh_run["config"]
    k = config["chunks_per_tick"]
    per_barrier = k * config["rows_per_chunk"]["bid"]
    seen, hot_share = 0, []
    state_bytes = sum(x.nbytes for x in
                      jax.tree_util.tree_leaves(mesh_run["state"]))
    for h, spans in window_spans(mesh_run):
        ids = {s["id"]: s for s in spans}
        collect = only(spans, "barrier.collect")
        barrier = only(spans, "ShardedHashAgg.barrier")
        chunks = only(spans, "ShardedHashAgg.chunks")
        split = only(spans, "shard.split")
        wait = only(spans, "agg.flush_wait")
        assert barrier["parent"] == chunks["parent"] == split["parent"] \
            == collect["id"]
        assert wait["parent"] == barrier["id"] and wait["wait"] == "device"
        # the split is a roll-up inside the chunk steps' time
        assert split["args"] == {"chunks": k, "transfers": 2 * k}
        assert 0 < split["dur_ns"] <= chunks["dur_ns"]
        assert chunks["args"]["chunks"] == k
        # counted on the device inside the step, fetched with the flush
        args = barrier["args"]
        assert args["rows_routed"] == per_barrier
        hot_share.append(args["rows_routed_max"] / args["rows_routed"])
        deltas = [s for s in spans if s["name"] == "agg.state_delta"]
        if h["checkpoint"]:
            (delta,) = deltas
            assert ids[delta["parent"]] is barrier
            assert delta["args"]["shards"] == 4
            assert delta["args"]["dirty_groups"] > 0
            assert delta["args"]["windows"] >= 1
            # the delta's rows, not the state: 4 shards x 72 B a slot
            assert 0 < delta["args"]["bytes_fetched"] < state_bytes // 8
            if native_codec() is None:
                assert delta["args"]["bytes_staged"] == 0
            else:
                assert delta["args"]["bytes_staged"] > 0
            seen += 1
        else:
            assert not deltas
    assert seen == 3
    # 9 bids in 10 go to the hot auction of the moment, one group on one
    # shard; the hot auction moves on every 1,667 bids, so a 512-bid
    # barrier now and then straddles two of them
    assert np.median(hot_share) >= 0.85
    assert all(0.25 <= share <= 1.0 for share in hot_share)


def test_dirty_groups_of_the_checkpoints_add_up_to_the_mv(mesh_run):
    """No group is born twice in this stream's first 30 barriers within
    one checkpoint interval only: every group is dirty at the checkpoint
    after its birth, so the deltas cover the MV."""
    dirty = sum(s["args"]["dirty_groups"]
                for _h, spans in window_spans(mesh_run) for s in spans
                if s["name"] == "agg.state_delta")
    assert dirty >= len(mesh_run["rows"])


def test_uniform_keys_spread_evenly_over_the_shards():
    """The same counter on keys the vnode map spreads: about a quarter of
    a barrier's rows on the fullest of four shards."""
    tracing.GLOBAL_TRACE.clear()
    s = Session(rw_config=load_config(None, **{
        "streaming.mesh_shape": 4, "streaming.chunk_capacity": 256,
        "streaming.agg_table_capacity": 4096}))
    s.run_sql("CREATE TABLE u (k BIGINT, v BIGINT)")
    s.run_sql("CREATE MATERIALIZED VIEW m AS "
              "SELECT k, count(*) AS n FROM u GROUP BY k")
    rng = random.Random(7)
    keys = [rng.randrange(1 << 40) for _ in range(2000)]
    s.run_sql("INSERT INTO u VALUES "
              + ", ".join(f"({k}, 1)" for k in keys))
    s.flush()
    routed = [sp["args"] for spans in tracing.epoch_spans().values()
              for sp in spans if sp["name"] == "ShardedHashAgg.barrier"
              and sp["args"].get("rows_routed")]
    assert sum(a["rows_routed"] for a in routed) == len(keys)
    share = sum(a["rows_routed_max"] for a in routed) / len(keys)
    assert 0.25 <= share <= 0.32
    assert len(s.mv_rows("m")) == len(set(keys))
    s.close()


# -- (c): recovery, on the same mesh and on another --------------------------

@pytest.mark.parametrize("reopen_mesh", [4, 2])
def test_reopen_after_a_checkpoint_and_tick_on(tmp_path, reopen_mesh):
    """Close after a checkpoint, reopen at ``mesh_shape`` 4 and 2, tick
    on: the MV equals the reference over every event — no group lost,
    doubled or stranded on a shard that no longer owns its vnode."""
    config = tiny_config()
    first = drive(config, str(tmp_path), FREQUENCY + 1)
    while not first.barrier_history()[-1]["checkpoint"]:
        first.barrier()
    history = first.barrier_history()
    assert history[-1]["result"] == "ok"
    assert first.committed_epoch() == history[-1]["epoch"]
    first.close()

    again = drive(tiny_config(reopen_mesh), str(tmp_path), 12, create=False)
    (ex,) = sharded_aggs(again.session)
    assert ex.n == reopen_mesh
    rows = again.read_back()
    state = jax.device_get(ex.agg.state)
    again.close()
    numbers = reference.compare(
        reference.expected(config, SEED, len(history) + 12), rows)
    assert numbers["rows_wrong"] == 0 and numbers["events_off"] == 0
    assert numbers["rows_expected"] > 500
    # every recovered or new group sits on one shard of the NEW mesh
    occupied = sum(int(np.count_nonzero(state.table.occupied[s]))
                   for s in range(reopen_mesh))
    assert occupied == len(rows)


# -- (d): retraction through the exchange ------------------------------------

def test_dml_retraction_on_the_mesh_equals_a_host_group_by():
    """Inserts, updates and deletes under GROUP BY on ``mesh_shape`` 4:
    groups fall to zero rows, leave the MV, and come back."""
    s = Session(rw_config=load_config(None, **{
        "streaming.mesh_shape": 4, "streaming.chunk_capacity": 64,
        "streaming.agg_table_capacity": 1024}))
    s.run_sql("CREATE TABLE t (id BIGINT PRIMARY KEY, g BIGINT, v BIGINT)")
    s.run_sql("CREATE MATERIALIZED VIEW m AS SELECT g, count(*) AS n, "
              "sum(v) AS sv FROM t GROUP BY g")
    assert sharded_aggs(s)
    rng = random.Random(31)
    table: dict = {}

    def host():
        out: dict = {}
        for g, v in table.values():
            n, sv = out.get(g, (0, 0))
            out[g] = (n + 1, sv + v)
        return sorted((g, n, sv) for g, (n, sv) in out.items())

    def check():
        s.flush()
        assert sorted(s.mv_rows("m")) == host()

    rows = {i: (rng.randrange(40), rng.randrange(1000)) for i in range(300)}
    s.run_sql("INSERT INTO t VALUES " + ", ".join(
        f"({i}, {g}, {v})" for i, (g, v) in rows.items()))
    table.update(rows)
    check()
    assert len(host()) == 40

    # updates move rows between groups (and so between shards)
    s.run_sql("UPDATE t SET g = g + 7, v = v + 1 WHERE id < 120")
    for i in range(120):
        g, v = table[i]
        table[i] = (g + 7, v + 1)
    check()

    # whole groups fall to zero ...
    s.run_sql("DELETE FROM t WHERE g < 20")
    gone = {g for g, _v in table.values() if g < 20}
    table = {i: gv for i, gv in table.items() if gv[0] >= 20}
    check()
    assert gone and not gone & {g for g, _n, _sv in host()}

    # ... and come back
    back = {1000 + j: (g, 5) for j, g in enumerate(sorted(gone))}
    s.run_sql("INSERT INTO t VALUES " + ", ".join(
        f"({i}, {g}, {v})" for i, (g, v) in back.items()))
    table.update(back)
    check()
    assert gone <= {g for g, _n, _sv in host()}

    s.run_sql("DELETE FROM t")
    table.clear()
    check()
    assert s.mv_rows("m") == []
    s.close()
