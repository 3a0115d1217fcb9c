"""Chip-less compile checks for the TPU path.

Two tiers, neither executes anything:

* **compile for a described v5e** (the ``topo`` / ``one_chip`` fixtures):
  the chip's own compiler — Mosaic for the Pallas kernels, XLA:TPU for a
  fused epoch — runs here against a *described* ``v5e:2x2`` topology, so
  what it refuses (layouts, unaligned slices, VMEM, 64-bit types) fails
  in CI at no chip time. Interpret mode shows none of that.
* **lower for platform "tpu"**:
  ``jax.jit(...).trace(...).lower(lowering_platforms=("tpu",))`` runs the
  Pallas→Mosaic lowering and StableHLO emission for every fused surface —
  kernel tracing errors, unsupported ops and block-spec/shape mismatches
  surface here; the device compile is the tier above.

A compile that passes is not a chip run: ``chip_smoke.py`` is the chip run.
"""

import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from risingwave_tpu.ops.interval_join import interval_match_pallas_call
from risingwave_tpu.ops.pallas_rank import rank_totals_pallas_call


# ---------------------------------------------------------------------------
# Tier 1: compile for a described v5e. The topology is described INSIDE a
# fixture (never at import or collection time): only one process may load
# the TPU library, and under xdist every worker imports every test file.
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A chip-less compile is written to the persistent cache but cannot
    be read back without a chip (the next run would warn and recompile):
    keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile_for(fn, *shapes):
    with _no_persistent_cache():
        return fn.lower(*shapes).compile()


@pytest.mark.parametrize("n,w", [(4096, 128), (1024, 16), (1024, 1), (768, 1)])
def test_rank_kernel_compiles_for_v5e(one_chip, n, w):
    """Mosaic accepts the rank kernel at the bench shape, at the SQL
    defaults (chunk_capacity 1024 × join_bucket_width 16) and at the two
    join inputs of the benchmark's nexmark-q101 (1,024-row flush chunks
    and 768-row auction chunks at join_bucket_width 1)."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _compile_for(
        jax.jit(lambda a, m: rank_totals_pallas_call(a, m)),
        s((n,), jnp.int32), s((n, w), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()


def test_interval_match_kernel_compiles_for_v5e(one_chip):
    nb, w = 1 << 15, 128                   # Q7_BUCKETS x Q7_LANES
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = _compile_for(
        jax.jit(lambda v, o, om, ol, nm, nl:
                interval_match_pallas_call(v, o, om, ol, nm, nl)),
        s((nb, w), jnp.int64), s((nb, w), jnp.bool_),
        s((nb,), jnp.int64), s((nb,), jnp.bool_),
        s((nb,), jnp.int64), s((nb,), jnp.bool_))
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_agg_epoch_compiles_for_v5e(one_chip):
    """One small fused source→project→agg epoch (the body every fused
    surface shares) through XLA:TPU, state donated as on the chip."""
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.fused_epoch import fused_source_agg_epoch
    from risingwave_tpu.ops.grouped_agg import AggCore

    cap = 512
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=cap))
    exprs = [call("tumble_start", col(5, TIMESTAMP),
                  Literal(10_000_000, INT64)), col(0, INT64)]
    core = AggCore((INT64, INT64), (0, 1), [count_star()],
                   table_capacity=1 << 12, out_capacity=cap)
    fused = fused_source_agg_epoch(gen.chunk_fn(), exprs, core, cap)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        on_chip, jax.eval_shape(core.init_state))
    key = on_chip(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    start = jax.ShapeDtypeStruct((), jnp.int64, sharding=one_chip)
    compiled = _compile_for(fused, state, start, key, 4)
    assert compiled.memory_analysis() is not None


def test_ckpt_delta_window_compiles_for_v5e_without_a_scatter(one_chip):
    """The checkpoint delta's window at deployment size (the benchmark's
    2^21-slot table, the executor's 8,192-row window): gathers only —
    a scatter over the capacity is what the contract rules out."""
    from risingwave_tpu.common import INT64
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.stream.state_delta import DELTA_WINDOW_ROWS

    core = AggCore((INT64, INT64), (0, 1), [count_star()],
                   table_capacity=1 << 21, out_capacity=4096)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(core.init_state))
    lo = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile_for(
        jax.jit(core.ckpt_delta_window, static_argnums=(2,)),
        state, lo, DELTA_WINDOW_ROWS)
    text = compiled.as_text()
    assert " gather(" in text and " scatter(" not in text


def test_join_ckpt_delta_window_compiles_for_v5e_without_a_scatter(one_chip):
    """The join's window at ``q8_catchup``'s size: the person side's
    ``[2^20, 1]`` arena (four columns with their null masks, occupancy,
    tombstones) read as one flat axis, 8,192 rows a window."""
    from risingwave_tpu.common import INT64, TIMESTAMP, VARCHAR, Schema
    from risingwave_tpu.ops.join_state import (
        JoinCore, JoinType, join_ckpt_delta_window,
    )
    from risingwave_tpu.stream.state_delta import DELTA_WINDOW_ROWS

    person = Schema.of(("id", INT64), ("name", VARCHAR),
                       ("starttime", TIMESTAMP), ("endtime", TIMESTAMP))
    auction = Schema.of(("seller", INT64), ("starttime", TIMESTAMP),
                        ("endtime", TIMESTAMP))
    core = JoinCore(person, auction, [0, 2, 3], [0, 1, 2], JoinType.INNER,
                    key_capacity=1 << 20, bucket_width=1)
    side = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        jax.eval_shape(core.init_state).left)
    assert side.ckpt_dirty.shape == (1 << 20, 1)
    lo = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = _compile_for(
        jax.jit(join_ckpt_delta_window, static_argnums=(2,)),
        side, lo, DELTA_WINDOW_ROWS)
    text = compiled.as_text()
    assert " gather(" in text and " scatter(" not in text
    out = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda s, l: join_ckpt_delta_window(s, l, DELTA_WINDOW_ROWS),
        side, lo))
    assert sorted({x.shape for x in out}) == [(), (DELTA_WINDOW_ROWS,)]


def test_sharded_ckpt_delta_window_stays_on_its_shards(topo):
    """The mesh cell's checkpoint window (``q5core_exec_mesh4_catchup``:
    state ``[4, 2^19]`` sharded on the leading axis) through XLA:TPU for
    the described 2x2 mesh: the one-chip window under ``vmap`` over the
    shard axis, every shard searching and gathering its own rows — no
    collective, no scatter, outputs ``[4, G]`` left where they were made."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from risingwave_tpu.common import INT64
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.parallel.sharded_agg import SHARD_AXIS
    from risingwave_tpu.stream.state_delta import DELTA_WINDOW_ROWS

    n = 4
    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    sharded = NamedSharding(mesh, P(SHARD_AXIS))
    core = AggCore((INT64, INT64), (0, 1), [count_star()],
                   table_capacity=1 << 19, out_capacity=4096)
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharded),
        jax.eval_shape(
            lambda: jax.vmap(lambda _: core.init_state())(jnp.arange(n))))
    window = jax.jit(
        jax.vmap(core.ckpt_delta_window, in_axes=(0, None, None)),
        static_argnums=(2,))
    compiled = _compile_for(window, state, np.int32(0), DELTA_WINDOW_ROWS)
    text = compiled.as_text()
    assert " gather(" in text and " scatter(" not in text
    for collective in ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute"):
        assert collective not in text
    assert all(s.spec == P(SHARD_AXIS) for s in
               jax.tree_util.tree_leaves(compiled.output_shardings))


def test_sharded_agg_step_compiles_for_the_four_chip_mesh(topo):
    """The mesh cell's per-chunk program (``q5core_exec_mesh4_catchup``:
    2^19 slots a shard, a 4,096-row chunk packed [4, k, 1024] by dtype)
    through XLA:TPU for the described 2x2 mesh: the vnode exchange is in
    it as all-to-alls, and a shard's state fits a chip many times over."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from risingwave_tpu.common import INT64
    from risingwave_tpu.common.chunk import Column, StreamChunk
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.parallel.executors import pack_chunk, unpack_like
    from risingwave_tpu.parallel.sharded_agg import (
        SHARD_AXIS, build_sharded_agg_step,
    )

    n, rows = 4, 4096
    mesh = Mesh(np.array(topo.devices), (SHARD_AXIS,))
    assert mesh.devices.size == n
    sharded = NamedSharding(mesh, P(SHARD_AXIS))

    def on_mesh(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharded)

    core = AggCore((INT64, INT64), (0, 1), [count_star()],
                   table_capacity=1 << 19, out_capacity=rows)
    state = jax.tree_util.tree_map(on_mesh, jax.eval_shape(
        lambda: jax.vmap(lambda _: core.init_state())(jnp.arange(n))))

    def col(dtype):
        return jax.ShapeDtypeStruct((rows,), dtype)
    chunk = StreamChunk(col(jnp.int8), col(jnp.bool_),
                        (Column(col(jnp.int64), col(jnp.bool_)),) * 2)
    stacks = jax.eval_shape(lambda c: pack_chunk(c, n), chunk)
    assert sorted((x.shape, str(x.dtype)) for x in stacks) == [
        ((4, 2, 1024), "int64"), ((4, 4, 1024), "int8")]
    step = build_sharded_agg_step(core, mesh, unpack_like(chunk))
    compiled = _compile_for(
        step, state, on_mesh(jax.ShapeDtypeStruct((n,), jnp.int64)),
        tuple(on_mesh(x) for x in stacks))
    assert "all-to-all" in compiled.as_text()
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert 30e6 < per_chip < 45e6          # 72 B a slot x 2^19


# ---------------------------------------------------------------------------
# Tier 2: lower for platform "tpu" (StableHLO + embedded Mosaic payload)
# ---------------------------------------------------------------------------


def _lower_tpu(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def test_rank_kernel_lowers_for_tpu():
    # the bench shapes (N=4096, W=128)
    ident = jnp.zeros(4096, jnp.int32)
    matches = jnp.zeros((4096, 128), jnp.bool_)
    text = _lower_tpu(lambda a, m: rank_totals_pallas_call(a, m),
                      ident, matches)
    assert "tpu_custom_call" in text      # the Mosaic kernel is embedded
    assert "stablehlo" in text


def test_interval_match_kernel_lowers_for_tpu():
    nb, w = 1 << 15, 128                   # Q7_BUCKETS x Q7_LANES
    vals = jnp.zeros((nb, w), jnp.int64)
    occ = jnp.zeros((nb, w), jnp.bool_)
    mx = jnp.zeros(nb, jnp.int64)
    live = jnp.zeros(nb, jnp.bool_)
    text = _lower_tpu(
        lambda v, o, om, ol, nm, nl:
        interval_match_pallas_call(v, o, om, ol, nm, nl),
        vals, occ, mx, live, mx, live)
    assert "tpu_custom_call" in text
    assert "stablehlo" in text


def test_lowering_is_compile_only():
    """The proxy must never execute: lowering a kernel whose EXECUTION
    would fail on CPU still succeeds (no backend dispatch happens)."""
    ident = jnp.zeros(256, jnp.int32)
    matches = jnp.zeros((256, 128), jnp.bool_)
    # no TPU in CI — executing rank_totals_pallas_call(interpret=False)
    # here would die; lowering for TPU is pure compilation
    text = _lower_tpu(lambda a, m: rank_totals_pallas_call(a, m),
                      ident, matches)
    assert len(text) > 0


# ---------------------------------------------------------------------------
# Fused surfaces (q8 session windows, TPC-H q3, multi-job co-scheduled
# epochs): lowered for platform "tpu" WITHOUT executing, so a fused core
# that stopped lowering for the chip fails CI at no chip time — same
# contract as the Pallas kernels above.
# ---------------------------------------------------------------------------


def _lower_tpu_jitted(jitted, *args) -> str:
    return jitted.trace(*args).lower(lowering_platforms=("tpu",)).as_text()


def test_fused_session_epoch_lowers_for_tpu():
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import col
    from risingwave_tpu.ops.fused_epoch import fused_source_session_epoch
    from risingwave_tpu.ops.session_window import SessionWindowCore

    core = SessionWindowCore(
        Schema((Field("bidder", INT64), Field("ts", TIMESTAMP))),
        key_col=0, ts_col=1, gap_us=500_000,
        capacity=1 << 12, closed_capacity=1 << 12)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=512))
    fused = fused_source_session_epoch(
        gen.chunk_fn(), [col(1, INT64), col(5, TIMESTAMP)], core, 512,
        donate=False)
    text = _lower_tpu_jitted(fused, core.init_state(), jnp.int64(0),
                             jax.random.PRNGKey(0), 4, jnp.int64(0))
    assert "stablehlo" in text and ("while" in text or "scan" in text)


def test_fused_q3_epoch_lowers_for_tpu():
    from risingwave_tpu.connector.tpch import (
        DeviceQ3Generator, Q3_CUTOFF_DAYS, TpchQ3Config,
    )
    from risingwave_tpu.ops.fused_epoch import fused_source_q3_epoch
    from risingwave_tpu.ops.stream_q3 import Q3Core

    core = Q3Core(Q3_CUTOFF_DAYS, orders_capacity=1 << 12,
                  agg_capacity=1 << 12)
    gen = DeviceQ3Generator(TpchQ3Config(chunk_capacity=512))
    fused = fused_source_q3_epoch(gen.chunk_fn(), core, 512, donate=False)
    text = _lower_tpu_jitted(fused, core.init_state(), jnp.int64(0),
                             jax.random.PRNGKey(0), 4)
    assert "stablehlo" in text and ("while" in text or "scan" in text)


def test_multi_job_epoch_lowers_for_tpu():
    """The co-scheduled group epoch (vmapped over the job axis) lowers
    for the chip at no chip time."""
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import BID_SCHEMA, NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops import fused_multi as fm
    from risingwave_tpu.stream import HashAggExecutor, ProjectExecutor
    from risingwave_tpu.stream.source import MockSource

    exprs = [call("tumble_start", col(5, TIMESTAMP),
                  Literal(1_000_000, INT64)), col(0, INT64)]
    proj = ProjectExecutor(MockSource(BID_SCHEMA, []), exprs,
                           names=("ws", "a"))
    agg = HashAggExecutor(proj, [0, 1], [count_star()],
                          table_capacity=1 << 12, out_capacity=512)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=512))
    multi = fm.fused_multi_agg_epoch(gen.chunk_fn(), exprs, agg.core,
                                     512, donate=False)
    stacked = fm.stack_states([agg.core.init_state() for _ in range(8)])
    starts = jnp.zeros(8, jnp.int64)
    keys = jnp.stack([jax.random.PRNGKey(j) for j in range(8)])
    text = _lower_tpu_jitted(multi, stacked, starts, keys, 4)
    assert "stablehlo" in text and ("while" in text or "scan" in text)


@pytest.mark.parametrize("shape", ["agg", "join"])
def test_sharded_fused_epoch_lowers_for_tpu(shape):
    """The mesh-sharded fused epochs (ops/fused_sharded.py) — shard_map
    around the solo epoch body with the in-dispatch all_to_all shuffle —
    lower for platform "tpu" chip-free over the virtual CPU mesh, so a
    sharded surface that stopped lowering for the chip fails CI at no
    chip time."""
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.fused_sharded import SHARDED_EPOCH_BUILDERS
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.ops.interval_join import IntervalJoinCore
    from risingwave_tpu.ops.fused_multi import stack_states
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    n = 4
    assert len(jax.devices()) >= n
    mesh = make_mesh(n)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=256))
    exprs = [call("tumble_start", col(5, TIMESTAMP),
                  Literal(5_000, INT64)), col(0, INT64), col(2, INT64)]
    if shape == "agg":
        core = AggCore([INT64, INT64], [0, 1], [count_star()],
                       1 << 10, 128)
        builder = SHARDED_EPOCH_BUILDERS["source_agg"]
    else:
        core = IntervalJoinCore(
            Schema((Field("ws", TIMESTAMP), Field("auction", INT64),
                    Field("price", INT64))),
            ts_col=0, val_col=2, window_us=5_000, n_buckets=256,
            lane_width=64)
        builder = SHARDED_EPOCH_BUILDERS["source_join"]
    fused = builder(gen.chunk_fn(), exprs, core, 256, mesh)
    stacked = stack_states([core.init_state() for _ in range(n)])
    text = _lower_tpu_jitted(fused, stacked, jnp.int64(0),
                             jax.random.PRNGKey(0), 4)
    assert "stablehlo" in text and ("while" in text or "scan" in text)
    assert "all-to-all" in text or "all_to_all" in text


@pytest.mark.parametrize("shape", ["session", "q3"])
def test_sharded_q8_q3_epochs_lower_for_tpu(shape):
    """The two NEW shard_map epochs (PR 13: sharded q8 session windows
    and sharded TPC-H q3 with its in-dispatch global top-n) lower for
    platform "tpu" chip-free, with the in-dispatch all_to_all visible
    in the StableHLO — same CI contract as the q5/q7 sharded surfaces."""
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.connector.tpch import (
        DeviceQ3Generator, Q3_CUTOFF_DAYS, TpchQ3Config,
    )
    from risingwave_tpu.expr import col
    from risingwave_tpu.ops.fused_multi import stack_states
    from risingwave_tpu.ops.fused_sharded import SHARDED_EPOCH_BUILDERS
    from risingwave_tpu.ops.session_window import SessionWindowCore
    from risingwave_tpu.ops.stream_q3 import Q3Core
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    n = 4
    mesh = make_mesh(n)
    if shape == "session":
        core = SessionWindowCore(
            Schema((Field("bidder", INT64), Field("ts", TIMESTAMP))),
            key_col=0, ts_col=1, gap_us=5_000,
            capacity=1 << 10, closed_capacity=1 << 10)
        gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=256))
        fused = SHARDED_EPOCH_BUILDERS["source_session"](
            gen.chunk_fn(), [col(1, INT64), col(5, TIMESTAMP)], core,
            256, mesh)
        args = (jnp.int64(0), jax.random.PRNGKey(0), 4, jnp.int64(0))
    else:
        core = Q3Core(Q3_CUTOFF_DAYS, orders_capacity=1 << 10,
                      agg_capacity=1 << 10)
        gen = DeviceQ3Generator(TpchQ3Config(chunk_capacity=256))
        fused = SHARDED_EPOCH_BUILDERS["source_q3"](
            gen.chunk_fn(), core, 256, mesh)
        args = (jnp.int64(0), jax.random.PRNGKey(0), 4)
    stacked = stack_states([core.init_state() for _ in range(n)])
    text = _lower_tpu_jitted(fused, stacked, *args)
    assert "stablehlo" in text and ("while" in text or "scan" in text)
    assert "all-to-all" in text or "all_to_all" in text
    if shape == "q3":
        # the global top-n flush all_gathers the candidate union
        assert "all-gather" in text or "all_gather" in text


def test_sharded_group_epoch_lowers_for_tpu():
    """The K×S co-scheduled group epoch (fusion surface 6:
    vmap-over-jobs inside shard_map with the hand-batched group
    all_to_all) lowers for the chip at no chip time."""
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.fused_multi import stack_states
    from risingwave_tpu.ops.fused_sharded import SHARDED_EPOCH_BUILDERS
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    n, jobs = 4, 8
    mesh = make_mesh(n)
    exprs = [call("tumble_start", col(5, TIMESTAMP),
                  Literal(1_000_000, INT64)), col(0, INT64)]
    core = AggCore([INT64, INT64], [0, 1], [count_star()], 1 << 10, 128)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=256))
    fused = SHARDED_EPOCH_BUILDERS["group_agg"](
        gen.chunk_fn(), exprs, core, 256, mesh)
    per_job = [stack_states([core.init_state() for _ in range(n)])
               for _ in range(jobs)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=1), *per_job)
    starts = jnp.zeros(jobs, jnp.int64)
    keys = jnp.stack([jax.random.PRNGKey(j) for j in range(jobs)])
    nos = jnp.zeros(jobs, jnp.int64)
    text = _lower_tpu_jitted(fused, stacked, starts, keys, nos, 4)
    assert "stablehlo" in text and ("while" in text or "scan" in text)
    assert "all-to-all" in text or "all_to_all" in text


def test_sharded_equi_join_epoch_lowers_for_tpu():
    """The generic sharded-fused equi-join epoch (JoinCore under
    shard_map, k chunks per dispatch) lowers for platform "tpu"
    chip-free with the all_to_all routing visible."""
    from risingwave_tpu.common import INT64
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.ops.fused_multi import stack_states
    from risingwave_tpu.ops.fused_sharded import SHARDED_EPOCH_BUILDERS
    from risingwave_tpu.ops.join_state import JoinCore, JoinType
    from risingwave_tpu.parallel.sharded_agg import make_mesh
    from risingwave_tpu.common.chunk import Column, StreamChunk

    n, k, cap = 4, 3, 64
    mesh = make_mesh(n)
    ls = Schema((Field("k", INT64), Field("v", INT64)))
    rs = Schema((Field("k", INT64), Field("w", INT64)))
    core = JoinCore(ls, rs, [0], [0], JoinType.INNER,
                    key_capacity=1 << 8, bucket_width=8)
    fused = SHARDED_EPOCH_BUILDERS["equi_join"](core, mesh, [0], [0])
    stacked = stack_states([core.init_state() for _ in range(n)])
    cols = tuple(Column(jnp.zeros((n, k, cap), jnp.int64),
                        jnp.zeros((n, k, cap), jnp.bool_))
                 for _ in range(2))
    batch = StreamChunk(jnp.zeros((n, k, cap), jnp.int8),
                        jnp.zeros((n, k, cap), jnp.bool_), cols)
    text = fused.trace(stacked, batch, side="left").lower(
        lowering_platforms=("tpu",)).as_text()
    assert "stablehlo" in text and ("while" in text or "scan" in text)
    assert "all-to-all" in text or "all_to_all" in text


@pytest.mark.parametrize("tier", ["padded", "mega"])
def test_hetero_tick_compiler_epochs_lower_for_tpu(tier):
    """Both tick-compiler dispatch tiers (ISSUE 19: the skeletonized
    padded supergroup epoch and the concatenated mega-epoch) lower for
    platform "tpu" chip-free — same CI contract as every other fused
    surface."""
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import BID_SCHEMA, NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import agg as agg_call, count_star
    from risingwave_tpu.ops.fused_hetero import (
        build_mega_epoch, build_padded_group_epoch,
    )
    from risingwave_tpu.ops.fused_multi import stack_states
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.stream.coschedule import FusedJobSpec
    from risingwave_tpu.stream.tick_compiler import skeletonize_exprs

    import numpy as np

    cap = 256
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=cap))
    exprs = (call("tumble_start", col(5, TIMESTAMP),
                  Literal(1_000_000, INT64)), col(0, INT64),
             col(2, INT64))
    core = AggCore([INT64, INT64], [0, 1], [count_star()], 1 << 10, cap)
    if tier == "padded":
        jobs = 8
        skel, hole_types, params = skeletonize_exprs(
            exprs, len(BID_SCHEMA))
        fused = build_padded_group_epoch(gen.chunk_fn(), skel, core,
                                         cap, donate=False)
        stacked = stack_states([core.init_state()
                                for _ in range(jobs)])
        param_cols = tuple(
            jnp.asarray(np.full(jobs, params[h], t.np_dtype))
            for h, t in enumerate(hole_types))
        args = (stacked, jnp.zeros(jobs, jnp.int64),
                jnp.stack([jax.random.PRNGKey(j) for j in range(jobs)]),
                jnp.zeros(jobs, jnp.int64), param_cols, 4)
    else:
        other = AggCore([INT64], [1], [count_star(),
                                       agg_call("max", 2, INT64)],
                        1 << 10, cap)
        specs = [
            FusedJobSpec("agg", ("agg", ("nexmark_bid", cap)),
                         gen.chunk_fn(), exprs, core, cap, seed=0),
            FusedJobSpec("agg", ("agg", ("nexmark_bid", cap)),
                         gen.chunk_fn(), exprs, other, cap, seed=1),
        ]
        fused = build_mega_epoch(specs, donate=False)
        args = ((core.init_state(), other.init_state()),
                jnp.zeros(2, jnp.int64),
                jnp.stack([jax.random.PRNGKey(j) for j in range(2)]),
                jnp.zeros(2, jnp.int64), 4)
    text = _lower_tpu_jitted(fused, *args)
    assert "stablehlo" in text and ("while" in text or "scan" in text)
