"""Benchmark: NEXmark q5/q7/q8 + TPC-H q3 fused-epoch throughput plus a
many-small-MVs co-scheduling phase, TPU vs CPU stand-in, plus p99
barrier latency.

Runs the hot paths of NEXmark q5 (tumble-window COUNT aggregation), q7
(bids joined with the per-window MAX(price)), q8 (session-gap windows
over bidders — ops/session_window.py) and a streaming TPC-H q3 MV
(orders⋈lineitem revenue top-10 — ops/stream_q3.py), each as ONE fused
``lax.scan`` dispatch per epoch, and a "many small MVs" phase measuring
16 co-scheduled MVs batched into one dispatch per epoch vs the same 16
dispatched sequentially (stream/coschedule.py — ROADMAP item 4).

Process design:

* Source chunks are generated ON DEVICE (``DeviceBidGenerator`` /
  ``DeviceQ3Generator``): the only per-epoch host→device traffic is two
  scalars, so the chip never waits on host ingest.
* Each epoch is ONE ``lax.scan`` dispatch; host↔device round-trips per
  epoch are O(1).
* EVERY measurement phase runs in its own subprocess, one at a time. The
  parent process never imports JAX: a chip belongs to one process at a
  time, and a parent that held it would starve its own phases.
* A cheap SMOKE PROBE (tiny jit in a fresh subprocess) runs before the
  full TPU phase and must land on platform "tpu".
* Every completed phase's record is appended to ``BENCH_partial.json``
  (JSON lines, git-ignored) AS IT FINISHES.
* A chip phase that fails makes the run fail: there is no retry with the
  Pallas kernels switched off, no CPU number under a chip metric's name,
  and no exit code 0 without a chip result.
* All phases share the repo's one compile cache
  (risingwave_tpu/common/compile_cache.py: ``JAX_COMPILATION_CACHE_DIR``
  if set, else the fixed ``.jax_cache/`` in the checkout).

``vs_baseline`` is measured, not assumed: the SAME pipeline runs in a
JAX_PLATFORMS=cpu subprocess first (the documented stand-in for the
reference's Rust CPU engine — BASELINE.md config 2 wants ≥10× a 16-vCPU CPU
engine), and the ratio reported is tpu_rows_per_sec / cpu_rows_per_sec.

``--smoke`` runs one tiny in-process phase (seconds, CPU) for CI
(scripts/check.sh): fused q5/q8/q3 epochs + a 4-job co-scheduled group,
with the 1-dispatch-per-epoch invariant asserted.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.
"""

import asyncio
import json
import os
import subprocess
import sys
import threading
import time

WATCHDOG_SECS = 1500
# backend init completes in seconds; a short init watchdog turns a hung
# init into a diagnostic line instead of a full phase timeout
INIT_WATCHDOG_SECS = 300
# must exceed INIT_WATCHDOG_SECS + WATCHDOG_SECS with slack so the
# child's diagnostic fail line always beats the parent's kill
PHASE_TIMEOUT = 2100              # per-subprocess wall clock
# smoke probe: backend init + one tiny jit; anything slower is wedged
PROBE_TIMEOUT = INIT_WATCHDOG_SECS + 180

CHUNK = 4096
WINDOW_US = 10_000_000  # 10s tumble as the q5 core window
# Epoch cadence: ~1M rows per barrier so a barrier closes roughly every
# second at the target throughput — the reference's default 1 s barrier
# interval (src/common/src/config.rs:595) at saturation.
N_CHUNKS = 1024
WARMUP_CHUNKS = 256
CHUNKS_PER_EPOCH = 256
CPU_N_CHUNKS = 256      # stand-in run is shorter; it reports a rate
Q7_N_CHUNKS = 512       # join consumes every event on both sides
Q7_CPU_N_CHUNKS = 128
# q7 window: 5 ms of event time ≈ 50 bids/window at the generator's
# 10K events/s. The probe side stores every bid of a live window under ONE
# join key, and the bucketed arena bounds per-key cardinality by its lane
# width — the 10 s window of the full q7 (100K rows/key) needs the sharded
# join + watermark cleaning, not a single-chip dense arena; window size is
# a bench parameter of the join core, not of its throughput semantics.
Q7_WINDOW_US = 5_000
# fused q7 (ops/interval_join.py): ring of window buckets + lane width.
# One epoch spans 256 chunks x 4096 events x 100 us ≈ 105 s ≈ 21K windows
# of 5 ms; the ring must outlast an epoch so a slot is never reclaimed
# while its flush delta is pending (1.5x margin). 128 lanes hold the ~50
# bids per window with chunk-straddle headroom.
Q7_BUCKETS = 1 << 15
Q7_LANES = 128
# q8 session windows (ops/session_window.py): 0.5 s session gap — hot
# bidders (90% of bids) never gap out; cold bidders' ~1 s inter-event
# spacing closes a steady session stream. Closed buffer must hold one
# epoch's closures (≈10% of events worst case); key table bounds
# distinct bidders over the whole run (id clock drifts 1 per 50 events).
Q8_N_CHUNKS = 512
Q8_CPU_N_CHUNKS = 128
Q8_GAP_US = 500_000
Q8_TABLE_CAP = 1 << 18
Q8_CLOSED_CAP = 1 << 17
# TPC-H q3 (ops/stream_q3.py + connector/tpch.py): ~10% of orders
# qualify (segment 1-of-5 x date ~1/2); capacities bound QUALIFYING
# orders / live revenue groups over the run.
Q3_N_CHUNKS = 512
Q3_CPU_N_CHUNKS = 128
Q3_ORDERS_CAP = 1 << 17
Q3_AGG_CAP = 1 << 17
# many-small-MVs co-scheduling phase (stream/coschedule.py): 16 q5-shaped
# MVs with SMALL chunks and tables — the per-job-overhead-bound regime
# where hundreds of MVs ticking together live. Measured END TO END
# through the Session: the same 16 CREATE MATERIALIZED VIEWs ticked with
# [streaming] coschedule = true (the whole group's epoch in ONE vmapped
# dispatch) vs false (16 executor pipelines, each dispatching its own
# epochs — the pre-coscheduler behavior).
COSCHED_JOBS = 16
COSCHED_CHUNK = 64             # rows per chunk (the "small MV" shape)
COSCHED_CHUNKS_PER_TICK = 8
COSCHED_TABLE_CAP = 1 << 11
COSCHED_TICKS = 12
COSCHED_WARMUP_TICKS = 3
COSCHED_SMOKE_CHUNK = 256      # ops-level shapes for --smoke
COSCHED_SMOKE_TABLE = 1 << 12
# heterogeneous tick-compiler phase (stream/tick_compiler.py): N
# DISSIMILAR small MVs — mixed skeletons, widths, window literals — in
# one Session, ticked with [streaming] tick_compiler = true (the
# compiler buckets them into shape-class padded supergroups + jitted
# mega-epochs: a handful of dispatches per tick) vs false (N executor
# pipelines, each dispatching its own epochs).
HETERO_JOBS = 12
HETERO_TICKS = 12
# mesh-sharded fused phase (ops/fused_sharded.py + parallel/fused.py):
# the fused q5/q7 epochs promoted to the whole mesh — one dispatch per
# epoch across all chips, state hash-partitioned via the in-dispatch
# all_to_all. On the CPU stand-in the mesh is virtual
# (XLA_FLAGS=--xla_force_host_platform_device_count); on a healthy chip
# it is the real slice. Aggregate rows/s recorded per shard count.
SHARDED_SHARD_COUNTS = (1, 4, 8)
SHARDED_N_CHUNKS = 128
SHARDED_WARMUP_CHUNKS = 32
SHARDED_Q7_N_CHUNKS = 64
COSCHED_SHARDED_JOBS = 4       # K jobs × S shards phase (surface 6)
SHARDED_VIRTUAL_DEVICES = 8    # CPU stand-in virtual mesh size
# serving phase (frontend/serving.py — ROADMAP item 3): concurrent
# point-lookups + small group-by reads over a LIVE q5 MV while the
# stream keeps ticking. Cached+two-phase (the serving plane) vs the
# uncached single-phase baseline ([batch] serving_cache_size = 0,
# serving_tasks = 1 — every query replans/relowers under the API lock,
# the pre-serving-plane behavior). QPS + p50/p99 per run.
SERVING_SECONDS = 3.0          # measured wall clock per variant
SERVING_THREADS = 4            # concurrent reader threads
SERVING_TICK_S = 0.1           # live-stream tick cadence during reads
SERVING_WARM_TICKS = 3

# fleet phase (docs/control-plane.md): one standalone MetaServer + one
# writer session share a durable dir with N serving FRONTEND PROCESSES,
# each serving cached MV reads over pgwire to several connections —
# the multi-tenant deployment shape, measured end to end (attach,
# notification-driven catalog, admission control, merged QPS/p99).
FLEET_SECONDS = 3.0            # measured wall clock
FLEET_FRONTENDS = 2            # serving frontend PROCESSES
FLEET_CONNS = 4                # pgwire connections per frontend


def _emit(obj: dict) -> None:
    print(json.dumps(obj))
    sys.stdout.flush()


def _fail_line(msg: str) -> dict:
    return {"metric": "nexmark_q5_core_throughput", "value": 0.0,
            "unit": "rows/s", "vs_baseline": 0.0, "error": msg}


def _watchdog_fire():
    # A daemon-thread timer (not SIGALRM): a hang inside native PJRT/XLA
    # code never returns to the bytecode loop, so a Python signal handler
    # would be deferred forever.
    _emit(_fail_line(
        "watchdog timeout: backend init or compile hung (chip held?)"))
    os._exit(2)


# ---------------------------------------------------------------------------
# Child phase: actual measurement on whatever backend this process gets
# ---------------------------------------------------------------------------

class _DeviceBidSource:
    """Source executor over the on-device generator: one ChunkBatch + one
    barrier per epoch. Fresh scripts are configured via reset()."""

    def __init__(self, n_chunks: int, first_epoch: int, cfg=None):
        from risingwave_tpu.connector import BID_SCHEMA, NexmarkConfig
        from risingwave_tpu.connector.nexmark import DeviceBidGenerator
        self.schema = BID_SCHEMA
        self.gen = DeviceBidGenerator(cfg or NexmarkConfig(
            chunk_capacity=CHUNK))
        self.n_chunks = n_chunks
        self.first_epoch = first_epoch

    def reset(self, n_chunks: int, first_epoch: int) -> None:
        self.n_chunks = n_chunks
        self.first_epoch = first_epoch

    async def execute(self):
        from risingwave_tpu.stream import Barrier
        yield Barrier.new(self.first_epoch)
        epoch = self.first_epoch
        for i in range(0, self.n_chunks, CHUNKS_PER_EPOCH):
            k = min(CHUNKS_PER_EPOCH, self.n_chunks - i)
            yield self.gen.next_batch(k)
            epoch += 1
            yield Barrier.new(epoch)


def _q5_pipeline(src):
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.stream import HashAggExecutor, ProjectExecutor
    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(WINDOW_US, INT64)),
        col(0, INT64),
    ]
    proj = ProjectExecutor(src, exprs, names=("window_start", "auction"))
    agg = HashAggExecutor(proj, [0, 1], [count_star()],
                          table_capacity=1 << 21, out_capacity=CHUNK)
    return exprs, agg


def measure_q5(n_chunks: int) -> float:
    """Sustained source rows/s of the q5-core EXECUTOR pipeline (three
    dispatches per epoch: generate / project / agg-scan)."""
    import jax

    src = _DeviceBidSource(WARMUP_CHUNKS, 1)
    _, agg = _q5_pipeline(src)

    async def drive() -> float:
        async for _ in agg.execute():  # warmup pass compiles every step
            pass
        jax.block_until_ready(agg.state.lanes)
        src.reset(n_chunks, WARMUP_CHUNKS // CHUNKS_PER_EPOCH + 2)
        t0 = time.perf_counter()
        async for _ in agg.execute():
            pass
        jax.block_until_ready(agg.state.lanes)
        return time.perf_counter() - t0

    elapsed = asyncio.run(drive())
    return n_chunks * CHUNK / elapsed


def measure_q5_fused(n_chunks: int) -> float:
    """Sustained source rows/s of the q5 core with the WHOLE epoch —
    generation, projection, aggregation — fused into one lax.scan
    dispatch (ops/fused_epoch.py; the BASELINE.md headroom item). The
    barrier path (probe + flush-window gathers + finish) mirrors
    HashAggExecutor.on_barrier exactly so the work per barrier matches
    the executor pipeline."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.ops.fused_epoch import fused_source_agg_epoch

    src = _DeviceBidSource(1, 1)
    exprs, agg = _q5_pipeline(src)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CHUNK))
    fused = fused_source_agg_epoch(gen.chunk_fn(), exprs, agg.core, CHUNK)

    def run(state, n, start_event, batch_no):
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)  # remainder epoch kept
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(17), batch_no)
            batch_no += 1
            state = fused(state, jnp.int64(start_event), key, per)
            start_event += per * CHUNK
            packed, rank = agg._probe(state)
            n_dirty, overflow, _live = (
                int(x) for x in jax.device_get(packed))
            if overflow:
                raise RuntimeError("q5 fused: group table overflow")
            lo = 0
            while lo < n_dirty:
                agg._gather(state, rank, jnp.int64(lo))
                lo += agg.core.groups_per_chunk
            state = agg._finish(state)
        return state, start_event, batch_no

    state, start_event, batch_no = run(
        agg.state, WARMUP_CHUNKS, 0, 0)        # compile everything
    jax.block_until_ready(state.lanes)
    t0 = time.perf_counter()
    state, _, _ = run(state, n_chunks, start_event, batch_no)
    jax.block_until_ready(state.lanes)
    elapsed = time.perf_counter() - t0
    return n_chunks * CHUNK / elapsed


def measure_q7(n_chunks: int) -> float:
    """Sustained source rows/s of the q7-core windowed join: bids joined
    with the per-window MAX(price) (BASELINE.md config 3). Each source
    event feeds both join sides (two device generators with the same seed
    produce identical streams); the rate reported is source events/s."""
    import jax
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import agg
    from risingwave_tpu.stream import (
        HashAggExecutor, HashJoinExecutor, ProjectExecutor,
    )

    warm = 64

    def pipeline():
        probe_src = _DeviceBidSource(warm, 1)
        probe = ProjectExecutor(probe_src, [
            call("tumble_start", col(5, TIMESTAMP),
                 Literal(Q7_WINDOW_US, INT64)),
            col(0, INT64),
            col(2, INT64),
        ], names=("window_start", "auction", "price"))
        build_src = _DeviceBidSource(warm, 1)
        build_pre = ProjectExecutor(build_src, [
            call("tumble_start", col(5, TIMESTAMP),
                 Literal(Q7_WINDOW_US, INT64)),
            col(2, INT64),
        ], names=("window_start", "price"))
        build = HashAggExecutor(build_pre, [0], [agg("max", 1, INT64)],
                                table_capacity=1 << 16, out_capacity=CHUNK)
        cond = call("equal", col(2, INT64), col(4, INT64))  # price = max
        join = HashJoinExecutor(
            probe, build, [0], [0], condition=cond,
            key_capacity=1 << 16, bucket_width=128, out_capacity=CHUNK)
        return probe_src, build_src, join

    probe_src, build_src, join = pipeline()

    async def drive() -> float:
        async for _ in join.execute():   # warmup compiles all steps
            pass
        jax.block_until_ready(join.state.left.occupied)
        first = (warm + CHUNKS_PER_EPOCH - 1) // CHUNKS_PER_EPOCH + 2
        probe_src.reset(n_chunks, first)
        build_src.reset(n_chunks, first)
        t0 = time.perf_counter()
        async for _ in join.execute():
            pass
        jax.block_until_ready(join.state.left.occupied)
        return time.perf_counter() - t0

    elapsed = asyncio.run(drive())
    return n_chunks * CHUNK / elapsed


def measure_q7_fused(n_chunks: int) -> float:
    """Sustained source rows/s of the q7 core with the WHOLE pipeline —
    generation, projection, the bucketed interval join, and the
    per-window max flush — fused into one lax.scan dispatch per epoch
    (ops/interval_join.py + fused_source_join_epoch; the dispatch-ladder
    elimination q5 got, extended to the join family). Per epoch the host
    reads ONE packed stats vector and gathers the emitted windows."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.chunk import (
        flatten_shards, gather_units_window,
    )
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.ops.fused_epoch import fused_source_join_epoch
    from risingwave_tpu.ops.interval_join import IntervalJoinCore

    exprs = [
        call("tumble_start", col(5, TIMESTAMP),
             Literal(Q7_WINDOW_US, INT64)),
        col(0, INT64),
        col(2, INT64),
    ]
    probe_schema = Schema((Field("window_start", TIMESTAMP),
                           Field("auction", INT64), Field("price", INT64)))
    core = IntervalJoinCore(probe_schema, ts_col=0, val_col=2,
                            window_us=Q7_WINDOW_US, n_buckets=Q7_BUCKETS,
                            lane_width=Q7_LANES)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CHUNK))
    fused = fused_source_join_epoch(gen.chunk_fn(), exprs, core, CHUNK)
    gather_flush = jax.jit(core.gather_flush,
                           static_argnames=("out_capacity",))
    probe_gather = jax.jit(lambda po, lo: gather_units_window(
        flatten_shards(po), lo, CHUNK))

    def run(state, n, start_event, batch_no):
        last = None
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)   # remainder epoch kept
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(23), batch_no)
            batch_no += 1
            (state, probe_out, del_m, ins_m, old_emitted,
             packed) = fused(state, jnp.int64(start_event), key, per)
            start_event += per * CHUNK
            n_flush, ovf, clobber, sawdel, n_probe = (
                int(x) for x in jax.device_get(packed))
            if ovf or clobber or sawdel:
                raise RuntimeError(
                    f"q7 fused: flags ovf={ovf} clobber={clobber} "
                    f"sawdel={sawdel}")
            # drain both emission surfaces (what downstream would consume)
            lo = 0
            while lo < n_probe:
                last = probe_gather(probe_out, jnp.int64(lo))
                lo += CHUNK // 2
            lo = 0
            while lo < n_flush:
                last = gather_flush(state, del_m, ins_m, old_emitted,
                                    jnp.int64(lo), out_capacity=CHUNK)
                lo += CHUNK
        if last is not None:
            jax.block_until_ready(last)
        return state, start_event, batch_no

    state, start_event, batch_no = run(
        core.init_state(), WARMUP_CHUNKS, 0, 0)    # compile everything
    jax.block_until_ready(state.cur_max)
    t0 = time.perf_counter()
    state, _, _ = run(state, n_chunks, start_event, batch_no)
    jax.block_until_ready(state.cur_max)
    elapsed = time.perf_counter() - t0
    return n_chunks * CHUNK / elapsed


def measure_q8_fused(n_chunks: int) -> float:
    """Sustained source rows/s of the q8 core: bidder session-gap windows
    (ops/session_window.py) with generation, projection, sessionization
    AND the watermark close fused into one lax.scan dispatch per epoch
    (fused_source_session_epoch). Per epoch the host reads ONE packed
    stats vector and gathers the closed-session windows."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import col
    from risingwave_tpu.ops.fused_epoch import EPOCH_BUILDERS
    from risingwave_tpu.ops.session_window import SessionWindowCore

    exprs = [col(1, INT64), col(5, TIMESTAMP)]   # bidder, date_time
    schema = Schema((Field("bidder", INT64), Field("ts", TIMESTAMP)))
    core = SessionWindowCore(schema, key_col=0, ts_col=1,
                             gap_us=Q8_GAP_US, capacity=Q8_TABLE_CAP,
                             closed_capacity=Q8_CLOSED_CAP)
    cfg = NexmarkConfig(chunk_capacity=CHUNK)
    gen = DeviceBidGenerator(cfg)
    fused = EPOCH_BUILDERS["source_session"](gen.chunk_fn(), exprs, core,
                                             CHUNK)
    gather = jax.jit(core.gather_closed, static_argnames=("out_capacity",))
    us_per_event = max(1_000_000 // max(cfg.events_per_second, 1), 1)

    def run(state, n, start_event, batch_no):
        last = None
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(31), batch_no)
            batch_no += 1
            end_event = start_event + per * CHUNK
            wm = cfg.start_time_us + end_event * us_per_event - Q8_GAP_US
            state, snap, packed = fused(state, jnp.int64(start_event),
                                        key, per, jnp.int64(wm))
            start_event = end_event
            n_closed, ovf, covf, sawdel, ooo = (
                int(x) for x in jax.device_get(packed))
            if ovf or covf or sawdel or ooo:
                raise RuntimeError(
                    f"q8 fused: flags table_ovf={ovf} closed_ovf={covf} "
                    f"saw_delete={sawdel} out_of_order={ooo}")
            lo = 0
            while lo < n_closed:
                last = gather(snap, jnp.int64(n_closed), jnp.int64(lo),
                              out_capacity=CHUNK)
                lo += CHUNK
        if last is not None:
            jax.block_until_ready(last)
        return state, start_event, batch_no

    state, start_event, batch_no = run(
        core.init_state(), WARMUP_CHUNKS, 0, 0)    # compile everything
    jax.block_until_ready(state.last_ts)
    t0 = time.perf_counter()
    state, _, _ = run(state, n_chunks, start_event, batch_no)
    jax.block_until_ready(state.last_ts)
    elapsed = time.perf_counter() - t0
    return n_chunks * CHUNK / elapsed


def measure_q3_fused(n_chunks: int) -> float:
    """Sustained source rows/s of the TPC-H q3 streaming MV: orders-table
    build + lineitem probe + revenue agg + top-10 churn fused into one
    dispatch per epoch (ops/stream_q3.py + fused_source_q3_epoch). The
    flush output is a fixed 20-row churn chunk returned BY the dispatch —
    zero extra gathers."""
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.connector.tpch import (
        DeviceQ3Generator, Q3_CUTOFF_DAYS, TpchQ3Config,
    )
    from risingwave_tpu.ops.fused_epoch import EPOCH_BUILDERS
    from risingwave_tpu.ops.stream_q3 import Q3Core

    gen = DeviceQ3Generator(TpchQ3Config(chunk_capacity=CHUNK))
    core = Q3Core(Q3_CUTOFF_DAYS, orders_capacity=Q3_ORDERS_CAP,
                  agg_capacity=Q3_AGG_CAP)
    fused = EPOCH_BUILDERS["source_q3"](gen.chunk_fn(), core, CHUNK)

    def run(state, n, start_event, batch_no):
        last = None
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(37), batch_no)
            batch_no += 1
            state, out, packed = fused(state, jnp.int64(start_event),
                                       key, per)
            start_event += per * CHUNK
            _n_out, o_ovf, a_ovf, sawdel = (
                int(x) for x in jax.device_get(packed))
            if o_ovf or a_ovf or sawdel:
                raise RuntimeError(
                    f"q3 fused: flags orders_ovf={o_ovf} agg_ovf={a_ovf} "
                    f"saw_delete={sawdel}")
            last = out
        if last is not None:
            jax.block_until_ready(last)
        return state, start_event, batch_no

    state, start_event, batch_no = run(
        core.init_state(), WARMUP_CHUNKS, 0, 0)
    jax.block_until_ready(state.odate)
    t0 = time.perf_counter()
    state, _, _ = run(state, n_chunks, start_event, batch_no)
    jax.block_until_ready(state.odate)
    elapsed = time.perf_counter() - t0
    return n_chunks * CHUNK / elapsed


def measure_q5_sharded_fused(n_chunks: int, n_shards: int) -> float:
    """Aggregate source rows/s of the q5 core MESH-SHARDED: generation,
    projection, the in-dispatch vnode all_to_all shuffle, and per-shard
    aggregation fused into one dispatch per epoch across ``n_shards``
    devices (ops/fused_sharded.py). The flush is one packed fetch for
    every shard + per-shard churn gathers — the solo fused barrier
    cadence, at mesh width."""
    import jax
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.parallel.fused import ShardedFusedAgg
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(WINDOW_US, INT64)),
        col(0, INT64),
    ]
    # capacities are PER SHARD: the group set partitions across the mesh
    core = AggCore([INT64, INT64], [0, 1], [count_star()],
                   max((1 << 21) // n_shards, 1 << 16), CHUNK)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CHUNK))
    sf = ShardedFusedAgg(make_mesh(n_shards), core, gen.chunk_fn(),
                         exprs, CHUNK)

    def run(n, start_event, batch_no):
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(17), batch_no)
            batch_no += 1
            sf.run_epoch(start_event, key, per)
            start_event += per * CHUNK
            sf.flush()
        return start_event, batch_no

    start_event, batch_no = run(SHARDED_WARMUP_CHUNKS, 0, 0)
    jax.block_until_ready(sf.stacked.lanes)
    t0 = time.perf_counter()
    run(n_chunks, start_event, batch_no)
    jax.block_until_ready(sf.stacked.lanes)
    return n_chunks * CHUNK / (time.perf_counter() - t0)


def measure_q7_sharded_fused(n_chunks: int, n_shards: int) -> float:
    """Aggregate source rows/s of the q7 core MESH-SHARDED: the bucketed
    interval join's ring partitions by window vnode across the mesh
    (per-shard ring ≈ solo/n — windows spread uniformly under the hash),
    and one dispatch per epoch covers every shard's ingest AND flush
    plan; ONE [n, 6] packed fetch covers all flags and counts."""
    import jax
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.ops.interval_join import IntervalJoinCore
    from risingwave_tpu.parallel.fused import ShardedFusedJoin
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    exprs = [
        call("tumble_start", col(5, TIMESTAMP),
             Literal(Q7_WINDOW_US, INT64)),
        col(0, INT64),
        col(2, INT64),
    ]
    probe_schema = Schema((Field("window_start", TIMESTAMP),
                           Field("auction", INT64), Field("price", INT64)))
    core = IntervalJoinCore(
        probe_schema, ts_col=0, val_col=2, window_us=Q7_WINDOW_US,
        # per-shard ring: 2x the expected windows-per-shard share
        n_buckets=max(2 * Q7_BUCKETS // n_shards, 1 << 10),
        lane_width=Q7_LANES)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CHUNK))
    sf = ShardedFusedJoin(make_mesh(n_shards), core, gen.chunk_fn(),
                          exprs, CHUNK)

    def run(n, start_event, batch_no):
        last = None
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(23), batch_no)
            batch_no += 1
            sf.run_epoch(start_event, key, per)
            start_event += per * CHUNK
            probe, churn = sf.flush(out_capacity=CHUNK)
            if churn:
                last = churn[-1]
            elif probe:
                last = probe[-1]
        if last is not None:
            jax.block_until_ready(last)
        return start_event, batch_no

    start_event, batch_no = run(SHARDED_WARMUP_CHUNKS, 0, 0)
    jax.block_until_ready(sf.stacked.cur_max)
    t0 = time.perf_counter()
    run(n_chunks, start_event, batch_no)
    jax.block_until_ready(sf.stacked.cur_max)
    return n_chunks * CHUNK / (time.perf_counter() - t0)


def measure_q8_sharded_fused(n_chunks: int, n_shards: int) -> float:
    """Aggregate source rows/s of the q8 session-window core
    MESH-SHARDED (ops/fused_sharded.sharded_session_epoch): generation,
    projection, the in-dispatch vnode all_to_all route by session key,
    per-shard sessionization AND the watermark close in one dispatch
    per epoch; ONE [n, 6] packed fetch covers all flags and counts."""
    import jax
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import col
    from risingwave_tpu.ops.session_window import SessionWindowCore
    from risingwave_tpu.parallel.fused import ShardedFusedSession
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    exprs = [col(1, INT64), col(5, TIMESTAMP)]   # bidder, date_time
    schema = Schema((Field("bidder", INT64), Field("ts", TIMESTAMP)))
    # capacities are PER SHARD: keys partition across the mesh
    core = SessionWindowCore(
        schema, key_col=0, ts_col=1, gap_us=Q8_GAP_US,
        capacity=max(Q8_TABLE_CAP // n_shards, 1 << 14),
        closed_capacity=max(Q8_CLOSED_CAP // n_shards, 1 << 14))
    cfg = NexmarkConfig(chunk_capacity=CHUNK)
    gen = DeviceBidGenerator(cfg)
    sf = ShardedFusedSession(make_mesh(n_shards), core, gen.chunk_fn(),
                             exprs, CHUNK)
    us_per_event = max(1_000_000 // max(cfg.events_per_second, 1), 1)

    def run(n, start_event, batch_no):
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(31), batch_no)
            batch_no += 1
            end_event = start_event + per * CHUNK
            wm = cfg.start_time_us + end_event * us_per_event - Q8_GAP_US
            sf.run_epoch(start_event, key, per, wm)
            start_event = end_event
            sf.flush(out_capacity=CHUNK)
        return start_event, batch_no

    start_event, batch_no = run(SHARDED_WARMUP_CHUNKS, 0, 0)
    jax.block_until_ready(sf.stacked.last_ts)
    t0 = time.perf_counter()
    run(n_chunks, start_event, batch_no)
    jax.block_until_ready(sf.stacked.last_ts)
    return n_chunks * CHUNK / (time.perf_counter() - t0)


def measure_q3_sharded_fused(n_chunks: int, n_shards: int) -> float:
    """Aggregate source rows/s of the TPC-H q3 streaming MV
    MESH-SHARDED (ops/fused_sharded.sharded_q3_epoch): orders +
    lineitems route by orderkey, per-shard build/probe/agg, and the
    GLOBAL top-10 churn (local top-k → all_gather → shared recompute)
    all inside one dispatch per epoch."""
    import jax
    from risingwave_tpu.connector.tpch import (
        DeviceQ3Generator, Q3_CUTOFF_DAYS, TpchQ3Config,
    )
    from risingwave_tpu.ops.stream_q3 import Q3Core
    from risingwave_tpu.parallel.fused import ShardedFusedQ3
    from risingwave_tpu.parallel.sharded_agg import make_mesh

    gen = DeviceQ3Generator(TpchQ3Config(chunk_capacity=CHUNK))
    core = Q3Core(Q3_CUTOFF_DAYS,
                  orders_capacity=max(Q3_ORDERS_CAP // n_shards, 1 << 14),
                  agg_capacity=max(Q3_AGG_CAP // n_shards, 1 << 14))
    sf = ShardedFusedQ3(make_mesh(n_shards), core, gen.chunk_fn(), CHUNK)

    def run(n, start_event, batch_no):
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)
            done += per
            key = jax.random.fold_in(jax.random.PRNGKey(37), batch_no)
            batch_no += 1
            sf.run_epoch(start_event, key, per)
            start_event += per * CHUNK
            sf.flush()
        return start_event, batch_no

    start_event, batch_no = run(SHARDED_WARMUP_CHUNKS, 0, 0)
    jax.block_until_ready(sf.stacked.odate)
    t0 = time.perf_counter()
    run(n_chunks, start_event, batch_no)
    jax.block_until_ready(sf.stacked.odate)
    return n_chunks * CHUNK / (time.perf_counter() - t0)


def measure_cosched_sharded(n_chunks: int, n_shards: int,
                            n_jobs: int) -> float:
    """Aggregate source rows/s of ``n_jobs`` signature-equal q5-shaped
    MVs × ``n_shards`` mesh shards — the SIXTH fusion surface
    (ops/fused_sharded.build_sharded_group_epoch): the whole K×S group
    is ONE dispatch per epoch, so rows/s counts every job's stream."""
    import jax
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.ops.grouped_agg import AggCore
    from risingwave_tpu.parallel.fused import ShardedCoGroup
    from risingwave_tpu.parallel.sharded_agg import make_mesh
    from risingwave_tpu.stream.coschedule import FusedJobSpec

    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(WINDOW_US, INT64)),
        col(0, INT64),
    ]
    core = AggCore([INT64, INT64], [0, 1], [count_star()],
                   max((1 << 21) // n_shards, 1 << 16), CHUNK)
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=CHUNK))
    spec = FusedJobSpec("agg", ("bench_sharded_cosched",),
                        gen.chunk_fn(), tuple(exprs), core, CHUNK, seed=0)
    group = ShardedCoGroup(make_mesh(n_shards), spec)
    for j in range(n_jobs):
        group.add(f"mv{j}", seed=j)

    def run(n):
        done = 0
        while done < n:
            per = min(CHUNKS_PER_EPOCH, n - done)
            done += per
            group.run_epoch(per)
            group.flush()

    run(SHARDED_WARMUP_CHUNKS)
    jax.block_until_ready(group.stacked.lanes)
    t0 = time.perf_counter()
    run(n_chunks)
    jax.block_until_ready(group.stacked.lanes)
    return n_jobs * n_chunks * CHUNK / (time.perf_counter() - t0)


def run_sharded_phase(n_chunks: int, q7_chunks: int) -> None:
    """Child entry for the mesh-sharded fused phase: measure q5 at
    every shard count this process's backend can host, and the heavier
    surfaces — q7, q8, q3, and the K×S co-scheduled group — once at
    the widest mesh; print one JSON line (MULTICHIP-style: n_devices +
    ok + per-shard-count rates)."""
    import jax
    n_devices = len(jax.devices())
    by_shards: dict = {}
    for n in SHARDED_SHARD_COUNTS:
        if n > n_devices:
            continue
        entry = {"q5_rows_per_sec": round(
            measure_q5_sharded_fused(n_chunks, n), 1)}
        if n == max(c for c in SHARDED_SHARD_COUNTS if c <= n_devices):
            # the slow measurements run once, at the widest mesh
            entry["q7_rows_per_sec"] = round(
                measure_q7_sharded_fused(q7_chunks, n), 1)
            entry["q8_rows_per_sec"] = round(
                measure_q8_sharded_fused(q7_chunks, n), 1)
            entry["q3_rows_per_sec"] = round(
                measure_q3_sharded_fused(q7_chunks, n), 1)
            entry["cosched_rows_per_sec"] = round(
                measure_cosched_sharded(q7_chunks, n,
                                        COSCHED_SHARDED_JOBS), 1)
        by_shards[str(n)] = entry
    widest = max((int(k) for k in by_shards), default=0)
    top = by_shards.get(str(widest), {})
    _emit({
        "metric": "sharded_fused_epochs",
        "unit": "rows/s",
        "n_devices": n_devices,
        "ok": bool(by_shards),
        "backend": jax.default_backend(),
        "sharded_fused_shards": widest,
        "sharded_fused_by_shards": by_shards,
        "q5_sharded_fused_rows_per_sec": top.get("q5_rows_per_sec"),
        "q7_sharded_fused_rows_per_sec": top.get("q7_rows_per_sec"),
        "q8_sharded_fused_rows_per_sec": top.get("q8_rows_per_sec"),
        "q3_sharded_fused_rows_per_sec": top.get("q3_rows_per_sec"),
        "cosched_sharded_rows_per_sec": top.get("cosched_rows_per_sec"),
        "cosched_sharded_jobs": (COSCHED_SHARDED_JOBS
                                 if "cosched_rows_per_sec" in top
                                 else None),
    })


def _cosched_parts():
    """Ops-level build for the --smoke dispatch-count check: one small
    q5-shaped agg core + projection over the device bid source."""
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.connector import BID_SCHEMA, NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.expr import Literal, call, col
    from risingwave_tpu.expr.agg import count_star
    from risingwave_tpu.stream import HashAggExecutor, ProjectExecutor
    from risingwave_tpu.stream.source import MockSource

    exprs = [
        call("tumble_start", col(5, TIMESTAMP), Literal(WINDOW_US, INT64)),
        col(0, INT64),
    ]
    proj = ProjectExecutor(MockSource(BID_SCHEMA, []), exprs,
                           names=("window_start", "auction"))
    agg = HashAggExecutor(proj, [0, 1], [count_star()],
                          table_capacity=COSCHED_SMOKE_TABLE,
                          out_capacity=COSCHED_SMOKE_CHUNK)
    gen = DeviceBidGenerator(
        NexmarkConfig(chunk_capacity=COSCHED_SMOKE_CHUNK))
    return exprs, agg, gen.chunk_fn()


_COSCHED_SOURCE_SQL = """CREATE SOURCE bid (auction BIGINT, bidder BIGINT,
    price BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
    extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'bid')"""


def _cosched_session_rate(coschedule: bool, n_jobs: int, n_ticks: int,
                          warmup_ticks: int, pipeline_depth: int = 1,
                          data_dir=None,
                          checkpoint_frequency: int = 10):
    """Aggregate source rows/s (plus the measured window's barrier
    latency snapshot) of ``n_jobs`` small q5-shaped MVs ticked
    end-to-end through one Session. ``coschedule`` toggles group-batched
    fused dispatch vs per-MV executor pipelines; ``pipeline_depth``
    toggles the asynchronous epoch pipeline; ``data_dir`` makes the
    session durable (the pipelined checkpoint-encode offload only
    exists on a durable tier)."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig

    s = Session(config=BuildConfig(coschedule=coschedule,
                                   agg_table_capacity=COSCHED_TABLE_CAP,
                                   chunk_capacity=COSCHED_CHUNK),
                source_chunk_capacity=COSCHED_CHUNK,
                checkpoint_frequency=checkpoint_frequency,
                chunks_per_tick=COSCHED_CHUNKS_PER_TICK,
                pipeline_depth=pipeline_depth,
                data_dir=data_dir)
    try:
        s.run_sql(_COSCHED_SOURCE_SQL)
        for j in range(n_jobs):
            s.run_sql(f"CREATE MATERIALIZED VIEW cosched_mv{j} AS "
                      "SELECT auction, count(*) AS n FROM bid "
                      "GROUP BY auction")
        for _ in range(warmup_ticks):     # jit compiles land here
            s.tick()
        s.barrier_latency.samples.clear()
        t0 = time.perf_counter()
        for _ in range(n_ticks):
            s.tick()
        elapsed = time.perf_counter() - t0
        lat = s.barrier_latency.snapshot()
    finally:
        s.close()
    return (n_jobs * n_ticks * COSCHED_CHUNKS_PER_TICK * COSCHED_CHUNK
            / elapsed, lat)


def measure_coscheduled(n_jobs: int, n_ticks: int) -> dict:
    """The many-small-MVs phase: ``n_jobs`` identical NEXmark-shaped MVs
    in one Session, co-scheduled ([streaming] coschedule = true — the
    whole group's epoch is ONE vmapped dispatch per tick,
    stream/coschedule.py) vs sequential (the same CREATEs with the flag
    off: one executor pipeline per MV, each dispatching its own epochs —
    exactly the pre-coscheduler session). End-to-end rows/s through
    materialization, so the ratio is the user-visible win."""
    seq, _ = _cosched_session_rate(False, n_jobs, n_ticks,
                                   COSCHED_WARMUP_TICKS)
    cos, _ = _cosched_session_rate(True, n_jobs, n_ticks,
                                   COSCHED_WARMUP_TICKS)
    return {
        "coscheduled_mvs_rows_per_sec": round(cos, 1),
        "coscheduled_sequential_rows_per_sec": round(seq, 1),
        "coschedule_speedup": round(cos / seq, 2),
        "coscheduled_n_mvs": n_jobs,
    }


def _hetero_mv_sql(j: int) -> str:
    """The j-th DISSIMILAR small MV: three skeletons (sum-with-literal,
    count+max over another key, plain count) with a per-j literal so
    same-skeleton MVs still differ — the tick compiler must lift the
    literal into a parameter hole to fuse them."""
    kind = j % 3
    if kind == 0:
        return (f"CREATE MATERIALIZED VIEW hetero_mv{j} AS "
                f"SELECT auction, sum(price + {100 + j}) AS s "
                "FROM bid GROUP BY auction")
    if kind == 1:
        return (f"CREATE MATERIALIZED VIEW hetero_mv{j} AS "
                "SELECT bidder, count(*) AS c, max(price) AS m "
                "FROM bid GROUP BY bidder")
    return (f"CREATE MATERIALIZED VIEW hetero_mv{j} AS "
            "SELECT auction, count(*) AS c FROM bid GROUP BY auction")


def _hetero_session_rate(tick_compiler: bool, n_jobs: int, n_ticks: int,
                         warmup_ticks: int):
    """Aggregate source rows/s of ``n_jobs`` DISSIMILAR small MVs
    ticked end-to-end through one Session; ``tick_compiler`` toggles
    the compiled minimal-dispatch schedule vs per-MV executor
    pipelines. Returns ``(rows_per_sec, dispatches_per_tick)`` —
    dispatches_per_tick is None on the baseline."""
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.build import BuildConfig

    s = Session(config=BuildConfig(tick_compiler=tick_compiler,
                                   agg_table_capacity=COSCHED_TABLE_CAP,
                                   chunk_capacity=COSCHED_CHUNK),
                source_chunk_capacity=COSCHED_CHUNK,
                chunks_per_tick=COSCHED_CHUNKS_PER_TICK)
    try:
        s.run_sql(_COSCHED_SOURCE_SQL)
        for j in range(n_jobs):
            s.run_sql(_hetero_mv_sql(j))
        for _ in range(warmup_ticks):     # jit compiles land here
            s.tick()
        t0 = time.perf_counter()
        for _ in range(n_ticks):
            s.tick()
        elapsed = time.perf_counter() - t0
        dpt = (s.metrics()["hetero"]["dispatches_per_tick"]
               if tick_compiler else None)
    finally:
        s.close()
    return (n_jobs * n_ticks * COSCHED_CHUNKS_PER_TICK * COSCHED_CHUNK
            / elapsed, dpt)


def measure_hetero(n_jobs: int, n_ticks: int) -> dict:
    """The heterogeneous many-small-MVs phase (ISSUE 19): ``n_jobs``
    DISSIMILAR NEXmark-shaped MVs in one Session, tick-compiled
    ([streaming] tick_compiler = true — shape-class padded supergroups
    + jitted mega-epochs, stream/tick_compiler.py) vs sequential (the
    same CREATEs with the flag off: one executor pipeline per MV).
    End-to-end rows/s through materialization."""
    seq, _ = _hetero_session_rate(False, n_jobs, n_ticks,
                                  COSCHED_WARMUP_TICKS)
    het, dpt = _hetero_session_rate(True, n_jobs, n_ticks,
                                    COSCHED_WARMUP_TICKS)
    return {
        "hetero_rows_per_sec": round(het, 1),
        "hetero_sequential_rows_per_sec": round(seq, 1),
        "hetero_speedup": round(het / seq, 2),
        "hetero_dispatches_per_tick": dpt,
        "hetero_n_mvs": n_jobs,
    }


def run_hetero_phase(n_jobs: int, n_ticks: int) -> None:
    """Child entry for ``--hetero-phase``: the heterogeneous
    tick-compiler measurement alone, one JSON line."""
    out = {"metric": "hetero_tick_compiler_rows_per_sec",
           "unit": "rows/s"}
    out.update(measure_hetero(n_jobs, n_ticks))
    out["value"] = out["hetero_rows_per_sec"]
    _emit(out)


def measure_pipelined(n_jobs: int, n_ticks: int) -> dict:
    """The asynchronous-epoch-pipeline phase (docs/performance.md
    "Pipelined tick"): the SAME 16-MV co-scheduled workload, durable
    (tempdir segment store, checkpoint every 5th barrier), measured
    with ``[streaming] pipeline_depth`` 1 vs 2 — the only variable.
    Depth 2 defers each packed flush fetch one tick (epoch N+1's
    dispatch launches before epoch N's stats resolve) and moves the
    checkpoint segment encode+write onto a worker thread, so both
    rows/s and the checkpoint-tick latency tail (p99) are reported."""
    import shutil
    import tempfile

    dirs = [tempfile.mkdtemp(prefix="rwtpu_bench_pipe_")
            for _ in range(2)]
    try:
        off, off_lat = _cosched_session_rate(
            True, n_jobs, n_ticks, COSCHED_WARMUP_TICKS,
            pipeline_depth=1, data_dir=dirs[0], checkpoint_frequency=5)
        on, on_lat = _cosched_session_rate(
            True, n_jobs, n_ticks, COSCHED_WARMUP_TICKS,
            pipeline_depth=2, data_dir=dirs[1], checkpoint_frequency=5)
    finally:
        for d in dirs:
            shutil.rmtree(d, ignore_errors=True)
    return {
        "pipeline_on_rows_per_sec": round(on, 1),
        "pipeline_off_rows_per_sec": round(off, 1),
        "pipeline_speedup": round(on / off, 2),
        "pipeline_on_p50_barrier_ms": on_lat.get("p50_ms"),
        "pipeline_on_p99_barrier_ms": on_lat.get("p99_ms"),
        "pipeline_off_p50_barrier_ms": off_lat.get("p50_ms"),
        "pipeline_off_p99_barrier_ms": off_lat.get("p99_ms"),
        "pipeline_depth": 2,
    }


def measure_barrier_latency(in_flight: int = 1) -> dict:
    """p99 barrier latency under a live Session-driven NEXmark MV at the
    reference's defaults (checkpoint every 10th barrier — BASELINE.md
    methodology / docs/metrics.md semantics)."""
    from risingwave_tpu.frontend import Session
    s = Session(source_chunk_capacity=CHUNK, checkpoint_frequency=10,
                in_flight_barriers=in_flight)
    s.run_sql("""CREATE SOURCE bid (auction BIGINT, price BIGINT)
                 WITH (connector = 'nexmark', nexmark_table = 'bid')""")
    s.run_sql("""CREATE MATERIALIZED VIEW m AS
        SELECT auction, count(*) AS n FROM bid GROUP BY auction""")
    for _ in range(5):
        s.tick()                    # warmup: jit compiles land here
    s._drain_inflight()
    s.barrier_latency.samples.clear()
    for _ in range(30):
        s.tick()
    s._drain_inflight()
    snap = s.barrier_latency.snapshot()
    # per-stage waterfall percentiles from the barrier ledger (ISSUE 16)
    # ride along so the trend record shows WHERE latency moved, not just
    # that it moved
    snap["stages"] = s._barrier_ledger.stage_percentiles()
    s.close()
    return snap


_SERVING_BID_DDL = """CREATE SOURCE bid (auction BIGINT, bidder BIGINT,
price BIGINT, channel VARCHAR, url VARCHAR, date_time TIMESTAMP,
extra VARCHAR) WITH (connector = 'nexmark', nexmark_table = 'bid')"""

_SERVING_Q5 = """CREATE MATERIALIZED VIEW q5 AS
    SELECT AuctionBids.auction, AuctionBids.num FROM (
        SELECT bid.auction, count(*) AS num, window_start AS starttime
        FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
        GROUP BY window_start, bid.auction
    ) AS AuctionBids
    JOIN (
        SELECT max(CountBids.num) AS maxn, CountBids.starttime_c
        FROM (
            SELECT count(*) AS num, window_start AS starttime_c
            FROM HOP(bid, date_time, INTERVAL '2' SECOND,
                     INTERVAL '10' SECOND)
            GROUP BY bid.auction, window_start
        ) AS CountBids
        GROUP BY CountBids.starttime_c
    ) AS MaxBids
    ON AuctionBids.starttime = MaxBids.starttime_c
       AND AuctionBids.num = MaxBids.maxn"""


def _serving_run(cached: bool, seconds: float, n_threads: int) -> dict:
    """One serving variant end to end: live q5 MV, a tick thread keeping
    the stream moving, ``n_threads`` readers issuing point-lookups and
    small group-by reads through ``Session.query``. ``cached=False``
    zeroes the plan cache and the two-phase split — every query replans,
    relowers, and runs single-phase under the API lock (the
    pre-serving-plane read path)."""
    from risingwave_tpu.common.config import load_config
    from risingwave_tpu.frontend import Session
    from risingwave_tpu.frontend.parser import parse_sql

    overrides = {"streaming.chunk_capacity": 512}
    if not cached:
        overrides.update({"batch.serving_cache_size": 0,
                          "batch.serving_tasks": 1})
    s = Session(rw_config=load_config(None, **overrides))
    s.run_sql(_SERVING_BID_DDL)
    s.run_sql(_SERVING_Q5)
    for _ in range(SERVING_WARM_TICKS):
        s.tick()
    s.flush()
    rows = s.mv_rows("q5")
    key = rows[0][0] if rows else 1000
    point = parse_sql(f"SELECT num FROM q5 WHERE auction = {key}")[0].select
    group = parse_sql("SELECT auction % 8, count(*), sum(num) "
                      "FROM q5 GROUP BY auction % 8")[0].select
    s.query(point)                      # warm: compiles land here
    s.query(group)

    stop = threading.Event()

    def ticker():
        while not stop.is_set():
            s.tick()
            stop.wait(SERVING_TICK_S)

    lat: dict = {0: [], 1: []}
    counts = [0] * n_threads
    errors: list = []
    t_tick = threading.Thread(target=ticker, daemon=True)

    def reader(idx: int, deadline: float):
        sels = (point, group)
        mine = ([], [])
        i = 0
        try:
            while time.perf_counter() < deadline:
                t0 = time.perf_counter()
                s.query(sels[i % 2])
                mine[i % 2].append(time.perf_counter() - t0)
                i += 1
        except BaseException as e:  # noqa: BLE001 - fails the phase
            errors.append(f"reader {idx}: {type(e).__name__}: {e}")
        counts[idx] = i
        lat[0].extend(mine[0])
        lat[1].extend(mine[1])

    t0 = time.perf_counter()
    deadline = t0 + seconds
    t_tick.start()
    threads = [threading.Thread(target=reader, args=(i, deadline))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    t_tick.join()
    wall = time.perf_counter() - t0
    m = s.metrics()["serving"]
    s.close()
    if errors:
        # a dead reader would silently skew QPS/p99 — attribute it like
        # every other phase failure instead
        raise RuntimeError("; ".join(errors))
    allq = sorted(lat[0] + lat[1])

    def pct(xs, q):
        return round(xs[min(len(xs) - 1, int(q * len(xs)))] * 1e3, 3) \
            if xs else None

    return {
        "qps": round(sum(counts) / wall, 1),
        "point_qps": round(len(lat[0]) / wall, 1),
        "group_qps": round(len(lat[1]) / wall, 1),
        "p50_ms": pct(allq, 0.5),
        "p99_ms": pct(allq, 0.99),
        "cache_hits": m["cache_hits"],
        "cache_misses": m["cache_misses"],
        "reexecutions": m["reexecutions"],
        "tasks_fired_local": m["tasks_fired_local"],
    }


def run_serving_phase(seconds: float, n_threads: int) -> None:
    """Child entry for --serving-phase: cached+two-phase vs uncached
    single-phase, one JSON line."""
    base = _serving_run(False, seconds, n_threads)
    served = _serving_run(True, seconds, n_threads)
    out = {
        "metric": "serving_qps", "unit": "queries/s",
        "value": served["qps"],
        "serving_qps": served["qps"],
        "serving_point_qps": served["point_qps"],
        "serving_group_qps": served["group_qps"],
        "serving_p50_ms": served["p50_ms"],
        "serving_p99_ms": served["p99_ms"],
        "serving_baseline_qps": base["qps"],
        "serving_baseline_p99_ms": base["p99_ms"],
        "serving_speedup": (round(served["qps"] / base["qps"], 2)
                            if base["qps"] else None),
        "serving_threads": n_threads,
        "serving_cache_hits": served["cache_hits"],
        "serving_reexecutions": served["reexecutions"],
    }
    _emit(out)


def _pg_startup(sock) -> None:
    """Minimal pgwire client startup (trust auth) on a raw socket."""
    import struct
    body = struct.pack("!I", 196608) + b"user\x00bench\x00\x00"
    sock.sendall(struct.pack("!I", len(body) + 4) + body)
    buf = b""
    while b"Z\x00\x00\x00\x05I" not in buf:
        chunk = sock.recv(4096)
        if not chunk:
            raise ConnectionError("pgwire startup EOF")
        buf += chunk


def _pg_query(sock, sql: str) -> bytes:
    """One simple-protocol query; returns the raw response bytes
    (ending with ReadyForQuery)."""
    import struct
    body = sql.encode() + b"\x00"
    sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
    buf = b""
    while not buf.endswith(b"Z\x00\x00\x00\x05I"):
        chunk = sock.recv(65536)
        if not chunk:
            raise ConnectionError("pgwire EOF mid-query")
        buf += chunk
    return buf


def run_fleet_frontend(meta_addr: str, data_dir: str) -> None:
    """Hidden child entry for --fleet-frontend: attach ONE read-only
    serving session to the fleet's meta + shared state dir, serve it
    over pgwire on an ephemeral port, print ``FLEET_READY <port>``,
    run until the parent writes a line on stdin, then print
    ``FLEET_STATS {json}`` (admission counters + serving-cache hits)
    and exit."""
    import asyncio as _asyncio

    from risingwave_tpu.frontend.pgwire import PgWireServer
    from risingwave_tpu.frontend.session import Session

    sess = Session(data_dir=data_dir, meta_addr=meta_addr, role="serving")
    srv = PgWireServer(sess, port=0)
    loop = _asyncio.new_event_loop()
    _asyncio.set_event_loop(loop)
    loop.run_until_complete(srv.start())
    port = srv._server.sockets[0].getsockname()[1]
    print(f"FLEET_READY {port}", flush=True)

    def wait_stdin():
        sys.stdin.readline()           # parent writes STOP (or closes)
        loop.call_soon_threadsafe(loop.stop)

    threading.Thread(target=wait_stdin, daemon=True).start()
    loop.run_forever()
    loop.run_until_complete(srv.close())
    m = sess.metrics()["serving"]
    print("FLEET_STATS " + json.dumps(
        {"admission": srv.admission.snapshot(),
         "cache_hits": m["cache_hits"],
         "cache_misses": m["cache_misses"]}), flush=True)
    sess.close()


def run_fleet_phase(seconds: float, n_frontends: int,
                    n_conns: int) -> None:
    """Child entry for --fleet-phase: the multi-tenant control plane end
    to end — a standalone MetaServer and one writer session build an MV
    over a shared durable hummock dir; ``n_frontends`` serving frontend
    PROCESSES attach read-only and serve it over pgwire; ``n_conns``
    connections per frontend hammer the same cached point read. Emits
    merged fleet QPS + p50/p99 and the admission counters (queued /
    shed) summed across frontends. One JSON line."""
    import socket
    import tempfile

    from risingwave_tpu.frontend.session import Session
    from risingwave_tpu.meta.server import MetaServer

    d = tempfile.mkdtemp(prefix="rwtpu_bench_fleet_")
    from risingwave_tpu.common.compile_cache import (
        export_compile_cache_env,
    )
    export_compile_cache_env()
    meta = MetaServer(data_dir=os.path.join(d, "meta"))
    addr = meta.start()
    writer = Session(data_dir=d, meta_addr=addr, state_store="hummock")
    procs: list = []
    lats: list = []
    stats: list = []
    try:
        writer.run_sql("CREATE TABLE ft (k BIGINT, v BIGINT)")
        writer.run_sql("INSERT INTO ft VALUES " + ", ".join(
            f"({i % 64}, {i})" for i in range(512)))
        writer.run_sql(
            "CREATE MATERIALIZED VIEW fleet_mv AS SELECT k, "
            "count(*) AS n, sum(v) AS s FROM ft GROUP BY k")
        writer.flush()

        ports = []
        for _ in range(n_frontends):
            procs.append(subprocess.Popen(
                [sys.executable, os.path.abspath(__file__),
                 "--fleet-frontend", addr, d],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                text=True,
                cwd=os.path.dirname(os.path.abspath(__file__))))
        for pr in procs:
            while True:
                line = pr.stdout.readline()
                if not line:
                    raise RuntimeError("fleet frontend died during attach")
                if line.startswith("FLEET_READY "):
                    ports.append(int(line.split()[1]))
                    break

        lat_lock = threading.Lock()
        stop_at = time.perf_counter() + seconds

        def reader(port: int) -> None:
            sock = socket.create_connection(("127.0.0.1", port))
            try:
                _pg_startup(sock)
                sql = "SELECT k, n, s FROM fleet_mv WHERE k = 7"
                _pg_query(sock, sql)          # warm the plan cache
                mine = []
                while time.perf_counter() < stop_at:
                    t0 = time.perf_counter()
                    _pg_query(sock, sql)
                    mine.append((time.perf_counter() - t0) * 1e3)
                with lat_lock:
                    lats.extend(mine)
            finally:
                sock.close()

        threads = [threading.Thread(target=reader, args=(p,))
                   for p in ports for _ in range(n_conns)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        for pr in procs:
            try:
                pr.stdin.write("STOP\n")
                pr.stdin.flush()
            except OSError:
                pass
            out, _ = pr.communicate(timeout=60)
            for line in out.splitlines():
                if line.startswith("FLEET_STATS "):
                    stats.append(json.loads(line[len("FLEET_STATS "):]))
    finally:
        for pr in procs:
            if pr.poll() is None:
                pr.kill()
        writer.close()
        meta.stop()

    lats.sort()

    def pct(q: float):
        if not lats:
            return None
        return round(lats[min(len(lats) - 1, int(q * len(lats)))], 2)

    _emit({
        "metric": "fleet_qps", "unit": "queries/s",
        "value": round(len(lats) / wall, 1) if lats else 0.0,
        "fleet_qps": round(len(lats) / wall, 1) if lats else 0.0,
        "fleet_p50_ms": pct(0.50),
        "fleet_p99_ms": pct(0.99),
        "fleet_queued": sum(s["admission"]["queued"] for s in stats),
        "fleet_shed": sum(s["admission"]["shed"] for s in stats),
        "fleet_frontends": n_frontends,
        "fleet_conns_per_frontend": n_conns,
        "fleet_cache_hits": sum(s["cache_hits"] for s in stats),
    })


def run_rescale_phase(ticks: int = 6, cap: int = 256) -> None:
    """Child entry for --rescale-phase: one LIVE 2→4 vnode migration of
    a spanning grouped-agg job on a 4-worker cluster (docs/scaling.md),
    recording rows/s before / during / after plus the migration pause
    (drain→init wall time) and the moved vnode count. One JSON line."""
    import tempfile

    from risingwave_tpu.frontend.build import BuildConfig
    from risingwave_tpu.frontend.session import Session

    d = tempfile.mkdtemp(prefix="rwtpu_bench_rescale_")
    from risingwave_tpu.common.compile_cache import (
        export_compile_cache_env,
    )
    export_compile_cache_env()
    s = Session(workers=4, seed=42, data_dir=d, source_chunk_capacity=cap,
                config=BuildConfig(fragment_parallelism=2))
    try:
        s.run_sql(
            "CREATE SOURCE bid (auction BIGINT, bidder BIGINT, "
            "price BIGINT, channel VARCHAR, url VARCHAR, "
            "date_time TIMESTAMP, extra VARCHAR) "
            "WITH (connector = 'nexmark', nexmark_table = 'bid')")
        s.run_sql("CREATE MATERIALIZED VIEW q AS SELECT auction, "
                  "count(*) AS n, max(price) AS mx FROM bid "
                  "GROUP BY auction")
        assert "q" in s._spanning_specs, "q did not span workers"

        def run_ticks(n: int) -> float:
            t0 = time.perf_counter()
            for _ in range(n):
                s.tick()
            return (n * s.chunks_per_tick * cap) / (
                time.perf_counter() - t0)

        run_ticks(2)                       # warm the compiled graphs
        before = run_ticks(ticks)
        t0 = time.perf_counter()
        out = s.rescale("q", 4)
        mid = run_ticks(ticks)
        during_wall = time.perf_counter() - t0
        # "during" folds the migration pause into the window's rate —
        # the number a serving operator actually experiences
        during = (ticks * s.chunks_per_tick * cap) / during_wall
        after = run_ticks(ticks)
        _emit({
            "metric": "rescale_pause_ms", "unit": "ms",
            "value": out["pause_ms"],
            "rescale_pause_ms": out["pause_ms"],
            "rescale_moved_vnodes": out["moved_vnodes"],
            "rescale_rows_per_sec_before": round(before, 1),
            "rescale_rows_per_sec_during": round(during, 1),
            "rescale_rows_per_sec_after": round(after, 1),
            "rescale_parallelism": out["parallelism"],
            "rescale_mid_window_rows_per_sec": round(mid, 1),
        })
    finally:
        s.close()


def run_failover_phase(seed: int = 7) -> None:
    """Child entry for --failover-phase: one full leader-failover
    acceptance run (sim.run_failover — kill -9 the writer process
    mid-stream, a standby auto-promotes, exactly-once audited),
    recording the recovery-time numbers ISSUE 18 publishes: MTTR
    (kill → standby conducting), leader-down detection latency, and the
    p99 gap between committed checkpoints over the whole run — the
    unavailability window a serving operator actually experiences
    (dominated by the failover gap). One JSON line."""
    import tempfile

    from risingwave_tpu.sim import run_failover

    r = run_failover(seed=seed,
                     data_dir=tempfile.mkdtemp(prefix="rwtpu_benchfo_"))
    gaps = sorted(r.get("gap_samples_ms") or [0.0])
    p99 = gaps[min(len(gaps) - 1, int(0.99 * len(gaps)))]
    _emit({
        "metric": "failover_mttr_ms", "unit": "ms",
        "value": r["mttr_ms"],
        "failover_mttr_ms": r["mttr_ms"],
        "failover_detect_ms": r["detect_ms"],
        "failover_p99_unavail_ms": round(p99, 3),
        "failover_lease_ttl_s": r["lease_ttl_s"],
        "failover_terms": r["terms"],
        "failover_elections_lost": r["elections_lost"],
        "failover_audit_ok": int(all(r["audit"].values())),
        "failovers": r["failovers"],
    })


def run_phase(n_chunks: int, q7_chunks: int, q8_chunks: int,
              q3_chunks: int) -> None:
    """Child entry: measure everything on this process's backend, print one
    JSON line."""
    out = {"metric": "nexmark_q5_core_throughput", "unit": "rows/s"}
    # fused single-dispatch epochs are the headline for EVERY query; the
    # q5/q7 executor paths are kept as secondaries so the fusion win
    # stays visible in the record
    out["value"] = round(measure_q5_fused(n_chunks), 1)
    out["q5_executor_rows_per_sec"] = round(measure_q5(n_chunks), 1)
    out["q7_rows_per_sec"] = round(measure_q7_fused(2 * q7_chunks), 1)
    out["q7_executor_rows_per_sec"] = round(measure_q7(q7_chunks), 1)
    out["q8_rows_per_sec"] = round(measure_q8_fused(q8_chunks), 1)
    out["q3_rows_per_sec"] = round(measure_q3_fused(q3_chunks), 1)
    out.update(measure_coscheduled(COSCHED_JOBS, COSCHED_TICKS))
    out.update(measure_hetero(HETERO_JOBS, HETERO_TICKS))
    out.update(measure_pipelined(COSCHED_JOBS, COSCHED_TICKS))
    # p50/p99 barrier latency is measured on every backend
    lat = measure_barrier_latency(in_flight=1)
    out["p99_barrier_ms"] = lat.get("p99_ms")
    out["p50_barrier_ms"] = lat.get("p50_ms")
    for stage in ("inject", "pending", "collect", "commit"):
        pct = (lat.get("stages") or {}).get(stage) or {}
        out[f"barrier_{stage}_p50_ms"] = pct.get("p50_ms")
        out[f"barrier_{stage}_p99_ms"] = pct.get("p99_ms")
    lat4 = measure_barrier_latency(in_flight=4)
    out["p99_barrier_ms_inflight4"] = lat4.get("p99_ms")
    _emit(out)


def run_probe() -> None:
    """Child entry for the cheap smoke probe: prove the backend can
    compile + run ONE tiny jit, print one JSON line. Costs seconds on a
    healthy backend; a wedged one trips the init watchdog instead of
    burning a full phase timeout."""
    import jax
    import jax.numpy as jnp
    y = jax.jit(lambda x: x * 2 + 1)(jnp.arange(8))
    jax.block_until_ready(y)
    _emit({"probe": "ok", "backend": jax.default_backend(),
           "n_devices": len(jax.devices())})


# ---------------------------------------------------------------------------
# Parent: subprocess orchestration (never initializes a JAX backend)
# ---------------------------------------------------------------------------

#: per-phase diagnostics, emitted in EVERY result JSON: each phase
#: records its rc and the full stderr tail, so a failing run is
#: debuggable from the record alone.
PHASE_LOG: dict = {}

#: per-phase persistence: each completed phase's record is appended here
#: as a JSON line the moment it finishes, so a mid-run kill still leaves
#: every completed phase on disk.
PARTIAL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "BENCH_partial.json")


def _persist_phase(name: str, record: dict) -> None:
    try:
        with open(PARTIAL_PATH, "a") as f:
            f.write(json.dumps(
                {"phase": name,
                 "ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                 "record": record}) + "\n")
            f.flush()
            os.fsync(f.fileno())
    except OSError as e:                    # persistence must never kill
        sys.stderr.write(f"bench: partial persist failed: {e}\n")


def _spawn_phase(name: str, env_overrides: dict, args_tail: list,
                 timeout: float = PHASE_TIMEOUT) -> dict:
    env = dict(os.environ)
    for k, v in env_overrides.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    args = [sys.executable, os.path.abspath(__file__)] + args_tail
    t0 = time.monotonic()
    rec: dict = {"env": {k: v for k, v in env_overrides.items()
                         if v is not None}}
    PHASE_LOG[name] = rec
    try:
        res = subprocess.run(
            args, env=env, capture_output=True, text=True,
            timeout=timeout,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
    except subprocess.TimeoutExpired as e:
        rec.update({"rc": "timeout", "duration_s": round(
            time.monotonic() - t0, 1),
            "stderr_tail": ((e.stderr or b"").decode(errors="replace")
                            if isinstance(e.stderr, bytes)
                            else (e.stderr or ""))[-4000:]})
        _persist_phase(name, rec)
        raise RuntimeError(
            f"phase {name} timed out after {timeout}s") from None
    rec["rc"] = res.returncode
    rec["duration_s"] = round(time.monotonic() - t0, 1)
    if res.returncode != 0:
        rec["stderr_tail"] = (res.stderr or "")[-4000:]
        rec["stdout_tail"] = (res.stdout or "")[-1000:]
        # the child's diagnostic fail-line (if it got that far) carries
        # the root cause as structured JSON on stdout — surface it
        for line in reversed((res.stdout or "").strip().splitlines()):
            try:
                parsed = json.loads(line)
            except ValueError:
                continue
            if isinstance(parsed, dict) and "error" in parsed:
                rec["error"] = parsed["error"]
            break
        _persist_phase(name, rec)
        raise RuntimeError(
            f"phase {name} rc={res.returncode}: "
            f"{rec.get('error') or (res.stderr or res.stdout or '')[-500:]}")
    line = res.stdout.strip().splitlines()[-1]
    parsed = json.loads(line)
    if "error" in parsed:
        rec["error"] = parsed["error"]
        rec["stderr_tail"] = (res.stderr or "")[-4000:]
        _persist_phase(name, rec)
        raise RuntimeError(parsed["error"])
    _persist_phase(name, parsed)
    return parsed


def _measure_args(n_chunks: int, q7: int, q8: int, q3: int) -> list:
    return ["--phase", str(n_chunks), str(q7), str(q8), str(q3)]


def measure_cpu_standin() -> dict:
    """Run the same pipelines under JAX_PLATFORMS=cpu in a fresh
    subprocess."""
    env = {"JAX_PLATFORMS": "cpu"}
    return _spawn_phase("cpu_standin", env,
                        _measure_args(CPU_N_CHUNKS, Q7_CPU_N_CHUNKS,
                                      Q8_CPU_N_CHUNKS, Q3_CPU_N_CHUNKS))


_SHARDED_RESULT_FIELDS = (
    "sharded_fused_shards", "sharded_fused_by_shards",
    "q5_sharded_fused_rows_per_sec", "q7_sharded_fused_rows_per_sec",
    "q8_sharded_fused_rows_per_sec", "q3_sharded_fused_rows_per_sec",
    "cosched_sharded_rows_per_sec", "cosched_sharded_jobs",
)

_SERVING_RESULT_FIELDS = (
    "serving_qps", "serving_point_qps", "serving_group_qps",
    "serving_p50_ms", "serving_p99_ms",
    "serving_baseline_qps", "serving_baseline_p99_ms", "serving_speedup",
)

_RESCALE_RESULT_FIELDS = (
    "rescale_pause_ms", "rescale_moved_vnodes",
    "rescale_rows_per_sec_before", "rescale_rows_per_sec_during",
    "rescale_rows_per_sec_after",
)

_FLEET_RESULT_FIELDS = (
    "fleet_qps", "fleet_p50_ms", "fleet_p99_ms",
    "fleet_queued", "fleet_shed", "fleet_frontends",
)

_FAILOVER_RESULT_FIELDS = (
    "failover_mttr_ms", "failover_p99_unavail_ms",
    "failover_detect_ms",
)


def measure_failover_cpu() -> dict:
    """The leader-failover phase on the CPU stand-in: one full
    sim.run_failover acceptance run (standalone meta + doomed writer
    process + 2 standbys; a control-plane measurement — fresh
    subprocess like every phase, which itself spawns the writer
    process it kills)."""
    env = {"JAX_PLATFORMS": "cpu"}
    return _spawn_phase("failover_cpu", env, ["--failover-phase"])


def measure_fleet_cpu() -> dict:
    """The multi-tenant fleet phase on the CPU stand-in: standalone
    meta + writer + 2 serving frontend processes × several pgwire
    connections each (a Session/control-plane measurement; fresh
    subprocess like every phase — which itself spawns the frontend
    processes)."""
    env = {"JAX_PLATFORMS": "cpu"}
    return _spawn_phase("fleet_cpu", env,
                        ["--fleet-phase", str(FLEET_SECONDS),
                         str(FLEET_FRONTENDS), str(FLEET_CONNS)])


def measure_rescale_cpu() -> dict:
    """The elastic-scaling phase on the CPU stand-in: a live 2→4 vnode
    migration of a spanning job mid-stream, measuring the migration
    pause and rows/s before/during/after (a Session-level measurement;
    fresh subprocess like every phase)."""
    env = {"JAX_PLATFORMS": "cpu"}
    return _spawn_phase("rescale_cpu", env, ["--rescale-phase"])


def measure_serving_cpu() -> dict:
    """The serving phase on the CPU stand-in (a Session-level
    measurement: plan cache + two-phase reads vs the uncached
    single-phase baseline, concurrent with live ticks). Runs in a fresh
    subprocess like every phase."""
    env = {"JAX_PLATFORMS": "cpu"}
    return _spawn_phase("serving_cpu", env,
                        ["--serving-phase", str(SERVING_SECONDS),
                         str(SERVING_THREADS)])


def measure_sharded_cpu() -> dict:
    """The mesh-sharded fused phase on the CPU stand-in: a virtual
    8-device mesh (XLA_FLAGS=--xla_force_host_platform_device_count) in
    a fresh subprocess. The record persisted to BENCH_partial.json is the
    MULTICHIP-style sub-record (n_devices / ok / per-shard-count rates)
    the driver's dryrun artifacts established."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags = (flags + " --xla_force_host_platform_device_count="
                 f"{SHARDED_VIRTUAL_DEVICES}").strip()
    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags}
    return _spawn_phase("sharded_fused_cpu", env,
                        ["--sharded-phase", str(SHARDED_N_CHUNKS),
                         str(SHARDED_Q7_N_CHUNKS)])


def measure_sharded_tpu() -> dict:
    """The sharded phase on the real mesh — only meaningful on a
    multi-chip host; a single-chip backend still records a 1-shard
    point. A failure raises, like every chip phase."""
    return _spawn_phase("sharded_fused_tpu", _tpu_cache_env(),
                        ["--sharded-phase", str(SHARDED_N_CHUNKS),
                         str(SHARDED_Q7_N_CHUNKS)])


def _compile_cache_dir() -> str:
    """The repo's one compile-cache resolver
    (risingwave_tpu/common/compile_cache.py), loaded BY PATH: importing
    the package would import JAX into this parent."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_rw_compile_cache",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "risingwave_tpu", "common", "compile_cache.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.compile_cache_dir()


def _tpu_cache_env() -> dict:
    """The persistent XLA compilation cache every chip phase shares:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else the fixed in-checkout
    path — identical across calls, so a second run hits (min-compile-
    time 0 so even small executables cache)."""
    return {"JAX_COMPILATION_CACHE_DIR": _compile_cache_dir(),
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}


def measure_tpu() -> dict:
    """The chip phase: ONE attempt in a fresh process, after a cheap
    smoke probe (tiny jit, short timeout) that must land on platform
    "tpu". Runs what the selector picks on the chip (the compiled
    Pallas kernels); any failure raises — nothing is retried with a
    switch flipped."""
    env = _tpu_cache_env()
    probe = _spawn_phase("tpu_probe", env, ["--probe"],
                         timeout=PROBE_TIMEOUT)
    if probe.get("backend") != "tpu":
        raise RuntimeError(
            f"probe landed on {probe.get('backend')!r}, not tpu")
    return _spawn_phase("tpu_phase", env,
                        _measure_args(N_CHUNKS, Q7_N_CHUNKS,
                                      Q8_N_CHUNKS, Q3_N_CHUNKS))


#: fields the chip phases measure (the --phase and --sharded-phase
#: records): copied from the CHIP record only — a field the chip phases
#: did not produce stays null
_SHARED_FIELDS = (
    "q5_executor_rows_per_sec", "q7_executor_rows_per_sec",
    "q8_rows_per_sec", "q3_rows_per_sec",
    "coscheduled_mvs_rows_per_sec",
    "coscheduled_sequential_rows_per_sec", "coschedule_speedup",
    "coscheduled_n_mvs",
    # heterogeneous tick compiler (stream/tick_compiler.py)
    "hetero_rows_per_sec", "hetero_sequential_rows_per_sec",
    "hetero_speedup", "hetero_dispatches_per_tick", "hetero_n_mvs",
    # asynchronous epoch pipeline ([streaming] pipeline_depth = 2 vs 1)
    "pipeline_on_rows_per_sec", "pipeline_off_rows_per_sec",
    "pipeline_speedup", "pipeline_depth",
    "pipeline_on_p50_barrier_ms", "pipeline_on_p99_barrier_ms",
    "pipeline_off_p50_barrier_ms", "pipeline_off_p99_barrier_ms",
    "p99_barrier_ms", "p50_barrier_ms", "p99_barrier_ms_inflight4",
    # barrier-observatory waterfall (common/barrier_ledger.py)
    "barrier_inject_p50_ms", "barrier_inject_p99_ms",
    "barrier_pending_p50_ms", "barrier_pending_p99_ms",
    "barrier_collect_p50_ms", "barrier_collect_p99_ms",
    "barrier_commit_p50_ms", "barrier_commit_p99_ms",
    # mesh-sharded fused epochs (ops/fused_sharded.py)
    "sharded_fused_shards", "sharded_fused_by_shards",
    "q5_sharded_fused_rows_per_sec", "q7_sharded_fused_rows_per_sec",
    "q8_sharded_fused_rows_per_sec", "q3_sharded_fused_rows_per_sec",
    "cosched_sharded_rows_per_sec", "cosched_sharded_jobs",
)


def main() -> int:
    # fresh per-phase persistence file for this run (appended as phases
    # finish; survives any later wedge/kill)
    try:
        open(PARTIAL_PATH, "w").close()
    except OSError:
        pass
    # the CPU phases: the stand-in baseline, the virtual-mesh sharded
    # phase, and the Session/control-plane phases (serving, live
    # rescale, frontend fleet, leader failover). Any phase that fails
    # fails the run — none is caught and reported beside an exit code 0.
    try:
        cpu = measure_cpu_standin()
        for measure, fields in (
                (measure_sharded_cpu, _SHARDED_RESULT_FIELDS),
                (measure_serving_cpu, _SERVING_RESULT_FIELDS),
                (measure_rescale_cpu, _RESCALE_RESULT_FIELDS),
                (measure_fleet_cpu, _FLEET_RESULT_FIELDS),
                (measure_failover_cpu, _FAILOVER_RESULT_FIELDS)):
            rec = measure()
            for f in fields:
                cpu[f] = rec.get(f)
    except Exception as e:
        sys.stderr.write(f"bench: cpu phase failed: {e}\n")
        out = _fail_line(f"cpu phase failed: {e}")
        out["phases"] = PHASE_LOG
        _emit(out)
        return 2
    cpu_rps, cpu_q7 = cpu["value"], cpu["q7_rows_per_sec"]
    # the chip phases: a failure here FAILS the run — the CPU stand-in is
    # a baseline, never the headline, and no CPU-measured field is copied
    # into the chip record
    try:
        tpu = measure_tpu()
        sharded_tpu = measure_sharded_tpu()
    except Exception as e:
        sys.stderr.write(f"bench: chip phase failed: {e}\n")
        out = _fail_line(f"chip phase failed: {e}")
        out["phases"] = PHASE_LOG
        _emit(out)
        return 2
    for f in _SHARDED_RESULT_FIELDS:
        tpu[f] = sharded_tpu.get(f)
    tpu["sharded_fused_n_devices"] = sharded_tpu.get("n_devices")
    out = {
        "metric": "nexmark_q5_core_throughput",
        "value": tpu["value"],
        "unit": "rows/s",
        "vs_baseline": round(tpu["value"] / cpu_rps, 2),
        "baseline_kind": "same pipeline, JAX_PLATFORMS=cpu "
                         "(Rust-engine stand-in)",
        "cpu_standin_rows_per_sec": round(cpu_rps, 1),
        "q5_cpu_executor_rows_per_sec": cpu.get("q5_executor_rows_per_sec"),
        "chunks_per_dispatch": CHUNKS_PER_EPOCH,
        "ingest": "fused single-dispatch epochs (gen+project+agg in one "
                  "lax.scan; ops/fused_epoch.py)",
        "q7_join": "fused single-dispatch epochs (gen+project+bucketed "
                   "interval join+max flush in one lax.scan; "
                   "ops/interval_join.py)",
        "q7_join_rows_per_sec": tpu["q7_rows_per_sec"],
        "q7_vs_baseline": round(tpu["q7_rows_per_sec"] / cpu_q7, 2),
        "q7_cpu_standin_rows_per_sec": round(cpu_q7, 1),
        "q7_cpu_executor_rows_per_sec": cpu.get("q7_executor_rows_per_sec"),
        "q8_cpu_rows_per_sec": cpu.get("q8_rows_per_sec"),
        "q3_cpu_rows_per_sec": cpu.get("q3_rows_per_sec"),
        "cpu_coschedule_speedup": cpu.get("coschedule_speedup"),
        "cpu_pipeline_speedup": cpu.get("pipeline_speedup"),
        "cpu_p99_barrier_ms": cpu.get("p99_barrier_ms"),
        "cpu_p50_barrier_ms": cpu.get("p50_barrier_ms"),
        "phases": PHASE_LOG,
    }
    for f in _SHARED_FIELDS:
        out[f] = tpu.get(f)
    # serving / rescale / fleet / failover are Session- and control-
    # plane-level phases that ran under JAX_PLATFORMS=cpu: reported
    # under a name that says so, never among the chip's fields
    out["cpu_control_plane"] = {
        f: cpu.get(f)
        for f in (_SERVING_RESULT_FIELDS + _RESCALE_RESULT_FIELDS
                  + _FLEET_RESULT_FIELDS + _FAILOVER_RESULT_FIELDS)}
    qv = tpu.get("q8_rows_per_sec")
    if qv and cpu.get("q8_rows_per_sec"):
        out["q8_vs_baseline"] = round(qv / cpu["q8_rows_per_sec"], 2)
    qv = tpu.get("q3_rows_per_sec")
    if qv and cpu.get("q3_rows_per_sec"):
        out["q3_vs_baseline"] = round(qv / cpu["q3_rows_per_sec"], 2)
    _emit(out)
    return 0


# ---------------------------------------------------------------------------
# --smoke: one tiny in-process phase for CI (scripts/check.sh) — seconds,
# CPU, asserts the 1-dispatch-per-epoch invariant on every fused surface
# ---------------------------------------------------------------------------


def run_smoke() -> int:
    import jax
    import jax.numpy as jnp
    from risingwave_tpu.common import INT64, TIMESTAMP
    from risingwave_tpu.common.dispatch_count import count_dispatches
    from risingwave_tpu.common.types import Field, Schema
    from risingwave_tpu.connector import NexmarkConfig
    from risingwave_tpu.connector.nexmark import DeviceBidGenerator
    from risingwave_tpu.connector.tpch import (
        DeviceQ3Generator, Q3_CUTOFF_DAYS, TpchQ3Config,
    )
    from risingwave_tpu.expr import col
    from risingwave_tpu.ops.fused_epoch import (
        fused_source_q3_epoch, fused_source_session_epoch,
    )
    from risingwave_tpu.ops.session_window import SessionWindowCore
    from risingwave_tpu.ops.stream_q3 import Q3Core
    from risingwave_tpu.stream.coschedule import CoGroup, FusedJobSpec

    t0 = time.perf_counter()
    cap, k, jobs = 128, 4, 4
    checks = []
    with count_dispatches() as c:
        # q5-shaped co-scheduled group: 1 dispatch per epoch for J jobs
        exprs, agg, chunk_fn = _cosched_parts()
        spec = FusedJobSpec("agg", ("smoke",), chunk_fn, tuple(exprs),
                            agg.core, COSCHED_SMOKE_CHUNK, seed=0)
        group = CoGroup(spec)
        for j in range(jobs):
            group.add(f"mv{j}", agg.core.init_state(), seed=j)
        group.run_epoch(k)
        group.flush()
        c.reset()
        group.run_epoch(k)
        n = c.counts["build_group_epoch.<locals>.coscheduled_epoch"]
        assert n == 1, f"cosched epoch took {n} dispatches"
        checks.append(f"cosched[{jobs}]=1 dispatch/epoch")

        # heterogeneous tick compiler (stream/tick_compiler.py): 200
        # DISSIMILAR small jobs must compile to a <= 8-dispatch
        # schedule, and a live run must issue exactly one dispatch per
        # compiled group per epoch (cross-checked against the profiler)
        from risingwave_tpu.expr.agg import agg as _agg, count_star
        from risingwave_tpu.ops.grouped_agg import AggCore
        from risingwave_tpu.stream.tick_compiler import (
            MEGA_EPOCH_FN, PADDED_EPOCH_FN, TickCompiler,
        )
        from risingwave_tpu.common import INT64 as _I64
        from risingwave_tpu.expr import Literal, call as _call, col as _col
        from risingwave_tpu.common.types import TIMESTAMP as _TS
        hcap, hrows = 256, 64
        hgen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=hrows))

        def _hspec(j):
            kind = j % 4
            if kind == 0:       # tumble window, per-j literal (holes)
                exprs = [_call("tumble_start", _col(5, _TS),
                               Literal(1_000_000 + j, _I64)),
                         _col(0, _I64)]
                core = AggCore((_I64, _I64), (0, 1), [count_star()],
                               table_capacity=hcap, out_capacity=hrows)
            elif kind == 1:     # sum with per-j literal over auction
                exprs = [_col(0, _I64),
                         _call("add", _col(2, _I64),
                               Literal(100 + j, _I64))]
                core = AggCore((_I64,), (0,),
                               [count_star(), _agg("sum", 1, _I64)],
                               table_capacity=hcap, out_capacity=hrows)
            elif kind == 2:     # max over bidder (no holes)
                exprs = [_col(1, _I64), _col(2, _I64)]
                core = AggCore((_I64,), (0,), [_agg("max", 1, _I64)],
                               table_capacity=hcap, out_capacity=hrows)
            else:               # plain count over auction
                exprs = [_col(0, _I64)]
                core = AggCore((_I64,), (0,), [count_star()],
                               table_capacity=hcap, out_capacity=hrows)
            return FusedJobSpec(
                "agg", ("smoke-hetero", kind), hgen.chunk_fn(),
                tuple(exprs), core, hrows, seed=j), core

        tc = TickCompiler()
        for j in range(200):
            spec_j, core_j = _hspec(j)
            tc.add(f"h{j}", spec_j, core_j.init_state(),
                   n_source_cols=7)
        # two UNIQUE skeletons: singletons that must pack into one
        # mega-epoch (tier 2) rather than get a dispatch each
        for nm, aggs in (("h_min", [_agg("min", 1, _I64)]),
                         ("h_sum", [_agg("sum", 1, _I64)])):
            core_s = AggCore((_I64,), (0,), aggs,
                             table_capacity=hcap, out_capacity=hrows)
            spec_s = FusedJobSpec(
                "agg", ("smoke-hetero", nm), hgen.chunk_fn(),
                (_col(1, _I64), _col(2, _I64)), core_s, hrows, seed=0)
            tc.add(nm, spec_s, core_s.init_state(), n_source_cols=7)
        tc.ensure_compiled()
        hstats = tc.stats()
        assert hstats["jobs"] == 202
        assert sorted(g["kind"] for g in hstats["groups"]) == \
            ["mega", "padded", "padded", "padded", "padded"]
        assert hstats["dispatches_per_tick"] <= 8, \
            f"200 MVs need {hstats['dispatches_per_tick']} dispatches"
        c.reset()
        for g in tc.groups:
            g.run_epoch(2)
        got = (c.counts.get(PADDED_EPOCH_FN, 0)
               + c.counts.get(MEGA_EPOCH_FN, 0))
        assert got == hstats["dispatches_per_tick"], \
            f"epoch took {got} dispatches, schedule promised " \
            f"{hstats['dispatches_per_tick']}"
        checks.append(
            f"hetero[202]={hstats['dispatches_per_tick']} "
            "dispatches/tick (<=8)")

        # q8 session epoch
        sw = SessionWindowCore(
            Schema((Field("bidder", INT64), Field("ts", TIMESTAMP))),
            0, 1, gap_us=5_000, capacity=1 << 10,
            closed_capacity=1 << 10)
        gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=cap))
        q8 = fused_source_session_epoch(
            gen.chunk_fn(), [col(1, INT64), col(5, TIMESTAMP)], sw, cap)
        st, snap, packed = q8(sw.init_state(), jnp.int64(0),
                              jax.random.PRNGKey(0), k, jnp.int64(0))
        c.reset()
        st, snap, packed = q8(st, jnp.int64(k * cap),
                              jax.random.PRNGKey(1), k, jnp.int64(0))
        n = c.counts["fused_source_session_epoch.<locals>.epoch"]
        assert n == 1, f"q8 epoch took {n} dispatches"
        assert not any(int(x) for x in jax.device_get(packed)[1:])
        checks.append("q8=1 dispatch/epoch")

        # q3 epoch
        q3core = Q3Core(Q3_CUTOFF_DAYS, orders_capacity=1 << 10,
                        agg_capacity=1 << 10)
        q3gen = DeviceQ3Generator(TpchQ3Config(chunk_capacity=cap))
        q3 = fused_source_q3_epoch(q3gen.chunk_fn(), q3core, cap)
        st3, out3, packed3 = q3(q3core.init_state(), jnp.int64(0),
                                jax.random.PRNGKey(0), k)
        c.reset()
        st3, out3, packed3 = q3(st3, jnp.int64(k * cap),
                                jax.random.PRNGKey(0), k)
        n = c.counts["fused_source_q3_epoch.<locals>.epoch"]
        assert n == 1, f"q3 epoch took {n} dispatches"
        assert not any(int(x) for x in jax.device_get(packed3)[1:])
        checks.append("q3=1 dispatch/epoch")

        # mesh-sharded fused epochs (ops/fused_sharded.py) on whatever
        # mesh this backend can host (CI pins CPU without a virtual
        # mesh, so usually 1 device — the invariant is identical)
        from risingwave_tpu.parallel.fused import (
            ShardedCoGroup, ShardedFusedAgg, ShardedFusedQ3,
            ShardedFusedSession,
        )
        from risingwave_tpu.parallel.sharded_agg import make_mesh
        n_dev = min(len(jax.devices()), 4)
        mesh = make_mesh(n_dev)
        exprs2, agg2, chunk_fn2 = _cosched_parts()
        sf = ShardedFusedAgg(mesh, agg2.core, chunk_fn2,
                             exprs2, COSCHED_SMOKE_CHUNK)
        sf.run_epoch(0, jax.random.PRNGKey(0), k)
        sf.flush()
        c.reset()
        sf.run_epoch(k * COSCHED_SMOKE_CHUNK, jax.random.PRNGKey(1), k)
        n = c.counts["sharded_agg_epoch.<locals>.epoch"]
        assert n == 1, f"sharded epoch took {n} dispatches"
        sf.flush()
        checks.append(f"sharded[{n_dev}]=1 dispatch/epoch")

        # sharded q8 session epoch: ONE dispatch regardless of shards/k
        sw8 = SessionWindowCore(
            Schema((Field("bidder", INT64), Field("ts", TIMESTAMP))),
            0, 1, gap_us=5_000, capacity=1 << 10,
            closed_capacity=1 << 10)
        gen8 = DeviceBidGenerator(NexmarkConfig(chunk_capacity=cap))
        sfs = ShardedFusedSession(
            mesh, sw8, gen8.chunk_fn(),
            [col(1, INT64), col(5, TIMESTAMP)], cap)
        sfs.run_epoch(0, jax.random.PRNGKey(0), k, 0)
        sfs.flush(out_capacity=cap)
        c.reset()
        sfs.run_epoch(k * cap, jax.random.PRNGKey(1), k, 0)
        n = c.counts["sharded_session_epoch.<locals>.epoch"]
        assert n == 1, f"sharded q8 epoch took {n} dispatches"
        sfs.flush(out_capacity=cap)
        checks.append(f"sharded-q8[{n_dev}]=1 dispatch/epoch")

        # sharded q3 epoch (incl. the global top-n flush): ONE dispatch
        q3s = Q3Core(Q3_CUTOFF_DAYS, orders_capacity=1 << 10,
                     agg_capacity=1 << 10)
        sfq3 = ShardedFusedQ3(
            mesh, q3s,
            DeviceQ3Generator(TpchQ3Config(chunk_capacity=cap)).chunk_fn(),
            cap)
        sfq3.run_epoch(0, jax.random.PRNGKey(0), k)
        sfq3.flush()
        c.reset()
        sfq3.run_epoch(k * cap, jax.random.PRNGKey(0), k)
        n = c.counts["sharded_q3_epoch.<locals>.epoch"]
        assert n == 1, f"sharded q3 epoch took {n} dispatches"
        sfq3.flush()
        checks.append(f"sharded-q3[{n_dev}]=1 dispatch/epoch")

        # K×S co-scheduled group (fusion surface 6): J jobs × S shards,
        # still exactly ONE dispatch per epoch
        exprs3, agg3, chunk_fn3 = _cosched_parts()
        spec3 = FusedJobSpec("agg", ("smoke-sharded",), chunk_fn3,
                             tuple(exprs3), agg3.core,
                             COSCHED_SMOKE_CHUNK, seed=0)
        sgroup = ShardedCoGroup(mesh, spec3)
        for j in range(jobs):
            sgroup.add(f"mv{j}", seed=j)
        sgroup.run_epoch(k)
        sgroup.flush()
        c.reset()
        sgroup.run_epoch(k)
        n = c.counts[
            "build_sharded_group_epoch.<locals>.sharded_coscheduled_epoch"]
        assert n == 1, f"sharded group epoch took {n} dispatches"
        sgroup.flush()
        checks.append(
            f"sharded-cosched[{jobs}x{n_dev}]=1 dispatch/epoch")

        # generic sharded-fused equi-join: k chunks in ONE dispatch
        from risingwave_tpu.common.chunk import physical_chunk
        from risingwave_tpu.common.types import Schema as _Schema
        from risingwave_tpu.ops.join_state import JoinType
        from risingwave_tpu.parallel.sharded_join import ShardedHashJoin
        ls = _Schema((Field("k", INT64), Field("v", INT64)))
        rs = _Schema((Field("k", INT64), Field("w", INT64)))
        shj = ShardedHashJoin(mesh, ls, rs, [0], [0], JoinType.INNER,
                              key_capacity=1 << 8, bucket_width=8)
        def _jb(lo):
            return shj.batch_chunks([
                physical_chunk(ls, [(lo + 16 * s + r, r) for r in range(16)],
                               16) for s in range(n_dev)])
        shj.step_epoch("left", [_jb(0), _jb(1000)])
        c.reset()
        shj.step_epoch("left", [_jb(2000), _jb(3000)])
        n = c.counts["sharded_equi_join_epoch.<locals>.epoch"]
        assert n == 1, f"sharded equi-join epoch took {n} dispatches"
        checks.append(f"sharded-equijoin[{n_dev}]=1 dispatch/epoch")
    # device profiling plane (common/profiling.py): ON by default, and
    # every 1-dispatch assertion above ran THROUGH its wrappers — so the
    # invariants passing IS the proof that profiling adds zero
    # dispatches. Cross-check its live counters against the same
    # qualnames the dispatch counter keyed.
    from risingwave_tpu.common.profiling import GLOBAL_PROFILER
    assert GLOBAL_PROFILER.enabled, "profiling plane is off by default"
    prof = GLOBAL_PROFILER.counts()
    for qn in ("build_group_epoch.<locals>.coscheduled_epoch",
               "build_padded_group_epoch.<locals>.padded_epoch",
               "build_mega_epoch.<locals>.mega_epoch",
               "fused_source_session_epoch.<locals>.epoch",
               "fused_source_q3_epoch.<locals>.epoch",
               "sharded_agg_epoch.<locals>.epoch",
               "sharded_session_epoch.<locals>.epoch",
               "sharded_q3_epoch.<locals>.epoch",
               "sharded_equi_join_epoch.<locals>.epoch",
               "build_sharded_group_epoch.<locals>"
               ".sharded_coscheduled_epoch"):
        assert prof.get(qn, 0) >= 1, \
            f"profiler missed dispatches for {qn}: {prof}"
    checks.append("profiling on: counters live, 0 added dispatches")
    # asynchronous epoch pipeline ([streaming] pipeline_depth = 2):
    # the SAME co-scheduled workload must be BIT-EXACT vs the
    # synchronous path after the drain (flush) AND add ZERO dispatches
    # (identical per-qualname counts — the pipeline reorders dispatches
    # across ticks, it must never add one)
    from risingwave_tpu.frontend.build import BuildConfig

    def _pipe_run(depth: int):
        from risingwave_tpu.frontend import Session
        with count_dispatches() as pc:
            s = Session(config=BuildConfig(coschedule=True),
                        chunks_per_tick=2, source_chunk_capacity=128,
                        checkpoint_frequency=4, pipeline_depth=depth)
            s.run_sql(_COSCHED_SOURCE_SQL)
            for j in range(2):
                s.run_sql(f"CREATE MATERIALIZED VIEW pipe_mv{j} AS "
                          "SELECT auction, count(*) AS n FROM bid "
                          "GROUP BY auction")
            for _ in range(9):
                s.tick()
            s.flush()
            rows = [sorted(s.run_sql(f"SELECT * FROM pipe_mv{j}"))
                    for j in range(2)]
            counts = dict(pc.counts)
            s.close()
        return rows, counts

    rows_sync, counts_sync = _pipe_run(1)
    rows_pipe, counts_pipe = _pipe_run(2)
    assert rows_sync == rows_pipe, \
        "pipeline_depth=2 diverged from the synchronous path"
    for qn in ("build_group_epoch.<locals>.coscheduled_epoch",
               "multi_agg_probe.<locals>.probe",
               "multi_agg_finish.<locals>.finish",
               "gather_job_flush_chunk.<locals>.gather"):
        assert counts_sync.get(qn) == counts_pipe.get(qn) \
            and counts_sync.get(qn), (
            f"pipelining changed the dispatch count for {qn}: "
            f"sync={counts_sync.get(qn)} pipe={counts_pipe.get(qn)}")
    checks.append("pipeline[depth=2]: bit-exact, 0 added dispatches")
    # serving plane: a repeated identical SELECT must create ZERO new
    # jit wrappers (plan+compilation cache, frontend/serving.py) — and a
    # write in between re-executes the SAME cached executors, still
    # zero. Warm OUTSIDE the counter: count_dispatches counts calls of
    # functions jitted inside it, so any replan/relower on the repeats
    # would surface as nonzero counts.
    from risingwave_tpu.frontend import Session
    s = Session()
    s.run_sql("CREATE TABLE st (a BIGINT, b BIGINT)")
    s.run_sql("INSERT INTO st VALUES (1, 10), (2, 20), (1, 30)")
    s.flush()
    sql = "SELECT a, count(*), sum(b) FROM st GROUP BY a"
    s.run_sql(sql)                      # warm: plan + lower + jit
    with count_dispatches() as c:
        assert s.run_sql(sql) == s.run_sql(sql)
        assert c.total == 0, \
            f"cached SELECT re-jitted: {dict(c.counts)}"
        s.run_sql("INSERT INTO st VALUES (3, 5)")
        s.flush()
        rows = s.run_sql(sql)
        assert c.total == 0, \
            f"version-bump re-execution re-jitted: {dict(c.counts)}"
    assert sorted(rows) == [(1, 2, 40), (2, 1, 20), (3, 1, 5)], rows
    m = s.metrics()["serving"]
    assert m["cache_hits"] >= 2 and m["reexecutions"] >= 1, m
    s.close()
    checks.append("serving cache: 0 new jits on repeat + re-exec")
    _emit({"metric": "bench_smoke", "value": round(
        time.perf_counter() - t0, 2), "unit": "s",
        "backend": jax.default_backend(), "checks": checks})
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] in ("--phase", "--probe",
                                             "--sharded-phase",
                                             "--serving-phase",
                                             "--rescale-phase",
                                             "--fleet-phase",
                                             "--fleet-frontend",
                                             "--failover-phase",
                                             "--hetero-phase"):
        watchdog = threading.Timer(INIT_WATCHDOG_SECS, _watchdog_fire)
        watchdog.daemon = True
        watchdog.start()
        import jax
        try:
            _ = jax.devices()          # a hung init trips the watchdog
        except Exception as e:
            _emit(_fail_line(f"jax backend init failed: {e!r}"))
            raise SystemExit(2)
        watchdog.cancel()
        if sys.argv[1] == "--probe":
            try:
                run_probe()
            except Exception as e:
                _emit(_fail_line(f"probe failed: {type(e).__name__}: {e}"))
                raise SystemExit(2)
            raise SystemExit(0)
        if sys.argv[1] == "--serving-phase":
            watchdog = threading.Timer(WATCHDOG_SECS, _watchdog_fire)
            watchdog.daemon = True
            watchdog.start()
            try:
                run_serving_phase(
                    float(sys.argv[2]) if len(sys.argv) > 2
                    else SERVING_SECONDS,
                    int(sys.argv[3]) if len(sys.argv) > 3
                    else SERVING_THREADS)
            except Exception as e:
                _emit(_fail_line(
                    f"serving phase failed: {type(e).__name__}: {e}"))
                raise SystemExit(2)
            finally:
                watchdog.cancel()
            raise SystemExit(0)
        if sys.argv[1] == "--fleet-frontend":
            # hidden child of --fleet-phase: line-oriented protocol on
            # stdout (FLEET_READY / FLEET_STATS), not a JSON result line
            run_fleet_frontend(sys.argv[2], sys.argv[3])
            raise SystemExit(0)
        if sys.argv[1] == "--fleet-phase":
            watchdog = threading.Timer(WATCHDOG_SECS, _watchdog_fire)
            watchdog.daemon = True
            watchdog.start()
            try:
                run_fleet_phase(
                    float(sys.argv[2]) if len(sys.argv) > 2
                    else FLEET_SECONDS,
                    int(sys.argv[3]) if len(sys.argv) > 3
                    else FLEET_FRONTENDS,
                    int(sys.argv[4]) if len(sys.argv) > 4
                    else FLEET_CONNS)
            except Exception as e:
                _emit(_fail_line(
                    f"fleet phase failed: {type(e).__name__}: {e}"))
                raise SystemExit(2)
            finally:
                watchdog.cancel()
            raise SystemExit(0)
        if sys.argv[1] == "--failover-phase":
            watchdog = threading.Timer(WATCHDOG_SECS, _watchdog_fire)
            watchdog.daemon = True
            watchdog.start()
            try:
                run_failover_phase(
                    int(sys.argv[2]) if len(sys.argv) > 2 else 7)
            except Exception as e:
                _emit(_fail_line(
                    f"failover phase failed: {type(e).__name__}: {e}"))
                raise SystemExit(2)
            finally:
                watchdog.cancel()
            raise SystemExit(0)
        if sys.argv[1] == "--hetero-phase":
            watchdog = threading.Timer(WATCHDOG_SECS, _watchdog_fire)
            watchdog.daemon = True
            watchdog.start()
            try:
                run_hetero_phase(
                    int(sys.argv[2]) if len(sys.argv) > 2
                    else HETERO_JOBS,
                    int(sys.argv[3]) if len(sys.argv) > 3
                    else HETERO_TICKS)
            except Exception as e:
                _emit(_fail_line(
                    f"hetero phase failed: {type(e).__name__}: {e}"))
                raise SystemExit(2)
            finally:
                watchdog.cancel()
            raise SystemExit(0)
        if sys.argv[1] == "--rescale-phase":
            watchdog = threading.Timer(WATCHDOG_SECS, _watchdog_fire)
            watchdog.daemon = True
            watchdog.start()
            try:
                run_rescale_phase(
                    int(sys.argv[2]) if len(sys.argv) > 2 else 6)
            except Exception as e:
                _emit(_fail_line(
                    f"rescale phase failed: {type(e).__name__}: {e}"))
                raise SystemExit(2)
            finally:
                watchdog.cancel()
            raise SystemExit(0)
        if sys.argv[1] == "--sharded-phase":
            watchdog = threading.Timer(WATCHDOG_SECS, _watchdog_fire)
            watchdog.daemon = True
            watchdog.start()
            try:
                run_sharded_phase(int(sys.argv[2]), int(sys.argv[3]))
            except Exception as e:
                _emit(_fail_line(
                    f"sharded phase failed: {type(e).__name__}: {e}"))
                raise SystemExit(2)
            finally:
                watchdog.cancel()
            raise SystemExit(0)
        n = int(sys.argv[2])
        n7 = int(sys.argv[3])
        n8 = int(sys.argv[4]) if len(sys.argv) > 4 else Q8_CPU_N_CHUNKS
        n3 = int(sys.argv[5]) if len(sys.argv) > 5 else Q3_CPU_N_CHUNKS
        watchdog = threading.Timer(WATCHDOG_SECS, _watchdog_fire)
        watchdog.daemon = True
        watchdog.start()
        try:
            run_phase(n, n7, n8, n3)
        except Exception as e:
            _emit(_fail_line(f"phase failed: {type(e).__name__}: {e}"))
            raise SystemExit(2)
        finally:
            watchdog.cancel()
        raise SystemExit(0)
    if len(sys.argv) > 1 and sys.argv[1] == "--smoke":
        # same hung-init protection as the measurement phases
        watchdog = threading.Timer(INIT_WATCHDOG_SECS, _watchdog_fire)
        watchdog.daemon = True
        watchdog.start()
        try:
            rc = run_smoke()
        finally:
            watchdog.cancel()
        raise SystemExit(rc)
    raise SystemExit(main())
