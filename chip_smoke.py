#!/usr/bin/env python3
"""chip_smoke.py — the SQL→MV main path, once, on the attached TPU.

One process, one chip (``--chips 4``: one process, four chips, the mesh
phase only). Every phase prints one JSON line when it finishes; the LAST
line of stdout is the verdict:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed check or exception — and any platform other than "tpu",
whatever the phases did — ends the run non-zero with ``"ok": false`` in
the last line. Off the chip the script stops at the device phase unless
``--tiny`` asks it to rehearse the phases at a small size (it still ends
non-zero: a CPU rehearsal is never a chip result).

Phases (no arguments, one chip):

* device  — platform, device_kind, count, jax/libtpu versions
* kernels — ``rank_totals`` / ``interval_match`` through their public
  selectors at the main path's shapes: the compiled Pallas kernel ran
  (``tpu_custom_call`` in the compiled program) and equals the jnp twin
  bit for bit
* sql     — ``Session(data_dir=<fresh>)``, checkpoints every 10th barrier,
  NEXmark sources from ``--seed``, three MVs by SQL text: a q5-shaped
  tumble count on the executor path, the same MV with ``[streaming]
  coschedule = true`` (fused epoch, donated state), and the q8 join MV
  as a LEFT OUTER join (person ⟕ auction per window, through
  ops/join_state.py and the rank kernel — an INNER join never uses the
  kernel's result and XLA drops the call). Each MV is read back by
  ``SELECT`` and compared EXACTLY with a plain numpy recomputation of the
  same event stream.
* recover — close, reopen the same ``data_dir``, compare again (the MVs
  are the last checkpoint's cut, to the row), tick on, compare again
* cache   — where the compile cache is, seconds spent compiling, hits

``--chips 4`` runs the mesh phase and what it is compared with, and no
other phase: the same q5 and q8 MVs at the same size through
``[streaming] mesh_shape = 4`` (q5 twice: the sharded fused epoch with
``coschedule`` on, and the default path with it off — the sharded hash
agg executor the benchmark's ``q5core_exec_mesh4_catchup`` guards;
sharded executors and the rank kernel under shard_map for the join)
against the same MVs on one chip of the same host and the numpy
recomputation. (NEXmark q7 is not the join MV: its join keeps every bid
keyed by price and the arena is rectangular — 2 M log-uniform prices put
~3,000 bids on the hottest key, so a shard needs 4096 lanes x >= 2^15
keys = 2^27 cells against a growth ceiling of 2^24; see PERF.md.)
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

T0 = time.perf_counter()


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# compile accounting: JAX's own monitoring events
# ---------------------------------------------------------------------------

COMPILE = {"backend_compile_s": 0.0, "trace_lower_s": 0.0,
           "cache_hits": 0, "cache_misses": 0}


def install_compile_listeners() -> None:
    from jax import monitoring

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILE["backend_compile_s"] += secs
        elif event.startswith("/jax/core/compile/"):
            COMPILE["trace_lower_s"] += secs

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            COMPILE["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            COMPILE["cache_misses"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)


class Phase:
    """Times one phase and prints its JSON line on a clean exit."""

    def __init__(self, name: str):
        self.name = name
        self.info: dict = {}

    def __enter__(self) -> "Phase":
        self.t0 = time.perf_counter()
        self.c0 = dict(COMPILE)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            emit({"phase": self.name, "ok": True,
                  "seconds": round(time.perf_counter() - self.t0, 3),
                  "compile_seconds": round(
                      COMPILE["backend_compile_s"]
                      - self.c0["backend_compile_s"], 3),
                  "trace_lower_seconds": round(
                      COMPILE["trace_lower_s"]
                      - self.c0["trace_lower_s"], 3),
                  **self.info})
        return False


# ---------------------------------------------------------------------------
# sizes
# ---------------------------------------------------------------------------


def sizes(tiny: bool) -> dict:
    if tiny:
        return dict(chunk=256, chunks_per_tick=2, ticks=12, more_ticks=2,
                    agg_slots=1 << 13, join_keys=1 << 9, join_width=16,
                    rank_shapes=((512, 128), (256, 16)),
                    match_shape=(1 << 9, 128))
    # a deployment, not a unit test: 32 barriers x 16 chunks x 4096 rows
    # = 2,097,152 bid events, 3 checkpoints, 2^20-slot agg tables and a
    # 2^17-key x 16-lane join arena per side (person ids are unique on the
    # shared event clock: 49,840 join keys over the 35 barriers)
    return dict(chunk=4096, chunks_per_tick=16, ticks=32, more_ticks=3,
                agg_slots=1 << 20, join_keys=1 << 17, join_width=16,
                rank_shapes=((4096, 128), (1024, 16)),
                match_shape=(1 << 15, 128))


CHECKPOINT_FREQUENCY = 10          # the reference cadence (common/config.py)
WINDOW_US = 10_000_000             # INTERVAL '10' SECOND

BID_COLS = ("auction BIGINT, bidder BIGINT, price BIGINT, channel VARCHAR, "
            "url VARCHAR, date_time TIMESTAMP, extra VARCHAR")
AUCTION_COLS = ("id BIGINT, item_name VARCHAR, description VARCHAR, "
                "initial_bid BIGINT, reserve BIGINT, date_time TIMESTAMP, "
                "expires TIMESTAMP, seller BIGINT, category BIGINT, "
                "extra VARCHAR")
PERSON_COLS = ("id BIGINT, name VARCHAR, email_address VARCHAR, "
               "credit_card VARCHAR, city VARCHAR, state VARCHAR, "
               "date_time TIMESTAMP, extra VARCHAR")

# the q5-shaped tumble count (the grouped-agg core of NEXmark q5)
Q5_SQL = """CREATE MATERIALIZED VIEW q5 AS
    SELECT window_start, auction, count(*) AS num
    FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)
    GROUP BY window_start, auction"""
Q5_SELECT = "SELECT window_start, auction, num FROM q5"

# NEXmark q8 (tests/test_nexmark_queries.py) as a LEFT OUTER join: an
# INNER join never uses the rank/total of ops/join_state.py — XLA removes
# the dead kernel call — so the outer variant is the one that runs it
Q8_SQL = """CREATE MATERIALIZED VIEW q8 AS
    SELECT P.id, P.name, P.starttime, A.seller
    FROM (
        SELECT id, name, window_start AS starttime,
               window_end AS endtime
        FROM TUMBLE(person, date_time, INTERVAL '10' SECOND)
        GROUP BY id, name, window_start, window_end
    ) P
    LEFT JOIN (
        SELECT seller, window_start AS starttime,
               window_end AS endtime
        FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND)
        GROUP BY seller, window_start, window_end
    ) A ON P.id = A.seller AND P.starttime = A.starttime
           AND P.endtime = A.endtime"""
Q8_SELECT = "SELECT id, name, starttime, seller FROM q8"


def side_rows(chunk: int) -> tuple:
    """person / auction rows per chunk beside ``chunk`` bids: NEXmark's
    1:3:46 event proportions."""
    person = max(1, round(chunk / 46))
    return person, 3 * person


def source_ddl(chunk: int, tables=("bid", "auction", "person")) -> str:
    person, auction = side_rows(chunk)
    ddl = {
        "bid": f"CREATE SOURCE bid ({BID_COLS}) WITH "
               "(connector = 'nexmark', nexmark_table = 'bid')",
        "auction": f"CREATE SOURCE auction ({AUCTION_COLS}) WITH "
                   "(connector = 'nexmark', nexmark_table = 'auction', "
                   f"rows_per_chunk = '{auction}')",
        "person": f"CREATE SOURCE person ({PERSON_COLS}) WITH "
                  "(connector = 'nexmark', nexmark_table = 'person', "
                  f"rows_per_chunk = '{person}')",
    }
    return ";\n".join(ddl[t] for t in tables)


# ---------------------------------------------------------------------------
# the plain host references: numpy over a replay of the same event stream
# (none of risingwave_tpu.ops)
# ---------------------------------------------------------------------------


def host_bid_stream(seed: int, chunk: int, n_chunks: int):
    """(auction, date_time) of the first ``n_chunks`` bid chunks the
    executor-path source leaf produces (the session's reader is this
    generator with the session seed), as numpy arrays."""
    import numpy as np
    from risingwave_tpu.connector.nexmark import (
        NexmarkConfig, NexmarkGenerator,
    )
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=chunk), seed=seed)
    auctions, times = [], []
    for _ in range(n_chunks):
        ch = gen.next_bid_chunk()
        auctions.append(np.asarray(ch.columns[0].data))
        times.append(np.asarray(ch.columns[5].data))
    if not auctions:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(auctions), np.concatenate(times)


def device_bid_stream(seed: int, chunk: int, k: int, ticks: int):
    """(auction, date_time) of the stream a fused epoch generates INSIDE
    its dispatch: ``DeviceBidGenerator`` is counter-based — epoch ``j``
    folds ``j`` into the seed key, chunk ``i`` of the epoch folds ``i`` —
    so the same events replay here, outside any epoch, as plain arrays."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from risingwave_tpu.connector.nexmark import (
        DeviceBidGenerator, NexmarkConfig,
    )
    fn = DeviceBidGenerator(NexmarkConfig(chunk_capacity=chunk),
                            seed=seed).chunk_fn()

    @jax.jit
    def epoch_events(start, epoch_key):
        def one(i):
            ch = fn(start + i * chunk, jax.random.fold_in(epoch_key, i))
            return ch.columns[0].data, ch.columns[5].data
        return jax.vmap(one)(jnp.arange(k, dtype=jnp.int64))

    base = jax.random.PRNGKey(seed)
    auctions, times = [], []
    for j in range(ticks):
        a, t = epoch_events(jnp.int64(j * k * chunk),
                            jax.random.fold_in(base, j))
        auctions.append(np.asarray(a).reshape(-1))
        times.append(np.asarray(t).reshape(-1))
    if not auctions:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    return np.concatenate(auctions), np.concatenate(times)


def ref_q5(auction, date_time):
    """{(window_start, auction): count} as a sorted [n, 3] int64 array."""
    import numpy as np
    ws = (date_time // WINDOW_US) * WINDOW_US
    keys = np.stack([ws, auction], axis=1)
    uniq, counts = np.unique(keys, axis=0, return_counts=True)
    return np.concatenate([uniq, counts[:, None]], axis=1).astype(np.int64)


def side_stream_rows(seed: int, chunk: int, n_chunks: int) -> tuple:
    """Per-chunk python rows of the person and auction leaves (each source
    leaf is its own generator with the session seed)."""
    from risingwave_tpu.common import chunk_to_rows
    from risingwave_tpu.connector.nexmark import (
        AUCTION_SCHEMA, PERSON_SCHEMA, NexmarkConfig, NexmarkGenerator,
    )
    person_rows, auction_rows = side_rows(chunk)
    pgen = NexmarkGenerator(NexmarkConfig(chunk_capacity=person_rows),
                            seed=seed)
    agen = NexmarkGenerator(NexmarkConfig(chunk_capacity=auction_rows),
                            seed=seed)
    persons = [chunk_to_rows(pgen.next_person_chunk(), PERSON_SCHEMA)
               for _ in range(n_chunks)]
    auctions = [chunk_to_rows(agen.next_auction_chunk(), AUCTION_SCHEMA)
                for _ in range(n_chunks)]
    return persons, auctions


def ref_q8(person_chunks: list, auction_chunks: list) -> list:
    """The LEFT OUTER q8: every (person, name, window), with the seller
    where that person opened an auction in the window and NULL where
    not."""
    p_windows = {(p[0], p[1], (p[6] // WINDOW_US) * WINDOW_US)
                 for rows in person_chunks for p in rows}
    a_windows = {(a[7], (a[5] // WINDOW_US) * WINDOW_US)
                 for rows in auction_chunks for a in rows}
    return sort_q8((pid, name, ws, pid if (pid, ws) in a_windows else None)
                   for (pid, name, ws) in p_windows)


def sort_q8(rows) -> list:
    """q8 rows in one order (the null-padded seller sorts first)."""
    return sorted(rows, key=lambda r: (r[0], r[1], r[2],
                                       -1 if r[3] is None else r[3]))


def q5_rows_array(rows: list):
    """run_sql rows -> the reference's sorted [n, 3] int64 layout."""
    import numpy as np
    if not rows:
        return np.zeros((0, 3), np.int64)
    arr = np.asarray(rows, dtype=np.int64).reshape(-1, 3)
    return arr[np.lexsort((arr[:, 1], arr[:, 0]))]


def check_q5(label: str, got_rows: list, auction, date_time) -> dict:
    import numpy as np
    got = q5_rows_array(got_rows)
    exp = ref_q5(auction, date_time)
    check(got.shape == exp.shape and bool(np.array_equal(got, exp)),
          f"{label}: MV differs from the host recomputation "
          f"(got {got.shape[0]} groups / {int(got[:, 2].sum())} events, "
          f"expected {exp.shape[0]} / {int(exp[:, 2].sum())})")
    return {"groups": int(exp.shape[0]), "events": int(exp[:, 2].sum())}


def ticks_ingested(label: str, got_rows: list, per_tick: int,
                   at_most: int) -> int:
    """How many whole barriers of events an MV holds, from its own total
    count — after recovery this is the last checkpoint's cut."""
    total = sum(int(r[2]) for r in got_rows)
    check(total % per_tick == 0,
          f"{label}: {total} events is not a whole number of barriers "
          f"({per_tick} events each) — a torn epoch survived")
    t = total // per_tick
    check(0 < t <= at_most,
          f"{label}: holds {t} barriers of events, expected 1..{at_most}")
    return t


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(want_chips: int) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    with Phase("device") as ph:
        try:
            import libtpu
            libtpu_version = getattr(libtpu, "__version__", "unknown")
        except ImportError:
            libtpu_version = None
        ph.info.update(dev, jax=jax.__version__, libtpu=libtpu_version,
                       default_backend=jax.default_backend(),
                       chips_requested=want_chips)
    return dev


def _tpu_custom_call_in(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).compile().as_text()


def phase_kernels(sz: dict, seed: int, on_tpu: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from risingwave_tpu.ops.interval_join import (
        interval_match, interval_match_jnp,
    )
    from risingwave_tpu.ops.pallas_rank import (
        pallas_selected, rank_totals, rank_totals_jnp,
    )

    with Phase("kernels") as ph:
        selected = pallas_selected()
        check(selected == on_tpu,
              f"selector picked pallas={selected} on platform "
              f"{jax.devices()[0].platform}")
        rng = np.random.default_rng(seed)
        ran = []
        for n, w in sz["rank_shapes"]:
            # idents cluster heavily (hot keys) and include -1 (no match)
            ident = jnp.asarray(rng.integers(-1, 48, size=n), jnp.int32)
            matches = jnp.asarray(rng.random((n, w)) < 0.3)
            if on_tpu:
                check(_tpu_custom_call_in(rank_totals, ident, matches),
                      f"rank_totals({n},{w}): no compiled Pallas kernel "
                      "in the program the selector built")
            t0 = time.perf_counter()
            r, t = jax.block_until_ready(
                jax.jit(rank_totals)(ident, matches))
            dt = time.perf_counter() - t0
            r2, t2 = jax.jit(rank_totals_jnp)(ident, matches)
            check(bool(jnp.array_equal(r, r2))
                  and bool(jnp.array_equal(t, t2)),
                  f"rank_totals({n},{w}) differs from its jnp twin")
            check(int(t.sum()) > 0, "rank_totals: degenerate input")
            ran.append({"kernel": "rank_totals", "shape": [n, w],
                        "first_call_s": round(dt, 3)})
        nb, w = sz["match_shape"]
        vals = jnp.asarray(rng.integers(0, 1 << 40, size=(nb, w)),
                           jnp.int64)
        occ = jnp.asarray(rng.random((nb, w)) < 0.5)
        # old/new max drawn FROM the lanes so matches exist, with values
        # above 2^32 so both int32 halves matter
        old_max = vals[jnp.arange(nb), rng.integers(0, w, size=nb)]
        new_max = vals[jnp.arange(nb), rng.integers(0, w, size=nb)]
        old_live = jnp.asarray(rng.random(nb) < 0.7)
        new_live = jnp.asarray(rng.random(nb) < 0.7)
        args = (vals, occ, old_max, old_live, new_max, new_live)
        if on_tpu:
            check(_tpu_custom_call_in(interval_match, *args),
                  f"interval_match({nb},{w}): no compiled Pallas kernel "
                  "in the program the selector built")
        t0 = time.perf_counter()
        d, i = jax.block_until_ready(jax.jit(interval_match)(*args))
        dt = time.perf_counter() - t0
        d2, i2 = jax.jit(interval_match_jnp)(*args)
        check(bool(jnp.array_equal(d, d2)) and bool(jnp.array_equal(i, i2)),
              f"interval_match({nb},{w}) differs from its jnp twin")
        check(int(d.sum()) > 0 and int(i.sum()) > 0,
              "interval_match: degenerate input")
        ran.append({"kernel": "interval_match", "shape": [nb, w],
                    "first_call_s": round(dt, 3)})
        ph.info.update(
            path="pallas-compiled" if on_tpu else "jnp (not a TPU)",
            equal_to_jnp_twin=True, calls=ran)


def open_session(data_dir: str, sz: dict, seed: int, coschedule: bool,
                 mesh: int = 0):
    """The served path: a Session over a layered config, as `rw.toml`
    would give it (common/config.load_config)."""
    from risingwave_tpu.common.config import load_config
    from risingwave_tpu.frontend import Session
    overrides = {
        "streaming.checkpoint_frequency": CHECKPOINT_FREQUENCY,
        "streaming.chunk_capacity": sz["chunk"],
        "streaming.agg_table_capacity": sz["agg_slots"],
        "streaming.join_key_capacity": sz["join_keys"],
        "streaming.join_bucket_width": sz["join_width"],
        "streaming.coschedule": coschedule,
        "storage.data_dir": data_dir,
    }
    if mesh:
        overrides["streaming.mesh_shape"] = mesh
    return Session(rw_config=load_config(None, **overrides), seed=seed,
                   chunks_per_tick=sz["chunks_per_tick"])


def timed_ticks(s, n: int) -> dict:
    import numpy as np
    per = []
    for _ in range(n):
        t0 = time.perf_counter()
        s.tick()
        per.append(time.perf_counter() - t0)
    if not per:
        return {}
    # the barrier ledger's host-side stage medians
    # (common/barrier_ledger.py; collect = the executors working through
    # the barrier's chunks until they reach it)
    stages = s.metrics()["barrier"]["stages"]
    return {"tick_first_s": round(per[0], 3),
            "tick_median_s": round(float(np.median(per)), 4),
            "tick_max_s": round(max(per), 3),
            "stage_p50_ms": {st: v["p50_ms"] for st, v in stages.items()}}


class Replay:
    """The event streams of ``max_ticks`` barriers, replayed ONCE; every
    comparison recomputes its MV over a prefix of them."""

    def __init__(self, sz: dict, seed: int, max_ticks: int):
        chunk, self.k = sz["chunk"], sz["chunks_per_tick"]
        self.per_tick = chunk * self.k
        n_chunks = max_ticks * self.k
        self.host_auction, self.host_ts = host_bid_stream(
            seed, chunk, n_chunks)
        self.dev_auction, self.dev_ts = device_bid_stream(
            seed, chunk, self.k, max_ticks)
        self.persons, self.auctions = side_stream_rows(
            seed, chunk, n_chunks)

    def check_executor_session(self, s, ticks: int, label: str) -> dict:
        n = ticks * self.per_tick
        q5 = check_q5(f"{label} q5/executor", s.run_sql(Q5_SELECT),
                      self.host_auction[:n], self.host_ts[:n])
        got8 = sort_q8(s.run_sql(Q8_SELECT))
        exp8 = ref_q8(self.persons[:ticks * self.k],
                      self.auctions[:ticks * self.k])
        check(got8 == exp8,
              f"{label} q8: MV differs from the host recomputation "
              f"(got {len(got8)} rows, expected {len(exp8)})")
        matched = sum(1 for r in exp8 if r[3] is not None)
        check(matched > 0, f"{label} q8: no matched row proves nothing")
        return {"q5_executor": q5, "q8_join_rows": len(exp8),
                "q8_matched_rows": matched}

    def check_cosched_session(self, s, ticks: int, label: str) -> dict:
        n = ticks * self.per_tick
        return {"q5_coscheduled": check_q5(
            f"{label} q5/coscheduled", s.run_sql(Q5_SELECT),
            self.dev_auction[:n], self.dev_ts[:n])}


def pipeline_nodes(job):
    """Every executor of a job's pipeline (and the sharded engines the
    mesh executors wrap)."""
    stack = [job.pipeline]
    while stack:
        node = stack.pop()
        yield node
        for attr in ("input", "left", "right", "agg", "join"):
            child = getattr(node, attr, None)
            if hasattr(child, "__dict__"):
                stack.append(child)


def join_step_rank_kernel(s, chunk: int) -> dict:
    """Did the Pallas rank kernel survive into the COMPILED program of
    q8's join step on this backend? XLA removes a dead call after
    lowering (an INNER join's), so only the compiled text can tell. The
    step is the executor's own jitted one (the auction side's pass is the
    one that needs rank/total for the LEFT OUTER transitions); called
    after the ticks, its compile is a persistent-cache hit when these are
    the shapes the ticks ran."""
    import jax
    from risingwave_tpu.common.chunk import make_chunk
    from risingwave_tpu.stream.hash_join import HashJoinExecutor

    joins = [n for n in pipeline_nodes(s.jobs["q8"])
             if isinstance(n, HashJoinExecutor)]
    check(len(joins) == 1, f"q8 built {len(joins)} HashJoinExecutors")
    join = joins[0]
    shape = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
        (join.state, make_chunk(join.right.schema, [], capacity=chunk)))
    misses = COMPILE["cache_misses"]
    text = join._apply["right"].lower(*shape, None).compile().as_text()
    return {"in_compiled_program": "tpu_custom_call" in text,
            "compile_was_cache_hit": COMPILE["cache_misses"] == misses}


def phase_sql_and_recover(sz: dict, seed: int, root: str,
                          on_tpu: bool) -> None:
    from risingwave_tpu import native

    chunk, k, ticks = sz["chunk"], sz["chunks_per_tick"], sz["ticks"]
    per_tick = chunk * k
    more = sz["more_ticks"]
    dir_a = os.path.join(root, "executor")
    dir_b = os.path.join(root, "coscheduled")

    with Phase("sql") as ph:
        replay = Replay(sz, seed, ticks + more)
        # A: executor path — q5-shaped tumble count + the q8 join MV
        a = open_session(dir_a, sz, seed, coschedule=False)
        a.run_sql(source_ddl(chunk))
        a.run_sql(Q5_SQL)
        a.run_sql(Q8_SQL)
        check(not a.metrics()["coschedule"]["jobs"],
              "executor session co-scheduled an MV")
        tick_a = timed_ticks(a, ticks)
        rank = join_step_rank_kernel(a, chunk)
        check(rank["in_compiled_program"] == on_tpu,
              f"q8's compiled join step holds the rank kernel: "
              f"{rank['in_compiled_program']}, on platform tpu={on_tpu}")
        res = replay.check_executor_session(a, ticks, "sql")
        # B: the same q5 MV with [streaming] coschedule = true — the
        # fused epoch with donated state. It draws its events from the
        # on-device generator (same distributions, its own stream), so
        # it is held to a recomputation of THAT stream, not to A's rows.
        b = open_session(dir_b, sz, seed, coschedule=True)
        b.run_sql(source_ddl(chunk, tables=("bid",)))
        b.run_sql(Q5_SQL)
        check(b.metrics()["coschedule"]["jobs"] == 1,
              "coschedule session did not take the fused epoch")
        tick_b = timed_ticks(b, ticks)
        res.update(replay.check_cosched_session(b, ticks, "sql"))
        check(res["q5_executor"]["events"] == ticks * per_tick
              == res["q5_coscheduled"]["events"], "event count")
        codec = "native" if native.codec() is not None else "python"
        ph.info.update(
            bid_events=ticks * per_tick, chunk_rows=chunk,
            chunks_per_barrier=k, barriers=ticks,
            checkpoints=a.epoch // CHECKPOINT_FREQUENCY,
            checkpoint_frequency=CHECKPOINT_FREQUENCY,
            agg_table_slots=sz["agg_slots"],
            join_arena_keys=sz["join_keys"],
            join_bucket_width=sz["join_width"],
            person_auction_rows_per_chunk=list(side_rows(chunk)),
            checkpoint_row_codec=codec,
            q8_join_step_rank_kernel=rank,
            executor_ticks=tick_a, coscheduled_ticks=tick_b, **res)

    with Phase("recover") as ph:
        a.close()
        b.close()
        a = open_session(dir_a, sz, seed, coschedule=False)
        b = open_session(dir_b, sz, seed, coschedule=True)
        check(b.metrics()["coschedule"]["jobs"] == 1,
              "recovered coschedule session lost its fused job")
        # exactly-once: each MV is the last checkpoint's cut — a whole
        # number of barriers, at most one checkpoint interval behind
        ta = ticks_ingested("recovered executor q5",
                            a.run_sql(Q5_SELECT), per_tick, ticks)
        tb = ticks_ingested("recovered coscheduled q5",
                            b.run_sql(Q5_SELECT), per_tick, ticks)
        check(ta == tb and ticks - ta <= CHECKPOINT_FREQUENCY,
              f"recovered cuts: executor {ta}, coscheduled {tb} of "
              f"{ticks} barriers")
        at_cut = replay.check_executor_session(a, ta, "recovered")
        at_cut.update(replay.check_cosched_session(b, tb, "recovered"))
        for s in (a, b):
            for _ in range(more):
                s.tick()
        after = replay.check_executor_session(a, ta + more,
                                              "recovered+ticked")
        after.update(replay.check_cosched_session(b, tb + more,
                                                  "recovered+ticked"))
        a.close()
        b.close()
        ph.info.update(barriers_before_close=ticks,
                       barriers_at_recovered_cut=ta,
                       barriers_ticked_after=more,
                       at_cut=at_cut, after_more_ticks=after)


def phase_cache(cache_dir: str) -> None:
    with Phase("cache") as ph:
        n_files = 0
        n_bytes = 0
        for base, _dirs, files in os.walk(cache_dir):
            for f in files:
                n_files += 1
                n_bytes += os.path.getsize(os.path.join(base, f))
        ph.info.update(
            compile_cache_dir=cache_dir,
            from_env=bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
            entries=n_files, bytes=n_bytes,
            total_backend_compile_s=round(COMPILE["backend_compile_s"], 3),
            total_trace_lower_s=round(COMPILE["trace_lower_s"], 3),
            cache_hits=COMPILE["cache_hits"],
            cache_misses=COMPILE["cache_misses"],
            wall_s=round(time.perf_counter() - T0, 3))


def executor_state_leaves(job) -> list:
    """Every device array of the state a job's executors hold (on a mesh,
    the sharded engines of parallel/: ShardedHashAgg, ShardedHashJoin)."""
    import jax
    return [x for node in pipeline_nodes(job)
            for x in jax.tree_util.tree_leaves(getattr(node, "state", None))
            if hasattr(x, "sharding")]


def phase_mesh(sz: dict, seed: int, root: str, n: int) -> None:
    """--chips 4: the q5 and q8 MVs of the one-chip phases, at the same
    size, on an n-device mesh against the same MVs on one chip of the
    same host."""
    import jax
    import numpy as np

    chunk, k, ticks = sz["chunk"], sz["chunks_per_tick"], sz["ticks"]

    def spread_over(devices: int, label: str, leaves: list,
                    out: dict) -> None:
        spread = sorted({len(x.sharding.device_set) for x in leaves})
        check(spread == [devices], f"{label} state leaves live on "
              f"{spread} devices, not {devices}")
        out[f"{label}_state_leaves"] = len(leaves)
        out[f"{label}_state_bytes_per_device"] = sum(
            x.addressable_shards[0].data.nbytes for x in leaves)

    def run_mvs(mesh: int, tag: str) -> dict:
        out = {}
        # q5: the fused epoch (coschedule on) — sharded across the mesh
        # when mesh_shape is set
        s = open_session(os.path.join(root, f"q5_{tag}"), sz, seed,
                         coschedule=True, mesh=mesh)
        s.run_sql(source_ddl(chunk, tables=("bid",)))
        s.run_sql(Q5_SQL)
        out["q5_ticks"] = timed_ticks(s, ticks)
        out["q5"] = s.run_sql(Q5_SELECT)
        if mesh:
            check(not s.metrics()["coschedule"]["jobs"]
                  and [e.kind.name for e in s._fused.engines.values()]
                  == ["shardfused"],
                  "mesh session did not take the sharded fused epoch")
            (group,) = s._fused.groups()
            spread_over(mesh, "q5",
                        jax.tree_util.tree_leaves(group.stacked), out)
        else:
            check(s.metrics()["coschedule"]["jobs"] == 1,
                  "one-chip session did not take the fused epoch")
        s.close()
        # q5 again at DEFAULT settings (coschedule off): host bid source →
        # project → hash agg executor — on a mesh the sharded executor of
        # parallel/ (chunk split, vnode all-to-all, per-shard upsert), the
        # path the benchmark's q5core_exec_mesh4_catchup guards
        s = open_session(os.path.join(root, f"q5x_{tag}"), sz, seed,
                         coschedule=False, mesh=mesh)
        s.run_sql(source_ddl(chunk, tables=("bid",)))
        s.run_sql(Q5_SQL)
        out["q5x_ticks"] = timed_ticks(s, ticks)
        out["q5x"] = s.run_sql(Q5_SELECT)
        check(not s._fused.engines,
              "default-path q5 session took a fused epoch")
        aggs = [type(node).__name__ for node in pipeline_nodes(s.jobs["q5"])
                if type(node).__name__.endswith("HashAggExecutor")]
        check(aggs == (["ShardedHashAggExecutor"] if mesh
                       else ["HashAggExecutor"]),
              f"default-path q5 runs {aggs}")
        spread_over(mesh or 1, "q5x",
                    executor_state_leaves(s.jobs["q5"]), out)
        s.close()
        # q8 LEFT OUTER: the join MV through the executors — the
        # mesh-sharded executors of parallel/ (two sharded aggs feeding
        # the sharded join, rank kernel under shard_map) when mesh_shape
        # is set
        s = open_session(os.path.join(root, f"q8_{tag}"), sz, seed,
                         coschedule=True, mesh=mesh)
        s.run_sql(source_ddl(chunk, tables=("auction", "person")))
        s.run_sql(Q8_SQL)
        out["q8_ticks"] = timed_ticks(s, ticks)
        out["q8_checkpoints"] = s.epoch // CHECKPOINT_FREQUENCY
        out["q8"] = sort_q8(s.run_sql(Q8_SELECT))
        spread_over(mesh or 1, "q8", executor_state_leaves(s.jobs["q8"]),
                    out)
        s.close()
        return out

    with Phase("mesh") as ph:
        one = run_mvs(0, "one_chip")
        many = run_mvs(n, f"mesh{n}")
        got5, exp5 = q5_rows_array(many["q5"]), q5_rows_array(one["q5"])
        check(got5.shape == exp5.shape and bool(np.array_equal(got5, exp5)),
              f"q5 on {n} chips differs from q5 on one chip "
              f"({got5.shape[0]} vs {exp5.shape[0]} groups)")
        got5x, exp5x = q5_rows_array(many["q5x"]), q5_rows_array(one["q5x"])
        check(got5x.shape == exp5x.shape
              and bool(np.array_equal(got5x, exp5x)),
              f"default-path q5 on {n} chips differs from one chip's "
              f"({got5x.shape[0]} vs {exp5x.shape[0]} groups)")
        check(many["q8"] == one["q8"],
              f"q8 on {n} chips differs from q8 on one chip "
              f"({len(many['q8'])} vs {len(one['q8'])} rows)")
        # and both against the plain host recomputation
        auction, ts = device_bid_stream(seed, chunk, k, ticks)
        q5 = check_q5("mesh q5", many["q5"], auction, ts)
        q5x = check_q5("mesh default-path q5", many["q5x"],
                       *host_bid_stream(seed, chunk, ticks * k))
        exp8 = ref_q8(*side_stream_rows(seed, chunk, ticks * k))
        check(many["q8"] == exp8,
              f"q8 differs from the host recomputation "
              f"({len(many['q8'])} vs {len(exp8)} rows)")
        matched = sum(1 for r in exp8 if r[3] is not None)
        check(matched > 0, "mesh q8: no matched row proves nothing")
        mem = []
        for d in jax.devices():
            stats = d.memory_stats() or {}
            mem.append({"device": d.id,
                        "bytes_in_use": stats.get("bytes_in_use"),
                        "peak_bytes_in_use": stats.get(
                            "peak_bytes_in_use")})
        rows = ("q5", "q5x", "q8")
        ph.info.update(
            chips=n, barriers=ticks, chunk_rows=chunk, chunks_per_barrier=k,
            checkpoint_frequency=CHECKPOINT_FREQUENCY,
            q5=q5, q5_default_path=q5x, q5_bid_events=ticks * k * chunk,
            q8_join_rows=len(exp8), q8_matched_rows=matched,
            person_auction_rows_per_chunk=list(side_rows(chunk)),
            equal_rows_mesh_vs_one_chip=True,
            one_chip={x: one[x] for x in one if x not in rows},
            mesh={x: many[x] for x in many if x not in rows},
            memory_stats=mem)


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def run(args) -> tuple:
    """-> (ok, device_dict | None, error | None)"""
    import jax  # noqa: F401 - an ImportError here is the verdict
    from risingwave_tpu.common.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    # every program caches, however quick its compile: the second run of
    # the same checkout should compile next to nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    install_compile_listeners()

    dev = phase_device(args.chips)
    on_tpu = dev["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        return False, dev, (f"platform is {dev['platform']!r}, not 'tpu': "
                            "nothing to smoke (use --tiny to rehearse the "
                            "phases off the chip)")
    if on_tpu or args.chips > 1:
        check(dev["count"] == args.chips,
              f"{dev['count']} devices attached, --chips {args.chips} "
              "asked for")
    sz = sizes(args.tiny)
    root = tempfile.mkdtemp(prefix="chip_smoke_", dir=args.work_dir)
    try:
        if args.chips > 1:
            phase_mesh(sz, args.seed, root, args.chips)
        else:
            phase_kernels(sz, args.seed, on_tpu)
            phase_sql_and_recover(sz, args.seed, root, on_tpu)
        phase_cache(cache_dir)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    if not on_tpu:
        return False, dev, (f"phases rehearsed on {dev['platform']!r}: "
                            "not a chip run")
    return True, dev, None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the mesh phase and what it is compared "
                    "with, and no other phase")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal sizes (and, off the chip, run the "
                    "phases anyway — the run still ends non-zero)")
    ap.add_argument("--work-dir", default=None,
                    help="parent of the fresh data dirs (default: the "
                    "system temp dir)")
    args = ap.parse_args(argv)
    ok, dev, err = False, None, None
    try:
        ok, dev, err = run(args)
    except BaseException as e:  # noqa: BLE001 - the verdict line must print
        import traceback
        traceback.print_exc()
        err = f"{type(e).__name__}: {e}"
        ok = False
    sys.stderr.flush()
    if ok:
        emit({"ok": True, "device": dev})
    else:
        emit({"ok": False, "error": err, "device": dev})
    sys.stdout.flush()
    # hard exit: no lingering thread of a failed phase may hold the
    # process (and the chip) past the verdict
    os._exit(0 if ok else 1)


if __name__ == "__main__":
    main()
