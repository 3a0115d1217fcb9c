"""Playground CLI: one-process cluster behind a Postgres port.

Counterpart of the reference's all-in-one binary
(reference: src/cmd_all/src/bin/risingwave.rs:118 ``playground`` mode and
the node binaries under src/cmd/src/bin/). Usage:

    python -m risingwave_tpu playground [--port 4566] [--data-dir DIR]
    python -m risingwave_tpu sql "CREATE TABLE ..." [--data-dir DIR]
    python -m risingwave_tpu sql-file script.sql [--data-dir DIR]
"""

from __future__ import annotations

import argparse
import sys


def _build_session(args):
    from .frontend.session import Session
    kwargs = {}
    if args.data_dir:
        kwargs["data_dir"] = args.data_dir
    if getattr(args, "checkpoint_frequency", None):
        kwargs["checkpoint_frequency"] = args.checkpoint_frequency
    if getattr(args, "workers", 0):
        kwargs["workers"] = args.workers
    if getattr(args, "state_store", None):
        kwargs["state_store"] = args.state_store
    if getattr(args, "compactors", 0):
        kwargs["compactors"] = args.compactors
    if getattr(args, "meta_addr", None):
        kwargs["meta_addr"] = args.meta_addr
    if getattr(args, "role", None):
        kwargs["role"] = args.role
    fp = getattr(args, "fragment_parallelism", 1)
    mesh_n = getattr(args, "mesh", 0)
    if (fp and fp != 1) or mesh_n:
        from .frontend.build import BuildConfig
        mesh = None
        if mesh_n:
            # refuses loudly (MeshUnavailableError) when the process has
            # fewer devices than asked for — see [streaming] mesh_shape
            from .parallel.sharded_agg import make_mesh
            mesh = make_mesh(mesh_n)
        kwargs["config"] = BuildConfig(fragment_parallelism=fp, mesh=mesh)
    return Session(**kwargs)


#: one default shared by every session-building subcommand, so a durable
#: data dir deployed from any of them recovers under the same topology
#: (the library default, BuildConfig/StreamingConfig fragment_parallelism
#: = 1, stays single-actor for embedded/API use)
FRAGMENT_PARALLELISM_DEFAULT = 2


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="risingwave_tpu")
    sub = p.add_subparsers(dest="command", required=True)

    # shared by playground / sql / sql-file / ctl via parents=[...]
    fp_arg = argparse.ArgumentParser(add_help=False)
    fp_arg.add_argument(
        "--fragment-parallelism", type=int,
        default=FRAGMENT_PARALLELISM_DEFAULT,
        help="parallel actors per fragmentable operator (grouped aggs / "
        "joins run as multi-fragment jobs with hash-dispatch exchanges; "
        "1 = single actor; must match the value a durable data dir was "
        "deployed with so recovery and `ctl fragments` reflect the live "
        "topology; reference: streaming.default_parallelism)")
    fp_arg.add_argument(
        "--mesh", type=int, default=0, metavar="N",
        help="shard operator state across an N-device mesh "
        "(BuildConfig.mesh / [streaming] mesh_shape): grouped aggs and "
        "joins run the mesh-sharded executors, and eligible fused MVs "
        "tick as one dispatch per epoch across all chips. Refuses "
        "loudly when the process has fewer than N devices (on CPU set "
        "XLA_FLAGS=--xla_force_host_platform_device_count=N); 0 = "
        "single-chip")
    fp_arg.add_argument(
        "--meta-addr", default=None, metavar="HOST:PORT",
        help="attach to a standalone meta server (`ctl meta serve`) "
        "instead of running the control plane in-process — the "
        "multi-tenant deployment shape: one meta + one shared state "
        "dir, one writer session, N serving frontends "
        "(docs/control-plane.md); also settable via [meta] addr")

    pg = sub.add_parser("playground", parents=[fp_arg],
                        help="serve SQL over the Postgres wire protocol")
    pg.add_argument("--host", default="127.0.0.1")
    pg.add_argument("--port", type=int, default=4566)
    pg.add_argument("--data-dir", default=None,
                    help="durable state directory (RAM-only if absent)")
    pg.add_argument("--checkpoint-frequency", type=int, default=10)
    pg.add_argument("--tick-interval-ms", type=int, default=1000,
                    help="barrier interval (reference default 1000ms)")
    pg.add_argument("--workers", type=int, default=0,
                    help="worker PROCESSES hosting MV jobs (reference: "
                    "compute nodes; 0 = everything in-process)")
    pg.add_argument("--state-store", default=None,
                    choices=["segment", "hummock"],
                    help="durable tier for a NEW data dir: epoch-delta "
                    "segment log, or Hummock-lite L0 SSTs under a "
                    "versioned manifest (recovery auto-detects)")
    pg.add_argument("--compactors", type=int, default=0,
                    help="dedicated compactor worker PROCESSES "
                    "(hummock tier; 0 = in-process background fold)")
    pg.add_argument("--user", default="root",
                    help="user name for password auth (with --password)")
    pg.add_argument("--password", default=None,
                    help="enable md5 password authentication "
                    "(default: trust, like the reference playground)")
    pg.add_argument("--dashboard-port", type=int, default=None,
                    help="serve the meta dashboard (cluster / fragment "
                    "graphs / await-tree) on this port")
    pg.add_argument("--role", default=None,
                    choices=["writer", "serving", "standby"],
                    help="session role when attached to a standalone "
                    "meta (--meta-addr): the single 'writer' conducts "
                    "barriers and owns DDL; 'serving' frontends are "
                    "read-mostly replicas sharing the writer's state "
                    "dir; 'standby' serves reads AND races the "
                    "election when the writer's lease expires, "
                    "promoting in place (docs/control-plane.md)")

    q = sub.add_parser("sql", parents=[fp_arg],
                       help="run SQL statements and print results")
    q.add_argument("statement")
    q.add_argument("--data-dir", default=None)

    qf = sub.add_parser("sql-file", parents=[fp_arg],
                        help="run a SQL script file")
    qf.add_argument("path")
    qf.add_argument("--data-dir", default=None)

    ctl = sub.add_parser(
        "ctl", parents=[fp_arg],
        help="admin inspection of a durable data dir "
             "(reference: risectl)")
    ctl.add_argument("what", choices=["jobs", "parameters", "fragments",
                                      "metrics", "trace", "backup",
                                      "restore", "backup-info",
                                      "hummock", "vacuum", "cluster",
                                      "profile", "udf", "meta"])
    ctl.add_argument("sub", nargs="?", default=None,
                     help="subcommand for `ctl cluster` "
                     "(fragments — dump the persisted fragment→worker "
                     "placement and per-edge permit state of spanning "
                     "jobs; rescale — live-migrate one spanning job to "
                     "a new parallelism; autoscaler — dump the scaling "
                     "plane's policy state and executed migrations), "
                     "`ctl profile` (roofline — AOT cost/memory "
                     "analysis of every registered fused surface "
                     "against the chip roofline, chip-free), "
                     "`ctl udf` (serve — run a standalone out-of-process "
                     "UDF server in the foreground; sessions attach via "
                     "[udf] addr = \"host:port\" — docs/robustness.md), "
                     "and `ctl trace` (barrier — the barrier "
                     "observatory's per-epoch waterfall history and "
                     "stage percentiles; add --inflight for live "
                     "stuck-barrier blame — docs/observability.md), "
                     "and `ctl meta` (serve — run a standalone meta "
                     "server in the foreground over --data-dir; "
                     "sessions attach with --meta-addr / [meta] addr; "
                     "leader — who holds the lease: session, term, TTL "
                     "remaining, failover count and term history, read "
                     "live over --meta-addr or offline from --data-dir "
                     "— docs/control-plane.md)")
    ctl.add_argument("job", nargs="?", default=None,
                     help="job name for `ctl cluster rescale`")
    ctl.add_argument("--parallelism", type=int, default=None,
                     help="target fragment parallelism for "
                     "`ctl cluster rescale` (docs/scaling.md)")
    ctl.add_argument("--data-dir", default=None,
                     help="durable data dir (required for every ctl "
                     "command except `profile` and `udf`, "
                     "which read no cluster state)")
    ctl.add_argument("--port", type=int, default=0,
                     help="udf serve: listen port (0 = ephemeral, "
                     "printed as UDF_READY <port>)")
    ctl.add_argument("--json", action="store_true",
                     help="profile/trace barrier: emit the full "
                     "JSON report instead of the table")
    ctl.add_argument("--inflight", action="store_true",
                     help="trace barrier: walk the LIVE in-flight "
                     "barrier accounting and name the actors/links "
                     "that have not acked (stuck-barrier blame)")
    ctl.add_argument("--peak-flops", type=float, default=None,
                     help="profile roofline: chip peak FLOP/s "
                     "(default [observability] chip_peak_flops, else "
                     "by the attached device's kind)")
    ctl.add_argument("--peak-bandwidth", type=float, default=None,
                     help="profile roofline: chip HBM bandwidth in "
                     "bytes/s (default [observability] "
                     "chip_peak_bandwidth)")
    ctl.add_argument("--surface", default=None,
                     help="profile roofline: analyze ONE registered "
                     "fused surface (e.g. source_session, "
                     "sharded:group_agg) instead of the whole ladder")
    ctl.add_argument("--backup-dir",
                     help="backup location for backup/restore/backup-info")
    ctl.add_argument("--workers", type=int, default=0,
                     help="worker processes to recover the cluster with "
                     "(metrics/trace/cluster over a data dir deployed "
                     "with --workers N needs the same N; `cluster "
                     "fragments` infers it from the persisted placement "
                     "when omitted)")
    ctl.add_argument("--force", action="store_true",
                     help="vacuum: actually delete (default is a dry "
                     "run; only safe with no live session on the dir)")
    ctl.add_argument("--lease-ttl", type=float, default=None,
                     help="meta serve: leader lease TTL in seconds — a "
                     "writer that misses heartbeats for this long is "
                     "declared down and standbys race the election "
                     "(default 2.0; docs/control-plane.md)")

    comp = sub.add_parser(
        "compactor",
        help="run a dedicated Hummock-lite compaction worker against a "
             "shared object-store root (reference: the standalone "
             "compactor node)")
    comp.add_argument("--data-dir", required=True)
    comp.add_argument("--worker-id", type=int, default=0)
    comp.add_argument("--port", type=int, default=0)

    args = p.parse_args(argv)

    if args.command == "playground":
        return _playground(args)
    if args.command == "ctl":
        return _ctl(args)
    if args.command == "compactor":
        from .worker.compactor import main as compactor_main
        compactor_main(["--data-dir", args.data_dir,
                        "--worker-id", str(args.worker_id),
                        "--port", str(args.port)])
        return 0
    session = _build_session(args)
    sql = (args.statement if args.command == "sql"
           else open(args.path, "r", encoding="utf-8").read())
    rows = session.run_sql(sql)
    for row in rows:
        print("\t".join("" if v is None else str(v) for v in row))
    return 0


def _ctl(args) -> int:
    """risectl-lite: recover a session from the data dir and inspect it
    (reference: src/ctl/src/lib.rs:48-75 — cluster-info, table scan,
    trace, profile; meta backup/restore:
    src/meta/src/backup_restore/backup_manager.rs)."""
    import json as _json
    if args.what == "profile":
        if args.sub != "roofline":
            raise SystemExit("usage: ctl profile roofline "
                             "[--peak-flops F --peak-bandwidth B --json]")
        return _ctl_profile_roofline(args, _json)
    if args.what == "udf":
        if args.sub != "serve":
            raise SystemExit("usage: ctl udf serve [--port N]")
        # a PERSISTENT operator-managed server: clients come and go,
        # registrations outlive any one of them (auto-spawned servers
        # are one-client; udf/server.py)
        from .udf.server import main as udf_server_main
        udf_server_main(["--port", str(args.port), "--persistent"])
        return 0
    if args.what == "meta":
        if args.sub == "leader":
            return _ctl_meta_leader(args, _json)
        if args.sub != "serve":
            raise SystemExit("usage: ctl meta serve --data-dir DIR "
                             "[--port N --lease-ttl S] | "
                             "ctl meta leader (--meta-addr HOST:PORT | "
                             "--data-dir DIR) [--json]")
        if not args.data_dir:
            raise SystemExit("--data-dir is required (the meta store "
                             "lives under DIR/meta)")
        # the standalone control plane (docs/control-plane.md): serves
        # the MetaService surface over the wire protocol; prints
        # "META_READY host:port" once listening. The store lives under
        # DIR/meta — the SAME path an in-process session over DIR uses,
        # so `ctl cluster fragments` etc. keep reading it offline.
        import os as _os
        from .meta.server import main as meta_server_main
        argv = ["--data-dir", _os.path.join(args.data_dir, "meta"),
                "--port", str(args.port)]
        if args.lease_ttl is not None:
            argv += ["--lease-ttl", str(args.lease_ttl)]
        meta_server_main(argv)
        return 0
    if not args.data_dir:
        raise SystemExit("--data-dir is required")
    if args.what in ("backup", "restore", "backup-info"):
        from .storage.backup import (
            create_backup, list_backup, restore_backup,
        )
        if not args.backup_dir:
            raise SystemExit("--backup-dir is required")
        if args.what == "backup":
            desc = create_backup(args.data_dir, args.backup_dir)
        elif args.what == "restore":
            desc = restore_backup(args.backup_dir, args.data_dir)
        else:
            desc = list_backup(args.backup_dir)
        print(_json.dumps(desc, indent=2))
        return 0
    if args.what == "cluster":
        if args.sub == "fragments":
            return _ctl_cluster_fragments(args, _json)
        if args.sub == "rescale":
            return _ctl_cluster_rescale(args, _json)
        if args.sub == "autoscaler":
            return _ctl_cluster_autoscaler(args, _json)
        raise SystemExit(
            "usage: ctl cluster fragments|rescale|autoscaler "
            "--data-dir DIR [JOB --parallelism N]")
    if args.what in ("hummock", "vacuum"):
        # storage-only inspection: no session (and no job recovery) —
        # read the version manifest straight off the object store
        from .meta.hummock import HummockManager
        from .storage.object_store import open_object_store
        mgr = HummockManager(open_object_store(args.data_dir))
        if not mgr.exists():
            raise SystemExit(
                f"{args.data_dir!r} holds no hummock version manifest")
        if args.what == "vacuum":
            # OFFLINE-ONLY: pins, in-progress uploads, and in-flight
            # compaction tasks live in the OWNING session's memory — a
            # fresh manager cannot see them, so vacuuming under a live
            # session could delete objects it is about to reference. The
            # live path is the session's own vacuum (the compaction pump
            # runs it after every task). Default is therefore a DRY RUN;
            # --force performs the deletes and is the operator's
            # assertion that no session is running over this dir.
            if args.force:
                deleted = mgr.vacuum()
                print(_json.dumps({"deleted": deleted}, indent=2))
            else:
                victims = mgr.vacuum(dry_run=True)
                print(_json.dumps({
                    "would_delete": victims,
                    "note": "dry run — pass --force only when NO live "
                            "session is using this data dir (a live "
                            "cluster vacuums itself)"}, indent=2))
        else:
            print(_json.dumps({"version": mgr.version.summary(),
                               "stats": mgr.stats}, indent=2))
        return 0
    session = _build_session(args)
    try:
        _ctl_dispatch(args, session, _json)
    finally:
        session.close()
    return 0


def _roofline_surfaces() -> dict:
    """The full fused ladder for chip-free AOT analysis: one lazy
    builder per registered surface — every ``EPOCH_BUILDERS`` entry
    (q5/q7/q8/q3), the co-scheduled multi-job epoch, and every
    ``SHARDED_EPOCH_BUILDERS`` entry (sharded q5/q7/q8/q3, the generic
    equi-join, the K×S group) — at bench-like shapes. Each builder
    returns ``(callable, args)``; nothing is executed (AOT
    lower+compile only), so this works with no chip attached. Sharded
    surfaces build over the widest mesh THIS process hosts (force a
    virtual mesh with XLA_FLAGS=--xla_force_host_platform_device_count
    for multi-shard analysis on CPU)."""
    import jax
    import jax.numpy as jnp
    from .common import INT64, TIMESTAMP
    from .common.types import Field, Schema
    from .connector import NexmarkConfig
    from .connector.nexmark import DeviceBidGenerator
    from .connector.tpch import (
        DeviceQ3Generator, Q3_CUTOFF_DAYS, TpchQ3Config,
    )
    from .expr import Literal, call, col
    from .expr.agg import count_star
    from .ops.fused_epoch import EPOCH_BUILDERS
    from .ops.fused_multi import build_group_epoch, stack_states
    from .ops.fused_sharded import SHARDED_EPOCH_BUILDERS
    from .ops.grouped_agg import AggCore
    from .ops.interval_join import IntervalJoinCore
    from .ops.session_window import SessionWindowCore
    from .ops.stream_q3 import Q3Core
    from .parallel.sharded_agg import make_mesh

    cap, k, window_us, jobs = 1024, 8, 10_000_000, 8
    gen = DeviceBidGenerator(NexmarkConfig(chunk_capacity=cap))
    start, key = jnp.int64(0), jax.random.PRNGKey(0)

    def q5_parts():
        exprs = [call("tumble_start", col(5, TIMESTAMP),
                      Literal(window_us, INT64)), col(0, INT64)]
        core = AggCore((INT64, INT64), (0, 1), [count_star()],
                       table_capacity=1 << 16, out_capacity=cap)
        return exprs, core

    def q7_parts():
        exprs = [call("tumble_start", col(5, TIMESTAMP),
                      Literal(window_us, INT64)),
                 col(0, INT64), col(2, INT64)]
        core = IntervalJoinCore(
            Schema((Field("window_start", TIMESTAMP),
                    Field("auction", INT64), Field("price", INT64))),
            ts_col=0, val_col=2, window_us=window_us,
            n_buckets=1 << 12, lane_width=16)
        return exprs, core

    def q8_parts():
        exprs = [col(1, INT64), col(5, TIMESTAMP)]
        core = SessionWindowCore(
            Schema((Field("bidder", INT64), Field("ts", TIMESTAMP))),
            key_col=0, ts_col=1, gap_us=500_000,
            capacity=1 << 16, closed_capacity=1 << 16)
        return exprs, core

    def q3_parts():
        core = Q3Core(Q3_CUTOFF_DAYS, orders_capacity=1 << 16,
                      agg_capacity=1 << 16)
        return DeviceQ3Generator(TpchQ3Config(chunk_capacity=cap)), core

    def mesh_and_states(core):
        mesh = make_mesh(min(len(jax.devices()), 8))
        n = mesh.devices.size
        return mesh, stack_states([core.init_state() for _ in range(n)])

    def t_q5():
        exprs, core = q5_parts()
        fn = EPOCH_BUILDERS["source_agg"](gen.chunk_fn(), exprs, core, cap)
        return fn, (core.init_state(), start, key, k)

    def t_q7():
        exprs, core = q7_parts()
        fn = EPOCH_BUILDERS["source_join"](gen.chunk_fn(), exprs, core,
                                           cap)
        return fn, (core.init_state(), start, key, k)

    def t_q8():
        exprs, core = q8_parts()
        fn = EPOCH_BUILDERS["source_session"](gen.chunk_fn(), exprs, core,
                                              cap)
        return fn, (core.init_state(), start, key, k, jnp.int64(0))

    def t_q3():
        q3gen, core = q3_parts()
        fn = EPOCH_BUILDERS["source_q3"](q3gen.chunk_fn(), core, cap)
        return fn, (core.init_state(), start, key, k)

    def t_multi():
        exprs, core = q5_parts()
        fn = build_group_epoch("agg", gen.chunk_fn(), exprs, core, cap)
        stacked = stack_states([core.init_state() for _ in range(jobs)])
        starts = jnp.zeros(jobs, jnp.int64)
        keys = jnp.stack([jax.random.PRNGKey(j) for j in range(jobs)])
        nos = jnp.zeros(jobs, jnp.int64)
        return fn, (stacked, starts, keys, nos, k)

    def t_sharded_q5():
        exprs, core = q5_parts()
        mesh, stacked = mesh_and_states(core)
        fn = SHARDED_EPOCH_BUILDERS["source_agg"](
            gen.chunk_fn(), exprs, core, cap, mesh)
        return fn, (stacked, start, key, k)

    def t_sharded_q7():
        exprs, core = q7_parts()
        mesh, stacked = mesh_and_states(core)
        fn = SHARDED_EPOCH_BUILDERS["source_join"](
            gen.chunk_fn(), exprs, core, cap, mesh)
        return fn, (stacked, start, key, k)

    def t_sharded_q8():
        exprs, core = q8_parts()
        mesh, stacked = mesh_and_states(core)
        fn = SHARDED_EPOCH_BUILDERS["source_session"](
            gen.chunk_fn(), exprs, core, cap, mesh)
        return fn, (stacked, start, key, k, jnp.int64(0))

    def t_sharded_q3():
        q3gen, core = q3_parts()
        mesh, stacked = mesh_and_states(core)
        fn = SHARDED_EPOCH_BUILDERS["source_q3"](
            q3gen.chunk_fn(), core, cap, mesh)
        return fn, (stacked, start, key, k)

    def t_equi_join():
        from .connector.nexmark import AUCTION_SCHEMA, BID_SCHEMA
        from .ops.join_state import JoinCore, JoinType
        core = JoinCore(BID_SCHEMA, AUCTION_SCHEMA, [0], [0],
                        JoinType.INNER, key_capacity=1 << 10,
                        bucket_width=8)
        mesh, stacked = mesh_and_states(core)
        n = mesh.devices.size
        fn = SHARDED_EPOCH_BUILDERS["equi_join"](core, mesh, [0], [0])

        def zero_chunk():
            from .common.chunk import Column, StreamChunk
            cols = tuple(
                Column(jnp.zeros((n, k, cap), f.type.dtype),
                       jnp.zeros((n, k, cap), jnp.bool_))
                for f in BID_SCHEMA)
            return StreamChunk(jnp.zeros((n, k, cap), jnp.int8),
                               jnp.zeros((n, k, cap), jnp.bool_), cols)

        return fn, (stacked, zero_chunk(), "left")

    def t_sharded_group():
        exprs, core = q5_parts()
        mesh = make_mesh(min(len(jax.devices()), 8))
        n = mesh.devices.size
        fn = SHARDED_EPOCH_BUILDERS["group_agg"](
            gen.chunk_fn(), exprs, core, cap, mesh)
        per_job = [stack_states([core.init_state() for _ in range(n)])
                   for _ in range(jobs)]
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs, axis=1), *per_job)
        starts = jnp.zeros(jobs, jnp.int64)
        keys = jnp.stack([jax.random.PRNGKey(j) for j in range(jobs)])
        nos = jnp.zeros(jobs, jnp.int64)
        return fn, (stacked, starts, keys, nos, k)

    def t_hetero_padded():
        import numpy as np
        from .ops.fused_hetero import HETERO_EPOCH_BUILDERS
        from .stream.tick_compiler import skeletonize_exprs
        exprs, core = q5_parts()
        skel, hole_types, params = skeletonize_exprs(tuple(exprs), 7)
        fn = HETERO_EPOCH_BUILDERS["padded_agg"](
            gen.chunk_fn(), skel, core, cap)
        stacked = stack_states([core.init_state() for _ in range(jobs)])
        starts = jnp.zeros(jobs, jnp.int64)
        keys = jnp.stack([jax.random.PRNGKey(j) for j in range(jobs)])
        nos = jnp.zeros(jobs, jnp.int64)
        ps = tuple(jnp.asarray(np.full(jobs, params[h], t.np_dtype))
                   for h, t in enumerate(hole_types))
        return fn, (stacked, starts, keys, nos, ps, k)

    def t_hetero_mega():
        from .expr.agg import agg
        from .ops.fused_hetero import HETERO_EPOCH_BUILDERS
        from .stream.coschedule import FusedJobSpec

        def spec_of(exprs, core):
            return FusedJobSpec(
                kind="agg", signature=("roofline",),
                chunk_fn=gen.chunk_fn(), exprs=tuple(exprs),
                core=core, rows_per_chunk=cap, seed=0)

        exprs1, core1 = q5_parts()
        exprs2 = [col(0, INT64), col(2, INT64)]
        core2 = AggCore((INT64,), (0,),
                        [count_star(), agg("sum", 1, INT64)],
                        table_capacity=1 << 14, out_capacity=cap)
        fn = HETERO_EPOCH_BUILDERS["mega_agg"](
            [spec_of(exprs1, core1), spec_of(exprs2, core2)])
        states = (core1.init_state(), core2.init_state())
        starts = jnp.zeros(2, jnp.int64)
        keys = jnp.stack([jax.random.PRNGKey(j) for j in range(2)])
        nos = jnp.zeros(2, jnp.int64)
        return fn, (states, starts, keys, nos, k)

    return {
        "source_agg": t_q5, "source_join": t_q7,
        "source_session": t_q8, "source_q3": t_q3,
        "multi_agg": t_multi,
        "hetero:padded_agg": t_hetero_padded,
        "hetero:mega_agg": t_hetero_mega,
        "sharded:source_agg": t_sharded_q5,
        "sharded:source_join": t_sharded_q7,
        "sharded:source_session": t_sharded_q8,
        "sharded:source_q3": t_sharded_q3,
        "sharded:equi_join": t_equi_join,
        "sharded:group_agg": t_sharded_group,
    }


def _ctl_profile_roofline(args, _json) -> int:
    """`ctl profile roofline`: AOT-``lower().compile()`` EVERY
    registered fused surface — the four solo epochs, the co-scheduled
    multi-job epoch, and all six sharded surfaces — and print each
    kernel's flops / bytes accessed / arithmetic intensity / %-of-peak
    against the chip roofline: the measured-roofline artifact ROADMAP
    item 1 demands, available chip-free (docs/performance.md).
    ``--surface NAME`` restricts the (expensive) AOT compile to one
    surface."""
    from .common.config import ObservabilityConfig
    from .common.profiling import (
        UnknownChipError, aot_analysis, chip_peaks,
        render_roofline_table, roofline_report,
    )
    obs = ObservabilityConfig()
    try:
        peak_flops, peak_bw = chip_peaks(
            args.peak_flops or obs.chip_peak_flops,
            args.peak_bandwidth or obs.chip_peak_bandwidth)
    except UnknownChipError as e:
        raise SystemExit(f"ctl profile roofline: {e}")
    surfaces = _roofline_surfaces()
    pick = getattr(args, "surface", None)
    if pick is not None:
        if pick not in surfaces:
            raise SystemExit(
                f"unknown surface {pick!r}; choose from: "
                + ", ".join(sorted(surfaces)))
        surfaces = {pick: surfaces[pick]}
    analyses = {}
    for name, build in surfaces.items():
        # report keys = the dispatch qualnames common/dispatch_count.py,
        # the profiler, and Session.metrics()["dispatch"] all share
        # (unique per surface); the surface name is the selector only
        try:
            fn, fn_args = build()
            analyses[getattr(fn, "__qualname__", name)] = \
                aot_analysis(fn, *fn_args)
        except Exception as e:  # noqa: BLE001 - per-surface attribution
            analyses[name] = {"error": f"{type(e).__name__}: {e}"}
    report = roofline_report(analyses, peak_flops, peak_bw)
    if args.json:
        print(_json.dumps(report, indent=2))
    else:
        print(render_roofline_table(report))
    return 0


def _ctl_meta_leader(args, _json) -> int:
    """`ctl meta leader`: who holds the leader lease — session, term,
    TTL remaining, how it was acquired, failover count, and the term
    history. Live over ``--meta-addr`` (asks the server, which owns the
    in-memory deadline), or offline from ``--data-dir`` (reads the
    persisted lease record; TTL remaining is server memory and shows as
    unknown — docs/control-plane.md "Election")."""
    import os
    if getattr(args, "meta_addr", None):
        from .meta.client import MetaClient
        client = MetaClient(args.meta_addr, session_id="ctl-leader")
        try:
            info = client.lease_info()
        finally:
            client.close()
    elif args.data_dir:
        from .meta.service import MetaService
        path = os.path.join(args.data_dir, "meta", "meta.jsonl")
        if not os.path.exists(path):
            raise SystemExit(f"{args.data_dir!r} holds no meta store")
        meta = MetaService(data_dir=os.path.join(args.data_dir, "meta"))
        try:
            store = meta.store
            info = {"holder": None, "term": None, "acquired_at": None,
                    "reason": None, "lease_ttl_s": None,
                    "ttl_remaining_s": None, "expired": None,
                    "failovers": int(store.get("leader_failovers")
                                     or "0"),
                    "history": _json.loads(
                        store.get("leader_history") or "[]")}
            raw = store.get("leader")
            if raw is not None:
                holder = _json.loads(raw)
                info["holder"] = holder.get("session")
                info["term"] = int(holder.get(
                    "term", holder.get("generation", 0)))
                info["acquired_at"] = holder.get("acquired_at")
                info["reason"] = holder.get("reason")
        finally:
            meta.store.close()
    else:
        raise SystemExit("ctl meta leader needs --meta-addr HOST:PORT "
                         "(live) or --data-dir DIR (offline)")
    if args.json:
        print(_json.dumps(info, indent=2))
        return 0
    if info.get("holder") is None:
        print("leader: (none)")
    else:
        ttl = info.get("ttl_remaining_s")
        ttl_s = "unknown (offline)" if ttl is None else f"{ttl:.3f}s"
        print(f"leader:    {info['holder']}")
        print(f"term:      {info['term']}")
        print(f"reason:    {info.get('reason') or '-'}")
        print(f"ttl left:  {ttl_s}"
              + ("  [EXPIRED]" if info.get("expired") else ""))
    print(f"failovers: {info.get('failovers', 0)}")
    history = info.get("history") or []
    if history:
        print("term\tholder\treason\tleaderless_s")
        for h in history:
            gap = h.get("leaderless_s")
            print(f"{h.get('term')}\t{h.get('holder')}\t"
                  f"{h.get('reason')}\t"
                  f"{'' if gap is None else f'{gap:.3f}'}")
    return 0


def _ctl_cluster_fragments(args, _json) -> int:
    """`ctl cluster fragments`: where each spanning job ACTUALLY runs.
    Reads the persisted fragment→worker placement straight off the meta
    store (offline-safe, no job recovery), then — when the cluster can
    be brought up (--workers, or inferred from the placements) — attaches
    live per-edge permit state from the workers' exchange counters."""
    import os
    from .meta.service import MetaService
    path = os.path.join(args.data_dir, "meta", "meta.jsonl")
    if not os.path.exists(path):
        raise SystemExit(f"{args.data_dir!r} holds no meta store")
    meta = MetaService(data_dir=os.path.join(args.data_dir, "meta"))
    placements = meta.all_placements()
    meta.store.close()
    for job, p in sorted(placements.items()):
        print(f"-- {job} (root worker {p.root_worker})")
        for fid in sorted(p.actors):
            for a in p.actors[fid]:
                print(f"Fragment {fid} actor {a.actor}: "
                      f"worker {a.worker} "
                      f"vnodes [{a.vnode_start}, {a.vnode_end})")
    if not placements:
        print("(no spanning jobs placed)")
        return 0
    # live per-edge permit state: recover the cluster and scrape the
    # workers' exchange counters (skipped if bring-up fails — the
    # persisted placement above is still authoritative for WHERE)
    args.workers = _infer_workers(args)
    try:
        session = _build_session(args)
    except Exception as e:  # noqa: BLE001 - offline dump already printed
        print(f"(live edge state unavailable: {type(e).__name__}: {e})")
        return 0
    try:
        edges = session.metrics().get("exchange") or []
        print("-- live exchange edges")
        if not edges:
            print("(none reported)")
        for e in edges:
            print(f"{e.get('edge')} [{e.get('dir')}] worker {e.get('worker')}"
                  f" -> peer {e.get('peer_worker')}: chunks={e.get('chunks')}"
                  f" bytes={e.get('bytes')}"
                  f" permits_waited={e.get('permits_waited')}"
                  f" backlog={e.get('backlog')}")
    finally:
        session.close()
    return 0


def _infer_workers(args) -> int:
    """Workers needed to bring the persisted cluster up: the explicit
    --workers, raised to cover every worker any persisted placement
    names (a spanning job must find its per-worker stores)."""
    import os
    from .meta.service import MetaService
    n_workers = args.workers
    path = os.path.join(args.data_dir, "meta", "meta.jsonl")
    if os.path.exists(path):
        meta = MetaService(data_dir=os.path.join(args.data_dir, "meta"))
        for p in meta.all_placements().values():
            n_workers = max(n_workers, max(p.workers()) + 1)
        meta.store.close()
    return n_workers


def _ctl_cluster_rescale(args, _json) -> int:
    """`ctl cluster rescale JOB --parallelism N`: recover the cluster
    from the durable dir, run the LIVE vnode migration (only the vnode
    ranges whose owner changes move, as handoff refs — docs/scaling.md),
    persist the new placement, and report what moved. Offline-safe in
    the sense that it owns the cluster for the duration; a deployment
    with its own live session must issue Session.rescale there instead."""
    if not args.job or not args.parallelism:
        raise SystemExit(
            "usage: ctl cluster rescale JOB --parallelism N --data-dir DIR")
    args.workers = max(_infer_workers(args), args.parallelism)
    session = _build_session(args)
    try:
        out = session.rescale(args.job, args.parallelism)
        session.flush()
        print(_json.dumps(out, indent=2, default=str))
    finally:
        session.close()
    return 0


def _ctl_cluster_autoscaler(args, _json) -> int:
    """`ctl cluster autoscaler`: dump the scaling plane's state —
    policy streaks/cooldowns per job, decisions taken, executed
    migrations and their moved vnode ranges (metrics()["autoscaler"])."""
    args.workers = _infer_workers(args)
    session = _build_session(args)
    try:
        print(_json.dumps(session.metrics().get("autoscaler", {}),
                          indent=2, default=str))
    finally:
        session.close()
    return 0


def _ctl_dispatch(args, session, _json) -> None:
    if args.what == "jobs":
        for kind, reg in (("TABLE", session.catalog.tables),
                          ("MV", session.catalog.mvs),
                          ("SOURCE", session.catalog.sources),
                          ("SINK", session.catalog.sinks)):
            for name in sorted(reg):
                print(f"{kind}\t{name}")
    elif args.what == "parameters":
        for k, v in session.parameters():
            print(f"{k}\t{v}")
    elif args.what == "fragments":
        from .meta.fragment import fragment_plan
        for name, mv in sorted(session.catalog.mvs.items()):
            ast = getattr(mv, "query_ast", None)
            if ast is None:
                continue
            # the SAME frontend pipeline the job was built with — the
            # printed topology must match the deployed executors
            plan = session._plan(ast)
            print(f"-- {name}")
            print(fragment_plan(plan).explain())
    elif args.what == "metrics":
        print(_json.dumps(session.metrics(), indent=2, default=str))
    elif args.what == "trace":
        if args.sub == "barrier":
            _ctl_trace_barrier(args, session, _json)
            return
        # await_tree() federates worker-hosted jobs' trees (and takes the
        # API lock) — a bare dump_session would print them as
        # "<remote; no stats snapshot yet>"
        print(session.await_tree())


def _ctl_trace_barrier(args, session, _json) -> None:
    """`ctl trace barrier [--inflight] [--json]`: the barrier
    observatory over a live session — waterfall history + per-stage
    percentiles, or (--inflight) live stuck-barrier blame naming the
    exact actors/links that have not acked (docs/observability.md)."""
    from .common.barrier_ledger import ALL_STAGES
    ledger = session._barrier_ledger
    if args.json:
        out = {"history": ledger.history(),
               "stages": ledger.stage_percentiles(),
               "summary": ledger.summary()}
        if args.inflight:
            out["inflight"] = session.barrier_blame()
        print(_json.dumps(out, indent=2, default=str))
        return
    if args.inflight:
        findings = session.barrier_blame()
        if not findings:
            print("no in-flight barriers (nothing to blame)")
            return
        print("epoch\tage_ms\tkind\tjob\tworker\tactor\tlink\treason")
        for f in findings:
            age = "" if f["age_ms"] is None else f"{f['age_ms']:.1f}"
            actor = "" if f["actor"] is None else \
                f"f{f['fragment']}a{f['actor']}"
            print(f"{f['epoch']}\t{age}\t{f['kind']}\t"
                  f"{f['job'] or ''}\t{f['worker']}\t{actor}\t"
                  f"{f['link'] or ''}\t{f['reason']}")
        return
    history = ledger.history()
    if not history:
        print("no completed barriers in the history ring")
        return
    print("epoch\tckpt\tresult\ttotal_ms\t"
          + "\t".join(f"{s}_ms" for s in ALL_STAGES))
    for rec in history:
        stages = rec["stages"]
        cells = "\t".join(
            f"{stages[s]:.2f}" if s in stages else "-"
            for s in ALL_STAGES)
        print(f"{rec['epoch']}\t{'y' if rec['checkpoint'] else 'n'}\t"
              f"{rec['result']}\t{rec['total_ms']:.2f}\t{cells}")
    print()
    print("stage\tp50_ms\tp99_ms\tn")
    percentiles = ledger.stage_percentiles()
    for stage in ALL_STAGES:
        pct = percentiles.get(stage)
        if pct is None:
            continue
        print(f"{stage}\t{pct['p50_ms']}\t{pct['p99_ms']}\t{pct['n']}")


def _playground(args) -> int:
    import asyncio
    from .frontend.pgwire import PgWireServer

    session = _build_session(args)

    async def run():
        auth = ({args.user: args.password}
                if getattr(args, "password", None) else None)
        server = PgWireServer(session, args.host, args.port, auth=auth)
        await server.start()
        print(f"risingwave_tpu playground listening on "
              f"{args.host}:{args.port}", flush=True)
        if getattr(args, "dashboard_port", None) is not None:
            from .frontend.dashboard import serve_dashboard
            dash = serve_dashboard(session, args.host, args.dashboard_port)
            print(f"dashboard on http://{args.host}:{dash.port}/",
                  flush=True)

        session.barrier_interval_ms = args.tick_interval_ms

        async def ticker():
            # the meta barrier tick (reference: GlobalBarrierManager
            # barrier_interval_ms, src/common/src/config.rs:595). Reads the
            # interval live so SET barrier_interval_ms takes effect; a tick
            # failure is logged and retried, never silently fatal.
            while True:
                await asyncio.sleep(session.barrier_interval_ms / 1000)
                try:
                    await asyncio.get_running_loop().run_in_executor(
                        server._executor,
                        lambda: session.jobs and session.tick())
                except Exception as e:  # noqa: BLE001
                    print(f"barrier tick failed: {e}", file=sys.stderr,
                          flush=True)

        tick_task = asyncio.ensure_future(ticker())
        try:
            await server.serve_forever()
        finally:
            tick_task.cancel()
    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
