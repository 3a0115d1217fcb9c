"""Fused-job deployment: one registry, one builder, one tick driver.

A source+agg MV the device can run whole (``match_coschedulable``) is
deployed as a *fused job*: its ingest, projection and aggregation run
inside a scheduler's group dispatch, a real ``HashAggExecutor`` (over a
dummy source, never executed) is kept as the flush/persistence engine so
state-table checkpointing and recovery load are the executor path's own
code, and the MV pipeline is a plain QueueSource → Materialize fed by the
group's barrier flush. Three schedulers can host such a job; ``KINDS``
holds ONE row for each, in routing precedence:

* ``shardfused`` — parallel/fused.ShardedCoScheduler: signature-equal MVs
  join one K-jobs × S-shards group, one dispatch per epoch across every
  chip of ``config.mesh`` (ops/fused_sharded.py);
* ``hetero`` — stream/tick_compiler.TickCompiler: UNEQUAL jobs fused into
  shape-class supergroups + mega-epochs, recompiled lazily on DDL;
* ``coschedule`` — stream/coschedule.CoScheduler: signature-equal jobs
  stacked under one vmapped dispatch per group.

A row's ``name`` is at once the DDL-log marker word (``-- <name> <mv>``),
the ``metrics()`` key and the ``schedulers`` key. Everything else a kind
differs in is a field of its row; the builder (``create``) and the driver
(``tick``) are written once over ``JobAxisGroup``'s interface.

Arrows: frontend/session.py → this module → {coschedule, tick_compiler,
parallel/fused}; nothing here imports the session (it is handed in as
``host``: store, catalog, seed, chunk sizes, ``_plan``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from ..common.tracing import CAT_EPOCH, span
from ..common.types import INT64, VARCHAR, Field, Schema
from ..storage.state_table import StateTable
from .coschedule import (
    CoScheduler, DeviceSourceCursor, FusedJobSpec, agg_signature,
    declared_chunk_fn, match_coschedulable,
)
from .materialize import MaterializeExecutor
from .tick_compiler import TickCompiler


@dataclasses.dataclass
class FusedEngine:
    """One fused job's host side, by name."""

    kind: "FusedKind"            # its row of KINDS
    agg: object                  # flush/persistence HashAggExecutor
    queue: object                # QueueSource feeding its Materialize
    cursor: DeviceSourceCursor   # the device generator's event/epoch cursor
    split_state: StateTable      # where the cursor persists per checkpoint
    materialize: MaterializeExecutor


def _load_own(agg_of, state_table, config, recovering):
    """(a) the executor's own recovery load fills ``agg.state``."""
    agg = agg_of(state_table)
    return agg, agg.state


def _load_resharded(agg_of, state_table, config, recovering):
    """(a) mesh-sharded: the state table is attached AFTER construction —
    the executor's own recovery load would pull EVERY shard's rows into
    one solo table; replaying the vnode mapping over the committed rows
    re-partitions them onto THIS session's mesh instead, so an 8-shard
    checkpoint reopens cleanly on a 4-shard mesh."""
    from ..parallel.fused import load_shard_states
    agg = agg_of(None)
    agg.state_table = state_table
    rows = list(state_table.scan_all()) if recovering else []
    return agg, (load_shard_states(agg.core, rows, config.mesh.devices.size)
                 if rows else None)


def _sharded_scheduler(mesh):
    from ..parallel.fused import ShardedCoScheduler
    return ShardedCoScheduler(mesh)


def _emptied_group_epochs(sched, group) -> dict:
    """(d) a job that emptied its group takes the group out of the live
    registry: its epochs retire for the per_epoch ratio."""
    if group is not None and group.n_jobs == 0 and group.epochs_run:
        return {group.epoch_qualname: group.epochs_run}
    return {}


def _sharded_stats(sched) -> dict:
    # signature-equal MVs share one K×S group, so their stats coincide
    return {name: {"shards": g.n, "epochs_run": g.epochs_run,
                   "recv_width": g.recv_width,
                   "route_grows": g.route_grows, "group_jobs": g.n_jobs}
            for name, g in sched.jobs.items()}


@dataclasses.dataclass(frozen=True)
class FusedKind:
    """One scheduler that can host fused jobs: a row of ``KINDS``."""

    name: str                    # marker word, metrics key, schedulers key
    eligible: Callable           # config -> bool
    refusal: str                 # a marked MV reopened where not eligible
    scheduler: Callable          # mesh -> a fresh scheduler
    stats: Callable              # scheduler -> its metrics() entry
    # (a) recovered agg state: -> (engine, state to register)
    load: Callable = _load_own
    # (b) what ``add`` takes besides name, spec, start, batch_no
    add_args: Callable = lambda state, m: {"state": state}
    # (c) before a tick, once the scheduler holds jobs
    prepare: Callable = lambda sched: None
    # (c, d) epochs retired by an add / a remove: (scheduler, the job's
    # entry of ``scheduler.jobs`` before the remove) -> {qualname: epochs}
    retired: Callable = _emptied_group_epochs


KINDS = (
    FusedKind(
        name="shardfused",
        eligible=lambda c: (getattr(c, "coschedule", False)
                            and c.mesh is not None
                            and c.agg_hbm_budget is None),
        refusal=("MV {name!r} was created mesh-sharded fused; reopen "
                 "the session with a device mesh ([streaming] mesh_shape / "
                 "BuildConfig.mesh) and [streaming] coschedule = true — or "
                 "DROP and re-CREATE it"),
        scheduler=_sharded_scheduler, stats=_sharded_stats,
        load=_load_resharded,
        add_args=lambda state, m: {"shard_states": state}),
    # wins over ``coschedule`` when both are set: shape-class padding /
    # mega-epoch concatenation replace the exact-signature grouping rule
    FusedKind(
        name="hetero",
        eligible=lambda c: (getattr(c, "tick_compiler", False)
                            and c.mesh is None
                            and c.fragment_parallelism <= 1
                            and c.agg_hbm_budget is None),
        refusal=("MV {name!r} was created tick-compiled; reopen the "
                 "session with [streaming] tick_compiler = true and a "
                 "compatible config (no mesh, fragment_parallelism 1, "
                 "no agg_hbm_budget) — or DROP and re-CREATE it"),
        scheduler=lambda mesh: TickCompiler(),
        stats=lambda s: {**s.stats(), "attribution": s.attribution()},
        add_args=lambda state, m: {"state": state,
                                   "n_source_cols": len(m.col_map)},
        prepare=TickCompiler.ensure_compiled,
        # add and remove dissolve the schedule; its groups retire their
        # epochs into the compiler's ledger
        retired=lambda sched, _job: sched.take_retired()),
    # agg_hbm_budget: the co-scheduled flush has no eviction path, so
    # budgeted configs stay on the executor pipeline
    FusedKind(
        name="coschedule",
        eligible=lambda c: (getattr(c, "coschedule", False)
                            and c.mesh is None
                            and c.fragment_parallelism <= 1
                            and c.agg_hbm_budget is None),
        refusal=("MV {name!r} was created co-scheduled; reopen the "
                 "session with [streaming] coschedule = true and a "
                 "co-schedulable config (no mesh, fragment_parallelism 1, "
                 "no agg_hbm_budget) — or DROP and re-CREATE it"),
        scheduler=lambda mesh: CoScheduler(), stats=CoScheduler.stats),
)

#: the DDL-log lines that are markers, not statements
MARKER_PREFIXES = tuple(f"-- {kind.name}" for kind in KINDS)


class FusedJobs:
    """Every fused job of one Session: engines, marker sets, the three
    schedulers and the retired-epochs ledger."""

    def __init__(self, host, refuse: type):
        self._host = host
        self._refuse = refuse        # the host's SQL error class
        # MVs the DDL log marks as built by a kind: their durable
        # agg/split tables were laid out by this module's builder, and
        # decoding them through the executor path would shift table ids,
        # so recovery replays each down the path that wrote it or refuses
        # loudly — marker-directed in BOTH directions
        self.markers: dict[str, set] = {kind.name: set() for kind in KINDS}
        # epochs run by groups since dissolved or dropped, per dispatch
        # qualname — the profiler's counts are cumulative, so the live
        # per_epoch ratio must keep dividing by these after a DROP +
        # re-CREATE or a schedule recompile
        self.retired: dict[str, int] = {}
        self.pipeline_stats = {"deferred_flushes": 0, "drains": 0}
        self.reset()

    def reset(self) -> None:
        """Forget every job and marker (a writer demoted to serving)."""
        self.schedulers = {kind.name: kind.scheduler(None) for kind in KINDS}
        self.engines: dict[str, FusedEngine] = {}
        for names in self.markers.values():
            names.clear()

    # -- the DDL log ----------------------------------------------------------

    def parse_marker(self, line: str) -> bool:
        """Record a ``-- <kind> <mv>`` line; False for any other line."""
        for kind, prefix in zip(KINDS, MARKER_PREFIXES):
            if line.startswith(prefix):
                self.markers[kind.name].add(line[len(prefix):].strip())
                return True
        return False

    def forget(self, name: str) -> None:
        for names in self.markers.values():
            names.discard(name)

    # -- CREATE ---------------------------------------------------------------

    def _match(self, stmt, recovering: bool):
        host = self._host
        if not any(sd.connector == "nexmark"
                   for sd in host.catalog.sources.values()):
            # cheap gate: without an eligible source no plan can match —
            # skip the extra planning pass the match would need
            return None, None
        plan = host._plan(stmt.query, lenient=recovering)
        return plan, match_coschedulable(plan)

    def route(self, stmt, config, recovering: bool, pk_prefix: int = 0):
        """Deploy ``stmt`` on the first kind that takes it. Returns
        ``(engine, plan)``; engine is None when no kind is eligible or
        the shape is not fusable (the executor fallback — which reuses
        ``plan`` instead of planning the query twice). Raises the kind's
        refusal for a marked MV its kind cannot take back."""
        plan = m = None
        for kind in KINDS:
            marked = stmt.name in self.markers[kind.name]
            if not pk_prefix and kind.eligible(config) \
                    and (not recovering or marked):
                if plan is None:
                    plan, m = self._match(stmt, recovering)
                if m is not None:
                    return self.create(kind, stmt, plan, m, config,
                                       recovering), plan
            if recovering and marked:
                raise self._refuse(kind.refusal.format(name=stmt.name))
        return None, plan

    def create(self, kind: FusedKind, stmt, plan, m, config,
               recovering: bool) -> FusedEngine:
        """Build one fused job and register it with its scheduler. Table
        ids are allocated agg state, split state, MV. The host starts
        the job (catalog entry, StreamJob, source feed, barrier
        hand-shake) from the returned record."""
        from ..connector import NexmarkConfig
        from ..connector.nexmark import DeviceBidGenerator
        from ..frontend.runtime import QueueSource
        from .hash_agg import HashAggExecutor, agg_state_schema
        from .project import ProjectExecutor
        from .source import MockSource

        host, name = self._host, stmt.name
        # membership changes restack the job axis (or recompile the
        # schedule): resolve any deferred flush first
        self.drain()
        proj = ProjectExecutor(MockSource(m.source.schema, []),
                               list(m.exprs), names=m.proj_names)
        key_fields = [proj.schema[i] for i in m.group_keys]
        agg, state = kind.load(
            lambda state_table: HashAggExecutor(
                proj, list(m.group_keys), list(m.agg_calls),
                state_table=state_table,
                table_capacity=config.agg_table_capacity,
                out_capacity=config.chunk_capacity),
            StateTable(host.store, host.catalog.next_table_id(),
                       agg_state_schema(key_fields, m.agg_calls),
                       list(range(len(m.group_keys)))),
            config, recovering)
        # split-state table: the device generator's event/epoch cursor,
        # persisted per checkpoint epoch exactly like a connector reader
        split_st = StateTable(
            host.store, host.catalog.next_table_id(),
            Schema((Field("split_id", VARCHAR),
                    Field("next_offset", INT64))), [0])
        cursor = DeviceSourceCursor()
        if recovering:
            offsets = {VARCHAR.to_python(r[0]): int(r[1])
                       for r in split_st.scan_all()}
            if offsets:
                cursor.seek(offsets)
        q = QueueSource(plan.schema)
        mat = MaterializeExecutor(
            q, StateTable(host.store, host.catalog.next_table_id(),
                          plan.schema, list(plan.pk)))
        # honor the declared source's rows_per_chunk exactly like the
        # host reader does (connector/factory.py make_reader)
        rate = (m.source.options or {}).get("rows_per_chunk")
        rows_per_chunk = int(rate) if rate else host.source_chunk_capacity
        # seed parity with the solo executor path: every nexmark reader
        # is seeded with the session seed (factory.make_reader), so the
        # same CREATE yields the same stream regardless of the flag
        src_cfg = NexmarkConfig(chunk_capacity=rows_per_chunk)
        gen = DeviceBidGenerator(src_cfg, seed=host.seed)
        source_sig = ("nexmark_bid", src_cfg.chunk_capacity,
                      src_cfg.events_per_second, src_cfg.active_people,
                      src_cfg.in_flight_auctions, src_cfg.start_time_us,
                      m.col_map,
                      tuple(sorted((m.source.options or {}).items())))
        spec = FusedJobSpec(
            kind="agg",
            signature=agg_signature(agg.core, m.exprs, rows_per_chunk,
                                    source_sig),
            chunk_fn=declared_chunk_fn(gen.chunk_fn(), m.col_map),
            exprs=tuple(m.exprs), core=agg.core,
            rows_per_chunk=rows_per_chunk, seed=host.seed)
        sched = self.schedulers[kind.name]
        if getattr(sched, "mesh", None) is not config.mesh:
            # only the mesh-sharded scheduler is bound to a mesh (the
            # other kinds are eligible without one only): a new one per
            # mesh
            sched = self.schedulers[kind.name] = kind.scheduler(config.mesh)
        sched.add(name, spec, start=cursor.events, batch_no=cursor.epochs,
                  **kind.add_args(state, m))
        self._retire(kind.retired(sched, None))
        engine = FusedEngine(kind, agg, q, cursor, split_st, mat)
        self.engines[name] = engine
        self.markers[kind.name].add(name)
        if host.data_dir is not None and not recovering:
            host.store.log.log_ddl(f"-- {kind.name} {name}")
        return engine

    # -- DROP -----------------------------------------------------------------

    def drop(self, name: str) -> None:
        self.forget(name)
        engine = self.engines.pop(name, None)
        if engine is None:
            return
        sched = self.schedulers[engine.kind.name]
        job = sched.jobs.get(name)
        if job is not None:
            sched.remove(name)
            self._retire(engine.kind.retired(sched, job))

    def _retire(self, epochs: dict) -> None:
        for qualname, n in epochs.items():
            self.retired[qualname] = self.retired.get(qualname, 0) + n

    # -- the tick -------------------------------------------------------------

    def groups(self) -> list:
        """Every live group of every scheduler."""
        out: list = []
        for sched in self.schedulers.values():
            live = sched.groups
            out.extend(live.values() if isinstance(live, dict) else live)
        return out

    def _push(self, outs: dict) -> None:
        """Feed a resolved group flush into each member MV's
        Materialize queue (they ride the next barrier)."""
        for name, chunks in outs.items():
            q = self.engines[name].queue
            for ch in chunks:
                q.push(ch)

    def tick(self, epoch: int, checkpoint: bool, generate: bool) -> None:
        """Per-tick driver: ONE fused dispatch per group covers every
        member MV's epoch; the group flush feeds each job's Materialize
        queue; checkpoint barriers write each job's delta through its
        own HashAggExecutor's state-table flush, then restack once.

        Pipelined cadence (docs/performance.md "Pipelined tick"): the
        LAST tick's deferred flushes resolve first (their packed fetch
        has been streaming while the host ran the previous barrier, and
        their chunks ride THIS barrier), then EVERY group's next epoch
        is enqueued before any flush decode — the device queue stays
        full while Python gathers. With ``pipeline_depth >= 2`` the new
        flush stays pending into the next tick; checkpoint barriers
        (and generate-off ticks) resolve it synchronously, so committed
        state is bit-exact vs the synchronous path. The sharded
        grow-retry drains inside ``finish_flush`` before anything else
        dispatches, and sharded epochs never donate, so the deferred
        handle's pre-finish state stays valid for the gathers."""
        k = self._host.chunks_per_tick
        for kind in KINDS:
            sched = self.schedulers[kind.name]
            if sched.jobs:
                kind.prepare(sched)
        groups = self.groups()

        def conductor(name: str, stage: Optional[str]):
            return span(name, epoch=epoch, stage=stage, cat=CAT_EPOCH,
                        tid="conductor")

        # 1. resolve last tick's deferred flushes (pipeline_depth >= 2);
        #    the wait and the decode inside carry the stages
        if any(group.pending is not None for group in groups):
            with conductor("cosched.resolve_deferred", None):
                for group in groups:
                    if group.pending is not None:
                        self._push(group.finish_flush())
        # 2. enqueue every group's epoch (cross-engine overlap)
        ran = generate and k > 0
        if ran:
            with conductor("cosched.dispatch", "epoch_dispatch"):
                for group in groups:
                    group.run_epoch(k)
                    for j, name in enumerate(group.names):
                        cursor = self.engines[name].cursor
                        cursor.events = group.starts[j]
                        cursor.epochs = group.batch_nos[j]
        # 3. enqueue every group's probe + start its packed fetch BEFORE
        #    decoding any of them
        with conductor("cosched.flush_begin", "epoch_dispatch"):
            for group in groups:
                group.begin_flush()
        if self._host.pipeline_depth >= 2 and ran and not checkpoint:
            # 4a. defer resolution to the next tick / drain point: epoch
            # N+1 will dispatch before this packed fetch resolves
            self.pipeline_stats["deferred_flushes"] += len(groups)
            return
        # 4b. synchronous resolution (depth 1, checkpoint, or idle tick)
        for group in groups:
            self._push(group.finish_flush())
            if checkpoint:
                group.checkpoint({name: self.engines[name].agg
                                  for name in group.names}, epoch)

    def drain(self) -> None:
        """Resolve every deferred flush and feed its chunks to the job
        queues (they ride the next barrier). The pipeline's drain points
        — DDL, DROP, scoped recovery, checkpoint ticks — call this so
        membership changes and durable cuts never race an in-flight
        packed fetch. No-op when nothing is pending (always, at
        pipeline_depth = 1)."""
        for group in self.groups():
            if group.pending is not None:
                self._push(group.finish_flush())
                self.pipeline_stats["drains"] += 1

    # -- metrics() ------------------------------------------------------------

    def stats(self) -> dict:
        """``{kind: its scheduler's entry}``: group membership + epochs
        run, the compiled schedule's shape + per-job cost attribution,
        shard count + group size + grow-retry events per sharded job."""
        return {kind.name: kind.stats(self.schedulers[kind.name])
                for kind in KINDS}

    def epochs_by_qualname(self) -> dict:
        """Epochs run per epoch-dispatch qualname, live and retired: what
        the profiler's dispatch counts divide by (per_epoch == 1.0)."""
        out = dict(self.retired)
        for g in self.groups():
            if g.epochs_run:
                out[g.epoch_qualname] = \
                    out.get(g.epoch_qualname, 0) + g.epochs_run
        return out

    def pending_flushes(self) -> int:
        return sum(1 for g in self.groups() if g.pending is not None)
