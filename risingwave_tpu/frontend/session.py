"""Session: SQL entry point + single-process cluster (playground mode).

Counterpart of the reference's Session/handler dispatch + playground runtime
(reference: src/frontend/src/handler/mod.rs:167 per-statement dispatch;
src/cmd_all/src/playground.rs one-process cluster). The Session owns the
catalog, the state store, the running stream jobs, and the epoch clock: its
``tick()`` is the GlobalBarrierManager's inject/collect cycle (SURVEY.md
§3.2) — generate source chunks, push a barrier into every root queue, await
all jobs, commit the epoch on checkpoints.

Batch ``SELECT`` runs the SAME operator pipeline over snapshot sources (two
barriers bracket the snapshot), then folds the delta stream into rows — the
streaming/batch unification the reference gets from running batch plans
over Hummock snapshots (SURVEY.md §3.5), obtained here by construction.
"""

from __future__ import annotations

import asyncio
import dataclasses
import functools
import threading
from typing import Any, Callable, Optional, Sequence

from ..common.chunk import (
    OP_DELETE, OP_INSERT, OP_UPDATE_DELETE, OP_UPDATE_INSERT, HostChunk,
    RowIdSequence, StagedCounts, StreamChunk, chunk_to_rows, make_chunk,
)
from ..common.config import MeshUnavailableError
from ..common.types import Field, Schema
from ..connector.base import feed_chunks
from ..connector.nexmark import (
    AUCTION_SCHEMA, BID_SCHEMA, PERSON_SCHEMA, NexmarkConfig, NexmarkGenerator,
)
from ..storage.state_store import MemoryStateStore
from ..storage.state_table import StateTable
from ..stream.eowc import WatermarkFilterExecutor
from ..stream.executor import Executor
from ..stream.fused_jobs import MARKER_PREFIXES, FusedJobs
from ..stream.materialize import MaterializeExecutor
from ..stream.message import Barrier, Message, Mutation, MutationKind
from ..stream.row_id_gen import RowIdGenExecutor
from ..stream.source import MockSource
from . import sqlast as A
from .binder import BindError, ExprBinder, Scope
from .build import BuildConfig, BuildContext, build_plan, collect_leaves
from .catalog import (
    Catalog, CatalogError, MaterializedViewDef, SinkDef, SourceDef, TableDef,
    type_from_name,
)
from .parser import parse_sql
from .planner import Planner, PMvScan, PSource, PTableScan, PValues, PlanError
from .runtime import ChangelogBus, QueueSource, StreamJob


class SqlError(ValueError):
    pass


def _udf_snapshot() -> dict:
    from ..udf.client import udf_plane
    return udf_plane().snapshot()


def _ast_uses_udf(node) -> bool:
    """True when a query AST calls a REGISTERED UDF anywhere (generic
    dataclass walk). Placement routing: such plans build session-local —
    only this process's UDF plane can resolve the name."""
    import dataclasses as _dc
    from ..expr.udf import _UDF_NAMES
    if not _UDF_NAMES:
        return False
    stack = [node]
    while stack:
        n = stack.pop()
        if isinstance(n, (list, tuple)):
            stack.extend(n)
            continue
        if not _dc.is_dataclass(n):
            continue
        if isinstance(n, A.FuncCall) and \
                str(n.name).lower() in _UDF_NAMES:
            return True
        for f in _dc.fields(n):
            stack.append(getattr(n, f.name))
    return False


def _retry_snapshot() -> dict:
    from ..common.retry import GLOBAL_RETRY_METRICS
    return GLOBAL_RETRY_METRICS.snapshot()


def _locked(fn):
    """Serialize a public Session entry point on the session's API lock.

    The Session is single-threaded by design, but observability endpoints
    (dashboard / Prometheus HTTP threads) read catalog, metrics, and the
    event loop concurrently with the driving thread — the lock makes every
    public entry a consistent snapshot boundary (pgwire gets the same
    property from its one-worker executor). Reentrant: locked entries call
    each other (run_sql → flush → tick)."""

    @functools.wraps(fn)
    def inner(self, *args, **kwargs):
        with self._api_lock:
            return fn(self, *args, **kwargs)

    return inner


from ..connector.factory import DEBEZIUM_NEEDS_PK as _DEBEZIUM_NEEDS_PK

#: state-table id range reserved per fragment of a spanning job: each
#: fragment's build allocates ids from its own deterministic window, so
#: actors of one fragment (different workers, disjoint stores) share ids
#: while fragments never collide — and recovery replays identically
_SPAN_ID_STRIDE = 256


def _values_chunk(leaf: PValues) -> StreamChunk:
    """Constant-fold VALUES expressions into one chunk (row-less exprs are
    evaluated over a dummy 1-row chunk — the frontend's eval_const)."""
    import jax.numpy as jnp
    from ..expr.expr import Literal
    dummy = StreamChunk(jnp.zeros(1, jnp.int8), jnp.ones(1, jnp.bool_), ())
    rows = []
    for r in leaf.rows:
        vals = []
        for e in r:
            if isinstance(e, Literal):
                vals.append(e.value)
            else:
                c = e.eval(dummy)
                vals.append(e.type.to_python(c.data[0])
                            if bool(c.mask[0]) else None)
        rows.append(tuple(vals))
    return make_chunk(leaf.schema, rows, capacity=max(len(rows), 1))


@dataclasses.dataclass
class _BackfillRef:
    """A live BackfillExecutor and its owning job (for teardown)."""

    bf: Any
    job: str = ""


@dataclasses.dataclass
class _SourceFeed:
    """A connector instance feeding one job's source leaf.

    ``reader`` + ``state_table`` carry the split-state checkpoint contract
    (reference: source split state,
    src/stream/src/executor/source/state_table_handler.rs): the session
    records ``reader.offsets`` per injected epoch and persists the offsets
    for each checkpoint epoch atomically with that epoch's state commit;
    recovery seeks the reader before the first tick."""

    queue: QueueSource
    #: one chunk's host columns a call; ``tick`` stages a barrier's together
    generator: Callable[[], Optional[HostChunk]]
    reader: Optional[Any] = None
    state_table: Optional[StateTable] = None
    offsets_at_epoch: dict = dataclasses.field(default_factory=dict)
    job: str = ""          # owning stream job; feed dies with it on DROP
    #: where the leaf's hidden _row_id stands; the chunks get the column
    #: as they are staged (None: a fused job's feed, which stages nothing)
    row_ids: Optional[RowIdSequence] = None


def _split_sql(sql: str) -> list[str]:
    """Split a script into statement texts (';' outside string literals and
    ``--`` line comments) so DDL statements can be logged verbatim for
    recovery replay."""
    parts, buf = [], []
    in_str = in_comment = False
    i = 0
    while i < len(sql):
        ch = sql[i]
        if in_comment:
            buf.append(ch)
            if ch == "\n":
                in_comment = False
        elif in_str:
            buf.append(ch)
            if ch == "'":
                in_str = False
        elif ch == "'":
            in_str = True
            buf.append(ch)
        elif ch == "-" and sql[i:i + 2] == "--":
            in_comment = True
            buf.append(ch)
        elif ch == ";":
            parts.append("".join(buf))
            buf = []
        else:
            buf.append(ch)
        i += 1
    parts.append("".join(buf))
    return [p for p in parts if p.strip()]


class Session:
    def __init__(self, checkpoint_frequency: int = 10,
                 chunks_per_tick: int = 1, source_chunk_capacity: int = 1024,
                 config: Optional[BuildConfig] = None, seed: int = 42,
                 data_dir: Optional[str] = None,
                 in_flight_barriers: int = 1,
                 workers: int = 0,
                 state_store: Optional[str] = None,
                 compactors: int = 0,
                 rw_config=None,
                 fault_config=None,
                 autoscaler_config=None,
                 pipeline_depth: int = 1,
                 meta_addr: Optional[str] = None,
                 role: str = "writer"):
        # layered config (common/config.py): an RwConfig overrides the
        # keyword defaults; explicit kwargs are not merged (callers pick one
        # style). Reference: load_config + SystemParams (config.rs:128).
        # API lock FIRST: _recover() below runs locked entry points, and
        # observability HTTP threads may attach before __init__ returns
        self._api_lock = threading.RLock()
        # slow-epoch detector + span-tree snapshots (common/tracing.py)
        self.slow_epoch_threshold_ms: float = 0.0
        import collections as _collections
        self._slow_epochs: _collections.deque = _collections.deque(maxlen=16)
        self._slow_epoch_total = 0
        # federation cache: last stats snapshot per worker (metrics() and
        # await_tree() refresh it; it survives a dead worker for post-hoc
        # inspection)
        self._worker_stats: dict[int, dict] = {}
        self._worker_stats_at = 0.0            # monotonic; rate-limits polls
        self._worker_span_ack: dict[int, int] = {}   # last span_seq ingested
        from ..common.config import ObservabilityConfig
        self.observability = ObservabilityConfig()
        if rw_config is not None:
            st = rw_config.streaming
            checkpoint_frequency = st.checkpoint_frequency
            in_flight_barriers = st.in_flight_barrier_nums
            source_chunk_capacity = st.chunk_capacity
            pipeline_depth = st.pipeline_depth
            data_dir = rw_config.storage.data_dir or data_dir
            if state_store is None:
                state_store = rw_config.storage.state_store
            if not compactors:
                compactors = rw_config.storage.compactors
            # span ring + slow-epoch knobs: [observability] is the
            # canonical section; the original [streaming] fields remain a
            # legacy alias — a set (non-None) observability value wins
            obs = rw_config.observability
            self.observability = obs
            self.slow_epoch_threshold_ms = float(
                obs.slow_epoch_threshold_ms
                if obs.slow_epoch_threshold_ms is not None
                else st.slow_epoch_threshold_ms)
            ring = (obs.trace_ring_capacity
                    if obs.trace_ring_capacity is not None
                    else st.trace_ring_capacity)
            from ..common.tracing import GLOBAL_TRACE
            if ring != GLOBAL_TRACE.capacity:
                GLOBAL_TRACE.set_capacity(ring)
        # barrier observatory (common/barrier_ledger.py): the per-barrier
        # waterfall history ring, and the slow-epoch capture ring resized
        # to its [observability] knob (the maxlen=16 above predates it)
        cap = max(1, int(self.observability.slow_epoch_capture_capacity))
        if cap != self._slow_epochs.maxlen:
            self._slow_epochs = _collections.deque(self._slow_epochs,
                                                   maxlen=cap)
        from ..common.barrier_ledger import BarrierLedger
        self._barrier_ledger = BarrierLedger(
            self.observability.barrier_history_capacity)
        # every XLA compilation becomes an `xla.compile` span of the
        # epoch it stalls (once per process)
        from ..common.compile_cache import install_compile_listener
        install_compile_listener()
        self._worker_stage_ack: dict[int, int] = {}  # last stage_seq seen
        # device profiling plane (common/profiling.py): per-dispatch
        # telemetry + HBM ledger; pure host bookkeeping, on by default
        from ..common.profiling import GLOBAL_PROFILER
        GLOBAL_PROFILER.enabled = self.observability.profiling
        GLOBAL_PROFILER.span_min_ms = self.observability.dispatch_span_min_ms
        if rw_config is not None:
            mesh = None
            if st.mesh_shape:
                # [streaming] mesh_shape: a 1-D device mesh for the
                # sharded paths, built over the first N local devices —
                # N = 1 included, so the knob agrees with `--mesh 1`
                # (a durable job created either way recovers under the
                # other). make_mesh refuses loudly (MeshUnavailableError)
                # when the process has fewer devices than configured.
                from ..parallel.sharded_agg import make_mesh
                mesh = make_mesh(st.mesh_shape)
            config = config or BuildConfig(
                chunk_capacity=st.chunk_capacity,
                agg_table_capacity=st.agg_table_capacity,
                join_key_capacity=st.join_key_capacity,
                join_bucket_width=st.join_bucket_width,
                join_row_capacity=st.join_row_capacity,
                topn_table_capacity=st.topn_table_capacity,
                fragment_parallelism=st.fragment_parallelism,
                coschedule=st.coschedule,
                tick_compiler=st.tick_compiler,
                mesh=mesh)
        # fault-tolerance knobs for every external boundary (object-store
        # retry, sink degrade, broker reconnect, worker deadlines) —
        # common/config.py FaultConfig; explicit fault_config wins over
        # the rw_config section
        from ..common.config import FaultConfig
        self.fault = (fault_config
                      or (rw_config.fault if rw_config is not None
                          else FaultConfig()))
        # out-of-process UDF plane (ISSUE 15, docs/robustness.md): the
        # client boundary is PROCESS-global, so a session only imposes
        # its [udf] section when one was explicitly given — a plain
        # Session() must not clobber a plane another session (or a
        # test/chaos harness) already configured. Servers auto-spawn
        # lazily at the first UDF call; chaos injection traces persist
        # under the first data_dir a session offers.
        from ..udf.client import udf_plane
        if rw_config is not None:
            udf_plane().configure(rw_config.udf, trace_dir=data_dir)
        elif data_dir is not None and udf_plane().trace_dir is None:
            udf_plane().configure(udf_plane().config, trace_dir=data_dir)
        self.udf_config = udf_plane().config
        # multi-tenant attachment (docs/control-plane.md): a "writer"
        # conducts barriers and owns DDL; a "serving" session is a
        # read-only frontend sharing one meta + one Hummock dir with the
        # writer, kept current by meta notifications; a "standby" is a
        # serving session that VOLUNTEERED for election — on a
        # leader_down push it races lease.acquire and the CAS winner
        # promotes in place to writer. In-process meta (meta_addr None)
        # stays the playground default — bit-identical.
        if role not in ("writer", "serving", "standby"):
            raise ValueError(f"unknown session role {role!r} "
                             "(expected 'writer', 'serving' or 'standby')")
        if meta_addr is None and rw_config is not None \
                and getattr(rw_config, "meta", None) is not None:
            meta_addr = rw_config.meta.addr or None
        if role in ("serving", "standby") and meta_addr is None:
            raise ValueError(f"a {role} session needs a meta_addr "
                             "to attach to")
        #: election eligibility survives role flips: a promoted standby
        #: that later demotes goes back to waiting for leader_down
        self._standby = role == "standby"
        self.role = "serving" if role == "standby" else role
        role = self.role
        # failover knobs ([meta] section): the TTL itself is enforced
        # server-side (`ctl meta serve --lease-ttl`); the client keeps
        # the heartbeat cadence and the election jitter cap
        _meta_cfg = (getattr(rw_config, "meta", None)
                     if rw_config is not None else None)
        self._lease_ttl_s = (float(_meta_cfg.lease_ttl_s)
                             if _meta_cfg is not None else 2.0)
        self._lease_heartbeat_s = (float(_meta_cfg.heartbeat_s)
                                   if _meta_cfg is not None else 0.5)
        self._election_backoff_s = (
            float(_meta_cfg.election_backoff_ms) / 1000.0
            if _meta_cfg is not None else 0.1)
        # leadership telemetry (metrics()["leadership"] → Prometheus
        # rw_leader_* / rw_failover_* families)
        self._leadership: dict = {
            "promotions": 0, "demotions": 0, "elections_lost": 0,
            "lease_lost": 0, "last_failover_ms": None}
        # post-promotion vacuum grace: the runs the promoted writer's
        # adopted version referenced, protected until readers re-report
        # pins (one notification round-trip) or the deadline passes
        self._pin_grace_refs: set[str] = set()
        self._pin_grace_deadline = 0.0
        self._pin_grace_epoch = 0
        self._election_lock = threading.Lock()
        self._election_busy = False
        self.meta_addr = meta_addr
        self.catalog = Catalog()
        self.data_dir = data_dir
        if data_dir is not None:
            import os as _osp
            hummock_dir = _osp.path.exists(
                _osp.path.join(data_dir, "hummock", "version.json"))
            if state_store is None:
                # recovery auto-detect: a dir written by the Hummock tier
                # is self-describing (its version manifest exists), so a
                # plain Session(data_dir=...) reopens the right backend
                state_store = "hummock" if hummock_dir else "segment"
            elif state_store == "segment" and hummock_dir:
                raise ValueError(
                    f"{data_dir!r} was written by the hummock state "
                    "store; opening it as 'segment' would recover an "
                    "empty store (drop the explicit state_store to "
                    "auto-detect)")
            elif state_store == "hummock" and not hummock_dir \
                    and _osp.path.exists(
                        _osp.path.join(data_dir, "manifest.json")):
                raise ValueError(
                    f"{data_dir!r} was written by the segment state "
                    "store; opening it as 'hummock' would recover an "
                    "empty store (drop the explicit state_store to "
                    "auto-detect)")
            # durable-tier object store: local FS → optional seeded fault
            # injection (tests/sim chaos) → retry layer, per the fault
            # config (storage/object_store.py open_object_store)
            from ..storage.object_store import open_object_store
            _obj = open_object_store(
                data_dir, self.fault.io_retry_policy(),
                fault_transient_rate=(
                    self.fault.inject_object_store_transient_rate),
                fault_seed=self.fault.inject_object_store_seed,
                fault_torn_write_rate=(
                    self.fault.inject_object_store_torn_write_rate))
            if state_store == "hummock":
                from ..storage.hummock import HummockStateStore
                # a dedicated compactor role takes over compaction; with
                # none configured the store folds in-process (background
                # thread), mirroring the segment log
                # serving sessions never compact or vacuum: the writer
                # owns storage maintenance (a reader rewriting runs
                # would race the writer's version publishes)
                self.store: MemoryStateStore = HummockStateStore(
                    data_dir, object_store=_obj,
                    inline_compaction=(compactors == 0
                                       and role == "writer"))
            elif state_store == "segment":
                from ..storage.checkpoint import DurableStateStore
                self.store = DurableStateStore(data_dir, object_store=_obj)
            else:
                raise ValueError(
                    f"unknown state_store {state_store!r} "
                    "(expected 'segment' or 'hummock')")
        else:
            self.store = MemoryStateStore()
        self.state_store_kind = (state_store if data_dir is not None
                                 else "memory")
        # meta tier as the control plane (VERDICT r3 item 3): catalog
        # mutations write through to the MetaStore + notifications; barrier
        # conduction publishes; the heartbeat detector drives scoped job
        # recovery (reference: meta managers, src/meta/src/manager/)
        import os as _os
        from ..meta.service import MetaBackedCatalog, MetaService
        if meta_addr is not None:
            # remote control plane: the MetaClient mirrors the
            # MetaService surface, so every call site below (and the
            # catalog write-through) works unchanged over the wire
            from ..meta.client import MetaClient
            self.meta = MetaClient(meta_addr)
        else:
            self.meta = MetaService(
                data_dir=_os.path.join(data_dir, "meta")
                if data_dir is not None else None)
        self.catalog_writer = MetaBackedCatalog(self.catalog, self.meta)
        # set once this writer's lease is superseded (a newer writer
        # acquired the leader key): barrier injection and checkpoint
        # commits are refused from then on
        self._fenced = False
        # remote reader pins (meta "hummock_pins" channel): the writer's
        # vacuum treats serving sessions' pinned runs like local pins
        self._remote_pin_runs: set[str] = set()
        # session-generation fencing token (ISSUE 9): monotone across
        # session restarts (persisted in the meta store) and bumped on
        # every scoped recovery. Stamped on every session→worker frame;
        # a stale pre-recovery worker can neither ack barriers (the
        # session drops acks from older generations) nor commit
        # checkpoints (the worker refuses commit frames older than a
        # job's deployment generation).
        if role == "writer":
            self._generation = int(
                self.meta.store.get("session_generation") or "0") + 1
            self.meta.store.put("session_generation",
                                str(self._generation))
            if meta_addr is not None:
                # the same token doubles as the writer's leader-lease
                # TERM (strictly newer terms win the CAS; TTL expiry
                # triggers standby election — docs/control-plane.md)
                self.meta.acquire_leader(self._generation)
                self.meta.start_heartbeat(self._lease_heartbeat_s,
                                          on_lost=self._on_lease_lost)
        else:
            # read-only attachment: adopt (never advance) the token
            self._generation = int(
                self.meta.store.get("session_generation") or "0")
        self._jobs_to_recover: list[str] = []
        self._dead_jobs: set[str] = set()
        self.meta.on_job_failure(self._jobs_to_recover.append)
        # elastic scaling plane (meta/rescale.py + meta/autoscaler.py):
        # the autoscaler observes per-edge exchange pressure each tick
        # and issues LIVE rescale plans; stats feed metrics()/Prometheus
        from ..common.config import AutoscalerConfig
        from ..meta.autoscaler import Autoscaler
        self.autoscaler_config = (
            autoscaler_config
            or (rw_config.autoscaler if rw_config is not None
                else AutoscalerConfig()))
        self.autoscaler = Autoscaler(self.autoscaler_config)
        self._rescale_stats: dict = {"migrations": 0, "moved_vnodes": 0,
                                     "last": None, "history": []}
        self._autoscaler_pw: dict[str, int] = {}
        self._autoscaler_slow_seen = 0
        self._in_rescale = False
        self.config = config or BuildConfig()
        self.checkpoint_frequency = checkpoint_frequency
        # barrier cadence for interval-driven drivers (CLI ticker); mutable
        # via SET barrier_interval_ms
        self.barrier_interval_ms = (
            rw_config.streaming.barrier_interval_ms
            if rw_config is not None else 1000)
        # output schema of the most recent batch SELECT (pgwire reads it
        # instead of re-planning the statement)
        self.last_select_schema: list = []
        self.chunks_per_tick = chunks_per_tick
        self.source_chunk_capacity = source_chunk_capacity
        self.seed = seed
        self.epoch = max(1, self.store.committed_epoch)  # last completed epoch
        # the failure detector's clock is the epoch counter: align it with
        # the session's starting epoch or a recovered session (epoch >> 0)
        # would instantly expire every worker registered at clock 0
        # (writers only: a reader attaching on a stale store snapshot
        # must not drag the shared clock backwards)
        if role == "writer":
            self.meta.advance_epoch_clock(self.epoch)
        self.jobs: dict[str, StreamJob] = {}          # mv/table name -> job
        # fused jobs (stream/fused_jobs.py): eligible source+agg MVs run
        # their whole epoch inside one scheduler's group dispatch —
        # co-scheduled ([streaming] coschedule = true), tick-compiled
        # (tick_compiler = true) or mesh-sharded (coschedule + a mesh)
        self._fused = FusedJobs(self, SqlError)
        # asynchronous epoch pipeline ([streaming] pipeline_depth,
        # docs/performance.md "Pipelined tick"): depth >= 2 defers each
        # fused group's packed flush fetch to the NEXT tick, so epoch
        # N+1's dispatch launches while epoch N's stats stream back and
        # the host decodes/materializes — drained at checkpoint
        # barriers, FLUSH, DDL and recovery, so committed state is
        # bit-exact vs the synchronous path
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.feeds: list[_SourceFeed] = []
        self.backfills: list[_BackfillRef] = []
        # DML rendezvous (reference: DmlManager, src/source/src/
        # dml_manager.rs:44): INSERTs stage here and land in the next epoch
        from ..stream.dml import DmlManager
        self.dml = DmlManager()
        self._table_queues: dict[str, list[QueueSource]] = {}
        self._next_shard = 0
        self._recovering = False
        # barrier pipelining: up to k epochs in flight before tick() blocks
        # on the oldest (reference: in_flight_barrier_nums,
        # src/common/src/config.rs:380-381; GlobalBarrierManager pipelining,
        # src/meta/src/barrier/mod.rs:152)
        self.in_flight_barriers = max(1, in_flight_barriers)
        self._inflight: list[tuple[int, bool]] = []  # (epoch, checkpoint)
        self._injected = self.epoch                  # last injected epoch
        self.paused = False
        self._pending_mutation: Optional[Mutation] = None
        from ..stream.metrics import LatencyRecorder
        self.barrier_latency = LatencyRecorder()
        self._inject_time: dict[int, int] = {}     # epoch -> tracing.now_ns()
        # the session owns its event loop: jobs are long-lived tasks that
        # must survive across synchronous API calls, independent of any
        # ambient loop other code may create/close
        self.loop = asyncio.new_event_loop()
        # pre-warm the native row codec off the hot path: its first use
        # otherwise pays a synchronous g++ compile inside a barrier
        from ..native import codec as _native_codec
        threading.Thread(target=_native_codec, daemon=True).start()
        # remote worker processes (reference: compute nodes; the session
        # doubles as meta + frontend — playground --workers N). MV jobs are
        # placed round-robin on workers; tables/sinks/batch stay local.
        self.workers: list = []
        self._remote_specs: dict[str, dict] = {}
        # spanning jobs: one MV's fragment graph across SEVERAL worker
        # processes (meta/fragment.py scheduler + stream/remote_exchange)
        self._spanning_specs: dict[str, dict] = {}
        import itertools as _it
        # worker↔worker exchange channel ids, disjoint from the per-worker
        # session-channel space (worker_id * 100_000 + n)
        self._next_span_chan = _it.count(10_000_000)
        self._next_remote = 0
        if workers:
            import tempfile
            from .remote import RemoteWorker
            base = data_dir or tempfile.mkdtemp(prefix="rwtpu_cluster_")
            self._workers_base = base
            for k in range(workers):
                w = RemoteWorker(_os.path.join(base, f"worker_{k}"), k,
                                 self.loop,
                                 permits=self.config.exchange_permits)
                # control-frame deadlines: a wedged worker trips these
                # (and the heartbeat-TTL recovery) instead of hanging the
                # session forever
                w.request_timeout = self.fault.worker_request_timeout_s
                w.epoch_timeout = self.fault.worker_epoch_timeout_s
                w.generation = self._generation
                w.spawn()
                self._await(w.connect())
                self.workers.append(w)
                # fragment-placement target registry (reference: compute
                # nodes registering with the meta ClusterManager)
                self.meta.register_compute(w.worker_id, "127.0.0.1",
                                           w.port)
        # dedicated compactor workers (reference: standalone compactor
        # nodes, src/storage/compactor/src/server.rs:57): stateless
        # processes over the SAME object-store root; the session plays
        # the meta role, handing out version-manager tasks off the
        # barrier path (_kick_compaction)
        # serving plane (frontend/serving.py): version-pinned plan cache
        # + two-phase distributed batch aggregation + the lock-free
        # concurrent read path. The data-version seqlock: EVEN = stores
        # quiescent, ODD = a mutation (tick / commit / recovery) is in
        # flight; every mutator brackets itself with _enter_mutation /
        # _exit_mutation and optimistic readers accept a result only
        # when the same even version spans their whole scan.
        self._data_version = 0
        self._mutation_depth = 0
        from ..common.config import BatchConfig
        self.batch_config = (rw_config.batch if rw_config is not None
                             else BatchConfig())
        from .serving import ServingPlane
        self._serving = ServingPlane(self.batch_config)
        self.compactors: list = []
        self._compaction_pump: Optional[threading.Thread] = None
        if compactors and data_dir is not None \
                and self.state_store_kind == "hummock":
            from ..worker.compactor import CompactorClient
            for k in range(compactors):
                c = CompactorClient(data_dir, k)
                c.spawn()
                self.compactors.append(c)
        if role == "serving":
            # no jobs, no DDL replay, no barrier conduction: rebuild the
            # catalog read cache from the meta store and follow the
            # writer through notifications
            self._attach_serving()
        elif data_dir is not None:
            self._recover()
        if meta_addr is not None:
            self._attach_meta_observers()

    def _recover(self) -> None:
        """Crash recovery: replay the logged DDL over the recovered store.
        Executors find non-empty state tables and reload device state from
        them; MV-on-MV leaves skip the backfill snapshot (their recovered
        state already reflects the upstream through the committed epoch).
        Source connector offsets are persisted per checkpoint epoch in each
        feed's split-state table; replayed CREATEs seek their readers there
        (_stream_leaf). Reference: orchestrated recovery,
        src/meta/src/barrier/recovery.rs:110."""
        ddl = self.store.log.ddl()  # type: ignore[attr-defined]
        if not ddl:
            return
        # pre-scan for persisted rescale configs: the LAST one per job wins,
        # but a later DROP of the job voids it (a re-CREATE after the drop
        # is a NEW job that ran under the session default); its CREATE below
        # replays under that config so restarts keep their layout
        # (round-4 weak #5)
        resched_cfg: dict[str, object] = {}
        for piece in ddl:
            line = piece.strip()
            if self._fused.parse_marker(line):
                continue
            if not line.startswith("-- reschedule"):
                if (resched_cfg or any(self._fused.markers.values())) \
                        and "drop" in line.lower():
                    try:
                        for stmt in parse_sql(piece):
                            if isinstance(stmt, A.DropStatement):
                                resched_cfg.pop(stmt.name, None)
                                self._fused.forget(stmt.name)
                    except Exception:  # noqa: BLE001 - replay parses below
                        pass
                continue
            rest = line[len("-- reschedule"):].strip()
            mv_name, _, cfg_json = rest.partition(" ")
            if not cfg_json:
                import warnings
                warnings.warn(
                    f"reschedule {mv_name}: legacy log entry without a "
                    "persisted config; the job recovered with the "
                    "session's default BuildConfig")
                continue
            try:
                import os as _os
                from .build import config_from_json
                # RWTPU_ALLOW_MESH_RESHARD=1 is the operator's EXPLICIT
                # consent to shrink a saved mesh to the available devices
                # (state re-shards by vnode replay on load)
                allow = _os.environ.get(
                    "RWTPU_ALLOW_MESH_RESHARD") == "1"
                resched_cfg[mv_name] = config_from_json(
                    cfg_json, allow_reshard=allow)
            except MeshUnavailableError as e:
                # the saved mesh topology needs more devices than this
                # process has. The old behavior degraded SILENTLY to the
                # session default (an 8-shard job quietly reopening
                # unsharded); refuse loudly instead — the operator either
                # restores the device count or re-shards explicitly
                raise RuntimeError(
                    f"reschedule {mv_name}: {e}. Restart with at least "
                    "that many devices (on CPU: XLA_FLAGS="
                    "--xla_force_host_platform_device_count=N), or "
                    "re-shard explicitly onto the available devices by "
                    "reopening with RWTPU_ALLOW_MESH_RESHARD=1"
                ) from e
            except Exception as e:  # noqa: BLE001 - corrupt/unportable cfg
                # a corrupt/truncated log line (JSONDecodeError/KeyError):
                # every job still recovers under the default config
                import warnings
                warnings.warn(
                    f"reschedule {mv_name}: persisted layout not "
                    f"restorable on this process ({e}); recovering with "
                    "the session's default BuildConfig")
        self._recovering = True
        try:
            for piece in ddl:
                if piece.strip().startswith(("-- reschedule",)
                                            + MARKER_PREFIXES):
                    continue
                for stmt in parse_sql(piece):
                    name = getattr(stmt, "name", None)
                    if (isinstance(stmt, A.CreateMaterializedView)
                            and name in resched_cfg):
                        saved = self.config
                        self.config = resched_cfg[name]  # type: ignore[assignment]
                        try:
                            self._run_statement(stmt)
                        finally:
                            self.config = saved
                    else:
                        self._run_statement(stmt)
        finally:
            self._recovering = False

    # -- multi-tenant attachment (docs/control-plane.md) -----------------------

    def _attach_serving(self) -> None:
        """Read-only attachment: the catalog read cache comes from the
        meta store's ``catalog/`` keyspace, the data comes from the
        shared Hummock dir, and both are kept current by notifications
        (no jobs, no ticks, no generation bump — the writer owns those)."""
        self._load_catalog_from_meta()
        self._report_reader_pins()

    def _load_catalog_from_meta(self) -> None:
        """Rebuild the catalog from the persisted summaries the writer's
        ``MetaBackedCatalog`` write-through maintains. Bracketed by the
        seqlock: an optimistic reader racing the swap retries."""
        import json as _json
        from ..common.types import DataType, Field, Schema, TypeKind
        from .catalog import (IndexDef, MaterializedViewDef, SinkDef,
                              SourceDef, TableDef, type_from_name)

        def _typ(name: str) -> DataType:
            try:
                return type_from_name(name)
            except ValueError:
                return DataType(TypeKind(name))

        rows = self.meta.store.list_prefix("catalog/")
        self._enter_mutation()
        try:
            cat = self.catalog
            cat.sources.clear(); cat.tables.clear(); cat.mvs.clear()
            cat.sinks.clear(); cat.indexes.clear()
            max_id = 0
            for _key, raw in rows:
                d = _json.loads(raw)
                kind, name = d["kind"], d["name"]
                tid = int(d.get("table_id", -1))
                max_id = max(max_id, tid)
                pk = tuple(d.get("pk", ()))
                if kind == "index":
                    cat.indexes[name] = IndexDef(
                        name, d.get("table", ""),
                        tuple(d.get("columns", ())),
                        d.get("mv_name", ""))
                    continue
                schema = Schema([Field(n, _typ(t))
                                 for n, t in d.get("columns", [])])
                if kind == "source":
                    cat.sources[name] = SourceDef(
                        name, schema, d.get("connector", ""), {})
                elif kind == "table":
                    cat.tables[name] = TableDef(name, schema, pk, tid)
                elif kind == "materialized_view":
                    cat.mvs[name] = MaterializedViewDef(
                        name, schema, pk, tid, d.get("definition", ""))
                elif kind == "sink":
                    cat.sinks[name] = SinkDef(
                        name, schema, d.get("connector", ""), {},
                        d.get("from_name", ""), tid)
            cat._next_table_id = max(cat._next_table_id, max_id + 1)
        finally:
            self._serving.invalidate_catalog()
            self._exit_mutation()

    def _attach_meta_observers(self) -> None:
        """Subscribe to the remote meta's push channels. Observers run
        on the MetaClient's subscription thread; every mutation they
        perform is seqlock-bracketed so concurrent lock-free reads
        retry instead of tearing."""
        notif = self.meta.notifications
        notif.subscribe("system_params", self._on_system_params_push)
        notif.subscribe("leader", self._on_leader_push)
        # every remote session hears about a dead leader; only standbys
        # (_on_leader_down checks) actually race the election
        notif.subscribe("leader_down", self._on_leader_down)
        if self.role == "serving":
            notif.subscribe("catalog", self._on_catalog_push)
            notif.subscribe("checkpoint", self._on_checkpoint_push)
        else:
            notif.subscribe("hummock_pins", self._on_pins_push)
            manager = getattr(self.store, "manager", None)
            if manager is not None:
                manager.external_refs = self._external_pin_refs
        self.meta.on_resync(self._on_meta_resync)

    def _on_catalog_push(self, _version: int, _info) -> None:
        try:
            self._load_catalog_from_meta()
        except Exception:
            pass        # next notification (or resync) retries

    def _on_checkpoint_push(self, _version: int, _info) -> None:
        refresh = getattr(self.store, "refresh", None)
        if refresh is None:
            return
        try:
            self._enter_mutation()
            try:
                refresh()
            finally:
                self._exit_mutation()
            self._report_reader_pins()
        except Exception:
            pass        # transient object-store race; next checkpoint retries

    def _on_system_params_push(self, _version: int, info) -> None:
        try:
            self._apply_system_param(info["name"], info["value"])
        except Exception:
            pass

    def _on_leader_push(self, _version: int, info) -> None:
        # only a STRICTLY newer generation fences: the subscription
        # replays the log from the start, so our own (and older
        # writers') acquisition events come past every observer
        generation = info.get("generation")
        if self.role == "writer" and generation is not None \
                and generation > self._generation:
            self._fenced = True

    def _on_pins_push(self, _version: int, info) -> None:
        self._remote_pin_runs = set(info.get("ssts", ()))
        # post-promotion grace ends after ONE notification round-trip:
        # our first checkpoint notify made readers refresh and re-report,
        # and this push is the server's updated union — from here the
        # live pin registry protects everything a reader still holds
        if self._pin_grace_refs \
                and self.store.committed_epoch > self._pin_grace_epoch:
            self._pin_grace_refs = set()

    def _on_meta_resync(self) -> None:
        """The meta process restarted (its notification log reset): the
        durable state survived in its store, so re-read everything we
        track through notifications. Writers re-check the lease but
        never re-acquire — an auto-re-acquire could steal the lease back
        from a legitimately newer writer."""
        try:
            if self.role == "writer":
                from ..meta.client import MetaFenced
                try:
                    self.meta.assert_leader()
                except MetaFenced:
                    self._fenced = True
            else:
                self._load_catalog_from_meta()
                self._on_checkpoint_push(0, None)
        except Exception:
            pass

    def _report_reader_pins(self) -> None:
        """Tell meta which SST runs this reader's current version holds
        so the writer's vacuum spares them (the remote analogue of the
        manager's local pin lease)."""
        runs = getattr(self.store, "version_runs", None)
        report = getattr(self.meta, "report_pins", None)
        if runs is None or report is None:
            return
        try:
            report(runs())
        except Exception:
            pass

    def _check_fenced(self) -> None:
        if self._fenced:
            from ..meta.client import MetaFenced
            raise MetaFenced(
                "this session's writer lease was superseded; barrier "
                "conduction and checkpoint commits are refused")

    # -- leader failover (docs/control-plane.md "Election") --------------------

    def _external_pin_refs(self) -> set:
        """What the vacuum must spare beyond local pins: the live remote
        pin registry, plus — inside the post-promotion grace window —
        every run the version adopted at promotion referenced (a reader
        that reconnected mid-failover may hold pins the registry forgot
        until it re-reports)."""
        refs = set(self._remote_pin_runs)
        if self._pin_grace_refs:
            import time as _t
            if _t.monotonic() < self._pin_grace_deadline:
                refs |= self._pin_grace_refs
            else:
                self._pin_grace_refs = set()
        return refs

    def _on_lease_lost(self, _exc) -> None:
        """Heartbeat thread: a renewal came back LeaseLost — another
        session holds a newer term. Flag only; the next conduction
        attempt raises MetaFenced and the tick path demotes us."""
        self._fenced = True
        self._leadership["lease_lost"] += 1

    def _on_leader_down(self, _version: int, info) -> None:
        """Subscription thread: the server's TTL detector declared the
        leader dead. Standbys race ``lease.acquire`` at down-term + 1 on
        a dedicated thread (promotion takes the session lock and does
        real work — it must never block notification delivery)."""
        if not self._standby or self.role == "writer":
            return
        with self._election_lock:
            if self._election_busy:
                return
            self._election_busy = True
        down_term = int(info.get("term", info.get("generation", 0)) or 0)
        threading.Thread(target=self._run_election, args=(down_term,),
                         name="leader-election", daemon=True).start()

    def _run_election(self, down_term: int) -> None:
        """One election round. Every candidate computes the SAME target
        term — down-term + 1, taken from the ``leader_down`` payload the
        server pushed once per expiry — so the server CAS admits exactly
        one; losers take the typed LeaseLost and stay serving. The term
        must NOT be re-derived from the store here: a late candidate
        reading ``session_generation`` after the winner bumped it would
        compute term + 2, be admitted as "strictly newer", and take the
        leadership right back — a split brain by term escalation. The
        winner starts heartbeating BEFORE the (possibly long) promotion
        so the lease cannot expire under it."""
        from ..meta.client import LeaseLost, MetaUnavailable
        import hashlib as _hl
        import time as _t
        try:
            if self._election_backoff_s > 0:
                # deterministic per-session jitter spreads the CAS storm
                h = int(_hl.sha256(
                    self.meta.session_id.encode()).hexdigest(), 16)
                _t.sleep((h % 1000) / 1000.0 * self._election_backoff_s)
            t0 = _t.monotonic()
            term = int(down_term) + 1
            try:
                self.meta.acquire_leader(term, reason="election")
            except (LeaseLost, MetaUnavailable):
                self._leadership["elections_lost"] += 1
                return
            self.meta.start_heartbeat(self._lease_heartbeat_s,
                                      on_lost=self._on_lease_lost)
            try:
                self.promote(term)
            except Exception:
                # a wedged half-promotion must not hold the lease: stop
                # renewing so the TTL frees it for the next candidate
                self.meta.stop_heartbeat()
                raise
            self._leadership["last_failover_ms"] = round(
                (_t.monotonic() - t0) * 1e3, 3)
        except Exception:  # noqa: BLE001 - election must not kill the relay
            pass
        finally:
            with self._election_lock:
                self._election_busy = False

    @_locked
    def promote(self, term: int) -> None:
        """In-place standby → writer takeover under ``term``: adopt the
        committed Hummock cut read-write, rebuild every streaming job by
        replaying the DDL log (the same ``_recover`` path a restarted
        writer takes — jobs land on their last committed checkpoint and
        source readers seek persisted offsets, so the takeover is
        exactly-once), then resume barrier conduction. The caller must
        already hold the lease at ``term``."""
        if self.role == "writer":
            return
        self._enter_mutation()
        try:
            self._fenced = False
            self._generation = int(term)
            self.meta.store.put("session_generation",
                                str(self._generation))
            for w in self.workers:
                w.generation = self._generation
            # adopt the committed cut (the version manifest carries the
            # DDL log, so refresh() brings that too)
            refresh = getattr(self.store, "refresh", None)
            if refresh is not None:
                refresh()
            # vacuum grace: spare every run the adopted version
            # references until readers re-report under this writer
            import time as _t
            runs = getattr(self.store, "version_runs", None)
            self._pin_grace_refs = (set(runs()) if runs is not None
                                    else set())
            self._pin_grace_deadline = (_t.monotonic()
                                        + max(self._lease_ttl_s, 1.0))
            self._pin_grace_epoch = self.store.committed_epoch
            try:
                self._remote_pin_runs = set(self.meta.pins_union())
            except Exception:
                pass
            # observer rewiring: a writer must not chase its own
            # commits through catalog/checkpoint pushes
            notif = self.meta.notifications
            notif.unsubscribe("catalog", self._on_catalog_push)
            notif.unsubscribe("checkpoint", self._on_checkpoint_push)
            notif.subscribe("hummock_pins", self._on_pins_push,
                            from_version=notif.current_version)
            manager = getattr(self.store, "manager", None)
            if manager is not None:
                manager.external_refs = self._external_pin_refs
            # rebuild jobs from the DDL log exactly like a restarted
            # writer: from an EMPTY catalog (replayed CREATEs write
            # through to meta idempotently)
            cat = self.catalog
            cat.sources.clear(); cat.tables.clear(); cat.mvs.clear()
            cat.sinks.clear(); cat.indexes.clear()
            cat._next_table_id = 1
            self.role = "writer"
            self.epoch = max(1, self.store.committed_epoch)
            self._injected = self.epoch
            self._inflight.clear()
            self._inject_time.clear()
            self._pending_mutation = None
            if self.data_dir is not None:
                self._recover()
            # the writer owns storage maintenance now (serving sessions
            # opened with compaction routed away)
            if getattr(self.store, "inline_compaction", None) is False \
                    and not self.compactors:
                self.store.inline_compaction = True
            self.meta.advance_epoch_clock(self.epoch)
            self._leadership["promotions"] += 1
        finally:
            self._serving.invalidate_catalog()
            self._exit_mutation()

    def _demote_to_serving(self) -> None:
        """A fenced ex-writer (partitioned, not dead — a successor holds
        a newer term) converts itself into a WORKING serving session
        instead of crashing: stop conducting, discard uncommitted
        in-flight epochs (the successor's recovery replays them from
        committed offsets exactly once), drop the jobs, and follow the
        new writer through notifications like any other reader."""
        self.meta.stop_heartbeat()
        self._inflight.clear()
        self._inject_time.clear()
        self._pending_mutation = None
        for job in list(self.jobs.values()):
            sink = getattr(job.pipeline, "sink", None)
            if sink is not None:
                try:
                    sink.close()
                except Exception:  # noqa: BLE001 - already dying
                    pass
        jobs = list(self.jobs.values())
        if jobs:
            async def _stop_all():
                await asyncio.gather(*(j.stop() for j in jobs),
                                     return_exceptions=True)
                for _ in range(3):
                    await asyncio.sleep(0)
            try:
                self._await(_stop_all())
            except Exception:  # noqa: BLE001 - best-effort teardown
                pass
        self.jobs.clear()
        self.feeds.clear()
        self.backfills.clear()
        self._table_queues.clear()
        self._fused.reset()
        self._dead_jobs.clear()
        self._jobs_to_recover.clear()
        # discard staged-but-uncommitted state: fully discarded is the
        # demotion half of "committed exactly once or fully discarded"
        pending = getattr(self.store, "_pending", None)
        if pending is not None:
            pending.clear()
        if getattr(self.store, "inline_compaction", None) is True:
            self.store.inline_compaction = False
        self.role = "serving"
        self._fenced = False   # the serving read path is healthy
        self._leadership["demotions"] += 1
        notif = self.meta.notifications
        notif.subscribe("catalog", self._on_catalog_push,
                        from_version=notif.current_version)
        notif.subscribe("checkpoint", self._on_checkpoint_push,
                        from_version=notif.current_version)
        try:
            self._load_catalog_from_meta()
        except Exception:  # noqa: BLE001 - next push retries
            pass
        self._on_checkpoint_push(0, None)

    def _maybe_demote(self, exc: BaseException) -> None:
        """Conduction raised: if it was the fencing signal on a remote
        control plane, demote in place (swallowing demotion errors — the
        caller re-raises the original MetaFenced either way)."""
        if (type(exc).__name__ == "MetaFenced" and self._fenced
                and self.role == "writer" and self.meta_addr is not None):
            try:
                self._demote_to_serving()
            except Exception:  # noqa: BLE001 - keep the fencing signal
                pass

    # ------------------------------------------------------------------ SQL --

    @_locked
    def run_sql(self, sql: str) -> list:
        """Execute statements; returns the last statement's result rows."""
        out: list = []
        for piece in _split_sql(sql):
            for stmt in parse_sql(piece):
                out = self._run_statement(stmt)
                if (self.data_dir is not None and not self._recovering
                        and isinstance(stmt, (
                            A.CreateSource, A.CreateTable,
                            A.CreateMaterializedView, A.CreateSink,
                            A.CreateIndex, A.DropStatement))):
                    self.store.log.log_ddl(piece)  # type: ignore[attr-defined]
        return out

    def _run_statement(self, stmt: A.Statement) -> list:
        if self.role == "serving" and isinstance(stmt, (
                A.CreateSource, A.CreateTable, A.CreateMaterializedView,
                A.CreateSink, A.CreateIndex, A.DropStatement, A.Insert,
                A.Delete, A.Update, A.FlushStatement)):
            raise SqlError(
                "serving sessions are read-only: run DDL/DML on the "
                "writer session (docs/control-plane.md)")
        if isinstance(stmt, (A.CreateSource, A.CreateTable,
                             A.CreateMaterializedView, A.CreateSink,
                             A.CreateIndex)):
            # transactional table-id allocation: a failed CREATE must not
            # shift later statements' ids (recovery replays only logged —
            # successful — DDL, so id assignment must be replay-deterministic)
            saved_id = self.catalog._next_table_id
            # DDL is a data mutation for the seqlock too: a CREATE/DROP
            # rearranges store tables mid-statement, and a lock-free
            # optimistic reader racing it must see the version move and
            # retry instead of accepting a torn scan
            self._enter_mutation()
            try:
                if isinstance(stmt, A.CreateSource):
                    return self._create_source(stmt)
                if isinstance(stmt, A.CreateTable):
                    return self._create_table(stmt)
                if isinstance(stmt, A.CreateSink):
                    return self._create_sink(stmt)
                if isinstance(stmt, A.CreateIndex):
                    return self._create_index(stmt)
                return self._create_mv(stmt)
            except BaseException:
                self.catalog._next_table_id = saved_id
                raise
            finally:
                # cached serving plans may reference the (attempted)
                # relations — clear on every catalog transition, BEFORE
                # the version goes even again so no reader can re-cache
                # against the old catalog
                self._serving.invalidate_catalog()
                self._exit_mutation()
        if isinstance(stmt, A.DropStatement):
            self._enter_mutation()
            try:
                return self._drop(stmt)
            finally:
                self._serving.invalidate_catalog()
                self._exit_mutation()
        if isinstance(stmt, A.Insert):
            return self._insert(stmt)
        if isinstance(stmt, A.Delete):
            return self._delete_dml(stmt)
        if isinstance(stmt, A.Update):
            return self._update_dml(stmt)
        if isinstance(stmt, A.Query):
            return self.query(stmt.select)
        if isinstance(stmt, A.ShowStatement):
            if stmt.what == "parameters":
                return self.parameters()
            reg = {"tables": self.catalog.tables,
                   "sources": self.catalog.sources,
                   "sinks": self.catalog.sinks,
                   "indexes": self.catalog.indexes,
                   "materialized_views": self.catalog.mvs}.get(stmt.what)
            if reg is None:
                raise SqlError(f"cannot SHOW {stmt.what}")
            return [(name,) for name in sorted(reg)
                    if not name.startswith("__idx_")]
        if isinstance(stmt, A.Explain):
            return self._explain(stmt)
        if isinstance(stmt, A.FlushStatement):
            self.flush()
            return []
        if isinstance(stmt, A.SetStatement):
            return self._set_param(stmt)
        raise SqlError(f"unsupported statement {type(stmt).__name__}")

    def _set_param(self, stmt: A.SetStatement) -> list:
        """Runtime-mutable system params (reference:
        src/common/src/system_param/mod.rs — hot-propagated). ``SET``
        applies to this session; ``ALTER SYSTEM SET`` additionally
        publishes a ``system_params`` notification through meta so every
        attached session (writer and readers alike) applies it live."""
        from ..common.config import MUTABLE_SYSTEM_PARAMS
        name = stmt.name.lower()
        coerce = MUTABLE_SYSTEM_PARAMS.get(name)
        if coerce is None:
            raise SqlError(f"unknown or immutable parameter {stmt.name!r}")
        value = coerce(stmt.value)
        self._apply_system_param(name, value)
        if getattr(stmt, "system", False):
            self.meta.notifications.notify(
                "system_params", {"name": name, "value": value})
        return []

    def _apply_system_param(self, name: str, value) -> None:
        """Assign one mutable param (idempotent: a session's own ALTER
        SYSTEM comes back to it on the notification channel too)."""
        if name == "checkpoint_frequency":
            if value < 1:
                raise SqlError("checkpoint_frequency must be >= 1")
            self.checkpoint_frequency = value
        elif name == "in_flight_barrier_nums":
            self.in_flight_barriers = max(1, value)
        elif name == "barrier_interval_ms":
            self.barrier_interval_ms = value   # read live by the CLI ticker
        elif name == "slow_epoch_threshold_ms":
            self.slow_epoch_threshold_ms = max(0.0, value)

    def parameters(self) -> list:
        """SHOW PARAMETERS rows (name, value)."""
        return [
            ("barrier_interval_ms", str(self.barrier_interval_ms)),
            ("checkpoint_frequency", str(self.checkpoint_frequency)),
            ("in_flight_barrier_nums", str(self.in_flight_barriers)),
            ("slow_epoch_threshold_ms", str(self.slow_epoch_threshold_ms)),
        ]

    # ----------------------------------------------------------------- DDL --

    def _create_source(self, stmt: A.CreateSource) -> list:
        if stmt.if_not_exists and stmt.name in self.catalog.sources:
            return []
        connector = str(stmt.with_options.get("connector", ""))
        fmt = str(stmt.with_options.get("format", "")).lower()
        if fmt in ("debezium", "debezium_json"):
            # fail at DDL time, not first-MV-build time (same gate as
            # _connector_reader — see the rationale there)
            raise SqlError(_DEBEZIUM_NEEDS_PK)
        if connector == "nexmark":
            table = str(stmt.with_options.get("nexmark_table",
                                              stmt.with_options.get("table", "bid")))
            schema = {"bid": BID_SCHEMA, "auction": AUCTION_SCHEMA,
                      "person": PERSON_SCHEMA}[table.lower()]
            if stmt.columns:
                declared = {c.name for c in stmt.columns}
                missing = declared - set(schema.names)
                if missing:
                    raise SqlError(f"columns {missing} not in nexmark {table}")
        elif stmt.columns:
            schema = Schema(tuple(
                Field(c.name, type_from_name(c.type_name))
                for c in stmt.columns))
        else:
            raise SqlError("CREATE SOURCE requires columns or a known connector")
        watermark = None
        if stmt.watermark is not None:
            watermark = self._bind_watermark(stmt.watermark, schema)
        self.catalog_writer.add_source(SourceDef(
            stmt.name, schema, connector, dict(stmt.with_options),
            watermark=watermark))
        return []

    def _bind_watermark(self, wm_ast, schema: Schema):
        col_name, expr = wm_ast
        try:
            idx = list(schema.names).index(col_name)
        except ValueError:
            raise SqlError(f"watermark column {col_name!r} not found")
        # supported shape: col - INTERVAL 'x'
        if (isinstance(expr, A.BinaryOp) and expr.op == "-"
                and isinstance(expr.left, A.ColumnRef)
                and expr.left.name == col_name
                and isinstance(expr.right, A.Lit)):
            return (idx, int(expr.right.value))
        raise SqlError("watermark must be '<col> - INTERVAL ...'")

    def _create_table(self, stmt: A.CreateTable) -> list:
        if stmt.if_not_exists and stmt.name in self.catalog.tables:
            return []
        self._drain_inflight()   # job wiring happens at a quiesced boundary
        self.catalog._check_free(stmt.name)   # fail BEFORE allocating ids
        fields = tuple(Field(c.name, type_from_name(c.type_name))
                       for c in stmt.columns)
        schema = Schema(fields)
        names = list(schema.names)
        if stmt.pk:
            pk = tuple(names.index(c) for c in stmt.pk)
        else:
            # hidden _row_id pk (reference: tables without pk get one)
            from ..common.types import SERIAL
            schema = Schema(fields + (Field("_row_id", SERIAL),))
            pk = (len(fields),)
        t = TableDef(stmt.name, schema, pk,
                     table_id=self.catalog.next_table_id(),
                     append_only=stmt.append_only)
        self.catalog_writer.add_table(t)
        # the table IS a stream job: DML queue -> (row id gen) -> materialize
        q = QueueSource(Schema(fields))
        src: Executor = q
        if not stmt.pk:
            start_seq = 0
            if self._recovering:
                # continue above the recovered max row id (its serial
                # number, under whatever shard prefix it had)
                recovered = StateTable(self.store, t.table_id, schema, list(pk))
                seqs = [RowIdSequence.seq_of(r[len(fields)])
                        for r in recovered.scan_all()]
                start_seq = max(seqs) + 1 if seqs else 0
            src = RowIdGenExecutor(
                q, schema, RowIdSequence(self._alloc_shard(), start_seq))
        mat = MaterializeExecutor(
            src, StateTable(self.store, t.table_id, schema, list(pk)))
        job = StreamJob(stmt.name, mat, [q])
        self.jobs[stmt.name] = job
        from ..stream.dml import TableDmlHandle
        self.dml.register(t.table_id, TableDmlHandle(q.push))
        self._table_queues.setdefault(stmt.name, []).append(q)
        job.start(self.loop)
        q.push(Barrier.new(self.epoch))
        self._await(job.wait_barrier(self.epoch))
        return []

    def _plan(self, query: A.Select, lenient: bool = False):
        """Plan + optimize one SELECT (the full frontend pipeline:
        parse → bind → plan → rule-engine passes)."""
        from .optimizer import optimize
        plan = Planner(self.catalog, lenient=lenient,
                       session=self).plan_select(query)
        return optimize(plan)

    def _explain(self, stmt: "A.Explain") -> list:
        """EXPLAIN: optimized plan as one row per line (reference:
        handler/explain.rs renders the same way)."""
        inner = stmt.stmt
        if isinstance(inner, A.Query):
            sel = inner.select
        elif isinstance(inner, (A.CreateMaterializedView, A.CreateSink)):
            sel = inner.query
            if sel is None:
                raise SqlError("EXPLAIN CREATE SINK requires AS SELECT")
        else:
            raise SqlError(
                f"cannot EXPLAIN {type(inner).__name__}")
        plan = self._plan(sel)
        from ..common.types import VARCHAR
        self.last_select_schema = [("QUERY PLAN", VARCHAR)]
        return [(line,) for line in plan.explain().split("\n")]

    def _build_query_pipeline(self, query: A.Select, plan=None):
        """Shared CREATE MV / CREATE SINK AS SELECT plumbing: plan, build
        executors via the stream-leaf factory, collect session-driven
        queues + their init feeds and (under recovery) the scan leaves
        whose backfill may need re-running. ``plan`` reuses a plan the
        caller already built (the coschedule match) instead of planning
        the same query twice."""
        if plan is None:
            plan = self._plan(query, lenient=self._recovering)
        queues: list[QueueSource] = []
        init_msgs: list[tuple[QueueSource, list[Message]]] = []
        scan_leaf_queues: list[tuple[list, StreamJob]] = []

        def factory(leaf) -> Executor:
            # scan leaves backfill concurrently through their progress
            # tables (stream/backfill.py) — no init-snapshot replay here;
            # scan_leaf_queues remains only for CREATE SINK FROM <mv>,
            # which subscribes outside this factory
            ex, q, init = self._stream_leaf(leaf)
            if q is not None:
                queues.append(q)
                init_msgs.append((q, init))
            return ex

        ctx = BuildContext(self.store, self.catalog.next_table_id, factory,
                           self.config, durable=True)
        pipeline = build_plan(plan, ctx)
        return plan, pipeline, ctx, queues, init_msgs, scan_leaf_queues

    def _maybe_rebackfill(self, state_tids, scan_leaf_queues) -> None:
        """Recovery: the DDL log records a CREATE the moment it succeeds,
        but its state first persists at the NEXT checkpoint. If we crashed
        in that window the recovered state is empty — re-run the backfill
        snapshot from the recovered upstream instead of trusting state
        that never existed."""
        if not self._recovering:
            return
        has_state = any(self.store.table_len(tid) > 0 for tid in state_tids)
        if not has_state:
            for init, up_job in scan_leaf_queues:
                init.extend(up_job.snapshot_messages(
                    Barrier.new(self.epoch), self.source_chunk_capacity))

    def _create_index(self, stmt: A.CreateIndex) -> list:
        """CREATE INDEX = a hidden MV materializing the base relation
        re-keyed by the index columns (reference: an index is a
        StreamMaterialize with order/distribution on the index columns,
        src/frontend/src/handler/create_index.rs). Batch point lookups
        prefix-scan its state table (batch/lower.py)."""
        from .catalog import IndexDef, strip_schema
        if stmt.if_not_exists and stmt.name in self.catalog.indexes:
            return []
        self.catalog._check_free(stmt.name)
        base_name = strip_schema(stmt.table)
        kind, d = self.catalog.resolve_relation(base_name)
        if kind == "source":
            raise SqlError("cannot index a source; index a table or MV")
        n_vis = getattr(d, "n_visible", len(d.schema))
        visible = [f.name for i, f in enumerate(d.schema) if i < n_vis]
        for c in stmt.columns:
            if c not in visible:
                raise SqlError(f"column {c!r} not found in {base_name!r}")
        for i in d.pk:
            if d.schema[i].name not in visible:
                raise SqlError(
                    f"cannot index {base_name!r}: its stream key has "
                    "hidden columns")
        rest = [c for c in visible if c not in stmt.columns]
        mv_name = f"__idx_{stmt.name}"
        sel = parse_sql(
            f"SELECT {', '.join(list(stmt.columns) + rest)} "
            f"FROM {base_name}")[0].select
        self._create_mv(
            A.CreateMaterializedView(mv_name, sel),
            pk_prefix=len(stmt.columns))
        self.catalog_writer.add_index(
            IndexDef(stmt.name, base_name, tuple(stmt.columns),
                     mv_name=mv_name))
        return []

    def _create_mv(self, stmt: A.CreateMaterializedView,
                   pk_prefix: int = 0) -> list:
        if stmt.if_not_exists and stmt.name in self.catalog.mvs:
            return []
        self._drain_inflight()   # subscribe at a quiesced epoch boundary
        self.catalog._check_free(stmt.name)   # fail BEFORE building executors
        if self.workers and not pk_prefix \
                and not _ast_uses_udf(stmt.query):
            # index arrangements always build session-local (they scan
            # session-owned base state); worker placement is for plain MVs.
            # UDF-projecting plans also stay LOCAL: registered UDFs live
            # behind THIS process's client plane (udf/client.py) — a
            # worker process has no registration to resolve the name
            # against, so shipping the plan would fail at build time
            # (ISSUE 15; per-worker UDF planes are future work).
            # With ≥2 workers, source-fed plans deploy as CROSS-WORKER
            # fragment graphs (vnode-mapped placement, remote exchange);
            # unsupported shapes fall back to whole-job placement.
            from ..meta.fragment import SpanUnsupported
            # a replayed MV with a persisted placement MUST re-deploy as
            # the same spanning graph: falling through to whole-job
            # placement would resume fresh=False over per-worker stores
            # laid out for FRAGMENTS — refuse loudly instead of decoding
            # another layout's tables
            was_spanning = (self._recovering
                            and self.meta.load_placement(stmt.name)
                            is not None)
            if len(self.workers) >= 2:
                try:
                    return self._create_mv_spanning(stmt)
                except SpanUnsupported as e:
                    if was_spanning:
                        raise SqlError(
                            f"MV {stmt.name!r} was deployed as a "
                            f"spanning fragment graph but cannot be "
                            f"re-deployed ({e}); restart with the same "
                            "multi-worker topology (or DROP and "
                            "re-CREATE it)") from e
            elif was_spanning:
                raise SqlError(
                    f"MV {stmt.name!r} was deployed as a spanning "
                    "fragment graph; restart with the same multi-worker "
                    "topology (or DROP and re-CREATE it)")
            return self._create_mv_remote(stmt)
        id0 = self.catalog._next_table_id   # for reschedule id replay
        # fused jobs (stream/fused_jobs.py): an eligible source+agg MV
        # runs inside a scheduler's group dispatch; ineligible shapes
        # fall through to the executors below, reusing the plan. A
        # recovered MV replays down the path that wrote it, or refuses.
        fused, fused_plan = self._fused.route(
            stmt, self.config, self._recovering, pk_prefix)
        if fused is not None:
            self.feeds.append(_SourceFeed(
                fused.queue, lambda: None, reader=fused.cursor,
                state_table=fused.split_state, job=stmt.name))
            return self._launch_mv(
                stmt, fused_plan, list(fused_plan.pk), fused.materialize,
                (fused.agg.state_table.table_id,), id0, [fused.queue],
                init_msgs=[(fused.queue, [])])
        n_feeds0 = len(self.feeds)
        n_bf0 = len(self.backfills)
        (plan, pipeline, ctx, queues, init_msgs,
         scan_leaf_queues) = self._build_query_pipeline(
            stmt.query, plan=fused_plan)
        mv_table_id = self.catalog.next_table_id()
        mv_pk = list(plan.pk)
        if pk_prefix:
            # index arrangement: key by the index columns first, base pk
            # after (dedup keeps key order); prefix scans by index value
            # ride the sorted key encoding
            mv_pk = list(range(pk_prefix)) + [
                i for i in plan.pk if i >= pk_prefix]
        mat = MaterializeExecutor(
            pipeline,
            StateTable(self.store, mv_table_id, plan.schema, mv_pk))
        # (no _maybe_rebackfill here: scan leaves re-run their own backfill
        # from the persisted cursor — created-but-never-checkpointed
        # recovery is the empty-progress case of stream/backfill.py)
        for f in self.feeds[n_feeds0:]:
            f.job = stmt.name
        for b in self.backfills[n_bf0:]:
            b.job = stmt.name
        return self._launch_mv(stmt, plan, mv_pk, mat, ctx.state_table_ids,
                               id0, queues, ctx.actors, init_msgs)

    def _launch_mv(self, stmt: A.CreateMaterializedView, plan, mv_pk: list,
                   mat: MaterializeExecutor, state_table_ids, id0: int,
                   queues: list, actors: Sequence = (),
                   init_msgs: Sequence = ()) -> list:
        """Enter a built MV into the catalog, start its job and join it
        to the barrier stream at the current epoch."""
        mv = MaterializedViewDef(
            stmt.name, plan.schema, tuple(mv_pk), table_id=mat.table.table_id,
            definition="")
        mv.n_visible = sum(  # type: ignore[attr-defined]
            1 for f in plan.schema if not f.name.startswith("_"))
        mv.state_table_ids = tuple(state_table_ids)  # type: ignore[attr-defined]
        # reschedule metadata: the query AST + the id range the build
        # consumed (allocation order is deterministic, so a rebuild can
        # replay the same ids over the same durable state tables)
        mv.query_ast = stmt.query  # type: ignore[attr-defined]
        mv.table_id_range = (id0, self.catalog._next_table_id)  # type: ignore[attr-defined]
        self.catalog_writer.add_mv(mv)
        job = StreamJob(stmt.name, mat, queues, actors=actors)
        self.jobs[stmt.name] = job
        job.start(self.loop)
        # the next barrier announces the new downstream to the graph
        # (reference: Mutation::Add, executor/mod.rs:220-238)
        self._pending_mutation = Mutation(MutationKind.ADD, stmt.name)
        # init cut: every root replays up to the current epoch's barrier
        for q, init in init_msgs:
            for m in init:
                q.push(m)
            q.push(Barrier.new(self.epoch))
        self._await(job.wait_barrier(self.epoch))
        return []

    # ------------------------------------------------------ remote MV jobs --

    def _plan_remote_mv(self, query: A.Select, worker):
        """Plan + classify leaves for a worker-hosted MV: connector
        sources run worker-side; table/MV scans become remote exchange
        channels fed by the session (the upstream jobs are local)."""
        plan = self._plan(query, lenient=self._recovering)
        leaves = collect_leaves(plan)
        defs, channels, ups = [], {}, {}
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, PSource):
                defs.append(leaf.source)
            elif isinstance(leaf, PTableScan):
                defs.append(leaf.table)
                channels[i] = worker.alloc_chan()
                ups[i] = (leaf.table.name, leaf.schema)
            elif isinstance(leaf, PMvScan):
                if self._mv_worker(leaf.mv.name) is not None:
                    raise SqlError(
                        "an MV over a worker-hosted MV is not supported "
                        "yet; chain MVs in-process or via a table")
                defs.append(leaf.mv)
                channels[i] = worker.alloc_chan()
                ups[i] = (leaf.mv.name, leaf.schema)
            else:
                raise SqlError(
                    f"cannot place {type(leaf).__name__} on a worker")
        return plan, defs, channels, ups

    def _create_mv_remote(self, stmt: A.CreateMaterializedView) -> list:
        """CREATE MATERIALIZED VIEW on a worker process (reference: the
        meta DdlController building actors on compute nodes,
        src/meta/src/rpc/ddl_controller.rs + stream_service.rs:46-233)."""
        from .plan_json import defs_to_json, plan_to_json
        from .remote import RemoteJob
        worker = self.workers[self._next_remote % len(self.workers)]
        self._next_remote += 1
        plan, defs, channels, ups = self._plan_remote_mv(stmt.query, worker)
        # id allocation must stay replay-deterministic: a FAILED create
        # must roll the counter back, or every later object shifts ids
        # relative to the DDL replay that skips the failure
        id_rollback = self.catalog._next_table_id
        mv_table_id = self.catalog.next_table_id()
        id_start = self.catalog._next_table_id
        cfg = self.config
        req = {
            "type": "create_job", "name": stmt.name,
            "plan": plan_to_json(plan), "defs": defs_to_json(defs),
            "mv_table_id": mv_table_id, "id_start": id_start,
            "channels": {str(i): c for i, c in channels.items()},
            "config": {
                "chunk_capacity": cfg.chunk_capacity,
                "agg_table_capacity": cfg.agg_table_capacity,
                "join_key_capacity": cfg.join_key_capacity,
                "join_bucket_width": cfg.join_bucket_width,
                "join_row_capacity": cfg.join_row_capacity,
                "topn_table_capacity": cfg.topn_table_capacity,
                "agg_hbm_budget": cfg.agg_hbm_budget,
            },
            "chunks_per_tick": self.chunks_per_tick,
            "chunk_capacity": self.source_chunk_capacity,
            "seed": self.seed,
            # fault knobs travel with the job: worker-hosted broker
            # readers honor the same reconnect budget as local ones
            "fault": dataclasses.asdict(self.fault),
            # session-restart replay of a channel-fed job rebuilds fresh
            # from the upstream snapshot (the changelog between the
            # worker's and the session's last commits is unrecoverable);
            # source-fed jobs resume from worker-durable state + offsets
            "fresh": bool(channels) and self._recovering,
        }
        try:
            resp = self._await(worker.request(req))
        except BaseException:
            self.catalog._next_table_id = id_rollback
            raise
        self.catalog._next_table_id = max(self.catalog._next_table_id,
                                          resp["ids_end"])
        n_visible = sum(1 for f in plan.schema
                        if not f.name.startswith("_"))
        mv = MaterializedViewDef(stmt.name, plan.schema, tuple(plan.pk),
                                 table_id=mv_table_id, definition="")
        mv.n_visible = n_visible  # type: ignore[attr-defined]
        mv.state_table_ids = tuple(resp["state_table_ids"])  # type: ignore[attr-defined]
        mv.query_ast = stmt.query  # type: ignore[attr-defined]
        mv.table_id_range = (id_start, resp["ids_end"])  # type: ignore[attr-defined]
        mv.remote_worker = worker.worker_id  # type: ignore[attr-defined]
        self.catalog_writer.add_mv(mv)
        job = RemoteJob(stmt.name, worker)
        self.jobs[stmt.name] = job
        self._remote_specs[stmt.name] = {
            "worker": worker, "channels": channels, "ups": ups, "req": req}
        self._wire_remote_channels(stmt.name)
        self._pending_mutation = Mutation(MutationKind.ADD, stmt.name)
        self._await(worker.init_barrier(stmt.name, self.epoch))
        return []

    def _wire_remote_channels(self, name: str) -> None:
        """Build the session side of each remote exchange edge: subscribe
        to the upstream bus, ship the backfill snapshot, start the
        permit-metered forwarder (reference: exchange_service.rs:74-133 +
        backfill snapshot-then-deltas)."""
        spec = self._remote_specs[name]
        worker = spec["worker"]
        job = self.jobs[name]
        for i, chan in spec["channels"].items():
            up_name, leaf_schema = spec["ups"][i]
            up_job = self.jobs[up_name]
            snap = up_job.snapshot_messages(Barrier.new(self.epoch),
                                            self.source_chunk_capacity)
            q = QueueSource(leaf_schema)
            up_job.bus.subscribe(q)
            job.sources.append(q)

            async def _ship(snap=snap, chan=chan, schema=leaf_schema):
                for m in snap:
                    await worker.send_data(chan, m, schema)

            self._await(_ship())
            worker.start_forwarder(name, q, chan, leaf_schema)

    def _recover_remote_job(self, name: str) -> list[str]:
        """Scoped recovery of a worker-hosted job across the process
        boundary: respawn the worker if its process died, re-create the
        job (fresh-from-snapshot for channel-fed, durable-resume for
        source-fed), re-wire exchange edges (reference: recovery.rs:110
        rebuilding actors on a replacement worker)."""
        self._drain_inflight()
        self._bump_generation()
        spec = self._remote_specs[name]
        worker = spec["worker"]
        job = self.jobs.pop(name, None)
        if job is not None:
            self._await(job.stop())
            self._unsubscribe_job(job)
            self.meta.deregister_job(name)
            self._dead_jobs.discard(name)
        if worker.dead:
            worker.respawn(self._await)
            # the replacement process numbers its span batches from 0 —
            # a stale ack could match the fresh counter and make the
            # worker discard a never-delivered span outbox
            self._worker_span_ack.pop(worker.worker_id, None)
        from .remote import RemoteJob
        req = dict(spec["req"])
        if spec["channels"]:
            # fresh rebuild from the upstream's CURRENT state: the deltas
            # the dead worker consumed past its last commit are gone with
            # its bus subscription, so resuming from worker state would
            # fork history — snapshot-rebuild is the consistent cut
            req["fresh"] = True
            new_channels = {i: worker.alloc_chan()
                            for i in spec["channels"]}
            spec["channels"] = new_channels
            req["channels"] = {str(i): c for i, c in new_channels.items()}
        else:
            req["fresh"] = False
        spec["req"] = req
        self._await(worker.request(req))
        self.jobs[name] = RemoteJob(name, worker)
        self._wire_remote_channels(name)
        self._await(worker.init_barrier(name, self.epoch))
        self.meta.notifications.notify(
            "recovery", {"jobs": [name], "epoch": self.epoch})
        return [name]

    # ------------------------------------------ spanning fragment-graph jobs --

    def _create_mv_spanning(self, stmt: A.CreateMaterializedView) -> list:
        """CREATE MATERIALIZED VIEW as a fragment graph SPANNING worker
        processes: the meta scheduler places fragments by vnode mapping,
        each worker builds only its fragments, and the edges between them
        cross the wire protocol with permit-based credit (reference: the
        DdlController + scheduler splitting one streaming job's fragment
        graph over compute nodes, src/meta/src/stream/stream_graph/ +
        scale.rs vnode mappings)."""
        from ..meta.fragment import (
            FragmentScheduler, SpanUnsupported, span_plan,
        )
        from .plan_json import defs_to_json
        from .remote import SpanningJob
        plan = self._plan(stmt.query, lenient=self._recovering)
        graph = span_plan(plan)              # raises SpanUnsupported
        # placement targets come from the meta compute-node registry,
        # reconciled with the live process handles (reference: the
        # scheduler reads the ClusterManager's worker set)
        for w in self.workers:
            self.meta.cluster.set_compute_state(
                w.worker_id, "DOWN" if w.dead else "RUNNING")
        worker_ids = [n.worker_id
                      for n in self.meta.cluster.live_compute_nodes()]
        if len(worker_ids) < 2:
            raise SpanUnsupported("fewer than two live workers")
        placement = None
        fresh = not self._recovering
        if self._recovering:
            # a restarted session MUST re-place fragments where their
            # per-worker durable state lives: the persisted mapping wins
            prev = self.meta.load_placement(stmt.name)
            if prev is not None:
                if set(prev.actors) == set(graph.fragments) \
                        and set(prev.workers()) <= set(worker_ids):
                    placement = prev
                else:
                    # re-placing over stale per-worker stores with
                    # fresh=False would reload other shards' state —
                    # refuse loudly instead of corrupting silently
                    raise RuntimeError(
                        f"spanning MV {stmt.name!r} was deployed on "
                        f"workers {prev.workers()} "
                        f"({len(prev.actors)} fragments) but this "
                        f"session has workers {worker_ids}; restart "
                        "with the same --workers topology (or DROP and "
                        "re-CREATE the MV)")
            else:
                # no persisted placement (pre-spanning data dir or a
                # wiped meta store): rebuild from scratch — wiping is
                # consistent, resuming over unknown layouts is not
                fresh = True
        if placement is None:
            placement = FragmentScheduler().place(
                stmt.name, graph, worker_ids,
                parallelism=self.config.fragment_parallelism)
        defs, seen = [], set()
        for frag in graph.fragments.values():
            for leaf in collect_leaves(frag.plan):
                if isinstance(leaf, PSource) \
                        and leaf.source.name not in seen:
                    seen.add(leaf.source.name)
                    defs.append(leaf.source)
        id_rollback = self.catalog._next_table_id
        mv_table_id = self.catalog.next_table_id()
        id_start = self.catalog._next_table_id
        id_end = id_start + len(graph.fragments) * _SPAN_ID_STRIDE
        self.catalog._next_table_id = id_end
        by_id = {w.worker_id: w for w in self.workers}
        involved = [by_id[wid] for wid in placement.workers()]
        spec = {"graph": graph, "placement": placement,
                "workers": involved,
                "root_worker": by_id[placement.root_worker],
                "mv_table_id": mv_table_id, "id_start": id_start,
                "defs": defs_to_json(defs)}
        recover_at = None
        if not fresh:
            # session-restart replay: participants may sit one phase-2
            # frame apart (a worker killed between prepare and commit) —
            # settle every store on the cluster-decided cut first
            recover_at = self._span_decided_epoch(stmt.name, involved)
        reqs = self._span_requests(stmt.name, spec, fresh=fresh,
                                   recover_at=recover_at)
        created, state_table_ids = [], []
        try:
            for w in involved:
                resp = self._await(w.request(reqs[w.worker_id]))
                created.append(w)
                state_table_ids.extend(resp.get("state_table_ids", ()))
        except BaseException:
            # id-replay determinism + no half-deployed graph: roll the
            # counter back and tear down what was already built
            self.catalog._next_table_id = id_rollback
            for w in created:
                try:
                    self._await(w.request(
                        {"type": "drop_job", "name": stmt.name,
                         "epoch": self._injected + 1}))
                except Exception:  # noqa: BLE001 - best-effort undo
                    pass
            raise
        n_visible = sum(1 for f in plan.schema
                        if not f.name.startswith("_"))
        mv = MaterializedViewDef(stmt.name, plan.schema, tuple(plan.pk),
                                 table_id=mv_table_id, definition="")
        mv.n_visible = n_visible  # type: ignore[attr-defined]
        mv.state_table_ids = tuple(state_table_ids)  # type: ignore[attr-defined]
        mv.query_ast = stmt.query  # type: ignore[attr-defined]
        mv.table_id_range = (id_start, id_end)  # type: ignore[attr-defined]
        mv.span_workers = placement.workers()  # type: ignore[attr-defined]
        self.catalog_writer.add_mv(mv)
        from ..meta.rescale import commit_placement
        commit_placement(self.meta, placement)
        self.jobs[stmt.name] = SpanningJob(stmt.name, involved)
        self._spanning_specs[stmt.name] = spec
        self._pending_mutation = Mutation(MutationKind.ADD, stmt.name)

        async def _init_all() -> None:
            # every participant acks once ITS actors saw the init cut —
            # the barrier reaches non-source fragments over the wire, so
            # the waits must run concurrently
            await asyncio.gather(*(w.init_barrier(stmt.name, self.epoch)
                                   for w in involved))

        self._await(_init_all())
        return []

    def _span_requests(self, name: str, spec: dict, fresh: bool,
                       recover_at: Optional[int] = None,
                       import_refs: Optional[dict] = None) -> dict[int, dict]:
        """Per-worker ``create_fragments`` requests for one spanning job.
        Re-run at recovery with FRESH channel ids and the workers'
        CURRENT ports (a respawned worker listens on a new ephemeral
        port), so edge specs always name live peers. ``import_refs``
        ((fragment, actor) → handoff segment paths) rides a LIVE RESCALE
        deployment: the receiving worker imports those refs' rows before
        building (meta/rescale.py, docs/scaling.md)."""
        from .plan_json import plan_to_json
        graph, placement = spec["graph"], spec["placement"]
        by_id = {w.worker_id: w for w in self.workers}
        consumers: dict[int, int] = {}            # u_fid -> d_fid
        for d_fid, frag in graph.fragments.items():
            for u_fid in frag.upstream:
                consumers[u_fid] = d_fid
        chan_of: dict[tuple, int] = {}
        for u_fid, d_fid in consumers.items():
            for ua in range(len(placement.actors[u_fid])):
                for da in range(len(placement.actors[d_fid])):
                    chan_of[(u_fid, ua, d_fid, da)] = \
                        next(self._next_span_chan)

        def edge(u_fid, ua, d_fid, da) -> str:
            return f"{name}:f{u_fid}.{ua}->f{d_fid}.{da}"

        cfg = self.config
        frag_specs: dict[int, list] = {w.worker_id: []
                                       for w in spec["workers"]}
        for fid in sorted(graph.fragments):
            frag = graph.fragments[fid]
            plan_json = plan_to_json(frag.plan)   # same for every actor
            for ap in placement.actors[fid]:
                inputs = []
                for u_fid in frag.upstream:
                    chans = []
                    for up in placement.actors[u_fid]:
                        chans.append({
                            "chan": chan_of[(u_fid, up.actor, fid,
                                             ap.actor)],
                            "from_worker": up.worker,
                            "edge": edge(u_fid, up.actor, fid, ap.actor),
                        })
                    inputs.append({"up_fid": u_fid, "chans": chans})
                out = None
                if not frag.is_root:
                    d_fid = consumers[fid]
                    downs = placement.actors[d_fid]
                    if len(downs) > 1 and not frag.dist_keys:
                        raise RuntimeError(
                            f"fragment {fid} has {len(downs)} downstream "
                            "actors but no distribution keys")
                    out = {
                        "kind": "hash" if frag.dist_keys else "simple",
                        "keys": list(frag.dist_keys),
                        "targets": [{
                            "chan": chan_of[(fid, ap.actor, d_fid,
                                             dp.actor)],
                            "worker": dp.worker,
                            "host": "127.0.0.1",
                            "port": by_id[dp.worker].port,
                            "edge": edge(fid, ap.actor, d_fid, dp.actor),
                        } for dp in downs],
                    }
                fspec = {
                    "fid": fid, "actor": ap.actor,
                    "plan": plan_json,
                    "id_start": spec["id_start"] + fid * _SPAN_ID_STRIDE,
                    "shard_base": fid * 16,
                    "is_root": frag.is_root,
                    # owned vnode range: stateful executors reload (and
                    # the root MV serves scans for) ONLY this range, so
                    # placement == routing survives live migrations
                    "vnodes": [ap.vnode_start, ap.vnode_end],
                    "inputs": inputs, "output": out,
                }
                if import_refs:
                    refs = import_refs.get((fid, ap.actor))
                    if refs:
                        fspec["import_refs"] = list(refs)
                frag_specs[ap.worker].append(fspec)
        reqs = {}
        for w in spec["workers"]:
            reqs[w.worker_id] = {
                "type": "create_fragments", "name": name,
                "defs": spec["defs"],
                "mv_table_id": spec["mv_table_id"],
                "id_stride": _SPAN_ID_STRIDE,
                "permits": cfg.exchange_permits,
                "config": {
                    "chunk_capacity": cfg.chunk_capacity,
                    "agg_table_capacity": cfg.agg_table_capacity,
                    "join_key_capacity": cfg.join_key_capacity,
                    "join_bucket_width": cfg.join_bucket_width,
                    "join_row_capacity": cfg.join_row_capacity,
                    "topn_table_capacity": cfg.topn_table_capacity,
                    "agg_hbm_budget": cfg.agg_hbm_budget,
                },
                "chunks_per_tick": self.chunks_per_tick,
                "chunk_capacity": self.source_chunk_capacity,
                "seed": self.seed,
                "fault": dataclasses.asdict(self.fault),
                "fresh": fresh,
                "fragments": frag_specs[w.worker_id],
            }
            if recover_at is not None:
                reqs[w.worker_id]["recover_at"] = recover_at
        return reqs

    def _span_decided_epoch(self, name: str, workers) -> int:
        """The cluster-decided checkpoint cut for a spanning job: the MAX
        committed epoch across its participants. A commit frame is only
        sent after EVERY participant durably prepared the epoch, so any
        participant behind the max still holds that epoch prepared and
        rolls forward — all stores settle on one consistent cut
        (phase-2 asymmetry healed; reference: meta-owned atomic Hummock
        versions make this a non-problem in the reference)."""
        committed = []
        for w in workers:
            resp = self._await(w.request({"type": "job_epochs",
                                          "name": name}))
            committed.append(int(resp.get("committed", 0)))
        return max(committed) if committed else 0

    def _recover_spanning_job(self, name: str) -> list[str]:
        """Scoped recovery of a SPANNING job: respawn dead participants,
        drop the surviving fragments WITHOUT touching durable state, and
        re-deploy the same placement — every fragment reloads from its
        own worker's store at the last committed checkpoint and the
        deterministic sources replay the gap (reference: recovery.rs:110
        scoped to one job's actor set; unrelated jobs on the same workers
        keep running untouched)."""
        from .remote import SpanningJob, WorkerDied
        self._drain_inflight()
        # fence the dead incarnation FIRST: frames the rebuilt graph
        # sends carry the new generation, and anything still in flight
        # from the old one (delayed acks, stale commits) is refused
        self._bump_generation()
        spec = self._spanning_specs[name]
        job = self.jobs.pop(name, None)
        if job is not None:
            self._await(job.stop())
            self._unsubscribe_job(job)
            self.meta.deregister_job(name)
            self._dead_jobs.discard(name)
        for w in spec["workers"]:
            if w.dead:
                w.respawn(self._await)
                self._worker_span_ack.pop(w.worker_id, None)
                self.meta.register_compute(w.worker_id, "127.0.0.1",
                                           w.port)
        for w in spec["workers"]:
            try:
                self._await(w.request(
                    {"type": "drop_job", "name": name,
                     "epoch": self._injected + 1, "drop_state": False}))
            except (WorkerDied, RuntimeError):
                pass                 # fresh respawn or wedged: no-op
        decided = self._span_decided_epoch(name, spec["workers"])
        reqs = self._span_requests(name, spec, fresh=False,
                                   recover_at=decided)
        for w in spec["workers"]:
            self._await(w.request(reqs[w.worker_id]))
        self.jobs[name] = SpanningJob(name, spec["workers"])

        async def _init_all() -> None:
            await asyncio.gather(*(w.init_barrier(name, self.epoch)
                                   for w in spec["workers"]))

        self._await(_init_all())
        self.meta.notifications.notify(
            "recovery", {"jobs": [name], "epoch": self.epoch})
        return [name]

    # ------------------------------------- elastic scaling (live rescale) --

    @_locked
    def rescale(self, name: str, parallelism: int) -> dict:
        """Change one MV job's fragment parallelism (docs/scaling.md).

        * **spanning jobs** — LIVE vnode migration: pause the graph at an
          aligned checkpoint barrier, hand off only the vnode ranges
          whose owner changes as state refs (handoff segments on shared
          storage), fence the old incarnation by generation, redeploy
          with rewired exchange edges — no full-session restart, worker
          processes stay up (reference: scale.rs:657).
        * **session-local jobs** — no vnode-mapped placement exists;
          delegates to ``reschedule`` (quiesce + rebuild from durable
          state under the new ``fragment_parallelism``).
        * **whole-job remote placements** — refused loudly
          (``RescaleUnsupported``): a round-robined whole job has no
          fragments to migrate (VERDICT #78 made this failure explicit
          instead of silent).
        """
        from ..meta.rescale import RescaleUnsupported
        if name in self._spanning_specs:
            return self._rescale_spanning(name, parallelism)
        if name in self._remote_specs:
            raise RescaleUnsupported(
                f"MV {name!r} is placed WHOLE-JOB on worker "
                f"{self._remote_specs[name]['worker'].worker_id}: "
                "round-robined whole-job placements carry no vnode-mapped "
                "fragments, so there is nothing to migrate. DROP and "
                "re-CREATE it under a span-capable shape (sourced plan, "
                ">= 2 workers, fragment_parallelism >= 2) to make it "
                "rescalable — see docs/scaling.md")
        if name not in self.catalog.mvs:
            raise SqlError(f"materialized view {name!r} not found "
                           "(only MV jobs rescale)")
        cfg = dataclasses.replace(self.config,
                                  fragment_parallelism=max(1, parallelism))
        self.reschedule(name, config=cfg)
        return {"job": name, "mode": "local-rebuild",
                "parallelism": max(1, parallelism), "moved_vnodes": 0}

    def _rescale_spanning(self, name: str, parallelism: int) -> dict:
        """Diff-based live vnode migration of one spanning job.

        Protocol (every step under the API lock, the session being the
        barrier conductor — "paused" means no barrier can be injected
        while this runs):

        1. **aligned barrier**: drain in-flight epochs + checkpoint
           flush — every fragment's state durably committed at one cut
           ``E`` on its own worker;
        2. **plan**: ``meta.rescale.plan_rescale`` computes the new
           placement (ranges == the ``vnode_to_shard`` routing) and the
           minimal ``VnodeMove`` set;
        3. **fence**: bump the session generation — the pre-rescale
           incarnation can neither ack barriers nor commit;
        4. **hand off**: each moving range's committed rows are exported
           by the (still-live) source actors as handoff segments on
           shared storage; only REFS travel to the destinations;
        5. **pause actors**: stop + drop the job's actors on every old
           worker (``drop_state=False`` — processes stay up, durable
           state stays put);
        6. **redeploy**: ``create_fragments`` under the new placement
           with fresh exchange channels; destinations import their refs
           before building, every actor reloads only its owned range;
        7. **commit**: persist the placement (``commit_placement``) —
           the rollback/roll-forward watershed — then init barriers.

        A failure before step 7 ROLLS BACK (redeploy the old placement
        from the untouched durable cut); after it, failures ROLL FORWARD
        through the ordinary scoped recovery under the new placement.
        """
        import time as _time

        from ..meta.rescale import RescaleUnsupported, plan_rescale
        if self._in_rescale:
            raise RuntimeError("a rescale is already in flight")
        spec = self._spanning_specs[name]
        graph, old_placement = spec["graph"], spec["placement"]
        for w in self.workers:
            self.meta.cluster.set_compute_state(
                w.worker_id, "DOWN" if w.dead else "RUNNING")
        worker_ids = [n.worker_id
                      for n in self.meta.cluster.live_compute_nodes()]
        plan = plan_rescale(name, graph, old_placement, worker_ids,
                            parallelism)
        new_par = max(len(a) for a in plan.new.actors.values())
        if not plan.moves and plan.new.to_json() == old_placement.to_json():
            return {"job": name, "mode": "noop", "parallelism": new_par,
                    "moved_vnodes": 0, "pause_ms": 0.0}
        by_id = {w.worker_id: w for w in self.workers}
        missing = [wid for wid in plan.new.workers() if wid not in by_id]
        if missing:
            raise RescaleUnsupported(
                f"rescale of {name!r} needs workers {missing} which this "
                "session does not run")
        # 1. aligned barrier: quiesce + checkpoint-commit the cut
        t0 = _time.perf_counter()
        self._in_rescale = True
        try:
            return self._rescale_spanning_locked(name, spec, plan,
                                                 old_placement, by_id,
                                                 new_par, t0)
        finally:
            self._in_rescale = False

    def _rescale_spanning_locked(self, name: str, spec: dict, plan,
                                 old_placement, by_id: dict,
                                 new_par: int, t0: float) -> dict:
        import os as _os
        import time as _time

        from ..common.failpoint import fail_point
        from ..meta.rescale import commit_placement
        from .remote import SpanningJob, WorkerDied
        self._drain_inflight()
        self.flush()
        decided = self._span_decided_epoch(name, spec["workers"])
        # 3. fence the pre-rescale incarnation
        self._bump_generation()
        old_workers = list(spec["workers"])
        try:
            # 4. export the moving ranges as state refs on shared storage
            handoff_dir = _os.path.join(self._workers_base, "handoff",
                                        name, f"g{self._generation}")
            import_refs: dict[tuple, list] = {}
            for (src_wid, fid), moves in sorted(
                    plan.moves_by_source().items()):
                resp = self._await(by_id[src_wid].request({
                    "type": "rescale_export", "name": name,
                    "fragment": fid,
                    "ranges": [[m.vnode_start, m.vnode_end]
                               for m in moves],
                    "dir": handoff_dir}))
                for ref, m in zip(resp["refs"], moves):
                    import_refs.setdefault(
                        (fid, m.to_actor), []).append(ref["path"])
            fail_point("rescale.migrate")
            # 5. pause: tear the actors down in place (no process restart)
            job = self.jobs.pop(name, None)
            if job is not None:
                self._await(job.stop())
                self._unsubscribe_job(job)
                self.meta.deregister_job(name)
                self._dead_jobs.discard(name)
            for w in old_workers:
                self._await(w.request(
                    {"type": "drop_job", "name": name,
                     "epoch": self._injected + 1, "drop_state": False}))
            # 6. redeploy under the new placement, refs riding along
            spec["placement"] = plan.new
            spec["workers"] = [by_id[wid] for wid in plan.new.workers()]
            spec["root_worker"] = by_id[plan.new.root_worker]
            reqs = self._span_requests(name, spec, fresh=False,
                                       recover_at=decided,
                                       import_refs=import_refs)
            for w in spec["workers"]:
                self._await(w.request(reqs[w.worker_id]))
        except (WorkerDied, RuntimeError, OSError) as e:
            self._rollback_rescale(name, spec, old_placement, old_workers,
                                   by_id)
            raise RuntimeError(
                f"rescale of {name!r} failed mid-migration; the job was "
                f"rolled back to its previous placement") from e
        # 7. COMMIT: the new placement becomes authoritative — failures
        # from here roll FORWARD via scoped recovery under it
        commit_placement(self.meta, plan.new)
        # cached serving runners are bound to the PRE-rescale host set
        # (remote two-phase tasks name workers + vnode slices): drop
        # them — re-planning against the new placement is the only
        # correct re-execution (frontend/serving.py)
        self._serving.invalidate_catalog()
        mv = self.catalog.mvs.get(name)
        if mv is not None:
            mv.span_workers = plan.new.workers()  # type: ignore[attr-defined]
        self.jobs[name] = SpanningJob(name, spec["workers"])
        self._pending_mutation = Mutation(MutationKind.UPDATE, name)
        fail_point("rescale.commit")

        async def _init_all() -> None:
            await asyncio.gather(*(w.init_barrier(name, self.epoch)
                                   for w in spec["workers"]))

        try:
            self._await(_init_all())
        except (WorkerDied, RuntimeError):
            # committed: the new placement is truth — roll forward
            self._recover_spanning_job(name)
        pause_ms = round((_time.perf_counter() - t0) * 1e3, 3)
        out = {
            "job": name, "mode": "live-migration",
            "parallelism": new_par, "epoch": decided,
            "moved_vnodes": plan.moved_vnodes,
            "moved_ranges": [
                {"fragment": m.fragment_id, "vnodes":
                 [m.vnode_start, m.vnode_end],
                 "from_worker": m.from_worker, "to_worker": m.to_worker}
                for m in plan.moves],
            "workers": plan.new.workers(),
            "pause_ms": pause_ms,
        }
        self._rescale_stats["migrations"] += 1
        self._rescale_stats["moved_vnodes"] += plan.moved_vnodes
        self._rescale_stats["last"] = out
        self._rescale_stats["history"].append(
            {k: out[k] for k in ("job", "parallelism", "moved_vnodes",
                                 "pause_ms", "epoch")})
        del self._rescale_stats["history"][:-16]
        self.meta.notifications.notify(
            "rescale", {"job": name, "parallelism": new_par,
                        "moved_vnodes": plan.moved_vnodes})
        return out

    def _rollback_rescale(self, name: str, spec: dict, old_placement,
                          old_workers: list, by_id: dict) -> None:
        """Migration failed before the placement commit: the OLD
        placement is still authoritative. Drop whatever the attempt
        half-deployed on ANY worker (a new worker's orphan fragments
        would otherwise wedge its barrier collection forever), restore
        the spec, and redeploy the old layout from the untouched durable
        cut via the scoped-recovery machinery. Imported handoff rows a
        destination already committed are benign leftovers: every reload
        and scan filters to the actor's OWNED vnode range."""
        from .remote import WorkerDied
        spec["placement"] = old_placement
        spec["workers"] = old_workers
        spec["root_worker"] = by_id[old_placement.root_worker]
        for w in self.workers:
            if w.dead:
                continue
            try:
                self._await(w.request(
                    {"type": "drop_job", "name": name,
                     "epoch": self._injected + 1, "drop_state": False}))
            except (WorkerDied, RuntimeError):
                pass
        self._serving.invalidate_catalog()
        try:
            self._recover_spanning_job(name)
        except Exception as e2:
            raise RuntimeError(
                f"rescale of {name!r} failed AND the rollback redeploy "
                "failed; durable state is intact — restart the session "
                "to restore the job") from e2

    def _create_sink(self, stmt: A.CreateSink) -> list:
        """CREATE SINK: a stream job whose terminal is a SinkExecutor over
        a log store instead of a MaterializeExecutor (reference:
        src/stream/src/executor/sink.rs:38; log store
        common/log_store/mod.rs:57-168)."""
        if stmt.if_not_exists and stmt.name in self.catalog.sinks:
            return []
        self._drain_inflight()
        self.catalog._check_free(stmt.name)
        from ..connector.sinks import build_sink
        from ..stream.sink import PROGRESS_SCHEMA, SinkExecutor, log_table_schema
        connector = str(stmt.with_options.get("connector", "blackhole"))
        n_feeds0 = len(self.feeds)
        n_bf0 = len(self.backfills)
        scan_leaf_queues: list[tuple[list, StreamJob]] = []
        ctx_tids: tuple = ()
        actors: list = []
        if stmt.from_name is not None:
            kind, obj = self.catalog.resolve_relation(stmt.from_name)
            if kind == "source":
                raise SqlError("CREATE SINK FROM a source is not supported; "
                               "use CREATE SINK ... AS SELECT")
            if self._mv_worker(stmt.from_name) is not None:
                raise SqlError(
                    f"CREATE SINK FROM worker-hosted MV "
                    f"{stmt.from_name!r} is not supported yet")
            up_job = self.jobs[stmt.from_name]
            q = QueueSource(obj.schema)
            up_job.bus.subscribe(q)
            pipeline: Executor = q
            schema = obj.schema
            # visible = non-hidden columns (pk-less tables carry _row_id)
            n_visible = getattr(
                obj, "n_visible",
                sum(1 for f in schema if not f.name.startswith("_")))
            queues = [q]
            init_msgs = [(q, [])]   # snapshot decided after tid allocation
            scan_leaf_queues.append((init_msgs[0][1], up_job))
        else:
            (plan, pipeline, ctx, queues, init_msgs,
             scan_leaf_queues) = self._build_query_pipeline(stmt.query)
            ctx_tids = tuple(ctx.state_table_ids)
            actors = ctx.actors
            schema = plan.schema
            n_visible = sum(1 for f in schema if not f.name.startswith("_"))
        log_tid = self.catalog.next_table_id()
        prog_tid = self.catalog.next_table_id()
        if stmt.from_name is not None and not self._recovering:
            init_msgs[0][1].extend(up_job.snapshot_messages(
                Barrier.new(self.epoch), self.source_chunk_capacity))
        # recovery in the created-but-never-checkpointed window: state
        # tables (incl. the sink's own log/progress) are all empty — re-run
        # the backfill snapshot (same rule as MVs)
        self._maybe_rebackfill(ctx_tids + (log_tid, prog_tid),
                               scan_leaf_queues)
        visible_schema = Schema(tuple(schema)[:n_visible])
        sink = build_sink(connector, dict(stmt.with_options), visible_schema,
                          fault=self.fault)
        # delivery decoupling knobs: per-sink WITH options override the
        # session fault config (reference: sink decouple + retry params)
        opts = stmt.with_options
        ex = SinkExecutor(
            pipeline, sink,
            StateTable(self.store, log_tid, log_table_schema(schema), [0, 1]),
            StateTable(self.store, prog_tid, PROGRESS_SCHEMA, [0]),
            n_visible=n_visible, recovering=self._recovering,
            retry_policy=self.fault.sink_retry_policy(),
            degrade_after=int(opts.get("sink.degrade_after",
                                       self.fault.sink_degrade_after)),
            log_cap_rows=int(opts.get("sink.log_cap_rows",
                                      self.fault.sink_log_cap_rows)))
        sdef = SinkDef(stmt.name, schema, connector, dict(stmt.with_options),
                       from_name=stmt.from_name or "", table_id=log_tid,
                       progress_table_id=prog_tid)
        sdef.state_table_ids = ctx_tids + (prog_tid,)  # type: ignore[attr-defined]
        self.catalog_writer.add_sink(sdef)
        for f in self.feeds[n_feeds0:]:
            f.job = stmt.name
        for b in self.backfills[n_bf0:]:
            b.job = stmt.name
        job = StreamJob(stmt.name, ex, queues, actors=actors)
        self.jobs[stmt.name] = job
        job.start(self.loop)
        self._pending_mutation = Mutation(MutationKind.ADD, stmt.name)
        for q, init in init_msgs:
            for m in init:
                q.push(m)
            q.push(Barrier.new(self.epoch))
        self._await(job.wait_barrier(self.epoch))
        return []

    @_locked
    def reschedule(self, name: str, config: Optional[BuildConfig] = None):
        """Online rescale of one MV job: rebuild its executors under a new
        BuildConfig (typically a different ``mesh``) from durable state at
        a quiesced checkpoint boundary, without losing a row.

        Reference: the scale controller's Reschedule command
        (src/meta/src/stream/scale.rs:657, barrier/command.rs:48-60) —
        actors are rebuilt with new vnode mappings and state re-read from
        shared storage; here the "vnode mapping" is the mesh sharding of
        the rebuilt executors and the shared storage is the state store.
        """
        mv = self.catalog.mvs.get(name)
        if mv is None:
            raise SqlError(f"materialized view {name!r} not found "
                           "(only MV jobs reschedule)")
        if self._mv_worker(name) is not None:
            raise SqlError(
                "reschedule of a worker-hosted MV is not supported; "
                "spanning jobs rescale LIVE via Session.rescale / "
                "`ctl cluster rescale` (docs/scaling.md), whole-job "
                "placements must be dropped and re-created")
        self.flush()                       # all state durable + quiesced
        old_job = self.jobs[name]
        self._await(old_job.stop())
        self._unsubscribe_job(old_job)     # upstreams stop feeding dead queues
        # this job's source feeds are recreated (sought to their offsets)
        live = [f for f in self.feeds if f.job != name]
        self.feeds = live
        self.backfills = [b for b in self.backfills if b.job != name]
        id0, id1 = mv.table_id_range  # type: ignore[attr-defined]
        ids = iter(range(id0, id1))
        saved_alloc = self.catalog.next_table_id
        saved_recovering = self._recovering
        saved_config = self.config

        def replay_id() -> int:
            try:
                return next(ids)
            except StopIteration:
                raise RuntimeError(
                    "reschedule id replay diverged from the original build")

        self.catalog.next_table_id = replay_id  # type: ignore[assignment]
        self._recovering = True      # reload state, seek sources, no snapshot
        if config is not None:
            self.config = config
        n_feeds0 = len(self.feeds)
        n_bf0 = len(self.backfills)
        bus_subs0 = {n: list(j.bus.subscribers)
                     for n, j in self.jobs.items()}
        rollback_error: Optional[BaseException] = None
        try:
            try:
                (plan, pipeline, ctx, queues, init_msgs,
                 _slq) = self._build_query_pipeline(mv.query_ast)  # type: ignore[attr-defined]
                mv_table_id = self.catalog.next_table_id()
            except BaseException as e1:
                # the new config failed to build (incl. interrupts —
                # rollback is fast): roll back to the original config over
                # the same durable state. A stopped job left in self.jobs
                # would hang every later barrier. Undo the failed build's
                # feed/subscription side effects first.
                rollback_error = e1
                self.feeds = self.feeds[:n_feeds0]
                self.backfills = self.backfills[:n_bf0]
                for n, subs in bus_subs0.items():
                    self.jobs[n].bus.subscribers = list(subs)
                self.config = saved_config
                ids = iter(range(id0, id1))
                try:
                    (plan, pipeline, ctx, queues, init_msgs,
                     _slq) = self._build_query_pipeline(mv.query_ast)  # type: ignore[attr-defined]
                    mv_table_id = self.catalog.next_table_id()
                except BaseException as e2:
                    # config-independent failure: even the original config
                    # no longer builds. Deregister the job AND everything
                    # transitively fed by it (barrier-starved otherwise);
                    # durable state + catalog remain — a restart's
                    # recovery replay restores the jobs.
                    self.feeds = self.feeds[:n_feeds0]
                    self.backfills = self.backfills[:n_bf0]
                    for n, subs in bus_subs0.items():
                        self.jobs[n].bus.subscribers = list(subs)
                    self.jobs.pop(name, None)
                    self._pop_downstreams_of(old_job)
                    raise RuntimeError(
                        f"reschedule of {name!r} failed and the rollback "
                        "rebuild failed too; the job (and its downstream "
                        "MVs) are stopped — state is durable, restart the "
                        "session to restore them") from e2
            mat = MaterializeExecutor(
                pipeline,
                StateTable(self.store, mv_table_id, plan.schema,
                           list(plan.pk)))
        finally:
            self.catalog.next_table_id = saved_alloc  # type: ignore[assignment]
            self._recovering = saved_recovering
            self.config = saved_config
        for f in self.feeds[n_feeds0:]:
            f.job = name
        for b in self.backfills[n_bf0:]:
            b.job = name
        job = StreamJob(name, mat, queues, actors=ctx.actors)
        job.bus.subscribers = old_job.bus.subscribers   # downstreams keep
        self.jobs[name] = job
        job.start(self.loop)
        # the next barrier announces the config change (reference:
        # Mutation::Update on the reschedule barrier)
        self._pending_mutation = Mutation(MutationKind.UPDATE, name)
        for q, init in init_msgs:
            for m in init:
                q.push(m)
            q.push(Barrier.new(self.epoch))
        self._await(job.wait_barrier(self.epoch))
        if rollback_error is not None:
            # the job is healthy again under the SESSION DEFAULT config,
            # but the requested reschedule did NOT happen — persist the
            # layout the job actually runs now (an earlier successful
            # rescale's log entry would otherwise resurrect on restart a
            # layout the live session no longer has), then surface it
            if self.data_dir is not None:
                from .build import config_to_json
                self.store.log.log_ddl(  # type: ignore[attr-defined]
                    f"-- reschedule {name} {config_to_json(saved_config)}")
            raise RuntimeError(
                f"reschedule of {name!r} failed; the job was restored "
                "with its original config") from rollback_error
        # persist the rescale only once the rebuild SUCCEEDED: the config's
        # durable form (mesh topology, not live device handles) goes in the
        # DDL log; recovery replays the CREATE under this config so a
        # restart keeps its layout (reference: persisted vnode mappings,
        # stream/scale.rs:657)
        if self.data_dir is not None:
            from .build import config_to_json
            cfg_json = config_to_json(config if config is not None
                                      else saved_config)
            self.store.log.log_ddl(  # type: ignore[attr-defined]
                f"-- reschedule {name} {cfg_json}")

    def _pop_downstreams_of(self, job: StreamJob) -> None:
        """Remove jobs transitively fed by ``job``'s bus (they would wait
        forever for barriers a stopped upstream can never send). Full
        teardown per job: stop the task, unsubscribe its queues from live
        buses, drop its feeds and barrier queues."""
        sub_queues = set(map(id, job.bus.subscribers))
        for n, j in list(self.jobs.items()):
            if any(id(q) in sub_queues for q in j.sources):
                self.jobs.pop(n, None)
                self._teardown_job(n, j)
                self._pop_downstreams_of(j)

    def _teardown_job(self, name: str, j: StreamJob) -> None:
        """Full per-job teardown shared by drop-downstreams and scoped
        recovery: stop the task (and fragment actors), unsubscribe its
        queues from live buses, drop feeds/backfills/barrier queues, close
        its sink, deregister its worker."""
        sink = getattr(j.pipeline, "sink", None)
        if sink is not None:
            sink.close()
        self._await(j.stop())
        self._unsubscribe_job(j)
        self.feeds = [f for f in self.feeds if f.job != name]
        self.backfills = [b for b in self.backfills if b.job != name]
        self._table_queues.pop(name, None)
        self.meta.deregister_job(name)
        self._dead_jobs.discard(name)

    def sink_of(self, name: str):
        """The live Sink instance of a sink job (inspection/testing)."""
        job = self.jobs.get(name)
        return getattr(job.pipeline, "sink", None) if job else None

    @_locked
    def resume_sink(self, name: str) -> None:
        """Re-arm delivery on a DEGRADED sink job (the ALTER SINK ...
        RESUME shape): the logged backlog drains at the next barrier.
        No-op on a healthy sink."""
        if name not in self.catalog.sinks:
            raise SqlError(f"sink {name!r} not found")
        job = self.jobs.get(name)
        resume = getattr(job.pipeline, "resume", None) if job else None
        if resume is None:
            raise SqlError(f"sink {name!r} has no live delivery loop")
        resume()

    # ------------------------------------------------- scoped job recovery --

    def kill_job(self, name: str) -> None:
        """Chaos/test hook: hard-kill a job's actor task mid-flight (the
        madsim node-kill analogue). Nothing is cleaned up here — detection
        is the heartbeat detector's duty and restoration is
        ``_recover_job``'s (reference: madsim kill,
        src/tests/simulation/src/cluster.rs:498-510)."""
        job = self.jobs[name]
        if job._task is not None:
            job._task.cancel()

    def _job_state_ids(self, name: str) -> list[int]:
        """Every state-table id a job (MV / table / sink) writes."""
        mv = self.catalog.mvs.get(name)
        if mv is not None:
            rng = getattr(mv, "table_id_range", None)
            if rng is not None:
                return list(range(*rng))
        obj = (self.catalog.tables.get(name)
               or self.catalog.sinks.get(name))
        if obj is None:
            return []
        ids = [obj.table_id]
        ids += [tid for tid in getattr(obj, "state_table_ids", ())
                if tid >= 0]
        prog = getattr(obj, "progress_table_id", -1)
        if prog >= 0:
            ids.append(prog)
        return ids

    def _downstream_names(self, job: StreamJob) -> list[str]:
        """Names of jobs transitively fed by ``job``'s bus."""
        sub_queues = set(map(id, job.bus.subscribers))
        out: list[str] = []
        for n, j in self.jobs.items():
            if any(id(q) in sub_queues for q in j.sources):
                if n not in out:
                    out.append(n)
                    for m in self._downstream_names(j):
                        if m not in out:
                            out.append(m)
        return out

    def _recover_job(self, name: str) -> list[str]:
        """Scoped recovery: rebuild a dead job (and its transitive
        downstream MVs) from durable state at the last committed epoch,
        WITHOUT restarting the session or touching unrelated jobs.

        Mirrors the reference's recovery sequence
        (src/meta/src/barrier/recovery.rs:110 — clean dirty state, rebuild
        actors, re-seek sources) scoped to one job subtree: torn staged
        writes are discarded, executors reload state tables at the last
        commit, and source readers seek their checkpointed offsets, so the
        rebuilt subtree replays exactly the rows lost since that commit.
        Only MV jobs are scoped-recoverable; a subtree containing a table
        or sink job falls back to requiring a session restart (state is
        durable). Returns the recovered subtree's job names (the caller
        dedups overlapping recovery requests with it)."""
        if name in self._spanning_specs:
            return self._recover_spanning_job(name)
        if name in self._remote_specs:
            return self._recover_remote_job(name)
        job = self.jobs.get(name)
        if job is None:
            return [name]
        # drain pipelined epochs first: the rebuilt jobs will only see
        # barriers from the NEXT injection on, so nothing may stay in
        # flight across the rebuild (dead jobs are tolerated by collect)
        self._fused.drain()
        self._drain_inflight()
        subtree = [name] + self._downstream_names(job)
        non_mv = [n for n in subtree if n not in self.catalog.mvs]
        if non_mv:
            raise RuntimeError(
                f"job {name!r} died and its subtree {subtree} contains "
                f"non-MV jobs {non_mv}; scoped recovery covers MV jobs — "
                "restart the session to restore from durable state")
        for n in subtree:
            j = self.jobs.pop(n, None)
            if j is None:
                continue
            self._teardown_job(n, j)
            mv = self.catalog.mvs[n]
            rng = getattr(mv, "table_id_range", None)
            if rng is not None:
                self.store.discard_pending_tables(range(*rng))
        # rebuild in creation order (upstream MVs before their readers)
        for n in [m for m in self.catalog.mvs if m in subtree]:
            self._rebuild_mv_job(n)
        self.meta.notifications.notify(
            "recovery", {"jobs": subtree, "epoch": self.epoch})
        return subtree

    def _rebuild_mv_job(self, name: str) -> None:
        """Rebuild one MV job from its catalog definition over existing
        durable state (the reschedule rebuild core, without a config
        change): table ids replay deterministically, ``_recovering`` makes
        executors reload state instead of snapshotting upstreams, and
        source readers seek their checkpointed offsets."""
        mv = self.catalog.mvs[name]
        id0, id1 = mv.table_id_range  # type: ignore[attr-defined]
        ids = iter(range(id0, id1))
        saved_alloc = self.catalog.next_table_id
        saved_recovering = self._recovering

        def replay_id() -> int:
            try:
                return next(ids)
            except StopIteration:
                raise RuntimeError(
                    "recovery id replay diverged from the original build")

        self.catalog.next_table_id = replay_id  # type: ignore[assignment]
        self._recovering = True
        n_feeds0 = len(self.feeds)
        n_bf0 = len(self.backfills)
        try:
            (plan, pipeline, ctx, queues, init_msgs,
             _slq) = self._build_query_pipeline(mv.query_ast)  # type: ignore[attr-defined]
            mv_table_id = self.catalog.next_table_id()
            mat = MaterializeExecutor(
                pipeline,
                StateTable(self.store, mv_table_id, plan.schema,
                           list(plan.pk)))
        finally:
            self.catalog.next_table_id = saved_alloc  # type: ignore[assignment]
            self._recovering = saved_recovering
        for f in self.feeds[n_feeds0:]:
            f.job = name
        for b in self.backfills[n_bf0:]:
            b.job = name
        job = StreamJob(name, mat, queues, actors=ctx.actors)
        self.jobs[name] = job
        job.start(self.loop)
        for q, init in init_msgs:
            for m in init:
                q.push(m)
            q.push(Barrier.new(self.epoch))
        self._await(job.wait_barrier(self.epoch))

    def _stream_leaf(self, leaf):
        """-> (executor, session_driven_queue_or_None, init_messages)"""
        if isinstance(leaf, PSource):
            src_def = leaf.source
            reader = self._connector_reader(src_def)
            row_ids = RowIdSequence(self._alloc_shard())
            ex: Executor
            if reader is None:
                # fed by pushes of chunks that are on the device already:
                # the one executor that gives those their _row_id
                q = QueueSource(src_def.schema)
                self.feeds.append(_SourceFeed(q, lambda: None))
                ex = RowIdGenExecutor(q, leaf.schema, row_ids)
            else:
                # split-state table: (split_id, next_offset), persisted on
                # checkpoint epochs, sought on recovery
                from ..common.types import INT64, VARCHAR
                st = StateTable(
                    self.store, self.catalog.next_table_id(),
                    Schema((Field("split_id", VARCHAR),
                            Field("next_offset", INT64))), [0])
                if self._recovering:
                    offsets = {
                        VARCHAR.to_python(r[0]): int(r[1])
                        for r in st.scan_all()}
                    if offsets:
                        reader.seek(offsets)
                        # row ids must continue above any id assigned
                        # before the crash (pk collisions in downstream
                        # materialized state otherwise)
                        row_ids.next = reader.rows_emitted()
                # the feed's chunks leave the staging dispatch with their
                # _row_id (common/chunk.stage_chunks): the queue IS the leaf
                ex = q = QueueSource(leaf.schema)
                self.feeds.append(_SourceFeed(
                    q, reader.next_host_chunk, reader=reader, state_table=st,
                    row_ids=row_ids))
            if src_def.watermark is not None:
                col, delay = src_def.watermark
                ex = WatermarkFilterExecutor(ex, time_col=col, delay=delay)
            return ex, q, []
        if isinstance(leaf, (PTableScan, PMvScan)):
            name = leaf.table.name if isinstance(leaf, PTableScan) else leaf.mv.name
            if self._mv_worker(name) is not None:
                raise SqlError(
                    f"{name!r} is a worker-hosted MV; jobs consuming it "
                    "must also be worker-hosted (not supported yet)")
            up_job = self.jobs[name]
            q = QueueSource(leaf.schema)
            up_job.bus.subscribe(q)
            # CONCURRENT backfill (reference: executor/backfill.rs:48-69):
            # the upstream's durable table is snapshot-read in bounded
            # batches across barriers while live deltas keep flowing —
            # creating an MV over a huge upstream never stalls an epoch.
            # The progress table makes it crash-resumable; on recovery the
            # persisted cursor/done flag decides (done => pass-through,
            # matching the old recovered-state semantics; empty progress
            # after a create-but-never-checkpointed crash => fresh
            # backfill, subsuming _maybe_rebackfill for scan leaves).
            from ..stream.backfill import BackfillExecutor
            from ..stream.backfill import PROGRESS_SCHEMA as BF_PROGRESS
            prog = StateTable(self.store, self.catalog.next_table_id(),
                              BF_PROGRESS, [0])
            meta = self.meta

            def report(p, _name=name):
                meta.notifications.notify(
                    "backfill", {"job": _name, **p})

            batch_rows = (self.config.backfill_batch_rows
                          or max(self.source_chunk_capacity * 4, 4096))
            bf = BackfillExecutor(
                q, up_job.table, batch_rows=batch_rows,
                chunk_capacity=self.source_chunk_capacity,
                progress_table=prog, on_progress=report)
            self.backfills.append(_BackfillRef(bf))
            # session does NOT drive this queue; upstream bus does. The
            # init barrier is pushed at creation (empty init list).
            return bf, q, []
        if isinstance(leaf, PValues):
            q = QueueSource(leaf.schema)
            chunk = _values_chunk(leaf)
            return q, q, [chunk]
        raise PlanError(f"cannot stream {type(leaf).__name__}")

    def _connector_reader(self, src: SourceDef):
        """Instantiate the connector's SplitReader via the shared factory
        (connector/factory.py); None for declared-schema sources fed only
        by tests."""
        from ..connector.factory import ConnectorError, make_reader
        try:
            return make_reader(src.connector, src.options, src.schema,
                               self.source_chunk_capacity, self.seed,
                               fault=self.fault)
        except ConnectorError as e:
            raise SqlError(str(e)) from None

    def _unsubscribe_job(self, job: StreamJob) -> None:
        """Remove a stopped job's input queues from every upstream bus —
        otherwise upstreams keep pushing into dead queues forever."""
        for other in self.jobs.values():
            if other is job:
                continue
            for q in job.sources:
                other.bus.unsubscribe(q)

    def _drop(self, stmt: A.DropStatement) -> list:
        if stmt.kind == "index":
            ix = self.catalog.indexes.get(stmt.name)
            if ix is None:
                if stmt.if_exists:
                    return []
                raise SqlError(f"index {stmt.name!r} not found")
            self.catalog_writer.drop("index", stmt.name, False)
            # the arrangement MV goes with it
            return self._drop(dataclasses.replace(
                stmt, kind="materialized_view", name=ix.mv_name,
                if_exists=True))
        # dropping a base relation cascades to its indexes — a dangling
        # index would keep serving the DROPPED table's rows to lookups
        for ix_name in [n for n, ix in self.catalog.indexes.items()
                        if ix.table == stmt.name]:
            self._drop(dataclasses.replace(
                stmt, kind="index", name=ix_name, if_exists=True))
        # a deferred fused flush must resolve BEFORE membership changes
        # restack the job axis (and before its chunks would be lost)
        self._fused.drain()
        self._drain_inflight()
        # free the object's durable state (tombstoned in the manifest so
        # recovery and compaction skip it)
        obj = (self.catalog.tables.get(stmt.name)
               or self.catalog.mvs.get(stmt.name)
               or self.catalog.sinks.get(stmt.name))
        existed = self.catalog_writer.drop(stmt.kind, stmt.name, stmt.if_exists)
        if existed:
            # the job's source feeds die with it: free their split-state
            # tables (collect BEFORE teardown filters them away)
            dead_feeds = [f for f in self.feeds if f.job == stmt.name]
            self._fused.drop(stmt.name)
            if stmt.name in self.jobs:
                job = self.jobs.pop(stmt.name)
                # full shared teardown: also clears _dead_jobs / worker
                # registry — a dropped dead job's name must not poison a
                # future job of the same name
                self._teardown_job(stmt.name, job)
            for f in dead_feeds:
                if f.state_table is not None:
                    self.store.drop_table(f.state_table.table_id)
            spec = self._remote_specs.pop(stmt.name, None)
            if spec is not None and not spec["worker"].dead:
                from .remote import WorkerDied
                try:
                    self._await(spec["worker"].request(
                        {"type": "drop_job", "name": stmt.name,
                         "epoch": self._injected + 1}))
                except (WorkerDied, RuntimeError):
                    pass             # worker gone; its state dir is stale
            span = self._spanning_specs.pop(stmt.name, None)
            if span is not None:
                from .remote import WorkerDied
                self.meta.drop_placement(stmt.name)
                for w in span["workers"]:
                    if w.dead:
                        continue     # its state dir is stale; respawn wipes
                    try:
                        self._await(w.request(
                            {"type": "drop_job", "name": stmt.name,
                             "epoch": self._injected + 1}))
                    except (WorkerDied, RuntimeError):
                        pass
        if existed and obj is not None:
            self.dml.unregister_table(obj.table_id)
            for tid in ((obj.table_id,)
                        + tuple(getattr(obj, "state_table_ids", ()))):
                if tid >= 0:
                    self.store.drop_table(tid)
        return []

    # ----------------------------------------------------------------- DML --

    def _insert(self, stmt: A.Insert) -> list:
        from .catalog import strip_schema
        t = self.catalog.tables.get(strip_schema(stmt.table))
        if t is None:
            raise SqlError(f"table {stmt.table!r} not found")
        binder = ExprBinder(Scope([]))
        data_fields = [f for f in t.schema if f.name != "_row_id"]
        names = [f.name for f in data_fields]
        cols = list(stmt.columns) or names
        rows = []
        for vrow in stmt.rows:
            if len(vrow) != len(cols):
                raise SqlError("INSERT arity mismatch")
            by_name = {}
            for cname, vexpr in zip(cols, vrow):
                lit = binder.bind(vexpr)
                from ..expr.expr import Literal
                if not isinstance(lit, Literal):
                    raise SqlError("INSERT values must be literals")
                by_name[cname] = lit.value
            rows.append(tuple(by_name.get(n) for n in names))
        chunk = make_chunk(Schema(tuple(data_fields)), rows,
                           capacity=max(len(rows), 1))
        self.dml.stage(t.table_id, chunk)
        return []

    def _dml_target(self, name: str):
        """Resolve + preconditions shared by DELETE/UPDATE (reference:
        batch Delete/Update executors via DmlManager)."""
        from .catalog import strip_schema
        t = self.catalog.tables.get(strip_schema(name))
        if t is None:
            raise SqlError(f"table {name!r} not found")
        if t.append_only:
            raise SqlError(f"table {name!r} is APPEND ONLY")
        if len(t.pk) == 1 and t.schema[t.pk[0]].name == "_row_id":
            raise SqlError(
                "DELETE/UPDATE require a declared PRIMARY KEY "
                "(hidden row-id tables are insert-only)")
        # read-your-writes: staged DML must be visible to the match. A
        # plain (non-checkpoint) epoch suffices — materialize ingests into
        # the store's pending view; no durable commit per statement
        if self.dml.has_staged():
            self.tick(generate=False, checkpoint=False)
        self._drain_inflight()
        return t

    def _match_rows(self, t, where) -> list:
        """Physical rows of ``t`` matching ``where`` (vectorized eval)."""
        import numpy as np
        from ..common.chunk import physical_chunk
        table = StateTable(self.store, t.table_id, t.schema, list(t.pk))
        rows = list(table.scan_all())
        if where is None or not rows:
            return rows
        pred = ExprBinder(Scope.of_schema(t.schema)).bind(where)
        chunk = physical_chunk(t.schema, rows, len(rows))
        cond = pred.eval(chunk)
        keep = np.asarray(cond.data & cond.mask)[:len(rows)]
        return [r for r, k in zip(rows, keep) if k]

    def _delete_dml(self, stmt: A.Delete) -> list:
        from ..common.chunk import OP_DELETE, make_chunk
        t = self._dml_target(stmt.table)
        rows = self._match_rows(t, stmt.where)
        if rows:
            chunk = make_chunk(t.schema, rows, ops=[OP_DELETE] * len(rows),
                               capacity=len(rows), physical=True)
            self.dml.stage(t.table_id, chunk)
        return [("DELETE", len(rows))]

    def _update_dml(self, stmt: A.Update) -> list:
        import numpy as np
        from ..common.chunk import (
            OP_UPDATE_DELETE, OP_UPDATE_INSERT, make_chunk, physical_chunk,
        )
        t = self._dml_target(stmt.table)
        names = list(t.schema.names)
        assigns = []
        for col, e in stmt.assignments:
            if col not in names:
                raise SqlError(f"column {col!r} not found")
            assigns.append((names.index(col),
                            ExprBinder(Scope.of_schema(t.schema)).bind(e)))
        rows = self._match_rows(t, stmt.where)
        if rows:
            from ..expr.expr import cast as _cast
            chunk = physical_chunk(t.schema, rows, len(rows))
            new_cols = {}
            for idx, e in assigns:
                e2 = (e if e.type == t.schema[idx].type
                      else _cast(e, t.schema[idx].type))
                c = e2.eval(chunk)
                new_cols[idx] = (np.asarray(c.data), np.asarray(c.mask))
            new_rows = []
            for r, old in enumerate(rows):
                new = list(old)
                for idx, _ in assigns:
                    d, m = new_cols[idx]
                    new[idx] = d[r].item() if m[r] else None
                new_rows.append(tuple(new))
            pk_cols = set(t.pk)
            pk_changed = any(idx in pk_cols for idx, _ in assigns)
            if not pk_changed:
                # same-pk updates: adjacent U-/U+ pairs (order-safe — pks
                # are unique within the statement)
                pairs, ops = [], []
                for old, new in zip(rows, new_rows):
                    pairs.extend((tuple(old), new))
                    ops.extend((OP_UPDATE_DELETE, OP_UPDATE_INSERT))
            else:
                # pk-moving updates: sequential pair application could
                # delete a freshly-moved row (SET k = k + 1 over k=1,2).
                # Emit ALL deletes before ALL inserts, and reject
                # duplicate-key outcomes the way a database must.
                from ..common.chunk import OP_DELETE, OP_INSERT
                def pk_of(row):
                    return tuple(row[i] for i in t.pk)
                old_pks = {pk_of(r) for r in rows}
                seen = set()
                table = StateTable(self.store, t.table_id, t.schema,
                                   list(t.pk))
                for nr in new_rows:
                    npk = pk_of(nr)
                    if npk in seen:
                        raise SqlError(
                            f"UPDATE produces duplicate key {npk}")
                    seen.add(npk)
                    if npk not in old_pks and \
                            table.get_row(list(npk)) is not None:
                        raise SqlError(
                            f"UPDATE key {npk} collides with an "
                            "existing row")
                pairs = [tuple(r) for r in rows] + new_rows
                ops = [OP_DELETE] * len(rows) + [OP_INSERT] * len(new_rows)
            out = make_chunk(t.schema, pairs, ops=ops,
                             capacity=len(pairs), physical=True)
            self.dml.stage(t.table_id, out)
        return [("UPDATE", len(rows))]

    # --------------------------------------------------------------- epochs --

    # -- data-version seqlock (frontend/serving.py reads it) ------------------
    # State-store mutation sections (tick / barrier completion / recovery):
    # the data version goes ODD on entry of the outermost section and EVEN
    # again on exit. Optimistic serving readers accept a scan only when
    # the same even version spans it; mutators always hold the API lock,
    # so the depth counter needs no extra lock. Plain enter/exit methods —
    # these sit on the hot path of every tick.

    def _enter_mutation(self) -> None:
        self._mutation_depth += 1
        if self._mutation_depth == 1:
            self._data_version += 1              # odd: in progress

    def _exit_mutation(self) -> None:
        self._mutation_depth -= 1
        if self._mutation_depth == 0:
            self._data_version += 1              # even: quiescent

    @_locked
    def tick(self, generate: bool = True, checkpoint: Optional[bool] = None,
             mutation: Optional[Mutation] = None) -> int:
        """One barrier cycle: feed sources, inject the barrier, and await
        completion of the oldest in-flight epoch once more than
        ``in_flight_barriers`` are outstanding — the reference's pipelined
        inject/collect loop (src/meta/src/barrier/mod.rs:152,
        in_flight_barrier_nums config.rs:380-381). With the default of 1
        this is the classic synchronous cycle. Returns the last COMPLETED
        epoch."""
        self._enter_mutation()
        try:
            return self._tick_impl(generate, checkpoint, mutation)
        except Exception as exc:
            # a fenced ex-writer on a remote control plane demotes to a
            # working serving session instead of wedging (the original
            # MetaFenced still surfaces so the driver knows)
            self._maybe_demote(exc)
            raise
        finally:
            self._exit_mutation()

    def _tick_impl(self, generate: bool, checkpoint: Optional[bool],
                   mutation: Optional[Mutation]) -> int:
        if self.role == "serving":
            raise RuntimeError(
                "serving sessions do not conduct barriers: only the "
                "writer session ticks (docs/control-plane.md)")
        # a fenced ex-writer must not inject another barrier: a newer
        # writer owns conduction now (lease loss arrives either on the
        # leader notification channel or as a refused publish/commit)
        self._check_fenced()
        epoch = self._injected + 1
        # tag this tick's dispatch spans (common/profiling.py) so a slow
        # epoch's span-tree capture includes the dispatches that caused it
        from ..common import tracing
        from ..common.profiling import GLOBAL_PROFILER
        GLOBAL_PROFILER.epoch = epoch
        if checkpoint is None:
            checkpoint = epoch % self.checkpoint_frequency == 0
        # the root of the epoch's span tree covers the WHOLE call; its
        # duration is the ledger record's tick_ms (attached late: the
        # record is sealed inside)
        tracing.set_conductor_epoch(epoch)
        root = tracing.span("session.tick", epoch=epoch, cat=tracing.CAT_EPOCH,
                            tid="conductor", checkpoint=checkpoint)
        try:
            with root:
                return self._tick_body(epoch, checkpoint, generate, mutation)
        finally:
            tracing.set_conductor_epoch(None)
            self._barrier_ledger.set_tick_ms(epoch, root.dur_ns / 1e6)

    def _tick_body(self, epoch: int, checkpoint: bool, generate: bool,
                   mutation: Optional[Mutation]) -> int:
        from ..common import tracing
        # keep the worker registry in sync with the live job set (workers
        # register with last_heartbeat = the current epoch clock). With a
        # remote meta, re-anchor the epoch clock FIRST: a restarted meta
        # process comes back with clock 0, and letting sync_jobs register
        # at 0 before completion advances to `epoch` would expire every
        # job in one jump (in-process meta: clock already equals
        # self.epoch, so this is a no-op kept off that path)
        if self.meta_addr is not None:
            self.meta.advance_epoch_clock(self.epoch)
        self.meta.sync_jobs(self.jobs.keys())
        if mutation is None and self._pending_mutation is not None:
            mutation = self._pending_mutation
            self._pending_mutation = None
        barrier = Barrier.new(epoch, checkpoint=checkpoint, mutation=mutation)
        if generate and not self.paused:
            with tracing.span("source.feed", epoch=epoch,
                              stage="source_feed", cat=tracing.CAT_EPOCH,
                              tid="conductor") as feed_span:
                staged = StagedCounts()
                fed = fed_rows = 0
                for feed in self.feeds:
                    if feed.job in self._dead_jobs:
                        # a dead job consumes nothing: advancing its reader
                        # would move offsets past rows it never processed
                        continue
                    for chunk in feed_chunks(
                            feed.generator, self.chunks_per_tick,
                            feed.queue.push, staged, feed.row_ids):
                        fed += 1
                        fed_rows += chunk.capacity
                feed_span.set(chunks=fed, capacity_rows=fed_rows,
                              **dataclasses.asdict(staged))
        if self._fused.engines:
            # fused jobs: one dispatch per group covers every member
            # MV's epoch; flush chunks land on the job queues BEFORE the
            # barrier below
            self._fused.tick(epoch, checkpoint,
                             generate and not self.paused)
        import time as _time
        # barrier observatory: open this epoch's waterfall record and
        # time the inject stage (host-side clock only — zero added
        # dispatches, nothing on the device path)
        self._barrier_ledger.begin(epoch, checkpoint, _time.time(),
                                   tracing.now_ns())
        with tracing.span("barrier.inject", epoch=epoch, stage="inject",
                          cat=tracing.CAT_EPOCH, tid="conductor"):
            self.dml.drain_into_epoch()
            for feed in self.feeds:
                if feed.reader is not None:
                    feed.offsets_at_epoch[epoch] = feed.reader.offsets
                feed.queue.push(barrier)
            for queues in self._table_queues.values():
                for q in queues:
                    q.push(barrier)
            if self.workers:
                from .remote import WorkerDied
                dead_jobs = sorted(self._dead_jobs)

                async def _inject_remote() -> None:
                    for w in self.workers:
                        if w.dead:
                            continue
                        try:
                            # jobs already declared dead (a spanning job
                            # with a killed peer) are excluded: feeding
                            # them would advance readers past rows the
                            # job never processed, and waiting on them
                            # would wedge the worker's healthy jobs
                            await w.inject_barrier(
                                epoch, checkpoint,
                                generate and not self.paused, mutation,
                                exclude=dead_jobs)
                        except WorkerDied:
                            pass        # collect marks its jobs dead
                self._await(_inject_remote())
        self._injected = epoch
        self._inflight.append((epoch, checkpoint))
        # the barrier's latency clock starts here, after inject
        self._inject_time[epoch] = tracing.now_ns()
        # pipelined barriers would let an upstream run AHEAD of an active
        # backfill's snapshot reads (the scan would see a later epoch's
        # staged rows and the same update would also arrive as a delta —
        # double-apply). While any backfill is in flight, barriers
        # complete synchronously; completed backfills free pipelining.
        self.backfills = [b for b in self.backfills if not b.bf.done]
        limit = 1 if self.backfills else self.in_flight_barriers
        while len(self._inflight) >= limit:
            self._complete_oldest()
        # failure detection + scoped recovery (reference: heartbeat expiry
        # manager/cluster.rs:320-344 → recovery barrier/recovery.rs:110):
        # the TTL detector declares jobs that stopped heartbeating DOWN;
        # its listeners queue them and recovery runs here, outside the
        # collect path
        if not self._recovering:
            self.meta.check_job_failures()
            if self._jobs_to_recover:
                # a dead job's downstreams expire with it (barrier
                # starvation). Recover only subtree ROOTS — each root's
                # recovery rebuilds its whole downstream subtree, and
                # expiry order is not topological (the detector iterates a
                # registry), so covered names must be dropped, not just
                # deduped after the fact.
                pending = list(dict.fromkeys(self._jobs_to_recover))
                self._jobs_to_recover.clear()
                covered: set[str] = set()
                for m in pending:
                    j = self.jobs.get(m)
                    if j is not None:
                        covered.update(self._downstream_names(j))
                recovered: set[str] = set()
                for n in pending:
                    if n in covered or n in recovered:
                        continue
                    from .remote import WorkerDied
                    try:
                        recovered.update(self._recover_job(n))
                    except WorkerDied:
                        # the fabric is STILL faulty (an ongoing
                        # partition ate the rebuilt graph's init cut, or
                        # the respawned worker died again): a recovery
                        # attempt must not crash the session — requeue
                        # and retry on a later tick, when the fault
                        # window may have passed
                        if n in self.jobs:
                            self._dead_jobs.add(n)
                        self._jobs_to_recover.append(n)
            if (self.autoscaler_config.enabled and self.workers
                    and not self._in_rescale
                    and not self._dead_jobs and not self._jobs_to_recover):
                # backlog-driven autoscaling, AFTER failure handling: a
                # cluster mid-recovery must heal, not rescale — and a
                # rescale's own quiesce flush (a nested tick) must not
                # re-enter the policy mid-migration
                self._autoscaler_step()
        return self.epoch

    def _autoscaler_step(self) -> None:
        """One autoscaler observation per spanning job: fold this job's
        per-edge exchange counters (backlog, permits_waited growth) and
        the slow-epoch detector into the policy core
        (meta/autoscaler.py); execute any decision as a live rescale.
        A failed migration rolls back, notes the error, and holds the
        cooldown — the autoscaler can never crash a tick."""
        if not self._spanning_specs:
            return          # nothing rescalable: skip the stats fan-out
        stats = self._federate_worker_stats(force=True, timeout=0.5)
        slow_delta = self._slow_epoch_total - self._autoscaler_slow_seen
        self._autoscaler_slow_seen = self._slow_epoch_total
        if len(self._spanning_specs) > 1:
            # the slow-epoch detector times the WHOLE barrier tick, so
            # with several spanning jobs it cannot name a culprit — one
            # heavy job would scale out every idle sibling. Per-edge
            # backlog/permit counters stay per-job; only they decide.
            slow_delta = 0
        live_workers = sum(1 for w in self.workers if not w.dead)
        for name in list(self._spanning_specs):
            placement = self._spanning_specs[name]["placement"]
            par = max(len(a) for a in placement.actors.values())
            backlog = pw = 0
            for _wid, st in sorted(stats.items()):
                for e in st.get("exchange", ()) or ():
                    if str(e.get("edge", "")).startswith(f"{name}:"):
                        backlog += int(e.get("backlog", 0) or 0)
                        pw += int(e.get("permits_waited", 0) or 0)
            pw_delta = max(0, pw - self._autoscaler_pw.get(name, 0))
            self._autoscaler_pw[name] = pw
            target = self.autoscaler.observe(
                name, par, backlog=backlog, permits_waited=pw_delta,
                slow_epochs=slow_delta, live_workers=live_workers)
            if target is None or target == par:
                continue
            try:
                self.rescale(name, target)
            except Exception as e:  # noqa: BLE001 - rolled back + held
                self.autoscaler.note_failed(name, repr(e))

    @_locked
    def set_source_rate(self, chunks_per_tick: int) -> None:
        """Adjust the per-tick source generation rate LIVE, session-side
        and on every worker (``set_rate`` frames) — the traffic-spike
        lever the sim's autoscaler scenario drives (sim.py
        run_traffic_spike)."""
        self.chunks_per_tick = max(0, int(chunks_per_tick))
        if not self.workers:
            return
        from .remote import WorkerDied

        async def _all() -> None:
            for w in self.workers:
                if w.dead:
                    continue
                try:
                    await w.request({"type": "set_rate",
                                     "chunks_per_tick":
                                     self.chunks_per_tick})
                except WorkerDied:
                    pass          # recovery re-ships chunks_per_tick

        self._await(_all())

    def _complete_oldest(self) -> None:
        self._enter_mutation()
        try:
            self._complete_oldest_impl()
        finally:
            self._exit_mutation()

    def _complete_oldest_impl(self) -> None:
        from ..common import tracing
        from ..common.barrier_ledger import GLOBAL_STAGES
        from ..common.tracing import CAT_EPOCH, GLOBAL_TRACE
        e, ckpt = self._inflight.pop(0)
        ledger = self._barrier_ledger
        t_entry = tracing.now_ns()
        _pend = self._inject_time.get(e)
        if _pend is not None:
            # pending: injected, parked in _inflight behind older epochs
            # (pipelining) — with depth 1 this is ~0 and the waterfall
            # stage sum reconciles with the barrier latency recorder
            ledger.stage(e, "pending", (t_entry - _pend) / 1e6)
        dead_before = len(self._dead_jobs)
        result = "ok"
        try:
            with tracing.span("barrier.collect", epoch=e, stage="collect",
                              cat=CAT_EPOCH, tid="conductor"):
                self._await(self._collect_barrier(e))
        except BaseException:
            ledger.ingest_events(GLOBAL_STAGES.drain())
            ledger.finish(e, (tracing.now_ns() - t_entry) / 1e6, "failed")
            self._inject_time.pop(e, None)
            raise
        if len(self._dead_jobs) > dead_before:
            result = "failed"        # collect declared a job dead
        if ckpt and self._dead_jobs:
            # a dead job may have staged a torn subset of its tables for an
            # epoch whose checkpoint it never finished — keep those buffers
            # out of this commit (recovery reloads from the last good one).
            # Covers EVERY job kind: a killed table/sink job's torn epoch
            # must not become durable either.
            for n in self._dead_jobs:
                self.store.discard_pending_tables(self._job_state_ids(n))
        if ckpt:
            with tracing.span("checkpoint.commit", epoch=e, stage="commit",
                              cat=CAT_EPOCH, tid="conductor"):
                self._commit_checkpoint(e)
        # session-process storage/sink stage events (recorded at the 2PC
        # sites in storage/checkpoint.py and stream/sink.py) fold into
        # their records here, off the device path. Worker-side events
        # arrive later over stats federation and attach to the sealed
        # ring record by epoch.
        ledger.ingest_events(GLOBAL_STAGES.drain())
        t0 = self._inject_time.pop(e, None)
        if t0 is not None:
            lat_ns = tracing.now_ns() - t0
            lat = lat_ns / 1e9
            self.barrier_latency.record(lat)
            record = ledger.finish(e, lat * 1e3, result)
            # the barrier's latency interval (inject done → completed),
            # on a track of its own: with pipelined barriers it outlives
            # the tick that injected it, so it is no span's child
            tracing.record_span(f"epoch {e}", t0, lat_ns, epoch=e,
                                cat=CAT_EPOCH, tid="epoch",
                                parent=tracing.ROOT,
                                checkpoint=ckpt)
            lat_ms = lat * 1e3
            if (self.slow_epoch_threshold_ms
                    and lat_ms >= self.slow_epoch_threshold_ms):
                # slow-epoch detector: freeze the offending epoch's span
                # tree for post-hoc inspection (the ring may overwrite it
                # long before anyone looks). Pull workers' spans FIRST —
                # without the forced poll a worker-hosted job's capture
                # would hold only conductor-side spans. Short fuse: this
                # runs INSIDE barrier completion, and a 2s stall here
                # would itself keep every following epoch over threshold
                self._federate_worker_stats(force=True, timeout=0.25)
                self._slow_epoch_total += 1
                self._slow_epochs.append({
                    "epoch": e, "latency_ms": round(lat_ms, 3),
                    "checkpoint": ckpt,
                    # the offending barrier's waterfall record, refreshed
                    # post-federation so worker stages are attached
                    "barrier": ledger.get(e) or record,
                    "spans": [s.to_dict()
                              for s in GLOBAL_TRACE.snapshot(epoch=e)],
                })
        else:
            ledger.finish(e, (tracing.now_ns() - t_entry) / 1e6, result)
        self.epoch = e
        # control-plane publication (reference: barrier_complete responses +
        # hummock version notifications, SURVEY.md §3.2 tail)
        self.meta.advance_epoch_clock(e)
        try:
            self.meta.publish_barrier(e, ckpt)
            if ckpt:
                self.meta.publish_checkpoint(e)
        except Exception as exc:
            # a refused publish is how a stale writer learns it lost the
            # lease when the leader notification hasn't landed yet
            if type(exc).__name__ == "MetaFenced":
                self._fenced = True
            raise
        if ckpt and self.compactors:
            self._kick_compaction()

    def _commit_checkpoint(self, e: int) -> None:
        """Phase 2 of the cluster checkpoint for epoch ``e``: split
        offsets + the session store tier, then the workers' staged
        epochs."""
        # lease check BEFORE anything becomes durable: a stale ex-writer
        # (remote meta, lease superseded) must not commit. One host-side
        # RPC per checkpoint — nothing on the device path.
        self._check_fenced()
        assert_leader = getattr(self.meta, "assert_leader", None)
        if assert_leader is not None and self.role == "writer":
            from ..meta.client import MetaFenced
            try:
                assert_leader()
            except MetaFenced:
                self._fenced = True
                raise
        # persist source split offsets atomically with the epoch commit
        # (reference: split state committed with the checkpoint barrier)
        from ..common.types import VARCHAR
        for feed in self.feeds:
            if feed.state_table is None:
                continue
            if feed.job in self._dead_jobs:
                # freeze the dead job's offsets at its last completed
                # checkpoint: its state did not advance, so persisting
                # newer offsets would silently skip the rows in between
                continue
            latest = None
            for oe in sorted(list(feed.offsets_at_epoch)):
                if oe <= e:
                    latest = feed.offsets_at_epoch.pop(oe)
            if latest is not None:
                for sid, off in latest.items():
                    feed.state_table.insert(
                        (VARCHAR.to_physical(sid), int(off)))
                feed.state_table.commit(e)
        if self.pipeline_depth >= 2:
            # off-critical-path checkpoint encode: the committed-delta
            # serialization + segment write runs on a worker thread and
            # overlaps the next epoch's device compute; it is JOINED
            # before any 2PC phase-2 frame below (and on FLUSH/close),
            # so exactly-once semantics are untouched
            self.store.commit_async(e)
        else:
            self.store.commit(e)
        if self.workers:
            # the session tier must be durable before phase 2: a worker
            # committing ahead of a crashed session write would fork
            # history against the recovery rebuild
            self.store.join_commits()
            # phase 2 of the cluster checkpoint: workers sealed and
            # acked; only now may their staged epochs become durable
            # (a worker killed before this frame recovers one
            # checkpoint back and its deterministic sources replay).
            # Dead jobs are excluded: a spanning job with a killed peer
            # may have staged a TORN epoch on its surviving workers —
            # committing it would fork history against the recovery
            # rebuild (the session-store analogue is
            # discard_pending_tables above)
            from .remote import WorkerDied
            dead_jobs = sorted(self._dead_jobs)

            async def _commit_remote() -> None:
                for w in self.workers:
                    if w.dead:
                        continue
                    try:
                        await w.commit(e, skip_jobs=dead_jobs)
                    except WorkerDied:
                        pass
            self._await(_commit_remote())

    def _drain_inflight(self) -> None:
        while self._inflight:
            self._complete_oldest()

    # -- storage-tier compaction (dedicated compactor role) -------------------

    def _kick_compaction(self) -> None:
        """Hand the version manager's next merge task to a compactor
        worker — on a pump thread, never the barrier path (reference:
        compaction runs concurrently with checkpoints,
        src/storage/compactor/src/server.rs:57)."""
        t = self._compaction_pump
        if t is not None and t.is_alive():
            return
        task = self.store.manager.get_compact_task()  # type: ignore[attr-defined]
        if task is None:
            return
        t = threading.Thread(target=self._drive_compactor, args=(task,),
                             daemon=True, name="compaction-pump")
        self._compaction_pump = t
        t.start()

    def _drive_compactor(self, task) -> None:
        from ..common.tracing import CAT_STORAGE, span
        from ..worker.compactor import CompactorDied
        mgr = self.store.manager  # type: ignore[attr-defined]
        for c in self.compactors:
            if c.dead:
                try:
                    c.respawn()   # stateless role: nothing to recover
                except Exception:  # noqa: BLE001 - try the next worker
                    continue
            try:
                with span("compaction.dispatch", epoch=None,
                          cat=CAT_STORAGE, tid="conductor",
                          task_id=task.task_id, compactor=c.worker_id):
                    outputs = c.compact(task)
                mgr.report_compact_task(task.task_id, outputs)
                mgr.vacuum()
                return
            except (CompactorDied, RuntimeError) as e:
                import sys as _sys
                _sys.stderr.write(
                    f"compactor {c.worker_id} failed task "
                    f"{task.task_id}: {e!r}\n")
        # no worker finished it: forget the task; a later checkpoint
        # reschedules and converges (inputs are untouched)
        mgr.cancel_compact_task(task.task_id)

    def wait_compaction(self) -> None:
        """Join in-flight compaction work (tests / orderly shutdown)."""
        t = self._compaction_pump
        if t is not None and t.is_alive():
            t.join()
        wait = getattr(self.store, "wait_compaction", None)
        if wait is not None:
            wait()

    def pin_version(self):
        """Pin the current storage version for consistent snapshot reads
        (Hummock tier only): the returned snapshot's SSTs survive any
        concurrent compaction until ``unpin()``/context exit — the read
        contract batch nodes and backup rely on (reference:
        pin_version leases, src/meta/src/hummock/manager/versioning.rs)."""
        pin = getattr(self.store, "pin", None)
        if pin is None:
            raise SqlError(
                "version pinning requires the hummock state store "
                "(Session(state_store='hummock'))")
        return pin()

    async def _collect_barrier(self, epoch: int) -> None:
        # gather must be created inside the session loop (it binds futures
        # to the running loop). Each job that reports the barrier heartbeats
        # its worker entry; a job whose actor task was KILLED (cancelled —
        # the madsim node-kill analogue) stops heartbeating and is left to
        # the TTL detector + scoped recovery, while executor logic errors
        # keep propagating to the caller as before.
        #
        # Downstreams of a dead job are BARRIER-STARVED (nothing upstream
        # will ever forward this epoch's barrier): waiting on them would
        # deadlock the conductor, so they are skipped — and since skipping
        # also withholds their heartbeat, the TTL detector declares the
        # whole subtree DOWN and scoped recovery rebuilds it together.
        dead = {n for n, j in self.jobs.items()
                if isinstance(j._failure, asyncio.CancelledError)}
        self._dead_jobs |= dead
        starved: set[str] = set()
        for n in dead:
            starved.update(self._downstream_names(self.jobs[n]))
        starved -= dead

        async def one(name: str, job: StreamJob) -> None:
            if name in starved:
                return
            try:
                await job.wait_barrier(epoch)
            except BaseException:
                if isinstance(job._failure, asyncio.CancelledError):
                    self._dead_jobs.add(name)
                    return
                raise
            self.meta.job_heartbeat(name)

        await asyncio.gather(
            *(one(n, j) for n, j in self.jobs.items()))

    @_locked
    def flush(self) -> None:
        """FLUSH: complete a checkpoint epoch (DML + state made durable).
        Joins any deferred checkpoint encode — FLUSH is the durability
        promise, so it may not return while an async commit is in
        flight."""
        self.tick(generate=False, checkpoint=True)
        try:
            self._drain_inflight()
        except Exception as exc:
            self._maybe_demote(exc)
            raise
        self.store.join_commits()

    # ----------------------------------------------------------- mutations --

    @_locked
    def pause(self) -> None:
        """Stop source data flow; barriers keep flowing (reference:
        Mutation::Pause, executor/mod.rs:241-251 — used during config
        changes and recovery)."""
        if not self.paused:
            self.paused = True
            self.tick(generate=False, mutation=Mutation(MutationKind.PAUSE))

    @_locked
    def resume(self) -> None:
        if self.paused:
            self.paused = False
            self.tick(generate=False, mutation=Mutation(MutationKind.RESUME))

    # ---------------------------------------------------------------- query --

    @_locked
    def describe(self, sql: str):
        """Output schema of ``sql``'s LAST statement WITHOUT executing it
        — the extended-protocol Describe contract (reference: pgwire
        Describe → frontend infer_return_type,
        src/utils/pgwire/src/pg_protocol.rs:220-259). None = no rows."""
        stmts = parse_sql(sql)
        if not stmts:
            return None
        last = stmts[-1]
        from ..common.types import VARCHAR
        if isinstance(last, A.ShowStatement):
            if last.what == "parameters":
                return [("Name", VARCHAR), ("Value", VARCHAR)]
            return [("Name", VARCHAR)]
        if isinstance(last, A.Explain):
            return [("QUERY PLAN", VARCHAR)]
        if isinstance(last, A.Query):
            # raw plan suffices: every optimizer pass preserves the root
            # schema by contract, so skip the rewrite work here
            plan = Planner(self.catalog).plan_select(last.select)
            return [(f.name, f.type) for f in plan.schema
                    if not f.name.startswith("_")]
        return None

    def _push_remote_fragments(self, plan):
        """Cut maximal Filter/Project chains over worker-hosted MV scans
        into PRemoteFragment stages: the scan+filter+project runs ON the
        worker owning the state and only result rows cross the socket
        (reference: distributed batch stages,
        scheduler/distributed/query.rs:69,115)."""
        from .planner import (
            PFilter as _PF, PProject as _PP, PRemoteFragment,
        )

        def chain_base(node):
            cur = node
            while isinstance(cur, (_PF, _PP)):
                cur = cur.input
            return cur

        def make_fragment(node):
            base = chain_base(node)
            name = base.mv.name
            from .plan_json import defs_to_json, plan_to_json
            plan_json = plan_to_json(node)
            defs_json = defs_to_json([base.mv])
            hosts = self._mv_hosts(name)
            types = [f.type for f in node.schema]

            def fetch():
                import base64 as _b64

                from ..common.row import decode_value_row

                # data-plane requests: a big batch stage may legitimately
                # outlive the control-frame deadline — unbounded here;
                # wedge detection stays the barrier deadline's job. A
                # sharded-root MV's stage runs on EVERY slice-holding
                # worker, each restricted to ITS placed vnode range — a
                # live migration (meta/rescale.py) can leave handed-off
                # rows behind in a store, and an unrestricted scan would
                # union them twice against the range's current owner.
                async def _all():
                    def req(rng):
                        frame = {"type": "batch_task", "job": name,
                                 "plan": plan_json, "defs": defs_json}
                        if rng is not None:
                            frame["vnodes"] = list(range(rng[0], rng[1]))
                        return frame
                    return await asyncio.gather(*(
                        w.request(req(rng), timeout=0)
                        for w, rng in hosts))

                rows = []
                for resp in self._await(_all()):
                    if not resp.get("ok", True):
                        raise RuntimeError(
                            f"batch stage on {name!r}: {resp.get('error')}")
                    rows.extend(decode_value_row(_b64.b64decode(b), types)
                                for b in resp["rows"])
                return rows

            return PRemoteFragment(schema=node.schema, pk=node.pk,
                                   job=name, fetch=fetch)

        def rewrite(node):
            base = chain_base(node)
            if (isinstance(base, PMvScan)
                    and self._mv_worker(base.mv.name) is not None):
                return make_fragment(node)
            kids = list(node.children)
            if not kids:
                return node
            new_kids = [rewrite(k) for k in kids]
            if all(a is b for a, b in zip(new_kids, kids)):
                return node
            from .optimizer import _with_children
            return _with_children(node, new_kids)

        return rewrite(plan)

    def query(self, sel: A.Select) -> list:
        """Batch SELECT through the serving plane (frontend/serving.py):
        version-pinned plan cache (a repeated SELECT skips replan /
        relower / re-jit entirely), two-phase distributed aggregation
        for grouped-agg shapes, and a concurrent read path — cache hits
        and local re-executions never take the session API lock, so
        readers do not serialize behind each other or block barrier
        ticks. Batch-unservable shapes (windows, EOWC, DISTINCT aggs,
        fallback joins) run the stream-fold path below, exactly as
        before. NOTE: do not call ``lower_plan`` here directly — the
        serving cache is the only lowering entry (scripts/check.sh
        lints this)."""
        return self._serving.query(self, sel)

    def _query_stream_fold(self, sel: A.Select, plan) -> list:
        """Stream-only SELECT shapes: run the SAME operator pipeline over
        snapshot sources and fold the delta stream into rows (the
        streaming/batch unification path). Called by the serving plane
        WITH the API lock held."""
        if self._remote_specs or self._spanning_specs:
            plan = self._push_remote_fragments(plan)

        def factory(leaf) -> Executor:
            from .planner import PRemoteFragment
            if isinstance(leaf, (PTableScan, PMvScan, PRemoteFragment)):
                if isinstance(leaf, PTableScan):
                    tid, schema = leaf.table.table_id, leaf.table.schema
                elif isinstance(leaf, PMvScan):
                    tid, schema = leaf.mv.table_id, leaf.mv.schema
                else:
                    schema = leaf.schema
                if isinstance(leaf, PRemoteFragment):
                    rows = leaf.fetch()       # stage ran on the worker
                elif (isinstance(leaf, PMvScan)
                        and self._mv_worker(leaf.mv.name) is not None):
                    rows = self._remote_scan(leaf.mv.name, schema,
                                             physical=True)
                else:
                    table = StateTable(self.store, tid, schema, [])
                    rows = list(table.scan_all())
                msgs: list[Message] = [Barrier.new(1)]
                from ..common.chunk import physical_chunk
                cap = self.source_chunk_capacity
                for i in range(0, len(rows), cap):
                    msgs.append(physical_chunk(schema, rows[i:i + cap], cap))
                msgs.append(Barrier.new(2))
                return MockSource(schema, msgs)
            if isinstance(leaf, PValues):
                chunk = _values_chunk(leaf)
                return MockSource(leaf.schema,
                                  [Barrier.new(1), chunk, Barrier.new(2)])
            raise SqlError(
                "batch SELECT over an unbounded source is not supported; "
                "create a materialized view instead")

        ctx = BuildContext(self.store, self.catalog.next_table_id, factory,
                           self.config, durable=False)
        pipeline = build_plan(plan, ctx)
        rows = self._await(self._run_batch(pipeline))
        # fold the change stream into final rows
        acc: dict = {}
        for op, row in rows:
            if op in (OP_INSERT, OP_UPDATE_INSERT):
                acc[row] = acc.get(row, 0) + 1
            else:
                acc[row] = acc.get(row, 0) - 1
                if acc[row] == 0:
                    del acc[row]
        out = []
        for row, n in acc.items():
            out.extend([row] * n)
        out = self._present(out, sel, plan)
        return out

    async def _run_batch(self, pipeline: Executor) -> list:
        rows = []
        async for msg in pipeline.execute():
            if isinstance(msg, StreamChunk):
                rows.extend(chunk_to_rows(msg, pipeline.schema, with_ops=True))
        return rows

    def _present(self, rows: list, sel: A.Select, plan) -> list:
        """Presentation: ORDER BY sort, then strip hidden columns."""
        schema = plan.schema
        if sel.order_by:
            scope = Scope.of_schema(schema)
            keys = []
            for oi in sel.order_by:
                b = ExprBinder(scope).bind(oi.expr)
                from ..expr.expr import InputRef
                if isinstance(b, InputRef):
                    keys.append((b.index, oi.desc))
            for idx, desc in reversed(keys):
                rows = sorted(
                    rows,
                    key=lambda r: (r[idx] is None, r[idx] if r[idx] is not None else 0),
                    reverse=desc)
        visible = [i for i, f in enumerate(schema) if not f.name.startswith("_")]
        if len(visible) != len(schema):
            rows = [tuple(r[i] for i in visible) for r in rows]
        return rows

    # -------------------------------------------------------------- helpers --

    @_locked
    def mv_rows(self, name: str) -> list:
        """Current contents of an MV (visible columns, decoded)."""
        self._drain_inflight()   # read-your-writes
        mv = self.catalog.mvs.get(name)
        if mv is None:
            raise SqlError(f"materialized view {name!r} not found")
        n_vis = getattr(mv, "n_visible", len(mv.schema))
        if self._mv_worker(name) is not None:
            return [tuple(r[:n_vis])
                    for r in self._remote_scan(name, mv.schema)]
        job = self.jobs[name]
        rows = []
        for phys in job.pipeline.scan_all():
            rows.append(tuple(
                None if v is None else mv.schema[i].type.to_python(v)
                for i, v in enumerate(phys[:n_vis])))
        return rows

    def _mv_worker(self, name: str):
        """The PRIMARY worker process holding an MV's materialized table
        (first root actor for a spanning job); None for session-local
        MVs. Scan-shaped consumers must use ``_mv_hosts`` — a sharded
        root distributes the table over SEVERAL workers."""
        hosts = self._mv_hosts(name)
        return hosts[0][0] if hosts else None

    def _mv_hosts(self, name: str) -> list:
        """Every worker holding a slice of an MV's materialized table,
        as ``(worker, (vnode_start, vnode_end) | None)`` pairs: the one
        hosting worker for whole-job placement (owning the full ring),
        one entry per ROOT-FRAGMENT ACTOR for a spanning job — with a
        sharded root (meta/fragment.py ``shardable``) the MV table is
        vnode-distributed across ≥2 workers, each owning the contiguous
        range its actor was placed with. Empty for session-local MVs."""
        spec = self._remote_specs.get(name)
        if spec is not None:
            return [(spec["worker"], None)]
        span = self._spanning_specs.get(name)
        if span is not None:
            placement = span["placement"]
            graph = span["graph"]
            by_id = {w.worker_id: w for w in span["workers"]}
            return [(by_id[a.worker], (a.vnode_start, a.vnode_end))
                    for a in placement.actors[graph.root_id]]
        return []

    def _remote_scan(self, name: str, schema: Schema,
                     physical: bool = False) -> list:
        """Fetch a worker-hosted MV's rows over the scan RPC — the UNION
        over every worker holding a slice of its table (one worker for
        whole-job placement; every root actor of a sharded-root spanning
        job, whose slices are disjoint by vnode range)."""
        import base64

        from ..common.row import decode_value_row

        async def _scan_all() -> list:
            # data-plane requests: scanning a huge MV may exceed the
            # control deadline without the worker being wedged — unbounded
            return await asyncio.gather(*(
                w.request({"type": "scan", "name": name}, timeout=0)
                for w, _rng in self._mv_hosts(name)))

        types = [f.type for f in schema]
        out = []
        for resp in self._await(_scan_all()):
            for b in resp["rows"]:
                phys = decode_value_row(base64.b64decode(b), types)
                if physical:
                    out.append(phys)
                else:
                    out.append(tuple(
                        None if v is None else schema[i].type.to_python(v)
                        for i, v in enumerate(phys)))
        return out

    @_locked
    def metrics(self) -> dict:
        """Observability dump: per-job per-executor counters + session
        barrier latency percentiles (reference:
        src/stream/src/executor/monitor/streaming_stats.rs:27-88),
        FEDERATED across worker processes — a worker-hosted job's
        counters and state bytes appear exactly like a local job's
        (reference: per-compute-node exporters scraped into one
        Prometheus; here the session is the scraper)."""
        from ..common.memory import pipeline_state_bytes
        from ..stream.metrics import pipeline_metrics
        out = {
            "barrier_latency": self.barrier_latency.snapshot(),
            # barrier observatory (common/barrier_ledger.py): in-flight
            # count + per-stage p50/p99 over the waterfall history ring
            "barrier": {
                "inflight": len(self._inflight),
                **self._barrier_ledger.summary(),
            },
            "epoch": self.epoch,
            "jobs": {
                name: pipeline_metrics(job.pipeline)
                for name, job in self.jobs.items()
                if job.pipeline is not None
            },
            "state_bytes": {
                name: pipeline_state_bytes(job.pipeline)
                for name, job in self.jobs.items()
                if job.pipeline is not None
            },
            "slow_epoch_total": self._slow_epoch_total,
            "slow_epochs": [
                {k: v for k, v in se.items() if k != "spans"}
                for se in self._slow_epochs
            ],
            "storage": self._storage_metrics(),
            # fused jobs, one entry per scheduler: "coschedule",
            # "hetero" (with "attribution"), "shardfused"
            **self._fused.stats(),
            # serving plane (frontend/serving.py): plan-cache hit/miss,
            # two-phase task counts, partials merged, read latency p50/p99
            "serving": self._serving.metrics(),
            # leader failover plane (docs/control-plane.md "Election"):
            # current role/term, fencing state, promotion/demotion
            # counters → rw_leader_* / rw_failover_* Prometheus families
            "leadership": {
                "role": self.role,
                "standby": self._standby,
                "term": self._generation,
                "is_writer": int(self.role == "writer"
                                 and not self._fenced),
                "fenced": self._fenced,
                **self._leadership,
            },
            # asynchronous epoch pipeline ([streaming] pipeline_depth):
            # configured depth, deferred-flush/drain counters, how many
            # group flushes are pending right now, and the profiler's
            # completion/occupancy stats (common/profiling.py)
            "pipeline": self._pipeline_metrics(),
            # per-site retry counters from every boundary (object store,
            # broker, sink delivery) — common/retry.py global registry
            "retry": _retry_snapshot(),
            # out-of-process UDF plane (udf/client.py): server
            # generation, call/retry/respawn/timeout counters, fencing
            # drops, backpressure peaks
            "udf": _udf_snapshot(),
            # sink-decouple health: degraded flag, undelivered backlog,
            # delivery failure counters per sink job
            "sinks": {
                name: job.pipeline.sink_health()
                for name, job in self.jobs.items()
                if hasattr(job.pipeline, "sink_health")
            },
        }
        # network fault plane (rpc/faults.py): the session process's
        # installed schedule + injection counters, the fencing/dedup
        # counters injection forced, and every worker's plane snapshot
        from ..rpc.faults import chaos_snapshot
        out["chaos"] = {
            **chaos_snapshot(),
            "generation": self._generation,
            "stale_acks_dropped": sum(
                getattr(w, "stale_acks_dropped", 0) for w in self.workers),
            "dup_replies_dropped": sum(
                getattr(w, "dup_replies_dropped", 0) for w in self.workers),
            "dup_acks_dropped": sum(
                getattr(w, "dup_acks_dropped", 0) for w in self.workers),
        }
        worker_stats = self._federate_worker_stats()
        out["chaos"]["workers"] = {
            wid: st["chaos"] for wid, st in sorted(worker_stats.items())
            if st.get("chaos")}
        # elastic scaling plane (meta/rescale.py + meta/autoscaler.py):
        # policy state + executed migrations + per-worker handoff rows
        out["autoscaler"] = {
            "enabled": self.autoscaler_config.enabled,
            **self.autoscaler.status(),
            "migrations": self._rescale_stats["migrations"],
            "moved_vnodes": self._rescale_stats["moved_vnodes"],
            "last_rescale": self._rescale_stats["last"],
            "rescale_history": list(self._rescale_stats["history"]),
            "handoff_rows": {
                wid: st["rescale"]
                for wid, st in sorted(worker_stats.items())
                if st.get("rescale")},
        }
        exchange: list = []
        for wid, st in sorted(worker_stats.items()):
            # live local jobs win over cached worker snapshots of the
            # same name (an MV recreated in-process after worker death)
            for name, jm in st.get("jobs", {}).items():
                out["jobs"].setdefault(name, jm)
            for name, nb in st.get("state_bytes", {}).items():
                out["state_bytes"].setdefault(name, nb)
            # per-exchange-edge counters (permits waited, chunks/bytes
            # forwarded, backlog) from every worker hosting an endpoint
            for e in st.get("exchange", ()) or ():
                exchange.append({"worker": wid, **e})
        out["exchange"] = exchange
        out["workers"] = [
            {"worker": w.worker_id,
             "pid": getattr(getattr(w, "proc", None), "pid", None),
             "dead": bool(w.dead),
             "jobs": sorted(worker_stats.get(w.worker_id, {})
                            .get("jobs", {}))}
            for w in self.workers
        ]
        # device profiling plane (common/profiling.py): per-qualname
        # dispatch telemetry + the cluster-wide HBM ledger. The ledger
        # consumes the ALREADY-federated per-job state-bytes snapshot
        # above (session-local jobs + every worker's), attributing each
        # job to the process that hosts its state.
        from ..common.profiling import GLOBAL_PROFILER, hbm_ledger
        obs = self.observability
        job_owner: dict = {name: None for name, job in self.jobs.items()
                           if job.pipeline is not None}
        for wid, st in sorted(worker_stats.items()):
            for name in st.get("state_bytes", {}):
                job_owner.setdefault(name, wid)
        ledger_jobs = {}
        for name, nb in out["state_bytes"].items():
            if isinstance(nb, dict):
                total = nb.get("_total", 0)
                executors = {k: v for k, v in nb.items() if k != "_total"}
            else:
                total, executors = int(nb), {}
            ledger_jobs[name] = {"bytes": int(total),
                                 "executors": executors,
                                 "worker": job_owner.get(name)}
        out["profiling"] = {
            "enabled": GLOBAL_PROFILER.enabled,
            "dispatch": GLOBAL_PROFILER.snapshot(),
            "hbm": hbm_ledger(ledger_jobs, obs.hbm_capacity_bytes,
                              GLOBAL_PROFILER.peak_temp_bytes(),
                              obs.hbm_warn_fraction),
            "workers": {wid: st["profiling"]
                        for wid, st in sorted(worker_stats.items())
                        if st.get("profiling")},
        }
        # live twin of common/dispatch_count.py: per-qualname dispatch
        # counts, with the one-dispatch-per-epoch invariants readable
        # (fused engines report dispatches ÷ epochs_run)
        dispatch = {"counts": GLOBAL_PROFILER.counts(), "per_epoch": {}}
        counts = dispatch["counts"]
        epochs_by_name = self._fused.epochs_by_qualname()
        for qn, epochs in epochs_by_name.items():
            if qn in counts and epochs:
                dispatch["per_epoch"][qn] = round(counts[qn] / epochs, 4)
        out["dispatch"] = dispatch
        return out

    def _pipeline_metrics(self) -> dict:
        from ..common.profiling import GLOBAL_PROFILER
        return {
            "depth": self.pipeline_depth,
            "pending_flushes": self._fused.pending_flushes(),
            **self._fused.pipeline_stats,
            **GLOBAL_PROFILER.pipeline_stats(),
        }

    def _storage_metrics(self) -> dict:
        """Storage-tier counters for metrics()/Prometheus/dashboard:
        version id, level shape, compaction + vacuum progress (reference:
        hummock manager metrics scraped from the meta node)."""
        mgr = getattr(self.store, "manager", None)
        if mgr is not None:             # hummock tier
            out = {"tier": "hummock", **mgr.stats,
                   "pinned_versions": len(mgr.pinned_versions()),
                   "inflight_compact_tasks": len(mgr.inflight_tasks())}
            if self.compactors:
                out["compactors"] = [
                    {"worker": c.worker_id, "dead": bool(c.dead)}
                    for c in self.compactors]
            return out
        log = getattr(self.store, "log", None)
        if log is not None:             # segment tier
            try:
                m = log._read_manifest()
                return {"tier": "segment",
                        "segments": len(m.get("segments", ())),
                        "committed_epoch": m.get("committed_epoch", 0)}
            except Exception:  # noqa: BLE001 - stats must never fail
                return {"tier": "segment"}
        return {"tier": "memory"}

    def _federate_worker_stats(self, force: bool = False,
                               timeout: float = 0.5) -> dict[int, dict]:
        """Poll every live worker's ``stats`` frame. Worker spans merge
        into the session's trace ring (tagged pid = worker_id + 1) and the
        per-worker snapshot refreshes ``self._worker_stats`` — a dead
        worker keeps its last snapshot for post-hoc inspection.

        Polls are rate-limited and short-fused: the caller holds the API
        lock, so a scrape storm (dashboard auto-refresh + Prometheus) or
        a hung-but-connected worker must not stall tick()/run_sql() on
        the driving thread for long."""
        if not self.workers or self.loop.is_running():
            return self._worker_stats
        import time as _time
        now = _time.monotonic()
        if not force and now - self._worker_stats_at < 0.5:
            return self._worker_stats
        from ..common.tracing import GLOBAL_TRACE

        async def _one(w):
            try:
                return (w.worker_id, await w.get_stats(
                    timeout=timeout,
                    span_ack=self._worker_span_ack.get(w.worker_id),
                    stage_ack=self._worker_stage_ack.get(w.worker_id)))
            except Exception:  # noqa: BLE001 - stats are best-effort
                return None

        async def _fetch() -> list:
            # concurrent: a hung worker costs one timeout, not one per
            # worker, while the caller holds the API lock
            got = await asyncio.gather(
                *(_one(w) for w in self.workers if not w.dead))
            return [g for g in got if g is not None]

        for wid, resp in self._await(_fetch()):
            GLOBAL_TRACE.ingest(resp.pop("spans", []) or [], pid=wid + 1)
            seq = resp.pop("span_seq", None)
            if seq is not None:
                self._worker_span_ack[wid] = seq
            # barrier observatory: the worker's epoch-stamped stage
            # events (storage prepare/settle/commit, worker collect)
            # attach to their waterfall records in the history ring —
            # re-ingesting a resent batch only re-sums an epoch already
            # evicted from the ring, so ack discipline keeps it exact
            stage_seq = resp.pop("stage_seq", None)
            events = resp.pop("barrier_stages", []) or []
            if stage_seq is not None \
                    and stage_seq != self._worker_stage_ack.get(wid):
                self._barrier_ledger.ingest_events(events, worker=wid)
            if stage_seq is not None:
                self._worker_stage_ack[wid] = stage_seq
            self._worker_stats[wid] = resp
        self._worker_stats_at = _time.monotonic()
        return self._worker_stats

    @_locked
    def await_tree(self) -> str:
        """Federated await-tree dump: local jobs walked in-process plus
        every worker-hosted job's tree over the stats RPC — "the
        await-tree of a worker-hosted job, visible over HTTP while it
        runs" (reference: risectl trace / dashboard await-tree,
        monitor_service.rs:46)."""
        from ..stream.trace import dump_session
        self._federate_worker_stats()
        return dump_session(self)

    @_locked
    def export_chrome_trace(self, path: Optional[str] = None) -> dict:
        """Chrome trace-event JSON of the span ring (Perfetto-loadable):
        epochs on the conductor track, executors on their own tracks,
        workers as separate processes. Optionally written to ``path``."""
        from ..common.tracing import GLOBAL_TRACE, export_chrome_trace
        self._federate_worker_stats()    # pull workers' latest spans
        return export_chrome_trace(
            GLOBAL_TRACE.snapshot(), path=path,
            barrier_records=self._barrier_ledger.history())

    @_locked
    def slow_epochs(self) -> list:
        """Captured slow-epoch span trees (newest last), each
        ``{epoch, latency_ms, checkpoint, spans}``."""
        return list(self._slow_epochs)

    @_locked
    def barrier_blame(self) -> list:
        """Name who is holding up every in-flight barrier, NOW.

        Walks the live per-epoch accounting — local jobs' barrier
        events, every RemoteWorker's epoch events + per-job failure
        maps, and the federated per-exchange-edge counters (whose
        ``last_barrier_epoch`` says how far the barrier propagated on
        each link) — and returns one finding per suspect:

          {"epoch", "checkpoint", "age_ms", "kind", "job", "worker",
           "fragment", "actor", "link", "edge", "reason"}

        ``kind`` is ``local_job`` / ``worker`` / ``exchange_edge``. An
        exchange finding names the CONSUMER actor of the starved edge
        (parsed from the ``job:f<u>.<i>->f<d>.<j>`` edge id, resolved
        to its worker via the persisted placement), which is exactly
        the actor a partitioned link stops feeding — diagnosis by name
        within one tick, instead of waiting for the epoch-deadline
        recovery to kill the worker. Stats frames are chaos-META, so
        federation works through data-plane partitions. Empty list ⇔
        nothing in flight or everything already acked."""
        import re as _re
        from ..common.tracing import now_ns
        findings: list = []
        if not self._inflight:
            return findings
        # best-effort refresh of exchange counters; stats frames bypass
        # chaos partitions (rpc/faults.META_FRAME_TYPES)
        worker_stats = self._federate_worker_stats(force=True)
        edge_re = _re.compile(
            r"^(?P<job>.+):f(?P<uf>\d+)\.(?P<ua>\d+)"
            r"->f(?P<df>\d+)\.(?P<da>\d+)$")
        for epoch, ckpt in self._inflight:
            t0 = self._inject_time.get(epoch)
            age_ms = ((now_ns() - t0) / 1e6 if t0 is not None else None)

            def _add(kind, reason, job=None, worker=None, fragment=None,
                     actor=None, link=None, edge=None,
                     _epoch=epoch, _ckpt=ckpt, _age=age_ms):
                findings.append({
                    "epoch": _epoch, "checkpoint": bool(_ckpt),
                    "age_ms": _age, "kind": kind, "job": job,
                    "worker": worker, "fragment": fragment,
                    "actor": actor, "link": link, "edge": edge,
                    "reason": reason,
                })
            # local in-process jobs: the barrier event is set when the
            # barrier flows out of the pipeline's Materialize
            for name, job in self.jobs.items():
                ev_map = getattr(job, "_barrier_events", None)
                if ev_map is None:
                    continue          # RemoteJob/SpanningJob: below
                if getattr(job, "_failure", None) is not None:
                    _add("local_job", f"job failed: "
                         f"{type(job._failure).__name__}: {job._failure}",
                         job=name, worker=-1)
                    continue
                ev = ev_map.get(epoch)
                if ev is None or not ev.is_set():
                    _add("local_job", "barrier not yet emitted by "
                         "pipeline", job=name, worker=-1)
            # worker processes: epoch acks + per-job failure maps
            for w in self.workers:
                if w.dead:
                    _add("worker", "worker marked dead",
                         worker=w.worker_id, link=w.link)
                    continue
                errs = w._epoch_errors.get(epoch) or {}
                for jname, err in sorted(errs.items()):
                    _add("worker", f"job error: {err}",
                         job=None if jname == "*" else jname,
                         worker=w.worker_id, link=w.link)
                ev = w._epoch_events.get(epoch)
                if ev is None or not ev.is_set():
                    _add("worker", "barrier not acked by worker",
                         worker=w.worker_id, link=w.link)
            # exchange edges: an "in" edge whose last seen barrier lags
            # the in-flight epoch is starving its consumer actor
            for wid, st in sorted(worker_stats.items()):
                for e in st.get("exchange", ()) or ():
                    if e.get("dir") != "in":
                        continue
                    if int(e.get("last_barrier_epoch") or 0) >= epoch:
                        continue
                    m = edge_re.match(e.get("edge", ""))
                    job = frag = act = None
                    if m:
                        job = m.group("job")
                        frag = int(m.group("df"))
                        act = int(m.group("da"))
                    peer = e.get("peer_worker")
                    link = (f"w{peer}->w{wid}"
                            if peer is not None else None)
                    _add("exchange_edge",
                         "barrier missing on exchange edge "
                         f"(last seen epoch "
                         f"{e.get('last_barrier_epoch')})",
                         job=job, worker=wid, fragment=frag, actor=act,
                         link=link, edge=e.get("edge"))
        return findings

    def profile_report(self) -> dict:
        """Roofline report over every dispatch this process has seen:
        AOT-``lower().compile()`` each recorded epoch callable (chip-free
        on the CPU stand-in) and place its arithmetic intensity against
        the chip peaks ([observability] chip_peak_flops /
        chip_peak_bandwidth, else by the attached device's kind — an
        unknown kind raises UnknownChipError). Triggers compiles, so it deliberately does
        NOT take the session API lock — the profiler registry it reads
        has its own lock, and ticks/scrapes must not stall behind XLA."""
        from ..common.profiling import (
            GLOBAL_PROFILER, chip_peaks, roofline_report,
        )
        peak_flops, peak_bw = chip_peaks(
            self.observability.chip_peak_flops,
            self.observability.chip_peak_bandwidth)
        return roofline_report(GLOBAL_PROFILER.analyze(),
                               peak_flops, peak_bw)

    @_locked
    def close(self) -> None:
        """Graceful shutdown: stop all stream jobs, close sinks, close the
        session loop. A closed session cannot be reused."""
        if self.loop.is_closed():
            return
        self._serving.shutdown()      # stop the batch-task pool first
        if not self._fenced:
            self._drain_inflight()
        self.store.join_commits()     # deferred checkpoint encode lands
        for job in list(self.jobs.values()):
            sink = getattr(job.pipeline, "sink", None)
            if sink is not None:
                sink.close()
        jobs = list(self.jobs.values())

        async def _stop_all():
            # the gather future must be created INSIDE the session loop
            await asyncio.gather(*(job.stop() for job in jobs),
                                 return_exceptions=True)
            # abandoned per-input reader tasks (barrier_align / merge
            # recv futures) only PROCESS their cancellation on a later
            # loop tick; give them those ticks now or their queue.get
            # coroutines get GC-finalized after loop.close()
            for _ in range(3):
                await asyncio.sleep(0)

        self._await(_stop_all())
        self.jobs.clear()
        t = self._compaction_pump
        if t is not None and t.is_alive():
            t.join(timeout=30)
        for c in self.compactors:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001 - already dying
                pass
        self.compactors = []
        for w in self.workers:
            try:
                self._await(w.shutdown())
                self._await(w.aclose())
            except Exception:  # noqa: BLE001 - already dying
                pass
            w.terminate()
        self.workers = []
        # finalize abandoned executor generators (reschedule/stop leave
        # their `execute()` async generators suspended in `queue.get()`)
        # while the loop is still alive — if GC ran after loop.close(),
        # the asyncgen finalizer hook would call_soon on a closed loop and
        # trip "Event loop is closed" in asyncio.Queue's finalizer. Collect
        # FIRST (dropped generators finalize through the hook, scheduling
        # acloses), give those acloses loop ticks to run, then shut down
        # whatever generators are still referenced.
        import gc
        gc.collect()

        async def _drain_finalizers():
            for _ in range(10):
                await asyncio.sleep(0)

        self.loop.run_until_complete(_drain_finalizers())
        self.loop.run_until_complete(self.loop.shutdown_asyncgens())
        self.loop.close()
        # detach from a remote meta last: observers above may still have
        # been delivering (the in-process MetaService has no close)
        meta_close = getattr(self.meta, "close", None)
        if meta_close is not None:
            try:
                meta_close()
            except Exception:  # noqa: BLE001 - already dying
                pass

    def _bump_generation(self) -> None:
        """Advance the session-generation fencing token (persisted in
        the meta store, propagated to every worker handle). Called at
        the top of every scoped recovery, after in-flight epochs
        drained: from here on, frames from the pre-recovery incarnation
        are stale and are refused on both sides of the wire."""
        self._generation += 1
        self.meta.store.put("session_generation", str(self._generation))
        for w in self.workers:
            w.generation = self._generation

    def _alloc_shard(self) -> int:
        self._next_shard += 1
        return self._next_shard - 1

    def _await(self, coro):
        if self.loop.is_running():
            raise RuntimeError("Session API is synchronous; do not call from "
                               "inside the event loop")
        return self.loop.run_until_complete(coro)
