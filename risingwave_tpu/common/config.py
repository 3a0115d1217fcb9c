"""Layered configuration + runtime-mutable system parameters.

Counterpart of the reference's config system and system params
(reference: src/common/src/config.rs:128-634 — ``RwConfig`` sections with
defaults-in-code so absent keys stay version-stable;
src/common/src/system_param/mod.rs — cluster params mutable at runtime and
propagated to all nodes). Layering: defaults-in-code → TOML file →
explicit overrides; unknown keys are rejected loudly (the reference warns;
we fail fast since there is no compatibility surface yet).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional


class MeshUnavailableError(RuntimeError):
    """A requested or persisted device-mesh topology needs more devices
    than the process has. Raised loudly instead of silently degrading to
    a single-chip layout (frontend/build.py config_from_json,
    parallel/sharded_agg.py make_mesh): recovering a mesh-sharded job
    without its mesh would quietly fall back to an unsharded plan. Either
    restart with enough devices (on CPU:
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``) or re-shard
    explicitly (``config_from_json(..., allow_reshard=True)`` — the
    sharded state re-shards by replaying the vnode mapping on load)."""


@dataclasses.dataclass
class StreamingConfig:
    # reference: config.rs streaming section + system params
    barrier_interval_ms: int = 1000
    checkpoint_frequency: int = 10
    in_flight_barrier_nums: int = 1
    chunk_capacity: int = 1024
    agg_table_capacity: int = 1 << 16
    join_key_capacity: int = 1 << 13
    join_bucket_width: int = 16
    # rows of a join side's fan-out arena: the side a join key may hold
    # many rows of (its stream key is not inside the join key) moves
    # there when a key outgrows the bucket width (ops/join_state.py)
    join_row_capacity: int = 1 << 16
    topn_table_capacity: int = 1 << 16
    # actor parallelism per fragmentable operator (grouped aggs, joins):
    # >1 builds multi-fragment jobs with hash-dispatch exchanges
    # (frontend/fragments.py; reference: streaming.default_parallelism)
    fragment_parallelism: int = 1
    # epoch co-scheduling (stream/coschedule.py): eligible MVs (NEXmark
    # bid source → projection → grouped agg) created while this is true
    # are batched into ONE fused XLA dispatch per tick for the whole
    # group instead of one executor pipeline each; ineligible shapes
    # fall back to the solo executor path (docs/performance.md)
    coschedule: bool = False
    # the heterogeneous tick compiler (stream/tick_compiler.py):
    # eligible MVs created while this is true join a compiled dispatch
    # schedule — jobs sharing an operator skeleton pad into shape-class
    # supergroups (one vmapped dispatch per class), the rest
    # concatenate into jitted mega-epochs — so N dissimilar small MVs
    # tick in a handful of dispatches instead of N. Recompiled only on
    # DDL; takes precedence over ``coschedule`` for eligible shapes.
    tick_compiler: bool = False
    # device mesh for the mesh-sharded paths (parallel/): N >= 1 builds a
    # 1-D mesh over the first N local devices (BuildConfig.mesh) so
    # grouped aggs/joins shard across chips — and, with ``coschedule``
    # on, eligible fused MVs take the mesh-sharded fused-epoch path
    # (ops/fused_sharded.py): one dispatch per epoch across ALL chips.
    # Refuses loudly (MeshUnavailableError) when the process has fewer
    # devices. 0/None = single-chip.
    mesh_shape: Optional[int] = None
    # asynchronous epoch pipeline (docs/performance.md "Pipelined
    # tick"): 1 = the classic synchronous cycle (every fused flush
    # resolves in its own tick); 2 = double-buffered epochs — each
    # epoch's packed flush fetch defers across the tick boundary (the
    # copy streams while the previous barrier's host work runs, so
    # resolving it next tick is nearly free) and epoch N+1's dispatch
    # launches before epoch N's flush chunks are decoded/materialized,
    # so that host work + the checkpoint encode overlap device
    # compute. State threads on-device, so results are
    # bit-exact; reads simply see the previous barrier's snapshot
    # between drain points (checkpoint barriers, FLUSH, DDL). Applies
    # to the fused surfaces (coschedule/shardfused) and moves the
    # durable checkpoint encode off the barrier path.
    pipeline_depth: int = 1
    # LEGACY aliases of [observability] trace_ring_capacity /
    # slow_epoch_threshold_ms (kept so existing configs keep working;
    # an explicitly-set [observability] value wins — see
    # ObservabilityConfig below)
    trace_ring_capacity: int = 16384
    slow_epoch_threshold_ms: float = 0.0


@dataclasses.dataclass
class StorageConfig:
    data_dir: Optional[str] = None          # None = RAM-only playground
    segment_target_bytes: int = 4 << 20
    # durable-tier backend: "segment" = epoch-delta log + in-process fold
    # (storage/checkpoint.py); "hummock" = L0 SSTs under a meta-managed
    # version with a compactor role (storage/hummock.py). None = AUTO:
    # recovery detects an existing dir's tier; a new dir gets "segment".
    # The default must stay None — a concrete default would be
    # indistinguishable from an explicit choice and would silently open
    # an existing hummock dir as a fresh segment store.
    state_store: Optional[str] = None
    # dedicated compactor worker processes (hummock tier only; 0 keeps
    # compaction on an in-process background thread)
    compactors: int = 0


@dataclasses.dataclass
class BatchConfig:
    """Serving-plane knobs for batch reads (frontend/serving.py;
    reference capability: the batch section + frontend query caches of
    src/common/src/config.rs — distributed query execution and the
    per-frontend plan caches)."""

    # version-pinned plan+compilation cache: entries keyed on the
    # statement's canonical form; an entry survives data-version bumps
    # (it re-executes against the new snapshot WITHOUT replanning or new
    # jit compilations) and is evicted LRU past this bound. 0 disables.
    serving_cache_size: int = 64
    # two-phase distributed aggregation: number of per-vnode-slice
    # partial tasks a local grouped agg splits into (clamped to the
    # vnode count; 0/1 keeps single-phase execution)
    serving_tasks: int = 4
    # thread pool executing local partial tasks (BatchTaskManager)
    serving_threads: int = 4
    # optimistic concurrent reads: attempts to observe a quiescent data
    # version before falling back to the API-locked path
    serving_read_retries: int = 32


@dataclasses.dataclass
class FaultConfig:
    """Fault-tolerance knobs for every external boundary (common/retry.py,
    storage/object_store.py, connector/broker.py, stream/sink.py,
    frontend/remote.py). Reference capability: object-store retry config +
    sink retry/decouple knobs (src/common/src/config.rs storage.object
    retry section; sink decouple system params)."""

    # object-store IO retry (RetryingObjectStore under hummock/segment/
    # compactor/backup)
    io_retry_attempts: int = 5
    io_retry_base_ms: float = 10.0
    io_retry_max_ms: float = 1000.0
    io_retry_deadline_ms: float = 30_000.0
    # sink delivery retry + degrade (stream/sink.py): past
    # ``sink_degrade_after`` consecutive failed epochs the sink job
    # degrades (log accumulates, barriers keep committing) instead of
    # failing the epoch; past ``sink_log_cap_rows`` logged-undelivered
    # rows it fails loudly (bounded-log backpressure)
    sink_retry_attempts: int = 3
    sink_retry_base_ms: float = 20.0
    sink_retry_deadline_ms: float = 2000.0
    sink_degrade_after: int = 3
    sink_log_cap_rows: int = 1_000_000
    # broker client reconnect-with-backoff (connector/broker.py)
    broker_reconnect_attempts: int = 6
    broker_reconnect_base_ms: float = 25.0
    broker_reconnect_max_ms: float = 1000.0
    # worker control-frame deadlines (frontend/remote.py): a wedged
    # worker trips these instead of hanging the session forever
    worker_request_timeout_s: float = 120.0
    worker_epoch_timeout_s: float = 300.0
    # idle-link keepalive on worker↔worker exchange sockets
    # (rpc/exchange.py): a half-open peer socket — peer died without a
    # FIN, or a severed link — is probed with exg_ping and declared
    # broken after ``exchange_keepalive_timeout_s`` without a pong, so
    # the pool evicts it BEFORE the next epoch's send burns a permit on
    # a doomed frame. 0 disables probing.
    exchange_keepalive_s: float = 10.0
    exchange_keepalive_timeout_s: float = 5.0
    # seeded object-store fault injection (tests / sim chaos only)
    inject_object_store_transient_rate: float = 0.0
    inject_object_store_torn_write_rate: float = 0.0
    inject_object_store_seed: int = 0

    def io_retry_policy(self):
        from .retry import RetryPolicy
        from ..storage.object_store import PermanentObjectStoreError
        return RetryPolicy(
            max_attempts=self.io_retry_attempts,
            base_delay_ms=self.io_retry_base_ms,
            max_delay_ms=self.io_retry_max_ms,
            deadline_ms=self.io_retry_deadline_ms,
            retryable=(OSError, ConnectionError, TimeoutError),
            non_retryable=(PermanentObjectStoreError,))

    def sink_retry_policy(self):
        from .retry import RetryPolicy
        return RetryPolicy(
            max_attempts=self.sink_retry_attempts,
            base_delay_ms=self.sink_retry_base_ms,
            max_delay_ms=max(self.sink_retry_base_ms * 8, 250.0),
            deadline_ms=self.sink_retry_deadline_ms,
            retryable=(Exception,))

    def broker_retry_policy(self):
        from .retry import RetryPolicy
        return RetryPolicy(
            max_attempts=self.broker_reconnect_attempts,
            base_delay_ms=self.broker_reconnect_base_ms,
            max_delay_ms=self.broker_reconnect_max_ms,
            retryable=(OSError, ConnectionError, TimeoutError))


@dataclasses.dataclass
class UdfConfig:
    """Out-of-process UDF plane knobs (udf/client.py, docs/robustness.md
    "UDF isolation plane"; reference capability: the Arrow-Flight UDF
    boundary of src/udf/src/lib.rs — user code behind a wire so it can
    never wedge an epoch). Registered UDFs evaluate in a dedicated
    server PROCESS over the rpc/wire.py frame protocol; the client side
    enforces per-call deadlines, kill + seeded respawn + bounded-retry
    batch replay, generation fencing, and bounded in-flight batches."""

    #: "process" = out-of-process evaluation (the default robustness
    #: contract); "inproc" = the documented DEGRADED mode — user code
    #: runs inside the calling process on the tick path (tests, or
    #: environments that cannot spawn subprocesses)
    mode: str = "process"
    #: attach to an already-running server ("host:port", e.g. one
    #: started with `ctl udf serve`) instead of auto-spawning; the
    #: client cannot kill an external server, so crash recovery
    #: degrades to reconnect-and-replay
    addr: Optional[str] = None
    #: per-call deadline: a batch whose reply misses it is treated as a
    #: wedged/crashed server — kill, respawn, replay (bounded below)
    call_timeout_s: float = 10.0
    #: deadline on server spawn + registration replay
    spawn_timeout_s: float = 30.0
    #: bounded-retry replay: attempts per batch beyond the first (each
    #: retry respawns the server); exhausted retries surface a typed
    #: UdfTimeoutError/UdfCallError that fails the STATEMENT, never the
    #: epoch loop
    max_retries: int = 2
    #: backpressure: batches admitted into the boundary concurrently;
    #: excess callers wait up to queue_timeout_s then fail typed
    #: (UdfOverloadedError) instead of queueing unboundedly
    max_inflight: int = 4
    queue_timeout_s: float = 30.0


@dataclasses.dataclass
class AutoscalerConfig:
    """Backlog-driven autoscaler policy (meta/autoscaler.py): watches
    the per-edge exchange counters (permits_waited, backlog —
    rpc/exchange.py EdgeStats) and the slow-epoch detector
    (common/tracing.py) and grows/shrinks a spanning job's fragment
    parallelism by issuing live rescale plans (meta/rescale.py,
    docs/scaling.md). Hysteresis + cooldown keep it from flapping under
    oscillating load; all thresholds are per observation (one barrier
    tick)."""

    enabled: bool = False
    # scale-OUT triggers: any one sustained for ``hysteresis``
    # consecutive observations fires target = parallelism * 2
    high_backlog: int = 64            # queued chunks across the job's edges
    high_permits_waited: int = 16     # new permit waits since last observe
    high_slow_epochs: int = 1         # slow-epoch detections since last
    # scale-IN: ALL load signals at/below these for ``scale_in_after``
    # consecutive observations fires target = parallelism // 2
    low_backlog: int = 0
    low_permits_waited: int = 0
    # consecutive high observations required before scaling out
    hysteresis: int = 3
    # observations after ANY decision during which no new decision may
    # fire (and streaks reset) — the anti-flap guard
    cooldown: int = 16
    # consecutive all-quiet observations required before scaling in
    # (deliberately >> hysteresis: scale-in re-migrates state, so it
    # must be much lazier than scale-out)
    scale_in_after: int = 32
    min_parallelism: int = 1
    max_parallelism: int = 8


@dataclasses.dataclass
class ObservabilityConfig:
    """Device profiling plane + tracing knobs (common/profiling.py,
    common/tracing.py, docs/observability.md). Reference capability:
    the monitor-service profiling handlers + streaming metrics config
    (src/compute/src/rpc/service/monitor_service.rs)."""

    # per-dispatch telemetry (DispatchProfiler): wall seconds, recompile
    # events, trace-ring spans for every profiled dispatch site. Pure
    # host bookkeeping — adds zero dispatches (CI-guarded); off turns
    # the wrappers into passthroughs.
    profiling: bool = True
    # dispatch spans shorter than this skip the trace ring (0 = record
    # every dispatch; the ring is bounded either way)
    dispatch_span_min_ms: float = 0.0
    # span ring + slow-epoch detector — the canonical home of the knobs
    # that used to live only on [streaming] (which still works as a
    # legacy alias). Unset (None) inherits the alias; ANY value set
    # here wins, including one equal to the alias default (effective
    # defaults: 16384 spans — 400 barriers at up to 40 spans each, see
    # docs/observability.md "Sizing the ring" — and 0.0 = detector off)
    trace_ring_capacity: Optional[int] = None
    slow_epoch_threshold_ms: Optional[float] = None
    # barrier observatory (common/barrier_ledger.py): how many sealed
    # per-barrier waterfall records the history ring retains
    # (rw_catalog.rw_barrier_history, ctl trace barrier)
    barrier_history_capacity: int = 256
    # slow-epoch capture ring: how many offending epochs' span-tree +
    # waterfall captures Session.slow_epochs() retains (was a hardcoded
    # 16 before the [observability] knob existed)
    slow_epoch_capture_capacity: int = 16
    # cluster-wide HBM ledger: resident state + analyzed peak temp
    # bytes are charged against this capacity (default 16 GiB ≈ one
    # v5e chip); a job reaching hbm_warn_fraction of it is flagged
    hbm_capacity_bytes: int = 16 << 30
    hbm_warn_fraction: float = 0.8
    # roofline model peaks (ctl profile roofline): chip peak FLOP/s and
    # HBM bandwidth in bytes/s. Unset (None) = looked up by the attached
    # device's ``device_kind`` in common/profiling.CHIP_PEAKS (v5e:
    # 197 TFLOP/s bf16, 819 GB/s); an unknown kind is an error there,
    # not a default
    chip_peak_flops: Optional[float] = None
    chip_peak_bandwidth: Optional[float] = None


@dataclasses.dataclass
class MetaConfig:
    """The meta control plane attachment + frontend admission knobs
    (docs/control-plane.md; reference: src/meta/src/rpc/server.rs).

    ``addr`` empty means in-process meta — the playground default, with
    behavior bit-identical to before the control plane grew a process
    boundary. Set it (``host:port``) and the session attaches through a
    ``MetaClient`` instead; combined with ``Session(role="serving")``
    that is how a frontend fleet shares one writer's state."""

    #: "host:port" of a `ctl meta serve` process; "" = in-process meta
    addr: str = ""
    #: pgwire admission control: max queries executing concurrently per
    #: frontend process (the rest queue), and per-connection in-flight cap
    admission_max_inflight: int = 8
    admission_per_conn_inflight: int = 2
    #: queries allowed to WAIT beyond the in-flight cap before the
    #: frontend sheds load with a PG error (bounded queue: overload
    #: degrades with bounded p99 instead of collapsing)
    admission_queue_depth: int = 64
    #: leader lease TTL: a writer missing this many seconds of
    #: heartbeats is declared down and standbys elect (bounds failover
    #: MTTR from above; too low and a long GC pause looks like death)
    lease_ttl_s: float = 2.0
    #: writer heartbeat period; keep well under lease_ttl_s so several
    #: consecutive renewals must fail before the lease expires
    heartbeat_s: float = 0.5
    #: per-candidate jitter cap before racing lease.acquire on
    #: leader_down — spreads CAS attempts without delaying the winner
    #: by more than this
    election_backoff_ms: float = 100.0


@dataclasses.dataclass
class ServerConfig:
    host: str = "127.0.0.1"
    port: int = 4566


@dataclasses.dataclass
class RwConfig:
    server: ServerConfig = dataclasses.field(default_factory=ServerConfig)
    streaming: StreamingConfig = dataclasses.field(
        default_factory=StreamingConfig)
    storage: StorageConfig = dataclasses.field(default_factory=StorageConfig)
    batch: BatchConfig = dataclasses.field(default_factory=BatchConfig)
    fault: FaultConfig = dataclasses.field(default_factory=FaultConfig)
    autoscaler: AutoscalerConfig = dataclasses.field(
        default_factory=AutoscalerConfig)
    observability: ObservabilityConfig = dataclasses.field(
        default_factory=ObservabilityConfig)
    udf: UdfConfig = dataclasses.field(default_factory=UdfConfig)
    meta: MetaConfig = dataclasses.field(default_factory=MetaConfig)


def _parse_toml_subset(text: str) -> dict:
    """Fallback parser for the config-file TOML subset (``[section]`` +
    scalar ``key = value`` lines) on interpreters without ``tomllib``
    (< 3.11, no vendored tomli). Enough for every rw_config knob: ints,
    floats, bools, quoted strings."""
    data: dict = {}
    section: dict = data
    for raw in text.splitlines():
        # strip comments, but only a '#' OUTSIDE quotes starts one
        line = raw
        quote = None
        for i, ch in enumerate(raw):
            if quote:
                if ch == quote:
                    quote = None
            elif ch in "'\"":
                quote = ch
            elif ch == "#":
                line = raw[:i]
                break
        line = line.strip()
        if not line:
            continue
        if line.startswith("[") and line.endswith("]"):
            section = data.setdefault(line[1:-1].strip(), {})
            continue
        key, sep, val = line.partition("=")
        if not sep:
            raise ValueError(f"unparseable config line: {raw!r}")
        key, val = key.strip(), val.strip()
        if val.startswith(("'", '"')) and val.endswith(val[0]):
            section[key] = val[1:-1]
        elif val in ("true", "false"):
            section[key] = val == "true"
        else:
            try:
                section[key] = int(val)
            except ValueError:
                section[key] = float(val)
    return data


def load_config(path: Optional[str] = None, **overrides: Any) -> RwConfig:
    """defaults ← TOML file ← dotted-key overrides
    (e.g. ``load_config("rw.toml", **{"streaming.checkpoint_frequency": 4})``)."""
    cfg = RwConfig()
    if path is not None:
        try:
            import tomllib
        except ModuleNotFoundError:
            tomllib = None
        if tomllib is not None:
            with open(path, "rb") as f:
                data = tomllib.load(f)
        else:
            with open(path, "r", encoding="utf-8") as f:
                data = _parse_toml_subset(f.read())
        for section, values in data.items():
            _apply_section(cfg, section, values)
    for dotted, v in overrides.items():
        section, _, key = dotted.partition(".")
        if not key:
            raise ValueError(f"override key must be section.key: {dotted!r}")
        _apply_section(cfg, section, {key: v})
    return cfg


def _apply_section(cfg: RwConfig, section: str, values: dict) -> None:
    target = getattr(cfg, section, None)
    if target is None or not dataclasses.is_dataclass(target):
        raise ValueError(f"unknown config section {section!r}")
    names = {f.name for f in dataclasses.fields(target)}
    for k, v in values.items():
        if k not in names:
            raise ValueError(f"unknown config key {section}.{k}")
        setattr(target, k, v)


# -- system params (runtime-mutable; reference: system_param/mod.rs) ---------

#: params a live session accepts via SET; value = coercion fn
MUTABLE_SYSTEM_PARAMS = {
    "checkpoint_frequency": int,
    "barrier_interval_ms": int,
    "in_flight_barrier_nums": int,
    "slow_epoch_threshold_ms": float,
}
