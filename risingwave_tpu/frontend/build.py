"""Executor-graph builder: plan tree → wired executor pipeline.

Counterpart of the reference's create_executor dispatch
(reference: src/stream/src/from_proto/mod.rs:119-165 — proto plan node →
executor, recursively). The builder also allocates state tables for every
stateful operator (the reference's fragmenter fills internal-table ids,
src/meta/src/stream/stream_graph/fragment.rs:258).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

from ..common.types import Field, INT64, Schema
from ..expr.expr import InputRef
from ..ops.join_state import JoinType
from ..storage.state_store import MemoryStateStore
from ..storage.state_table import StateTable
from ..stream.dynamic_filter import DynamicFilterExecutor
from ..stream.eowc import SortExecutor
from ..stream.executor import Executor, SingleInputExecutor
from ..stream.hash_agg import HashAggExecutor, agg_state_schema
from ..stream.hash_join import HashJoinExecutor
from ..stream.hop_window import HopWindowExecutor
from ..stream.materialize import MaterializeExecutor
from ..stream.project import FilterExecutor, ProjectExecutor
from ..stream.simple_agg import SimpleAggExecutor
from ..stream.top_n import TopNExecutor
from ..stream.union import UnionExecutor
from . import planner as P
from .runtime import QueueSource

_JOIN_TYPES = {
    "inner": JoinType.INNER, "left": JoinType.LEFT_OUTER,
    "right": JoinType.RIGHT_OUTER, "full": JoinType.FULL_OUTER,
    "left_semi": JoinType.LEFT_SEMI, "left_anti": JoinType.LEFT_ANTI,
}


@dataclasses.dataclass
class BuildConfig:
    chunk_capacity: int = 1024
    agg_table_capacity: int = 1 << 16
    join_key_capacity: int = 1 << 13
    join_bucket_width: int = 16
    join_row_capacity: int = 1 << 16
    topn_table_capacity: int = 1 << 16
    # Data parallelism: a jax.sharding.Mesh routes grouped aggs and joins
    # through the mesh-sharded executors (parallel/executors.py); None keeps
    # every operator single-chip. Capacities above are per shard when set.
    mesh: Optional[object] = None
    # Pipeline parallelism via the dispatch fabric (stream/dispatch.py):
    # >1 builds grouped aggs as MULTI-FRAGMENT jobs — the upstream fragment
    # hash-dispatches over PermitChannels to N parallel agg actors whose
    # outputs merge-fan-in (reference: fragments + exchanges,
    # dispatch.rs:532 / merge.rs:114). Orthogonal to ``mesh`` (host actor
    # concurrency vs device sharding); ignored for batch builds.
    fragment_parallelism: int = 1
    exchange_permits: int = 32
    # Epoch co-scheduling (stream/coschedule.py): CREATE MATERIALIZED
    # VIEW routes eligible source+agg plans into a fused multi-job
    # dispatch group — K co-scheduled MVs tick in ONE jit dispatch.
    # Opt-in ([streaming] coschedule = true); ineligible shapes build
    # the normal executor pipeline.
    coschedule: bool = False
    # The heterogeneous tick compiler (stream/tick_compiler.py):
    # eligible MVs join a compiled dispatch schedule — shape-class
    # padded supergroups plus jitted mega-epochs — so DISSIMILAR small
    # MVs fuse too. Opt-in ([streaming] tick_compiler = true); wins
    # over ``coschedule`` for eligible shapes.
    tick_compiler: bool = False
    # HBM pressure: cap on live groups per grouped-agg executor; coldest
    # groups evict to the state table at checkpoints and fault back in on
    # access (reference: cache/managed_lru.rs). None = grow-or-raise.
    agg_hbm_budget: Optional[int] = None
    # HBM pressure for joins: cap on live join KEYS per arena; coldest
    # keys' buckets evict from BOTH sides to the state tables at
    # checkpoints and fault back on mention (reference: JoinHashMap's
    # ManagedLruCache, managed_state/join/mod.rs:228-258).
    join_hbm_budget: Optional[int] = None
    # max snapshot rows per barrier during concurrent backfill
    # (stream/backfill.py); None = max(4 * chunk capacity, 4096)
    backfill_batch_rows: Optional[int] = None
    # wrap every built executor with the logical sanitizers (schema /
    # epoch / update-pair checks — reference:
    # src/stream/src/executor/wrapper/); debug & sim runs, off in prod
    sanity_checks: bool = False


def join_state_pk(join_keys, stream_pk) -> list:
    """Join state tables lay their pk out as join_keys ++ stream_pk: rows
    of one join key are contiguous in key order, so cold-tier fault-in is
    a pk prefix scan (the reference's JoinHashMap tables are likewise
    keyed join-key-first, managed_state/join/mod.rs)."""
    return list(join_keys) + [i for i in stream_pk if i not in join_keys]


def fanout_row_key(join_keys, stream_pk):
    """A join side's row key where one join key may hold many rows — its
    stream key is known and not inside the join key (a source's rows, a
    GROUP BY wider than the join key) — else None: a side whose stream
    key lies inside its join key holds at most one live row a key and
    keeps the bucket arena (ops/join_state.py "Side layouts")."""
    if not stream_pk or set(stream_pk) <= set(join_keys):
        return None
    return tuple(join_state_pk(join_keys, stream_pk))


class BuildContext:
    """Per-job build state: allocated sources and state tables.

    ``source_factory(plan_node) -> Executor`` supplies the leaves — the
    Session passes a factory that creates queue-fed sources for streaming
    jobs or snapshot replays for batch queries."""

    def __init__(
        self,
        store: MemoryStateStore,
        next_table_id: Callable[[], int],
        source_factory: Callable[[P.PlanNode], Executor],
        config: Optional[BuildConfig] = None,
        durable: bool = True,
        vnode_range: Optional[tuple] = None,
    ):
        self.store = store
        self.next_table_id = next_table_id
        self.source_factory = source_factory
        self.config = config or BuildConfig()
        self.durable = durable
        # (vnode_start, vnode_end) owned by a SPANNING fragment actor:
        # stateful executors reload only rows in this range, so a store
        # holding ranges that migrated away (meta/rescale.py) never
        # resurrects them into device state
        self.vnode_range = vnode_range
        self.state_table_ids: list[int] = []
        # actor coroutine factories for multi-fragment builds; the
        # StreamJob spawns one task per entry alongside the root pipeline
        self.actors: list = []

    def state_table(self, schema: Schema, pk) -> Optional[StateTable]:
        if not self.durable:
            return None
        tid = self.next_table_id()
        self.state_table_ids.append(tid)
        return StateTable(self.store, tid, schema, list(pk))


def build_plan(plan: P.PlanNode, ctx: BuildContext) -> Executor:
    """Build one plan node (recursively); with ``cfg.sanity_checks`` every
    built executor is wrapped in the logical sanitizers, mirroring the
    reference's WrapperExecutor around every actor node
    (src/stream/src/task/stream_manager.rs WrapperExecutor +
    executor/wrapper/{schema_check,epoch_check,update_check}.rs)."""
    ex = _build_plan(plan, ctx)
    if ctx.config.sanity_checks:
        from ..stream.executor import (
            EpochCheckExecutor, SchemaCheckExecutor, UpdateCheckExecutor,
        )
        ex = SchemaCheckExecutor(UpdateCheckExecutor(EpochCheckExecutor(ex)))
    return ex


def _build_plan(plan: P.PlanNode, ctx: BuildContext) -> Executor:
    cfg = ctx.config
    if isinstance(plan, (P.PSource, P.PTableScan, P.PMvScan, P.PValues,
                         P.PRemoteFragment, P.PExchange)):
        return ctx.source_factory(plan)

    if isinstance(plan, P.PProject):
        inp = build_plan(plan.input, ctx)
        return ProjectExecutor(inp, list(plan.exprs),
                               names=plan.schema.names)

    if isinstance(plan, P.PFilter):
        inp = build_plan(plan.input, ctx)
        return FilterExecutor(inp, plan.predicate)

    if isinstance(plan, P.PHopWindow):
        inp = build_plan(plan.input, ctx)
        return HopWindowExecutor(inp, plan.time_col, plan.slide, plan.size)

    if isinstance(plan, P.PAgg):
        from ..stream.materialized_agg import (
            MaterializedAggExecutor, call_needs_materialized,
            materialized_agg_state_schema,
        )
        if any(call_needs_materialized(c, plan.append_only_input)
               for c in plan.agg_calls):
            # exact DISTINCT / array_agg / string_agg / percentile / mode /
            # min-max-under-retraction: materialized-input state on the
            # host tier (reference: AggStateStorage::MaterializedInput);
            # ragged per-group multisets have no fixed-lane device layout.
            # ALL sibling calls ride along — approx_count_distinct included
            # (evaluated there exactly, a superset of its approx contract)
            if plan.eowc:
                raise ValueError(
                    "EMIT ON WINDOW CLOSE does not support materialized-"
                    "input aggregates")
            inp = build_plan(plan.input, ctx)
            key_fields = [plan.input.schema[i] for i in plan.group_keys]
            nk = len(plan.group_keys)
            st = ctx.state_table(
                materialized_agg_state_schema(key_fields),
                list(range(nk + 5)))     # keys + agg_idx/is_null/vi/vf/vs
            return MaterializedAggExecutor(
                inp, list(plan.group_keys), list(plan.agg_calls),
                state_table=st, out_capacity=cfg.chunk_capacity,
                load_vnodes=ctx.vnode_range)
        if (plan.group_keys and cfg.fragment_parallelism > 1
                and cfg.mesh is None and ctx.durable):
            # multi-fragment build over the dispatch fabric; batch builds
            # (durable=False) have no actor runtime and stay fused
            from .fragments import build_fragmented_agg
            return build_fragmented_agg(plan, ctx)
        inp = build_plan(plan.input, ctx)
        if plan.group_keys:
            key_fields = [plan.input.schema[i] for i in plan.group_keys]
            st = ctx.state_table(
                agg_state_schema(key_fields, plan.agg_calls),
                list(range(len(plan.group_keys))))
            if cfg.mesh is not None:
                from ..parallel.executors import ShardedHashAggExecutor
                return ShardedHashAggExecutor(
                    inp, cfg.mesh, list(plan.group_keys),
                    list(plan.agg_calls), state_table=st,
                    table_capacity=cfg.agg_table_capacity,
                    out_capacity=cfg.chunk_capacity)
            return HashAggExecutor(
                inp, list(plan.group_keys), list(plan.agg_calls),
                state_table=st, table_capacity=cfg.agg_table_capacity,
                out_capacity=cfg.chunk_capacity,
                load_vnodes=ctx.vnode_range,
                hbm_group_budget=cfg.agg_hbm_budget)
        from ..stream.simple_agg import simple_agg_state_schema
        st = ctx.state_table(simple_agg_state_schema(plan.agg_calls), [0])
        return SimpleAggExecutor(inp, list(plan.agg_calls), state_table=st)

    if isinstance(plan, P.PJoin):
        if getattr(plan, "null_aware", False) and (
                cfg.mesh is not None or (
                    plan.left_keys and cfg.fragment_parallelism > 1
                    and ctx.durable)):
            # sharded/fragmented anti joins don't carry the NOT IN null
            # guard; fail at build time, not with silently wrong rows
            raise ValueError(
                "NOT IN (SELECT ...) is not supported on sharded or "
                "fragmented join layouts; use NOT EXISTS or the default "
                "layout")
        if (plan.left_keys and cfg.fragment_parallelism > 1
                and cfg.mesh is None and ctx.durable):
            # multi-fragment build: both sides hash-dispatch by join key
            # to N parallel join actors (reference: hash-distributed
            # HashJoin fragments, dispatch.rs:532)
            from .fragments import build_fragmented_join
            return build_fragmented_join(plan, ctx, _JOIN_TYPES)
        left = build_plan(plan.left, ctx)
        right = build_plan(plan.right, ctx)
        lst = ctx.state_table(plan.left.schema,
                              join_state_pk(plan.left_keys, plan.left.pk))
        rst = ctx.state_table(plan.right.schema,
                              join_state_pk(plan.right_keys, plan.right.pk))
        if cfg.mesh is not None:
            from ..parallel.executors import ShardedHashJoinExecutor
            return ShardedHashJoinExecutor(
                left, right, cfg.mesh, list(plan.left_keys),
                list(plan.right_keys), join_type=_JOIN_TYPES[plan.kind],
                condition=plan.condition,
                left_state_table=lst, right_state_table=rst,
                key_capacity=cfg.join_key_capacity,
                bucket_width=cfg.join_bucket_width,
                out_capacity=cfg.chunk_capacity)
        return HashJoinExecutor(
            left, right, list(plan.left_keys), list(plan.right_keys),
            join_type=_JOIN_TYPES[plan.kind], condition=plan.condition,
            left_state_table=lst, right_state_table=rst,
            key_capacity=cfg.join_key_capacity,
            bucket_width=cfg.join_bucket_width,
            out_capacity=cfg.chunk_capacity,
            hbm_key_budget=cfg.join_hbm_budget,
            null_aware_anti=getattr(plan, "null_aware", False),
            row_keys=(fanout_row_key(plan.left_keys, plan.left.pk),
                      fanout_row_key(plan.right_keys, plan.right.pk)),
            row_capacity=cfg.join_row_capacity)

    if isinstance(plan, P.PTopN):
        inp = build_plan(plan.input, ctx)
        st = ctx.state_table(plan.schema, list(plan.pk))
        return TopNExecutor(
            inp, list(plan.order), plan.offset, plan.limit,
            pk_indices=list(plan.pk), group_by=list(plan.group_by),
            with_ties=plan.with_ties, state_table=st,
            table_capacity=cfg.topn_table_capacity,
            out_capacity=cfg.chunk_capacity)

    if isinstance(plan, P.PDynFilter):
        left = build_plan(plan.input, ctx)
        right = build_plan(plan.right, ctx)
        st = ctx.state_table(plan.schema, list(plan.pk))
        bt = None
        if st is not None:
            bt = ctx.state_table(
                Schema((Field("id", INT64),
                        Field("bound", plan.schema[plan.key_col].type))), [0])
        return DynamicFilterExecutor(
            left, right, key_col=plan.key_col, cmp=plan.cmp,
            pk_indices=list(plan.pk), state_table=st, bound_table=bt,
            table_capacity=cfg.topn_table_capacity,
            out_capacity=cfg.chunk_capacity)

    if isinstance(plan, P.PTemporalJoin):
        from ..stream.temporal_join import TemporalJoinExecutor
        inp = build_plan(plan.input, ctx)
        rdef = plan.right_def
        right_table = StateTable(ctx.store, rdef.table_id, rdef.schema,
                                 list(rdef.pk))
        return TemporalJoinExecutor(
            inp, right_table, list(plan.left_keys), list(plan.right_keys),
            outer=plan.outer, condition=plan.condition,
            out_capacity=cfg.chunk_capacity)

    if isinstance(plan, P.POverWindow):
        from ..stream.over_window import (
            EowcOverWindowExecutor, OverWindowExecutor, eowc_acc_schema,
        )
        inp = build_plan(plan.input, ctx)
        in_schema = plan.input.schema
        pk = list(plan.input.pk)
        if plan.eowc:
            order_col = plan.calls[0].order_by[0].col
            sort_st = ctx.state_table(in_schema, pk)
            inp = SortExecutor(inp, time_col=order_col, pk_indices=pk,
                               state_table=sort_st,
                               table_capacity=cfg.topn_table_capacity,
                               out_capacity=cfg.chunk_capacity)
            acc_schema = eowc_acc_schema(in_schema, plan.calls)
            npart = len(plan.calls[0].partition_by)
            acc_st = ctx.state_table(acc_schema, list(range(npart)))
            buf_st = ctx.state_table(in_schema, pk)
            return EowcOverWindowExecutor(
                inp, plan.calls, pk_indices=pk, acc_table=acc_st,
                buffer_table=buf_st, out_capacity=cfg.chunk_capacity)
        st = ctx.state_table(in_schema, pk)
        return OverWindowExecutor(inp, plan.calls, pk_indices=pk,
                                  state_table=st,
                                  out_capacity=cfg.chunk_capacity)

    if isinstance(plan, P.PProjectSet):
        from ..stream.project_set import ProjectSetExecutor
        inp = build_plan(plan.input, ctx)
        return ProjectSetExecutor(inp, list(plan.exprs),
                                  names=plan.schema.names,
                                  out_capacity=cfg.chunk_capacity)

    if isinstance(plan, P.PUnion):
        return UnionExecutor([build_plan(i, ctx) for i in plan.inputs])

    raise NotImplementedError(f"cannot build {type(plan).__name__}")


def config_to_json(cfg: BuildConfig) -> str:
    """Durable form of a BuildConfig (reschedule persistence). A live
    ``mesh`` can't be pickled across processes/restarts; what IS durable
    is its topology — axis names + shape — from which an equivalent mesh
    reassembles over the restarted process's devices (the reference
    persists vnode mappings in meta for the same reason,
    src/meta/src/stream/scale.rs:657)."""
    import json
    d = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
         if f.name != "mesh"}
    if cfg.mesh is not None:
        d["mesh"] = {"axis_names": list(cfg.mesh.axis_names),
                     "shape": list(cfg.mesh.devices.shape)}
    else:
        d["mesh"] = None
    return json.dumps(d, sort_keys=True)


def config_from_json(s: str, allow_reshard: bool = False) -> BuildConfig:
    """Rebuild a BuildConfig from its durable form.

    When the persisted mesh topology needs more devices than the process
    has this REFUSES loudly (``MeshUnavailableError``) — the silent
    alternative was an N-shard job quietly reopening on the session's
    default (unsharded) layout. ``allow_reshard=True`` is the explicit
    escape hatch: a 1-D mesh shrinks to the available device count, which
    is safe because the sharded executors and the fused sharded path
    re-shard durable state by replaying the vnode mapping on load
    (parallel/fused.load_shard_states, ShardedHashAggExecutor's
    load-shard filter)."""
    import json
    d = json.loads(s)
    mesh_spec = d.pop("mesh", None)
    known = {f.name for f in dataclasses.fields(BuildConfig)}
    cfg = BuildConfig(**{k: v for k, v in d.items() if k in known})
    if mesh_spec is not None:
        import math
        import jax
        import numpy as _np
        from ..common.config import MeshUnavailableError
        shape = list(mesh_spec["shape"])
        n = math.prod(shape)
        devs = jax.devices()
        if len(devs) < n:
            if allow_reshard and len(shape) == 1 and devs:
                shape = [len(devs)]
                n = len(devs)
            else:
                raise MeshUnavailableError(
                    f"persisted mesh needs {n} devices, process has "
                    f"{len(devs)}")
        cfg = dataclasses.replace(cfg, mesh=jax.sharding.Mesh(
            _np.array(devs[:n]).reshape(shape),
            tuple(mesh_spec["axis_names"])))
    return cfg


def collect_leaves(plan: P.PlanNode) -> list:
    """All leaf nodes (sources/scans/values) in plan order."""
    if not plan.children:
        return [plan] if isinstance(
            plan, (P.PSource, P.PTableScan, P.PMvScan, P.PValues,
                   P.PRemoteFragment, P.PExchange)) else []
    out = []
    for c in plan.children:
        out.extend(collect_leaves(c))
    return out
