"""Epoch co-scheduler: many MVs' epochs batched into ONE dispatch per tick.

The host-side grouping layer over ops/fused_multi.py. A *group* holds
jobs whose fused-epoch trace is identical (same core shape — source+agg
or source+join — same static config, same projection, same source
family); their states live STACKED under a leading job axis and every
tick runs one jitted, vmapped epoch for the whole group. Per-job
identity rides as data: a start-event cursor and a PRNG base key per
job (keys are folded with the per-job batch counter INSIDE the jit, so
adding the fold costs zero extra dispatches and stays bit-identical to
the solo path's host-side ``jax.random.fold_in``).

Grouping rules (docs/performance.md "Epoch co-scheduling"):

* eligibility is decided by a static **signature** — (shape kind,
  source signature, rows/chunk, projection exprs, core config). Equal
  signature ⇒ identical trace ⇒ stackable. Different window literals,
  agg calls, capacities… ⇒ different signature ⇒ different group.
* a job that matches no group's signature simply starts its own group
  (a group of one is still one dispatch — the solo fused epoch with a
  [1] job axis, bit-exact vs the un-stacked builder).
* membership changes (CREATE/DROP) restack the job axis and recompile
  at the new [J] shape; jit caches per shape, so toggling between two
  sizes does not re-trace.

Barrier work is also batched: one vmapped probe returns the WHOLE
group's packed stats in a single [J, 3] fetch; only per-job output
gathers remain per job (they are per-job data), served by one compiled
gather with a traced job index.

``match_coschedulable`` is the Session's CREATE MATERIALIZED VIEW hook:
it recognizes the fusable source+agg plan shape (NEXmark bid source →
projection → grouped agg) and returns a build recipe, or None — the
documented solo-executor fallback for every other shape (joins under
the planner, retraction-bearing inputs, materialized-input aggs,
watermarked sources, fragmented/sharded/worker-placed builds).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..common.fetch import PendingFlush, async_fetch
from ..common.tracing import CAT_EPOCH, span
from ..ops.fused_multi import (
    append_state, build_group_epoch, gather_job_flush_chunk, index_state,
    multi_agg_finish, multi_agg_probe, remove_state, stack_states,
)


@dataclasses.dataclass
class FusedJobSpec:
    """One co-schedulable job: everything needed to trace its epoch."""

    kind: str                  # "agg" | "join"
    signature: tuple           # static trace signature (grouping key)
    chunk_fn: Callable         # traceable (start, key) -> StreamChunk
    exprs: tuple               # projection Exprs ((), for no projection)
    core: object               # AggCore / IntervalJoinCore
    rows_per_chunk: int
    seed: int                  # per-job PRNG base seed


def _expr_sig(e) -> str:
    # runtime Exprs are frozen dataclasses: repr() recurses into fields,
    # so it is a complete structural signature
    return repr(e)


def agg_signature(core, exprs, rows_per_chunk: int,
                  source_sig: tuple) -> tuple:
    """Static signature of a source+agg fused epoch: equal signatures ⇒
    identical traced computation ⇒ stackable."""
    return ("agg", source_sig, int(rows_per_chunk),
            tuple(_expr_sig(e) for e in exprs),
            tuple(repr(t) for t in core.key_types),
            tuple(core.group_keys), repr(tuple(core.agg_calls)),
            core.capacity, core.out_capacity)


def join_signature(core, exprs, rows_per_chunk: int,
                   source_sig: tuple) -> tuple:
    return ("join", source_sig, int(rows_per_chunk),
            tuple(_expr_sig(e) for e in exprs),
            repr(core.probe_schema), core.ts_col, core.val_col,
            core.window_us, core.n_buckets, core.W, core.band_col,
            core.band_us)


#: dispatch_count / profiler identity of the co-scheduled group epoch
GROUP_EPOCH_FN = "build_group_epoch.<locals>.coscheduled_epoch"


def restack_span(epoch: int):
    """A checkpoint moving state out of / back into a group's job axis."""
    return span("cosched.restack", epoch=epoch, stage="state_delta",
                cat=CAT_EPOCH, tid="conductor")


class JobAxisGroup:
    """What every fused scheduler's group shares (CoGroup here, the tick
    compiler's PaddedHeteroGroup / MegaGroup, parallel/fused.py's
    ShardedCoGroup): jobs along a leading axis, per-job identity as data.
    stream/fused_jobs.py drives any of them through ``names``,
    ``starts``, ``batch_nos``, ``pending``, ``epochs_run``,
    ``epoch_qualname``, ``run_epoch(k)``, ``begin_flush()``,
    ``finish_flush()`` and ``checkpoint(engines, epoch)``."""

    epoch_qualname: str          # profiler identity of the epoch dispatch
    names: list
    seeds: list
    pending: Optional[PendingFlush]
    _base_keys = None

    @property
    def n_jobs(self) -> int:
        return len(self.names)

    def _keys(self):
        # stacked per-job base keys, rebuilt only on membership change;
        # the per-epoch fold happens INSIDE the group dispatch
        if self._base_keys is None:
            self._base_keys = jnp.stack(
                [jax.random.PRNGKey(s) for s in self.seeds])
        return self._base_keys

    def flush(self) -> dict:
        """Synchronous barrier flush (begin + finish in one call): one
        vmapped probe, ONE packed fetch, per-job gathers, one vmapped
        finish — the pre-pipeline cadence, still the default."""
        if self.pending is None:
            self.begin_flush()
        return self.finish_flush()

    def finish_flush(self) -> dict:
        """Resolve the in-flight flush: one packed [J, 3] fetch (already
        streaming — usually landed) for all J jobs, then the group's
        per-job gather windows against the pending pre-finish state
        (``_decode_flush``). Returns {job: [StreamChunk, ...]}. The wait
        on the device and the decode are sibling spans of the caller's
        epoch."""
        p = self.pending
        if p is None:
            p = self.begin_flush()
        self.pending = None
        with span("cosched.epoch_wait", epoch=None, stage="epoch_wait",
                  wait="device", cat=CAT_EPOCH, tid="conductor"):
            packed_h = np.asarray(p.fetch.result())
        with span("cosched.flush_decode", epoch=None, stage="flush_decode",
                  cat=CAT_EPOCH, tid="conductor"):
            return self._decode_flush(p, packed_h)

    def checkpoint(self, engines: dict, epoch: int) -> None:
        """Write every job's delta through its OWN HashAggExecutor
        persistence engine (``engines``: job → executor), then restack
        the job axis once instead of J in-place scatters."""
        ckpt_states = []
        for name in self.names:
            agg = engines[name]
            with restack_span(epoch):
                agg.state = self.state_of(name)
            agg._checkpoint_to_state_table(epoch)
            ckpt_states.append(agg.state)
        with restack_span(epoch):
            self.set_states(ckpt_states)


class CoGroup(JobAxisGroup):
    """One signature's job set: stacked state + compiled group steps.

    The authoritative per-job state lives in ``self.stacked``;
    ``state_of``/``set_state`` give solo-shaped views for checkpointing
    and bit-exactness tests."""

    epoch_qualname = GROUP_EPOCH_FN

    def __init__(self, spec: FusedJobSpec, donate: bool = True):
        self.kind = spec.kind
        self.signature = spec.signature
        self.core = spec.core
        self.rows_per_chunk = spec.rows_per_chunk
        self.names: list[str] = []
        self.starts: list[int] = []      # per-job event cursor
        self.batch_nos: list[int] = []   # per-job epoch counter (PRNG fold)
        self.seeds: list[int] = []
        self.stacked = None
        self.epochs_run = 0
        self._epoch = build_group_epoch(
            spec.kind, spec.chunk_fn, spec.exprs, spec.core,
            spec.rows_per_chunk, donate)
        if spec.kind == "agg":
            self._probe = multi_agg_probe(spec.core)
            self._finish = multi_agg_finish(spec.core)
            self._gather = gather_job_flush_chunk(spec.core)
        self._join_out = None            # last join epoch's outputs
        self.pending: Optional[PendingFlush] = None

    # -- membership -----------------------------------------------------------

    def add(self, name: str, state, start: int = 0, seed: int = 0,
            batch_no: int = 0) -> None:
        if name in self.names:
            raise ValueError(f"job {name!r} already co-scheduled")
        assert self.pending is None, \
            "membership change with a flush in flight (drain first)"
        if self.stacked is None:
            self.stacked = stack_states([state])
        else:
            self.stacked = append_state(self.stacked, state)
        self.names.append(name)
        self.starts.append(int(start))
        self.batch_nos.append(int(batch_no))
        self.seeds.append(int(seed))
        self._base_keys = None

    def remove(self, name: str):
        """Drop a job; returns its final solo-shaped state."""
        assert self.pending is None, \
            "membership change with a flush in flight (drain first)"
        j = self.names.index(name)
        st = index_state(self.stacked, j)
        self.stacked = (remove_state(self.stacked, j)
                        if self.n_jobs > 1 else None)
        for lst in (self.names, self.starts, self.batch_nos, self.seeds):
            lst.pop(j)
        self._base_keys = None
        return st

    def state_of(self, name: str):
        return index_state(self.stacked, self.names.index(name))

    def set_states(self, states: list) -> None:
        """Replace every job's state (post-checkpoint write-back):
        ONE restack instead of J in-place scatters."""
        assert len(states) == self.n_jobs
        self.stacked = stack_states(states)

    # -- ticking --------------------------------------------------------------

    def run_epoch(self, k: int):
        """ONE dispatch: every member job advances k chunks. For join
        groups the epoch's flush outputs are held for ``flush()``."""
        starts = jnp.asarray(self.starts, jnp.int64)
        nos = jnp.asarray(self.batch_nos, jnp.int64)
        res = self._epoch(self.stacked, starts, self._keys(), nos, k)
        if self.kind == "agg":
            self.stacked = res
        else:
            self.stacked = res[0]
            self._join_out = res[1:]
        for j in range(self.n_jobs):
            self.starts[j] += k * self.rows_per_chunk
            self.batch_nos[j] += 1
        self.epochs_run += 1
        return res if self.kind == "join" else None

    def begin_flush(self) -> "PendingFlush":
        """Start the barrier flush WITHOUT resolving it: one vmapped
        probe is enqueued and its packed [J, 3] stats start streaming to
        the host (common/fetch.py), then the vmapped finish is enqueued
        eagerly — finish depends only on device state, so the NEXT
        epoch's dispatch can launch on finished state before this
        flush's fetch resolves (pipeline_depth = 2). The pre-finish
        state rides in the pending handle for the gathers."""
        if self.kind != "agg":
            raise NotImplementedError(
                "join-group flush is driven by the caller from the "
                "epoch outputs (bench.py measure pattern)")
        assert self.pending is None, "flush already in flight"
        packed, ranks = self._probe(self.stacked)
        self.pending = PendingFlush(
            self.stacked, packed, ranks,
            async_fetch(packed, dispatch=self._probe.__qualname__))
        self.stacked = self._finish(self.stacked)
        return self.pending

    def _decode_flush(self, p: "PendingFlush", packed_h) -> dict:
        out: dict = {}
        for j, name in enumerate(self.names):
            n_dirty, overflow = int(packed_h[j, 0]), int(packed_h[j, 1])
            if overflow:
                raise RuntimeError(
                    f"co-scheduled job {name!r}: group table overflow "
                    f"(capacity {self.core.capacity}); increase "
                    "agg_table_capacity")
            chunks = []
            lo = 0
            while lo < n_dirty:
                chunks.append(self._gather(p.stacked, p.ranks,
                                           jnp.int64(j), jnp.int64(lo)))
                lo += self.core.groups_per_chunk
            out[name] = chunks
        return out


class CoScheduler:
    """Signature-keyed group registry (one per Session)."""

    def __init__(self, donate: bool = True):
        self.groups: dict[tuple, CoGroup] = {}
        self.jobs: dict[str, CoGroup] = {}
        self.donate = donate

    def add(self, name: str, spec: FusedJobSpec, state,
            start: int = 0, batch_no: int = 0) -> CoGroup:
        group = self.groups.get(spec.signature)
        if group is None:
            group = CoGroup(spec, donate=self.donate)
            self.groups[spec.signature] = group
        group.add(name, state, start=start, seed=spec.seed,
                  batch_no=batch_no)
        self.jobs[name] = group
        return group

    def remove(self, name: str):
        group = self.jobs.pop(name, None)
        if group is None:
            return None
        st = group.remove(name)
        if group.n_jobs == 0:
            self.groups.pop(group.signature, None)
        return st

    def stats(self) -> dict:
        return {
            "jobs": len(self.jobs),
            "groups": [
                {"kind": g.kind, "jobs": list(g.names),
                 "epochs_run": g.epochs_run}
                for g in self.groups.values()
            ],
        }


# ---------------------------------------------------------------------------
# Session-side plan matching (CREATE MATERIALIZED VIEW hook)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CoschedMatch:
    """Recipe for building a plan as a co-scheduled fused job."""

    exprs: tuple               # projection onto the agg input
    proj_names: tuple
    group_keys: tuple
    agg_calls: tuple
    source: object             # SourceDef (nexmark bid)
    col_map: tuple             # declared column -> device BID_SCHEMA column


def _nexmark_bid_colmap(schema) -> Optional[tuple]:
    """Declared source columns → device BID_SCHEMA positions (the host
    reader adapts chunks to the declared schema by name; the fused path
    does the same with a column gather around chunk_fn). None when a
    declared column does not exist in the bid stream."""
    from ..connector import BID_SCHEMA
    by_name = {f.name: i for i, f in enumerate(BID_SCHEMA)}
    cmap = []
    for f in schema:
        i = by_name.get(f.name)
        if i is None or BID_SCHEMA[i].type.kind != f.type.kind:
            return None
        cmap.append(i)
    return tuple(cmap)


def declared_chunk_fn(full_fn: Callable, col_map: tuple) -> Callable:
    """Wrap a full-schema device chunk_fn to emit the declared column
    subset (a tuple re-index — free under fusion)."""
    def fn(start, key):
        ch = full_fn(start, key)
        return ch.with_columns(tuple(ch.columns[i] for i in col_map))
    return fn


def _expr_refs(e):
    # the optimizer's field-walking helper covers every Expr subtype
    from ..frontend.optimizer import expr_refs
    return expr_refs(e)


def match_coschedulable(plan) -> Optional[CoschedMatch]:
    """Recognize the fusable source+agg shape: PAgg over PProject over
    PSource(nexmark, table=bid). Returns a build recipe or None (solo
    fallback). Conservative on purpose — anything the device NEXmark
    generator + AggCore pair cannot reproduce bit-exactly stays on the
    executor path."""
    from ..expr.expr import InputRef
    from ..frontend import planner as P
    if isinstance(plan, P.PProject):
        # the planner wraps the agg in an output-naming projection;
        # accept the identity one (SELECT keys, aggs in plan order) —
        # reordering/computed outputs fall back to the executor path
        if not (len(plan.exprs) == len(plan.input.schema)
                and all(isinstance(e, InputRef) and e.index == i
                        for i, e in enumerate(plan.exprs))):
            return None
        plan = plan.input
    if not isinstance(plan, P.PAgg) or not plan.group_keys or plan.eowc:
        return None
    for c in plan.agg_calls:
        if c.lanes_unsupported or c.is_string_minmax:
            return None            # materialized-input / rank-table aggs
    inp = plan.input
    if not isinstance(inp, P.PProject):
        return None
    src = inp.input
    if not isinstance(src, P.PSource):
        return None
    sd = src.source
    if sd.connector != "nexmark":
        return None
    if (sd.options or {}).get("nexmark_table", "bid").lower() != "bid":
        return None                # device generator covers bids only
    if sd.watermark is not None:
        return None                # watermark filter not in the fused body
    # projection must not touch the hidden row-id column (the device
    # chunk has only the declared bid columns)
    n_data_cols = len(sd.schema)
    for e in inp.exprs:
        if any(r >= n_data_cols for r in _expr_refs(e)):
            return None
    col_map = _nexmark_bid_colmap(sd.schema)
    if col_map is None:
        return None                # declared column unknown to the stream
    return CoschedMatch(
        exprs=tuple(inp.exprs), proj_names=tuple(inp.schema.names),
        group_keys=tuple(plan.group_keys),
        agg_calls=tuple(plan.agg_calls), source=sd, col_map=col_map)


class DeviceSourceCursor:
    """Split-state shim for a device-generated source: the feed
    machinery persists ``offsets`` per checkpoint epoch and seeks on
    recovery, exactly like a connector SplitReader (frontend/session.py
    ``_SourceFeed``)."""

    SPLIT = "device"

    def __init__(self, events: int = 0, epochs: int = 0):
        self.events = int(events)
        self.epochs = int(epochs)     # PRNG batch counter rides along

    @property
    def offsets(self) -> dict:
        # pack (events, epochs) into the split map — both cursors must
        # recover together or replayed generation would re-key
        return {self.SPLIT: self.events, "epochs": self.epochs}

    def seek(self, offsets: dict) -> None:
        self.events = int(offsets.get(self.SPLIT, 0))
        self.epochs = int(offsets.get("epochs", 0))

    def rows_emitted(self) -> int:
        return self.events
