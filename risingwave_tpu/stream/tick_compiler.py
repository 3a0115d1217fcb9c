"""The heterogeneous tick compiler: UNEQUAL jobs → minimal dispatches.

Host-side scheduler over ops/fused_hetero.py. The co-scheduler
(stream/coschedule.py) batches jobs whose traces are IDENTICAL; every
job that misses a signature still pays its own dispatch, so a tenant
mix of 200 small dissimilar MVs ticks in ~200 dispatches. The tick
compiler takes the LIVE JOB SET and emits a minimal dispatch schedule
in two tiers:

1. **Shape-class supergroups** — ``skeletonize_exprs`` lifts numeric
   literals out of each job's projection (window widths, scale
   factors…) into parameter holes; jobs whose skeletons, agg calls and
   group keys then coincide share a ``shape_class`` (the coarsened
   ``agg_signature`` — capacities and literal VALUES excluded). Each
   member's state is padded to the class-max table capacity
   (``repad_agg_state``) and the whole bucket runs as ONE vmapped
   dispatch (``build_padded_group_epoch``) with per-job literals
   riding down the job axis as data.

2. **Mega-epochs** — jobs that share no skeleton are concatenated
   sequentially INSIDE one compiled dispatch (``build_mega_epoch``):
   one launch, one packed multi-job fetch, regardless of how unlike
   the bodies are.

The schedule is recompiled only on DDL: CREATE/DROP marks it dirty and
``ensure_compiled`` rebuilds lazily at the next tick (so creating 200
MVs triggers ONE compile, not 200 restacks). Dissolving a schedule
writes every job's state/cursor back into its job record and retires
each group's epochs-run counters (``take_retired``) so the live
``per_epoch`` dispatch-ratio invariant stays 1.0 across recompiles —
the same ledger discipline stream/fused_jobs.py applies to dropped
co-scheduled groups.

Both group kinds are ``JobAxisGroup``s (stream/coschedule.py), so the
one fused tick driver (stream/fused_jobs.py) gives them the same
pipeline-depth deferral and checkpoint write-back as equal groups, and
each job keeps its own HashAggExecutor-backed flush engine —
checkpoint/recovery is unchanged
(``_checkpoint_to_state_table`` is capacity-agnostic, so padded states
persist through the job's own engine).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax.numpy as jnp
import numpy as np

from ..common.fetch import PendingFlush, async_fetch
from ..expr.expr import FunctionCall, InputRef, Literal
from ..ops.fused_hetero import (
    build_mega_agg_finish, build_mega_agg_probe, build_mega_epoch,
    build_padded_group_epoch, mega_agg_gathers, padded_agg_probe,
    repad_agg_state,
)
from ..ops.fused_multi import (
    gather_job_flush_chunk, index_state, multi_agg_finish, stack_states,
)
from .coschedule import FusedJobSpec, JobAxisGroup, _expr_sig

#: dispatch_count / profiler identities of the two compiled surfaces
PADDED_EPOCH_FN = "build_padded_group_epoch.<locals>.padded_epoch"
MEGA_EPOCH_FN = "build_mega_epoch.<locals>.mega_epoch"


# ---------------------------------------------------------------------------
# skeletonization: literals → parameter holes
# ---------------------------------------------------------------------------


def skeletonize_exprs(exprs, n_source_cols: int):
    """Lift numeric literals out of projection exprs: ``(skel_exprs,
    hole_types, params)``. Hole ``h`` becomes ``InputRef(n_source_cols
    + h)`` — the epoch body appends one broadcast parameter column per
    hole, so evaluation is bit-identical to the inlined literal.
    ``params`` holds each hole's PHYSICAL value (``type.to_physical``),
    ready to ride as device data.

    Conservative on purpose: only plain int/float literals lift (bools,
    strings, decimals, NULLs stay inline — part of the skeleton), and
    only InputRef/Literal/FunctionCall nodes are walked; any other node
    keeps its subtree verbatim, which merely coarsens less (two jobs
    differing inside an unwalked subtree land in different classes and
    fall to the mega tier — never wrong, only less fused)."""
    hole_types: list = []
    params: list = []

    def walk(e):
        if isinstance(e, Literal):
            v = e.value
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                return e
            slot = len(params)
            hole_types.append(e.type)
            params.append(e.type.to_physical(v))
            return InputRef(n_source_cols + slot, e.type)
        if isinstance(e, FunctionCall):
            return FunctionCall(e.name, tuple(walk(a) for a in e.args),
                                e.type)
        return e

    skel = tuple(walk(e) for e in exprs)
    return skel, tuple(hole_types), tuple(params)


def shape_class(core, skel_exprs, hole_types, rows_per_chunk: int,
                source_sig: tuple) -> tuple:
    """The coarsened grouping key: ``agg_signature`` minus table/output
    capacities (padded to class max) minus literal values (parameter
    data), plus the hole dtype row (two skeletons only share a class
    when their holes line up positionally and typewise)."""
    return ("hetero_agg", source_sig, int(rows_per_chunk),
            tuple(_expr_sig(e) for e in skel_exprs),
            tuple(repr(t) for t in hole_types),
            tuple(repr(t) for t in core.key_types),
            tuple(core.group_keys), repr(tuple(core.agg_calls)))


@dataclasses.dataclass
class HeteroJob:
    """One compiled-schedule member: spec + skeleton + live cursors.
    ``state`` is authoritative only while the job is UNGROUPED (fresh
    add, or between dissolve and recompile); once scheduled the group
    holds it, and dissolve writes it back here."""

    spec: FusedJobSpec
    skel_exprs: tuple
    hole_types: tuple
    params: tuple              # physical hole values (host scalars)
    shape_class: tuple
    state: object
    start: int
    batch_no: int

    @property
    def state_capacity(self) -> int:
        return self.state.dirty.shape[0]


# ---------------------------------------------------------------------------
# compiled dispatch groups
# ---------------------------------------------------------------------------


class PaddedHeteroGroup(JobAxisGroup):
    """Tier 1: one shape class, one vmapped dispatch; per-job literals
    ride as stacked parameter data and every member's state lives
    padded at the class-max capacity."""

    kind = "padded"
    epoch_qualname = PADDED_EPOCH_FN

    def __init__(self, named_jobs: list, donate: bool = True):
        self.names = [n for n, _ in named_jobs]
        jobs = [j for _, j in named_jobs]
        base = jobs[0]
        # class capacity: max over declared cores AND current states —
        # a member padded by an earlier schedule never shrinks (repad
        # grows only; per-key values are capacity-invariant)
        cap = max(max(j.spec.core.capacity, j.state_capacity)
                  for j in jobs)
        out_cap = max(j.spec.core.out_capacity for j in jobs)
        padded = []
        core = None
        for j in jobs:
            jcore = j.spec.core
            if j.state_capacity != jcore.capacity:
                # state already padded by a previous schedule: repad
                # from its CURRENT capacity, not the declared one
                jcore = type(jcore)(jcore.key_types, jcore.group_keys,
                                    jcore.agg_calls, j.state_capacity,
                                    jcore.out_capacity)
            core, st = repad_agg_state(jcore, j.state, cap,
                                       out_capacity=out_cap)
            padded.append(st)
        self.core = core
        self.rows_per_chunk = base.spec.rows_per_chunk
        self.stacked = stack_states(padded)
        self.params = tuple(
            jnp.asarray(np.array([j.params[h] for j in jobs],
                                 dtype=t.np_dtype))
            for h, t in enumerate(base.hole_types))
        self.starts = [j.start for j in jobs]
        self.batch_nos = [j.batch_no for j in jobs]
        self.seeds = [j.spec.seed for j in jobs]
        self.epochs_run = 0
        self.flush_weights = dict.fromkeys(self.names, 0)
        self.pending: Optional[PendingFlush] = None
        self._epoch = build_padded_group_epoch(
            base.spec.chunk_fn, base.skel_exprs, self.core,
            self.rows_per_chunk, donate)
        self._probe = padded_agg_probe(self.core)
        self._finish = multi_agg_finish(self.core)
        self._gather = gather_job_flush_chunk(self.core)

    def state_of(self, name: str):
        return index_state(self.stacked, self.names.index(name))

    def set_states(self, states: list) -> None:
        assert len(states) == self.n_jobs
        self.stacked = stack_states(states)

    def run_epoch(self, k: int):
        starts = jnp.asarray(self.starts, jnp.int64)
        nos = jnp.asarray(self.batch_nos, jnp.int64)
        self.stacked = self._epoch(self.stacked, starts, self._keys(),
                                   nos, self.params, k)
        for j in range(self.n_jobs):
            self.starts[j] += k * self.rows_per_chunk
            self.batch_nos[j] += 1
        self.epochs_run += 1

    def begin_flush(self) -> PendingFlush:
        assert self.pending is None, "flush already in flight"
        packed, ranks = self._probe(self.stacked)
        self.pending = PendingFlush(
            self.stacked, packed, ranks,
            async_fetch(packed, dispatch=self._probe.__qualname__))
        self.stacked = self._finish(self.stacked)
        return self.pending

    def _decode_flush(self, p: PendingFlush, packed_h) -> dict:
        out: dict = {}
        for j, name in enumerate(self.names):
            n_dirty, overflow = int(packed_h[j, 0]), int(packed_h[j, 1])
            if overflow:
                raise RuntimeError(
                    f"tick-compiled job {name!r}: padded group table "
                    f"overflow (class capacity {self.core.capacity}); "
                    "increase agg_table_capacity")
            self.flush_weights[name] += n_dirty
            chunks = []
            lo = 0
            while lo < n_dirty:
                chunks.append(self._gather(p.stacked, p.ranks,
                                           jnp.int64(j), jnp.int64(lo)))
                lo += self.core.groups_per_chunk
            out[name] = chunks
        return out


class MegaGroup(JobAxisGroup):
    """Tier 2: heterogeneous epoch bodies concatenated in ONE compiled
    dispatch. States stay a per-job tuple (no shape relation between
    members); the barrier is one probe dispatch / one packed [J, 3]
    fetch, with per-job gathers (per-job data, as everywhere)."""

    kind = "mega"
    epoch_qualname = MEGA_EPOCH_FN

    def __init__(self, named_jobs: list, donate: bool = True):
        self.names = [n for n, _ in named_jobs]
        jobs = [j for _, j in named_jobs]
        self.cores = [j.spec.core for j in jobs]
        self.rows_per_chunks = [j.spec.rows_per_chunk for j in jobs]
        self.states = tuple(j.state for j in jobs)
        self.starts = [j.start for j in jobs]
        self.batch_nos = [j.batch_no for j in jobs]
        self.seeds = [j.spec.seed for j in jobs]
        self.epochs_run = 0
        self.flush_weights = dict.fromkeys(self.names, 0)
        self.pending: Optional[PendingFlush] = None
        self._epoch = build_mega_epoch([j.spec for j in jobs], donate)
        self._probe = build_mega_agg_probe(self.cores)
        self._finish = build_mega_agg_finish(self.cores)
        self._gathers = mega_agg_gathers(self.cores)

    def state_of(self, name: str):
        return self.states[self.names.index(name)]

    def set_states(self, states: list) -> None:
        assert len(states) == self.n_jobs
        self.states = tuple(states)

    def run_epoch(self, k: int):
        starts = jnp.asarray(self.starts, jnp.int64)
        nos = jnp.asarray(self.batch_nos, jnp.int64)
        self.states = self._epoch(self.states, starts, self._keys(),
                                  nos, k)
        for j in range(self.n_jobs):
            self.starts[j] += k * self.rows_per_chunks[j]
            self.batch_nos[j] += 1
        self.epochs_run += 1

    def begin_flush(self) -> PendingFlush:
        assert self.pending is None, "flush already in flight"
        packed, ranks = self._probe(self.states)
        self.pending = PendingFlush(
            self.states, packed, ranks,
            async_fetch(packed, dispatch=self._probe.__qualname__))
        self.states = self._finish(self.states)
        return self.pending

    def _decode_flush(self, p: PendingFlush, packed_h) -> dict:
        out: dict = {}
        for j, name in enumerate(self.names):
            n_dirty, overflow = int(packed_h[j, 0]), int(packed_h[j, 1])
            if overflow:
                raise RuntimeError(
                    f"tick-compiled job {name!r}: agg table overflow "
                    f"(capacity {self.cores[j].capacity}); increase "
                    "agg_table_capacity")
            self.flush_weights[name] += n_dirty
            chunks = []
            lo = 0
            while lo < n_dirty:
                chunks.append(self._gathers[j](p.stacked[j], p.ranks[j],
                                               jnp.int64(lo)))
                lo += self.cores[j].groups_per_chunk
            out[name] = chunks
        return out


# ---------------------------------------------------------------------------
# the compiler
# ---------------------------------------------------------------------------


class TickCompiler:
    """Live job set → minimal dispatch schedule (one per Session).

    DDL only marks the schedule dirty; ``ensure_compiled`` (called at
    the first subsequent tick) buckets jobs by ``shape_class`` —
    buckets of ≥ 2 become padded supergroups, the remainder packs into
    mega-epochs of at most ``mega_max_jobs`` in insertion order — so a
    burst of 200 CREATEs costs ONE schedule compile."""

    def __init__(self, donate: bool = True, mega_max_jobs: int = 32):
        self.jobs: dict[str, HeteroJob] = {}
        self.groups: list = []
        self.job_group: dict[str, object] = {}
        self.dirty = False
        self.donate = donate
        self.mega_max_jobs = int(mega_max_jobs)
        self.schedule_compiles = 0
        self._retired: dict[str, int] = {}

    # -- DDL ------------------------------------------------------------------

    def add(self, name: str, spec: FusedJobSpec, state,
            n_source_cols: int, start: int = 0, batch_no: int = 0
            ) -> HeteroJob:
        if name in self.jobs:
            raise ValueError(f"job {name!r} already tick-compiled")
        self._dissolve()
        skel, hole_types, params = skeletonize_exprs(
            spec.exprs, n_source_cols)
        sc = shape_class(spec.core, skel, hole_types,
                         spec.rows_per_chunk, spec.signature[1])
        job = HeteroJob(spec, skel, hole_types, params, sc, state,
                        int(start), int(batch_no))
        self.jobs[name] = job
        return job

    def remove(self, name: str):
        """Drop a job; returns its final solo-shaped state (possibly
        padded — per-key values are capacity-invariant) or None."""
        if name not in self.jobs:
            return None
        self._dissolve()
        return self.jobs.pop(name).state

    def _dissolve(self) -> None:
        """Tear the compiled schedule down to job records: write every
        group's states/cursors back and retire its epochs-run under its
        dispatch qualname — the ledger stream/fused_jobs.py drains via
        ``take_retired`` to keep the per-epoch ratio exactly 1.0 across
        recompiles (ISSUE 19 satellite: DROP + re-CREATE)."""
        self.dirty = True
        if not self.groups:
            return
        for g in self.groups:
            assert g.pending is None, \
                "schedule change with a flush in flight (drain first)"
            if g.epochs_run:
                qn = g.epoch_qualname
                self._retired[qn] = self._retired.get(qn, 0) \
                    + g.epochs_run
            for j, name in enumerate(g.names):
                job = self.jobs[name]
                job.state = g.state_of(name)
                job.start = g.starts[j]
                job.batch_no = g.batch_nos[j]
        self.groups = []
        self.job_group = {}

    def take_retired(self) -> dict:
        """Drain retired epoch counts (qualname → epochs): the caller
        (stream/fused_jobs.py) folds them into its ``retired`` ledger."""
        out, self._retired = self._retired, {}
        return out

    # -- scheduling -----------------------------------------------------------

    def ensure_compiled(self) -> None:
        if not self.dirty:
            return
        buckets: dict[tuple, list] = {}
        for name, job in self.jobs.items():
            buckets.setdefault(job.shape_class, []).append(name)
        groups: list = []
        singles: list = []
        for names in buckets.values():
            if len(names) >= 2:
                groups.append(PaddedHeteroGroup(
                    [(n, self.jobs[n]) for n in names],
                    donate=self.donate))
            else:
                singles.extend(names)
        for i in range(0, len(singles), self.mega_max_jobs):
            groups.append(MegaGroup(
                [(n, self.jobs[n]) for n in
                 singles[i:i + self.mega_max_jobs]],
                donate=self.donate))
        self.groups = groups
        self.job_group = {n: g for g in groups for n in g.names}
        self.dirty = False
        if self.jobs:
            self.schedule_compiles += 1

    # -- observability --------------------------------------------------------

    def stats(self) -> dict:
        return {
            "jobs": len(self.jobs),
            "dispatches_per_tick": len(self.groups),
            "schedule_compiles": self.schedule_compiles,
            "dirty": self.dirty,
            "groups": [
                {"kind": g.kind, "jobs": list(g.names),
                 "epochs_run": g.epochs_run,
                 "capacity": (g.core.capacity if g.kind == "padded"
                              else [c.capacity for c in g.cores])}
                for g in self.groups
            ],
        }

    def attribution(self) -> dict:
        """Per-job cost weights inside fused dispatches: cumulative
        flushed-group counts (packed slot 0) per job, grouped by
        dispatch qualname. common/profiling.per_job_attribution splits
        a qualname's measured seconds over these weights."""
        out: dict = {}
        for g in self.groups:
            out.setdefault(g.epoch_qualname, {}).update(g.flush_weights)
        return out
