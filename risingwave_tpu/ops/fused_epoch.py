"""Single-dispatch epochs: source generation → projection → stateful core
fused into ONE jitted ``lax.scan``.

The dispatch-boundary ladder this removes (BASELINE.md "residual
headroom"; VERDICT r4 item 1): generating an epoch's ChunkBatch is one
dispatch, projecting it a second, the agg scan a third — and the
intermediate [k, cap, n_cols] batch materializes in HBM between them.
Fusing the three means per-epoch host→device traffic is two scalars and
XLA fuses the generator's elementwise work and the projection directly
into the stateful update, so no intermediate epoch batch ever exists
at HBM granularity (the scan carry is the core state; each iteration's
chunk lives only inside the step).

Four fusion surfaces now exist (docs/performance.md):

* ``fused_source_agg_epoch`` — the q5 shape: source → project → AggCore.
* ``fused_source_join_epoch`` — the q7 shape: source → project → bucketed
  interval join (ops/interval_join.py), INCLUDING the barrier flush (the
  per-window max delta applied to the stored arena) so a whole epoch —
  k chunks of ingest+probe plus the build-side update — is one dispatch.
* ``fused_source_session_epoch`` — the q8 shape: source → project →
  session-gap windows (ops/session_window.py), including the
  watermark-driven close at the barrier.
* ``fused_source_q3_epoch`` — the TPC-H q3 shape: source → orders-table
  build + lineitem probe + revenue agg + top-n churn
  (ops/stream_q3.py), the whole join+agg+topn MV in one dispatch.

All take any traceable ``chunk_fn(start, key) -> StreamChunk`` source
(connector/nexmark.py ``DeviceBidGenerator.chunk_fn``, connector/tpch.py
``DeviceQ3Generator.chunk_fn``) and — where projection applies — any
expression list. The epoch *bodies* are exposed separately
(``agg_epoch_body`` etc.) so ops/fused_multi.py can ``vmap`` the exact
same computation over a leading job axis: the co-scheduled multi-job
epoch is bit-identical per job to the solo epoch because it IS the same
traced function. The reference has no equivalent — its engine is
interpreter-style row batches (src/stream/src/executor/hash_agg.rs);
this is what designing for a compiler buys.
"""

from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from ..common.profiling import profile_dispatch
from ..expr import Expr


def _donate(donate: bool):
    # the same on every backend: the CPU tests run what the chip runs,
    # so a use-after-donate shows up there first
    return (0,) if donate else ()


# ---------------------------------------------------------------------------
# epoch bodies — unjitted, shared by the solo jits below and the vmapped
# multi-job epochs (ops/fused_multi.py)
# ---------------------------------------------------------------------------


def agg_epoch_body(chunk_fn: Callable, exprs: Sequence[Expr], core,
                   rows_per_chunk: int) -> Callable:
    """``epoch(state, start_event, key, k) -> state``: ``k`` generated +
    projected chunks folded into ``core`` (ops/grouped_agg.AggCore) by
    one ``lax.scan``."""
    exprs = tuple(exprs)

    def epoch(state, start, key, k: int):
        def body(st, i):
            # named scopes: metadata only, the names a device trace shows
            with jax.named_scope("source_gen"):
                ch = chunk_fn(start + i * rows_per_chunk,
                              jax.random.fold_in(key, i))
            with jax.named_scope("project"):
                projected = ch.with_columns(
                    tuple(e.eval(ch) for e in exprs))
            return core.apply_chunk(st, projected), None

        state, _ = jax.lax.scan(body, state,
                                jnp.arange(k, dtype=jnp.int64))
        return state

    return epoch


def join_epoch_body(chunk_fn: Callable, exprs: Sequence[Expr], core,
                    rows_per_chunk: int) -> Callable:
    """``epoch(state, start, key, k)`` for the q7 join shape — see
    ``fused_source_join_epoch`` for the return contract."""
    exprs = tuple(exprs)

    def epoch(state, start, key, k: int):
        def body(st, i):
            ch = chunk_fn(start + i * rows_per_chunk,
                          jax.random.fold_in(key, i))
            projected = ch.with_columns(tuple(e.eval(ch) for e in exprs))
            st, out = core.apply_chunk(st, projected)
            return st, out

        state, probe_out = jax.lax.scan(
            body, state, jnp.arange(k, dtype=jnp.int64))
        old_emitted_max = state.emitted_max
        del_mask, ins_mask, packed = core.flush_plan(state)
        state = core.finish_flush(state)
        packed = jnp.concatenate(
            [packed, jnp.sum(probe_out.vis).astype(jnp.int64)[None]])
        return state, probe_out, del_mask, ins_mask, old_emitted_max, packed

    return epoch


def session_epoch_body(chunk_fn: Callable, exprs: Sequence[Expr], core,
                       rows_per_chunk: int) -> Callable:
    """``epoch(state, start, key, k, watermark)`` for the q8 session
    shape — see ``fused_source_session_epoch``."""
    exprs = tuple(exprs)

    def epoch(state, start, key, k: int, watermark):
        def body(st, i):
            ch = chunk_fn(start + i * rows_per_chunk,
                          jax.random.fold_in(key, i))
            if exprs:
                ch = ch.with_columns(tuple(e.eval(ch) for e in exprs))
            return core.apply_chunk(st, ch), None

        state, _ = jax.lax.scan(body, state,
                                jnp.arange(k, dtype=jnp.int64))
        state, packed = core.flush_plan(state, watermark)
        snapshot = core.snapshot_closed(state)
        state = core.finish_flush(state)
        return state, snapshot, packed

    return epoch


def q3_epoch_body(chunk_fn: Callable, core,
                  rows_per_chunk: int) -> Callable:
    """``epoch(state, start, key, k)`` for the TPC-H q3 shape — see
    ``fused_source_q3_epoch``."""

    def epoch(state, start, key, k: int):
        def body(st, i):
            ch = chunk_fn(start + i * rows_per_chunk,
                          jax.random.fold_in(key, i))
            return core.apply_chunk(st, ch), None

        state, _ = jax.lax.scan(body, state,
                                jnp.arange(k, dtype=jnp.int64))
        state, out, packed = core.flush(state)
        return state, out, packed

    return epoch


# ---------------------------------------------------------------------------
# solo single-dispatch epochs
# ---------------------------------------------------------------------------


def fused_source_agg_epoch(chunk_fn: Callable, exprs: Sequence[Expr],
                           core, rows_per_chunk: int,
                           donate: bool = True) -> Callable:
    """Build ``epoch(state, start_event, key, k) -> state``: one compiled
    dispatch applying ``k`` generated+projected chunks to ``core``.

    ``chunk_fn(start_event, key)``: traceable producer of ONE flat chunk
    of ``rows_per_chunk`` rows. ``exprs``: projection onto the agg input
    schema. ``core``: ops.grouped_agg.AggCore (its ``apply_chunk`` is the
    scan body's fold).
    """
    epoch = agg_epoch_body(chunk_fn, exprs, core, rows_per_chunk)
    # counter identity for common/dispatch_count.py regressions stays
    # stable across the shared-body refactor
    epoch.__qualname__ = "fused_source_agg_epoch.<locals>.epoch"
    return profile_dispatch(jax.jit(epoch, static_argnums=(3,),
                                    donate_argnums=_donate(donate)),
                            epoch.__qualname__)


def fused_source_join_epoch(chunk_fn: Callable, exprs: Sequence[Expr],
                            core, rows_per_chunk: int,
                            donate: bool = True) -> Callable:
    """Build ``epoch(state, start_event, key, k)`` for the q7 join shape:
    ONE compiled dispatch generating + projecting + probe-inserting ``k``
    chunks into ``core`` (ops/interval_join.IntervalJoinCore), then —
    still inside the same dispatch — computing the barrier flush (the
    per-window aggregate delta joined against the stored probe arena)
    and advancing the downstream-visible build rows.

    Returns ``(state, probe_out, del_mask, ins_mask, old_emitted_max,
    packed)``:

    * ``probe_out``: stacked [k, cap] StreamChunk of probe-time matches
      (a ChunkBatch-shaped pytree; flatten_shards + gather_units_window
      compact it downstream).
    * ``del_mask``/``ins_mask``/``old_emitted_max``: inputs for
      ``core.gather_flush`` (the only remaining per-epoch host work is
      reading ``packed`` and gathering output windows).
    * ``packed``: [n_flush_units, lane_overflow, ring_clobber,
      saw_delete, n_probe_units] — ONE scalar fetch per epoch covers
      every host-checked flag AND both emission counts, exactly the
      packed-probe idiom of the executor barriers.
    """
    epoch = join_epoch_body(chunk_fn, exprs, core, rows_per_chunk)
    epoch.__qualname__ = "fused_source_join_epoch.<locals>.epoch"
    return profile_dispatch(jax.jit(epoch, static_argnums=(3,),
                                    donate_argnums=_donate(donate)),
                            epoch.__qualname__)


def fused_source_session_epoch(chunk_fn: Callable, exprs: Sequence[Expr],
                               core, rows_per_chunk: int,
                               donate: bool = True) -> Callable:
    """Build ``epoch(state, start_event, key, k, watermark)`` for the q8
    session-window shape (ops/session_window.SessionWindowCore): ``k``
    generated + projected chunks sessionized in one dispatch, then —
    inside the same dispatch — open sessions the ``watermark`` has
    passed close, the epoch's closed-session buffer is snapshotted for
    emission, and the buffer clears.

    Returns ``(state, snapshot, packed)``; ``packed`` = [n_closed,
    table_overflow, closed_overflow, saw_delete, out_of_order] — one
    scalar fetch per epoch; ``core.gather_closed(snapshot, n_closed, lo,
    cap)`` packs the emission windows."""
    epoch = session_epoch_body(chunk_fn, exprs, core, rows_per_chunk)
    epoch.__qualname__ = "fused_source_session_epoch.<locals>.epoch"
    return profile_dispatch(jax.jit(epoch, static_argnums=(3,),
                                    donate_argnums=_donate(donate)),
                            epoch.__qualname__)


def fused_source_q3_epoch(chunk_fn: Callable, core, rows_per_chunk: int,
                          donate: bool = True) -> Callable:
    """Build ``epoch(state, start_event, key, k)`` for the TPC-H q3
    streaming-MV shape (ops/stream_q3.Q3Core): ``k`` order/lineitem
    event chunks build + probe + aggregate in one dispatch, and the
    same dispatch recomputes the top-10 and emits its churn.

    Returns ``(state, out_chunk, packed)``; ``out_chunk`` is the fixed
    [2·limit]-row delete/insert churn (already gathered — no windowed
    host drain needed at top-n cardinality); ``packed`` = [n_out,
    orders_overflow, agg_overflow, saw_delete]."""
    epoch = q3_epoch_body(chunk_fn, core, rows_per_chunk)
    epoch.__qualname__ = "fused_source_q3_epoch.<locals>.epoch"
    return profile_dispatch(jax.jit(epoch, static_argnums=(3,),
                                    donate_argnums=_donate(donate)),
                            epoch.__qualname__)


#: builder registry — the single path bench.py / frontend wiring use to
#: resolve a fused surface by shape name (the q5/q7 entries predate it;
#: q8/q3 registered alongside so new surfaces are discoverable)
EPOCH_BUILDERS = {
    "source_agg": fused_source_agg_epoch,        # NEXmark q5
    "source_join": fused_source_join_epoch,      # NEXmark q7
    "source_session": fused_source_session_epoch,  # NEXmark q8
    "source_q3": fused_source_q3_epoch,          # TPC-H q3
}
