"""pre_barrier_ms — the time of a barrier that no ledger stage covers.

On the co-scheduled path ``Session.tick()`` runs the fused epoch dispatch
and the flush decode BEFORE it opens the barrier's ledger record, so the
main work of the fast path is in no stage. It is read here as the
barrier's host-clock time around ``tick()`` minus the ledger's record of
the same barrier (its ``inject`` stage plus ``total_ms``, which spans
pending, collect and commit); median over the window's barriers."""

from benchmark.window import median


def read(ctx: dict):
    return median([b["wall_ms"] - b["ledger"]["inject_ms"]
                   - b["ledger"]["total_ms"]
                   for b in ctx["barriers"] if b["ledger"]])
