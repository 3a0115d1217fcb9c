"""pre_barrier_checkpoint_ms — ``pre_barrier_ms`` over the window's
CHECKPOINT barriers alone: on the co-scheduled path the agg's state-table
delta is made (dirty groups found, fetched and staged) before the
barrier's ledger record opens, so that part of a checkpoint is in no
ledger stage either and ``commit_ms`` does not hold it."""

from benchmark.window import median


def read(ctx: dict):
    return median([b["wall_ms"] - b["ledger"]["inject_ms"]
                   - b["ledger"]["total_ms"]
                   for b in ctx["barriers"]
                   if b["ledger"] and b["ledger"]["checkpoint"]])
