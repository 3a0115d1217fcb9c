"""commit_ms — the barrier ledger's ``commit`` stage (the checkpoint's
second phase: encode the epoch's deltas and publish the segment), median
over the window's CHECKPOINT barriers."""

from benchmark.window import median


def read(ctx: dict):
    return median([b["ledger"]["commit_ms"] for b in ctx["barriers"]
                   if b["ledger"] and b["ledger"]["checkpoint"]
                   and b["ledger"]["commit_ms"] is not None])
