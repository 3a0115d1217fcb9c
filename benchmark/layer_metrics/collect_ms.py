"""collect_ms — the barrier ledger's ``collect`` stage (the executors
working through the barrier's chunks until every actor has acknowledged
it), median over the window's barriers."""

from benchmark.window import median


def read(ctx: dict):
    return median([b["ledger"]["collect_ms"] for b in ctx["barriers"]
                   if b["ledger"] and b["ledger"]["collect_ms"] is not None])
