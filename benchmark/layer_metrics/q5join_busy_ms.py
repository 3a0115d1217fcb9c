"""q5join_busy_ms — per barrier, the host time the q5 self-join accounts
for: ``join_busy_ms``'s sum (``HashJoin.chunks`` + ``HashJoin.barrier``),
median over the covered window barriers, read in the cell whose join has
a fan-out side (AuctionBids: thousands of rows a window key). On a line
of its own, per barrier (medians over the covered barriers) what the
fan-out arena did, as ``HashJoin.chunks`` carries it: ``fanout_probes``
(live rows of the MaxBids chunks that scanned the arena),
``candidate_rows`` (the arena rows of their keys), ``fanout_pairs`` (the
pairs that passed num >= maxn), ``rows_out``, ``fanout_rows`` (live rows
in the arena at the barrier) and ``fanout_fill_pct`` (its row table's
fill); and for the whole window the ``rewinds`` and ``grows`` (either
above 0: the arenas were rebuilt, and a program compiled, inside the
window). A count a program does not carry reads null. Nothing where no
barrier of the window has a join span."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import join_busy_ms
from benchmark.window import median

SPAN = "HashJoin.chunks"
COUNTS = ("fanout_probes", "candidate_rows", "fanout_pairs", "rows_out",
          "fanout_rows", "fanout_fill_pct")


def read(ctx: dict):
    value = join_busy_ms.read(ctx)
    if value is None:
        return None
    per_barrier = [[s.get("args") or {} for s in spans if s["name"] == SPAN]
                   for _b, spans in ps.window(ctx)]

    def med(name):
        values = [sum(a[name] for a in args) for args in per_barrier
                  if args and all(name in a for a in args)]
        return median(values) if values else None

    flat = [a for args in per_barrier for a in args]
    print(json.dumps({"q5join_busy": {
        **{name: med(name) for name in COUNTS},
        "rewinds": sum(a.get("rewinds", 0) for a in flat),
        "grows": sum(a.get("grows", 0) for a in flat)}}), flush=True)
    return value
