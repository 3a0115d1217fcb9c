"""ajoin_busy_ms — per barrier, the host time the anti hash join
accounts for: ``join_busy_ms``'s sum (``HashJoin.chunks`` +
``HashJoin.barrier``), median over the covered window barriers, read in
the cell whose join is a null-aware LEFT ANTI one fed a HAVING-filtered
changelog. On a line of its own: the join's ``bucket_width`` (the last
the window saw) and the ``rewinds`` and ``grows`` of the whole window — a
width above the configuration's, or either count above 0, means the
arenas were rebuilt (and a program compiled) inside the window. Nothing
where no barrier of the window has a join span."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import join_busy_ms

SPAN = "HashJoin.chunks"


def read(ctx: dict):
    value = join_busy_ms.read(ctx)
    if value is None:
        return None
    args = [s.get("args") or {} for _b, spans in ps.window(ctx)
            for s in spans if s["name"] == SPAN]
    print(json.dumps({"ajoin_busy": {
        "bucket_width": next((a["bucket_width"] for a in reversed(args)
                              if "bucket_width" in a), None),
        "rewinds": sum(a.get("rewinds", 0) for a in args),
        "grows": sum(a.get("grows", 0) for a in args)}}), flush=True)
    return value
