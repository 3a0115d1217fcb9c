"""mesh_hot_shard_pct — how unevenly the exchange spreads a barrier's
rows over the shards: 100 x ``rows_routed_max`` / ``rows_routed`` of the
``ShardedHashAgg.barrier`` span (rows the all-to-all handed to the
fullest shard over rows handed to all of them, counted on the device
inside the step). 100 / shards is even (25 on four chips), 100 is one
chip doing all of it. Median over the covered window barriers that
routed a row. Nothing where no barrier span carries the counts (a
program without them); a program that has them owes them on every
barrier."""

from benchmark import program_spans as ps
from benchmark.window import median

SPAN = "ShardedHashAgg.barrier"


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            "rows_routed" in (s.get("args") or {})
            for _b, spans in covered for s in spans if s["name"] == SPAN):
        return None
    values = []
    for _b, spans in covered:
        routed = routed_max = 0
        for s in ps.named(spans, SPAN, "mesh_hot_shard_pct"):
            args = s.get("args") or {}
            if "rows_routed" not in args:
                raise LookupError(
                    f"mesh_hot_shard_pct: {SPAN} of epoch {s['epoch']} "
                    "carries no rows_routed")
            routed += args["rows_routed"]
            routed_max += args["rows_routed_max"]
        if routed:
            values.append(100.0 * routed_max / routed)
    return median(values) if values else None
