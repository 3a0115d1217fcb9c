"""mesh_agg_busy_ms — per barrier, the host time the mesh-sharded hash agg
accounts for: its ``ShardedHashAgg.chunks`` roll-up (per input chunk the
split onto the mesh — ``shard.split``, inside this time — and the dispatch
of the sharded step) plus its ``ShardedHashAgg.barrier`` span (rank, the
flush's fetch, the output windows and, on a checkpoint, the sharded state
delta). Median over the covered window barriers. Nothing where no barrier
of the window has such a span (a deployment without a mesh, or a program
without the spans); an executor that has them owes both on every
barrier. Where the program records ``shard.split``, its median time and
counts are printed on a line of their own."""

import json

from benchmark import program_spans as ps
from benchmark.window import median

NAMES = ("ShardedHashAgg.chunks", "ShardedHashAgg.barrier")
SPLIT = "shard.split"


def per_barrier(spans: list) -> float:
    return sum(ps.ms(ps.named(spans, name, "mesh_agg_busy_ms"))
               for name in NAMES)


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] in NAMES for _b, spans in covered for s in spans):
        return None
    splits = [[s for s in spans if s["name"] == SPLIT]
              for _b, spans in covered]
    if all(splits):
        print(json.dumps({"shard_split": {
            "median_ms": median([ps.ms(found) for found in splits]),
            **{name: median([sum(s["args"][name] for s in found)
                             for found in splits])
               for name in ("chunks", "transfers")}}}), flush=True)
    return ps.median_over(ctx, per_barrier)
