"""join_busy_ms — per barrier, the host time the hash join accounts for:
its ``HashJoin.chunks`` roll-up (its own steps over the barrier's input
chunks: dispatch, the stats fetch, the output gathers; the consumer's
time excluded) plus its ``HashJoin.barrier`` span (flag check and, on a
checkpoint, the state delta of both sides). Median over the covered
window barriers. Nothing where no barrier of the window has a join span
(a deployment without a join, or a program without the spans); a join
that has them owes both on every barrier."""

from benchmark import program_spans as ps

NAMES = ("HashJoin.chunks", "HashJoin.barrier")


def per_barrier(spans: list) -> float:
    return sum(ps.ms(ps.named(spans, name, "join_busy_ms"))
               for name in NAMES)


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] in NAMES for _b, spans in covered for s in spans):
        return None
    return ps.median_over(ctx, per_barrier)
