"""join_wait_ms — per barrier, the host time the hash join spends blocked
on the device: its ``join.emit_wait`` spans (``wait = device``), the
fetch of the packed stats of the chunks applied since the last sync,
which lands when the device has run every one of their steps. It lies
inside ``HashJoin.chunks``. Median over the covered window barriers.
Nothing where the program records no such span in the window (an older
commit); a barrier in which the join took no chunk reads 0."""

from benchmark import program_spans as ps

NAME = "join.emit_wait"


def per_barrier(spans: list) -> float:
    return ps.ms([s for s in spans if s["name"] == NAME])


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] == NAME for _b, spans in covered for s in spans):
        return None
    return ps.median_over(ctx, per_barrier)
