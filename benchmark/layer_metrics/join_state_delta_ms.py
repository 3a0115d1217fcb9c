"""join_state_delta_ms — the hash join's share of a checkpoint: its
``join.state_delta`` spans, one per side (the dirty marks fetched, then
every ``[capacity, W]`` column of the side, the dirty rows encoded and
staged; inside ``HashJoinExecutor._checkpoint``). Both sides summed,
median over the covered CHECKPOINT barriers of the window. Nothing where
the program records no such span in the window (an older commit); a
program that has it owes it on every checkpoint barrier."""

from benchmark import program_spans as ps

NAME = "join.state_delta"


def per_barrier(spans: list) -> float:
    return ps.ms(ps.named(spans, NAME, "join_state_delta_ms"))


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] == NAME for _b, spans in covered for s in spans):
        return None
    return ps.median_over(ctx, per_barrier, checkpoint_only=True)
