"""materialize_ms — per barrier, the host time the MV's egress accounts
for: the ``Materialize.chunks`` roll-up (its own steps over the barrier's
chunks — since ISSUE 28 one async fetch a chunk; before, the blocking
fetches and the Python rows) plus the ``Materialize.barrier`` span (the
wait for the epoch's fetches, the columnar encode and stage, the table's
seal). Median over the covered window barriers. Nothing where no barrier
of the window has a Materialize span (a program without the spans); a
program that has them owes both on every barrier."""

from benchmark import program_spans as ps

NAMES = ("Materialize.chunks", "Materialize.barrier")


def per_barrier(spans: list) -> float:
    return sum(ps.ms(ps.named(spans, name, "materialize_ms"))
               for name in NAMES)


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] in NAMES for _b, spans in covered for s in spans):
        return None
    return ps.median_over(ctx, per_barrier)
