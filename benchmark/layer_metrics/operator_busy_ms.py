"""operator_busy_ms — per barrier, the host time the executors themselves
account for inside ``barrier.collect``: every operator's
``<identity>.chunks`` roll-up (its own steps over the barrier's chunks,
the consumer's time excluded) plus its ``<identity>.barrier`` span, taken
among the direct children of ``barrier.collect``. Median over the covered
window barriers. ``collect_ms`` minus this is the event loop, the queues
and whatever no operator owns."""

from benchmark import program_spans as ps


def per_barrier(spans: list) -> float:
    (collect,) = ps.named(spans, "barrier.collect", "operator_busy_ms")
    own = [s for s in spans if s["parent"] == collect["id"]
           and s["name"].endswith((".chunks", ".barrier"))]
    if not own:
        raise LookupError(
            f"operator_busy_ms: no operator span under barrier.collect in "
            f"epoch {spans[0]['epoch']}")
    return ps.ms(own)


def read(ctx: dict):
    return ps.median_over(ctx, per_barrier)
