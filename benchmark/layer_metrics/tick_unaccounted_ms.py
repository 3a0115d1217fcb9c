"""tick_unaccounted_ms — what of a ``Session.tick()`` call no span names:
the ``session.tick`` root span minus its direct children. Median over the
covered window barriers. It holds the conductor's own bookkeeping between
stages and what ``tick()`` does after the ledger seals the barrier's
record (publishing the barrier, failure detection); a part of it that
grows past a millisecond should get a span of its own."""

from benchmark import program_spans as ps


def per_barrier(spans: list) -> float:
    (tick,) = ps.named(spans, "session.tick", "tick_unaccounted_ms")
    return ps.ms([tick]) - ps.ms(
        [s for s in spans if s["parent"] == tick["id"]])


def read(ctx: dict):
    return ps.median_over(ctx, per_barrier)
