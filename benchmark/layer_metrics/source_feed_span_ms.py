"""source_feed_span_ms — the program's own ``source.feed`` span: every
source of the deployment generating its chunks and queueing them, inside
``Session.tick()`` before the barrier is injected. Median over the window's
barriers the span ring still holds. Times from inside what
``source_feed_ms`` takes from outside by subtraction."""

from benchmark import program_spans as ps


def read(ctx: dict):
    return ps.median_over(ctx, lambda spans: ps.ms(
        ps.named(spans, "source.feed", "source_feed_span_ms")))
