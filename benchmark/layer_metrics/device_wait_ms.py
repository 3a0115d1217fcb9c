"""device_wait_ms — per barrier, the host time spent blocked on the
device: the sum of the program's spans marked ``wait = device``
(``cosched.epoch_wait``: the fused flush's packed fetch, which lands when
the epoch program has run; ``agg.flush_wait``: the hash agg's barrier
fetch on the executor path). Median over the covered window barriers. A
barrier without any such span is an error: both paths fetch once a
barrier."""

from benchmark import program_spans as ps


def per_barrier(spans: list) -> float:
    waits = [s for s in spans if s.get("wait") == "device"]
    if not waits:
        raise LookupError(
            f"device_wait_ms: no span with wait = 'device' in epoch "
            f"{spans[0]['epoch']}")
    return ps.ms(waits)


def read(ctx: dict):
    return ps.median_over(ctx, per_barrier)
