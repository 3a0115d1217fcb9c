"""fused_host_ms — the host's own work on the co-scheduled path before
the barrier is injected: the ``cosched.*`` spans directly under
``session.tick`` (epoch dispatch, flush begin, flush decode, a deferred
flush's resolution) without the time spent waiting for the device
(``cosched.epoch_wait``, also where it is nested in a resolution) and
without the checkpoint's restack (``cosched.restack``, which
``state_delta_ms`` holds). Median over the covered window barriers."""

from benchmark import program_spans as ps

NOT_HOST = ("cosched.epoch_wait", "cosched.restack")
OWED = ("cosched.dispatch", "cosched.flush_begin", "cosched.flush_decode")


def per_barrier(spans: list) -> float:
    for name in OWED:
        ps.named(spans, name, "fused_host_ms")
    (tick,) = ps.named(spans, "session.tick", "fused_host_ms")
    top = [s for s in spans if s["name"].startswith("cosched.")
           and s["parent"] == tick["id"] and s["name"] not in NOT_HOST]
    ids = {s["id"] for s in top}
    waits_inside = [s for s in spans if s["name"] == "cosched.epoch_wait"
                    and s["parent"] in ids]
    return ps.ms(top) - ps.ms(waits_inside)


def read(ctx: dict):
    return ps.median_over(ctx, per_barrier)
