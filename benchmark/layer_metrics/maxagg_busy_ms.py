"""maxagg_busy_ms — per barrier, the host time q5's MAX over the count's
changelog accounts for: the ``MaterializedAgg.chunks`` roll-up (the
changelog's rows taken into the per-window multisets, in Python) plus the
``MaterializedAgg.barrier`` span (each touched window's maximum
recomputed and emitted, and on a checkpoint its multiset staged). Median
over the covered window barriers. On a line of its own, the medians per
barrier of ``rows_in`` and ``groups_recomputed`` of
``MaterializedAgg.chunks`` (null where a program does not carry them).
Nothing where no barrier of the window has the spans; a program that has
them owes both on every barrier."""

import json

from benchmark import program_spans as ps
from benchmark.window import median

NAMES = ("MaterializedAgg.chunks", "MaterializedAgg.barrier")
COUNTS = ("rows_in", "groups_recomputed")


def per_barrier(spans: list) -> float:
    return sum(ps.ms(ps.named(spans, name, "maxagg_busy_ms"))
               for name in NAMES)


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] in NAMES for _b, spans in covered for s in spans):
        return None
    value = ps.median_over(ctx, per_barrier)
    args = [[s.get("args") or {} for s in spans if s["name"] == NAMES[0]]
            for _b, spans in covered]

    def med(name):
        values = [sum(a[name] for a in per) for per in args
                  if per and all(name in a for a in per)]
        return median(values) if values else None

    print(json.dumps({"maxagg_busy": {name: med(name) for name in COUNTS}}),
          flush=True)
    return value
