"""mesh_state_delta_ms — the sharded hash agg's checkpoint delta: its
``agg.state_delta`` span (the sharded state fetched, the dirty groups of
every shard found and staged into the state table), told from a one-chip
agg's span of the same name by the ``shards`` count it carries. Median
over the covered CHECKPOINT barriers of the window. Nothing where no
barrier of the window has such a span (a program whose sharded executor
records none); one that has it owes it on every checkpoint barrier. The
span's counts (medians over the same barriers) are printed on a line of
their own."""

import json

from benchmark import program_spans as ps
from benchmark.window import median


def sharded(spans: list) -> list:
    return [s for s in spans if s["name"] == "agg.state_delta"
            and "shards" in (s.get("args") or {})]


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(sharded(spans) for _b, spans in covered):
        return None
    values, counts = [], {}
    for b, spans in covered:
        if not b["ledger"]["checkpoint"]:
            continue
        found = sharded(spans)
        if not found:
            raise LookupError(
                "mesh_state_delta_ms: no sharded agg.state_delta in "
                f"checkpoint epoch {b['ledger']['epoch']}")
        values.append(ps.ms(found))
        for name in found[0]["args"]:
            counts.setdefault(name, []).append(
                sum(s["args"][name] for s in found))
    if not values:
        return None
    print(json.dumps({"mesh_state_delta": {
        "checkpoint_barriers": len(values),
        **{name: median(v) for name, v in counts.items()}}}), flush=True)
    return median(values)
