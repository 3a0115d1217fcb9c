"""delta_wait_ms — of a checkpoint's state-table deltas, the host's wait
for the device: every ``delta.fetch_wait`` of the barrier (``wait =
device``; inside each ``*.state_delta`` span: window 0's blocking fetch
and the later windows' results). Median over the covered CHECKPOINT
barriers of the window; prints ``windows``. Nothing where no barrier of
the window has such a span; a program that has it owes it on every
checkpoint barrier.

The line also lays every ``*.state_delta`` span of a checkpoint out by
part — its own ms, the three children's and what is left as its self
time (window 0's dispatch, the numpy cut, the caller's masks and reset)
— with the counts it carries, medians over the same barriers."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median

NAME = "delta.fetch_wait"
PARTS = (NAME, "delta.encode", "delta.stage")
DELTA_COUNTS = ("dirty_groups", "dirty_rows", "windows", "bytes_fetched",
                "bytes_staged", "shards")


def by_delta(covered: list) -> dict:
    """``{"<parent name>[.<side>]": {"ms", "<part>", ..., "self_ms",
    counts}}``, medians over the checkpoint barriers."""
    per: dict = {}
    for b, spans in covered:
        if not b["ledger"]["checkpoint"]:
            continue
        for delta in spans:
            if not delta["name"].endswith(".state_delta"):
                continue
            args = delta.get("args") or {}
            label = ".".join(filter(None, (delta["name"], args.get("side"))))
            row = {"ms": delta["dur_ns"] / 1e6}
            for part in PARTS:
                row[part] = ps.ms([s for s in spans if s["name"] == part
                                   and s["parent"] == delta["id"]])
            row["self_ms"] = row["ms"] - sum(row[p] for p in PARTS)
            row.update({k: args[k] for k in DELTA_COUNTS if k in args})
            rows = per.setdefault(label, {})
            for key, value in row.items():
                rows.setdefault(key, []).append(value)
    return {label: {key: median(values) for key, values in rows.items()}
            for label, rows in per.items()}


def read(ctx: dict):
    found = actor_run_ms.find(ctx, "delta_wait_ms", (NAME,),
                              checkpoint_only=True)
    if found is None:
        return None
    print(json.dumps({"delta_wait": {
        "checkpoint_barriers": len(found),
        **actor_run_ms.counts(found, ("windows",)),
        "by_delta": by_delta(ps.window(ctx))}}), flush=True)
    return median([ps.ms(spans) for spans in found])
