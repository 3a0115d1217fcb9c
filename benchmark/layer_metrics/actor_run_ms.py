"""actor_run_ms — per barrier, the job tasks' own clock: the ``actor.run``
spans (one a job task and epoch, from the task's first message after the
last barrier to the moment it has passed this barrier on), summed over
the tasks. Median over the covered window barriers. ``collect_ms`` minus
this is the conductor and the event loop's entry and exit. The spans'
``messages`` and the ``source.feed`` span's counts (what the sources
staged for the barrier) are printed on a line of their own.

``find`` and ``counts`` serve the other readers of the span tree's leaves
too (``executor_unowned_ms``, ``delta_*_ms``, ``commit_*_ms``): nothing
where NO barrier of the window has a span of the names (a program without
them); a program that has them owes every name on every barrier the
metric is over."""

import json

from benchmark import program_spans as ps
from benchmark.window import median

NAME = "actor.run"
FEED = "source.feed"
FEED_COUNTS = ("chunks", "capacity_rows", "transfers", "bytes_staged",
               "dispatches")


def find(ctx: dict, metric: str, names: tuple,
         checkpoint_only: bool = False):
    """``[[span, ...], ...]``: per covered barrier (checkpoint barriers
    alone, if asked) its spans called one of ``names``; None where the
    window has none at all or no such barrier; ``LookupError`` where a
    barrier lacks one of the names."""
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] in names for _b, spans in covered for s in spans):
        return None
    out = []
    for b, spans in covered:
        if checkpoint_only and not b["ledger"]["checkpoint"]:
            continue
        found = [s for s in spans if s["name"] in names]
        missing = set(names) - {s["name"] for s in found}
        if missing:
            raise LookupError(
                f"{metric}: no span {sorted(missing)} in epoch "
                f"{b['ledger']['epoch']}")
        out.append(found)
    return out or None


def counts(found: list, args: tuple) -> dict:
    """Median over the barriers of each arg summed over a barrier's spans
    (an arg no span of the window carries is left out)."""
    out = {}
    for arg in args:
        per = [sum((s.get("args") or {}).get(arg, 0) for s in spans)
               for spans in found
               if any(arg in (s.get("args") or {}) for s in spans)]
        if per:
            out[arg] = median(per)
    return out


def read(ctx: dict):
    found = find(ctx, "actor_run_ms", (NAME,))
    if found is None:
        return None
    feeds = [[s for s in spans if s["name"] == FEED]
             for _b, spans in ps.window(ctx)]
    print(json.dumps({"actor_run": {
        "tasks": median([len(spans) for spans in found]),
        **counts(found, ("messages",)),
        "source_feed": counts(feeds, FEED_COUNTS)}}), flush=True)
    return median([ps.ms(spans) for spans in found])
