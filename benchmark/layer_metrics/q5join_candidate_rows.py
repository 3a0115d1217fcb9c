"""q5join_candidate_rows — per barrier, the arena rows the q5 self-join's
fan-out probes read: the ``candidate_rows`` of ``HashJoin.chunks``,
counted on the device inside the join's step (for each live probe row of
a MaxBids chunk, the AuctionBids rows of its window key, whether or not
they pass num >= maxn) and fetched with its packed stats. Median over the
covered window barriers; lower is better — it is what an index on
``num`` would cut, where the rows that pass are one or two a window.
Nothing where no join span carries the count (a program without the
fan-out arena); a program that has it owes it on every barrier."""

from benchmark import program_spans as ps

SPAN = "HashJoin.chunks"


def per_barrier(spans: list) -> float:
    total = 0
    for s in ps.named(spans, SPAN, "q5join_candidate_rows"):
        args = s.get("args") or {}
        if "candidate_rows" not in args:
            raise LookupError(
                f"q5join_candidate_rows: {SPAN} of epoch {s['epoch']} "
                "carries no candidate_rows")
        total += args["candidate_rows"]
    return float(total)


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            "candidate_rows" in (s.get("args") or {})
            for _b, spans in covered for s in spans if s["name"] == SPAN):
        return None
    return ps.median_over(ctx, per_barrier)
