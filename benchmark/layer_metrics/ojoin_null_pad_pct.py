"""ojoin_null_pad_pct — the share of the join's output that is the
outer-join mechanism: 100 x (``null_padded_out`` + 2 x ``transitions``)
/ ``rows_out`` of the ``HashJoin.chunks`` span, all three counted on the
device inside the step and fetched with the join's packed stats. A
NULL-padded row (an auction no bid has reached, emitted or retracted on
the ``pself`` lane) counts once; a degree transition 0 -> 1 or 1 -> 0 of
the opposite side counts twice, for the adjacent update pair that
replaces the NULL-padded row by the matched one (or restores it). Median
over the covered window barriers in which the join emitted a row. Nothing
where no span carries the counts (a program without them: the parent of
PR 33); a program that has them owes them on every barrier."""

from benchmark import program_spans as ps
from benchmark.window import median

SPAN = "HashJoin.chunks"
COUNTS = ("rows_out", "null_padded_out", "transitions")


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            "rows_out" in (s.get("args") or {})
            for _b, spans in covered for s in spans if s["name"] == SPAN):
        return None
    values = []
    for _b, spans in covered:
        total = dict.fromkeys(COUNTS, 0)
        for s in ps.named(spans, SPAN, "ojoin_null_pad_pct"):
            args = s.get("args") or {}
            missing = [name for name in COUNTS if name not in args]
            if missing:
                raise LookupError(
                    f"ojoin_null_pad_pct: {SPAN} of epoch {s['epoch']} "
                    f"carries no {missing}")
            for name in COUNTS:
                total[name] += args[name]
        if total["rows_out"]:
            values.append(100.0 * (total["null_padded_out"]
                                   + 2 * total["transitions"])
                          / total["rows_out"])
    return median(values) if values else None
