"""ajoin_retract_pct — the share of the anti join's output that takes a
row back: 100 x ``matched`` / ``rows_out`` of the ``HashJoin.chunks``
span, both counted on the device inside ``jit_join_pack_stats`` and
fetched with the join's packed stats. ``matched`` counts the degree
transitions 0 -> 1 of the preserved side: under a LEFT ANTI join each is
one auction row DELETED from the view because a first bid group reached
it; the rest of ``rows_out`` are the own rows of the ``pself`` lane
(``null_padded_out``: an auction no counted bid has reached, inserted)
and the ``unmatched`` transitions 1 -> 0 (a row that comes back). Median
over the covered window barriers in which the join emitted a row. The
window's totals of ``matched``, ``unmatched``, ``null_padded_out`` and
``rows_in_right`` are printed on a line of their own (``rows_in_right``
against the reference's groups entering + leaving is the count of
equal-row update pairs that reach the join). Nothing where no span
carries ``matched`` (a program without it: the parent of PR 37); a
program that has it owes the counts on every barrier."""

import json

from benchmark import program_spans as ps
from benchmark.window import median

SPAN = "HashJoin.chunks"
COUNTS = ("rows_out", "matched", "unmatched", "null_padded_out",
          "rows_in_right")


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            "matched" in (s.get("args") or {})
            for _b, spans in covered for s in spans if s["name"] == SPAN):
        return None
    values = []
    window = dict.fromkeys(COUNTS, 0)
    for _b, spans in covered:
        total = dict.fromkeys(COUNTS, 0)
        for s in ps.named(spans, SPAN, "ajoin_retract_pct"):
            args = s.get("args") or {}
            missing = [name for name in COUNTS if name not in args]
            if missing:
                raise LookupError(
                    f"ajoin_retract_pct: {SPAN} of epoch {s['epoch']} "
                    f"carries no {missing}")
            for name in COUNTS:
                total[name] += args[name]
        for name in COUNTS:
            window[name] += total[name]
        if total["rows_out"]:
            values.append(100.0 * total["matched"] / total["rows_out"])
    print(json.dumps({"ajoin_retract": window}), flush=True)
    return median(values) if values else None
