"""packed_rows_pct — of the rows a checkpoint's commit hands the segment
writer, the share that reached it in a packed layer (the codec's blobs,
never one Python ``bytes`` a row): 100 x ``packed`` / ``rows`` of
``commit.pending``. Median over the covered CHECKPOINT barriers of the
window; prints ``rows`` and ``packed`` of ``commit.pending``,
``segment.encode`` and ``store.apply``, and the state tables that handed
on a dict layer (``dict_tables``: a table whose types the codec does not
serve, or a row-at-a-time writer). Nothing where no barrier's
``commit.pending`` carries ``packed`` (a program before the packed
delta)."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median

NAME = "commit.pending"
NAMES = (NAME, "segment.encode", "store.apply")


def _args(span: dict) -> dict:
    return span.get("args") or {}


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None:
        return None
    found = [[s for s in spans if s["name"] in NAMES]
             for b, spans in covered if b["ledger"]["checkpoint"]]
    pendings = [[s for s in spans if s["name"] == NAME and "packed" in _args(s)]
                for spans in found]
    if not any(pendings):
        return None
    if not all(pendings):
        raise LookupError(
            "packed_rows_pct: a checkpoint barrier of the window has no "
            "commit.pending with a packed count")
    shares = []
    for spans in pendings:
        rows = sum(_args(s).get("rows", 0) for s in spans)
        packed = sum(_args(s)["packed"] for s in spans)
        shares.append(100.0 * packed / rows if rows else 100.0)
    print(json.dumps({"packed_rows": {
        **{name: actor_run_ms.counts(
            [[s for s in spans if s["name"] == name] for spans in found],
            ("rows", "packed")) for name in NAMES},
        "dict_tables": sorted({t for spans in pendings for s in spans
                               for t in _args(s).get("dict_tables", ())})}}),
        flush=True)
    return median(shares)
