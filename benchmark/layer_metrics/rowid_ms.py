"""rowid_ms — per barrier, what giving the source's rows their hidden
``_row_id`` costs the host on the default path: ``RowIdAppend.chunks``
(the column's slot: two eager dispatches a chunk) + ``RowIdGen.chunks`` +
``RowIdGen.barrier`` (the serial ids: one jitted step a chunk) — what
making the ids where the chunks are staged would remove (ROADMAP A4 (i)).
Median over the covered window barriers. Nothing where no barrier of the
window has such a span (a program whose append has no clock; the fused
cell, whose bids are made on the device)."""

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median

NAMES = ("RowIdAppend.chunks", "RowIdGen.chunks", "RowIdGen.barrier")


def read(ctx: dict):
    covered = ps.window(ctx)
    if covered is None or not any(
            s["name"] == NAMES[0] for _b, spans in covered for s in spans):
        return None
    found = actor_run_ms.find(ctx, "rowid_ms", NAMES)
    return median([ps.ms(spans) for spans in found])
