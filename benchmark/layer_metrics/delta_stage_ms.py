"""delta_stage_ms — of a checkpoint's state-table deltas, the encoded rows
put into the state table: every ``delta.stage`` of the barrier (the
``dict(zip(...))`` of keys and values, ``stage_encoded`` — or ``insert`` /
``delete`` a row —, the table's ``commit``). Median over the covered
CHECKPOINT barriers of the window; prints ``puts``, ``deletes``. Nothing
where no barrier of the window has such a span; a program that has it
owes it on every checkpoint barrier."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median

NAME = "delta.stage"


def read(ctx: dict):
    found = actor_run_ms.find(ctx, "delta_stage_ms", (NAME,),
                              checkpoint_only=True)
    if found is None:
        return None
    print(json.dumps({"delta_stage": actor_run_ms.counts(
        found, ("puts", "deletes"))}), flush=True)
    return median([ps.ms(spans) for spans in found])
