"""delta_encode_ms — of a checkpoint's state-table deltas, the dirty rows
turned into key and value bytes: every ``delta.encode`` of the barrier
(the native codec's ``encode_keys`` / ``encode_value_rows``, or the Python
row loop where it does not serve). Median over the covered CHECKPOINT
barriers of the window; prints ``rows``, ``bytes``, ``native`` (spans the
codec served). Nothing where no barrier of the window has such a span; a
program that has it owes it on every checkpoint barrier."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median

NAME = "delta.encode"


def read(ctx: dict):
    found = actor_run_ms.find(ctx, "delta_encode_ms", (NAME,),
                              checkpoint_only=True)
    if found is None:
        return None
    print(json.dumps({"delta_encode": {
        "spans": median([len(spans) for spans in found]),
        **actor_run_ms.counts(found, ("rows", "bytes", "native"))}}),
        flush=True)
    return median([ps.ms(spans) for spans in found])
