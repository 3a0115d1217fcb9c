"""commit_apply_ms — of a checkpoint's commit, the store's in-memory
half: ``commit.pending`` (the epochs' staged deltas merged into one
delta a table) + ``store.apply`` (that delta applied to the committed
view, row by row). Median over the covered CHECKPOINT barriers of the
window; prints ``rows`` of each. Nothing where no barrier of the window
has such a span; a program that has them owes both on every checkpoint
barrier."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median

NAMES = ("commit.pending", "store.apply")


def read(ctx: dict):
    found = actor_run_ms.find(ctx, "commit_apply_ms", NAMES,
                              checkpoint_only=True)
    if found is None:
        return None
    by_name = {name: [[s for s in spans if s["name"] == name]
                      for spans in found] for name in NAMES}
    print(json.dumps({"commit_apply": {
        name: {"ms": median([ps.ms(spans) for spans in per]),
               **actor_run_ms.counts(per, ("rows",))}
        for name, per in by_name.items()}}), flush=True)
    return median([ps.ms(spans) for spans in found])
