"""commit_io_ms — of a checkpoint's commit, what goes to the object
store: ``segment.put`` (the encoded segment's ``put`` with its fsync) +
``manifest.write`` (the manifest read, re-serialised and published by an
atomic rename), inside ``DurableStateStore.commit``. Median over the
covered CHECKPOINT barriers of the window; prints ``bytes`` and
``segments``, and, of the spans around them, ``segment.encode``'s ms
(``DurableStateStore.commit`` minus this metric) and the store writer's
own counts (``tables``, ``rows``, ``bytes``, ``native``) and self time.
Nothing where no barrier of the window has such a span; a program that
has them owes both on every checkpoint barrier."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import actor_run_ms
from benchmark.window import median

NAMES = ("segment.put", "manifest.write")
ENCODE = "segment.encode"
WRITERS = ("DurableStateStore.commit", "DurableStateStore.prepare",
           "DurableStateStore.commit_async")


def read(ctx: dict):
    found = actor_run_ms.find(ctx, "commit_io_ms", NAMES,
                              checkpoint_only=True)
    if found is None:
        return None
    checkpoints = [spans for b, spans in ps.window(ctx)
                   if b["ledger"]["checkpoint"]]
    encodes = [[s for s in spans if s["name"] == ENCODE]
               for spans in checkpoints]
    writers = [[s for s in spans if s["name"] in WRITERS]
               for spans in checkpoints]
    inside = [ps.ms(w) - ps.ms(e) - ps.ms(f)
              for w, e, f in zip(writers, encodes, found)]
    print(json.dumps({"commit_io": {
        **actor_run_ms.counts(found, ("bytes", "segments")),
        "segment_encode_ms": median([ps.ms(e) for e in encodes]),
        "writer": {"ms": median([ps.ms(w) for w in writers]),
                   "self_ms": median(inside),
                   **actor_run_ms.counts(
                       writers, ("tables", "rows", "bytes", "native"))}}}),
        flush=True)
    return median([ps.ms(spans) for spans in found])
