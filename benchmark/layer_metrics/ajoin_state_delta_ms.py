"""ajoin_state_delta_ms — the anti hash join's share of a checkpoint: its
``join.state_delta`` spans, one per side (the dirty rows selected and
gathered on the device, fetched, encoded and staged — the left side's
puts, the right side's puts AND the deletes of groups that left the set
or were replaced since the last checkpoint, in one delta a side). Both
sides summed, median over the covered CHECKPOINT barriers of the window.
The spans' counts (``dirty_rows``, ``windows``, ``bytes_fetched``; both
sides summed, medians over the same barriers) are printed on a line of
their own. Nothing where the program records no such span in the window;
a program that has it owes it on every checkpoint barrier."""

import json

from benchmark import program_spans as ps
from benchmark.layer_metrics import join_state_delta_ms
from benchmark.window import median

COUNTS = ("dirty_rows", "windows", "bytes_fetched")


def read(ctx: dict):
    value = join_state_delta_ms.read(ctx)
    if value is None:
        return None
    counts = {name: [] for name in COUNTS}
    for b, spans in ps.window(ctx):
        if not b["ledger"]["checkpoint"]:
            continue
        found = [s.get("args") or {} for s in spans
                 if s["name"] == join_state_delta_ms.NAME]
        for name in COUNTS:
            if any(name in a for a in found):
                counts[name].append(sum(a.get(name, 0) for a in found))
    print(json.dumps({"ajoin_state_delta": {
        name: median(v) for name, v in counts.items() if v}}), flush=True)
    return value
