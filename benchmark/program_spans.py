"""The program's own spans, for the per-layer metrics that read them.

The second and last place of the benchmark that knows the program (the
first is ``system.py``): the program records every named piece of a
barrier through one primitive into a ring that outlives the session
(``risingwave_tpu.common.tracing.epoch_spans()`` → ``{epoch: [span dict,
...]}``; a span dict has ``name``, ``start_ns``, ``dur_ns``, ``epoch``,
``id``, ``parent``, ``wait``, ``args``). ``window(ctx)`` hands the readers
the spans of the window's barriers, found by the epochs of the ledger
records in ``ctx["barriers"]``.

* A program without ``epoch_spans`` (an older commit) gives ``None``, and
  every span metric is left out of the line.
* A program that has it owes the readers what their metrics name: a span
  missing from a covered barrier, or fewer covered window barriers than
  were traced (``len(ctx["traced"])``: the ring was too small for the run),
  raises ``LookupError`` and the run gives no result.

One line is printed per run: how many window barriers the ring still
held, the median per barrier of every span name, and each operator's
``<identity>.chunks`` roll-up.
"""

from __future__ import annotations

import json
from typing import Callable, Optional

from benchmark.window import median

_KEY = "_program_spans"


def load() -> Optional[dict]:
    """``{epoch: [span dict, ...]}`` from the program's ring, or None
    where the program has no such call."""
    try:
        from risingwave_tpu.common import tracing
    except ImportError:
        return None
    epoch_spans = getattr(tracing, "epoch_spans", None)
    return epoch_spans() if epoch_spans is not None else None


def ms(spans: list) -> float:
    return sum(s["dur_ns"] for s in spans) / 1e6


def named(spans: list, name: str, metric: str) -> list:
    """The spans of one barrier called ``name``; none is an error."""
    found = [s for s in spans if s["name"] == name]
    if not found:
        raise LookupError(
            f"{metric}: no span {name!r} in epoch {spans[0]['epoch']} "
            f"(has: {sorted({s['name'] for s in spans})})")
    return found


def window(ctx: dict) -> Optional[list]:
    """``[(barrier, spans), ...]`` for the window barriers whose spans the
    ring still holds whole, in window order; None where the program has
    no spans to give. Cached in ``ctx``; prints the run's one line."""
    if _KEY in ctx:
        return ctx[_KEY]
    by_epoch = load()
    if by_epoch is None:
        ctx[_KEY] = None
        return None
    covered = [(b, by_epoch[b["ledger"]["epoch"]]) for b in ctx["barriers"]
               if b["ledger"] and b["ledger"]["epoch"] in by_epoch]
    need = len(ctx.get("traced") or ())
    if len(covered) < max(need, 1):
        raise LookupError(
            f"program spans: the ring holds {len(covered)} of the window's "
            f"{len(ctx['barriers'])} barriers, {max(need, 1)} are needed: "
            "raise observability.trace_ring_capacity")
    per_name: dict = {}
    for _b, spans in covered:
        sums: dict = {}
        for s in spans:
            sums[s["name"]] = sums.get(s["name"], 0.0) + s["dur_ns"] / 1e6
        for name, value in sums.items():
            per_name.setdefault(name, []).append(value)
    medians = {name: median(values)
               for name, values in sorted(per_name.items())
               if not name.startswith("epoch ")}
    print(json.dumps({"program_spans": {
        "window_barriers": len(ctx["barriers"]),
        "covered_barriers": len(covered),
        "spans_per_barrier": median([len(s) for _b, s in covered]),
        "median_ms_where_present": medians,
        "chunks_median_ms": {n: v for n, v in medians.items()
                             if n.endswith(".chunks")}}}), flush=True)
    ctx[_KEY] = covered
    return covered


def median_over(ctx: dict, per_barrier: Callable[[list], float],
                checkpoint_only: bool = False) -> Optional[float]:
    """Median of ``per_barrier(spans)`` over the covered window barriers
    (over the checkpoint barriers among them alone, if asked)."""
    covered = window(ctx)
    if covered is None:
        return None
    return median([per_barrier(spans) for b, spans in covered
                   if b["ledger"]["checkpoint"] or not checkpoint_only])
