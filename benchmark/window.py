"""The measured window: the closed loop that drives barriers, and the
arithmetic from its per-barrier host-clock readings to the end-to-end
metrics. Rates are taken over ALL the work and ALL the seconds of the
window, tails over ALL its barriers."""

from __future__ import annotations

import contextlib
import math
import time
from typing import Callable, Optional


def percentile(values: list, q: float) -> float:
    """Nearest-rank percentile (the smallest value with at least ``q`` of
    the sample at or below it) — no interpolation, so a p95 is always one
    of the barriers that ran."""
    if not values:
        raise ValueError("percentile of an empty sample")
    ordered = sorted(values)
    rank = max(1, math.ceil(q * len(ordered)))
    return ordered[rank - 1]


def median(values: list) -> Optional[float]:
    if not values:
        return None
    ordered = sorted(values)
    n = len(ordered)
    mid = n // 2
    return ordered[mid] if n % 2 else 0.5 * (ordered[mid - 1] + ordered[mid])


def drive(barrier: Callable[[], None], seconds: float, max_barriers: int,
          clock: Callable[[], float] = time.perf_counter,
          before: Optional[Callable[[int], None]] = None,
          around: Optional[Callable[[int], contextlib.AbstractContextManager]]
          = None) -> dict:
    """Run barriers back to back until ``seconds`` have passed or
    ``max_barriers`` have run, whichever is first. A barrier that starts
    inside the window is finished and counted, with its time.

    The traced run's hooks: ``before(i, seconds_left, barrier_s)`` runs
    ahead of barrier ``i`` once it is settled that ``i`` runs, outside its
    time (the profiler's start and stop), with the seconds the window has
    left and the barrier times so far; ``around(i)`` gives a context
    manager to run it inside (a trace annotation). Returns
    ``{"barrier_s": [...], "elapsed_s": first start -> last return,
    "stopped_by": "seconds" | "max_barriers"}``."""
    per: list = []
    start = clock()
    deadline = start + seconds
    end = start
    stopped_by = "seconds"
    while True:
        if len(per) >= max_barriers:
            stopped_by = "max_barriers"
            break
        t0 = clock()
        if t0 >= deadline:
            break
        if before is not None:
            before(len(per), deadline - t0, per)
            t0 = clock()
        with around(len(per)) if around else contextlib.nullcontext():
            barrier()
        end = clock()
        per.append(end - t0)
    return {"barrier_s": per, "elapsed_s": end - start,
            "stopped_by": stopped_by}


def end_to_end(barrier_s: list, elapsed_s: float,
               events_per_barrier: int) -> dict:
    """events_per_s over the whole window; barrier_p95_ms over all its
    barriers."""
    if not barrier_s or elapsed_s <= 0:
        raise ValueError("an empty window has no metrics")
    return {
        "events_per_s": len(barrier_s) * events_per_barrier / elapsed_s,
        "barrier_p95_ms": percentile(barrier_s, 0.95) * 1e3,
    }
