#!/usr/bin/env python3
"""Charge the device's idle time to the program span the host was in.

    python3 scripts/idle_by_span.py --config nexmark-q5core-fused --seed 7

Drives one of the benchmark's deployments (``benchmark/configs/<name>.json``
through ``benchmark.system.System``) for ``--warmup`` barriers, then
``--barriers`` more under ``jax.profiler``. The program's spans
(``common/tracing.span``) are in the profiler's trace as host annotations,
on the same clock as the device's operations, so every idle gap of the
device can be split at the span boundaries and each piece charged to the
INNERMOST span it falls in (``between ticks`` outside any). Prints one JSON
line: idle seconds by span, by ``<span> after <program that ran before>``,
and the host seconds of every span, over the traced barriers.

Until a ``benchmark`` PR passes the program's span names to
``benchmark/trace.py``'s ``extract`` itself, this script is how PERF.md §5
gets its by-span attribution; it then goes (PERF.md §7). On a machine
without a TPU (``--tiny``) there is no device plane: it prints the host
spans alone and says so.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as harness  # noqa: E402
from benchmark import system, trace  # noqa: E402

OUTSIDE = "between ticks"


class Innermost:
    """The innermost annotation at every instant (annotations nest, so
    that is the one that started last among those still open), over a
    trace's annotations sorted once: an executor path's trace holds a few
    hundred step annotations a barrier and tens of thousands of gaps."""

    def __init__(self, notes: list):
        self.by_start = sorted(notes, key=lambda n: n[1])
        self.starts = [n[1] for n in self.by_start]
        self.cuts = sorted({t for _n, s, d in notes for t in (s, s + d)})
        # the latest end among the annotations up to each one: where it
        # is past, nothing that started earlier is still open
        self.open_until, latest = [], 0
        for _n, s, d in self.by_start:
            latest = max(latest, s + d)
            self.open_until.append(latest)

    def at(self, t: int) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.open_until[i] > t:
            name, s, d = self.by_start[i]
            if t < s + d:
                return name
            i -= 1
        return OUTSIDE

    def pieces(self, lo: int, hi: int) -> list:
        """``[(start, end, name), ...]`` covering ``[lo, hi)``."""
        cuts = [lo, *self.cuts[bisect.bisect_right(self.cuts, lo):
                               bisect.bisect_left(self.cuts, hi)], hi]
        out = []
        for a, b in zip(cuts, cuts[1:]):
            name = self.at((a + b) // 2)
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
        return out


def attribute(raw: dict) -> dict:
    """Idle seconds of the first device plane by innermost span."""
    notes = raw["annotations"]
    ticks = [n for n in notes if n[0] == "session.tick"]
    lo = min(s for _n, s, _d in ticks)
    hi = max(s + d for _n, s, d in ticks)
    device = next(d for d in raw["devices"] if d["ops"])
    ops = [[max(s, lo), min(s + d, hi)] for _n, s, d in device["ops"]]
    busy_ns, busy = trace.union_ns([o for o in ops if o[1] > o[0]])
    programs = sorted((s + d, trace.program_name(n))
                      for n, s, d in device["programs"])
    ends = [p[0] for p in programs]
    gaps, cursor = [], lo
    for s, e in busy:
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        gaps.append((cursor, hi))
    by_span: dict = {}
    by_pair: dict = {}
    innermost = Innermost(notes)
    for g0, g1 in gaps:
        i = bisect.bisect_right(ends, g0)
        before = programs[i - 1][1] if i else "start"
        for a, b, name in innermost.pieces(g0, g1):
            by_span[name] = by_span.get(name, 0) + (b - a)
            key = f"{name} after {before}"
            by_pair[key] = by_pair.get(key, 0) + (b - a)
    rank = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                      sorted(d.items(), key=lambda kv: -kv[1])]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy_ns / 1e9,
            "idle_s": sum(g1 - g0 for g0, g1 in gaps) / 1e9,
            "idle_by_span_s": rank(by_span),
            "idle_by_span_after_program_s": rank(by_pair)[:16]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True,
                    help="a file name under benchmark/configs, without .json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--barriers", type=int, default=20)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from risingwave_tpu.common import tracing

    config = harness.load_json(ROOT, "benchmark", "configs",
                               f"{args.config}.json")
    if args.tiny:
        config = harness.tiny_sizes(config)
    system.enable_compile_cache()
    work_dir = tempfile.mkdtemp(prefix="rw_idle_by_span_")
    sut = system.System(config, os.path.join(work_dir, "data"), args.seed)
    sut.create()
    for _ in range(args.warmup):
        sut.barrier()
    tracing.GLOBAL_TRACE.clear()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    log_dir = os.path.join(work_dir, "trace")
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        for _ in range(args.barriers):
            sut.barrier()
    finally:
        jax.profiler.stop_trace()
    history = sut.barrier_history()[-args.barriers:]
    sut.close()

    names = {d["name"] for spans in tracing.epoch_spans().values()
             for d in spans}
    raw = trace.extract(trace.find_xplane(log_dir), tuple(names))
    host: dict = {}
    for name, _s, dur in raw["annotations"]:
        host[name] = host.get(name, 0) + dur / 1e9
    out = {"config": args.config, "barriers": args.barriers,
           "checkpoint_barriers": sum(h["checkpoint"] for h in history),
           "device": jax.devices()[0].device_kind,
           "host_span_s": dict(sorted(host.items(), key=lambda kv: -kv[1]))}
    if any(d["ops"] for d in raw["devices"]):
        out.update(attribute(raw))
    else:
        out["note"] = "no device plane in the trace: host spans only"
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
