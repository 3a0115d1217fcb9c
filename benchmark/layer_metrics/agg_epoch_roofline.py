"""agg_epoch_roofline — the least time the chip could take for the traced
barriers' grouped-agg epochs (``benchmark/work.py``, by the name the
configuration gives; zero FLOPs, so the HBM roof binds) over the device
time of the epoch programs' events in the trace (the programs the
configuration lists under ``trace_programs.agg_epoch``).

A cell that lists this metric has to give it something to read: a
configuration without ``work`` or ``trace_programs.agg_epoch``, or a trace
that lacks ANY of the named programs, is an error and the run gives no
result — a program renamed or re-wrapped would otherwise drop out of the
denominator in silence and raise the share. The seconds summed are
printed, per program, on a line of their own. Nothing only where there is
no device trace at all."""

import json

from benchmark import work


def read(ctx: dict):
    trace, config = ctx["trace"], ctx["config"]
    if not trace:
        return None
    names = config.get("trace_programs", {}).get("agg_epoch")
    if not names or "work" not in config:
        raise LookupError(
            f"agg_epoch_roofline: configuration {config['name']!r} names no "
            "'work' function or no 'trace_programs.agg_epoch'")
    missing = [n for n in names if trace["program_s"].get(n, 0.0) <= 0]
    if missing:
        raise LookupError(
            f"agg_epoch_roofline: the trace holds no device time for "
            f"{missing} (programs in the trace: "
            f"{sorted(trace['program_s'])}): the configuration's "
            "trace_programs.agg_epoch no longer names the epoch's programs")
    summed = {n: trace["program_s"][n] for n in names}
    device_s = sum(summed.values())
    least = 0.0
    for i in ctx["traced"]:
        w = work.of(config, ctx["events_per_barrier"],
                    ctx["groups_touched"][ctx["first_barrier"] + i])
        least += work.least_seconds(w, ctx["peaks"])[0]
    print(json.dumps({"agg_epoch_roofline": {
        "program_s_summed": summed, "device_s": device_s,
        "least_s": least, "traced_barriers": len(ctx["traced"])}}),
        flush=True)
    return 100.0 * least / device_s
