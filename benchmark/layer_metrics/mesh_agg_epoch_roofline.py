"""mesh_agg_epoch_roofline — the least time the MESH could take for the
traced barriers' grouped-agg epochs over the device time its epoch
programs took, per device.

The work is the one-chip cells' (``benchmark/work.py`` by the name the
configuration gives: the same bytes an event and a flushed group,
whatever implements the epoch); the roof is the mesh's, ``devices`` x the
chip's HBM bandwidth. The denominator is the per-device mean of the
device seconds of the programs the configuration lists under
``trace_programs.mesh_agg_epoch`` (``trace.reduce`` sums a program over
the device planes and divides by their number). Bytes the exchange moves
between chips are not counted, so the share can only read low.

Nothing where there is no device trace, and nothing for a program that
does not yet name its sharded step: one whose ``ShardedHashAgg.barrier``
spans carry no ``rows_routed`` (the commit before the step had a stable
name). For a program that does, a configuration without ``work`` or the
list, or a trace that lacks ANY of the named programs, is an error and
the run gives no result."""

import json

from benchmark import program_spans as ps
from benchmark import work

KEY = "mesh_agg_epoch"


def names_its_step(ctx: dict) -> bool:
    covered = ps.window(ctx)
    return covered is not None and any(
        "rows_routed" in (s.get("args") or {})
        for _b, spans in covered for s in spans
        if s["name"] == "ShardedHashAgg.barrier")


def read(ctx: dict):
    trace, config = ctx["trace"], ctx["config"]
    if not trace or not names_its_step(ctx):
        return None
    names = config.get("trace_programs", {}).get(KEY)
    if not names or "work" not in config:
        raise LookupError(
            f"mesh_agg_epoch_roofline: configuration {config['name']!r} "
            f"names no 'work' function or no 'trace_programs.{KEY}'")
    missing = [n for n in names if trace["program_s"].get(n, 0.0) <= 0]
    if missing:
        raise LookupError(
            f"mesh_agg_epoch_roofline: the trace holds no device time for "
            f"{missing} (programs in the trace: "
            f"{sorted(trace['program_s'])}): the configuration's "
            f"trace_programs.{KEY} no longer names the epoch's programs")
    summed = {n: trace["program_s"][n] for n in names}
    device_s = sum(summed.values())
    mesh = {**ctx["peaks"], "hbm_bytes_per_s":
            trace["devices"] * ctx["peaks"]["hbm_bytes_per_s"],
            "flops_per_s_bf16":
            trace["devices"] * ctx["peaks"]["flops_per_s_bf16"]}
    least = 0.0
    for i in ctx["traced"]:
        w = work.of(config, ctx["events_per_barrier"],
                    ctx["groups_touched"][ctx["first_barrier"] + i])
        least += work.least_seconds(w, mesh)[0]
    print(json.dumps({"mesh_agg_epoch_roofline": {
        "program_s_summed_per_device": summed, "device_s": device_s,
        "least_s": least, "devices": trace["devices"],
        "traced_barriers": len(ctx["traced"])}}), flush=True)
    return 100.0 * least / device_s
