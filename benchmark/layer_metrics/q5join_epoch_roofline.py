"""q5join_epoch_roofline — the least time the chip could take for the
traced barriers' join work over the device time of the join's programs
in the trace (those the configuration lists under
``trace_programs.join_epoch``), for the self-join of NEXmark q5.

The least work, from shapes alone and the same whatever implements the
step: per row the join takes in (the reference's ``groups_touched[b][0]``:
the AuctionBids and MaxBids changelog rows of the barrier) the row is
read once, its arena row written once, and one key slot of the opposite
side read; per row the view gained or lost in the barrier
(``groups_touched[b][1]``, barrier end against barrier end) one output
row written. The candidate rows a MaxBids update scans, the rows emitted
and taken back again inside one barrier, are NOT counted, so the share
can only read low. Zero FLOPs are counted, so the HBM roof binds. The row
widths are the join's schemas as q5 has them (``ROW_BYTES``: BIGINT and
TIMESTAMP 8 bytes, one validity byte a column; left ``(auction, num,
starttime)``, right ``(maxn, starttime_c)``; a row in is counted at the
wider side's width), the key ``(starttime)``, the output row both sides'
columns.

A cell that lists this metric has to give it something to read: a
configuration without ``trace_programs.join_epoch``, or a trace that
lacks ANY of the named programs, is an error and the run gives no result,
as with ``join_epoch_roofline``. Nothing only where there is no device
trace at all."""

import json

#: one input row of the join, data and validity bytes
ROW_BYTES = {"left": 3 * (8 + 1), "right": 2 * (8 + 1)}
#: one key slot of the opposite side's table: the 8-byte key column, its
#: validity byte and the slot's occupancy
KEY_SLOT_BYTES = 8 + 1 + 1
#: one output row: both sides' columns
OUT_ROW_BYTES = sum(ROW_BYTES.values())


def work(rows_in: int, rows_out: int) -> dict:
    """Least FLOPs and bytes for a barrier whose join takes in ``rows_in``
    rows and changes ``rows_out`` rows of the view."""
    row = max(ROW_BYTES.values())
    return {"flops": 0,
            "bytes": rows_in * (2 * row + KEY_SLOT_BYTES)
            + rows_out * OUT_ROW_BYTES}


def read(ctx: dict):
    trace, config = ctx["trace"], ctx["config"]
    if not trace:
        return None
    names = config.get("trace_programs", {}).get("join_epoch")
    if not names:
        raise LookupError(
            f"q5join_epoch_roofline: configuration {config['name']!r} names "
            "no 'trace_programs.join_epoch'")
    missing = [n for n in names if trace["program_s"].get(n, 0.0) <= 0]
    if missing:
        raise LookupError(
            f"q5join_epoch_roofline: the trace holds no device time for "
            f"{missing} (programs in the trace: "
            f"{sorted(trace['program_s'])}): the configuration's "
            "trace_programs.join_epoch no longer names the join's programs")
    summed = {n: trace["program_s"][n] for n in names}
    device_s = sum(summed.values())
    per = [ctx["groups_touched"][ctx["first_barrier"] + i]
           for i in ctx["traced"]]
    rows_in = sum(p[0] for p in per)
    rows_out = sum(p[1] for p in per)
    least = work(rows_in, rows_out)["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    print(json.dumps({"q5join_epoch_roofline": {
        "program_s_summed": summed, "device_s": device_s,
        "least_s": least, "rows_in": rows_in, "rows_out": rows_out,
        "traced_barriers": len(ctx["traced"])}}), flush=True)
    return 100.0 * least / device_s
