"""ajoin_epoch_roofline — the least time the chip could take for the
traced barriers' join input over the device time of the join's programs
in the trace (those the configuration lists under
``trace_programs.join_epoch``), for the LEFT ANTI join of q104.

The least work, from shapes alone and the same whatever implements the
step: per row the join takes in (``groups_touched``: the auctions
ingested plus the bid groups that entered or left the under-20 set in
the barrier) the row is read once, its arena row written once, and one
key slot of the opposite side read. The rows emitted — own rows and
retractions — are NOT counted, nor is an update pair that stays under 20
(the agg's row changed, the set did not), so the share can only read
low. Zero FLOPs are counted, so the HBM roof binds. The row widths are
the join's input schemas as q104 has them (``ROW_BYTES``: BIGINT and the
row id 8 bytes, VARCHAR a 4-byte id, one validity byte a column; left
``(id, item_name, _row_id)``, right ``(auction)``), the key ``(id |
auction)``; a barrier's rows are counted at the wider side's width.

A cell that lists this metric has to give it something to read: a
configuration without ``trace_programs.join_epoch``, or a trace that
lacks ANY of the named programs, is an error and the run gives no result,
as with ``join_epoch_roofline``. Nothing only where there is no device
trace at all."""

import json

#: one input row of the join, data and validity bytes
ROW_BYTES = {"left": 8 + 4 + 8 + 3, "right": 8 + 1}
#: one key slot of the opposite side's table: the 8-byte key column, its
#: validity byte and the slot's occupancy
KEY_SLOT_BYTES = 8 + 1 + 1


def work(rows_in: int) -> dict:
    """Least FLOPs and bytes for a barrier whose join input is
    ``rows_in`` rows."""
    row = max(ROW_BYTES.values())
    return {"flops": 0, "bytes": rows_in * (2 * row + KEY_SLOT_BYTES)}


def read(ctx: dict):
    trace, config = ctx["trace"], ctx["config"]
    if not trace:
        return None
    names = config.get("trace_programs", {}).get("join_epoch")
    if not names:
        raise LookupError(
            f"ajoin_epoch_roofline: configuration {config['name']!r} names "
            "no 'trace_programs.join_epoch'")
    missing = [n for n in names if trace["program_s"].get(n, 0.0) <= 0]
    if missing:
        raise LookupError(
            f"ajoin_epoch_roofline: the trace holds no device time for "
            f"{missing} (programs in the trace: "
            f"{sorted(trace['program_s'])}): the configuration's "
            "trace_programs.join_epoch no longer names the join's programs")
    summed = {n: trace["program_s"][n] for n in names}
    device_s = sum(summed.values())
    rows_in = sum(ctx["groups_touched"][ctx["first_barrier"] + i]
                  for i in ctx["traced"])
    least = work(rows_in)["bytes"] / ctx["peaks"]["hbm_bytes_per_s"]
    print(json.dumps({"ajoin_epoch_roofline": {
        "program_s_summed": summed, "device_s": device_s,
        "least_s": least, "rows_in": rows_in,
        "traced_barriers": len(ctx["traced"])}}), flush=True)
    return 100.0 * least / device_s
