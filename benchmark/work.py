"""What an epoch program has to do at the least, from shapes alone — the
same whatever implements the epoch. Each function is found by the name a
configuration gives under ``"work"`` and returns ``{"flops", "bytes"}``
for one barrier."""

from __future__ import annotations


def grouped_agg_epoch(params: dict, events: int, groups_flushed: int) -> dict:
    """A grouped aggregate's epoch: per ingested event the group-key and
    timestamp columns read once (``input_bytes_per_event``) and one group
    slot read and written once (key and lane bytes of the agg state); per
    flushed group one row gathered (``flush_row_bytes``). Counting and
    comparing keys is integer work of no note beside the traffic: zero
    FLOPs, so the memory roof is the bound."""
    slot = params["slot_key_bytes"] + params["slot_lane_bytes"]
    per_event = params["input_bytes_per_event"] + 2 * slot
    return {"flops": 0,
            "bytes": events * per_event
            + groups_flushed * params["flush_row_bytes"]}


def of(config: dict, events: int, groups_flushed: int) -> dict:
    """One barrier's work by the function the configuration names."""
    return globals()[config["work"]](config["work_params"], events,
                                     groups_flushed)


def least_seconds(work: dict, peaks: dict) -> tuple:
    """(seconds, which roof binds) for ``work`` on a chip with ``peaks``."""
    by_flops = work["flops"] / peaks["flops_per_s_bf16"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops > by_bytes else (by_bytes, "hbm")


def load_peaks(table: dict, device_kind: str) -> dict:
    """A device that is not in the table is an error, not a default."""
    try:
        return table["device_kinds"][device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table['device_kinds'])}): add it with its "
            "source before measuring on it") from None
