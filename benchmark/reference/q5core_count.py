"""The q5-core MV ``count(*) GROUP BY window_start, auction`` recomputed
from a replayed bid stream, and its comparison with the rows read back.
The stream-specific references (``q5core_device_stream``,
``q5core_host_stream``) replay their source's ``(auction, date_time)``
columns and hand them here. numpy only; nothing of the program."""

from __future__ import annotations

import numpy as np


def _codes(auction, ts, nx: dict):
    """One int64 per (window, auction): the window's index above, the
    auction id below (auction ids stay far under 2^40)."""
    window = (ts - nx["start_time_us"]) // nx["window_us"]
    return (window << 40) | auction


def expected(stream, nx: dict, rows: int, barriers: int, broken: str) -> dict:
    """The MV after ``barriers`` barriers: sorted ``[n, 3]`` int64 rows
    ``(window_start, auction, num)``, and how many groups each barrier
    touched (what its flush has to gather). ``stream`` yields
    ``(auction, date_time)`` blocks of shape ``[barriers_in_block,
    events_per_barrier]``, in order.

    ``broken`` is the CONTROL, never the reference: ``"at_least_once"``
    counts the last barrier's first chunk (``rows`` events) twice, as a
    source that is replayed after a restart without its offset would."""
    if broken not in ("", "at_least_once"):
        raise ValueError(f"q5-core has no control {broken!r}")
    uniq_parts, count_parts, touched = [], [], []
    seen = 0
    for auction, ts in stream:
        codes = _codes(auction, ts, nx)
        touched.extend(int(np.unique(row).size) for row in codes)
        seen += codes.shape[0]
        flat = codes.reshape(-1)
        if broken == "at_least_once" and seen == barriers:
            flat = np.concatenate([flat, codes[-1, :rows]])
        u, c = np.unique(flat, return_counts=True)
        uniq_parts.append(u)
        count_parts.append(c)
    if not uniq_parts:
        return {"rows": np.zeros((0, 3), np.int64), "groups_touched": []}
    u, inv = np.unique(np.concatenate(uniq_parts), return_inverse=True)
    # float64 weights are exact far beyond any count a run can reach
    counts = np.bincount(inv, weights=np.concatenate(count_parts),
                         minlength=u.size).astype(np.int64)
    window_start = nx["start_time_us"] + (u >> 40) * nx["window_us"]
    auction = u & ((1 << 40) - 1)
    return {"rows": np.stack([window_start, auction, counts], axis=1),
            "groups_touched": touched}


def rows_array(rows) -> np.ndarray:
    """``run_sql`` rows (or an ``[n, 3]`` array) in the reference's sorted
    layout."""
    arr = np.asarray(rows, dtype=np.int64).reshape(-1, 3)
    return arr[np.lexsort((arr[:, 1], arr[:, 0]))]


def compare(exp: dict, got_rows) -> dict:
    """The numbers compared, each exact (limit 0): rows of the MV that are
    not rows of the recomputation plus rows of the recomputation the MV
    lacks, and the events the MV's counts are short of (or over)."""
    want = exp["rows"]
    got = rows_array(got_rows)
    if got.shape == want.shape and np.array_equal(got, want):
        wrong = 0
    else:
        both = np.concatenate([got, want])
        _, counts = np.unique(both, axis=0, return_counts=True)
        wrong = int(np.sum(counts == 1))
    return {"rows_wrong": wrong,
            "events_off": abs(int(got[:, 2].sum()) - int(want[:, 2].sum())),
            "rows_expected": int(want.shape[0])}
