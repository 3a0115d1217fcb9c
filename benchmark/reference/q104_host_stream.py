"""Plain reference for RisingWave's nexmark q104 (the auctions that are
NOT IN the set of auctions with fewer than 20 bids) over the HOST bid and
auction streams.

The deployment and its two sources are q101's, so the replay of the
columns the MV reads is the sibling reference's ``streams`` (numpy alone;
neither file imports anything of the program). This file counts the bids
per auction and recomputes the MV: one row per ingested auction whose
count is 0 or at least 20 — an auction a bid has reached is IN the
subquery's set until its 20th bid takes it out again.

Rows are held as ``(auction_id, item number)``; an item name is
``item-<n>``. The read-back's SQL rows come ``(item_name, auction_id)``,
as the configuration's ``select`` orders them.

The control is ``bid_chunk_lost`` (the last barrier's first bid chunk is
never counted): some auction stays in the view that a bid should have
removed. The q5 cells' ``at_least_once`` cannot serve: under this
connector only the hot auction of each 100 ids ever reaches 20 bids, and
it passes 20 by hundreds, so a bid chunk counted twice moves no auction
across either edge of the set (``benchmark/tests/test_q104.py`` holds
that as a test).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.q101_host_stream import (
    _item_number, _one_side_only, streams,
)

#: HAVING COUNT(*) < UNDER: the view's own constant
UNDER = 20


def expected(config: dict, seed: int, barriers: int, broken: str = "") -> dict:
    """The MV after ``barriers`` barriers: ``[n, 2]`` int64 rows
    ``(auction_id, item number)`` sorted by id; ``hot_ids``, the sorted
    ids with at least 20 bids; per barrier ``groups_touched``, the join's
    least input — the auctions ingested plus the bid groups that entered
    (first bid, fewer than 20 by the barrier's end) or left (20th bid) the
    under-20 set in that barrier; an update that stays under 20 is no
    work and is not counted; ``unbid_rows``, the rows without a bid; and
    from barrier end to barrier end ``retracted`` (auctions that were in
    the view and left it) and ``returned`` (ingested earlier, out of the
    view, now in it).

    ``broken`` is the CONTROL, never the reference: ``"bid_chunk_lost"``
    leaves the last barrier's first bid chunk (``rows`` bids) out, as a
    source that skips a chunk after a restart would."""
    if broken not in ("", "bid_chunk_lost"):
        raise ValueError(f"q104 has no control {broken!r}")
    nx = config["nexmark"]
    b_rows = config["rows_per_chunk"]["bid"]
    # every id a bid can name: cold bids reach in_flight ids under the first
    base = nx["first_auction_id"] - nx["in_flight_auctions"]
    per_epoch = (nx["person_proportion"] + nx["auction_proportion"]
                 + nx["bid_proportion"])
    k = barriers * config["chunks_per_tick"]
    n_ids = nx["in_flight_auctions"] + 1 + max(
        k * config["rows_per_chunk"]["auction"],
        nx["auction_proportion"] * (k * b_rows // per_epoch + 1))
    count = np.zeros(n_ids, np.int64)
    ingested = np.zeros(n_ids, np.bool_)
    ids, items, touched = [], [], []
    retracted = returned = 0
    for b, (aid, item, bid_auction, _price) in enumerate(
            streams(config, seed, barriers)):
        if broken == "bid_chunk_lost" and b == barriers - 1:
            bid_auction = bid_auction[b_rows:]
        ids.append(aid)
        items.append(item)
        group, bids = np.unique(bid_auction - base, return_counts=True)
        before = count[group]
        after = before + bids
        entered = (before == 0) & (after < UNDER)
        left = (before > 0) & (before < UNDER) & (after >= UNDER)
        seen = ingested[group]
        retracted += int(np.sum(seen & entered))
        returned += int(np.sum(seen & left))
        count[group] = after
        ingested[aid - base] = True
        touched.append(int(aid.size + np.sum(entered) + np.sum(left)))
    hot_ids = np.flatnonzero(count >= UNDER) + base
    if not ids:
        return {"rows": np.zeros((0, 2), np.int64), "hot_ids": hot_ids,
                "groups_touched": [], "unbid_rows": 0, "retracted": 0,
                "returned": 0}
    aid = np.concatenate(ids)
    n = count[aid - base]
    shown = (n == 0) | (n >= UNDER)
    rows = np.stack([aid, np.concatenate(items)], axis=1)[shown]
    return {"rows": rows_array(rows), "hot_ids": hot_ids,
            "groups_touched": touched,
            "unbid_rows": int(np.sum(n == 0)),
            "retracted": retracted, "returned": returned}


def rows_array(rows) -> np.ndarray:
    """``run_sql`` rows ``(item string, auction_id)`` (or an ``[n, 2]``
    int64 array ``(auction_id, item number)``) in the reference's sorted
    layout."""
    if isinstance(rows, np.ndarray):
        arr = rows.astype(np.int64).reshape(-1, 2)
    else:
        arr = np.array([(r[1], _item_number(r[0])) for r in rows],
                       dtype=np.int64).reshape(-1, 2)
    return arr[np.lexsort((arr[:, 1], arr[:, 0]))]


def compare(exp: dict, got_rows) -> dict:
    """The numbers compared, each exact (limit 0): ``rows_wrong``, rows on
    one side only; ``unbid_rows_off``, the same over the rows the view
    shows because no bid was counted on their auction (every row but the
    20-bid auctions': what only the anti join's own-row lane and its
    retractions decide; a row of an auction the reference holds under 20
    counts here); ``events_off``, the distinct auctions the MV attests
    against the reference's (each row of q104 is one auction event of
    the source); ``rows_expected`` has the floor 1."""
    want = exp["rows"]
    got = rows_array(got_rows)

    def unbid(rows):
        return rows[~np.isin(rows[:, 0], exp["hot_ids"])]

    return {"rows_wrong": _one_side_only(got, want),
            "unbid_rows_off": _one_side_only(unbid(got), unbid(want)),
            "events_off": abs(int(np.unique(got[:, 0]).size)
                              - int(np.unique(want[:, 0]).size)),
            "rows_expected": int(want.shape[0])}
