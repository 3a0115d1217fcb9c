"""Plain reference for RisingWave's nexmark q101 (every auction with its
current highest bid, NULL where it has none) over the HOST bid and
auction streams.

The deployment has two sources, each its own NEXmark generator on the
host seeded with the run's seed. The j-th auction has the id
``first_auction_id + j``; an auction chunk draws from
``numpy.random.default_rng(seed)`` seven arrays of ``rows`` numbers (item,
initial bid, reserve, expiry, hot seller?, cold seller's place, category).
The bid stream counts its own events: bid ``e`` belongs to auction epoch
``e // 50``, whose newest auction is ``first_auction_id + 3 * (e // 50)``;
a bid chunk draws seven arrays (hot auction?, cold auction's offset, hot
bidder?, cold bidder's offset, price, channel, url). 9 bids in 10 go to
the hot auction (the first of each 100 ids), the rest uniformly to the
newest 100; a price is ``100 * 1000 ** U``. This file replays the columns
the MV reads (auction ``id``, ``item_name``; bid ``auction``, ``price``)
with numpy alone — it imports nothing of the program — and recomputes
the MV: one row per auction ingested, with the maximum price over every
bid ingested on it.

Rows are ``(auction_id, item_name, current_highest_bid)``; an item name
is ``item-<n>`` and is held as ``n``, a NULL bid as ``-1`` (a price is at
least 100).

The control is ``bid_chunk_lost`` (the last barrier's first bid chunk is
never counted): it breaks the side the outer join pads, so some auction
shows a lower maximum or a NULL it should not. The q5 cells'
``at_least_once`` cannot serve: a bid chunk counted twice changes no
maximum (``benchmark/tests/test_q101.py`` holds that as a test).
"""

from __future__ import annotations

import numpy as np

ITEM_PREFIX = "item-"
NULL = -1


def streams(config: dict, seed: int, barriers: int):
    """Yield per barrier ``(auction_id, item, bid_auction, price)``, int64
    arrays over the barrier's auction rows and bid rows."""
    nx = config["nexmark"]
    k = config["chunks_per_tick"]
    a_rows = config["rows_per_chunk"]["auction"]
    b_rows = config["rows_per_chunk"]["bid"]
    per_epoch = (nx["person_proportion"] + nx["auction_proportion"]
                 + nx["bid_proportion"])
    ratio = nx["hot_auction_ratio"]
    a_rng = np.random.default_rng(seed)
    b_rng = np.random.default_rng(seed)
    for b in range(barriers):
        item = np.empty((k, a_rows), np.int64)
        hot = np.empty((k, b_rows), np.bool_)
        offset = np.empty((k, b_rows), np.int64)
        price = np.empty((k, b_rows), np.int64)
        for c in range(k):
            item[c] = a_rng.integers(0, nx["item_names"], a_rows)
            a_rng.integers(1, 1000, a_rows)                   # initial bid
            a_rng.integers(0, 1000, a_rows)                   # reserve
            a_rng.integers(1_000_000, 60_000_000, a_rows)     # expiry
            a_rng.integers(0, 4, a_rows)                      # hot seller?
            a_rng.random(a_rows)                              # cold seller
            a_rng.integers(0, 5, a_rows)                      # category
            hot[c] = b_rng.random(b_rows) < nx["hot_share"]
            offset[c] = b_rng.integers(0, nx["in_flight_auctions"], b_rows)
            b_rng.random(b_rows)                              # hot bidder?
            b_rng.integers(0, nx["active_people"], b_rows)    # cold bidder
            price[c] = (100 * np.exp(b_rng.random(b_rows)
                                     * np.log(1000.0))).astype(np.int64)
            b_rng.integers(0, 4, b_rows)                      # channel
            b_rng.integers(0, 64, b_rows)                     # url
        jth = b * k * a_rows + np.arange(k * a_rows, dtype=np.int64)
        event = b * k * b_rows + np.arange(k * b_rows, dtype=np.int64)
        last_auction = (nx["first_auction_id"]
                        + (event // per_epoch) * nx["auction_proportion"])
        bid_auction = np.where(hot.reshape(-1),
                               (last_auction // ratio) * ratio,
                               last_auction - offset.reshape(-1))
        yield (nx["first_auction_id"] + jth, item.reshape(-1), bid_auction,
               price.reshape(-1))


def expected(config: dict, seed: int, barriers: int, broken: str = "") -> dict:
    """The MV after ``barriers`` barriers: ``[n, 3]`` int64 rows
    ``(auction_id, item number, highest bid or -1)`` sorted by id; per
    barrier ``groups_touched``, the rows the join takes in — the auctions
    ingested plus the bid groups (an auction id with a bid, whether or
    not an auction row ever brings it) whose maximum is new or changed in
    the barrier; ``null_rows``, the rows without a bid; and
    ``maxima_replaced``, how many times a barrier raised a maximum an
    earlier barrier had set.

    ``broken`` is the CONTROL, never the reference: ``"bid_chunk_lost"``
    leaves the last barrier's first bid chunk (``rows`` bids) out, as a
    source that skips a chunk after a restart would."""
    if broken not in ("", "bid_chunk_lost"):
        raise ValueError(f"q101 has no control {broken!r}")
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
    best = np.full(n_ids, NULL, np.int64)
    ids, items, touched = [], [], []
    replaced = 0
    for b, (aid, item, bid_auction, price) in enumerate(
            streams(config, seed, barriers)):
        if broken == "bid_chunk_lost" and b == barriers - 1:
            bid_auction, price = bid_auction[b_rows:], price[b_rows:]
        ids.append(aid)
        items.append(item)
        group, inverse = np.unique(bid_auction - base, return_inverse=True)
        highest = np.full(group.size, NULL, np.int64)
        np.maximum.at(highest, inverse, price)
        changed = highest > best[group]
        replaced += int(np.sum(changed & (best[group] != NULL)))
        best[group] = np.maximum(best[group], highest)
        touched.append(int(aid.size + np.sum(changed)))
    if not ids:
        return {"rows": np.zeros((0, 3), np.int64), "groups_touched": [],
                "null_rows": 0, "maxima_replaced": 0}
    aid = np.concatenate(ids)
    rows = np.stack([aid, np.concatenate(items), best[aid - base]], axis=1)
    return {"rows": rows_array(rows), "groups_touched": touched,
            "null_rows": int(np.sum(rows[:, 2] == NULL)),
            "maxima_replaced": replaced}


def _item_number(name) -> int:
    """``item-<n>`` as ``n``; -2 for anything else (never an item of the
    stream, so the row counts as wrong)."""
    if isinstance(name, str) and name.startswith(ITEM_PREFIX):
        digits = name[len(ITEM_PREFIX):]
        if digits.isdigit():
            return int(digits)
    return -2


def rows_array(rows) -> np.ndarray:
    """``run_sql`` rows ``(auction_id, item string, bid or None)`` (or an
    ``[n, 3]`` int64 array) in the reference's sorted layout."""
    if isinstance(rows, np.ndarray):
        arr = rows.astype(np.int64).reshape(-1, 3)
    else:
        arr = np.array([(r[0], _item_number(r[1]),
                         NULL if r[2] is None else r[2]) for r in rows],
                       dtype=np.int64).reshape(-1, 3)
    return arr[np.lexsort((arr[:, 2], arr[:, 1], arr[:, 0]))]


def _one_side_only(got: np.ndarray, want: np.ndarray) -> int:
    """Rows that are not in both (a row read twice counts)."""
    if got.shape == want.shape and np.array_equal(got, want):
        return 0
    _, counts = np.unique(np.concatenate([got, want]), axis=0,
                          return_counts=True)
    return int(np.sum(counts != 2))


def compare(exp: dict, got_rows) -> dict:
    """The numbers compared, each exact (limit 0): ``rows_wrong``, rows on
    one side only; ``null_rows_off``, the same over the NULL-padded rows
    alone (what only an outer join emits); ``events_off``, the auction
    events the MV's rows attest against the reference's (each row of
    q101 is one auction event of the source, so this is the difference
    of the distinct ids); ``rows_expected`` has the floor 1."""
    want = exp["rows"]
    got = rows_array(got_rows)
    return {"rows_wrong": _one_side_only(got, want),
            "null_rows_off": _one_side_only(got[got[:, 2] == NULL],
                                            want[want[:, 2] == NULL]),
            "events_off": abs(int(np.unique(got[:, 0]).size)
                              - int(np.unique(want[:, 0]).size)),
            "rows_expected": int(want.shape[0])}
