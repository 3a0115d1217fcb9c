"""Plain reference for the q5-core MV over the HOST bid stream.

On the default executor path the deployment's bid source is one NEXmark
generator on the host, seeded with the run's seed: chunk ``c`` holds
``rows`` consecutive events of its own clock, and draws from
``numpy.random.default_rng(seed)`` seven arrays of ``rows`` numbers in a
fixed order (hot auction?, cold auction's offset, hot bidder?, cold
bidder's offset, price, channel, url). This file replays the two columns
the MV reads (``auction``, ``date_time``) with numpy alone — it imports
nothing of the program — and recomputes the MV
``count(*) GROUP BY window_start, auction`` over them.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import q5core_count

#: barriers handed on per block
BLOCK_BARRIERS = 32


def bid_stream(config: dict, seed: int, barriers: int):
    """Yield ``(auction, date_time)`` int64 arrays of shape
    ``[n_barriers_in_block, events_per_barrier]`` covering the first
    ``barriers`` barriers, in order."""
    nx = config["nexmark"]
    rows = config["rows_per_chunk"]["bid"]
    k = config["chunks_per_tick"]
    total = (nx["person_proportion"] + nx["auction_proportion"]
             + nx["bid_proportion"])
    us_per_event = max(1_000_000 // max(nx["events_per_second"], 1), 1)
    ratio = nx["hot_auction_ratio"]
    rng = np.random.default_rng(seed)
    for j0 in range(0, barriers, BLOCK_BARRIERS):
        n = min(BLOCK_BARRIERS, barriers - j0)
        hot = np.empty((n * k, rows), np.bool_)
        offset = np.empty((n * k, rows), np.int64)
        for c in range(n * k):
            hot[c] = rng.random(rows) < nx["hot_share"]
            offset[c] = rng.integers(0, nx["in_flight_auctions"], rows)
            rng.random(rows)                              # hot bidder?
            rng.integers(0, nx["active_people"], rows)    # cold bidder
            rng.random(rows)                              # price
            rng.integers(0, 4, rows)                      # channel
            rng.integers(0, 64, rows)                     # url
        event = (j0 * k * rows
                 + np.arange(n * k * rows, dtype=np.int64)).reshape(n * k,
                                                                    rows)
        last_auction = (nx["first_auction_id"]
                        + (event // total) * nx["auction_proportion"])
        auction = np.where(hot, (last_auction // ratio) * ratio,
                           last_auction - offset)
        ts = nx["start_time_us"] + event * us_per_event
        yield auction.reshape(n, k * rows), ts.reshape(n, k * rows)


def expected(config: dict, seed: int, barriers: int, broken: str = "") -> dict:
    """The MV after ``barriers`` barriers (``q5core_count.expected``)."""
    return q5core_count.expected(
        bid_stream(config, seed, barriers), config["nexmark"],
        config["rows_per_chunk"]["bid"], barriers, broken)


compare = q5core_count.compare
