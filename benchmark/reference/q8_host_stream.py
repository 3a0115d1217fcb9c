"""Plain reference for NEXmark q8 ("monitor new users") over the HOST
person and auction streams.

The deployment has two sources, each its own NEXmark generator on the
host seeded with the run's seed, both walking ONE event sequence: the
k-th person is event ``50*k`` with id ``first_person_id + k``, the j-th
auction is event ``50*(j // 3) + 1 + j % 3``; an event's time is
``start_time_us + event * 100 us``. A person chunk draws from
``numpy.random.default_rng(seed)`` three arrays of ``rows`` numbers (name,
city, state); an auction chunk draws seven (item, initial bid, reserve,
expiry, hot seller?, cold seller's place, category). A seller is, 3 times
in 4, the first id of the newest 100-person batch, else uniform over the
newest ``active_people`` ids and ``person_id_lead`` ids not yet issued.
This file replays the columns the MV reads (person ``id``, ``name``,
``date_time``; auction ``seller``, ``date_time``) with numpy alone — it
imports nothing of the program — and recomputes the MV: the persons with
an auction of theirs in the 10 s window they registered in.

Rows are ``(id, name, window_start)``; a name is ``person-<n>`` and is
held as ``n``.

The control is ``chunk_lost`` (the last barrier's first person chunk is
never delivered). The q5 cells' ``at_least_once`` cannot serve here: both
inputs pass a GROUP BY without an aggregate before they join, so a chunk
delivered twice changes no row of the MV (``benchmark/tests`` holds that
as a test); a control has to break something the MV shows.
"""

from __future__ import annotations

import numpy as np

NAME_PREFIX = "person-"


def _streams(config: dict, seed: int, barriers: int):
    """Yield per barrier ``(person_id, name, person_ts, seller,
    auction_ts)``, int64 arrays over the barrier's rows."""
    nx = config["nexmark"]
    k = config["chunks_per_tick"]
    p_rows = config["rows_per_chunk"]["person"]
    a_rows = config["rows_per_chunk"]["auction"]
    per_epoch = (nx["person_proportion"] + nx["auction_proportion"]
                 + nx["bid_proportion"])
    us_per_event = max(1_000_000 // max(nx["events_per_second"], 1), 1)
    p_rng = np.random.default_rng(seed)
    a_rng = np.random.default_rng(seed)
    for b in range(barriers):
        name = np.empty((k, p_rows), np.int64)
        hot = np.empty((k, a_rows), np.bool_)
        place = np.empty((k, a_rows), np.float64)
        for c in range(k):
            name[c] = p_rng.integers(0, nx["person_names"], p_rows)
            p_rng.integers(0, 5, p_rows)                      # city
            p_rng.integers(0, 5, p_rows)                      # state
            a_rng.integers(0, 499, a_rows)                    # item
            a_rng.integers(1, 1000, a_rows)                   # initial bid
            a_rng.integers(0, 1000, a_rows)                   # reserve
            a_rng.integers(1_000_000, 60_000_000, a_rows)     # expiry
            hot[c] = a_rng.integers(0, nx["hot_sellers_ratio"], a_rows) > 0
            place[c] = a_rng.random(a_rows)
            a_rng.integers(0, 5, a_rows)                      # category
        kth = b * k * p_rows + np.arange(k * p_rows, dtype=np.int64)
        jth = b * k * a_rows + np.arange(k * a_rows, dtype=np.int64)
        epoch = jth // nx["auction_proportion"]
        a_event = (epoch * per_epoch + nx["person_proportion"]
                   + jth % nx["auction_proportion"])
        people = epoch * nx["person_proportion"] + 1
        active = np.minimum(people, nx["active_people"])
        hot_seller = ((people - 1) // nx["hot_seller_batch"]) \
            * nx["hot_seller_batch"]
        cold_seller = people - active + np.floor(
            place.reshape(-1) * (active + nx["person_id_lead"])
        ).astype(np.int64)
        seller = nx["first_person_id"] + np.where(hot.reshape(-1),
                                                  hot_seller, cold_seller)
        yield (nx["first_person_id"] + kth, name.reshape(-1),
               nx["start_time_us"] + kth * per_epoch * us_per_event,
               seller, nx["start_time_us"] + a_event * us_per_event)


def _add_new(seen: np.ndarray, codes: np.ndarray) -> tuple:
    """(the sorted ``seen`` with the distinct ``codes`` merged in, how many
    of them it did not hold)."""
    u = np.unique(codes)
    at = np.searchsorted(seen, u)
    held = np.zeros(u.size, np.bool_)
    inside = at < seen.size
    held[inside] = seen[at[inside]] == u[inside]
    return np.insert(seen, at[~held], u[~held]), int(np.sum(~held))


def expected(config: dict, seed: int, barriers: int, broken: str = "") -> dict:
    """The MV after ``barriers`` barriers: sorted ``[n, 3]`` int64 rows
    ``(id, name number, window_start)``; per barrier ``groups_touched``,
    the groups new in it on both sides of the join — distinct (id, name,
    window) of its persons plus distinct (seller, window) of its auctions
    that no earlier barrier held: the rows the join takes in; and
    ``windows``, how many event-time windows the MV's rows lie in.

    ``broken`` is the CONTROL, never the reference: ``"chunk_lost"`` leaves
    the last barrier's first person chunk (``rows`` persons) out, as a
    source that skips a chunk after a restart would."""
    if broken not in ("", "chunk_lost"):
        raise ValueError(f"q8 has no control {broken!r}")
    nx = config["nexmark"]
    p_rows = config["rows_per_chunk"]["person"]
    touched = []
    seen_p = np.zeros(0, np.int64)
    seen_a = np.zeros(0, np.int64)
    for b, (pid, name, p_ts, seller, a_ts) in enumerate(
            _streams(config, seed, barriers)):
        if broken == "chunk_lost" and b == barriers - 1:
            pid, name, p_ts = pid[p_rows:], name[p_rows:], p_ts[p_rows:]
        p_win = (p_ts - nx["start_time_us"]) // nx["window_us"]
        a_win = (a_ts - nx["start_time_us"]) // nx["window_us"]
        # ids stay far under 2^40, names under 2^10
        p_code = (p_win << 50) | (pid << 10) | name
        a_code = (a_win << 50) | (seller << 10)
        seen_p, new_p = _add_new(seen_p, p_code)
        seen_a, new_a = _add_new(seen_a, a_code)
        touched.append(new_p + new_a)
    if not touched:
        return {"rows": np.zeros((0, 3), np.int64), "groups_touched": [],
                "windows": 0}
    joined = seen_p[np.isin(seen_p >> 10, seen_a >> 10)]
    window = joined >> 50
    rows = np.stack([(joined >> 10) & ((1 << 40) - 1), joined & 1023,
                     nx["start_time_us"] + window * nx["window_us"]], axis=1)
    return {"rows": rows_array(rows), "groups_touched": touched,
            "windows": int(np.unique(window).size)}


def _name_number(name) -> int:
    """``person-<n>`` as ``n``; -1 for anything else (never a name of the
    stream, so the row counts as wrong)."""
    if isinstance(name, str) and name.startswith(NAME_PREFIX):
        digits = name[len(NAME_PREFIX):]
        if digits.isdigit():
            return int(digits)
    return -1


def rows_array(rows) -> np.ndarray:
    """``run_sql`` rows ``(id, name string, starttime)`` (or an ``[n, 3]``
    int64 array) in the reference's sorted layout."""
    if isinstance(rows, np.ndarray):
        arr = rows.astype(np.int64).reshape(-1, 3)
    else:
        arr = np.array([(r[0], _name_number(r[1]), r[2]) for r in rows],
                       dtype=np.int64).reshape(-1, 3)
    return arr[np.lexsort((arr[:, 1], arr[:, 0], arr[:, 2]))]


def compare(exp: dict, got_rows) -> dict:
    """The numbers compared, each exact (limit 0): ``rows_wrong``, rows on
    one side only (a row read twice counts); ``events_off``, the person
    events the MV's rows attest against the reference's (each row of q8
    is one person event of the source, so this is the difference of the
    distinct ids); ``rows_expected`` has the floor 1."""
    want = exp["rows"]
    got = rows_array(got_rows)
    if got.shape == want.shape and np.array_equal(got, want):
        wrong = 0
    else:
        both = np.concatenate([got, want])
        _, counts = np.unique(both, axis=0, return_counts=True)
        wrong = int(np.sum(counts != 2))
    return {"rows_wrong": wrong,
            "events_off": abs(int(np.unique(got[:, 0]).size)
                              - int(np.unique(want[:, 0]).size)),
            "rows_expected": int(want.shape[0])}
