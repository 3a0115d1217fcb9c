"""Plain reference for NEXmark q5 as written ("hot items": the auctions with
the most bids of each sliding window) over the HOST bid stream.

The deployment's bid source is q5-core-exec's, so the replay of the two
columns the view reads (``auction``, ``date_time``) is the sibling
reference's ``bid_stream`` (numpy alone; neither file imports anything of
the program). This file puts every bid in its ``size / slide`` HOP
windows, counts the bids per (window, auction) (AuctionBids, and CountBids,
which is the same subquery), takes each window's maximum (MaxBids) and
keeps the rows with ``num >= maxn`` — the view's rows ``(auction, num)``,
one a (window, auction) at its window's maximum, ties all kept.

Per barrier it also gives the join's input and its least output:
``groups_touched[b] = (rows in, rows out)``. Rows in are the changelog
rows the two aggregates flush into the join in barrier ``b``: a new
(window, auction) group is one Insert and a changed one a U-/U+ pair
(AuctionBids, the join's left side); a new window's maximum one Insert
and a changed one a pair, an unchanged one nothing (MaxBids, its right
side). Rows out are the view's rows that barrier ``b`` put or took back
(barrier end against barrier end): the least the join has to emit.

The control is ``at_least_once`` (the last barrier's first bid chunk is
counted twice, as a source replayed after a restart without its offset
would be): a hot auction's stretch in that chunk outgrows its windows'
maximum, and the view shows another row. ``bid_chunk_lost`` (that chunk
never counted) is kept to show why it cannot serve: the view shows each
window's maximum rows alone, and a lost chunk often takes no bid from
them (``benchmark/tests/test_q5.py``).
"""

from __future__ import annotations

import numpy as np

from benchmark.reference.q5core_host_stream import bid_stream


def expected(config: dict, seed: int, barriers: int, broken: str = "") -> dict:
    """The view after ``barriers`` barriers: ``rows``, sorted ``[n, 2]``
    int64 ``(auction, num)``; ``windowed``, the same rows with their
    window's start in front (``[n, 3]``); ``groups_touched``, per barrier
    ``(join rows in, least join rows out)``; ``rows_in_left`` /
    ``rows_in_right`` per barrier, the two halves of rows in.

    ``broken`` is a CONTROL, never the reference: ``"at_least_once"``
    counts the last barrier's first chunk twice, ``"bid_chunk_lost"``
    leaves it out."""
    if broken not in ("", "bid_chunk_lost", "at_least_once"):
        raise ValueError(f"q5 has no control {broken!r}")
    nx, hop = config["nexmark"], config["hop"]
    slide, n_win = hop["slide_us"], hop["size_us"] // hop["slide_us"]
    rows = config["rows_per_chunk"]["bid"]
    # per window index: its auctions (sorted) and their counts
    windows: dict = {}
    touched, left_in, right_in = [], [], []
    b = 0
    for auction, ts in bid_stream(config, seed, barriers):
        for a, t in zip(auction, ts):
            if broken == "bid_chunk_lost" and b == barriers - 1:
                a, t = a[rows:], t[rows:]
            elif broken == "at_least_once" and b == barriers - 1:
                a = np.concatenate([a, a[:rows]])
                t = np.concatenate([t, t[:rows]])
            # every bid lies in n_win windows: the one starting at its
            # slide and the n_win - 1 before it
            first = (t - nx["start_time_us"]) // slide
            left = right = out = 0
            for w in np.unique(np.concatenate([first - i
                                               for i in range(n_win)])):
                mine = (first - w >= 0) & (first - w < n_win)
                old_a, old_n = windows.get(int(w), _EMPTY)
                new_a, inv = np.unique(np.concatenate([old_a, a[mine]]),
                                       return_inverse=True)
                had = np.zeros(new_a.size, np.bool_)
                had[inv[:old_a.size]] = True
                new_n = np.zeros(new_a.size, np.int64)
                new_n[inv[:old_a.size]] = old_n
                grew = np.bincount(inv[old_a.size:], minlength=new_a.size) > 0
                new_n += np.bincount(inv[old_a.size:], minlength=new_a.size)
                left += int(np.sum(grew & ~had) + 2 * np.sum(grew & had))
                top_old = int(old_n.max()) if old_n.size else None
                top = int(new_n.max())
                right += 1 if top_old is None else 2 * (top > top_old)
                out += _one_side_only(
                    _at_top(old_a, old_n), _at_top(new_a, new_n))
                windows[int(w)] = (new_a, new_n)
            left_in.append(left)
            right_in.append(right)
            touched.append((left + right, out))
            b += 1
    parts = [np.stack([np.full(top.shape[0], nx["start_time_us"] + w * slide),
                       top[:, 0], top[:, 1]], axis=1)
             for w, (a, n) in sorted(windows.items())
             for top in (_at_top(a, n),)]
    shown = np.concatenate(parts) if parts else np.zeros((0, 3), np.int64)
    return {"rows": rows_array(shown[:, 1:]), "windowed": shown,
            "groups_touched": touched, "rows_in_left": left_in,
            "rows_in_right": right_in}


_EMPTY = (np.zeros(0, np.int64), np.zeros(0, np.int64))


def _at_top(auctions: np.ndarray, nums: np.ndarray) -> np.ndarray:
    """``[n, 2]`` (auction, num) of a window's groups at its maximum."""
    if not nums.size:
        return np.zeros((0, 2), np.int64)
    keep = nums >= nums.max()
    return np.stack([auctions[keep], nums[keep]], axis=1)


def _one_side_only(a: np.ndarray, b: np.ndarray) -> int:
    """Rows of ``a`` or ``b`` but not both, counted as multisets."""
    if a.shape == b.shape and np.array_equal(a, b):
        return 0
    both = np.concatenate([a, b]) if a.size or b.size else a
    if not both.size:
        return 0
    uniq, inv = np.unique(both, axis=0, return_inverse=True)
    in_a = np.bincount(inv[:len(a)], minlength=len(uniq))
    in_b = np.bincount(inv[len(a):], minlength=len(uniq))
    return int(np.abs(in_a - in_b).sum())


def rows_array(rows) -> np.ndarray:
    """``run_sql`` rows ``(auction, num)`` (or an ``[n, 2]`` array) in the
    reference's sorted layout."""
    arr = np.asarray(rows, dtype=np.int64).reshape(-1, 2)
    return arr[np.lexsort((arr[:, 1], arr[:, 0]))]


def compare(exp: dict, got_rows) -> dict:
    """The numbers compared, each exact (limit 0): ``rows_wrong``, rows on
    one side only (as multisets: two windows may show the same auction
    with the same count); ``events_off``, the bids the MV's counts attest
    against the reference's; ``rows_expected`` has the floor 1."""
    want = exp["rows"]
    got = rows_array(got_rows)
    return {"rows_wrong": _one_side_only(got, want),
            "events_off": abs(int(got[:, 1].sum()) - int(want[:, 1].sum())),
            "rows_expected": int(want.shape[0])}
