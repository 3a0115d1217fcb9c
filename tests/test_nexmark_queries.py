"""NEXmark queries end-to-end in SQL, checked against an independent Python
recomputation of the same deterministic generator stream (reference: the
query definitions in src/tests/simulation/src/nexmark/q*.sql and the golden
outputs of e2e_test/streaming/nexmark/)."""

import collections

import pytest

from risingwave_tpu.common import chunk_to_rows
from risingwave_tpu.connector.nexmark import (
    AUCTION_SCHEMA, BID_SCHEMA, PERSON_SCHEMA, NexmarkConfig, NexmarkGenerator,
)
from risingwave_tpu.frontend import Session

CAP = 64
TICKS = 4

DDL = """
CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,
  channel VARCHAR, url VARCHAR, date_time TIMESTAMP, extra VARCHAR)
WITH (connector = 'nexmark', nexmark_table = 'bid');
CREATE SOURCE auction (id BIGINT, item_name VARCHAR, description VARCHAR,
  initial_bid BIGINT, reserve BIGINT, date_time TIMESTAMP,
  expires TIMESTAMP, seller BIGINT, category BIGINT, extra VARCHAR)
WITH (connector = 'nexmark', nexmark_table = 'auction');
CREATE SOURCE person (id BIGINT, name VARCHAR, email_address VARCHAR,
  credit_card VARCHAR, city VARCHAR, state VARCHAR, date_time TIMESTAMP,
  extra VARCHAR)
WITH (connector = 'nexmark', nexmark_table = 'person')
"""


def make_session() -> Session:
    s = Session(source_chunk_capacity=CAP, chunks_per_tick=1)
    s.run_sql(DDL)
    return s


def replay(table: str, n_chunks: int):
    """The exact rows a session source leaf produced (same seed/config)."""
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=CAP), seed=42)
    fn = {"bid": gen.next_bid_chunk, "auction": gen.next_auction_chunk,
          "person": gen.next_person_chunk}[table]
    schema = {"bid": BID_SCHEMA, "auction": AUCTION_SCHEMA,
              "person": PERSON_SCHEMA}[table]
    rows = []
    for _ in range(n_chunks):
        rows.extend(chunk_to_rows(fn(), schema))
    return rows


SEC = 1_000_000


def run_mv(sql: str, name: str, ticks: int = TICKS):
    s = make_session()
    s.run_sql(sql)
    for _ in range(ticks):
        s.tick()
    return sorted(s.mv_rows(name))


def test_q1_currency_conversion():
    got = run_mv("""CREATE MATERIALIZED VIEW q1 AS
        SELECT auction, bidder, price * 89 / 100 AS price, date_time
        FROM bid""", "q1")
    bids = replay("bid", TICKS)
    exp = sorted((b[0], b[1], b[2] * 89 // 100, b[5]) for b in bids)
    assert got == exp


def test_q2_filter():
    got = run_mv("""CREATE MATERIALIZED VIEW q2 AS
        SELECT auction, price FROM bid
        WHERE auction % 123 = 0 OR auction % 125 = 0""", "q2")
    bids = replay("bid", TICKS)
    exp = sorted((b[0], b[2]) for b in bids
                 if b[0] % 123 == 0 or b[0] % 125 == 0)
    assert got == exp


def test_q3_join_filter():
    got = run_mv("""CREATE MATERIALIZED VIEW q3 AS
        SELECT P.name, P.city, P.state, A.id
        FROM auction AS A INNER JOIN person AS P on A.seller = P.id
        WHERE A.category = 10
          AND (P.state = 'OR' OR P.state = 'ID' OR P.state = 'CA')""",
        "q3", ticks=6)
    auctions = replay("auction", 6)
    persons = replay("person", 6)
    # NEXmark person ids repeat across events: a true multiset join
    exp = [
        (p[1], p[4], p[5], a[0])
        for a in auctions if a[8] == 10
        for p in persons
        if p[0] == a[7] and p[5] in ("OR", "ID", "CA")
    ]
    assert got == sorted(exp)
    assert len(got) > 0  # non-trivial


@pytest.mark.slow
def test_q4_avg_final_price():
    got = run_mv("""CREATE MATERIALIZED VIEW q4 AS
        SELECT Q.category, AVG(Q.final) as avg
        FROM (
            SELECT MAX(B.price) AS final, A.category
            FROM auction A, bid B
            WHERE A.id = B.auction
              AND B.date_time BETWEEN A.date_time AND A.expires
            GROUP BY A.id, A.category
        ) Q
        GROUP BY Q.category""", "q4", ticks=6)
    auctions = replay("auction", 6)
    bids = replay("bid", 6)
    finals: dict = {}
    for a in auctions:
        for b in bids:
            if a[0] == b[0] and a[5] <= b[5] <= a[6]:
                key = (a[0], a[8])
                finals[key] = max(finals.get(key, 0), b[2])
    per_cat = collections.defaultdict(list)
    for (aid, cat), final in finals.items():
        per_cat[cat].append(final)
    exp = sorted((cat, sum(v) / len(v)) for cat, v in per_cat.items())
    assert len(got) > 0
    assert [g[0] for g in got] == [e[0] for e in exp]
    for g, e in zip(got, exp):
        assert abs(g[1] - e[1]) < 1e-6


@pytest.mark.parametrize("cmp", ["=", ">="], ids=["eq", "ge"])
def test_q5_hot_items(cmp):
    """NEXmark q5 with the join's ``num = maxn`` and, as upstream writes
    it, ``num >= maxn``: the same rows (no count passes its window's
    maximum). The AuctionBids side holds every (window, auction) count of
    its window and moves to the fan-out arena."""
    s = make_session()
    s.run_sql(f"""CREATE MATERIALIZED VIEW q5 AS
        SELECT AuctionBids.auction, AuctionBids.num FROM (
            SELECT bid.auction, count(*) AS num, window_start AS starttime
            FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND)
            GROUP BY window_start, bid.auction
        ) AS AuctionBids
        JOIN (
            SELECT max(CountBids.num) AS maxn, CountBids.starttime_c
            FROM (
                SELECT count(*) AS num, window_start AS starttime_c
                FROM HOP(bid, date_time, INTERVAL '2' SECOND,
                         INTERVAL '10' SECOND)
                GROUP BY bid.auction, window_start
            ) AS CountBids
            GROUP BY CountBids.starttime_c
        ) AS MaxBids
        ON AuctionBids.starttime = MaxBids.starttime_c
           AND AuctionBids.num {cmp} MaxBids.maxn""")
    for _ in range(TICKS):
        s.tick()
    got = sorted(s.mv_rows("q5"))
    from risingwave_tpu.stream import HashJoinExecutor
    from risingwave_tpu.stream.metrics import iter_executors
    join = next(e for e in iter_executors(s.jobs["q5"].pipeline)
                if isinstance(e, HashJoinExecutor))
    assert join.core.layout["left"].fanout
    assert not join.core.layout["right"].fanout
    bids = replay("bid", TICKS)
    counts: dict = collections.defaultdict(int)
    slide, size = 2 * SEC, 10 * SEC
    n = size // slide
    for b in bids:
        ts = b[5]
        base = (ts // slide) * slide
        for i in range(n):
            ws = base - i * slide
            if ws <= ts < ws + size:
                counts[(ws, b[0])] += 1
    maxn: dict = collections.defaultdict(int)
    for (ws, auction), c in counts.items():
        maxn[ws] = max(maxn[ws], c)
    exp = sorted(
        (auction, c) for (ws, auction), c in counts.items()
        if c == maxn[ws])
    assert got == exp and len(got) > 0


def test_q7_highest_bid():
    got = run_mv("""CREATE MATERIALIZED VIEW q7 AS
        SELECT B.auction, B.price, B.bidder, B.date_time
        FROM bid B
        JOIN (
            SELECT MAX(price) AS maxprice, window_end as date_time
            FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)
            GROUP BY window_end
        ) B1 ON B.price = B1.maxprice
        WHERE B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND
              AND B1.date_time""", "q7")
    bids = replay("bid", TICKS)
    size = 10 * SEC
    win_max: dict = collections.defaultdict(int)
    for b in bids:
        we = (b[5] // size) * size + size
        win_max[we] = max(win_max[we], b[2])
    exp = []
    for b in bids:
        for we, mx in win_max.items():
            if b[2] == mx and we - size <= b[5] <= we:
                exp.append((b[0], b[2], b[1], b[5]))
    assert got == sorted(exp) and len(got) > 0


def test_q8_new_users():
    got = run_mv("""CREATE MATERIALIZED VIEW q8 AS
        SELECT P.id, P.name, P.starttime
        FROM (
            SELECT id, name, window_start AS starttime,
                   window_end AS endtime
            FROM TUMBLE(person, date_time, INTERVAL '10' SECOND)
            GROUP BY id, name, window_start, window_end
        ) P
        JOIN (
            SELECT seller, window_start AS starttime,
                   window_end AS endtime
            FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND)
            GROUP BY seller, window_start, window_end
        ) A ON P.id = A.seller AND P.starttime = A.starttime
               AND P.endtime = A.endtime""", "q8", ticks=6)
    persons = replay("person", 6)
    auctions = replay("auction", 6)
    size = 10 * SEC
    p_windows = {(p[0], p[1], (p[6] // size) * size) for p in persons}
    a_windows = {(a[7], (a[5] // size) * size) for a in auctions}
    exp = sorted(
        {(pid, name, ws) for (pid, name, ws) in p_windows
         if (pid, ws) in a_windows})
    assert got == exp and len(got) > 0


def test_q101_highest_bid_outer():
    """RisingWave's own nexmark q101 (e2e_test/streaming/nexmark/views/
    q101.slt.part): every auction with its current highest bid, NULL where
    it has none. Checked after EVERY barrier across a checkpoint: the
    outer join has to retract a NULL-padded row when the first bid on its
    auction arrives, and replace a maximum a later barrier raises."""
    bid_rows, auction_rows, ticks = 400, 24, 14     # 8 auction epochs a chunk
    s = Session(chunks_per_tick=1, checkpoint_frequency=5)
    s.run_sql(f"""
        CREATE SOURCE bid (auction BIGINT, bidder BIGINT, price BIGINT,
          channel VARCHAR, url VARCHAR, date_time TIMESTAMP, extra VARCHAR)
        WITH (connector = 'nexmark', nexmark_table = 'bid',
              rows_per_chunk = {bid_rows});
        CREATE SOURCE auction (id BIGINT, item_name VARCHAR,
          description VARCHAR, initial_bid BIGINT, reserve BIGINT,
          date_time TIMESTAMP, expires TIMESTAMP, seller BIGINT,
          category BIGINT, extra VARCHAR)
        WITH (connector = 'nexmark', nexmark_table = 'auction',
              rows_per_chunk = {auction_rows})""")
    s.run_sql("""CREATE MATERIALIZED VIEW nexmark_q101 AS
        SELECT a.id AS auction_id, a.item_name AS auction_item_name,
               b.max_price AS current_highest_bid
        FROM auction a
        LEFT OUTER JOIN (
            SELECT b1.auction, MAX(b1.price) max_price
            FROM bid b1 GROUP BY b1.auction
        ) b ON a.id = b.auction""")

    def streams(table, rows, schema):
        gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=rows), seed=42)
        fn = gen.next_bid_chunk if table == "bid" else gen.next_auction_chunk
        return [chunk_to_rows(fn(), schema) for _ in range(ticks)]

    bids = streams("bid", bid_rows, BID_SCHEMA)
    auctions = streams("auction", auction_rows, AUCTION_SCHEMA)
    items, best = {}, {}
    went_from_null = replaced = 0
    for t in range(ticks):
        s.tick()
        before, padded = dict(best), set(items)   # padded: in the MV already
        for a in auctions[t]:
            items[a[0]] = a[1]
        for b in bids[t]:
            best[b[0]] = max(best.get(b[0], 0), b[2])
        went_from_null += sum(1 for a in best
                              if a not in before and a in padded)
        replaced += sum(1 for a, p in before.items() if best[a] > p)
        exp = sorted((a, item, best.get(a)) for a, item in items.items())
        got = sorted(s.mv_rows("nexmark_q101"),
                     key=lambda r: r[0])
        assert got == exp, f"barrier {t + 1}"
    assert s.epoch > 2 * s.checkpoint_frequency
    assert 0 < sum(r[2] is None for r in got) < len(got)
    assert went_from_null > 0        # a NULL-padded row was retracted
    assert replaced > 0              # an update pair reached the join
