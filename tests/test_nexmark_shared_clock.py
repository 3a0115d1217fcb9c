"""The NEXmark connector's person and auction streams on ONE event clock
(ISSUE 27), and q8 over them through ``Session`` against the benchmark's
plain reference (``benchmark/reference/q8_host_stream.py``, numpy only)
over several windows and across a checkpoint and a reopen of the
``data_dir``."""

import hashlib
import importlib.util
import json
import os

import numpy as np
import pytest

from risingwave_tpu.common import chunk_to_rows
from risingwave_tpu.connector.nexmark import (
    AUCTION_SCHEMA, BID_SCHEMA, FIRST_AUCTION_ID, FIRST_PERSON_ID,
    HOT_SELLER_RATIO, PERSON_ID_LEAD, PERSON_SCHEMA, NexmarkConfig,
    NexmarkGenerator,
)
from risingwave_tpu.connector.nexmark_split import NexmarkReader
from risingwave_tpu.frontend import Session
from risingwave_tpu.frontend.build import BuildConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW_US = 10_000_000
START_US = NexmarkConfig().start_time_us


def _load(*parts):
    path = os.path.join(ROOT, *parts)
    spec = importlib.util.spec_from_file_location("q8_host_stream", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stream(table: str, rows: int, chunks: int, seed: int = 11) -> list:
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=rows), seed=seed)
    fn, schema = {"person": (gen.next_person_chunk, PERSON_SCHEMA),
                  "auction": (gen.next_auction_chunk, AUCTION_SCHEMA)}[table]
    return [r for _ in range(chunks) for r in chunk_to_rows(fn(), schema)]


@pytest.fixture(scope="module")
def persons():
    return stream("person", 500, 10)      # 5,000 persons = 2.5 windows


@pytest.fixture(scope="module")
def auctions():
    return stream("auction", 1500, 10)    # the same 250,000 events


def test_person_ids_are_unique_and_consecutive(persons):
    assert [p[0] for p in persons] == list(
        range(FIRST_PERSON_ID, FIRST_PERSON_ID + len(persons)))


def test_auction_ids_are_unique_and_consecutive(auctions):
    assert [a[0] for a in auctions] == list(
        range(FIRST_AUCTION_ID, FIRST_AUCTION_ID + len(auctions)))


def test_both_streams_walk_one_event_sequence(persons, auctions):
    """Person k is event 50k, auction j event 50(j // 3) + 1 + j % 3, 100 us
    of event time apart."""
    assert [p[6] for p in persons] == [
        START_US + 50 * k * 100 for k in range(len(persons))]
    assert [a[5] for a in auctions] == [
        START_US + (50 * (j // 3) + 1 + j % 3) * 100
        for j in range(len(auctions))]


def test_a_window_holds_2000_persons_and_6000_auctions(persons, auctions):
    for rows, ts_col, per_window in ((persons, 6, 2000), (auctions, 5, 6000)):
        windows = np.array([(r[ts_col] - START_US) // WINDOW_US
                            for r in rows])
        counts = np.bincount(windows)
        assert list(counts[:2]) == [per_window, per_window]
        assert len(counts) == 3


def test_three_in_four_sellers_are_the_hot_seller(auctions):
    """NEXmark's rule: the hot seller is the first id of the newest
    100-person batch; a cold one lies among the newest 1,000 ids and the
    10 not yet issued."""
    hot = 0
    for j, a in enumerate(auctions):
        people = j // 3 + 1            # persons issued when the auction is
        hot_id = FIRST_PERSON_ID + ((people - 1) // HOT_SELLER_RATIO) \
            * HOT_SELLER_RATIO
        seller = a[7]
        hot += seller == hot_id
        lo = FIRST_PERSON_ID + people - min(people, 1000)
        assert lo <= seller < FIRST_PERSON_ID + people + PERSON_ID_LEAD
    assert 0.73 < hot / len(auctions) < 0.77


@pytest.mark.parametrize("cap,seed,digest", [
    (4096, 42,
     "80349877f86387c4a06a5a460085449f224e5d749c98edf82d9d95799e0ba8f6"),
    (256, 7,
     "20d186fbc12371653662af5ca1909d4ed5faa29a4d499c07452907f674120d15"),
])
def test_bid_stream_is_the_parents_bit_for_bit(cap, seed, digest):
    """The first three bid chunks, as rows (strings decoded, so the digest
    does not depend on what was interned before): recorded on the commit
    before the shared clock (PR 26)."""
    gen = NexmarkGenerator(NexmarkConfig(chunk_capacity=cap), seed=seed)
    h = hashlib.sha256()
    for _ in range(3):
        h.update(repr(chunk_to_rows(gen.next_bid_chunk(),
                                    BID_SCHEMA)).encode())
    assert h.hexdigest() == digest


@pytest.mark.parametrize("table", ["person", "auction"])
def test_reader_seek_replays_to_the_same_rows(table):
    schema = {"person": PERSON_SCHEMA, "auction": AUCTION_SCHEMA}[table]
    a = NexmarkReader(table, chunk_capacity=64, seed=5)
    for _ in range(4):
        a.next_chunk()
    want = chunk_to_rows(a.next_chunk(), schema)
    b = NexmarkReader(table, chunk_capacity=64, seed=5)
    b.seek({"0": 4})
    assert chunk_to_rows(b.next_chunk(), schema) == want
    a.seek({"0": 4})                    # backwards: replays from the start
    assert chunk_to_rows(a.next_chunk(), schema) == want


# -- q8 through Session against the plain reference ---------------------------

@pytest.fixture(scope="module")
def q8():
    """The benchmark's configuration at a size of its own: 1,024 rows a
    barrier = 12,800 events = 1.28 s of event time, so 20 barriers roll
    through three windows."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "nexmark-q8.json")) as f:
        config = json.load(f)
    config["chunks_per_tick"] = 2
    config["rows_per_chunk"] = {"person": 128, "auction": 384}
    config["ddl"] = [
        d.replace("rows_per_chunk = 1024", "rows_per_chunk = 128")
         .replace("rows_per_chunk = 3072", "rows_per_chunk = 384")
        for d in config["ddl"]]
    assert all("rows_per_chunk = 128" in d or "rows_per_chunk = 384" in d
               for d in config["ddl"])
    return config, _load("benchmark", "reference", "q8_host_stream.py")


def open_q8(config: dict, data_dir: str, seed: int) -> Session:
    return Session(config=BuildConfig(chunk_capacity=256,
                                      agg_table_capacity=1 << 14,
                                      join_key_capacity=1 << 14,
                                      join_bucket_width=1),
                   seed=seed, chunks_per_tick=config["chunks_per_tick"],
                   checkpoint_frequency=4, data_dir=data_dir)


@pytest.mark.parametrize("seed", [3, 2_147_483_659])
def test_q8_equals_the_reference_over_windows_and_a_reopen(q8, seed,
                                                           tmp_path):
    config, ref = q8
    d = str(tmp_path / "db")
    s = open_q8(config, d, seed)
    for ddl in config["ddl"]:
        s.run_sql(ddl)
    s.run_sql(config["mv"])
    for _ in range(18):
        s.tick()
    exp = ref.expected(config, seed, 18)
    assert exp["windows"] >= 3
    got = ref.compare(exp, s.run_sql(config["select"]))
    assert got["rows_wrong"] == 0 and got["events_off"] == 0
    assert got["rows_expected"] > 500
    s.close()

    # reopen: the MV is the cut of a committed checkpoint, at most one
    # checkpoint interval (4 barriers) back ...
    s2 = open_q8(config, d, seed)
    rows = s2.run_sql(config["select"])
    cuts = [b for b in range(19)
            if ref.compare(ref.expected(config, seed, b), rows)
            ["rows_wrong"] == 0]
    assert len(cuts) == 1 and cuts[0] > 18 - 4, cuts
    # ... and the sources resume behind it: nothing lost, nothing twice
    for _ in range(6):
        s2.tick()
    got = ref.compare(ref.expected(config, seed, cuts[0] + 6),
                      s2.run_sql(config["select"]))
    assert got["rows_wrong"] == 0 and got["events_off"] == 0
    s2.close()
