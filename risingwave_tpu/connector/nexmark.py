"""NEXmark event generator — the benchmark source.

Counterpart of the reference's NEXmark connector
(reference: src/connector/src/source/nexmark/source/reader.rs:41; schemas
from src/tests/simulation/src/nexmark/create_source.sql). Generation is
vectorized numpy on the host (a whole chunk per call — there is no per-event
loop): ``*_columns`` draws a chunk's host columns, ``next_*_chunk`` stages
them on the device (common/chunk.stage_chunks). Distributions follow the NEXmark
spec shape: event ratio person:auction:bid = 1:3:46, hot-auction/hot-bidder
skew, price ~ geometric, monotonically advancing event time.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from ..common.chunk import Column, HostChunk, StreamChunk, stage_chunks
from ..common.types import (
    GLOBAL_STRING_DICT, INT64, Schema, TIMESTAMP, VARCHAR,
)
import jax.numpy as jnp

BID_SCHEMA = Schema.of(
    ("auction", INT64), ("bidder", INT64), ("price", INT64),
    ("channel", VARCHAR), ("url", VARCHAR), ("date_time", TIMESTAMP),
    ("extra", VARCHAR),
)

AUCTION_SCHEMA = Schema.of(
    ("id", INT64), ("item_name", VARCHAR), ("description", VARCHAR),
    ("initial_bid", INT64), ("reserve", INT64), ("date_time", TIMESTAMP),
    ("expires", TIMESTAMP), ("seller", INT64), ("category", INT64),
    ("extra", VARCHAR),
)

PERSON_SCHEMA = Schema.of(
    ("id", INT64), ("name", VARCHAR), ("email_address", VARCHAR),
    ("credit_card", VARCHAR), ("city", VARCHAR), ("state", VARCHAR),
    ("date_time", TIMESTAMP), ("extra", VARCHAR),
)

# NEXmark spec constants (mirroring the generator config semantics in
# src/connector/src/source/nexmark/mod.rs)
PERSON_PROPORTION = 1
AUCTION_PROPORTION = 3
BID_PROPORTION = 46
TOTAL_PROPORTION = PERSON_PROPORTION + AUCTION_PROPORTION + BID_PROPORTION
FIRST_PERSON_ID = 1000
FIRST_AUCTION_ID = 1000
FIRST_CATEGORY_ID = 10
HOT_AUCTION_RATIO = 100
HOT_BIDDER_RATIO = 100
NUM_CATEGORIES = 5
# the seller rule of NEXmark's AuctionGenerator: with probability
# 1 - 1/HOT_SELLERS_RATIO the first id of the newest HOT_SELLER_RATIO-person
# batch, else uniform over the newest ``active_people`` ids plus
# PERSON_ID_LEAD ids not yet issued
HOT_SELLER_RATIO = 100
HOT_SELLERS_RATIO = 4
PERSON_ID_LEAD = 10

_CHANNELS = ["Google", "Facebook", "Baidu", "Apple"]
_US_STATES = ["AZ", "CA", "ID", "OR", "WY"]
_CITIES = ["Phoenix", "Los Angeles", "San Francisco", "Boise", "Portland"]


@dataclasses.dataclass
class NexmarkConfig:
    chunk_capacity: int = 1024
    events_per_second: int = 10_000   # drives event-time spacing
    active_people: int = 1000
    in_flight_auctions: int = 100
    start_time_us: int = 1_600_000_000_000_000


class NexmarkGenerator:
    """Generates Bid / Auction / Person chunks. Person and auction rows are
    the person and auction events of ONE NEXmark event sequence (event ``e``
    is a person where ``e % 50 < 1``, an auction where ``e % 50 < 4``), with
    that sequence's ids and timestamps; the bid stream counts its own events
    (50 bids to an auction epoch), as its recorded consumers expect."""

    def __init__(self, config: NexmarkConfig = NexmarkConfig(), seed: int = 42):
        self.cfg = config
        self.rng = np.random.default_rng(seed)
        self.events_so_far = 0      # the bid stream's own event count
        self.persons_so_far = 0
        self.auctions_so_far = 0
        # pre-intern the small string vocabularies
        self._channel_ids = np.array(
            [GLOBAL_STRING_DICT.intern(c) for c in _CHANNELS], np.int32)
        self._url_ids = np.array(
            [GLOBAL_STRING_DICT.intern(f"https://www.nexmark.com/item{i}")
             for i in range(64)], np.int32)
        self._city_ids = np.array(
            [GLOBAL_STRING_DICT.intern(c) for c in _CITIES], np.int32)
        self._state_ids = np.array(
            [GLOBAL_STRING_DICT.intern(s) for s in _US_STATES], np.int32)
        self._name_ids = np.array(
            [GLOBAL_STRING_DICT.intern(f"person-{i}") for i in range(997)],
            np.int32)
        self._item_ids = np.array(
            [GLOBAL_STRING_DICT.intern(f"item-{i}") for i in range(499)],
            np.int32)
        self._empty = GLOBAL_STRING_DICT.intern("")

    # -- event-time / id helpers ---------------------------------------------

    def _advance(self, n: int) -> np.ndarray:
        """Event timestamps (us) for the next n events of this stream's clock."""
        ids = np.arange(self.events_so_far, self.events_so_far + n, dtype=np.int64)
        self.events_so_far += n
        return self._event_time(ids), ids

    def _last_auction_id(self, event_ids: np.ndarray) -> np.ndarray:
        epoch = event_ids // TOTAL_PROPORTION
        return FIRST_AUCTION_ID + epoch * AUCTION_PROPORTION

    def _last_person_id(self, event_ids: np.ndarray) -> np.ndarray:
        epoch = event_ids // TOTAL_PROPORTION
        return FIRST_PERSON_ID + epoch * PERSON_PROPORTION

    def _event_time(self, event_ids: np.ndarray) -> np.ndarray:
        us_per_event = 1_000_000 // max(self.cfg.events_per_second, 1)
        return self.cfg.start_time_us + event_ids * max(us_per_event, 1)

    def _chunk(self, schema: Schema, arrays: list[np.ndarray], n: int) -> StreamChunk:
        return stage_chunks(
            [HostChunk(schema, arrays, n, self.cfg.chunk_capacity)])[0]

    # -- streams --------------------------------------------------------------

    def next_bid_chunk(self, n: Optional[int] = None) -> StreamChunk:
        n = n or self.cfg.chunk_capacity
        return self._chunk(BID_SCHEMA, self.bid_columns(n), n)

    def bid_columns(self, n: int) -> list[np.ndarray]:
        """Host columns of the next ``n`` bids, in BID_SCHEMA's order."""
        ts, eids = self._advance(n)
        last_auction = self._last_auction_id(eids)
        last_person = self._last_person_id(eids)
        hot = self.rng.random(n) < 0.9  # hot auctions get ~90% of bids (spec ratio)
        hot_auction = (last_auction // HOT_AUCTION_RATIO) * HOT_AUCTION_RATIO
        cold_auction = last_auction - self.rng.integers(
            0, self.cfg.in_flight_auctions, n)
        auction = np.where(hot, hot_auction, cold_auction)
        hot_b = self.rng.random(n) < 0.9
        hot_bidder = (last_person // HOT_BIDDER_RATIO) * HOT_BIDDER_RATIO + 1
        cold_bidder = np.maximum(
            last_person - self.rng.integers(0, self.cfg.active_people, n),
            FIRST_PERSON_ID)
        bidder = np.where(hot_b, hot_bidder, cold_bidder)
        price = (100 * np.exp(self.rng.random(n) * np.log(1000.0))).astype(np.int64)
        channel = self._channel_ids[self.rng.integers(0, len(self._channel_ids), n)]
        url = self._url_ids[self.rng.integers(0, len(self._url_ids), n)]
        extra = np.full(n, self._empty, np.int32)
        return [auction, bidder, price, channel, url, ts, extra]

    def next_auction_chunk(self, n: Optional[int] = None) -> StreamChunk:
        n = n or self.cfg.chunk_capacity
        return self._chunk(AUCTION_SCHEMA, self.auction_columns(n), n)

    def auction_columns(self, n: int) -> list[np.ndarray]:
        """The next ``n`` auctions of the ONE NEXmark event sequence: the
        j-th auction is event ``50*(j // 3) + 1 + j % 3`` and has the id
        ``FIRST_AUCTION_ID + j``."""
        j = np.arange(self.auctions_so_far, self.auctions_so_far + n,
                      dtype=np.int64)
        self.auctions_so_far += n
        epoch = j // AUCTION_PROPORTION
        ts = self._event_time(epoch * TOTAL_PROPORTION + PERSON_PROPORTION
                              + j % AUCTION_PROPORTION)
        ids = FIRST_AUCTION_ID + j
        item = self._item_ids[self.rng.integers(0, len(self._item_ids), n)]
        desc = np.full(n, self._empty, np.int32)
        initial = self.rng.integers(1, 1000, n).astype(np.int64)
        reserve = initial + self.rng.integers(0, 1000, n)
        expires = ts + self.rng.integers(1_000_000, 60_000_000, n)
        # persons issued so far: one per epoch, the epoch's own included
        people = epoch * PERSON_PROPORTION + 1
        hot = self.rng.integers(0, HOT_SELLERS_RATIO, n) > 0
        hot_seller = ((people - 1) // HOT_SELLER_RATIO) * HOT_SELLER_RATIO
        active = np.minimum(people, self.cfg.active_people)
        cold_seller = people - active + np.floor(
            self.rng.random(n) * (active + PERSON_ID_LEAD)).astype(np.int64)
        seller = FIRST_PERSON_ID + np.where(hot, hot_seller, cold_seller)
        category = FIRST_CATEGORY_ID + self.rng.integers(0, NUM_CATEGORIES, n)
        extra = np.full(n, self._empty, np.int32)
        return [ids, item, desc, initial, reserve, ts, expires, seller,
                category, extra]

    def next_person_chunk(self, n: Optional[int] = None) -> StreamChunk:
        n = n or self.cfg.chunk_capacity
        return self._chunk(PERSON_SCHEMA, self.person_columns(n), n)

    def person_columns(self, n: int) -> list[np.ndarray]:
        """The next ``n`` persons of the same sequence: the k-th person is
        event ``50*k`` and has the id ``FIRST_PERSON_ID + k``."""
        k = np.arange(self.persons_so_far, self.persons_so_far + n,
                      dtype=np.int64)
        self.persons_so_far += n
        ts = self._event_time(k * TOTAL_PROPORTION)
        ids = FIRST_PERSON_ID + k
        name = self._name_ids[self.rng.integers(0, len(self._name_ids), n)]
        email = np.full(n, self._empty, np.int32)
        card = np.full(n, self._empty, np.int32)
        city = self._city_ids[self.rng.integers(0, len(self._city_ids), n)]
        state = self._state_ids[self.rng.integers(0, len(self._state_ids), n)]
        extra = np.full(n, self._empty, np.int32)
        return [ids, name, email, card, city, state, ts, extra]


class DeviceBidGenerator:
    """Bid ChunkBatches generated ON DEVICE inside one jitted step.

    The host generator above feeds correctness tests; this one is the
    benchmark/throughput source: the datagen *is* a compute kernel, so the
    only per-epoch host→device traffic is two scalars (start event id +
    PRNG key) — closing the acknowledged host→device ingest bottleneck
    (BASELINE.md "known headroom"; VERDICT r3 item 1c). Distributions match
    the host generator (NEXmark spec shape: 1:3:46 event ratio arithmetic
    for id clocks, hot-auction/hot-bidder 90% skew, price ~ 100·1000^U,
    event time advancing at events_per_second), using counter-based threefry
    keys so generation is deterministic and replayable from (seed, batch_no)
    alone (reference generator semantics:
    src/connector/src/source/nexmark/source/reader.rs:41)."""

    def __init__(self, config: NexmarkConfig = NexmarkConfig(),
                 seed: int = 42):
        import jax
        self.cfg = config
        self.events_so_far = 0
        self._batch_no = 0
        self._seed = seed
        self._channel_ids = jnp.asarray(
            [GLOBAL_STRING_DICT.intern(c) for c in _CHANNELS], jnp.int32)
        self._url_ids = jnp.asarray(
            [GLOBAL_STRING_DICT.intern(f"https://www.nexmark.com/item{i}")
             for i in range(64)], jnp.int32)
        self._empty = GLOBAL_STRING_DICT.intern("")
        self._gen = jax.jit(self._gen_impl, static_argnums=(2,))

    def _gen_impl(self, start, key, k: int) -> StreamChunk:
        import jax
        cfg = self.cfg
        cap = cfg.chunk_capacity
        n = k * cap
        eids = start + jnp.arange(n, dtype=jnp.int64)
        us_per_event = max(1_000_000 // max(cfg.events_per_second, 1), 1)
        ts = cfg.start_time_us + eids * us_per_event
        epoch = eids // TOTAL_PROPORTION
        last_auction = FIRST_AUCTION_ID + epoch * AUCTION_PROPORTION
        last_person = FIRST_PERSON_ID + epoch * PERSON_PROPORTION
        ks = jax.random.split(key, 7)
        hot = jax.random.uniform(ks[0], (n,)) < 0.9
        hot_auction = (last_auction // HOT_AUCTION_RATIO) * HOT_AUCTION_RATIO
        cold_auction = last_auction - jax.random.randint(
            ks[1], (n,), 0, cfg.in_flight_auctions).astype(jnp.int64)
        auction = jnp.where(hot, hot_auction, cold_auction)
        hot_b = jax.random.uniform(ks[2], (n,)) < 0.9
        hot_bidder = (last_person // HOT_BIDDER_RATIO) * HOT_BIDDER_RATIO + 1
        cold_bidder = jnp.maximum(
            last_person - jax.random.randint(
                ks[3], (n,), 0, cfg.active_people).astype(jnp.int64),
            FIRST_PERSON_ID)
        bidder = jnp.where(hot_b, hot_bidder, cold_bidder)
        price = (100.0 * jnp.exp(
            jax.random.uniform(ks[4], (n,)) * jnp.log(1000.0))
        ).astype(jnp.int64)
        channel = self._channel_ids[jax.random.randint(
            ks[5], (n,), 0, self._channel_ids.shape[0])]
        url = self._url_ids[jax.random.randint(
            ks[6], (n,), 0, self._url_ids.shape[0])]
        extra = jnp.full(n, self._empty, jnp.int32)

        full = jnp.ones((k, cap), jnp.bool_)

        def col(a, dtype):
            return Column(a.astype(dtype).reshape(k, cap), full)

        cols = (col(auction, jnp.int64), col(bidder, jnp.int64),
                col(price, jnp.int64), col(channel, jnp.int32),
                col(url, jnp.int32), col(ts, jnp.int64),
                col(extra, jnp.int32))
        ops = jnp.zeros((k, cap), jnp.int8)   # append-only source
        return StreamChunk(ops, full, cols)

    def next_batch(self, k: int):
        """One ChunkBatch of k full chunks, generated on device."""
        import jax
        from ..common.chunk import ChunkBatch
        key = jax.random.fold_in(jax.random.PRNGKey(self._seed),
                                 self._batch_no)
        self._batch_no += 1
        start = self.events_so_far
        self.events_so_far += k * self.cfg.chunk_capacity
        return ChunkBatch(self._gen(jnp.int64(start), key, k))

    def chunk_fn(self):
        """Traceable ``(start_event_id, key) -> StreamChunk`` producing ONE
        flat chunk — the fusion surface for single-dispatch epochs
        (ops/fused_epoch.py): callers compose it INSIDE their own jit, so
        generation fuses with downstream projection/aggregation — or with
        BOTH sides of the q7 windowed join (fused_source_join_epoch): the
        bucketed interval join derives its probe rows AND its per-window
        aggregate build side from the same generated chunk, where the
        executor bench path needs two same-seed generators producing the
        stream twice."""
        def fn(start, key):
            ch = self._gen_impl(start, key, 1)
            return StreamChunk(
                ch.ops[0], ch.vis[0],
                tuple(Column(c.data[0], c.mask[0]) for c in ch.columns))
        return fn
