"""Plain reference for the q5-core MV over the ON-DEVICE bid stream.

The deployment's source is NEXmark's bid stream as a counter-based
generator: barrier ``j`` (counted from 0 over the life of the data_dir)
folds ``j`` into the key made from the seed, chunk ``i`` of the barrier
folds ``i``, and a chunk's ``rows`` bids are drawn from that key. This
file replays that stream with nothing but ``jax.random`` and numpy — it
imports nothing of the program — and recomputes the MV
``count(*) GROUP BY window_start, auction`` over it.

Only the two columns the MV reads are replayed (``auction``,
``date_time``): the hot/cold draw and the cold auction's offset are the
first two of the seven keys a chunk's key is split into.
"""

from __future__ import annotations

import numpy as np

from benchmark.reference import q5core_count

#: barriers replayed per device call (one compiled shape, whatever the
#: count: the last block is cut to the prefix asked for)
BLOCK_BARRIERS = 32


def _block_fn(nx: dict, rows: int, k: int):
    import jax
    import jax.numpy as jnp

    us_per_event = max(1_000_000 // max(nx["events_per_second"], 1), 1)
    total = (nx["person_proportion"] + nx["auction_proportion"]
             + nx["bid_proportion"])

    def chunk(start, key):
        eids = start + jnp.arange(rows, dtype=jnp.int64)
        ts = nx["start_time_us"] + eids * us_per_event
        last_auction = (nx["first_auction_id"]
                        + (eids // total) * nx["auction_proportion"])
        ks = jax.random.split(key, 7)
        hot = jax.random.uniform(ks[0], (rows,)) < nx["hot_share"]
        ratio = nx["hot_auction_ratio"]
        hot_auction = (last_auction // ratio) * ratio
        cold_auction = last_auction - jax.random.randint(
            ks[1], (rows,), 0, nx["in_flight_auctions"]).astype(jnp.int64)
        return jnp.where(hot, hot_auction, cold_auction), ts

    def barrier(j, base):
        key = jax.random.fold_in(base, j)
        start = j * (k * rows)
        return jax.vmap(lambda i: chunk(start + i * rows,
                                        jax.random.fold_in(key, i)))(
            jnp.arange(k, dtype=jnp.int64))

    @jax.jit
    def block(j0, base):
        js = j0 + jnp.arange(BLOCK_BARRIERS, dtype=jnp.int64)
        return jax.vmap(lambda j: barrier(j, base))(js)

    return block


def bid_stream(config: dict, seed: int, barriers: int):
    """Yield ``(auction, date_time)`` int64 arrays of shape
    ``[n_barriers_in_block, events_per_barrier]`` covering the first
    ``barriers`` barriers, in order."""
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_enable_x64", True)     # 64-bit ids and seeds
    rows = config["rows_per_chunk"]["bid"]
    k = config["chunks_per_tick"]
    block = _block_fn(config["nexmark"], rows, k)
    base = jax.random.PRNGKey(seed)
    for j0 in range(0, barriers, BLOCK_BARRIERS):
        a, t = block(jnp.int64(j0), base)
        n = min(BLOCK_BARRIERS, barriers - j0)
        yield (np.asarray(a).reshape(BLOCK_BARRIERS, -1)[:n],
               np.asarray(t).reshape(BLOCK_BARRIERS, -1)[:n])


def expected(config: dict, seed: int, barriers: int, broken: str = "") -> dict:
    """The MV after ``barriers`` barriers (``q5core_count.expected``)."""
    return q5core_count.expected(
        bid_stream(config, seed, barriers), config["nexmark"],
        config["rows_per_chunk"]["bid"], barriers, broken)


compare = q5core_count.compare
