"""Pallas kernel parity: the fused rank/total kernel must match the jnp
matmul formulation bit-for-bit (interpret mode on CPU; the same kernel
compiles for TPU — SURVEY.md §7 stage 3)."""

import numpy as np
import pytest

import jax.numpy as jnp

from risingwave_tpu.ops.pallas_rank import (
    rank_totals_jnp, rank_totals_pallas,
)


@pytest.mark.parametrize("n,w,seed", [(256, 8, 0), (512, 128, 1),
                                      (1024, 16, 2)])
def test_rank_totals_parity(n, w, seed):
    rng = np.random.default_rng(seed)
    # idents cluster heavily (hot keys) and include -1 (no-match rows)
    ident = rng.integers(-1, 12, size=n).astype(np.int32)
    matches = rng.random((n, w)) < 0.3
    r_ref, t_ref = rank_totals_jnp(jnp.asarray(ident),
                                   jnp.asarray(matches))
    r_k, t_k = rank_totals_pallas(jnp.asarray(ident),
                                  jnp.asarray(matches), interpret=True)
    np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_k))
    np.testing.assert_array_equal(np.asarray(t_ref), np.asarray(t_k))


def test_rank_totals_semantics_small():
    """Hand-checked: rows 0,2 share key 7; row 3 shares with nobody."""
    ident = jnp.asarray([7, 1, 7, -1], jnp.int32)
    matches = jnp.asarray([[1], [1], [1], [1]], bool)
    r, t = rank_totals_pallas(ident, matches, interpret=True)
    # r counts EARLIER same-key matching rows; t counts all of them
    np.testing.assert_array_equal(np.asarray(r), [[0], [0], [1], [0]])
    np.testing.assert_array_equal(np.asarray(t), [[2], [1], [2], [0]])


def test_ragged_capacity_falls_back():
    ident = jnp.asarray(np.arange(300, dtype=np.int32))
    matches = jnp.ones((300, 4), bool)
    r, t = rank_totals_pallas(ident, matches)   # 300 % 256 != 0 → jnp
    r2, t2 = rank_totals_jnp(ident, matches)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t2))


@pytest.mark.parametrize("n,kernel", [(768, True), (1024, True),
                                      (800, False)],
                         ids=["auction_chunk", "flush_chunk", "ragged"])
def test_q101_chunk_capacities_take_the_kernel(n, kernel, monkeypatch):
    """The benchmark's nexmark-q101 hands the join 768-row auction chunks
    and 1,024-row flush chunks at join_bucket_width 1: both have a tile
    grid (n % 256 == 0), so on a TPU ``rank_totals`` runs the Mosaic
    kernel; a ragged capacity (the configuration's tiny 800) silently
    takes the jnp twin. Results equal either way."""
    import json
    import os

    from risingwave_tpu.ops import pallas_rank
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "configs", "nexmark-q101.json")) as f:
        config = json.load(f)
    assert config["rw_toml"]["streaming.join_bucket_width"] == 1
    assert config["rows_per_chunk"]["auction"] == 768
    assert config["tiny"]["rows_per_chunk"]["bid"] == 800
    assert "streaming.chunk_capacity" not in config["rw_toml"]   # 1,024
    calls = []
    real = pallas_rank.rank_totals_pallas_call
    monkeypatch.setattr(
        pallas_rank, "rank_totals_pallas_call",
        lambda *a, **kw: calls.append(a[1].shape) or real(*a, **kw))
    rng = np.random.default_rng(n)
    ident = jnp.asarray(rng.integers(-1, 40, size=n).astype(np.int32))
    matches = jnp.asarray(rng.random((n, 1)) < 0.5)
    # the un-jitted body, so that the call is seen however warm the cache
    r, t = pallas_rank.rank_totals_pallas.__wrapped__(
        ident, matches, interpret=True)
    assert calls == ([(n, 1)] if kernel else [])
    r2, t2 = rank_totals_jnp(ident, matches)
    np.testing.assert_array_equal(np.asarray(r), np.asarray(r2))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(t2))
