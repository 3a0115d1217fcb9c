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
