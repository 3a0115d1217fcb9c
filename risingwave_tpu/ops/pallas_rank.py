"""Pallas TPU kernel: fused same-key rank/total accumulation for the
streaming join probe.

The join's chunk pass needs, per probe row i (reference semantics:
eq_join_oneside's per-row match bookkeeping, hash_join.rs:972 — here
vectorized over the whole chunk):

    r[i, w] = |{ j < i : ident[j] == ident[i], matches[j, w] }|
    t[i, w] = |{ j     : ident[j] == ident[i], matches[j, w] }|

The jnp formulation (ops/join_state.py) builds ``eqf``/``lower`` as
[N, N] float32 matrices in HBM and runs two [N,N]·[N,W] matmuls — at the
bench shapes (N=4096, W=128) that is 2×64 MB of HBM traffic per chunk
pass just for the masks. This kernel fuses mask GENERATION into the
matmul: the [TI, TJ] equality tile is computed in VMEM from two [T]
slices of ``ident`` and fed straight to the MXU, so the [N, N] matrices
never exist in memory (SURVEY.md §7 stage 3: "hash probe … rank/degree
updates" is the named Pallas target).

Grid: (N/TI, N/TJ); j is the reduction dimension — TPU grid cells run
sequentially, so the output tile accumulates across the j sweep
(initialized at j == 0). Both outputs ride the same equality tile.

``rank_totals`` picks the implementation: the Pallas kernel on a TPU
backend, the jnp matmul formulation elsewhere (``pallas_selected``, the
one selector). Both produce bit-identical int32 results —
``tests/test_pallas_kernels.py`` asserts parity in interpret mode,
``tests/test_pallas_compile.py`` compiles the kernel for a described
v5e, ``chip_smoke.py`` compares the two on the chip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

TILE_I = 256
TILE_J = 256


def rank_totals_jnp(ident: jax.Array, matches: jax.Array):
    """Reference jnp formulation (the pre-kernel code path)."""
    n = ident.shape[0]
    idx = jnp.arange(n)
    eqf = (ident[:, None] == ident[None, :]) & (ident >= 0)[:, None]
    lower = eqf & (idx[None, :] < idx[:, None])
    mf = matches.astype(jnp.float32)
    r = jnp.round(lower.astype(jnp.float32) @ mf).astype(jnp.int32)
    t = jnp.round(eqf.astype(jnp.float32) @ mf).astype(jnp.int32)
    return r, t


def _kernel(ident_col_ref, ident_row_ref, m_ref, r_ref, t_ref):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        r_ref[:] = jnp.zeros_like(r_ref)
        t_ref[:] = jnp.zeros_like(t_ref)

    ti = ident_col_ref.shape[0]
    tj = ident_row_ref.shape[1]
    i0 = pl.program_id(0) * ti
    j0 = j * tj
    # [TI, 1] column and [1, TJ] row blocks (reshaped by XLA outside the
    # call): Mosaic broadcasts 2-D int32 along lanes/sublanes, but has no
    # layout for the rank change of a 1-D block, nor for a 1-D mask
    ident_i = jnp.broadcast_to(ident_col_ref[:], (ti, tj))
    ident_j = jnp.broadcast_to(ident_row_ref[:], (ti, tj))
    # the [TI, TJ] equality tile, generated in VMEM — never materialized
    # at [N, N]
    eq = (ident_i == ident_j) & (ident_i >= 0)
    row_i = i0 + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 0)
    col_j = j0 + jax.lax.broadcasted_iota(jnp.int32, (ti, tj), 1)
    lower = eq & (col_j < row_i)
    mf = m_ref[:]
    # typed float32 constants: under x64 a bare 1.0 is a float64, which
    # Mosaic has no layout for
    one, zero = jnp.float32(1), jnp.float32(0)
    r_ref[:] += jnp.dot(jnp.where(lower, one, zero), mf,
                        preferred_element_type=jnp.float32)
    t_ref[:] += jnp.dot(jnp.where(eq, one, zero), mf,
                        preferred_element_type=jnp.float32)


def rank_totals_pallas_call(ident: jax.Array, matches: jax.Array,
                            interpret: bool = False):
    """The raw pallas_call — no backend choice. Callers guarantee the tile
    divisibility; tests/test_pallas_compile.py compiles THIS for a
    described v5e from any host."""
    from jax.experimental import pallas as pl

    n, w = matches.shape
    ti = min(TILE_I, n)
    tj = min(TILE_J, n)
    grid = (n // ti, n // tj)
    z = np.int32(0)     # block indices are int32 (a bare 0 is int64 here)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((ti, 1), lambda i, j: (i, z)),
            pl.BlockSpec((1, tj), lambda i, j: (z, j)),
            pl.BlockSpec((tj, w), lambda i, j: (j, z)),
        ],
        out_specs=[
            pl.BlockSpec((ti, w), lambda i, j: (i, z)),
            pl.BlockSpec((ti, w), lambda i, j: (i, z)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, w), jnp.float32),
            jax.ShapeDtypeStruct((n, w), jnp.float32),
        ],
        interpret=interpret,
    )(ident.reshape(n, 1), ident.reshape(1, n),
      matches.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def rank_totals_pallas(ident: jax.Array, matches: jax.Array,
                       interpret: bool = False):
    """The kernel with its int32 epilogue. ``interpret=False`` compiles
    for the attached TPU and fails anywhere else; the backend choice is
    ``rank_totals``'s alone."""
    n, w = matches.shape
    if n % min(TILE_I, n) or n % min(TILE_J, n):
        # a choice by shape: ragged capacities have no tile grid
        return rank_totals_jnp(ident, matches)
    r, t = rank_totals_pallas_call(ident, matches, interpret=interpret)
    return (jnp.round(r).astype(jnp.int32),
            jnp.round(t).astype(jnp.int32))


def pallas_selected() -> bool:
    """The ONE place that chooses between the Mosaic kernels and their
    jnp twins (shared with ops/interval_join.interval_match so the two
    can never disagree): compiled Pallas on a TPU backend, the jnp
    formulation on every other. Nothing retries, nothing interprets."""
    return jax.default_backend() == "tpu"


def rank_totals(ident: jax.Array, matches: jax.Array):
    """r[i,w], t[i,w] as int32 — the kernel on TPU, jnp elsewhere."""
    if pallas_selected():
        return rank_totals_pallas(ident, matches)
    return rank_totals_jnp(ident, matches)
